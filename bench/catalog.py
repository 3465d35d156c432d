"""Find the files of a cell by the names in ``BENCHMARK.json``."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import types
from pathlib import Path
from typing import Callable, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class UnknownDevice(KeyError):
    """The device kind has no entry in the peaks table."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    end_to_end: bool
    moves: Optional[str]            # per-layer only: the end-to-end metric
    workloads: Optional[tuple]      # None: every cell that reports `moves`
    read: Callable                  # read(run) -> float | None


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict                    # bench/configs/<config>.json
    arch: types.ModuleType          # bench/arch/<model_type>.py
    traffic: dict                   # bench/traffic/<traffic>.json
    generator: Callable             # its "generator": a Stream class
    limits: dict                    # bench/cells/<name>.json "limits"
    sample: dict                    # bench/cells/<name>.json "sample"
    end_to_end: tuple               # Metric objects this cell reports
    per_layer: tuple


def load_module(kind: str, name: str, bench_dir: Path):
    path = bench_dir / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file for {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str, bench_dir: Path = BENCH_DIR) -> Callable:
    """The reader of metric ``name``: ``bench/metrics/<name>.py``."""
    return load_module("metrics", name, bench_dir).read


def load_generator(name: str, bench_dir: Path = BENCH_DIR) -> Callable:
    """The request generator ``name``: ``bench/generators/<name>.py``, whose
    ``Stream(traffic, seed, vocab=, max_total=)`` yields the requests."""
    return load_module("generators", name, bench_dir).Stream


def load_arch(model_type: str, bench_dir: Path = BENCH_DIR):
    """The architecture module ``model_type``: ``bench/arch/<model_type>.py``
    (the configuration file's ``model_type``), which holds all the harness
    knows of one architecture family (see ``bench/arch/gpt2.py``)."""
    return load_module("arch", model_type, bench_dir)


def _metrics(entries, *, end_to_end: bool, bench_dir: Path):
    return [Metric(name=e["name"], unit=e["unit"], end_to_end=end_to_end,
                   moves=e.get("moves"),
                   workloads=tuple(e["workloads"]) if "workloads" in e
                   else None,
                   read=load_reader(e["name"], bench_dir))
            for e in entries]


def _reports(metric: Metric, cell: str, e2e_names: set) -> bool:
    if metric.workloads is not None:
        return cell in metric.workloads
    return metric.end_to_end or metric.moves in e2e_names


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files."""
    bench = load_json(root / "BENCHMARK.json")
    bench_dir = root / "bench"
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(work)}")
    w = work[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[w["config"]]
    cell_file = load_json(bench_dir / "cells" / f"{name}.json")
    traffic = load_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in _metrics(bench["end_to_end"], end_to_end=True,
                               bench_dir=bench_dir)
           if _reports(m, name, set())]
    e2e_names = {m.name for m in e2e}
    per_layer = [m for m in _metrics(bench["per_layer"], end_to_end=False,
                                     bench_dir=bench_dir)
                 if _reports(m, name, e2e_names)]
    config = load_json(root / cfg_entry["file"])
    return Cell(name=name, chips=int(w["chips"]), config=config,
                arch=load_arch(config["model_type"], bench_dir),
                traffic=traffic,
                generator=load_generator(traffic["generator"], bench_dir),
                limits=cell_file["limits"], sample=cell_file["sample"],
                end_to_end=tuple(e2e), per_layer=tuple(per_layer))


def peaks(device_kind: str, bench_dir: Path = BENCH_DIR) -> dict:
    """Published peaks of one chip of ``device_kind``; unknown kinds raise."""
    table = load_json(bench_dir / "peaks.json")["devices"]
    if device_kind not in table:
        raise UnknownDevice(f"no peaks for device kind {device_kind!r} in "
                            f"bench/peaks.json; known: {sorted(table)}")
    return table[device_kind]
