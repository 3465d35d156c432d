"""Every name in BENCHMARK.json finds its files, and the file keeps to the
benchmark's contract."""
import json
import re

import pytest

from bench import catalog

BENCH = catalog.load_json(catalog.ROOT / "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_and_size():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_names_units_and_bounds():
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = [m["name"] for m in metrics] + CELLS + [
        c["name"] for c in BENCH["configs"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
        assert set(m.get("workloads", CELLS)) <= set(CELLS)


def test_configs_are_files_under_paths():
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))
    for c in BENCH["configs"]:
        assert c["file"].startswith("bench/configs/")
        cfg = catalog.load_json(catalog.ROOT / c["file"])
        assert c["reduced"] == [] and cfg["model_type"] == "gpt2"
        assert c["source"].startswith("https://")


@pytest.mark.parametrize("name", CELLS)
def test_cell_loads_by_name(name):
    cell = catalog.load_cell(name)
    assert cell.chips == 1
    assert {"setup_s", "output_tok_s"} <= {m.name for m in cell.end_to_end}
    assert cell.per_layer and all(callable(m.read) for m in cell.per_layer)
    assert cell.limits["gap_max"] > 0 and cell.sample["tokens"] > 0
    assert cell.traffic["loop"] in ("open", "closed")
    # every per-layer metric moves an end-to-end metric the cell reports
    e2e = {m.name for m in cell.end_to_end}
    assert all(m.moves in e2e for m in cell.per_layer)


def test_every_metric_has_a_reader():
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert callable(catalog.load_reader(m["name"]))


def test_peaks_table_is_keyed_by_device_kind():
    p = catalog.peaks("TPU v5 lite")
    assert p["flops_per_s"] == 1.97e14 and p["hbm_bytes_per_s"] == 8.19e11
    with pytest.raises(catalog.UnknownDevice):
        catalog.peaks("TPU v9 imaginary")
