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
# what the harness asks of an architecture module (bench/arch/gpt2.py)
ARCH_API = ("model_config", "weight_shapes", "weight_scale", "program_params",
            "logits", "lm_head", "layers", "matmul_flops_per_token")


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


# keys a configuration's ``reduced`` may never name: widths
WIDTH = re.compile(r"(_dim|_rank)$|^(hidden_size|intermediate_size|"
                   r"moe_intermediate_size|n_embd|n_inner|"
                   r"num_experts_per_tok)$")


def check_config(entry: dict, root=catalog.ROOT) -> None:
    """A configuration entry of ``<root>/BENCHMARK.json`` keeps to the
    contract: its file under ``bench/configs/``, each key it cuts a key of
    that file and no width, and an architecture module for its
    ``model_type``."""
    assert entry["file"].startswith("bench/configs/")
    assert entry["source"].startswith("https://")
    cfg = catalog.load_json(root / entry["file"])
    assert set(entry["reduced"]) <= set(cfg), entry["reduced"]
    assert not any(WIDTH.search(k) for k in entry["reduced"])
    arch = catalog.load_arch(cfg["model_type"], root / "bench")
    assert all(callable(getattr(arch, f)) for f in ARCH_API)


def check_cell(name: str, root=catalog.ROOT) -> None:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` loads with its files
    and reports what the harness reads."""
    cell = catalog.load_cell(name, root)
    assert cell.chips in (1, 4)
    assert {"setup_s", "output_tok_s"} <= {m.name for m in cell.end_to_end}
    assert cell.per_layer and all(callable(m.read) for m in cell.per_layer)
    assert cell.limits["gap_max"] > 0 and cell.sample["tokens"] > 0
    assert cell.traffic["loop"] in ("open", "closed")
    # every per-layer metric moves an end-to-end metric the cell reports
    e2e = {m.name for m in cell.end_to_end}
    assert all(m.moves in e2e for m in cell.per_layer)


def test_configs_are_files_under_paths():
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))
    for c in BENCH["configs"]:
        check_config(c)


@pytest.mark.parametrize("reduced,ok", [
    ([], True), (["n_layer"], True), (["n_layer", "vocab_size"], True),
    (["num_hidden_layers"], False),        # no key of the file
    (["n_embd"], False), (["n_layer", "n_inner"], False),  # widths
])
def test_reduced_names_keys_of_the_file_and_no_width(reduced, ok):
    entry = dict(BENCH["configs"][0], reduced=reduced)
    if ok:
        check_config(entry)
    else:
        with pytest.raises(AssertionError):
            check_config(entry)


def test_an_unknown_model_type_names_the_file_it_looked_for():
    with pytest.raises(FileNotFoundError, match=r"bench/arch/mamba9\.py"):
        catalog.load_arch("mamba9")


@pytest.mark.parametrize("name", CELLS)
def test_cell_loads_by_name(name):
    check_cell(name)


def test_every_metric_has_a_reader():
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert callable(catalog.load_reader(m["name"]))


def test_peaks_table_is_keyed_by_device_kind():
    p = catalog.peaks("TPU v5 lite")
    assert p["flops_per_s"] == 1.97e14 and p["hbm_bytes_per_s"] == 8.19e11
    with pytest.raises(catalog.UnknownDevice):
        catalog.peaks("TPU v9 imaginary")
