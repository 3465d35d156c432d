"""Architecture modules (``bench/arch/<model_type>.py``).

GPT-2's readings are pinned to the values the harness read before its
GPT-2 code became ``bench/arch/gpt2.py``: the weights a seed makes, the
reference logits of one sequence, and the work counts of one list of
requests, each exactly. Then a second ``model_type`` is added to a copy of
the benchmark as new files alone (``toy_arch.py``, a test fixture, with its
configuration, traffic, cell and ``BENCHMARK.json`` entries), and its cell
runs through ``run.run_cell`` on the CPU: correct, and its float8 control
not. A program scope that no harness file names is read the same way:
by a new metric file (``toy_metric.py``, copied in as
``bench/metrics/toy.mix_ms.py``) and its entry in ``BENCHMARK.json``.
"""
import hashlib
import json
import shutil
import time
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import catalog, reference, run, spans, weights, work
from bench.tests import tiny
from bench.tests.test_bench_spans import STEP_FN, _host, _op
from bench.tests.test_bench_tracing import _plane

GPT2 = catalog.load_arch("gpt2")
SEED = 2 ** 33 + 7
SEQ = (np.arange(40) * 37 + 5) % 1024
REQS = [(32, 100), (1, 700), (7, 7), (1, 1), (16, 1024), (3, 45)]
GEO = {"block_size": 16, "check_stride": 8}


def _digest(tree: dict) -> str:
    h = hashlib.sha256()
    for k in sorted(tree):
        a = np.asarray(tree[k])
        h.update(f"{k}|{a.dtype}|{a.shape}|".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def test_gpt2_weights_are_the_same_bit_for_bit():
    w = weights.make(GPT2, tiny.CONFIG, SEED)
    assert _digest(w) == ("dacadb24d9f36c8e8bb90f4c970d947a"
                          "13b96e6607f89719c8a94e7b4db2338f")


@pytest.mark.parametrize("low,want", [
    (None, "1666b87a0b6631d4e0c6de1c0981f3e7c57bc84d29efdad255d0f486eadd16fd"),
    ("bfloat16",
     "35656c1b60d82ec466e203d30f250074f6f7125bda50e36e2d12ccd5d180f422"),
    ("float8_e4m3fn",
     "f8139d84c750d7d8694266b80a64c5036d7c786c13c8012ed7fceee624f3913b"),
])
def test_gpt2_reference_logits_are_the_same(low, want):
    w = weights.make(GPT2, tiny.CONFIG, SEED)
    lg = jax.jit(lambda w, s: GPT2.logits(w, s, tiny.CONFIG, low=low))(
        w, jnp.asarray(SEQ, jnp.int32))
    assert lg.shape == (len(SEQ), tiny.CONFIG["vocab_size"])
    assert _digest({"x": lg}) == want


def test_gpt2_gaps_are_the_same():
    w = weights.make(GPT2, tiny.CONFIG, SEED)
    s, c = reference.gaps(w, jnp.asarray(SEQ, jnp.int32),
                          logits=lambda w, s, low=None: GPT2.logits(
                              w, s, tiny.CONFIG, low=low),
                          low="float8_e4m3fn")
    assert _digest({"s": s, "c": c}) == (
        "2439653bf3f48340f31cd0037571352c191b5cc1ca29470e6f605f6b428a1fa7")


def test_gaps_compile_once_per_architecture_and_configuration():
    # a module loaded anew and an equal configuration find the same
    # forward, so a second run's reference hits the jit cache
    cfg = dict(tiny.CONFIG)
    fwd = reference.forward(GPT2, tiny.CONFIG)
    assert reference.forward(catalog.load_arch("gpt2"), cfg) is fwd
    w = weights.make(GPT2, tiny.CONFIG, SEED)
    seq = jnp.asarray(SEQ[:41], jnp.int32)
    before = reference.gaps._cache_size()
    for arch in (GPT2, catalog.load_arch("gpt2")):
        reference.gaps(w, seq, logits=reference.forward(arch, cfg))
    assert reference.gaps._cache_size() == before + 1


@pytest.mark.parametrize("name,kernel_flops,kernel_bytes,per_token,attn,pool", [
    ("tiny", 6059264, 998400, 327680, 10152448, 1024),
    ("gpt2-f32", 72711168, 23961600, 247064064, 730976256, 147456),
    ("gpt2-xl", 151481600, 24960000, 3109942400, 6091468800, 614400),
])
def test_gpt2_work_counts_are_the_same(name, kernel_flops, kernel_bytes,
                                       per_token, attn, pool):
    cfg = tiny.CONFIG if name == "tiny" else catalog.load_json(
        catalog.BENCH_DIR / "configs" / f"{name}.json")
    layers = GPT2.layers(cfg)
    assert len(set(layers)) == 1 and layers[0].window is None
    kw = work.kernel_call_work(layers[0], GEO, REQS)
    assert (kw.flops, kw.bytes) == (kernel_flops, kernel_bytes)
    assert GPT2.matmul_flops_per_token(cfg) == per_token
    assert work.attention_flops(layers, REQS) == attn
    assert work.pool_bytes_per_token(layers, GEO) == pool


# -- a second model_type, as new files alone --------------------------------

TOY_TYPE = "toy_gqa_window"
TOY_CONFIG = {
    "model_type": TOY_TYPE, "num_hidden_layers": 4, "hidden_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "intermediate_size": 128, "vocab_size": 1024,
    "max_position_embeddings": 128, "rms_norm_eps": 1e-6,
    "hidden_act": "silu", "tie_word_embeddings": False,
    "sliding_window": 16, "global_every": 4, "dtype": "bfloat16",
    "engine": tiny.CONFIG["engine"],
}
# read on the CPU (Pallas interpreter) from this test's run, over seeds
# 1-8 and windows of 1.5 s: the program's widest gap read 0 to 0.0053, the
# float8 control's 0.098 to 0.23
TOY_CELL = {"limits": {"gap_max": 0.03, "sampled_tokens": 20},
            "sample": tiny.SAMPLE}
TOY_BENCH = {
    "configs": [{"name": "toy", "source": "https://example.org/toy",
                 "file": "bench/configs/toy.json",
                 "reduced": ["num_hidden_layers"],
                 "why": "a test fixture"}],
    "workloads": [{"name": "toy.short", "config": "toy",
                   "traffic": "toy_short", "chips": 1,
                   "why": "a test fixture"}],
    "per_layer": [{"name": "toy.mix_ms", "unit": "ms", "better": "lower",
                   "source": "device_trace", "layer": "toy mixer",
                   "moves": "itl_p95_ms", "workloads": ["toy.short"]}],
}
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
PEAKS = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def _files(root: Path) -> dict:
    return {p.relative_to(root): p.read_bytes() for p in root.rglob("*")
            if p.is_file()}


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench_copy")
    shutil.copy(catalog.ROOT / "BENCHMARK.json", root)
    shutil.copytree(catalog.BENCH_DIR, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _files(root)
    new = {
        "bench/arch/toy_gqa_window.py":
            (catalog.BENCH_DIR / "tests" / "toy_arch.py").read_text(),
        "bench/configs/toy.json": json.dumps(TOY_CONFIG),
        "bench/traffic/toy_short.json": json.dumps(tiny.TRAFFIC),
        "bench/cells/toy.short.json": json.dumps(TOY_CELL),
        "bench/metrics/toy.mix_ms.py":
            (catalog.BENCH_DIR / "tests" / "toy_metric.py").read_text(),
    }
    for rel, text in new.items():
        assert not (root / rel).exists()
        (root / rel).write_text(text)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for key, entries in TOY_BENCH.items():
        bench[key] += entries
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    # every file that was there is as it was, BENCHMARK.json but for the
    # entries added at the end of its lists
    after = _files(root)
    for rel, data in before.items():
        if rel.name != "BENCHMARK.json":
            assert after[rel] == data, rel
    old = json.loads(before[Path("BENCHMARK.json")])
    assert {k: v[:len(old[k])] if k in TOY_BENCH else v
            for k, v in bench.items()} == old
    return root


def test_a_new_model_types_entries_keep_to_the_contract(toy_root):
    # the catalog's contract checks, unedited, take a cut configuration
    from bench.tests import test_bench_catalog as contract
    bench = catalog.load_json(toy_root / "BENCHMARK.json")
    for entry in bench["configs"]:
        contract.check_config(entry, toy_root)
    for w in bench["workloads"]:
        contract.check_cell(w["name"], toy_root)


def test_a_new_model_type_is_new_files_and_its_cell_is_correct(toy_root):
    cell = catalog.load_cell("toy.short", toy_root)
    assert cell.arch.__file__ == str(toy_root / "bench" / "arch"
                                     / f"{TOY_TYPE}.py")
    res = run.run_cell(cell, 5, 1.5, False, device=CPU, peaks=PEAKS,
                       t_start=time.perf_counter(), control="float8_e4m3fn")
    assert res["correct"], res["checks"]
    assert res["checks"]["detections"]["value"] == 0
    # the control, judged by the same checks, is not correct
    assert not res["info"]["control_correct"]
    assert res["info"]["control_gap_max"] > TOY_CELL["limits"]["gap_max"]


def test_a_new_model_types_layers_count_kv_heads_and_windows(toy_root):
    arch = catalog.load_cell("toy.short", toy_root).arch
    layers = arch.layers(TOY_CONFIG)
    assert [lay.window for lay in layers] == [16, 16, 16, None]
    win, full = layers[0], layers[3]
    # a decode row at 69: 5 resident blocks, 2 of them in the window (keys
    # 54..69); a block is K, V and four checksum tiles of 8 rows for each
    # of the 2 KV heads of 16, in bf16; one query row read, one written
    block = (2 * 16 + 4 * 8) * 2 * 16 * 2
    rows = 2 * 4 * 16 * 2
    a = work.kernel_call_work(full, GEO, [(1, 70)])
    b = work.kernel_call_work(win, GEO, [(1, 70)])
    assert (a.bytes, b.bytes) == (5 * block + rows, 2 * block + rows)
    assert (a.bytes - rows) * 2 == (b.bytes - rows) * 5
    # 70 keys against 16 in QK^T and PV; checksums of 5 and 2 blocks
    fold = 2 * 2 * 2 * 16 * 2 * 16
    assert a.flops == 4 * 4 * 16 * 70 + 5 * fold
    assert b.flops == 4 * 4 * 16 * 16 + 2 * fold


# -- a new program scope, as a new metric file ------------------------------

def _toy_step():
    """A traced window of two engine steps whose device ops sit under the
    toy mixer's scopes, and the compiled program's text that names them."""
    def meta(path):
        return f'metadata={{op_name="{STEP_FN}/{path}" stack_frame_id=1}}'
    ops = [
        # its text names the operand %toy_mix.3; its time is toy_mix's
        ("%fusion.7 = f32[8,64] fusion(f32[8,64] %x, f32[64,128] "
         "%toy_mix.3), kind=kOutput", "toy_mix/dot_general", 105, 60),
        # toy_gate is opened inside toy_mix: the innermost one counts
        ("%reduce.2 = f32[8] reduce(f32[8,64] %y)",
         "toy_mix/toy_gate/reduce_max", 300, 30),
        # a scope of another reader, and a name toy_mix only begins
        ("%fusion.12 = f32[8] fusion(f32[8] %a), kind=kLoop",
         "kv_append/scatter", 525, 20),
        ("%fusion.9 = f32[8] fusion(f32[8] %b), kind=kLoop",
         "toy_mixer/dot_general", 560, 40),
        ("%copy.5 = f32[8] copy(f32[8] %d)", None, 850, 20)]
    device = _plane("/device:TPU:0", **{"XLA Ops": [
        _op(text.split(", kind=")[0], start, dur)
        for text, _, start, dur in ops]})
    hlo = "\n".join(f"  {text}, {meta(path)}" if path else f"  {text}"
                     for text, path, _, _ in ops)
    hlo += f"\n  %toy_mix.3 = f32[64,128] parameter(3), {meta('toy_mix/w')}"
    return [_host(), device], hlo


def test_a_new_program_scope_is_read_by_a_new_metric_file(toy_root):
    # no harness file of the copy names the scope; every file the
    # benchmark has is in the copy as it is here
    for path in catalog.BENCH_DIR.rglob("*"):
        if path.is_file() and "__pycache__" not in path.parts:
            rel = path.relative_to(catalog.ROOT)
            assert (toy_root / rel).read_bytes() == path.read_bytes(), rel
    harness = [p for p in (toy_root / "bench").rglob("*.py")
               if p.name != "toy.mix_ms.py" and "tests" not in p.parts]
    assert harness and not any("toy_mix" in p.read_text() for p in harness)
    # the cell finds the reader by its name in BENCHMARK.json
    cell = catalog.load_cell("toy.short", toy_root)
    (metric,) = [m for m in cell.per_layer if m.name == "toy.mix_ms"]
    planes, hlo = _toy_step()
    trace = spans.summarize(planes, [hlo])
    got = metric.read(types.SimpleNamespace(trace=trace,
                                            steps=[object()] * 2))
    # 60 us under toy_mix and 30 under toy_gate, over two steps
    assert got["toy_mix_ms"] == pytest.approx(0.03)
    assert got["toy_gate_ms"] == pytest.approx(0.015)
    assert got["value"] == pytest.approx(0.045)
    # a program without the scopes reads nothing
    bare = spans.summarize(planes, [hlo.replace("toy_", "other_")])
    assert metric.read(types.SimpleNamespace(trace=bare,
                                             steps=[object()] * 2)) is None
