"""A cell small enough for the CPU, for the harness's own tests.

GPT-2 in shape (learned positions, LayerNorm, gelu_new, tied embeddings)
at toy widths, served in bfloat16, with a closed-loop mix of short
requests. Its limit was read on the CPU (Pallas interpreter) from the
harness itself, over seeds 1-4 and windows of 2 s: the program's widest
gap read 0 to 0.0012, the float8 control's 0.030 to 0.064. It holds for
this cell only.
"""
from __future__ import annotations

from bench import catalog

CONFIG = {
    "model_type": "gpt2", "n_layer": 2, "n_embd": 64, "n_head": 4,
    "n_inner": None, "vocab_size": 1024, "n_positions": 128, "n_ctx": 128,
    "layer_norm_epsilon": 1e-05, "activation_function": "gelu_new",
    "tie_word_embeddings": True, "dtype": "bfloat16",
    "engine": {"n_slots": 4, "cache_len": 128, "block_size": 16,
               "chunk_size": 32},
}
TRAFFIC = {
    "generator": "lognormal", "loop": "closed", "clients": 4,
    "prompt": {"median": 24, "sigma": 0.5, "min": 4, "max": 60},
    "output": {"median": 6, "sigma": 0.5, "min": 2, "max": 12},
    "warm_steps": 2,
}
LIMITS = {"gap_max": 0.01, "sampled_tokens": 20}
SAMPLE = {"requests": 12, "tokens": 120}


def cell() -> catalog.Cell:
    """The tiny cell, reporting every metric the real cells report."""
    bench = catalog.load_json(catalog.ROOT / "BENCHMARK.json")

    def metrics(entries, e2e):
        return tuple(catalog.Metric(
            name=e["name"], unit=e["unit"], end_to_end=e2e,
            moves=e.get("moves"), workloads=None,
            read=catalog.load_reader(e["name"])) for e in entries)

    return catalog.Cell(
        name="tiny", chips=1, config=CONFIG,
        arch=catalog.load_arch(CONFIG["model_type"]),
        traffic=TRAFFIC, generator=catalog.load_generator("lognormal"),
        limits=LIMITS, sample=SAMPLE,
        end_to_end=metrics(bench["end_to_end"], True),
        per_layer=metrics(bench["per_layer"], False))
