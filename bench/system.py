"""The system under test: the program's served path, built for one cell.

This is the only module of the harness that imports the program. It turns
a configuration file into the program's ``ModelConfig``, hands the program
the weights the harness made, and builds ``PagedServeEngine`` with the
fused EFTA paged kernel as ``launch/serve.build_engine`` builds it for
``--chunked-prefill``. It also reads the engine's public counters.
"""
from __future__ import annotations

import numpy as np


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for a GPT-2 configuration file."""
    from repro.configs.base import AttnCfg, FTCfg, ModelConfig

    if cfg["model_type"] != "gpt2":
        raise ValueError(f"no mapping for model_type {cfg['model_type']!r}")
    if cfg["activation_function"] != "gelu_new" or \
            cfg["layer_norm_epsilon"] != 1e-5 or \
            not cfg["tie_word_embeddings"]:
        raise ValueError("the program's GPT-2 path computes gelu_new, "
                         "LayerNorm eps 1e-5 and tied embeddings only")
    d, heads = cfg["n_embd"], cfg["n_head"]
    return ModelConfig(
        name=cfg.get("name", "gpt2"), family="dense",
        num_layers=cfg["n_layer"], d_model=d,
        d_ff=cfg["n_inner"] or 4 * d, vocab_size=cfg["vocab_size"],
        attn=AttnCfg(num_heads=heads, num_kv_heads=heads,
                     head_dim=d // heads, pos="learned"),
        norm="layernorm", glu=False, act="gelu", tie_embeddings=True,
        dtype=cfg["dtype"], max_seq=cfg["n_positions"], ft=FTCfg())


def program_params(w: dict) -> dict:
    """The harness's weights (:mod:`bench.weights`) in the program's tree.
    The leaves are the same arrays: nothing is copied."""
    return {
        "embed": {"table": w["wte"]},
        "pos": {"pos": w["wpe"]},
        "final_norm": {"w": w["lnf_g"], "b": w["lnf_b"]},
        "blocks": {
            "norm1": {"w": w["ln1_g"], "b": w["ln1_b"]},
            "attn": {"wq": w["w_q"], "wk": w["w_k"], "wv": w["w_v"],
                     "wo": w["w_o"]},
            "norm2": {"w": w["ln2_g"], "b": w["ln2_b"]},
            "mlp": {"up": w["w_fc"], "down": w["w_proj"]},
        },
    }


def check_params(mcfg, params) -> None:
    """Raise unless ``params`` has the structure and shapes the program's
    own initialiser gives (traced abstractly: nothing is allocated)."""
    import jax
    from repro.models import build_model

    want = jax.eval_shape(build_model(mcfg).init, jax.random.PRNGKey(0))
    got = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError("the harness's weights do not match the program's "
                         "parameter tree")


def build_engine(mcfg, params, engine: dict):
    """``PagedServeEngine`` on the fused kernel, protection as configured."""
    from repro.models import build_model
    from repro.serve import PagedServeEngine

    return PagedServeEngine(
        build_model(mcfg), params, n_slots=engine["n_slots"],
        cache_len=engine["cache_len"], block_size=engine["block_size"],
        chunk_size=engine["chunk_size"], kernel="fused", kv_verify="always")


def submit(eng, prompt: np.ndarray, max_new: int):
    """Submit one greedy request; returns the program's ``Request``."""
    eng.submit(prompt, max_new)
    return eng.scheduler.waiting[-1]


SITES = ("gemm1", "exp", "rowmax", "rowsum", "gemm2", "kv")


def counters(eng) -> dict:
    """The fault-tolerance layer's public counters, summed over requests:
    detections per EFTA site (and resident KV blocks), corrections, step
    retries, KV blocks repaired."""
    det = np.zeros(6, np.int64)
    cor = np.zeros(6, np.int64)
    for st in eng.telemetry.requests.values():
        det += np.asarray(st.detected, np.int64)
        cor += np.asarray(st.corrected, np.int64)
    ps = eng.paged_stats
    return {"detected": dict(zip(SITES, det.tolist())),
            "corrected": dict(zip(SITES, cor.tolist())),
            "retries": eng.stats.retries,
            "kv_detected_blocks": ps.kv_detected_blocks,
            "kv_repaired_blocks": ps.kv_repaired_blocks}
