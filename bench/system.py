"""The system under test: the program's served path, built for one cell.

With the architecture modules' ``model_config`` (``bench/arch/``), this is
the only module of the harness that imports the program. It checks the
weights the harness made against the program's parameter tree, builds
``PagedServeEngine`` with the fused EFTA paged kernel as
``launch/serve.build_engine`` builds it for ``--chunked-prefill``, and
reads the engine's public counters.
"""
from __future__ import annotations

import numpy as np


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
