"""The reference's precision and the gaps that decide ``correct``.

Each architecture's plain float32 forward is its module's ``logits``
(``bench/arch/<model_type>.py``); it imports nothing of the program and
reads only the weights the harness made (:mod:`bench.weights`). Its every
matrix product is :func:`mm`, at ``Precision.HIGHEST`` (float32 on a TPU,
not bf16 passes).

``low=`` runs the same forward one precision lower: the control of the
comparison, which has to fail it. ``"high"`` computes every matrix product
in three bf16 passes (the step below float32 at ``highest``); a dtype name
rounds every matrix product's operands to that dtype.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
HIGH = jax.lax.Precision.HIGH
# the nearest precision below each served one
LOWER = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn",
         "float16": "float8_e4m3fn"}


def lower(cfg: dict) -> str:
    """The control's precision for a configuration: the step below its
    dtype, or below its matrix-product precision where that is stated."""
    if cfg["dtype"] == "float32" and cfg.get("matmul_precision") == "highest":
        return "high"
    return LOWER[cfg["dtype"]]


def _round(x, low):
    """``x`` rounded to ``low``; fp8 with a per-tensor scale to its range."""
    if low is None or low == "high":
        return x
    dt = jnp.dtype(low)
    if jnp.issubdtype(dt, jnp.floating) and jnp.finfo(dt).bits == 8:
        s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / float(jnp.finfo(dt).max)
        return (x / s).astype(dt).astype(jnp.float32) * s
    return x.astype(dt).astype(jnp.float32)


def mm(eq, a, b, low):
    """``einsum(eq, a, b)`` in float32 at ``highest``, or in ``low``."""
    return jnp.einsum(eq, _round(a, low), _round(b, low),
                      precision=HIGH if low == "high" else HIGHEST,
                      preferred_element_type=jnp.float32)


_FORWARDS: dict = {}


def forward(arch, cfg: dict):
    """``arch.logits`` bound to ``cfg``: one object for each architecture
    file and configuration, so that :func:`gaps`, which takes it as a
    static argument, traces and compiles once for them across runs."""
    key = (arch.__file__, json.dumps(cfg, sort_keys=True))
    if key not in _FORWARDS:
        _FORWARDS[key] = functools.partial(arch.logits, cfg=cfg)
    return _FORWARDS[key]


@functools.partial(jax.jit, static_argnames=("logits", "low"))
def gaps(w, seq, *, logits, low=None):
    """Per position ``t`` of ``seq`` ``(T,)``: how far below the reference's
    best logit lies the logit of token ``seq[t + 1]``, and (with ``low``)
    that of the token the lower-precision forward puts first. ``logits(w,
    seq, low=)`` is the architecture's forward for one configuration.
    Returns ``(served_gap (T-1,), control_gap (T-1,) or None)``."""
    ref = logits(w, seq)[:-1]
    best = jnp.max(ref, axis=-1)
    served = best - jnp.take_along_axis(ref, seq[1:, None], axis=-1)[:, 0]
    if low is None:
        return served, None
    pick = jnp.argmax(logits(w, seq, low=low)[:-1], axis=-1)
    return served, best - jnp.take_along_axis(ref, pick[:, None],
                                              axis=-1)[:, 0]


def bf16_logit_sigma(depth: int, head: jax.Array) -> float:
    """Predicted standard deviation of a bf16 logit's rounding error.

    The served path rounds to bf16 the residual stream twice per layer and
    about five matrix products per layer (q, k, v, attention output, MLP),
    plus the embedding sum and the final norm: ``n = 7 L + 2`` roundings
    (``L = depth`` layers) of relative size at most ``u = 2^-9``.
    Independent roundings add in quadrature, so the final hidden state
    carries a relative error of about ``sqrt(n) u``; after the final norm its norm is ``sqrt(d)``, and a
    logit is its product with a row of the LM head ``head`` ``(V, d)``,
    whose entries have a standard deviation ``s``. So
    ``sigma = sqrt(n) u sqrt(d) s``. The widest gap of a served greedy
    token is of the order of twice the largest logit error, a few sigma.
    """
    n = 7 * depth + 2
    return math.sqrt(n) * 2.0 ** -9 * math.sqrt(head.shape[-1]) * float(
        head.astype("float32").std())
