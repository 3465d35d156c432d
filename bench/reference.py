"""Plain float32 GPT-2 forward: the reference that decides ``correct``.

Written from the GPT-2 description (Radford et al. 2019): learned token and
position embeddings; per layer ``x += attn(LN1(x))`` then
``x += W_proj gelu_tanh(W_fc LN2(x))``; a final LayerNorm and the tied
embedding as the LM head. Causal softmax attention with scale
``1/sqrt(head_dim)``. Departure from GPT-2: no bias terms in the linear
layers, as in the program under test. It imports nothing of the program and
reads only the weights the harness made (:mod:`bench.weights`).

Every matrix product runs at ``Precision.HIGHEST`` (float32 on a TPU, not
bf16 passes), one layer at a time, each layer's weights cast to float32 as
it is used, so the reference fits beside the served dtype's weights.

``low=`` runs the same forward one precision lower: the control of the
comparison, which has to fail it. ``"high"`` computes every matrix product
in three bf16 passes (the step below float32 at ``highest``); a dtype name
rounds every matrix product's operands to that dtype.
"""
from __future__ import annotations

import functools
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


def _mm(eq, a, b, low):
    return jnp.einsum(eq, _round(a, low), _round(b, low),
                      precision=HIGH if low == "high" else HIGHEST,
                      preferred_element_type=jnp.float32)


def _ln(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _gelu_tanh(x):
    return 0.5 * x * (1 + jnp.tanh(math.sqrt(2 / math.pi)
                                   * (x + 0.044715 * x ** 3)))


def logits(w: dict, tokens: jax.Array, *, n_head: int, eps: float,
           low=None) -> jax.Array:
    """Float32 logits ``(T, V)`` of one sequence ``tokens`` ``(T,)``."""
    f32 = lambda a: a.astype(jnp.float32)   # noqa: E731
    T = tokens.shape[0]
    x = f32(w["wte"])[tokens] + f32(w["wpe"])[:T]
    d = x.shape[-1]
    hd = d // n_head
    causal = jnp.tril(jnp.ones((T, T), bool))

    def layer(x, lw):
        lw = jax.tree.map(f32, lw)
        h = _ln(x, lw["ln1_g"], lw["ln1_b"], eps)
        q, k, v = (_mm("td,de->te", h, lw[n], low).reshape(T, n_head, hd)
                   for n in ("w_q", "w_k", "w_v"))
        s = _mm("qhd,khd->hqk", q, k, low) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        a = _mm("hqk,khd->qhd", p, v, low).reshape(T, d)
        x = x + _mm("td,de->te", a, lw["w_o"], low)
        h = _ln(x, lw["ln2_g"], lw["ln2_b"], eps)
        h = _gelu_tanh(_mm("td,df->tf", h, lw["w_fc"], low))
        return x + _mm("tf,fd->td", h, lw["w_proj"], low), None

    per_layer = {k: v for k, v in w.items()
                 if k not in ("wte", "wpe", "lnf_g", "lnf_b")}
    x, _ = jax.lax.scan(layer, x, per_layer)
    x = _ln(x, f32(w["lnf_g"]), f32(w["lnf_b"]), eps)
    return _mm("td,vd->tv", x, f32(w["wte"]), low)


@functools.partial(jax.jit, static_argnames=("n_head", "eps", "low"))
def gaps(w, seq, *, n_head: int, eps: float, low=None):
    """Per position ``t`` of ``seq`` ``(T,)``: how far below the reference's
    best logit lies the logit of token ``seq[t + 1]``, and (with ``low``)
    that of the token the lower-precision forward puts first. Returns
    ``(served_gap (T-1,), control_gap (T-1,) or None)``."""
    ref = logits(w, seq, n_head=n_head, eps=eps)[:-1]
    best = jnp.max(ref, axis=-1)
    served = best - jnp.take_along_axis(ref, seq[1:, None], axis=-1)[:, 0]
    if low is None:
        return served, None
    pick = jnp.argmax(logits(w, seq, n_head=n_head, eps=eps, low=low)[:-1],
                      axis=-1)
    return served, best - jnp.take_along_axis(ref, pick[:, None],
                                              axis=-1)[:, 0]


def bf16_logit_sigma(cfg: dict, embed_std: float) -> float:
    """Predicted standard deviation of a bf16 logit's rounding error.

    The served path rounds to bf16 the residual stream twice per layer and
    about five matrix products per layer (q, k, v, attention output, MLP),
    plus the embedding sum and the final norm: ``n = 7 L + 2`` roundings of
    relative size at most ``u = 2^-9``. Independent roundings add in
    quadrature, so the final hidden state carries a relative error of about
    ``sqrt(n) u``; after the final LayerNorm its norm is ``sqrt(d)``, and a
    logit is its product with a tied embedding row of entries of standard
    deviation ``embed_std``. So ``sigma = sqrt(n) u sqrt(d) embed_std``.
    The widest gap of a served greedy token is of the order of twice the
    largest logit error, a few sigma.
    """
    n = 7 * cfg["n_layer"] + 2
    return math.sqrt(n) * 2.0 ** -9 * math.sqrt(cfg["n_embd"]) * embed_std
