"""GPT-2 (``model_type`` ``"gpt2"``): all the harness knows of the
architecture. A configuration file names its architecture by
``model_type``, and the harness loads ``bench/arch/<model_type>.py``
(:func:`bench.catalog.load_arch`), which gives:

- :func:`model_config`: the program's ``ModelConfig``;
- :func:`weight_shapes` and :func:`weight_scale`: the weights
  :func:`bench.weights.make` draws, and :func:`program_params`, the same
  arrays in the program's parameter tree;
- :func:`logits`: the plain float32 forward, the reference that decides
  ``correct``, and :func:`lm_head`, the matrix it ends with;
- :func:`layers`: each layer's attention, for the work counts
  (:mod:`bench.work`);
- :func:`matmul_flops_per_token`.

Only :func:`model_config` imports the program.

The reference is written from the GPT-2 description (Radford et al. 2019):
learned token and position embeddings; per layer ``x += attn(LN1(x))``
then ``x += W_proj gelu_tanh(W_fc LN2(x))``; a final LayerNorm and the tied
embedding as the LM head. Causal softmax attention with scale
``1/sqrt(head_dim)``. Departure from GPT-2: no bias terms in the linear
layers, as in the program under test. Every matrix product is
:func:`bench.reference.mm`, at ``Precision.HIGHEST``, one layer at a time,
each layer's weights cast to float32 as it is used, so the reference fits
beside the served dtype's weights.

Weights: token and position embeddings N(0, 0.02) (GPT-2's initializer
range), a linear map from ``n`` inputs N(0, 1/n), so every layer keeps unit
variance, and LayerNorm gains 1 + N(0, 0.05) and biases N(0, 0.05), so that
the norms' parameters take part in the comparison. Layers are stacked on a
leading axis.
"""
from __future__ import annotations

import math
from typing import List, Tuple

import jax
import jax.numpy as jnp

from bench import work
from bench.reference import mm


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for a GPT-2 configuration file."""
    from repro.configs.base import AttnCfg, FTCfg, ModelConfig

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


def weight_shapes(cfg: dict) -> dict:
    L, d, V = cfg["n_layer"], cfg["n_embd"], cfg["vocab_size"]
    ff = cfg["n_inner"] or 4 * d
    return {"wte": (V, d), "wpe": (cfg["n_positions"], d),
            "ln1_g": (L, d), "ln1_b": (L, d),
            "w_q": (L, d, d), "w_k": (L, d, d), "w_v": (L, d, d),
            "w_o": (L, d, d), "ln2_g": (L, d), "ln2_b": (L, d),
            "w_fc": (L, d, ff), "w_proj": (L, ff, d),
            "lnf_g": (d,), "lnf_b": (d,)}


def weight_scale(name: str, shape) -> Tuple[float, float]:
    """Standard deviation and mean of the weight ``name``."""
    mean = 1.0 if name.endswith("_g") else 0.0
    if name in ("wte", "wpe"):
        return 0.02, mean
    if name.startswith("w_"):
        return shape[-2] ** -0.5, mean
    return 0.05, mean


def program_params(w: dict) -> dict:
    """The harness's weights in the program's tree. The leaves are the
    same arrays: nothing is copied."""
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


def lm_head(w: dict) -> jax.Array:
    """The ``(vocab, d)`` matrix the logits are taken with: the tied
    token embedding."""
    return w["wte"]


def layers(cfg: dict) -> List[work.Layer]:
    """Every layer: multi-head attention, one KV head per query head, no
    window."""
    d, heads = cfg["n_embd"], cfg["n_head"]
    return [work.Layer(heads=heads, kv_heads=heads, head_dim=d // heads,
                       window=None, itemsize=work.itemsize(cfg["dtype"]))
            ] * cfg["n_layer"]


def matmul_flops_per_token(cfg: dict) -> int:
    """2 FLOPs per weight of every matrix product one token passes:
    q, k, v, o and the two MLP maps in each layer, and the LM head."""
    d = cfg["n_embd"]
    ff = cfg["n_inner"] or 4 * d
    return 2 * (cfg["n_layer"] * (4 * d * d + 2 * d * ff)
                + d * cfg["vocab_size"])


def _ln(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _gelu_tanh(x):
    return 0.5 * x * (1 + jnp.tanh(math.sqrt(2 / math.pi)
                                   * (x + 0.044715 * x ** 3)))


def logits(w: dict, tokens: jax.Array, cfg: dict, low=None) -> jax.Array:
    """Float32 logits ``(T, V)`` of one sequence ``tokens`` ``(T,)``;
    ``low`` as :func:`bench.reference.mm` takes it."""
    n_head, eps = cfg["n_head"], cfg["layer_norm_epsilon"]
    f32 = lambda a: a.astype(jnp.float32)   # noqa: E731
    T = tokens.shape[0]
    x = f32(w["wte"])[tokens] + f32(w["wpe"])[:T]
    d = x.shape[-1]
    hd = d // n_head
    causal = jnp.tril(jnp.ones((T, T), bool))

    def layer(x, lw):
        lw = jax.tree.map(f32, lw)
        h = _ln(x, lw["ln1_g"], lw["ln1_b"], eps)
        q, k, v = (mm("td,de->te", h, lw[n], low).reshape(T, n_head, hd)
                   for n in ("w_q", "w_k", "w_v"))
        s = mm("qhd,khd->hqk", q, k, low) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        a = mm("hqk,khd->qhd", p, v, low).reshape(T, d)
        x = x + mm("td,de->te", a, lw["w_o"], low)
        h = _ln(x, lw["ln2_g"], lw["ln2_b"], eps)
        h = _gelu_tanh(mm("td,df->tf", h, lw["w_fc"], low))
        return x + mm("tf,fd->td", h, lw["w_proj"], low), None

    per_layer = {k: v for k, v in w.items()
                 if k not in ("wte", "wpe", "lnf_g", "lnf_b")}
    x, _ = jax.lax.scan(layer, x, per_layer)
    x = _ln(x, f32(w["lnf_g"]), f32(w["lnf_b"]), eps)
    return mm("td,vd->tv", x, f32(w["wte"]), low)
