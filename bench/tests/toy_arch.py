"""A toy architecture module, a test fixture and no benchmark
configuration: ``test_bench_arch.py`` copies it into a copy of the
benchmark as ``bench/arch/toy_gqa_window.py`` to show that a configuration
of a new ``model_type`` is new files alone.

The model is one the program serves on its fused paged path: grouped
query heads (query head ``i`` reads KV head ``i // (heads / kv_heads)``),
RMSNorm (eps 1e-6), learned positions, a SwiGLU MLP
(``down(silu(gate x) * up x)``), an untied LM head, and a sliding window
of ``sliding_window`` keys (its own included) on every layer but every
``global_every``-th, which attends to every earlier key. No biases.
"""
from __future__ import annotations

import math
from typing import List, Tuple

import jax
import jax.numpy as jnp

from bench import work
from bench.reference import mm


def model_config(cfg: dict):
    from repro.configs.base import AttnCfg, FTCfg, ModelConfig

    if cfg["hidden_act"] != "silu" or cfg["rms_norm_eps"] != 1e-6:
        raise ValueError("the program computes SwiGLU with silu and "
                         "RMSNorm eps 1e-6 only")
    return ModelConfig(
        name="toy_gqa_window", family="dense",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        attn=AttnCfg(num_heads=cfg["num_attention_heads"],
                     num_kv_heads=cfg["num_key_value_heads"],
                     head_dim=cfg["head_dim"], pos="learned",
                     sliding_window=cfg["sliding_window"],
                     global_every=cfg["global_every"]),
        norm="rmsnorm", glu=True, act="silu",
        tie_embeddings=cfg["tie_word_embeddings"], dtype=cfg["dtype"],
        max_seq=cfg["max_position_embeddings"], ft=FTCfg())


def weight_shapes(cfg: dict) -> dict:
    L, d, V = cfg["num_hidden_layers"], cfg["hidden_size"], cfg["vocab_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    ff = cfg["intermediate_size"]
    return {"embed": (V, d), "pos": (cfg["max_position_embeddings"], d),
            "lm_head": (V, d), "final_norm": (d,),
            "norm1": (L, d), "wq": (L, d, q), "wk": (L, d, kv),
            "wv": (L, d, kv), "wo": (L, q, d), "norm2": (L, d),
            "gate": (L, d, ff), "up": (L, d, ff), "down": (L, ff, d)}


def weight_scale(name: str, shape) -> Tuple[float, float]:
    if name in ("embed", "pos", "lm_head"):
        return 0.02, 0.0
    if "norm" in name:
        return 0.05, 1.0
    return shape[-2] ** -0.5, 0.0


def program_params(w: dict) -> dict:
    return {
        "embed": {"table": w["embed"]}, "pos": {"pos": w["pos"]},
        "lm_head": {"table": w["lm_head"]},
        "final_norm": {"w": w["final_norm"]},
        "blocks": {
            "norm1": {"w": w["norm1"]},
            "attn": {k: w[k] for k in ("wq", "wk", "wv", "wo")},
            "norm2": {"w": w["norm2"]},
            "mlp": {k: w[k] for k in ("gate", "up", "down")},
        },
    }


def lm_head(w: dict) -> jax.Array:
    return w["lm_head"]


def _windows(cfg: dict) -> List:
    every = cfg["global_every"]
    return [None if i % every == every - 1 else cfg["sliding_window"]
            for i in range(cfg["num_hidden_layers"])]


def layers(cfg: dict) -> List[work.Layer]:
    return [work.Layer(heads=cfg["num_attention_heads"],
                       kv_heads=cfg["num_key_value_heads"],
                       head_dim=cfg["head_dim"], window=win,
                       itemsize=work.itemsize(cfg["dtype"]))
            for win in _windows(cfg)]


def matmul_flops_per_token(cfg: dict) -> int:
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    per_layer = d * q + 2 * d * kv + q * d + 3 * d * ff
    return 2 * (cfg["num_hidden_layers"] * per_layer
                + d * cfg["vocab_size"])


def _rms(x, g):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + 1e-6) * g


def logits(w: dict, tokens: jax.Array, cfg: dict, low=None) -> jax.Array:
    f32 = lambda a: a.astype(jnp.float32)   # noqa: E731
    T = tokens.shape[0]
    H, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    x = f32(w["embed"])[tokens] + f32(w["pos"])[:T]
    dist = jnp.arange(T)[:, None] - jnp.arange(T)[None, :]
    windows = jnp.asarray([T if win is None else win
                           for win in _windows(cfg)])

    def layer(x, inp):
        lw, window = inp
        lw = jax.tree.map(f32, lw)
        h = _rms(x, lw["norm1"])
        q = mm("td,de->te", h, lw["wq"], low).reshape(T, H, hd)
        k, v = (jnp.repeat(mm("td,de->te", h, lw[n], low).reshape(
            T, hkv, hd), H // hkv, axis=1) for n in ("wk", "wv"))
        s = mm("qhd,khd->hqk", q, k, low) / math.sqrt(hd)
        seen = (dist >= 0) & (dist < window)
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        a = mm("hqk,khd->qhd", p, v, low).reshape(T, H * hd)
        x = x + mm("tq,qd->td", a, lw["wo"], low)
        h = _rms(x, lw["norm2"])
        h = jax.nn.silu(mm("td,df->tf", h, lw["gate"], low)) \
            * mm("td,df->tf", h, lw["up"], low)
        return x + mm("tf,fd->td", h, lw["down"], low), None

    per_layer = {k: v for k, v in w.items()
                 if k not in ("embed", "pos", "lm_head", "final_norm")}
    x, _ = jax.lax.scan(layer, x, (per_layer, windows))
    x = _rms(x, f32(w["final_norm"]))
    return mm("td,vd->tv", x, f32(w["lm_head"]), low)
