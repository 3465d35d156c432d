"""Random GPT-2 weights, made on the device from the seed in one call.

The harness makes the weights; the program is handed them
(:func:`bench.system.program_params`) and the reference reads the same
arrays. Layers are stacked on a leading axis. Linear layers have no bias,
as in the program.

Scales: token and position embeddings N(0, 0.02) (GPT-2's initializer
range), a linear map from ``n`` inputs N(0, 1/n), so every layer keeps unit
variance, and LayerNorm gains 1 + N(0, 0.05) and biases N(0, 0.05), so that
the norms' parameters take part in the comparison.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def shapes(cfg: dict) -> dict:
    L, d, V = cfg["n_layer"], cfg["n_embd"], cfg["vocab_size"]
    ff = cfg["n_inner"] or 4 * d
    return {"wte": (V, d), "wpe": (cfg["n_positions"], d),
            "ln1_g": (L, d), "ln1_b": (L, d),
            "w_q": (L, d, d), "w_k": (L, d, d), "w_v": (L, d, d),
            "w_o": (L, d, d), "ln2_g": (L, d), "ln2_b": (L, d),
            "w_fc": (L, d, ff), "w_proj": (L, ff, d),
            "lnf_g": (d,), "lnf_b": (d,)}


def _scale(name: str, shape) -> float:
    if name in ("wte", "wpe"):
        return 0.02
    if name.startswith("w_"):
        return shape[-2] ** -0.5
    return 0.05


@functools.partial(jax.jit, static_argnames=("spec", "dtype"))
def _make(key, *, spec, dtype):
    out = {}
    for i, (name, shape) in enumerate(spec):
        x = jax.random.normal(jax.random.fold_in(key, i), shape, dtype)
        x = x * jnp.asarray(_scale(name, shape), dtype)
        out[name] = x + jnp.asarray(1, dtype) if name.endswith("_g") else x
    return out


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number (more bits than 32 included)."""
    seed %= 2 ** 64
    key = jax.random.fold_in(jax.random.PRNGKey(0),
                             jnp.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, jnp.uint32(seed >> 32))


def make(cfg: dict, seed: int) -> dict:
    """All weights of ``cfg`` in its served dtype, on the default device."""
    spec = tuple(sorted((k, tuple(v)) for k, v in shapes(cfg).items()))
    return _make(seed_key(seed), spec=spec, dtype=jnp.dtype(cfg["dtype"]))
