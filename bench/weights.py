"""Random weights, made on the device from the seed in one call.

The harness makes the weights; the program is handed them (the
architecture module's ``program_params``) and the reference reads the same
arrays. Names, shapes and scales are the architecture module's
(``weight_shapes``, ``weight_scale``); the draws are in the order of the
sorted names, the ``i``-th from ``fold_in(key, i)``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("spec", "dtype"))
def _make(key, *, spec, dtype):
    out = {}
    for i, (name, shape, std, mean) in enumerate(spec):
        x = jax.random.normal(jax.random.fold_in(key, i), shape, dtype)
        x = x * jnp.asarray(std, dtype)
        out[name] = x + jnp.asarray(mean, dtype) if mean else x
    return out


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number (more bits than 32 included)."""
    seed %= 2 ** 64
    key = jax.random.fold_in(jax.random.PRNGKey(0),
                             jnp.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, jnp.uint32(seed >> 32))


def make(arch, cfg: dict, seed: int) -> dict:
    """All weights of ``cfg`` in its served dtype, on the default device;
    ``arch`` is the configuration's architecture module."""
    spec = tuple(sorted((k, tuple(v), *arch.weight_scale(k, v))
                        for k, v in arch.weight_shapes(cfg).items()))
    return _make(seed_key(seed), spec=spec, dtype=jnp.dtype(cfg["dtype"]))
