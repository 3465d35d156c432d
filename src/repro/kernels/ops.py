"""Jit'd dispatch for attention implementations.

``attention(...)`` routes between:
  * ``efta_pallas`` — the fused Pallas TPU kernel (interpreted off-TPU)
  * ``efta``        — pure-JAX EFTA (jit/pjit/differentiable; used at scale)
  * ``flash``       — pure-JAX flash attention, fault tolerance off
  * ``reference``   — naive O(n²) softmax attention
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.efta import EFTAConfig, FTReport, efta_attention, reference_attention
from repro.kernels.efta_attention import efta_attention_pallas

IMPLS = ("efta_pallas", "efta", "flash", "reference")


def attention(
    q, k, v, *,
    impl: str = "efta",
    cfg: Optional[EFTAConfig] = None,
    causal: bool = False,
    window: Optional[int] = None,
    kv_len=None,
    q_offset=0,
    sm_scale: Optional[float] = None,
    fault=None,
    kv_positions=None,
):
    """Unified attention entry point. Returns (out, FTReport)."""
    cfg = cfg or EFTAConfig()
    if impl == "reference":
        out = reference_attention(q, k, v, causal=causal, window=window,
                                  kv_len=kv_len, q_offset=q_offset,
                                  sm_scale=sm_scale, kv_positions=kv_positions)
        return out, FTReport.zero()
    if impl == "flash":
        off = EFTAConfig(mode="off", stride=cfg.stride, block_kv=cfg.block_kv)
        return efta_attention(q, k, v, cfg=off, causal=causal, window=window,
                              kv_len=kv_len, q_offset=q_offset,
                              sm_scale=sm_scale, kv_positions=kv_positions)
    if impl == "efta":
        return efta_attention(q, k, v, cfg=cfg, causal=causal, window=window,
                              kv_len=kv_len, q_offset=q_offset,
                              sm_scale=sm_scale, fault=fault,
                              kv_positions=kv_positions)
    if impl == "efta_pallas":
        if kv_positions is not None or q_offset != 0 or (
                kv_len is not None
                and not isinstance(kv_len, (int, np.integer))):
            raise NotImplementedError(
                "ring caches / decode offsets / traced kv_len route through "
                "impl='efta'; the Pallas kernel takes a static ragged kv_len")
        out, det = efta_attention_pallas(
            q, k, v, cfg=cfg, causal=causal, window=window,
            kv_len=None if kv_len is None else int(kv_len),
            sm_scale=sm_scale, fault=fault)
        return out, FTReport(det, det if cfg.mode == "correct" else det * 0,
                             jnp.zeros((3,), jnp.float32))
    raise ValueError(f"unknown attention impl {impl!r}; one of {IMPLS}")


# --- paged KV-cache paths --------------------------------------------------
#
# The paged serve engine stores KV in a global block pool
# ``(num_layers, num_blocks, Hkv, block_size, head_dim)`` and addresses it
# through per-request block tables. Two decode backends consume it:
#
#   * gather (below): materialize each request's table as the contiguous
#     ``(.., Hkv, S, hd)`` layout every impl above already accepts (token
#     position == table order), so EFTA / flash / reference all serve paged
#     caches for free — at the cost of an extra HBM round-trip per byte and
#     a separate full-pool checksum pass. This is the portable baseline;
#     its prefill / prefix-extend / block-repair run through one
#     fixed-width chunked ``Model.extend`` program.
#   * fused (``repro.kernels.efta_paged.efta_paged_attention_pallas``):
#     unified multi-token Pallas kernel whose BlockSpec index maps read the
#     block table directly (scalar prefetch), with the batch axis in the
#     grid (native batched ragged chunks: per-request ``kv_len`` AND
#     ``q_len`` masking serve mixed prefill/extend/repair/decode batches in
#     one program) and the resident block-checksum verify folded into the
#     KV streaming loop. Dispatched via ``PagedServeEngine(kernel="fused")``
#     through ``repro.models.attention.PagedKVCache``.


def merge_block_axes(x: jax.Array) -> jax.Array:
    """(L, ..., mb, Hkv, bs, hd) gathered blocks -> (L, ..., Hkv, mb*bs, hd)
    contiguous KV layout (table order becomes token order)."""
    n = x.ndim
    x = x.transpose(*range(n - 4), n - 3, n - 4, n - 2, n - 1)
    return x.reshape(*x.shape[:-3], x.shape[-3] * x.shape[-2], x.shape[-1])


def gather_block_kv(pool: jax.Array,
                    block_table: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Gather a paged pool array by block table.

    ``pool``: (L, num_blocks, Hkv, bs, hd); ``block_table``: int32 block ids
    of shape (mb,) or (n_slots, mb), null-padded with block 0. Returns both
    views of the single gather: the raw block layout
    ``(L[, n_slots], mb, Hkv, bs, hd)`` (what read-time checksum
    verification folds over) and the contiguous per-request KV view
    ``(L[, n_slots], Hkv, mb*bs, hd)`` (what attention consumes).
    """
    raw = pool[:, block_table]
    return raw, merge_block_axes(raw)
