"""Attention block: GQA/MQA + RoPE + sliding window + cross-attention, with
the paper's EFTA as the attention implementation.

The KV cache uses slot = position % cache_len, which uniformly covers:
  * global layers  (cache_len = max_len, slot = position)
  * sliding window (cache_len = window,  ring buffer)
Keys are cached post-RoPE, so ring wraparound needs no re-rotation; masking
only needs ``kv_len`` (number of valid slots).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import AttnCfg, FTCfg
from repro.core import checksum as cks
from repro.core.efta import EFTAConfig, FTReport
from repro.kernels.efta_paged import efta_paged_attention_pallas
from repro.kernels.ops import attention as attention_op
from repro.models.layers import dense_init, matmul, rope


class KVCache(NamedTuple):
    k: jax.Array            # (B, Hkv, cache_len, hd)
    v: jax.Array
    pos: jax.Array          # int32 scalar: number of tokens seen so far
    # cross-attention memory (computed once at prefill; empty arrays if unused)
    ck: jax.Array
    cv: jax.Array


class PagedKVCache(NamedTuple):
    """One layer's view of the paged serve engine's checksummed block pool.

    Passed in place of :class:`KVCache` to run the unified batched step over
    ragged requests through the fused paged-attention kernel: K/V stay in the
    shared pool and are consumed by block table, never gathered into a
    contiguous view. The step is *multi-token*: each request feeds a chunk of
    ``q_len`` rows (1 = decode, up to the chunk width = prefill / extend /
    repair), so one mixed batch serves every regime through one compiled
    program. ``bad`` is an *output* plane: per-(request, table-slot)
    resident-checksum mismatches found this step (in-kernel for streamed
    blocks, at append time for partially-overwritten blocks), which the
    engine's repair path consumes. Stacked over layers for the transformer's
    block scan.
    """

    k: jax.Array     # (num_blocks+1, Hkv, block_size, hd); row 0 = null block
    v: jax.Array
    kc1: jax.Array   # (num_blocks+1, Hkv, check_stride, hd) resident encode_kv
    kc2: jax.Array
    vc1: jax.Array
    vc2: jax.Array
    bt: jax.Array    # (B, table_len) int32 per-request block tables (0-padded)
    pos: jax.Array   # (B,) int32 tokens resident before this step
    q_len: jax.Array  # (B,) int32 valid chunk rows this step (0 = idle slot)
    bad: jax.Array   # (B, table_len) int32 mismatch flags (in/out)


def efta_cfg(ft: FTCfg) -> EFTAConfig:
    return EFTAConfig(mode=ft.mode, stride=ft.stride, block_kv=ft.block_kv,
                      unified=ft.unified, shadow_rowsum=ft.shadow_rowsum,
                      shadow_rowmax=ft.shadow_rowmax, unroll=ft.scan_unroll,
                      kv_stride_override=ft.kv_stride_override,
                      out_stride_override=ft.out_stride_override)


def attn_init(key, d_model: int, a: AttnCfg, dtype, *, cross: bool = False):
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d_model, a.num_heads * a.head_dim, dtype),
        "wk": dense_init(ks[1], d_model, a.num_kv_heads * a.head_dim, dtype),
        "wv": dense_init(ks[2], d_model, a.num_kv_heads * a.head_dim, dtype),
        "wo": dense_init(ks[3], a.num_heads * a.head_dim, d_model, dtype),
    }
    return p


def init_cache(batch: int, a: AttnCfg, *, cache_len: int, dtype,
               cross_len: int = 0, d_model: int = 0) -> KVCache:
    shape = (batch, a.num_kv_heads, cache_len, a.head_dim)
    cshape = (batch, a.num_kv_heads, max(cross_len, 1), a.head_dim)
    return KVCache(
        k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
        pos=jnp.zeros((), jnp.int32),
        ck=jnp.zeros(cshape, dtype), cv=jnp.zeros(cshape, dtype))


def _paged_chunk(q, k, v, cache: PagedKVCache, *, cfg: EFTAConfig, window,
                 sm_scale, fault):
    """One unified batched multi-token step against the paged block pool.

    ``q``/``k``/``v``: this step's projected (+RoPE'd) (B, H|Hkv, C, hd)
    chunk tensors; request ``b`` feeds ``cache.q_len[b]`` valid rows at
    positions ``pos .. pos + q_len - 1`` (1 row = decode, more = chunked
    prefill / extend / block repair — one mixed batch, one program).
    Appends every valid row's K/V into its request's blocks (a chunk may
    straddle a block edge), regenerates the checksums of exactly the blocks
    the chunk touched, then dispatches the fused paged-attention kernel over
    the block tables — append-before-attend, exactly mirroring the gather
    path's in-step scatter, so each chunk row attends to itself and its
    predecessors.

    Verification split: the kernel verifies every streamed block in its KV
    loop, but the append below refreshes the touched blocks' checksums from
    current content — doing that over a corrupted row would launder the
    corruption into a consistent (permanently silent) state. Only the
    *first* touched block can hold prior valid rows (``pos % bs > 0``;
    later touched blocks are written from row 0), so it is verified here
    against its pre-append checksums first, and its flag joins the kernel's
    ``bad`` plane. ``fault`` is the fused kernel's int32[8] descriptor (see
    ``repro.kernels.efta_paged``), not a FaultSpec.
    """
    bs = cache.k.shape[2]
    cs = cache.kc1.shape[2]
    thr = cks.kv_block_threshold(cache.k.dtype)
    bt, pos, q_len = cache.bt, cache.pos, cache.q_len
    mb = bt.shape[1]
    c_width = k.shape[2]
    j0 = pos // bs                                             # (B,)
    off = pos % bs

    # -- laundering guard: pre-verify the first touched block's prior rows
    with jax.named_scope("kv_guard"):
        tgt0 = jnp.take_along_axis(bt, j0[:, None], axis=1)[:, 0]
        bad_tk, _ = cks.verify_block(
            cache.k[tgt0], cks.Checksums(cache.kc1[tgt0], cache.kc2[tgt0]),
            cs, threshold=thr)
        bad_tv, _ = cks.verify_block(
            cache.v[tgt0], cks.Checksums(cache.vc1[tgt0], cache.vc2[tgt0]),
            cs, threshold=thr)
        tail_bad = (jnp.any(bad_tk | bad_tv, axis=-1) & (tgt0 > 0)
                    & (off > 0) & (q_len > 0))                 # (B,)

    with jax.named_scope("kv_append"):
        # -- scatter the chunk's K/V rows into their blocks (append-
        # before-attend); padding rows (c >= q_len) divert to the null
        # scratch block
        c_idx = jnp.arange(c_width, dtype=jnp.int32)
        p_abs = pos[:, None] + c_idx[None, :]                  # (B, C)
        valid = c_idx[None, :] < q_len[:, None]
        jrow = jnp.clip(p_abs // bs, 0, mb - 1)
        tgt_rows = jnp.where(valid, jnp.take_along_axis(bt, jrow, axis=1), 0)
        offs = jnp.where(valid, p_abs % bs, 0)
        row_k = k.transpose(0, 2, 1, 3).astype(cache.k.dtype)  # B,C,Hkv,hd
        row_v = v.transpose(0, 2, 1, 3).astype(cache.v.dtype)
        new_k = cache.k.at[tgt_rows, :, offs, :].set(row_k)
        new_v = cache.v.at[tgt_rows, :, offs, :].set(row_v)

        # -- checksum generation for exactly the blocks the chunk touched
        # (the first may be partial, the rest start at row 0; untouched ->
        # null)
        nt = (c_width + bs - 2) // bs + 1  # max blocks a C-row chunk spans
        jt = j0[:, None] + jnp.arange(nt, dtype=jnp.int32)[None, :]  # B, nt
        last = (pos + jnp.maximum(q_len, 1) - 1) // bs
        touched = (jt <= last[:, None]) & (q_len[:, None] > 0)
        tid = jnp.where(touched, jnp.take_along_axis(
            bt, jnp.clip(jt, 0, mb - 1), axis=1), 0)
        kc = cks.encode_kv(new_k[tid], cs)             # (B, nt, Hkv, cs, hd)
        vc = cks.encode_kv(new_v[tid], cs)
        kc1 = cache.kc1.at[tid].set(kc.c1)
        kc2 = cache.kc2.at[tid].set(kc.c2)
        vc1 = cache.vc1.at[tid].set(vc.c1)
        vc2 = cache.vc2.at[tid].set(vc.c2)

    rep = efta_paged_attention_pallas(
        q, new_k, new_v,
        cks.Checksums(kc1, kc2), cks.Checksums(vc1, vc2),
        bt, pos + q_len, q_len, cfg=cfg, check_threshold=thr, window=window,
        sm_scale=sm_scale, fault=fault)

    tail_plane = (jnp.arange(mb, dtype=jnp.int32)[None, :] == j0[:, None]
                  ) & tail_bad[:, None]
    new_bad = jnp.maximum(cache.bad,
                          jnp.maximum(rep.bad_blocks, tail_plane)
                          .astype(jnp.int32))
    det = rep.detected[:, :5]
    report = FTReport(
        detected=det,
        corrected=det if cfg.mode == "correct" else det * 0,
        max_delta=jnp.zeros((3,), jnp.float32))
    new_cache = cache._replace(k=new_k, v=new_v, kc1=kc1, kc2=kc2,
                               vc1=vc1, vc2=vc2, pos=pos + q_len,
                               bad=new_bad)
    return rep.out, report, new_cache


def paged_rollback(k, v, kc1, kc2, vc1, vc2, bt, keep_pos, old_pos, *,
                   check_stride: int, threshold: float, max_span: int):
    """Fault-tolerant KV rollback: truncate rejected speculative rows.

    The propose→score→accept step appends every scored chunk row's K/V into
    the paged block pool *before* the acceptance verdict exists (append-
    before-attend). When the target rejects a draft suffix, rows
    ``keep_pos[b] .. old_pos[b] - 1`` of request ``b`` are junk that must not
    survive: this zeroes them (``kv_len`` truncation — matching the
    zero-padded-partial-block convention of the scatter path, so pool state
    is deterministic) and *re-generates* the touched tail blocks' checksums
    over the truncated content.

    Laundering guard: re-stamping a checksum from current content over a
    block that was corrupted between the scoring step's verify and this
    rollback would make the corruption permanently undetectable. So every
    touched block is first re-verified against its **pre-rollback**
    checksums; the returned ``bad`` plane (B, table_len) flags mismatches
    and the engine must re-prefill those blocks (the restamped checksums are
    then overwritten by the repair) — detection is never lost to a rollback.

    ``k``/``v``: (L, num_blocks+1, Hkv, bs, hd) pool arrays (row 0 = null
    block); ``kc1..vc2`` their resident checksum planes; ``bt`` (B, mb)
    block tables; ``keep_pos``/``old_pos`` (B,) with ``keep_pos <= old_pos``
    and ``old_pos - keep_pos <= max_span`` (the chunk width — static, so one
    compiled program serves every acceptance outcome). Slots with
    ``keep_pos == old_pos`` are untouched. Touched blocks are private tail
    blocks (shared blocks were COW-split before the speculative append), so
    no two slots roll back the same block.

    Returns ``(k, v, kc1, kc2, vc1, vc2, bad)``.
    """
    bs = k.shape[3]
    mb = bt.shape[1]
    cs = kc1.shape[3]
    nt = (max_span + bs - 2) // bs + 1     # max blocks a rollback can touch
    j0 = keep_pos // bs
    jt = j0[:, None] + jnp.arange(nt, dtype=jnp.int32)[None, :]    # (B, nt)
    last = (jnp.maximum(old_pos, keep_pos + 1) - 1) // bs
    touched = (jt <= last[:, None]) & (old_pos > keep_pos)[:, None]
    tid = jnp.where(
        touched, jnp.take_along_axis(bt, jnp.clip(jt, 0, mb - 1), axis=1), 0)

    # -- laundering guard: verify against the PRE-rollback checksums first
    bad_k, _ = cks.verify_block(
        k[:, tid], cks.Checksums(kc1[:, tid], kc2[:, tid]), cs,
        threshold=threshold)
    bad_v, _ = cks.verify_block(
        v[:, tid], cks.Checksums(vc1[:, tid], vc2[:, tid]), cs,
        threshold=threshold)
    bad_t = jnp.any(bad_k | bad_v, axis=(0, -1)) & (tid > 0)       # (B, nt)
    b_idx = jnp.arange(bt.shape[0])[:, None]
    bad = jnp.zeros(bt.shape, jnp.int32).at[
        b_idx, jnp.clip(jt, 0, mb - 1)].max(bad_t.astype(jnp.int32))

    # -- truncate: zero exactly the rejected rows of the touched blocks
    rows_abs = jt[:, :, None] * bs + jnp.arange(bs,
                                                dtype=jnp.int32)[None, None, :]
    kill = ((rows_abs >= keep_pos[:, None, None])
            & (rows_abs < old_pos[:, None, None])
            & touched[:, :, None])                                 # (B, nt, bs)
    kmask = kill[None, :, :, None, :, None]
    kb = jnp.where(kmask, 0.0, k[:, tid]).astype(k.dtype)
    vb = jnp.where(kmask, 0.0, v[:, tid]).astype(v.dtype)
    new_k = k.at[:, tid].set(kb)
    new_v = v.at[:, tid].set(vb)

    # -- re-stamp the touched blocks' checksums over the truncated content
    ck = cks.encode_kv(kb, check_stride)
    cv = cks.encode_kv(vb, check_stride)
    return (new_k, new_v,
            kc1.at[:, tid].set(ck.c1), kc2.at[:, tid].set(ck.c2),
            vc1.at[:, tid].set(cv.c1), vc2.at[:, tid].set(cv.c2), bad)


def _split_heads(x, n_heads, head_dim):
    b, s, _ = x.shape
    return x.reshape(b, s, n_heads, head_dim).transpose(0, 2, 1, 3)


def _merge_heads(x):
    b, h, s, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, h * d)


def attn_apply(
    params,
    x: jax.Array,                    # (B, S, d_model)
    *,
    acfg: AttnCfg,
    ft: FTCfg,
    window: Optional[int] = None,    # None = global (full) attention
    positions: Optional[jax.Array] = None,  # (S,) absolute positions
    cache: Optional[KVCache] = None,
    mode: str = "train",             # "train" | "prefill" | "decode"
    kv_x: Optional[jax.Array] = None,   # cross-attention memory (B, M, d)
    cross: bool = False,
    fault=None,
    mesh=None,
) -> tuple[jax.Array, FTReport, Optional[KVCache]]:
    b, s, _ = x.shape
    hd, h, hkv = acfg.head_dim, acfg.num_heads, acfg.num_kv_heads
    cfg = efta_cfg(ft)
    cross = cross or (kv_x is not None)
    # Tensor-parallel attention: shard heads over 'model'. GQA groups are
    # hostile to GSPMD propagation (reshape H -> (Hkv, G) is non-divisible),
    # so under TP we materialize repeated KV heads (Megatron practice when
    # TP > kv_heads) and shard all of q/k/v on the padded head dim.
    tp = (mesh is not None and "model" in mesh.shape
          and mesh.shape["model"] > 1)

    def _tp_heads(t):
        if not tp:
            return t
        from repro.models.transformer import DP_AXES  # avoid cycle at import
        dp = tuple(a for a in DP_AXES if a in mesh.shape)
        spec = jax.sharding.PartitionSpec(dp if dp else None, "model",
                                          None, None)
        return jax.lax.with_sharding_constraint(
            t, jax.sharding.NamedSharding(mesh, spec))

    def _expand_kv(t):
        if not tp or t.shape[1] == h:
            return t
        g = h // t.shape[1]
        t = jnp.broadcast_to(t[:, :, None], (t.shape[0], t.shape[1], g,
                                             t.shape[2], t.shape[3]))
        return t.reshape(t.shape[0], h, t.shape[3], t.shape[4])

    def _tp_kv(t):
        # Decode: q is tiny (Sq=1) but the KV cache is huge — shard the KV
        # *head* dim over 'model' (GSPMD pads kv_heads up to the axis size)
        # instead of materializing the 7x-expanded KV. q stays replicated
        # across 'model'; the grouped einsum runs against local kv heads.
        if not tp:
            return t
        from repro.models.transformer import DP_AXES
        dp = tuple(a for a in DP_AXES if a in mesh.shape)
        spec = jax.sharding.PartitionSpec(dp if dp else None, "model",
                                          None, None)
        return jax.lax.with_sharding_constraint(
            t, jax.sharding.NamedSharding(mesh, spec))

    if positions is None:
        base = cache.pos if (cache is not None and mode == "decode") else 0
        positions = base + jnp.arange(s, dtype=jnp.int32)

    q = _tp_heads(_split_heads(matmul(x, params["wq"], ff_abft=ft.ff_abft),
                               h, hd))
    if cross:
        if cache is not None and mode == "decode":
            k, v = cache.ck, cache.cv
        else:
            k = _split_heads(matmul(kv_x, params["wk"], ff_abft=ft.ff_abft), hkv, hd)
            v = _split_heads(matmul(kv_x, params["wv"], ff_abft=ft.ff_abft), hkv, hd)
        if acfg.pos == "rope":
            q = rope(q.transpose(0, 2, 1, 3), positions,
                     acfg.rope_theta).transpose(0, 2, 1, 3)
        out, rep = attention_op(
            q, _tp_heads(_expand_kv(k)), _tp_heads(_expand_kv(v)),
            impl=ft.attn_impl, cfg=cfg, causal=False,
            sm_scale=acfg.softmax_scale, fault=fault)
        new_cache = None
        if cache is not None and mode == "prefill":
            new_cache = cache._replace(ck=k, cv=v)
        y = matmul(_merge_heads(out), params["wo"], ff_abft=ft.ff_abft)
        return y, rep, new_cache

    k = _split_heads(matmul(x, params["wk"], ff_abft=ft.ff_abft), hkv, hd)
    v = _split_heads(matmul(x, params["wv"], ff_abft=ft.ff_abft), hkv, hd)
    if acfg.pos == "rope":
        q = rope(q.transpose(0, 2, 1, 3), positions,
                 acfg.rope_theta).transpose(0, 2, 1, 3)
        k = rope(k.transpose(0, 2, 1, 3), positions,
                 acfg.rope_theta).transpose(0, 2, 1, 3)

    if isinstance(cache, PagedKVCache):
        # Fused paged backend: unified natively batched ragged step straight
        # off the block tables (``positions`` is (B, S) here — per-request;
        # S is the chunk width, with ``cache.q_len`` valid rows per slot).
        if mode != "decode":
            raise NotImplementedError(
                "PagedKVCache attention is the unified batched decode/"
                "extend step; training prefill has no paged cache")
        out, rep, new_cache = _paged_chunk(
            q, k, v, cache, cfg=cfg, window=window,
            sm_scale=acfg.softmax_scale, fault=fault)
        y = matmul(_merge_heads(out), params["wo"], ff_abft=ft.ff_abft)
        return y, rep, new_cache

    new_cache = None
    if cache is None:
        # Training / encoding: self-attention over the full sequence.
        out, rep = attention_op(
            q, _tp_heads(_expand_kv(k)), _tp_heads(_expand_kv(v)),
            impl=ft.attn_impl, cfg=cfg, causal=acfg.causal,
            window=window, sm_scale=acfg.softmax_scale, fault=fault)
    else:
        cache_len = cache.k.shape[2]
        slots = positions % cache_len
        ck = cache.k.at[:, :, slots, :].set(k.astype(cache.k.dtype))
        cv = cache.v.at[:, :, slots, :].set(v.astype(cache.v.dtype))
        new_pos = positions[-1] + 1
        new_cache = cache._replace(k=ck, v=cv, pos=new_pos)
        if mode == "prefill":
            # Attend within the prompt itself (fresh cache).
            out, rep = attention_op(
                q, _tp_heads(_expand_kv(k)), _tp_heads(_expand_kv(v)),
                impl=ft.attn_impl, cfg=cfg, causal=acfg.causal,
                window=window, sm_scale=acfg.softmax_scale, fault=fault)
        else:
            # Decode: attend over the valid region of the (ring) cache.
            # Each slot's absolute position is reconstructed so causal and
            # sliding-window masks apply exactly even after wraparound.
            slot_idx = jnp.arange(cache_len, dtype=jnp.int32)
            last_written = new_pos - 1 - ((new_pos - 1 - slot_idx) % cache_len)
            kv_positions = jnp.where(last_written >= 0, last_written, -1)
            out, rep = attention_op(
                q, _tp_kv(ck), _tp_kv(cv),
                impl="efta" if ft.attn_impl == "efta_pallas"
                else ft.attn_impl,
                cfg=cfg, causal=True, window=window,
                q_offset=positions[0], kv_positions=kv_positions,
                sm_scale=acfg.softmax_scale, fault=fault)
    y = matmul(_merge_heads(out), params["wo"], ff_abft=ft.ff_abft)
    return y, rep, new_cache
