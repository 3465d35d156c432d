"""Fused block-table EFTA paged-attention kernel (unified multi-token path).

The paged serve engine's PR-2 decode gathered each request's block table into
a contiguous KV view *outside* the kernel, then vmapped the pure-JAX EFTA
path over slots — one extra HBM round-trip for every byte of KV, plus a
separate full-pool checksum pass. This kernel removes both:

  * **Block tables are consumed directly by BlockSpec index maps**: the grid
    is ``(batch, kv_heads, table_len)`` and the K/V (and checksum) tiles for
    step ``(b, h, j)`` are fetched from pool row ``block_table[b, j]`` via
    scalar-prefetch index maps — the contiguous view is never materialized.
  * **Native batched ragged decode**: the batch axis is a grid dimension, so
    one kernel launch decodes every slot; each request masks its own
    ``kv_len`` (valid-token count from its block table) and blocks past the
    valid prefix are skipped entirely.
  * **Read-time block verification rides the streaming loop**: the resident
    block checksums (``repro.core.checksum.encode_kv``, written at append /
    scatter time) stream through the same index map as the data, and the
    fold is recomputed and compared *in the pass that consumes the block* —
    site 6 (``kv``) of the report tile, plus a per-(request, table-slot)
    ``bad`` plane the engine's repair path consumes. A resident HBM bit flip
    therefore costs zero extra memory traffic to detect.

Since PR 4 the q block is **multi-token**: each request brings a chunk of up
to ``C`` query rows (``q`` of shape ``(B, H, C, D)``) with a per-request
valid-row count ``q_lens``, so *one* compiled program covers single-token
decode (``C = 1`` or ``q_len = 1``), chunked prefill, prefix-extend, and
block repair — the unified end-to-end protected kernel the paper argues for,
replacing the per-bucket prefill programs of the gather path. Chunk row
``c`` sits at absolute position ``kv_len - q_len + c``; masking is causal
within the chunk, sliding-window, and ragged per request, all per *row*.
Rows past ``q_len`` are padding: fully masked, they emit zero output and
cannot trip any verification (every check compares self-consistent computed
values). A chunk may straddle block edges; the KV rows the chunk itself
appends are scattered (and their block checksums regenerated) by the caller
*before* the launch (``repro.models.attention._paged_chunk``), so the
streaming verify covers the chunk's own blocks too.

GQA is handled by folding the query-head group — and now the chunk axis —
into the GEMM rows: the score tile for one (request, kv-head) step is
``(group * C, block_size)``, so MQA/GQA ratios and chunk widths change tile
shapes, not code paths. The EFTA scheme itself (tensor-checksum ABFT on
GEMM I, checksum-reuse EXP verify, shadow rowmax, SNVR + shadow rowsum,
unified output verification — paper Algorithm 1) is inherited unchanged from
``repro.kernels.efta_attention``; this kernel reuses its fold and correction
helpers so the two stay in lockstep.

Fault descriptor (int32[8]): [site, table_block j, batch b, kv-head h,
tile-row (group_row * C + chunk_row), col, bit, enabled] — one SEU per step,
matching the paper's single-event model. ``Site.KV`` faults are *not*
injected here: they strike the resident pool between steps
(``PagedServeEngine.inject_kv_fault``) and this kernel's job is to catch
them.

Validated in interpret mode on CPU. Compiles through Mosaic for a TPU v5e
in bf16 at head_dim 64, block 16, chunk 1 and 32, and with 4 query heads per
KV head (``tests/test_tpu_compile.py``, against a described chip), and
serves gpt2 at full width on one v5e chip (``chip_smoke.py``). The report
tile is one (8, 128k) int32 tile per (request, kv-head): row 0 holds the six
detection counters, row 1 the per-table-slot bad plane.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import checksum as cks
from repro.core.efta import EFTAConfig, MASK_VALUE
from repro.core.fault import Site
from repro.kernels.efta_attention import (REPORT_TILE, _correct_strided,
                                          _flip, _fold_prod, _fold_slices,
                                          _report_tile, _shadow_rowmax,
                                          _shadow_rowsum, resolve_interpret)

# fault descriptor layout (int32[8]):
# [site, table_block, batch, kv_head, tile_row, col, bit, enabled]
P_SITE, P_BLOCK, P_B, P_H, P_ROW, P_COL, P_BIT, P_ON = range(8)

NO_WINDOW = 1 << 30     # "global attention" sentinel for the window scalar


class PagedReport(NamedTuple):
    """Per-request outcome of one fused paged-attention call."""

    out: jax.Array        # (B, H, head_dim) or (B, H, C, head_dim) output
    detected: jax.Array   # (B, 6) int32 — [gemm1, exp, rowmax, rowsum,
    #                       gemm2, kv] per request, summed over kv heads
    bad_blocks: jax.Array  # (B, table_len) bool — resident-checksum
    #                        mismatches, addressed by table slot (not pool id)


def _hit(fault_ref, site, *, b, h, j):
    return ((fault_ref[P_ON] == 1)
            & (fault_ref[P_SITE] == int(site))
            & (fault_ref[P_B] == b)
            & (fault_ref[P_H] == h)
            & (fault_ref[P_BLOCK] == j))


def _paged_kernel(
    # scalar prefetch
    fault_ref, bt_ref, kvlen_ref, qlen_ref, win_ref,
    # inputs
    q_ref, k_ref, v_ref, kc1_ref, kc2_ref, vc1_ref, vc2_ref,
    # outputs
    o_ref, rep_ref,
    # scratch
    m_scr, l_scr, lsh_scr, r_scr, acc_scr, oc1_scr, oc2_scr, det_scr,
    vmax_scr,
    *,
    sm_scale: float,
    block_size: int,
    n_blocks: int,
    chunk: int,
    s_kv: int,
    s_out: int,
    kv_thr: float,
    mode: str,
    unified: bool,
    shadow_rowsum: bool,
    shadow_rowmax: bool,
    eps1: float,
    eps2: float,
    eps3: float,
):
    b = pl.program_id(0)
    h = pl.program_id(1)
    j = pl.program_id(2)
    ft = mode != "off"
    correct = mode == "correct"
    bs = block_size
    g_kv = bs // s_kv

    kv_len = kvlen_ref[b]       # valid tokens incl. the chunk's rows (traced)
    q_len = qlen_ref[b]         # valid chunk rows for this request (traced)
    window = win_ref[0]
    base = kv_len - q_len       # absolute position of chunk row 0

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, MASK_VALUE)
        l_scr[...] = jnp.zeros_like(l_scr)
        lsh_scr[...] = jnp.zeros_like(lsh_scr)
        r_scr[...] = jnp.zeros_like(r_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        oc1_scr[...] = jnp.zeros_like(oc1_scr)
        oc2_scr[...] = jnp.zeros_like(oc2_scr)
        for i in range(6):
            det_scr[i] = 0
        vmax_scr[0] = 0.0
        rep_ref[...] = jnp.zeros_like(rep_ref)

    # Ragged / causal skip: blocks entirely past every chunk row's valid
    # prefix (or entirely outside every row's sliding window — the earliest
    # row ``base`` has the lowest window floor) contribute nothing — no MXU
    # work, no checksum folds. Null-padded table entries point at pool row 0
    # and always land here or under the verify's ``real`` gate.
    kv_start = j * bs
    run = (kv_start < kv_len) & (base - (kv_start + bs - 1) < window)

    @pl.when(run)
    def _body():
        q = q_ref[...]                  # (grp * C, D), rows group-major
        k = k_ref[...]                  # (bs, D)
        v = v_ref[...]                  # (bs, D)
        real = bt_ref[b, j] > 0

        if ft:
            # ---- site 6 (kv): resident block verify, in the streaming ----
            # pass that consumes the block. Fold definition and threshold
            # semantics are shared with the gather path via core.checksum,
            # so both backends flag exactly the same corruptions.
            cs = kc1_ref.shape[0]
            fk = cks.encode_kv_tile(k, cs)
            fv = cks.encode_kv_tile(v, cs)
            bad_k = cks.block_fold_bad(
                fk, cks.Checksums(kc1_ref[...], kc2_ref[...]), threshold=kv_thr)
            bad_v = cks.block_fold_bad(
                fv, cks.Checksums(vc1_ref[...], vc2_ref[...]), threshold=kv_thr)
            flag = (bad_k | bad_v) & real
            det_scr[5] += flag.astype(jnp.int32)
            # row 1 of the report tile is the bad plane: lane j = table slot
            hit = ((jax.lax.broadcasted_iota(jnp.int32, rep_ref.shape, 0) == 1)
                   & (jax.lax.broadcasted_iota(jnp.int32, rep_ref.shape, 1)
                      == j) & flag)
            rep_ref[...] = jnp.where(hit, 1, rep_ref[...])

            # running max|V|: the convex-combination bound for finalize NVR
            vmax_scr[0] = jnp.maximum(
                vmax_scr[0], jnp.max(jnp.abs(v.astype(jnp.float32))))

        # ---- GEMM I on the MXU (f32 accumulate) + tensor-checksum ABFT ----
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # (grp * C, bs)
        s = _flip(s, on=_hit(fault_ref, Site.GEMM1, b=b, h=h, j=j),
                  row=fault_ref[P_ROW], col=fault_ref[P_COL],
                  bit=fault_ref[P_BIT])
        if ft:
            # NVR range restriction (see efta_attention): keeps the weighted
            # fold finite under exponent-bit corruptions.
            s = jnp.where(jnp.isfinite(s), jnp.clip(s, -1e6, 1e6), 0.0)

        if ft:
            # CCG: tensor checksums of K (same strided row fold as the
            # resident verify above, at the ABFT stride), then skinny GEMMs
            kc1, kc2 = cks.encode_kv_tile(k, s_kv)
            sc1 = jax.lax.dot_general(
                q.astype(jnp.float32), kc1, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            sc2 = jax.lax.dot_general(
                q.astype(jnp.float32), kc2, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            sc2 = sc2 * sm_scale
            sum1 = _fold_slices(s, s_kv, weighted=False)
            sum2 = _fold_slices(s, s_kv, weighted=True)
            d1 = sc1 - sum1
            d2 = sc2 - sum2
            bad = jnp.abs(d1) > eps1
            det_scr[0] += bad.sum(dtype=jnp.int32)
            if correct:
                s = _correct_strided(s, d1, d2, bad, s_kv)

        # ---- per-row causal + window + ragged mask, running max ----------
        # Tile rows are group-major: row r holds (group g = r // C, chunk
        # row c = r % C); chunk row c queries absolute position base + c.
        crow = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) % chunk
        qpos = base + crow
        cols = kv_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = (cols <= qpos) & (qpos - cols < window) & (crow < q_len)
        s_m = jnp.where(mask, s, MASK_VALUE)
        blockmax = jnp.max(s_m, axis=1, keepdims=True)      # (grp * C, 1)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, blockmax)
        m_new = _flip(m_new, on=_hit(fault_ref, Site.ROWMAX, b=b, h=h, j=j),
                      row=fault_ref[P_ROW], col=jnp.int32(0),
                      bit=fault_ref[P_BIT])
        if ft and shadow_rowmax:
            m_chk = jnp.maximum(m_prev, _shadow_rowmax(s_m, s_kv))
            bad_m = m_new != m_chk
            det_scr[2] += bad_m.sum(dtype=jnp.int32)
            if correct:
                m_new = jnp.where(bad_m, m_chk, m_new)
        m_scr[...] = m_new
        alive = m_new > MASK_VALUE / 2
        m_sub = jnp.where(alive, m_new, 0.0)

        # ---- EXP with checksum reuse (paper Case 2) ----------------------
        cap = 80.0 / g_kv
        p_raw = jnp.exp(jnp.minimum(s - m_sub, cap))
        p_raw = _flip(p_raw, on=_hit(fault_ref, Site.EXP, b=b, h=h, j=j),
                      row=fault_ref[P_ROW], col=fault_ref[P_COL],
                      bit=fault_ref[P_BIT])
        if ft:
            pc1 = jnp.exp(jnp.minimum(sc1 - g_kv * m_sub, cap * g_kv))
            prod = _fold_prod(p_raw, s_kv)
            ref = jnp.maximum(jnp.abs(pc1), 1e-20)
            bad_e = jnp.abs(prod - pc1) > eps2 * ref + 1e-20
            capped = (s - m_sub) > (cap - 1e-3)
            col_ok = jnp.ones((s.shape[0], s_kv), dtype=bool)
            for l in range(g_kv):
                col_ok &= ~capped[:, l * s_kv:(l + 1) * s_kv]
            bad_e &= col_ok
            det_scr[1] += bad_e.sum(dtype=jnp.int32)
            if correct:
                recomputed = jnp.exp(jnp.minimum(s - m_sub, cap))
                p_raw = jnp.concatenate(
                    [jnp.where(bad_e, recomputed[:, l * s_kv:(l + 1) * s_kv],
                               p_raw[:, l * s_kv:(l + 1) * s_kv])
                     for l in range(g_kv)], axis=-1)
        if ft and shadow_rowmax and correct:
            # exact recompute backstop (see efta_attention)
            recheck = jnp.exp(jnp.minimum(s - m_sub, cap))
            slipped = p_raw != recheck
            det_scr[1] += slipped.sum(dtype=jnp.int32)
            p_raw = jnp.where(slipped, recheck, p_raw)
        p = jnp.where(mask, p_raw, 0.0)

        # ---- rescale + rowsum (+ shadow) ---------------------------------
        alpha = jnp.where(alive, jnp.exp(m_prev - m_new), 1.0)
        l_new = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        l_new = _flip(l_new, on=_hit(fault_ref, Site.ROWSUM, b=b, h=h, j=j),
                      row=fault_ref[P_ROW], col=jnp.int32(0),
                      bit=fault_ref[P_BIT])
        l_scr[...] = l_new
        if ft and shadow_rowsum:
            lsh_scr[...] = alpha * lsh_scr[...] + _shadow_rowsum(p, s_kv)
        blk_alive = blockmax > MASK_VALUE / 2
        r_scr[...] = alpha * r_scr[...] + jnp.where(
            blk_alive, jnp.exp(blockmax - m_sub), 0.0)

        # ---- GEMM II + rescale, checksums carried ------------------------
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # (grp * C, D)
        acc_new = alpha * acc_scr[...] + pv
        acc_new = _flip(acc_new, on=_hit(fault_ref, Site.GEMM2, b=b, h=h, j=j),
                        row=fault_ref[P_ROW], col=fault_ref[P_COL],
                        bit=fault_ref[P_BIT])
        acc_scr[...] = acc_new
        if ft:
            g2 = v.shape[-1] // s_out
            vcs1 = jnp.zeros((v.shape[0], s_out), jnp.float32)
            vcs2 = jnp.zeros((v.shape[0], s_out), jnp.float32)
            for l in range(g2):
                seg = v[:, l * s_out:(l + 1) * s_out].astype(jnp.float32)
                vcs1 = vcs1 + seg
                vcs2 = vcs2 + float(l + 1) * seg
            pf = p.astype(jnp.float32)
            oc1_scr[...] = alpha * oc1_scr[...] + jax.lax.dot_general(
                pf, vcs1, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            oc2_scr[...] = alpha * oc2_scr[...] + jax.lax.dot_general(
                pf, vcs2, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            if not unified:
                s1 = _fold_slices(acc_scr[...], s_out, weighted=False)
                d1o = oc1_scr[...] - s1
                det_scr[4] += (jnp.abs(d1o) > eps3).sum(dtype=jnp.int32)

    # ---- finalize: SNVR on ℓ + unified output verification ----------------
    @pl.when(j == n_blocks - 1)
    def _finalize():
        l_f = l_scr[...]
        r_f = r_scr[...]
        if ft:
            # per-row SNVR bound: chunk row c attends at most qpos + 1 keys
            # (window-limited rows only tighten further; kv_len caps all)
            crow = jax.lax.broadcasted_iota(jnp.int32, l_f.shape, 0) % chunk
            upper = jnp.minimum(base + crow + 1, kv_len).astype(
                jnp.float32) + 1e-3
            in_range = (l_f >= r_f - 1e-3) & (l_f <= upper) & jnp.isfinite(l_f)
            if shadow_rowsum:
                lsh = lsh_scr[...]
                mism = jnp.abs(l_f - lsh) > 1e-5 * jnp.maximum(jnp.abs(lsh),
                                                               1e-6)
                bad_l = ((~in_range) | mism) & (r_f > 0)
                fb_ok = (lsh >= r_f - 1e-3) & (lsh <= upper) & jnp.isfinite(lsh)
                fallback = jnp.where(fb_ok, lsh, r_f)
            else:
                bad_l = (~in_range) & (r_f > 0)
                fallback = r_f
            det_scr[3] += bad_l.sum(dtype=jnp.int32)
            if correct:
                l_f = jnp.where(bad_l, fallback, l_f)
        l_safe = jnp.where(l_f == 0.0, 1.0, l_f)
        o = acc_scr[...] / l_safe
        if ft:
            if correct:
                bound = vmax_scr[0] * 1.001 + 1e-6
                o = jnp.where(jnp.isfinite(o) & (jnp.abs(o) <= bound),
                              o, 0.0)
            oc1 = oc1_scr[...] / l_safe
            oc2 = oc2_scr[...] / l_safe
            s1 = _fold_slices(o, s_out, weighted=False)
            s2 = _fold_slices(o, s_out, weighted=True)
            d1 = oc1 - s1
            d2 = oc2 - s2
            bad = ~(jnp.abs(d1) <= eps3)
            det_scr[4] += bad.sum(dtype=jnp.int32)
            if correct:
                o = _correct_strided(o, d1, d2, bad, s_out)
        o_ref[...] = o.astype(o_ref.dtype)
        rep_ref[...] = _report_tile(rep_ref[...],
                                    [det_scr[i] for i in range(6)])


def efta_paged_attention_pallas(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    k_checks: cks.Checksums,
    v_checks: cks.Checksums,
    block_tables: jax.Array,
    kv_lens: jax.Array,
    q_lens: Optional[jax.Array] = None,
    *,
    cfg: EFTAConfig,
    check_threshold: Optional[float] = None,
    window=None,
    sm_scale: Optional[float] = None,
    fault: Optional[jax.Array] = None,
    interpret: Optional[bool] = None,
) -> PagedReport:
    """Fused batched ragged paged attention with in-loop verification.

    ``q``: (B, H, D) — single decode token per request — or (B, H, C, D) —
    a multi-token chunk per request (unified prefill / extend / repair /
    decode). ``k_pool``/``v_pool``: (num_blocks + 1, Hkv, block_size, D)
    paged pools (row 0 is the null block). ``k_checks``/``v_checks``: the
    resident :func:`repro.core.checksum.encode_kv` pairs, (num_blocks + 1,
    Hkv, check_stride, D). ``block_tables``: (B, table_len) int32,
    null-padded with 0. ``kv_lens``: (B,) int32 valid tokens per request
    *including* the chunk's rows (their K/V must already sit in the pool —
    append before attend, exactly like the gather path's in-step scatter).
    ``q_lens``: (B,) int32 valid rows of each request's chunk (default: all
    C); chunk row ``c < q_len`` queries position ``kv_len - q_len + c``,
    rows past ``q_len`` are fully-masked padding and a request with
    ``q_len == 0`` contributes nothing (its resident blocks are still
    streamed and verified).

    ``window``: optional sliding-window size — python int or traced int32
    scalar (per-layer global/local selection). ``fault``: optional int32[8]
    descriptor (see module docstring). ``interpret`` as in
    :func:`repro.kernels.efta_attention.efta_attention_pallas`. Returns a
    :class:`PagedReport` whose ``out`` matches ``q``'s shape.
    """
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, :, None, :]
    b, h, chunk, d = q.shape
    nb1, hkv, bs, hd = k_pool.shape
    if hd != d:
        raise ValueError(f"head_dim mismatch: q {d} vs pool {hd}")
    grp = h // hkv
    mb = block_tables.shape[-1]
    cs = k_checks.c1.shape[-2]
    scale = sm_scale if sm_scale is not None else 1.0 / (d ** 0.5)
    s_kv = cfg.kv_stride(bs)
    s_out = cfg.out_stride(d)
    eps1, eps2, eps3 = cfg.thresholds(q.dtype)
    kv_thr = (check_threshold if check_threshold is not None
              else cks.kv_block_threshold(k_pool.dtype))

    # fold GQA group AND chunk into the GEMM rows, group-major: row
    # r = g * C + c so every per-row helper stays a plain lane-wise op
    qr = q.reshape(b, hkv, grp * chunk, d)
    if fault is None:
        fault = jnp.zeros((8,), jnp.int32)
    if q_lens is None:
        q_lens = jnp.full((b,), chunk, jnp.int32)
    win = (jnp.full((1,), NO_WINDOW, jnp.int32) if window is None
           else jnp.asarray(window, jnp.int32).reshape(1))

    kernel = functools.partial(
        _paged_kernel,
        sm_scale=scale, block_size=bs, n_blocks=mb, chunk=chunk, s_kv=s_kv,
        s_out=s_out, kv_thr=kv_thr, mode=cfg.mode, unified=cfg.unified,
        shadow_rowsum=cfg.shadow_rowsum, shadow_rowmax=cfg.shadow_rowmax,
        eps1=eps1, eps2=eps2, eps3=eps3)

    def pool_map(bi, hi, j, fault, bt, kvlen, qlen, win):
        return (bt[bi, j], hi, 0, 0)

    rows = grp * chunk
    # report tile: row 0 = the six detection counters, row 1 = the bad plane
    # (lane j = table slot j), padded to whole (8, 128) tiles
    rep_tile = (REPORT_TILE[0], -(-mb // REPORT_TILE[1]) * REPORT_TILE[1])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(b, hkv, mb),
        in_specs=[
            pl.BlockSpec((None, None, rows, d),
                         lambda bi, hi, j, *_: (bi, hi, 0, 0)),
            pl.BlockSpec((None, None, bs, d), pool_map),
            pl.BlockSpec((None, None, bs, d), pool_map),
            pl.BlockSpec((None, None, cs, d), pool_map),
            pl.BlockSpec((None, None, cs, d), pool_map),
            pl.BlockSpec((None, None, cs, d), pool_map),
            pl.BlockSpec((None, None, cs, d), pool_map),
        ],
        out_specs=[
            pl.BlockSpec((None, None, rows, d),
                         lambda bi, hi, j, *_: (bi, hi, 0, 0)),
            pl.BlockSpec((None, None) + rep_tile,
                         lambda bi, hi, j, *_: (bi, hi, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),   # m
            pltpu.VMEM((rows, 1), jnp.float32),   # l
            pltpu.VMEM((rows, 1), jnp.float32),   # l shadow
            pltpu.VMEM((rows, 1), jnp.float32),   # r (SNVR bound)
            pltpu.VMEM((rows, d), jnp.float32),   # output accumulator
            pltpu.VMEM((rows, s_out), jnp.float32),  # O checksum 1
            pltpu.VMEM((rows, s_out), jnp.float32),  # O checksum 2
            pltpu.SMEM((6,), jnp.int32),          # detection counters
            pltpu.SMEM((1,), jnp.float32),        # running max|V| (NVR)
        ],
    )

    out, rep = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, rows, d), q.dtype),
            jax.ShapeDtypeStruct((b, hkv) + rep_tile, jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
        name="efta_paged",
    )(fault, jnp.asarray(block_tables, jnp.int32),
      jnp.asarray(kv_lens, jnp.int32), jnp.asarray(q_lens, jnp.int32), win,
      qr, k_pool, v_pool, k_checks.c1, k_checks.c2, v_checks.c1, v_checks.c2)

    out = out.reshape(b, h, chunk, d)
    return PagedReport(
        out=out[:, :, 0, :] if squeeze else out,
        detected=rep[:, :, 0, :6].sum(axis=1),
        bad_blocks=jnp.any(rep[:, :, 1, :mb] > 0, axis=1))


def paged_fault_descriptor(spec, grp: int,
                           chunk: int = 1) -> Tuple[jax.Array, jax.Array]:
    """Translate the serve engine's per-slot :class:`FaultSpec` batch into
    the fused kernel's int32[8] descriptor.

    ``spec`` fields are (n_slots, n_faults); the single-event-upset model
    means at most one entry is enabled per step, so the first enabled entry
    wins. The vmapped gather path addresses the score tile as (head, row);
    the fused kernel's tile rows fold the GQA group and the chunk axis, so
    the query-head coordinate splits into (kv_head = head // grp, tile row
    = (head % grp) * chunk) — the SEU strikes chunk row 0, which is a valid
    row for every request that fed at least one token this step.
    """
    site = spec.site.reshape(-1)
    nf = spec.site.shape[-1]
    enabled = site >= 0
    idx = jnp.argmax(enabled)
    on = jnp.any(enabled).astype(jnp.int32)

    def take(a):
        return a.reshape(-1)[idx]

    head = take(spec.head)
    return jnp.stack([
        take(spec.site), take(spec.block), (idx // nf).astype(jnp.int32),
        head // grp, (head % grp) * chunk, take(spec.col), take(spec.bit), on,
    ]).astype(jnp.int32)
