"""Fused block-table EFTA paged-attention kernel (unified multi-token path).

The paged serve engine's PR-2 decode gathered each request's block table into
a contiguous KV view *outside* the kernel, then vmapped the pure-JAX EFTA
path over slots — one extra HBM round-trip for every byte of KV, plus a
separate full-pool checksum pass. This kernel removes both:

  * **Block tables are consumed directly by index maps**: the grid is
    ``(batch, table_len // P)`` and grid step ``(b, i)`` is one *compute
    block* of ``P`` consecutive table slots of request ``b``, for every KV
    head. Slot ``p`` of the block is a pipelined operand of its own whose
    scalar-prefetch index map names pool row ``block_table[b, i*P + p]``;
    a pool row holds all KV heads of a page, so each page is copied once,
    in one DMA, and the contiguous view is never materialized.
  * **Double-buffered across the whole grid**: the pipeline copies the next
    grid step's pages while this one computes, across requests too.
  * **Native batched ragged decode**: the batch axis is a grid dimension, so
    one kernel launch decodes every slot; each request masks its own
    ``kv_len``. A table slot is *live* when some chunk row can see it (below
    ``kv_len``, inside the earliest row's sliding window). A slot that is
    not live keeps naming a live page of the same slot, which the pipeline
    then does not copy again: only live pages are copied, and compute
    blocks with no live page skip the body.
  * **Read-time block verification rides the streaming loop**: the resident
    block checksums (``repro.core.checksum.encode_kv``, written at append /
    scatter time) are copied with the data, and each page's fold is
    recomputed and compared *in the pass that consumes the page* — site 6
    (``kv``) of the report tile, plus a per-(request, table-slot) ``bad``
    plane the engine's repair path consumes. A resident HBM bit flip
    therefore costs zero extra memory traffic to detect.

**Choosing P.** ``P = 128 // block_size`` pages (one for blocks of 128 keys
or more), capped at the table length: at block 16 a compute block is 128
keys, so the score tile fills the 128 lanes, and one grid step does the
work of ``8 * kv_heads`` steps of a one-page, one-head grid. A table whose
length ``P`` does not divide is padded with null slots, which are never
live. ``P`` comes from the shapes alone, so every program (chunk widths,
dtypes, GQA, repair, draft) runs this code.

**Protection stays per page.** The score tile of a compute block is
``(group * C, P * block_size)``, but every check keeps the granularity of
the one-page schedule: the resident verify folds each page on its own, and
the GEMM I tensor checksums, EXP verify and shadow rowsum fold each page's
``block_size`` keys at stride ``s_kv``, and are counted only on live pages.
The folds, the correction and V's column checksums are the contiguous
kernel's own helpers (``repro.kernels.efta_attention``), for which its KV
tile is one page: they shift the tile onto itself (``pltpu.roll``), so a
compute block of several pages stays lane-dense. Only the grouping of the
online-softmax reductions changes.

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

GQA is handled by folding the query-head group — and the chunk axis — into
the GEMM rows, so MQA/GQA ratios and chunk widths change tile shapes, not
code paths. The EFTA scheme itself (tensor-checksum ABFT on GEMM I,
checksum-reuse EXP verify, shadow rowmax, SNVR + shadow rowsum, unified
output verification — paper Algorithm 1) is inherited unchanged from
``repro.kernels.efta_attention``; this kernel calls its flip, fold,
correction, shadow and report helpers, so the two stay in lockstep.

Fault descriptor (int32[8]): [site, table_block j, batch b, kv-head h,
tile-row (group_row * C + chunk_row), col, bit, enabled] — one SEU per step,
matching the paper's single-event model. Element sites strike column
``(j - i*P) * block_size + col`` of the compute block holding slot ``j``;
a strike on a slot that is not live does nothing. ``Site.KV`` faults are
*not* injected here: they strike the resident pool between steps
(``PagedServeEngine.inject_kv_fault``) and this kernel's job is to catch
them.

Validated in interpret mode on CPU. Compiles through Mosaic for a TPU v5e
in bf16 and f32 at head_dim 64, block 16, chunk 1 and 32, and with 4 query
heads per KV head (``tests/test_tpu_compile.py``, against a described
chip), and serves gpt2 at full width on one v5e chip (``chip_smoke.py``).
The report tile is one (8, 128k) int32 tile per (request, kv-head): row 0
holds the six detection counters, row 1 the per-table-slot bad plane.
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
from repro.kernels.efta_attention import (
    REPORT_TILE, _col_checksums, _correct, _flip, _fold, _fold_index,
    _report_tile, _shadow_rowmax, _shadow_rowsum, _spread, _verify_output,
    resolve_interpret)

# fault descriptor layout (int32[8]):
# [site, table_block, batch, kv_head, tile_row, col, bit, enabled]
P_SITE, P_BLOCK, P_B, P_H, P_ROW, P_COL, P_BIT, P_ON = range(8)

NO_WINDOW = 1 << 30     # "global attention" sentinel for the window scalar
LANES = 128             # keys per compute block the score tile is sized for


class PagedReport(NamedTuple):
    """Per-request outcome of one fused paged-attention call."""

    out: jax.Array        # (B, H, head_dim) or (B, H, C, head_dim) output
    detected: jax.Array   # (B, 6) int32 — [gemm1, exp, rowmax, rowsum,
    #                       gemm2, kv] per request, summed over kv heads
    bad_blocks: jax.Array  # (B, table_len) bool — resident-checksum
    #                        mismatches, addressed by table slot (not pool id)


def pages_per_block(block_size: int, table_len: int) -> int:
    """Table slots one grid step streams: enough pages to fill the 128
    lanes of the score tile, at most the whole table."""
    return max(1, min(LANES // block_size, table_len))


class _Offset:
    """Indices ``base + c`` of a flat SMEM ref, which takes no unaligned
    views."""

    def __init__(self, ref, base):
        self.ref, self.base = ref, base

    def __getitem__(self, c):
        return self.ref[self.base + c]

    def __setitem__(self, c, x):
        self.ref[self.base + c] = x


def _paged_kernel(
    # scalar prefetch
    fault_ref, src_ref, kvlen_ref, qlen_ref, win_ref, lo_ref, hi_ref,
    # inputs: q, then P page blocks each of K, V, K c1, K c2, V c1, V c2
    q_ref, *refs,
    sm_scale: float,
    block_size: int,
    pages: int,
    n_blocks: int,
    kv_heads: int,
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
    i = pl.program_id(1)
    ft = mode != "off"
    correct = mode == "correct"
    bs = block_size
    g_kv = bs // s_kv
    j0 = i * pages                # first table slot of this compute block

    kv_len = kvlen_ref[b]       # valid tokens incl. the chunk's rows (traced)
    q_len = qlen_ref[b]         # valid chunk rows for this request (traced)
    window = win_ref[0]
    base = kv_len - q_len       # absolute position of chunk row 0
    # live table slots of request b: [lo, hi) — below kv_len and inside the
    # earliest row's window (empty as [0, 0))
    lo, hi = lo_ref[b], hi_ref[b]

    def live(j):
        return (j >= lo) & (j < hi)

    # Every block, output and piece of running state holds all KV heads, so
    # one grid step serves them all and its fixed cost is paid once. The
    # SMEM counters are flat: head h's six at h * 8.
    page_refs = [refs[x * pages:(x + 1) * pages] for x in range(6)]
    state = (q_ref,) + refs[6 * pages:-2]
    det_all, vmax_all = refs[-2:]

    def views(h):
        """Head h's view of the page blocks and of the per-head state."""
        return ([[r.at[h] for r in group] for group in page_refs],
                [r.at[h] for r in state],
                _Offset(det_all, h * 8), _Offset(vmax_all, h))

    @pl.when(i == 0)
    def _init():
        (_, _, rep_ref, m_scr, l_scr, lsh_scr, r_scr, acc_scr, oc1_scr,
         oc2_scr) = state
        m_scr[...] = jnp.full_like(m_scr, MASK_VALUE)
        for ref in (l_scr, lsh_scr, r_scr, acc_scr, oc1_scr, oc2_scr,
                    rep_ref):
            ref[...] = jnp.zeros_like(ref)
        for c in range(det_all.shape[0]):
            det_all[c] = 0
        for c in range(vmax_all.shape[0]):
            vmax_all[c] = 0.0

    def stream(h, carry):
        """Head h's pass over this compute block."""
        ((k_refs, v_refs, kc1_refs, kc2_refs, vc1_refs, vc2_refs),
         (q_h, o_ref, rep_ref, m_scr, l_scr, lsh_scr, r_scr, acc_scr, oc1_scr,
          oc2_scr), det_scr, vmax_scr) = views(h)
        w = pages * bs
        q = q_h[...]                  # (grp * C, D), rows group-major
        k = jnp.concatenate([r[...] for r in k_refs], axis=0)  # (P * bs, D)
        # a slot that is not live holds some other page: zero its V rows so
        # the masked (zero) probabilities cannot meet a NaN in GEMM II
        row_page = jax.lax.broadcasted_iota(jnp.int32, (w, 1), 0) // bs
        v = jnp.where(live(j0 + row_page),
                      jnp.concatenate([r[...] for r in v_refs], axis=0), 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (q.shape[0], w), 1)
        page_live = live(j0 + lane // bs)
        # the fold indices of live pages: where the per-page checks read
        fold_idx = page_live & _fold_index(lane.shape, s_kv, bs)

        # fault strikes this compute block's copy of a live slot
        fj = fault_ref[P_BLOCK]
        struck = ((fault_ref[P_ON] == 1) & (fault_ref[P_B] == b)
                  & (fault_ref[P_H] == h) & (fj >= j0) & (fj < j0 + pages)
                  & live(fj))
        fcol = (fj - j0) * bs + fault_ref[P_COL]

        def hit(site):
            return struck & (fault_ref[P_SITE] == int(site))

        if ft:
            # ---- site 6 (kv): resident verify of each page, in the ----
            # streaming pass that consumes it. Fold definition and threshold
            # semantics are shared with the gather path via core.checksum,
            # so both backends flag exactly the same corruptions.
            cs = kc1_refs[0].shape[-2]
            rep_lane = jax.lax.broadcasted_iota(jnp.int32, rep_ref.shape, 1)
            plane = jnp.zeros(rep_ref.shape, bool)
            n_bad = jnp.int32(0)
            for p in range(pages):
                j = j0 + p
                rows = slice(p * bs, (p + 1) * bs)
                bad_k = cks.block_fold_bad(
                    cks.encode_kv_tile(k[rows], cs),
                    cks.Checksums(kc1_refs[p][...], kc2_refs[p][...]),
                    threshold=kv_thr)
                bad_v = cks.block_fold_bad(
                    cks.encode_kv_tile(v[rows], cs),
                    cks.Checksums(vc1_refs[p][...], vc2_refs[p][...]),
                    threshold=kv_thr)
                flag = (bad_k | bad_v) & (src_ref[b, j] > 0) & live(j)
                n_bad = n_bad + flag.astype(jnp.int32)
                plane = plane | ((rep_lane == j) & flag)
            det_scr[5] += n_bad
            # row 1 of the report tile is the bad plane: lane j = table slot
            row1 = jax.lax.broadcasted_iota(jnp.int32, rep_ref.shape, 0) == 1
            rep_ref[...] = jnp.where(row1 & plane, 1, rep_ref[...])

            # running max|V|: the convex-combination bound for finalize NVR
            vmax_scr[0] = jnp.maximum(
                vmax_scr[0], jnp.max(jnp.abs(v.astype(jnp.float32))))

        # ---- GEMM I on the MXU (f32 accumulate) + tensor-checksum ABFT ----
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # (grp * C, w)
        s = _flip(s, on=hit(Site.GEMM1), row=fault_ref[P_ROW], col=fcol,
                  bit=fault_ref[P_BIT])
        if ft:
            # NVR range restriction (see efta_attention): keeps the weighted
            # fold finite under exponent-bit corruptions.
            s = jnp.where(jnp.isfinite(s), jnp.clip(s, -1e6, 1e6), 0.0)

            # CCG: tensor checksums of each page of K (the strided row fold
            # of encode_kv_tile, at the ABFT stride), then skinny GEMMs;
            # key row / score lane r of a page holds its fold r
            kf = k.astype(jnp.float32)
            sc1, sc2 = (jax.lax.dot_general(
                q.astype(jnp.float32),
                _fold(kf, s_kv, g_kv, axis=0, weighted=w),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
                for w in (False, True))
            d1 = sc1 - _fold(s, s_kv, g_kv, axis=1)
            d2 = sc2 - _fold(s, s_kv, g_kv, axis=1, weighted=True)
            bad = (jnp.abs(d1) > eps1) & fold_idx
            det_scr[0] += bad.sum(dtype=jnp.int32)
            if correct:
                s = _correct(s, d1, d2, bad, s_kv, g_kv)

        # ---- per-row causal + window + ragged mask, running max ----------
        # Tile rows are group-major: row r holds (group g = r // C, chunk
        # row c = r % C); chunk row c queries absolute position base + c.
        crow = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) % chunk
        qpos = base + crow
        cols = j0 * bs + lane
        mask = (cols <= qpos) & (qpos - cols < window) & (crow < q_len)
        s_m = jnp.where(mask, s, MASK_VALUE)
        blockmax = jnp.max(s_m, axis=1, keepdims=True)      # (grp * C, 1)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, blockmax)
        m_new = _flip(m_new, on=hit(Site.ROWMAX), row=fault_ref[P_ROW],
                      col=jnp.int32(0), bit=fault_ref[P_BIT])
        if ft and shadow_rowmax:
            m_chk = jnp.maximum(m_prev, _shadow_rowmax(s_m, s_kv, g_kv))
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
        p_raw = _flip(p_raw, on=hit(Site.EXP), row=fault_ref[P_ROW],
                      col=fcol, bit=fault_ref[P_BIT])
        if ft:
            pc1 = jnp.exp(jnp.minimum(sc1 - g_kv * m_sub, cap * g_kv))
            prod = _fold(p_raw, s_kv, g_kv, axis=1, op=jnp.multiply)
            ref = jnp.maximum(jnp.abs(pc1), 1e-20)
            bad_e = jnp.abs(prod - pc1) > eps2 * ref + 1e-20
            capped = ((s - m_sub) > (cap - 1e-3)).astype(jnp.float32)
            any_capped = _fold(capped, s_kv, g_kv, axis=1,
                               op=jnp.maximum) > 0
            bad_e &= fold_idx & ~any_capped
            det_scr[1] += bad_e.sum(dtype=jnp.int32)
            if correct:
                recomputed = jnp.exp(jnp.minimum(s - m_sub, cap))
                redo = _spread(bad_e.astype(jnp.float32), s_kv, g_kv) > 0
                p_raw = jnp.where(redo, recomputed, p_raw)
        if ft and shadow_rowmax and correct:
            # exact recompute backstop (see efta_attention)
            recheck = jnp.exp(jnp.minimum(s - m_sub, cap))
            slipped = (p_raw != recheck) & page_live
            det_scr[1] += slipped.sum(dtype=jnp.int32)
            p_raw = jnp.where(slipped, recheck, p_raw)
        p = jnp.where(mask, p_raw, 0.0)

        # ---- rescale + rowsum (+ shadow) ---------------------------------
        alpha = jnp.where(alive, jnp.exp(m_prev - m_new), 1.0)
        l_new = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        l_new = _flip(l_new, on=hit(Site.ROWSUM), row=fault_ref[P_ROW],
                      col=jnp.int32(0), bit=fault_ref[P_BIT])
        l_scr[...] = l_new
        if ft and shadow_rowsum:
            lsh_scr[...] = alpha * lsh_scr[...] + _shadow_rowsum(p, s_kv,
                                                                 g_kv)
        blk_alive = blockmax > MASK_VALUE / 2
        r_scr[...] = alpha * r_scr[...] + jnp.where(
            blk_alive, jnp.exp(blockmax - m_sub), 0.0)

        # ---- GEMM II + rescale, checksums carried ------------------------
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # (grp * C, D)
        acc_new = alpha * acc_scr[...] + pv
        acc_new = _flip(acc_new, on=hit(Site.GEMM2), row=fault_ref[P_ROW],
                        col=fault_ref[P_COL], bit=fault_ref[P_BIT])
        acc_scr[...] = acc_new
        if ft:
            vcs1, vcs2 = _col_checksums(v, s_out)
            pf = p.astype(jnp.float32)
            oc1_scr[...] = alpha * oc1_scr[...] + jax.lax.dot_general(
                pf, vcs1, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            oc2_scr[...] = alpha * oc2_scr[...] + jax.lax.dot_general(
                pf, vcs2, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            if not unified:
                n_bad, _ = _verify_output(acc_scr[...], oc1_scr[...],
                                          oc2_scr[...], s_out=s_out,
                                          eps3=eps3, correct=False)
                det_scr[4] += n_bad
        return carry

    def finalize(h, carry):
        """Head h's SNVR on ℓ and unified output verification."""
        (_, (_, o_ref, rep_ref, _, l_scr, lsh_scr, r_scr, acc_scr,
             oc1_scr, oc2_scr), det_scr, vmax_scr) = views(h)
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
                fb_ok = ((lsh >= r_f - 1e-3) & (lsh <= upper)
                         & jnp.isfinite(lsh))
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
            n_bad, o = _verify_output(o, oc1_scr[...] / l_safe,
                                      oc2_scr[...] / l_safe, s_out=s_out,
                                      eps3=eps3, correct=correct)
            det_scr[4] += n_bad
        o_ref[...] = o.astype(o_ref.dtype)
        rep_ref[...] = _report_tile(rep_ref[...],
                                    [det_scr[c] for c in range(6)])
        return carry

    # Ragged / causal skip: compute blocks with no live slot contribute
    # nothing — no MXU work, no checksum folds (and, by the index maps, no
    # page copies).
    @pl.when((j0 < hi) & (j0 + pages > lo))
    def _stream():
        jax.lax.fori_loop(0, kv_heads, stream, 0)

    @pl.when(i == n_blocks - 1)
    def _finalize():
        jax.lax.fori_loop(0, kv_heads, finalize, 0)


def _live_slots(kv_lens, q_lens, window, *, block_size: int):
    """Per request, the live table slots ``[lo, hi)``: slot ``j`` is live
    when its keys start below ``kv_len`` and its last key lies inside the
    earliest chunk row's window (``base - last < window``). An empty range
    is ``[0, 0)``."""
    hi = -(-kv_lens // block_size)
    lo = jnp.maximum(0, (kv_lens - q_lens - window + 1) // block_size)
    empty = lo >= hi
    return jnp.where(empty, 0, lo), jnp.where(empty, 0, hi)


def _source_pages(bt, lo, hi, pages: int):
    """The pool row slot ``p`` of each compute block copies: the page at
    table slot ``j`` while it is live, else the nearest live page of the
    same slot (``j = p mod P``) — the one the slot holds already or will
    hold next — so the pipeline, which copies a block only when its index
    changes, copies no page that is not live. A slot with no live page
    holds the null block."""
    j = jnp.arange(bt.shape[-1], dtype=jnp.int32)[None, :]
    p = j % pages
    first = lo[:, None] + (p - lo[:, None]) % pages
    last = hi[:, None] - 1 - (hi[:, None] - 1 - p) % pages
    near = jnp.clip(jnp.clip(j, first, last), 0, bt.shape[-1] - 1)
    return jnp.where(first < hi[:, None],
                     jnp.take_along_axis(bt, near, axis=1), 0)


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
    :class:`PagedReport` whose ``out`` matches ``q``'s shape. Each grid step
    streams :func:`pages_per_block` table slots.
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
    pages = pages_per_block(bs, mb)
    n_blocks = -(-mb // pages)
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
    kv_lens = jnp.asarray(kv_lens, jnp.int32)
    q_lens = jnp.asarray(q_lens, jnp.int32)
    # pad the table to whole compute blocks with null slots (never live)
    bt = jnp.pad(jnp.asarray(block_tables, jnp.int32),
                 ((0, 0), (0, n_blocks * pages - mb)))
    lo, hi = _live_slots(kv_lens, q_lens, win[0], block_size=bs)
    src = _source_pages(bt, lo, hi, pages)

    kernel = functools.partial(
        _paged_kernel,
        sm_scale=scale, block_size=bs, pages=pages, n_blocks=n_blocks,
        kv_heads=hkv, chunk=chunk, s_kv=s_kv, s_out=s_out, kv_thr=kv_thr,
        mode=cfg.mode, unified=cfg.unified, shadow_rowsum=cfg.shadow_rowsum,
        shadow_rowmax=cfg.shadow_rowmax, eps1=eps1, eps2=eps2, eps3=eps3)

    rows = grp * chunk
    # report tile: row 0 = the six detection counters, row 1 = the bad plane
    # (lane j = table slot j), padded to whole (8, 128) tiles
    rep_tile = (REPORT_TILE[0],
                -(-n_blocks * pages // REPORT_TILE[1]) * REPORT_TILE[1])
    per_b = pl.BlockSpec((None, hkv, rows, d),
                         lambda bi, i, *_: (bi, 0, 0, 0))

    def slot_spec(n, p):
        # Slot p of the compute block, one pipelined operand per slot and
        # pool: pool row src[bi, i*P + p], every head. A manual DMA could
        # gather the P pages into one buffer, but Mosaic slices no HBM ref
        # whose last dimension (head_dim 64) is narrower than its 128-lane
        # tile; the pipeline's own copies take such blocks.
        return pl.BlockSpec(
            (None, hkv, n, d),
            lambda bi, i, fault, src, *_: (src[bi, i * pages + p], 0, 0, 0))

    page_specs = [slot_spec(n, p) for n in (bs, bs, cs, cs, cs, cs)
                  for p in range(pages)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(b, n_blocks),
        in_specs=[per_b] + page_specs,
        out_specs=[
            per_b,
            pl.BlockSpec((None, hkv) + rep_tile,
                         lambda bi, i, *_: (bi, 0, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((hkv, rows, 1), jnp.float32),   # m
            pltpu.VMEM((hkv, rows, 1), jnp.float32),   # l
            pltpu.VMEM((hkv, rows, 1), jnp.float32),   # l shadow
            pltpu.VMEM((hkv, rows, 1), jnp.float32),   # r (SNVR bound)
            pltpu.VMEM((hkv, rows, d), jnp.float32),   # output accumulator
            pltpu.VMEM((hkv, rows, d), jnp.float32),   # O checksum 1
            pltpu.VMEM((hkv, rows, d), jnp.float32),   # O checksum 2
            pltpu.SMEM((hkv * 8,), jnp.int32),        # detection counters
            pltpu.SMEM((hkv,), jnp.float32),          # running max|V| (NVR)
        ],
    )

    pools = (k_pool, v_pool, k_checks.c1, k_checks.c2, v_checks.c1,
             v_checks.c2)
    out, rep = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, rows, d), q.dtype),
            jax.ShapeDtypeStruct((b, hkv) + rep_tile, jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
        name="efta_paged",
    )(fault, src, kv_lens, q_lens, win, lo, hi, qr,
      *[x for x in pools for _ in range(pages)])

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
