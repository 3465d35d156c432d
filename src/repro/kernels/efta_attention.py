"""EFTA — fused fault-tolerant flash attention as a Pallas TPU kernel.

This is the TPU-native artifact of the paper: attention computation and the
hybrid fault-tolerance scheme (tensor-checksum ABFT + SNVR + unified
verification, Algorithm 1) execute inside ONE kernel; the O(n²) score and
probability tiles never leave VMEM.

Architecture mapping (DESIGN.md §2):
  * grid = (batch·heads, Sq/Br, Skv/Bc); the KV axis is ``arbitrary``
    (sequential) so running (m, ℓ, O, O_checksums) accumulate in VMEM scratch
    across KV steps — the Pallas analogue of the paper's intra-CTA loop.
  * checksum folds shift the tile onto itself (``pltpu.roll``) in a tree
    of fan-in four, so every fold keeps the tile's shape and lane density
    (``s = 8`` reproduces the paper's MMA-atom stride for fidelity
    experiments). The same helpers fold each page of the paged kernel's
    multi-page compute block (``repro.kernels.efta_paged``): a KV tile
    here is one page.
  * fault injection is a scalar-prefetch descriptor (SEU model): a single bit
    of a chosen tile element is XOR-flipped at a chosen (site, kv-block).

Validated against ``repro.kernels.ref`` in interpret mode (CPU). The same
code compiles through Mosaic for a TPU v5e in bf16 at head_dim 64 and
sequence 1024 (``tests/test_tpu_compile.py``, against a described chip); it
runs compiled whenever the backend is a TPU (:func:`resolve_interpret`).
Mosaic-specific choices: report tiles are whole (8, 128) int32 tiles,
corrections spread the delta over the tile's segments by shifts instead of
``dynamic_update_slice``, and the shadow rowmax / rowsum differ from the
primary by their reduction order (Mosaic has no ``optimization_barrier``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.efta import EFTAConfig, MASK_VALUE
from repro.core.fault import Site


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Kernels run compiled on a TPU backend and interpreted elsewhere (the
    CPU the tests use); an explicit bool overrides, so compile-only tests can
    lower for a described chip while the backend is the CPU."""
    return jax.default_backend() != "tpu" if interpret is None else interpret

# fault descriptor layout (int32[8]):
# [site, kv_block, bh, row, col, bit, enabled, _pad]
F_SITE, F_BLOCK, F_BH, F_ROW, F_COL, F_BIT, F_ON = range(7)

REPORT_TILE = (8, 128)      # int32 per-grid-cell report tile (see _report_tile)


def _flip(tile, *, on, row, col, bit):
    """XOR-flip one bit of tile[row, col] when ``on`` — fully vectorized."""
    rows = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1)
    hit = (rows == row) & (cols == col) & on
    ubits = jax.lax.bitcast_convert_type(tile, jnp.uint32)
    mask = jnp.where(hit, jnp.left_shift(jnp.uint32(1), bit.astype(jnp.uint32)),
                     jnp.uint32(0))
    return jax.lax.bitcast_convert_type(ubits ^ mask, tile.dtype)


def _report_tile(base, counters):
    """Write scalar counters into lanes ``0..n-1`` of row 0 of an int32
    report tile (other rows and lanes keep ``base``).

    Report outputs are whole (8, 128)-aligned int32 tiles: Mosaic blocks a
    VMEM output only in (8, 128) units, so a 5- or 6-wide counter row cannot
    be an output block of its own.
    """
    rows = jax.lax.broadcasted_iota(jnp.int32, base.shape, 0)
    lanes = jax.lax.broadcasted_iota(jnp.int32, base.shape, 1)
    for i, c in enumerate(counters):
        base = jnp.where((rows == 0) & (lanes == i), c, base)
    return base


def _site_hit(fault_ref, site: Site, *, bh, blk):
    return ((fault_ref[F_ON] == 1)
            & (fault_ref[F_SITE] == int(site))
            & (fault_ref[F_BH] == bh)
            & (fault_ref[F_BLOCK] == blk))


def _radices(segments: int):
    """Factors of ``segments``, fours first, each the fan-in of one level
    of :func:`_fold`'s tree."""
    out, rest = [], segments
    while rest > 1:
        k = next(f for f in (4, 2, 3, *range(5, rest + 1)) if rest % f == 0)
        out.append(k)
        rest //= k
    return out


def _fold(x, stride: int, segments: int, *, axis: int, op=jnp.add,
          weighted: bool = False):
    """The strided checksum fold of every *page* of ``x`` along ``axis``,
    in place. A page is ``segments`` consecutive segments of ``stride``
    indices; at each *fold index* (offset below ``stride`` within its
    page) the result is ``op`` over the page's segments there, segment
    ``l`` weighted ``l + 1`` when ``weighted`` (``op`` is then the sum).
    The contiguous kernel's KV tile is one page; the paged kernel's compute
    block is ``P`` pages of ``block_size`` keys, each folded on its own.

    Segments come in by circular shifts of the whole tile (``pltpu.roll``),
    which keep its shape and lane density whatever the stride, in a tree
    of fan-in four: the shifts of one level are independent of each other,
    so a fold of four segments is one level deep, and one of 32 three.
    Indices other than the fold indices hold values no check reads. A
    negative ``stride`` shifts the other way (see :func:`_spread`).
    """
    n = x.shape[axis]

    def back(y, segs):        # y[i + segs * stride], circularly
        return pltpu.roll(y, (n - segs * stride) % n, axis) if segs else y

    # acc: op over the m segments from each index; wsum: the sum of
    # (segment - first) * value over them, when weighted
    acc, wsum, m = x, None, 1
    for k in _radices(segments):
        parts = [back(acc, t * m) for t in range(k)]
        if weighted:
            terms = [float(t * m) * parts[t] for t in range(1, k)]
            if wsum is not None:
                terms += [back(wsum, t * m) for t in range(k)]
            wsum = functools.reduce(jnp.add, terms)
        acc = functools.reduce(op, parts)
        m *= k
    return acc + wsum if weighted and wsum is not None else acc


def _spread(x, stride: int, segments: int):
    """Each fold index's value of ``x`` copied to the same offset of every
    segment of its page, along the last axis; ``x`` must be zero off the
    fold indices. This is the unweighted fold with its shifts reversed:
    ``out[i] = sum_l x[i - l * stride]``."""
    return _fold(x, -stride, segments, axis=x.ndim - 1)


def _fold_index(shape, stride: int, page: int):
    """Lanes at a fold index of their page (see :func:`_fold`)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)
    return lane % page < stride


def _correct(tile, d1, d2, bad, stride: int, segments: int):
    """Paper §4.1 correction of every page of ``tile`` along its last axis:
    at each flagged fold index (``bad``; ``d1``/``d2`` the unweighted and
    weighted checksum deltas there) locate the segment ``l*`` from the
    delta ratio and add ``d1`` back at that segment."""
    safe = jnp.where(bad, d1, 1.0)
    l_star = jnp.clip(jnp.round(d2 / safe) - 1, 0, segments - 1)
    at = _spread(jnp.where(bad, l_star + 1.0, 0.0), stride, segments)
    add = _spread(jnp.where(bad, d1, 0.0), stride, segments)
    lane = jax.lax.broadcasted_iota(jnp.int32, tile.shape, tile.ndim - 1)
    seg = (lane % (stride * segments)) // stride
    return tile + jnp.where(at == (seg + 1).astype(at.dtype), add, 0.0)


def _shadow_rowmax(tile, stride: int, segments: int):
    """Row max of ``tile`` through another reduction tree (segment-wise
    maxima first). Mosaic has no lowering for ``optimization_barrier``, so
    the shadow recompute is kept distinct from the primary by its form: the
    two are different expressions, which common-subexpression elimination
    does not merge, and max is exact, so they agree bit for bit unless a
    fault strikes one of them."""
    return jnp.max(_fold(tile, stride, segments, axis=1, op=jnp.maximum),
                   axis=1, keepdims=True)


def _shadow_rowsum(tile, stride: int, segments: int):
    """Row sum of ``tile`` folded by pages and segments first (see
    _shadow_rowmax); the summation order differs, so it agrees with the
    primary up to f32 rounding, far inside the shadow-rowsum tolerance."""
    idx = _fold_index(tile.shape, stride, stride * segments)
    return jnp.sum(jnp.where(idx, _fold(tile, stride, segments, axis=1),
                             0.0), axis=1, keepdims=True)


def _widen(x, width: int):
    """``x`` padded with zeros along its last axis to ``width``."""
    pad = width - x.shape[-1]
    if not pad:
        return x
    return jnp.concatenate(
        [x, jnp.zeros(x.shape[:-1] + (pad,), x.dtype)], axis=-1)


def _col_checksums(v, s_out: int):
    """V's column checksums for the output verification (``encode_cols``),
    through 0/1 and 1..g fold matrices on the MXU: feature ``f`` of V lands
    on fold lane ``f % s_out`` with weight ``f // s_out + 1``; lanes from
    ``s_out`` on are zero. Exact for bf16 V; float32 V takes the
    full-precision passes."""
    d = v.shape[-1]
    feat = jax.lax.broadcasted_iota(jnp.int32, (d, d), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (d, d), 1)
    on = (feat % s_out == col) & (col < s_out)
    prec = jax.lax.Precision.HIGHEST if v.dtype == jnp.float32 else None
    return tuple(
        jax.lax.dot_general(v, f.astype(v.dtype), (((1,), (0,)), ((), ())),
                            precision=prec,
                            preferred_element_type=jnp.float32)
        for f in (on, jnp.where(on, feat // s_out + 1, 0)))


def _verify_output(o, oc1, oc2, *, s_out: int, eps3: float, correct: bool):
    """Unified output verification (Alg. 1 l.22-24): fold ``o`` and compare
    with its carried checksums ``oc1``/``oc2`` (already divided by ℓ);
    returns the flagged fold count and ``o``, corrected in correct mode."""
    g = o.shape[-1] // s_out
    d1 = oc1 - _fold(o, s_out, g, axis=1)
    d2 = oc2 - _fold(o, s_out, g, axis=1, weighted=True)
    bad = ~(jnp.abs(d1) <= eps3) & _fold_index(o.shape, s_out, o.shape[-1])
    if correct:
        o = _correct(o, d1, d2, bad, s_out, g)
    return bad.sum(dtype=jnp.int32), o


def _efta_kernel(
    # scalar prefetch
    fault_ref,
    # inputs
    q_ref, k_ref, v_ref,
    # outputs
    o_ref, rep_ref,
    # scratch
    m_scr, l_scr, lsh_scr, r_scr, acc_scr, oc1_scr, oc2_scr, det_scr,
    vmax_scr,
    *,
    sm_scale: float,
    causal: bool,
    window: Optional[int],
    block_q: int,
    block_kv: int,
    n_kv: int,
    kv_seq_len: int,
    s_kv: int,
    s_out: int,
    mode: str,
    unified: bool,
    shadow_rowsum: bool,
    shadow_rowmax: bool,
    eps1: float,
    eps2: float,
    eps3: float,
):
    bh = pl.program_id(0)
    iq = pl.program_id(1)
    jk = pl.program_id(2)
    ft = mode != "off"
    correct = mode == "correct"
    g_kv = block_kv // s_kv

    @pl.when(jk == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, MASK_VALUE)
        l_scr[...] = jnp.zeros_like(l_scr)
        lsh_scr[...] = jnp.zeros_like(lsh_scr)
        r_scr[...] = jnp.zeros_like(r_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        oc1_scr[...] = jnp.zeros_like(oc1_scr)
        oc2_scr[...] = jnp.zeros_like(oc2_scr)
        det_scr[0] = 0
        det_scr[1] = 0
        det_scr[2] = 0
        det_scr[3] = 0
        det_scr[4] = 0
        vmax_scr[0] = 0.0

    # Causal block skipping: KV blocks strictly above the diagonal contribute
    # nothing — skip their MXU work entirely (flash-attention-2 style).
    q_start = iq * block_q
    kv_start = jk * block_kv
    run = True
    if causal:
        run = kv_start <= q_start + block_q - 1
    if window is not None:
        run = run & (q_start - (kv_start + block_kv - 1) < window)
    if kv_seq_len < n_kv * block_kv:
        # ragged KV: blocks entirely past the valid prefix are all-masked
        run = run & (kv_start < kv_seq_len)

    @pl.when(run)
    def _body():
        q = q_ref[...]                      # (Br, D)
        k = k_ref[...]                      # (Bc, D)
        v = v_ref[...]                      # (Bc, D)
        if ft:
            # running max|V| across KV blocks: the convex-combination bound
            # |O/l| <= max|V| used by the finalize-stage NVR restriction
            vmax_scr[0] = jnp.maximum(
                vmax_scr[0], jnp.max(jnp.abs(v.astype(jnp.float32))))

        # ---- GEMM I on the MXU (bf16 in, f32 accumulate) + ABFT ----------
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale      # (Br, Bc)
        fault_row = fault_ref[F_ROW] - q_start
        s = _flip(s, on=_site_hit(fault_ref, Site.GEMM1, bh=bh, blk=jk),
                  row=fault_row, col=fault_ref[F_COL], bit=fault_ref[F_BIT])
        if ft:
            # NVR range restriction on scores: keeps the weighted fold finite
            # under exponent-bit corruptions (location ratio stays exact);
            # NaN/inf zero out and the checksum delta restores them exactly.
            s = jnp.where(jnp.isfinite(s), jnp.clip(s, -1e6, 1e6), 0.0)

        # the fold indices of the tile, one page: where the checks read
        fold_idx = _fold_index(s.shape, s_kv, block_kv)
        if ft:
            # CCG: tensor checksums of K (strided fold along the key axis is
            # a fold along *rows* of K), then one skinny GEMM each, whose
            # (Br, s_kv) result is widened to the tile: fold lane r holds
            # fold r.
            kf = k.astype(jnp.float32)
            sc1, sc2 = (_widen(jax.lax.dot_general(
                q.astype(jnp.float32),
                _fold(kf, s_kv, g_kv, axis=0, weighted=w)[:s_kv],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale, block_kv)
                for w in (False, True))
            d1 = sc1 - _fold(s, s_kv, g_kv, axis=1)
            d2 = sc2 - _fold(s, s_kv, g_kv, axis=1, weighted=True)
            bad = (jnp.abs(d1) > eps1) & fold_idx
            det_scr[0] += bad.sum(dtype=jnp.int32)
            if correct:
                s = _correct(s, d1, d2, bad, s_kv, g_kv)

        # ---- mask, running max ------------------------------------------
        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = kv_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = cols < kv_seq_len
        if causal:
            mask &= cols <= rows
        if window is not None:
            mask &= rows - cols < window
        s_m = jnp.where(mask, s, MASK_VALUE)
        blockmax = jnp.max(s_m, axis=1, keepdims=True)          # (Br, 1)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, blockmax)
        m_new = _flip(m_new, on=_site_hit(fault_ref, Site.ROWMAX, bh=bh, blk=jk),
                      row=fault_row, col=jnp.int32(0), bit=fault_ref[F_BIT])
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
        p_raw = _flip(p_raw, on=_site_hit(fault_ref, Site.EXP, bh=bh, blk=jk),
                      row=fault_row, col=fault_ref[F_COL], bit=fault_ref[F_BIT])
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
            # Exact recompute backstop (beyond-paper, mirrors the jnp path):
            # EXP corruptions whose fold product underflows (g_kv segments of
            # e^{s-m} can reach 0 in f32) slip the product check, and the
            # NVR clamp alone only bounds the damage. The recompute is
            # already materialized for the correction path above, so an
            # exact compare-and-select closes the gap for one VPU pass.
            # Safe only with shadow_rowmax (m is exact).
            recheck = jnp.exp(jnp.minimum(s - m_sub, cap))
            slipped = p_raw != recheck
            det_scr[1] += slipped.sum(dtype=jnp.int32)
            p_raw = jnp.where(slipped, recheck, p_raw)
        p = jnp.where(mask, p_raw, 0.0)

        # ---- rescale + rowsum (+ shadow) ---------------------------------
        alpha = jnp.where(alive, jnp.exp(m_prev - m_new), 1.0)  # (Br, 1)
        l_new = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        l_new = _flip(l_new, on=_site_hit(fault_ref, Site.ROWSUM, bh=bh, blk=jk),
                      row=fault_row, col=jnp.int32(0), bit=fault_ref[F_BIT])
        l_scr[...] = l_new
        if ft and shadow_rowsum:
            lsh_scr[...] = alpha * lsh_scr[...] + _shadow_rowsum(p, s_kv,
                                                                 g_kv)
        blk_alive = blockmax > MASK_VALUE / 2
        r_scr[...] = alpha * r_scr[...] + jnp.where(
            blk_alive, jnp.exp(blockmax - m_sub), 0.0)

        # ---- GEMM II + rescale, checksums carried (Alg.1 l.18-21) --------
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                 # (Br, D)
        acc_new = alpha * acc_scr[...] + pv
        acc_new = _flip(acc_new, on=_site_hit(fault_ref, Site.GEMM2, bh=bh, blk=jk),
                        row=fault_row, col=fault_ref[F_COL], bit=fault_ref[F_BIT])
        acc_scr[...] = acc_new
        if ft:
            vc1, vc2 = _col_checksums(v, s_out)
            pf = p.astype(jnp.float32)
            oc1_scr[...] = alpha * oc1_scr[...] + jax.lax.dot_general(
                pf, vc1, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            oc2_scr[...] = alpha * oc2_scr[...] + jax.lax.dot_general(
                pf, vc2, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            if not unified:
                # Unoptimized EFTA: verify the output checksum at EVERY kv
                # step (Tables 1-2 compare this against unified verification).
                n_bad, _ = _verify_output(acc_scr[...], oc1_scr[...],
                                          oc2_scr[...], s_out=s_out,
                                          eps3=eps3, correct=False)
                det_scr[4] += n_bad

    # ---- finalize: SNVR on ℓ + unified output verification ---------------
    @pl.when(jk == n_kv - 1)
    def _finalize():
        l_f = l_scr[...]
        r_f = r_scr[...]
        if ft:
            upper = float(kv_seq_len) + 1e-3
            in_range = (l_f >= r_f - 1e-3) & (l_f <= upper) & jnp.isfinite(l_f)
            if shadow_rowsum:
                lsh = lsh_scr[...]
                mism = jnp.abs(l_f - lsh) > 1e-5 * jnp.maximum(jnp.abs(lsh), 1e-6)
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
                # NVR range restriction: O/l is a convex combination of V
                # rows, so |o| <= max|V|. Zero violations (incl. NaN/inf)
                # so the output-checksum delta restores the exact value —
                # otherwise a 1e38-scale accumulator corruption cancels
                # catastrophically in the correction add.
                bound = vmax_scr[0] * 1.001 + 1e-6
                o = jnp.where(jnp.isfinite(o) & (jnp.abs(o) <= bound),
                              o, 0.0)
            # NaN-safe in detect mode: a NaN delta is flagged
            n_bad, o = _verify_output(o, oc1_scr[...] / l_safe,
                                      oc2_scr[...] / l_safe, s_out=s_out,
                                      eps3=eps3, correct=correct)
            det_scr[4] += n_bad
        o_ref[...] = o.astype(o_ref.dtype)
        rep_ref[...] = _report_tile(jnp.zeros(rep_ref.shape, jnp.int32),
                                    [det_scr[i] for i in range(5)])


def efta_attention_pallas(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    cfg: EFTAConfig,
    causal: bool = False,
    window: Optional[int] = None,
    kv_len: Optional[int] = None,
    sm_scale: Optional[float] = None,
    fault: Optional[jax.Array] = None,
    block_q: int = 128,
    interpret: Optional[bool] = None,
) -> tuple[jax.Array, jax.Array]:
    """Fused EFTA kernel. q: (B, H, Sq, D); k, v: (B, Hkv, Skv, D).

    Returns (out (B, H, Sq, D), detected (5,) int32).
    ``fault``: int32[8] SEU descriptor (see module docstring) or None.
    ``kv_len`` (static int) masks a ragged KV tail: only the first ``kv_len``
    of the ``Skv`` cache slots are attended (serving caches are allocated at
    block-aligned capacity but only partially filled). It also tightens the
    SNVR rowsum bound to the number of *valid* keys.
    ``interpret``: None (default) compiles on a TPU backend and interprets
    elsewhere; a bool forces either (compile tests force False on the CPU).
    """
    b, h, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    grp = h // hkv
    scale = sm_scale if sm_scale is not None else 1.0 / (d ** 0.5)
    if kv_len is None:
        kv_len = skv
    if not 0 < kv_len <= skv:
        raise ValueError(f"kv_len {kv_len} out of range (0, {skv}]")

    block_q = min(block_q, sq)
    block_kv = min(cfg.block_kv, skv)
    if sq % block_q or skv % block_kv:
        raise ValueError(f"seq lens ({sq},{skv}) must divide blocks "
                         f"({block_q},{block_kv})")
    s_kv = cfg.kv_stride(block_kv)
    s_out = cfg.out_stride(d)
    eps1, eps2, eps3 = cfg.thresholds(q.dtype)
    n_q, n_kv = sq // block_q, skv // block_kv

    qr = q.reshape(b * h, sq, d)
    kr = k.reshape(b * hkv, skv, d)
    vr = v.reshape(b * hkv, skv, d)
    if fault is None:
        fault = jnp.zeros((8,), jnp.int32)

    kernel = functools.partial(
        _efta_kernel,
        sm_scale=scale, causal=causal, window=window,
        block_q=block_q, block_kv=block_kv, n_kv=n_kv, kv_seq_len=kv_len,
        s_kv=s_kv, s_out=s_out, mode=cfg.mode, unified=cfg.unified,
        shadow_rowsum=cfg.shadow_rowsum, shadow_rowmax=cfg.shadow_rowmax,
        eps1=eps1, eps2=eps2, eps3=eps3)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * h, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda bh, i, j, f: (bh, i, 0)),
            pl.BlockSpec((None, block_kv, d),
                         lambda bh, i, j, f, g=grp: (bh // g, j, 0)),
            pl.BlockSpec((None, block_kv, d),
                         lambda bh, i, j, f, g=grp: (bh // g, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, d), lambda bh, i, j, f: (bh, i, 0)),
            pl.BlockSpec((None, None) + REPORT_TILE,
                         lambda bh, i, j, f: (bh, i, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # m
            pltpu.VMEM((block_q, 1), jnp.float32),   # l
            pltpu.VMEM((block_q, 1), jnp.float32),   # l shadow
            pltpu.VMEM((block_q, 1), jnp.float32),   # r (SNVR bound)
            pltpu.VMEM((block_q, d), jnp.float32),   # output accumulator
            pltpu.VMEM((block_q, d), jnp.float32),   # O checksum 1
            pltpu.VMEM((block_q, d), jnp.float32),   # O checksum 2
            pltpu.SMEM((5,), jnp.int32),             # detection counters
            pltpu.SMEM((1,), jnp.float32),           # running max|V| (NVR)
        ],
    )

    out, rep = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, n_q) + REPORT_TILE, jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
        name="efta_attention",
    )(fault, qr, kr, vr)

    return out.reshape(b, h, sq, d), rep[:, :, 0, :5].sum(axis=(0, 1))
