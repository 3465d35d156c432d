"""End-to-End Fault Tolerant Attention (EFTA) — paper Algorithm 1 in pure JAX.

This is the framework-level implementation that models call: a flash-attention
style `lax.scan` over KV blocks with the paper's hybrid fault-tolerance scheme
fused into the same computation:

  * GEMM I  (S = Q·Kᵀ)      — tensor-checksum ABFT (encode K checksums, verify
                              the strided-fold identity on S, locate + correct)
  * subtract-max + EXP       — checksum reuse: the *same* S checksum, shifted
                              by ``g·m``, must equal the strided fold of
                              ``log P`` (the paper's product identity, Alg.1
                              line 13, verified in the log domain so
                              underflowing columns stay covered); EXP faults
                              are corrected by recomputation
  * ROWMAX                   — unprotected by design: errors cancel analytically
                              (paper Case 1); we compute in f32 to avoid the
                              overflow corner
  * ROWSUM (ℓ)               — SNVR: range restriction ``Σ_k e^{m_k - m} ≤ ℓ ≤
                              kv_len`` with analytic-approximation correction
                              (paper Case 3 / Alg.1 lines 22-24)
  * GEMM II + rescale + norm — unified verification: one output checksum is
                              carried through every rescale and the final
                              normalization, verified **once** at the end
                              (paper Alg.1 lines 18-28)

The TPU-native Pallas kernel (`repro.kernels.efta_attention`) implements the
same algorithm with explicit VMEM tiling; this module is its jit/pjit-friendly,
differentiable twin and the one exercised by the multi-pod dry-run.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import checksum as cks
from repro.core.fault import FaultSpec, Site, inject

MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)


@dataclasses.dataclass(frozen=True)
class EFTAConfig:
    """Fault-tolerance + tiling configuration for EFTA."""

    mode: str = "correct"          # "off" | "detect" | "correct"
    stride: int = cks.TPU_STRIDE   # max checksum fold stride (8 = paper)
    block_kv: int = 512            # KV block size (Bc)
    unified: bool = True           # unified verification (EFTA-o) vs per-block
    unroll: bool = False           # unroll the KV-block scan (dry-run probes)
    # Checksum *width* drives the MXU overhead: the checksum GEMMs add
    # 2*s_kv/Bc (GEMM I) and 2*s_out/d (GEMM II) extra FLOPs. The fold
    # *layout* (lane-aligned vs strided) is a VPU concern only. So widths
    # auto-tune to keep MXU overhead ~6-12% unless explicitly pinned —
    # measured in EXPERIMENTS.md §Perf (hypothesis: the naive s=128 "lane
    # aligned" port costs +50% GEMM-I FLOPs at Bc=512 — confirmed, refused).
    kv_stride_override: Optional[int] = None
    out_stride_override: Optional[int] = None
    # Beyond-paper: exact rowsum correction via a shadow accumulator (one f32
    # row vector in VMEM — cheap on TPU, where the paper avoided DMR because
    # of GPU register pressure). False = paper-faithful analytic approximation.
    shadow_rowsum: bool = True
    # Beyond-paper: recompute-compare on the running rowmax (one (Br,1) max +
    # compare) and NVR range-clamp P <= 1. The paper relies on analytic
    # cancellation of rowmax errors (Case 1), which holds only in exact
    # arithmetic — an understated max overflows exp() in fp16/bf16 on real
    # hardware. False = paper-faithful behaviour.
    shadow_rowmax: bool = True
    # Detection thresholds (see DESIGN.md §7.2 — re-derived for bf16).
    eps_gemm1: Optional[float] = None
    eps_exp: Optional[float] = None
    eps_out: Optional[float] = None

    def thresholds(self, dtype) -> tuple[float, float, float]:
        # All thresholds are RELATIVE to checksum magnitude (the paper's
        # absolute 0.48 for fp16 corresponds to ~0.05 relative at their
        # |S|~10 score scale). bf16 encode/verify rounding is ~2^-8 relative,
        # leaving a ~12x detection margin at 0.05.
        if jnp.dtype(dtype) == jnp.float32:
            d = (1e-3, 1e-3, 1e-3)
        else:  # bf16 / fp16 mixed precision — coarse mantissa
            # eps_exp stays loose: bf16 K-checksum rounding is an *absolute*
            # ~2^-8 * g * |s| error in the log-domain fold, which does not
            # shrink when the fold value itself cancels toward zero.
            d = (5e-2, 1.0, 5e-2)
        return (
            self.eps_gemm1 if self.eps_gemm1 is not None else d[0],
            self.eps_exp if self.eps_exp is not None else d[1],
            self.eps_out if self.eps_out is not None else d[2],
        )

    def out_stride(self, head_dim: int) -> int:
        # Keep >= 2 fold segments so the output checksum is a real fold, not a
        # duplicate (g=1 would degenerate tensor-checksum ABFT into DMR).
        if self.out_stride_override:
            s = min(self.out_stride_override, head_dim // 2)
        else:
            s = max(min(self.stride, head_dim // 16, 64), 4)
        while s > 1 and head_dim % s:
            s -= 1
        return max(s, 1)

    def kv_stride(self, block_kv: int) -> int:
        if self.kv_stride_override:
            return min(self.kv_stride_override, max(block_kv // 2, 1))
        p = max(block_kv // 32, 1)
        pow2 = 1 << (p.bit_length() - 1)
        return max(min(self.stride, pow2), 4)


class FTReport(NamedTuple):
    """Aggregatable fault-tolerance telemetry for one attention call."""

    detected: jax.Array    # (5,) int32 — [gemm1, exp, rowmax, rowsum, gemm2]
    corrected: jax.Array   # (5,) int32
    max_delta: jax.Array   # (3,) f32  — [gemm1 linear, exp product, out]

    @staticmethod
    def zero() -> "FTReport":
        return FTReport(
            jnp.zeros((5,), jnp.int32),
            jnp.zeros((5,), jnp.int32),
            jnp.zeros((3,), jnp.float32),
        )

    def merge(self, other: "FTReport") -> "FTReport":
        return FTReport(
            self.detected + other.detected,
            self.corrected + other.corrected,
            jnp.maximum(self.max_delta, other.max_delta),
        )


def _pad_kv(x: jax.Array, block: int) -> jax.Array:
    skv = x.shape[-2]
    pad = (-skv) % block
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 2) + [(0, pad), (0, 0)])
    return x


def reference_attention(q, k, v, *, causal=False, window=None, kv_len=None,
                        q_offset=0, sm_scale=None, kv_positions=None):
    """Naive softmax attention oracle (O(n^2) memory). GQA-aware."""
    b, h, sq, d = q.shape
    hkv = k.shape[1]
    g = h // hkv
    scale = sm_scale if sm_scale is not None else 1.0 / (d ** 0.5)
    qf = q.astype(jnp.float32).reshape(b, hkv, g, sq, d)
    s = jnp.einsum("bkgqd,bkgcd->bkgqc" if k.ndim == 5 else "bkgqd,bkcd->bkgqc",
                   qf, k.astype(jnp.float32)) * scale
    s = s.reshape(b, h, sq, k.shape[-2])
    mask = _full_mask(sq, k.shape[-2], causal=causal, window=window,
                      kv_len=kv_len, q_offset=q_offset,
                      kv_positions=kv_positions)
    s = jnp.where(mask, s, MASK_VALUE)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(mask, p, 0.0)
    pr = p.reshape(b, hkv, g, sq, k.shape[-2])
    o = jnp.einsum("bkgqc,bkcd->bkgqd", pr, v.astype(jnp.float32))
    return o.reshape(b, h, sq, d).astype(q.dtype)


def _full_mask(sq, skv, *, causal, window, kv_len, q_offset, kv_positions=None):
    qpos = jnp.arange(sq)[:, None] + q_offset
    if kv_positions is not None:
        kpos = kv_positions[None, :]
        m = kpos >= 0
    else:
        kpos = jnp.arange(skv)[None, :]
        m = jnp.ones((sq, skv), dtype=bool)
    if causal:
        m &= kpos <= qpos
    if window is not None:
        m &= qpos - kpos < window
    if kv_len is not None and kv_positions is None:
        m &= kpos < kv_len
    return m


def efta_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    cfg: EFTAConfig,
    causal: bool = False,
    window: Optional[int] = None,
    kv_len: Optional[jax.Array] = None,
    q_offset=0,
    sm_scale: Optional[float] = None,
    fault: Optional[FaultSpec] = None,
    kv_positions: Optional[jax.Array] = None,
) -> tuple[jax.Array, FTReport]:
    """EFTA forward. q: (B, H, Sq, D); k, v: (B, Hkv, Skv, D), H % Hkv == 0.

    Returns (output (B, H, Sq, D) in q.dtype, FTReport).
    ``kv_len`` masks a ragged KV cache; ``q_offset`` aligns causal masks when
    q is a suffix of the sequence (decode: q_offset = kv_len - Sq).
    ``kv_positions`` (Skv,) gives the absolute position held in each KV slot
    (ring caches); -1 marks invalid slots. Supersedes ``kv_len``.
    """
    b, h, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    grp = h // hkv
    scale = sm_scale if sm_scale is not None else 1.0 / (d ** 0.5)
    ft = cfg.mode != "off"
    correct = cfg.mode == "correct"
    eps1, eps2, eps3 = cfg.thresholds(q.dtype)

    block = min(cfg.block_kv, max(skv, 1))
    # round the block to a multiple of the fold stride (odd cache lengths
    # from serving are padded + masked below)
    for _ in range(2):
        s_fix = cfg.kv_stride(block)
        block = -(-block // s_fix) * s_fix
    k = _pad_kv(k, block)
    v = _pad_kv(v, block)
    skv_p = k.shape[2]
    nblk = skv_p // block
    if kv_positions is not None and skv_p != skv:
        kv_positions = jnp.pad(kv_positions, (0, skv_p - skv),
                               constant_values=-1)
    if kv_len is None and skv_p != skv and kv_positions is None:
        kv_len = jnp.int32(skv)
    s_kv = cfg.kv_stride(block)      # fold stride along the key axis
    s_out = cfg.out_stride(d)        # fold stride along the feature axis
    g_kv = block // s_kv

    # (nblk, B, Hkv, Bc, D) scan layout.
    kb = k.reshape(b, hkv, nblk, block, d).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(b, hkv, nblk, block, d).transpose(2, 0, 1, 3, 4)
    qf = q.reshape(b, hkv, grp, sq, d)

    qpos = jnp.arange(sq, dtype=jnp.int32)[:, None] + jnp.asarray(q_offset, jnp.int32)

    def block_mask(blk_idx, kvp_blk=None):
        if kvp_blk is not None:
            kpos = kvp_blk[None, :]
            m = kpos >= 0
        else:
            kpos = blk_idx * block + jnp.arange(block, dtype=jnp.int32)[None, :]
            m = jnp.ones((sq, block), dtype=bool)
        if causal:
            m = m & (kpos <= qpos)
        if window is not None:
            m = m & (qpos - kpos < window)
        if kv_len is not None and kvp_blk is None:
            m = m & (kpos < jnp.asarray(kv_len, jnp.int32))
        return m  # (Sq, Bc)

    kvp_blocks = (kv_positions.reshape(nblk, block)
                  if kv_positions is not None else None)

    def body(carry, inp):
        if kvp_blocks is not None:
            blk_idx, k_j, v_j, kvp_blk = inp
        else:
            blk_idx, k_j, v_j = inp
            kvp_blk = None
        (m_prev, l_prev, lsh_prev, r_prev, o_prev, oc1, oc2, rep) = carry

        # --- CCG: encode checksums of this K/V block (paper Alg.1 line 8) ---
        if ft:
            kc = cks.encode_kv(k_j, s_kv)          # (B,Hkv,s_kv,D) x2
            vc = cks.encode_cols(v_j, s_out)       # (B,Hkv,Bc,s_out) x2

        # --- GEMM I: S = Q Kᵀ (f32 accumulate on the MXU) ------------------
        s_ij = jnp.einsum("bkgqd,bkcd->bkgqc", qf, k_j,
                          preferred_element_type=jnp.float32) * scale
        s_ij = s_ij.reshape(b, h, sq, block)
        s_ij = inject(s_ij, fault, Site.GEMM1, blk_idx)
        if ft:
            # NVR range restriction on scores: attention scores are bounded
            # (|s| <= |q||k|/sqrt(d)); clipping an exponent-bit corruption
            # keeps the weighted fold finite so the ABFT location ratio stays
            # exact; NaN/inf corruptions zero out and the checksum delta then
            # restores the true value exactly.
            s_ij = jnp.where(jnp.isfinite(s_ij),
                             jnp.clip(s_ij, -1e6, 1e6), 0.0)

        if ft:
            sc1 = jnp.einsum("bkgqd,bksd->bkgqs", qf, kc.c1,
                             preferred_element_type=jnp.float32) * scale
            sc2 = jnp.einsum("bkgqd,bksd->bkgqs", qf, kc.c2,
                             preferred_element_type=jnp.float32) * scale
            sc1 = sc1.reshape(b, h, sq, s_kv)
            sc2 = sc2.reshape(b, h, sq, s_kv)
            # Linear verification + correction of S (tensor-checksum ABFT).
            verdict = cks.verify_and_correct(
                s_ij, cks.Checksums(sc1, sc2), s_kv,
                threshold=eps1, correct=correct)
            s_ij = verdict.corrected
            det = rep.detected.at[0].add(verdict.n_detected)
            cor = rep.corrected.at[0].add(verdict.n_detected if correct else 0)
            mxd = rep.max_delta.at[0].max(verdict.max_delta)
            rep = FTReport(det, cor, mxd)

        # --- mask + running max (ROWMAX: paper Case 1, analytic cancel) ----
        bm = block_mask(blk_idx, kvp_blk)
        s_m = jnp.where(bm, s_ij, MASK_VALUE)
        blockmax = jnp.max(s_m, axis=-1)                       # (B,H,Sq)
        m_new = jnp.maximum(m_prev, blockmax)
        m_new = inject(m_new, fault, Site.ROWMAX, blk_idx)
        if ft and cfg.shadow_rowmax:
            # Recompute-compare on the (cheap) rowmax recurrence: protects
            # against fp overflow from an understated max, which the paper's
            # analytic-cancellation argument (Case 1) does not cover.
            m_chk = jnp.maximum(jax.lax.optimization_barrier(m_prev), blockmax)
            bad_m = m_new != m_chk
            rep = FTReport(
                rep.detected.at[2].add(bad_m.sum(dtype=jnp.int32)),
                rep.corrected.at[2].add(
                    bad_m.sum(dtype=jnp.int32) if correct else 0),
                rep.max_delta)
            if correct:
                m_new = jnp.where(bad_m, m_chk, m_new)
        alive = m_new > MASK_VALUE / 2

        # --- EXP with checksum reuse (paper Case 2 / Alg.1 lines 11-16) ----
        m_sub = jnp.where(alive, m_new, 0.0)
        # Cap keeps the fold-product finite for masked raw entries; unmasked
        # entries satisfy S <= m so the cap never binds on data that matters.
        cap = 80.0 / g_kv
        p_raw = jnp.exp(jnp.minimum(s_ij - m_sub[..., None], cap))
        p_raw = inject(p_raw, fault, Site.EXP, blk_idx)
        if ft:
            # Log-domain fold check (ROADMAP EXP-coverage closure): comparing
            # the strided *product* of P against exp(S_check1 - g*m) goes
            # blind whenever one segment underflows — prod ~ 0 == check ~ 0
            # hides a corruption of any *other* entry in that column. In the
            # log domain the product is a sum, exact down to the f32 normal-
            # range floor, so detect mode no longer loses those columns.
            lc1 = jnp.minimum(sc1 - g_kv * m_sub[..., None], cap * g_kv)
            bad_exp, _ = cks.verify_product_log(p_raw, lc1, s_kv,
                                                threshold=eps2)
            # Exclusions, both computed from the (GEMM1-verified) scores: the
            # cap breaks the identity for columns whose *masked* raw scores
            # exceed it, and entries below the exp-underflow floor have no
            # log-domain image in P. Excluded entries are either zeroed by
            # the mask or exactly-zero probabilities — no coverage loss.
            sm_shift = s_ij - m_sub[..., None]
            excl = (sm_shift > (cap - 1e-3)) | (sm_shift < cks.LOG_PROD_FLOOR)
            col_ok = ~jnp.any(
                excl.reshape(*excl.shape[:-1], g_kv, s_kv), axis=-2)
            bad_exp = bad_exp & col_ok
            n_exp = bad_exp.sum(dtype=jnp.int32)
            if correct:
                # "Recompute" EXP over every segment of a flagged fold column.
                recompute = jnp.exp(jnp.minimum(s_ij - m_sub[..., None], cap))
                expand = bad_exp[..., None, :] & jnp.ones(
                    (g_kv, s_kv), dtype=bool)
                expand = expand.reshape(*bad_exp.shape[:-1], block)
                p_raw = jnp.where(expand, recompute, p_raw)
            delta_exp = jnp.float32(0)
            rep = FTReport(
                rep.detected.at[1].add(n_exp),
                rep.corrected.at[1].add(n_exp if correct else 0),
                rep.max_delta.at[1].max(delta_exp),
            )
        if ft and cfg.shadow_rowmax and correct:
            # Exact recompute backstop (beyond-paper): EXP corruptions whose
            # fold product underflows (g_kv segments of e^{s-m} reach 0 in
            # f32) slip past the product check; recomputing e^{s-m} and
            # compare-and-selecting restores them exactly. The correction
            # path above already materializes this recompute, so the backstop
            # adds one compare+select. Safe only with shadow_rowmax (m is
            # exact); subsumes the previous NVR clamp P <= 1.
            recheck = jnp.exp(jnp.minimum(s_ij - m_sub[..., None], cap))
            slipped = p_raw != recheck
            n_slip = slipped.sum(dtype=jnp.int32)
            p_raw = jnp.where(slipped, recheck, p_raw)
            rep = FTReport(rep.detected.at[1].add(n_slip),
                           rep.corrected.at[1].add(n_slip),
                           rep.max_delta)
        p = jnp.where(bm, p_raw, 0.0)

        # --- rescale + ROWSUM (SNVR tracker r: Σ_k e^{m_k - m}) ------------
        alpha = jnp.where(alive, jnp.exp(m_prev - m_new), 1.0)
        row = jnp.sum(p, axis=-1)
        l_new = alpha * l_prev + row
        l_new = inject(l_new, fault, Site.ROWSUM, blk_idx)
        if ft and cfg.shadow_rowsum:
            # Redundant accumulation (barrier defeats CSE on real hardware).
            row_sh = jnp.sum(jax.lax.optimization_barrier(p), axis=-1)
            lsh_new = alpha * lsh_prev + row_sh
        else:
            lsh_new = lsh_prev
        blk_alive = blockmax > MASK_VALUE / 2
        r_new = alpha * r_prev + jnp.where(
            blk_alive, jnp.exp(blockmax - m_sub), 0.0)

        # --- GEMM II + rescale, checksums carried along (Alg.1 l.18-21) ----
        pr = p.astype(q.dtype).reshape(b, hkv, grp, sq, block)
        o_blk = jnp.einsum("bkgqc,bkcd->bkgqd", pr, v_j,
                           preferred_element_type=jnp.float32)
        o_new = alpha[..., None] * o_prev + o_blk.reshape(b, h, sq, d)
        o_new = inject(o_new, fault, Site.GEMM2, blk_idx)
        if ft:
            oc1_blk = jnp.einsum("bkgqc,bkcs->bkgqs", pr, vc.c1,
                                 preferred_element_type=jnp.float32)
            oc2_blk = jnp.einsum("bkgqc,bkcs->bkgqs", pr, vc.c2,
                                 preferred_element_type=jnp.float32)
            oc1 = alpha[..., None] * oc1 + oc1_blk.reshape(b, h, sq, s_out)
            oc2 = alpha[..., None] * oc2 + oc2_blk.reshape(b, h, sq, s_out)
            if not cfg.unified:
                # Unoptimized EFTA (paper Tables 1-2 baseline): verify the
                # output checksum at EVERY kv step instead of once at the end.
                d1o = oc1 - cks.fold1(o_new, s_out)
                bad_o = jnp.abs(d1o) > eps3 * jnp.maximum(
                    jnp.abs(oc1), 1.0)
                rep = FTReport(
                    rep.detected.at[4].add(bad_o.sum(dtype=jnp.int32)),
                    rep.corrected,
                    rep.max_delta)

        return (m_new, l_new, lsh_new, r_new, o_new, oc1, oc2, rep), None

    init = (
        jnp.full((b, h, sq), MASK_VALUE, jnp.float32),
        jnp.zeros((b, h, sq), jnp.float32),
        jnp.zeros((b, h, sq), jnp.float32),
        jnp.zeros((b, h, sq), jnp.float32),
        jnp.zeros((b, h, sq, d), jnp.float32),
        jnp.zeros((b, h, sq, s_out), jnp.float32),
        jnp.zeros((b, h, sq, s_out), jnp.float32),
        FTReport.zero(),
    )
    if kvp_blocks is not None:
        xs = (jnp.arange(nblk, dtype=jnp.int32), kb, vb, kvp_blocks)
    else:
        xs = (jnp.arange(nblk, dtype=jnp.int32), kb, vb)
    (m_f, l_f, lsh_f, r_f, o_f, oc1, oc2, rep), _ = jax.lax.scan(
        body, init, xs, unroll=True if cfg.unroll else 1)

    # --- SNVR range restriction on the final rowsum (Alg.1 lines 22-24) ----
    if ft:
        n_keys = kv_len if kv_len is not None else skv
        upper = jnp.asarray(n_keys, jnp.float32) + 1e-3
        in_range = (l_f >= r_f - 1e-3) & (l_f <= upper) & jnp.isfinite(l_f)
        if cfg.shadow_rowsum:
            rel = jnp.maximum(jnp.abs(lsh_f), 1e-6)
            mismatch = jnp.abs(l_f - lsh_f) > 1e-5 * rel
            bad_l = ((~in_range) | mismatch) & (r_f > 0)
            fallback = jnp.where(
                (lsh_f >= r_f - 1e-3) & (lsh_f <= upper) & jnp.isfinite(lsh_f),
                lsh_f, r_f)
        else:
            bad_l = (~in_range) & (r_f > 0)
            fallback = r_f  # paper-faithful analytic approximation
        n_rowsum = bad_l.sum(dtype=jnp.int32)
        if correct:
            l_f = jnp.where(bad_l, fallback, l_f)
        rep = FTReport(
            rep.detected.at[3].add(n_rowsum),
            rep.corrected.at[3].add(n_rowsum if correct else 0),
            rep.max_delta,
        )

    # --- normalization, applied to output and its checksums alike ----------
    l_safe = jnp.where(l_f == 0, 1.0, l_f)[..., None]
    o_norm = o_f / l_safe

    # --- unified verification of GEMM II + rescale + normalization ---------
    if ft:
        if correct:
            # NVR range restriction on the normalized output: O/l is a
            # convex combination of V rows, so |o_norm| <= max|V|. Zeroing
            # violations (incl. NaN/inf from exponent-bit accumulator
            # corruptions) makes the output-checksum delta equal the *true*
            # value, so the unified correction below restores it exactly —
            # without this, a 1e38-magnitude corruption is "corrected" by
            # adding a delta that catastrophically cancels (residual = the
            # whole true value). Same trick as the GEMM1 score clip.
            vbound = jnp.max(jnp.abs(v.astype(jnp.float32))) * 1.001 + 1e-6
            o_norm = jnp.where(
                jnp.isfinite(o_norm) & (jnp.abs(o_norm) <= vbound),
                o_norm, 0.0)
        oc1_n = oc1 / l_safe
        oc2_n = oc2 / l_safe
        verdict = cks.verify_and_correct(
            o_norm, cks.Checksums(oc1_n, oc2_n), s_out,
            threshold=eps3, correct=correct)
        o_norm = verdict.corrected
        rep = FTReport(
            rep.detected.at[4].add(verdict.n_detected),
            rep.corrected.at[4].add(verdict.n_detected if correct else 0),
            rep.max_delta.at[2].max(verdict.max_delta),
        )

    return o_norm.astype(q.dtype), rep


def efta_mha(q, k, v, *, cfg: EFTAConfig, **kw):
    """Convenience wrapper returning only the output (report discarded)."""
    return efta_attention(q, k, v, cfg=cfg, **kw)[0]
