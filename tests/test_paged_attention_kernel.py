"""Parity + fault harness for the fused block-table EFTA paged-attention
kernel (``repro.kernels.efta_paged``).

The contract under test: for any block-table layout (permuted / fragmented),
any ragged per-request length, and any GQA head ratio, the fused kernel is
numerically interchangeable with the contiguous path —

    fused(bt)  ==  EFTA(gather_block_kv(pool, bt))  ==  reference softmax

with zero false-positive detections on clean pools; a resident pool bit flip
is flagged at the exact (request, table-slot) it occupies by the in-loop
verify (report-tile site 6, ``kv``); and in-compute SEUs at the five paper
sites behave exactly as in the contiguous EFTA kernel (corrected in
``correct`` mode, flagged in ``detect`` mode).

The kernel streams compute blocks of ``P = 128 // block_size`` table slots
(capped at the table length); the parity matrices run tables of one block
and of several, whose length ``P`` does not divide, and the fault tests
hold the kernel's counts to the one-page schedule (``pages=1``) with SEUs on
a page inside a block and on the last, partial block.
"""
import sys

import numpy as np
import pytest

sys.path.insert(0, __file__.rsplit("/", 1)[0])
from _propcheck import given, settings, st  # noqa: E402


def _make_case(seed, *, B, mb, bs, hkv, grp, hd, cs, fragment=True,
               stale_scale=1.0, lens=None):
    """Random pool + fragmented tables + ragged lengths (or ``lens``). Pool
    rows past each request's valid prefix hold *stale* data (recycled-block
    model) scaled by ``stale_scale`` — the kernel must mask them out, not
    read zeros."""
    import jax.numpy as jnp
    from repro.core import checksum as cks

    rng = np.random.default_rng(seed)
    per_req = (list(lens) if lens is not None else
               [int(rng.integers(1, mb * bs + 1)) for _ in range(B)])
    n_real = sum(-(-t // bs) for t in per_req)
    nb = n_real + 3                     # headroom: unmapped blocks stay stale
    ids = np.arange(1, nb + 1)
    if fragment:
        rng.shuffle(ids)
    bt = np.zeros((B, mb), np.int32)
    used = 0
    for i, t in enumerate(per_req):
        n = -(-t // bs)
        bt[i, :n] = ids[used:used + n]
        used += n
    pool_k = (rng.standard_normal((nb + 1, hkv, bs, hd)) * stale_scale
              ).astype(np.float32)
    pool_v = (rng.standard_normal((nb + 1, hkv, bs, hd)) * stale_scale
              ).astype(np.float32)
    if stale_scale != 1.0:
        # valid prefixes at unit scale; only rows past kv_len stay loud
        for i, t in enumerate(per_req):
            for j in range(-(-t // bs)):
                fill = min(bs, t - j * bs)
                for p in (pool_k, pool_v):
                    p[bt[i, j], :, :fill, :] = rng.standard_normal(
                        (hkv, fill, hd)).astype(np.float32)
    kc = cks.encode_kv(jnp.asarray(pool_k), cs)
    vc = cks.encode_kv(jnp.asarray(pool_v), cs)
    q = rng.standard_normal((B, hkv * grp, hd)).astype(np.float32)
    return (jnp.asarray(q), jnp.asarray(pool_k), jnp.asarray(pool_v), kc, vc,
            jnp.asarray(bt), jnp.asarray(per_req, jnp.int32))


def _oracles(q, pool_k, pool_v, bt, kv_lens, *, cfg):
    """Per-request contiguous oracles: gather + pure-JAX EFTA, and the naive
    reference."""
    import numpy as np
    from repro.core.efta import efta_attention, reference_attention
    from repro.kernels.ops import gather_block_kv

    outs, refs = [], []
    for i in range(q.shape[0]):
        _, kg = gather_block_kv(pool_k[None], bt[i])
        _, vg = gather_block_kv(pool_v[None], bt[i])
        qi = q[i][None, :, None, :]
        o, rep = efta_attention(qi, kg, vg, cfg=cfg, kv_len=int(kv_lens[i]))
        assert int(np.sum(np.asarray(rep.detected))) == 0, \
            "oracle EFTA false positive"
        outs.append(np.asarray(o)[0, :, 0, :])
        refs.append(np.asarray(reference_attention(
            qi, kg, vg, kv_len=int(kv_lens[i])))[0, :, 0, :])
    return np.stack(outs), np.stack(refs)


@pytest.fixture(scope="module")
def std_case():
    """One standard shape (GQA 2:1, 3 fragmented tables, ragged lengths)
    with its jitted kernel — compiled once, shared by the quick tests."""
    import functools
    import jax
    from repro.core.efta import EFTAConfig
    from repro.kernels.efta_paged import efta_paged_attention_pallas

    B, mb, bs, hkv, grp, hd, cs = 3, 3, 16, 2, 2, 16, 8
    cfg = EFTAConfig(mode="correct", stride=8, block_kv=bs)
    case = _make_case(7, B=B, mb=mb, bs=bs, hkv=hkv, grp=grp, hd=hd, cs=cs)
    fn = jax.jit(functools.partial(efta_paged_attention_pallas, cfg=cfg,
                                   interpret=True))
    fn_fault = jax.jit(lambda *a, fault: efta_paged_attention_pallas(
        *a, cfg=cfg, fault=fault, interpret=True))
    return case, cfg, fn, fn_fault


@pytest.mark.quick
def test_fused_matches_gather_efta_and_reference(std_case):
    (q, pk, pv, kc, vc, bt, lens), cfg, fn, _ = std_case
    rep = fn(q, pk, pv, kc, vc, bt, lens)
    efta_out, ref_out = _oracles(q, pk, pv, bt, lens, cfg=cfg)
    got = np.asarray(rep.out)
    # same KV blocking + same f32 accumulation order as the pure-JAX scan
    np.testing.assert_allclose(got, efta_out, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(got, ref_out, atol=1e-4, rtol=1e-4)
    assert np.asarray(rep.detected).sum() == 0      # no false positives
    assert not np.asarray(rep.bad_blocks).any()


@pytest.mark.quick
def test_resident_flip_flagged_at_exact_block(std_case):
    """A pool SEU between steps: the in-loop verify must flag exactly the
    (request, table-slot) holding the flipped block — nothing else — and
    count it at report site 6 (kv)."""
    import jax.numpy as jnp
    from repro.core.fault import flip_bit_at

    (q, pk, pv, kc, vc, bt, lens), cfg, fn, _ = std_case
    rng = np.random.default_rng(3)
    bt_np, lens_np = np.asarray(bt), np.asarray(lens)
    hkv, bs, hd = pk.shape[1], pk.shape[2], pk.shape[3]
    for trial in range(6):
        b = int(rng.integers(0, q.shape[0]))
        j = int(rng.integers(0, -(-int(lens_np[b]) // bs)))
        fill = min(bs, int(lens_np[b]) - j * bs)
        blk = int(bt_np[b, j])
        flat = (((blk * hkv + int(rng.integers(0, hkv))) * bs
                 + int(rng.integers(0, fill))) * hd
                + int(rng.integers(0, hd)))
        bit = int(rng.integers(24, 31))
        into_k = bool(rng.integers(0, 2))
        pkx = flip_bit_at(pk, jnp.int32(flat), jnp.int32(bit)) if into_k \
            else pk
        pvx = pv if into_k else flip_bit_at(pv, jnp.int32(flat),
                                            jnp.int32(bit))
        rep = fn(q, pkx, pvx, kc, vc, bt, lens)
        bad = np.asarray(rep.bad_blocks)
        det = np.asarray(rep.detected)
        # the flipped block may be shared by no one else: exactly the slots
        # of requests mapping it are flagged (here tables are disjoint)
        assert bad[b, j], f"trial {trial}: flip not flagged"
        assert bad.sum() == 1, f"trial {trial}: spurious flags {bad}"
        assert det[b, 5] >= 1 and det[:, 5].sum() == det[b, 5]


@pytest.mark.quick
def test_checksum_corruption_is_also_detected(std_case):
    """Site 6 covers the checksum words themselves: a flip in the resident
    c1 plane mismatches the recomputed fold exactly like a data flip."""
    import jax.numpy as jnp
    from repro.core import checksum as cks
    from repro.core.fault import flip_bit_at

    (q, pk, pv, kc, vc, bt, lens), cfg, fn, _ = std_case
    blk = int(np.asarray(bt)[1, 0])
    hkv, cs, hd = kc.c1.shape[1], kc.c1.shape[2], kc.c1.shape[3]
    flat = ((blk * hkv + 1) * cs + 2) * hd + 3
    kc_bad = cks.Checksums(flip_bit_at(kc.c1, jnp.int32(flat),
                                       jnp.int32(26)), kc.c2)
    rep = fn(q, pk, pv, kc_bad, vc, bt, lens)
    assert np.asarray(rep.bad_blocks)[1, 0]
    assert np.asarray(rep.detected)[1, 5] >= 1


@pytest.mark.quick
def test_compute_site_seus_corrected_in_kernel(std_case):
    """High-bit SEUs at the five EFTA sites, injected through the fused
    kernel's descriptor: correct mode repairs in-kernel (output still matches
    the oracle) and reports the site."""
    import jax.numpy as jnp
    from repro.core.fault import Site

    (q, pk, pv, kc, vc, bt, lens), cfg, fn, fn_fault = std_case
    efta_out, _ = _oracles(q, pk, pv, bt, lens, cfg=cfg)
    sites = [Site.GEMM1, Site.EXP, Site.ROWMAX, Site.ROWSUM, Site.GEMM2]
    for site in sites:
        # [site, table_block, b, kv_head, group_row, col, bit, on]
        desc = jnp.asarray([int(site), 0, 1, 1, 1, 3, 27, 1], jnp.int32)
        rep = fn_fault(q, pk, pv, kc, vc, bt, lens, fault=desc)
        err = np.max(np.abs(np.asarray(rep.out) - efta_out))
        det = np.asarray(rep.detected)
        assert err < 1e-3, f"{site.name}: residual {err:.2e}"
        if site != Site.ROWMAX:   # rowmax may cancel analytically (Case 1)
            assert det[1].sum() >= 1, f"{site.name}: no detection"
        assert np.asarray(rep.bad_blocks).sum() == 0   # not a memory fault


def test_detect_mode_flags_without_correcting():
    import functools
    import jax
    import jax.numpy as jnp
    from repro.core.efta import EFTAConfig
    from repro.core.fault import Site
    from repro.kernels.efta_paged import efta_paged_attention_pallas

    cfg = EFTAConfig(mode="detect", stride=8, block_kv=16)
    case = _make_case(11, B=2, mb=2, bs=16, hkv=2, grp=2, hd=16, cs=8)
    fn = jax.jit(functools.partial(efta_paged_attention_pallas, cfg=cfg,
                                   interpret=True))
    q, pk, pv, kc, vc, bt, lens = case
    desc = jnp.asarray([int(Site.GEMM2), 0, 0, 0, 0, 2, 28, 1], jnp.int32)
    rep = jax.jit(functools.partial(
        efta_paged_attention_pallas, cfg=cfg, interpret=True))(
        q, pk, pv, kc, vc, bt, lens, fault=desc)
    assert np.asarray(rep.detected)[0].sum() >= 1
    # clean pool, clean run: no detections in detect mode either
    rep2 = fn(q, pk, pv, kc, vc, bt, lens)
    assert np.asarray(rep2.detected).sum() == 0


@pytest.mark.parametrize("mb,examples", [(3, 6), (19, 3)],
                         ids=["one-block", "table19"])
def test_parity_property_ragged_gqa_fragmented(mb, examples):
    """Property sweep: random ragged lengths, permuted/fragmented tables,
    MHA/GQA/MQA ratios, two block sizes (P = 16 and 8 pages a compute
    block) and two table lengths — one compute block, and 19 slots, which
    neither P divides, so lengths end inside a block and the table is
    padded — fused == gather+EFTA == reference, zero detections. Loud stale
    rows past every valid prefix prove the ragged masking reads nothing it
    shouldn't. The 19-slot table, whose examples cost twice as much, takes
    half as many."""
    @given(st.integers(0, 10_000), st.sampled_from([8, 16]),
           st.sampled_from([(1, 1), (2, 1), (2, 2), (1, 4)]),
           st.booleans())
    @settings(max_examples=examples, deadline=None)
    def check(seed, bs, heads, fragment):
        import functools
        import jax
        from repro.core.efta import EFTAConfig
        from repro.kernels.efta_paged import efta_paged_attention_pallas

        hkv, grp = heads
        cfg = EFTAConfig(mode="correct", stride=8, block_kv=bs)
        case = _make_case(seed, B=2, mb=mb, bs=bs, hkv=hkv, grp=grp, hd=16,
                          cs=min(8, bs), fragment=fragment, stale_scale=50.0)
        q, pk, pv, kc, vc, bt, lens = case
        rep = jax.jit(functools.partial(
            efta_paged_attention_pallas, cfg=cfg, interpret=True))(
            q, pk, pv, kc, vc, bt, lens)
        efta_out, ref_out = _oracles(q, pk, pv, bt, lens, cfg=cfg)
        got = np.asarray(rep.out)
        np.testing.assert_allclose(got, efta_out, atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(got, ref_out, atol=1e-4, rtol=1e-4)
        assert np.asarray(rep.detected).sum() == 0
        assert not np.asarray(rep.bad_blocks).any()

    check()


def _chunk_oracle(q, pool_k, pool_v, bt, kv_lens, q_lens, *, cfg,
                  window=None):
    """Row-by-row oracle for the multi-token chunk: chunk row c of request i
    is exactly a single-token decode at kv_len = base + c + 1 (same blocks,
    same accumulation order), so the unified kernel must reproduce the
    sequential decode bit pattern the serve engines are pinned to."""
    from repro.core.efta import efta_attention
    from repro.kernels.ops import gather_block_kv

    B, H, C, hd = q.shape
    out = np.zeros((B, H, C, hd), np.float32)
    for i in range(B):
        _, kg = gather_block_kv(pool_k[None], bt[i])
        _, vg = gather_block_kv(pool_v[None], bt[i])
        base = int(kv_lens[i]) - int(q_lens[i])
        for c in range(int(q_lens[i])):
            qi = q[i, :, c][None, :, None, :]
            o, rep = efta_attention(
                qi, kg, vg, cfg=cfg, kv_len=base + c + 1, window=window,
                causal=window is not None, q_offset=base + c)
            assert int(np.sum(np.asarray(rep.detected))) == 0
            out[i, :, c] = np.asarray(o)[0, :, 0, :]
    return out


@pytest.mark.quick
def test_chunked_q_matches_per_row_decode_oracle():
    """The unified multi-token contract at one standard shape: a C-row chunk
    per request equals C sequential single-token decodes — including rows
    whose chunk straddles a block edge — with zero detections and rows past
    q_len emitting exactly zero."""
    import functools
    import jax
    import jax.numpy as jnp
    from repro.core.efta import EFTAConfig
    from repro.kernels.efta_paged import efta_paged_attention_pallas

    B, mb, bs, hkv, grp, hd, cs = 3, 3, 16, 2, 2, 16, 8
    cfg = EFTAConfig(mode="correct", stride=8, block_kv=bs)
    _, pk, pv, kc, vc, bt, lens = _make_case(
        7, B=B, mb=mb, bs=bs, hkv=hkv, grp=grp, hd=hd, cs=cs,
        stale_scale=50.0)
    rng = np.random.default_rng(2)
    C = 7                                # 7 rows over 16-blocks: straddles
    lens_np = np.asarray(lens)
    q_lens = np.minimum(C - 1, lens_np).astype(np.int32)  # also pad a row
    q = jnp.asarray(rng.standard_normal((B, hkv * grp, C, hd))
                    .astype(np.float32))
    rep = jax.jit(functools.partial(
        efta_paged_attention_pallas, cfg=cfg, interpret=True))(
        q, pk, pv, kc, vc, bt, lens, jnp.asarray(q_lens))
    got = np.asarray(rep.out)
    assert got.shape == (B, hkv * grp, C, hd)
    oracle = _chunk_oracle(q, pk, pv, bt, lens_np, q_lens, cfg=cfg)
    for i in range(B):
        n = int(q_lens[i])
        np.testing.assert_allclose(got[i, :, :n], oracle[i, :, :n],
                                   atol=2e-5, rtol=2e-5)
        assert not got[i, :, n:].any()   # padding rows are exactly zero
    assert np.asarray(rep.detected).sum() == 0
    assert not np.asarray(rep.bad_blocks).any()


def _chunk_reference(q, pool_k, pool_v, bt, kv_lens, q_lens, window=None):
    """Float64 softmax attention of every valid chunk row over its own
    causal (and windowed) prefix of the gathered table; zero elsewhere."""
    B, H, C, hd = q.shape
    hkv = pool_k.shape[1]
    grp = H // hkv
    out = np.zeros((B, H, C, hd))
    for i in range(B):
        k = np.asarray(pool_k, np.float64)[np.asarray(bt)[i]]
        v = np.asarray(pool_v, np.float64)[np.asarray(bt)[i]]
        k = k.transpose(1, 0, 2, 3).reshape(hkv, -1, hd)
        v = v.transpose(1, 0, 2, 3).reshape(hkv, -1, hd)
        base = int(kv_lens[i]) - int(q_lens[i])
        for c in range(int(q_lens[i])):
            pos = base + c
            j = np.arange(pos + 1)
            if window is not None:
                j = j[pos - j < window]
            for hq in range(H):
                s = k[hq // grp, j] @ np.asarray(q, np.float64)[i, hq, c]
                w = np.exp((s - s.max()) / np.sqrt(hd))
                out[i, hq, c] = (w / w.sum()) @ v[hq // grp, j]
    return out


@pytest.mark.parametrize("mb,chunks,examples", [(3, (3, 8, 13), 6),
                                                (19, (1,), 3),
                                                (19, (32,), 3)],
                         ids=["one-block", "table19-chunk1",
                              "table19-chunk32"])
def test_chunked_parity_property_matrix(mb, chunks, examples):
    """Property sweep of the unified kernel: chunk widths 1 to 32 x block
    sizes (P = 16 and 8) x tables of one compute block and of 19 slots
    (padded, lengths ending inside a block) x MHA/GQA/MQA x sliding windows
    x ragged lengths x fragmented tables, chunk boundaries landing mid-block
    — every valid row equals float64 softmax attention over its own
    prefix, zero detections, loud stale rows never read. The 19-slot
    tables, whose examples cost twice as much, take half as many."""
    @given(st.integers(0, 10_000), st.sampled_from([8, 16]),
           st.sampled_from([(1, 1), (2, 2), (1, 4)]),
           st.sampled_from([None, 9, 40]))
    @settings(max_examples=examples, deadline=None)
    def check(seed, bs, heads, window):
        import functools
        import jax
        import jax.numpy as jnp
        from repro.core.efta import EFTAConfig
        from repro.kernels.efta_paged import efta_paged_attention_pallas

        hkv, grp = heads
        chunk = chunks[seed % len(chunks)]
        cfg = EFTAConfig(mode="correct", stride=8, block_kv=bs)
        _, pk, pv, kc, vc, bt, lens = _make_case(
            seed, B=2, mb=mb, bs=bs, hkv=hkv, grp=grp, hd=16, cs=min(8, bs),
            stale_scale=50.0)
        rng = np.random.default_rng(seed + 1)
        lens_np = np.asarray(lens)
        q_lens = np.minimum(
            rng.integers(1, chunk + 1, size=lens_np.shape), lens_np
        ).astype(np.int32)
        q = jnp.asarray(rng.standard_normal(
            (2, hkv * grp, chunk, 16)).astype(np.float32))
        win = None if window is None else jnp.int32(window)
        rep = jax.jit(functools.partial(
            efta_paged_attention_pallas, cfg=cfg, interpret=True))(
            q, pk, pv, kc, vc, bt, lens, jnp.asarray(q_lens), window=win)
        got = np.asarray(rep.out)
        ref = _chunk_reference(q, pk, pv, bt, lens_np, q_lens, window=window)
        for i in range(2):
            n = int(q_lens[i])
            np.testing.assert_allclose(got[i, :, :n], ref[i, :, :n],
                                       atol=2e-5, rtol=2e-5)
            assert not got[i, :, n:].any()   # padding rows are exactly zero
        assert np.asarray(rep.detected).sum() == 0
        assert not np.asarray(rep.bad_blocks).any()

    check()


def test_chunked_sliding_window_and_idle_rows():
    """Chunk rows apply the sliding window at their own positions (not the
    batch max), and a q_len == 0 request contributes nothing while its
    resident blocks still stream through the in-loop verify."""
    import functools
    import jax
    import jax.numpy as jnp
    from repro.core.efta import EFTAConfig
    from repro.kernels.efta_paged import efta_paged_attention_pallas

    cfg = EFTAConfig(mode="correct", stride=8, block_kv=16)
    _, pk, pv, kc, vc, bt, lens = _make_case(
        5, B=3, mb=3, bs=16, hkv=2, grp=2, hd=16, cs=8)
    rng = np.random.default_rng(9)
    C, win = 5, 9
    lens_np = np.asarray(lens)
    q_lens = np.minimum(C, lens_np).astype(np.int32)
    q_lens[2] = 0                        # idle slot in the mixed batch
    q = jnp.asarray(rng.standard_normal((3, 4, C, 16)).astype(np.float32))
    rep = jax.jit(functools.partial(
        efta_paged_attention_pallas, cfg=cfg, interpret=True))(
        q, pk, pv, kc, vc, bt, lens, jnp.asarray(q_lens),
        window=jnp.int32(win))
    got = np.asarray(rep.out)
    oracle = _chunk_oracle(q, pk, pv, bt, lens_np, q_lens, cfg=cfg,
                           window=win)
    for i in range(2):
        n = int(q_lens[i])
        np.testing.assert_allclose(got[i, :, :n], oracle[i, :, :n],
                                   atol=2e-5, rtol=2e-5)
    assert not got[2].any()              # idle request: all-zero output
    assert np.asarray(rep.detected).sum() == 0

    # the idle request's resident corruption is still caught in-loop
    from repro.core.fault import flip_bit_at
    blk = int(np.asarray(bt)[2, 0])
    hkv_, bs_, hd_ = pk.shape[1], pk.shape[2], pk.shape[3]
    flat = ((blk * hkv_ + 0) * bs_ + 0) * hd_ + 1
    pk_bad = flip_bit_at(pk, jnp.int32(flat), jnp.int32(27))
    rep2 = jax.jit(functools.partial(
        efta_paged_attention_pallas, cfg=cfg, interpret=True))(
        q, pk_bad, pv, kc, vc, bt, lens, jnp.asarray(q_lens))
    assert np.asarray(rep2.bad_blocks)[2, 0]
    assert np.asarray(rep2.detected)[2, 5] >= 1


def test_chunked_compute_site_seus_corrected():
    """Compute-site SEUs injected into a chunk row (tile row = group_row *
    C + chunk_row): correct mode repairs in-kernel and reports the site,
    exactly as on the decode path."""
    import functools
    import jax
    import jax.numpy as jnp
    from repro.core.efta import EFTAConfig
    from repro.core.fault import Site
    from repro.kernels.efta_paged import efta_paged_attention_pallas

    cfg = EFTAConfig(mode="correct", stride=8, block_kv=16)
    _, pk, pv, kc, vc, bt, lens = _make_case(
        11, B=2, mb=3, bs=16, hkv=2, grp=2, hd=16, cs=8)
    rng = np.random.default_rng(4)
    C = 6
    lens_np = np.asarray(lens)
    q_lens = np.minimum(C, lens_np).astype(np.int32)
    q = jnp.asarray(rng.standard_normal((2, 4, C, 16)).astype(np.float32))
    fn = jax.jit(lambda *a, fault: efta_paged_attention_pallas(
        *a, cfg=cfg, fault=fault, interpret=True))
    oracle = _chunk_oracle(q, pk, pv, bt, lens_np, q_lens, cfg=cfg)
    for site in (Site.GEMM1, Site.EXP, Site.ROWSUM, Site.GEMM2):
        # tile row 1*C + 2: group row 1, chunk row 2 (a valid row)
        desc = jnp.asarray([int(site), 0, 1, 1, 1 * C + 2, 3, 27, 1],
                           jnp.int32)
        rep = fn(q, pk, pv, kc, vc, bt, lens, jnp.asarray(q_lens),
                 fault=desc)
        got = np.asarray(rep.out)
        n = int(q_lens[1])
        err = np.max(np.abs(got[1, :, :n] - oracle[1, :, :n]))
        assert err < 1e-3, f"{site.name}: residual {err:.2e}"
        assert np.asarray(rep.detected)[1].sum() >= 1, site.name
        assert np.asarray(rep.bad_blocks).sum() == 0


def test_sliding_window_masks_like_the_contiguous_path():
    """Per-request window masking (traced window scalar, as the per-layer
    global/local selection passes it)."""
    import functools
    import jax
    import jax.numpy as jnp
    from repro.core.efta import EFTAConfig, efta_attention
    from repro.kernels.efta_paged import efta_paged_attention_pallas
    from repro.kernels.ops import gather_block_kv

    cfg = EFTAConfig(mode="correct", stride=8, block_kv=16)
    q, pk, pv, kc, vc, bt, lens = _make_case(
        5, B=2, mb=3, bs=16, hkv=2, grp=2, hd=16, cs=8)
    win = 9
    rep = jax.jit(functools.partial(
        efta_paged_attention_pallas, cfg=cfg, interpret=True))(
        q, pk, pv, kc, vc, bt, lens, window=jnp.int32(win))
    for i in range(2):
        _, kg = gather_block_kv(pk[None], bt[i])
        _, vg = gather_block_kv(pv[None], bt[i])
        o, _ = efta_attention(q[i][None, :, None, :], kg, vg, cfg=cfg,
                              kv_len=int(lens[i]), window=win,
                              causal=True, q_offset=int(lens[i]) - 1)
        np.testing.assert_allclose(np.asarray(rep.out)[i],
                                   np.asarray(o)[0, :, 0, :],
                                   atol=2e-5, rtol=2e-5)
    assert np.asarray(rep.detected).sum() == 0


# ---- compute blocks against the one-page schedule --------------------------
#
# The oracle is the framework's pure-JAX EFTA (``repro.core.efta``) over the
# gathered table with ``block_kv = block_size``: one page a KV block, so every
# check of the one-page schedule, with the same strides and thresholds; and,
# for resident flips, the gather path's ``verify_block`` of each page.

BLOCK_LENS = (19 * 16 - 5, 11 * 16 + 3)   # slot 18 of 19, slot 11
# page 5 of compute block 1; page 2 of block 2, the last and partial one
IN_BLOCK, LAST_BLOCK = 13, 18


@pytest.fixture(scope="module", params=[1, 32], ids=["chunk1", "chunk32"])
def block_case(request):
    """Two requests over a 19-slot table at block 16 (P = 8: compute blocks
    of slots 0-7, 8-15 and 16-18 of a padded 24), GQA 2:1, chunk 1 or 32;
    the kernel jitted at the derived P, and request 0's one-page oracle."""
    import jax
    import jax.numpy as jnp
    from repro.core.efta import EFTAConfig, efta_attention
    from repro.kernels.efta_paged import (efta_paged_attention_pallas,
                                          pages_per_block)
    from repro.kernels.ops import gather_block_kv

    chunk = request.param
    B, mb, bs, hkv, grp, hd, cs = 2, 19, 16, 2, 2, 16, 8
    assert pages_per_block(bs, mb) == 8
    cfg = EFTAConfig(mode="correct", stride=8, block_kv=bs)
    _, pk, pv, kc, vc, bt, lens = _make_case(
        21, B=B, mb=mb, bs=bs, hkv=hkv, grp=grp, hd=hd, cs=cs,
        lens=BLOCK_LENS)
    rng = np.random.default_rng(22)
    q = jnp.asarray(rng.standard_normal((B, hkv * grp, chunk, hd))
                    .astype(np.float32))
    q_lens = jnp.asarray([chunk, max(chunk // 4, 1)], jnp.int32)
    fused = jax.jit(lambda q, pk, pv, kc, vc, fault: (
        efta_paged_attention_pallas(q, pk, pv, kc, vc, bt, lens, q_lens,
                                    cfg=cfg, fault=fault, interpret=True)))
    # request 0 feeds the whole chunk: rows at kv_len - chunk + c
    _, kg = gather_block_kv(pk[None], bt[0])
    _, vg = gather_block_kv(pv[None], bt[0])
    n = int(lens[0])
    one_page = jax.jit(lambda fault: efta_attention(
        q[:1], kg, vg, cfg=cfg, causal=True, kv_len=n, q_offset=n - chunk,
        fault=fault))
    return dict(q=q, pk=pk, pv=pv, kc=kc, vc=vc, bt=bt, lens=lens, cs=cs,
                chunk=chunk, grp=grp, q_lens=np.asarray(q_lens),
                fused=fused, one_page=one_page)


@pytest.mark.parametrize("slot", [IN_BLOCK, LAST_BLOCK],
                         ids=["page5-of-block1", "last-partial-block"])
@pytest.mark.parametrize("site", ["GEMM1", "EXP", "ROWMAX", "ROWSUM",
                                  "GEMM2"])
def test_compute_seus_counted_as_by_the_one_page_schedule(block_case, site,
                                                          slot):
    """An SEU on a page inside a compute block, or on the last, partial
    block: the kernel detects (and, in correct mode, repairs) it as the
    one-page schedule does — the same counts at every site, the strike
    counted, nothing on the other request, an empty bad plane — and its
    output is the oracle's faulted, corrected one."""
    import jax.numpy as jnp
    from repro.core.fault import FaultSpec, Site

    c = block_case
    chunk, grp = c["chunk"], c["grp"]
    row = min(2, chunk - 1)
    # kernel: [site, table_block, b, kv_head 1, tile row (group row 1,
    # chunk row `row`), col, bit, on]; oracle: query head 1 * grp + 1
    desc = jnp.asarray([int(Site[site]), slot, 0, 1, chunk + row, 3, 27, 1],
                       jnp.int32)
    got = c["fused"](c["q"], c["pk"], c["pv"], c["kc"], c["vc"], desc)
    o_ref, rep_ref = c["one_page"](FaultSpec.single(
        Site[site], block=slot, head=grp + 1, row=row, col=3, bit=27))
    det, det_ref = np.asarray(got.detected), np.asarray(rep_ref.detected)
    np.testing.assert_array_equal(det[0, :5], det_ref)
    np.testing.assert_array_equal(np.asarray(rep_ref.corrected), det_ref)
    report_index = ["GEMM1", "EXP", "ROWMAX", "ROWSUM", "GEMM2"].index(site)
    assert det_ref[report_index] >= 1, "the strike was not counted"
    assert det[0, 5] == 0 and not det[1].any()
    assert not np.asarray(got.bad_blocks).any()
    np.testing.assert_allclose(np.asarray(got.out)[0],
                               np.asarray(o_ref)[0], atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("slot", [IN_BLOCK, LAST_BLOCK],
                         ids=["page5-of-block1", "last-partial-block"])
@pytest.mark.parametrize("into", ["k", "v"])
def test_resident_flip_sets_exactly_its_slot(block_case, into, slot):
    """A resident K or V flip on a page inside a compute block, or on the
    last, partial one: the bad plane holds exactly that (request, table
    slot), and site 6 counts what the gather path's ``verify_block`` of
    each live page of each head flags."""
    import jax.numpy as jnp
    from repro.core import checksum as cks
    from repro.core.fault import flip_bit_at

    c = block_case
    pk, pv = c["pk"], c["pv"]
    hkv, bs, hd = pk.shape[1], pk.shape[2], pk.shape[3]
    bt, lens = np.asarray(c["bt"]), np.asarray(c["lens"])
    blk = int(bt[0, slot])
    flat = ((blk * hkv + 1) * bs + 3) * hd + 5     # head 1, row 3, col 5
    hit = flip_bit_at(pk if into == "k" else pv, jnp.int32(flat),
                      jnp.int32(28))
    pk, pv = (hit, pv) if into == "k" else (pk, hit)
    got = c["fused"](c["q"], pk, pv, c["kc"], c["vc"],
                     jnp.zeros((8,), jnp.int32))
    thr = cks.kv_block_threshold(pk.dtype)
    flagged = np.zeros(bt.shape + (hkv,), bool)
    for b in range(bt.shape[0]):
        live = -(-int(lens[b]) // bs)
        for x, checks in ((pk, c["kc"]), (pv, c["vc"])):
            ids = bt[b, :live]
            bad, _ = cks.verify_block(
                x[ids], cks.Checksums(checks.c1[ids], checks.c2[ids]),
                c["cs"], threshold=thr)
            flagged[b, :live] |= np.asarray(bad)
    assert np.argwhere(flagged.any(-1)).tolist() == [[0, slot]]
    np.testing.assert_array_equal(np.asarray(got.bad_blocks),
                                  flagged.any(-1))
    det = np.asarray(got.detected)
    np.testing.assert_array_equal(det[:, 5], flagged.sum(axis=(1, 2)))


@given(st.integers(0, 10_000), st.sampled_from([4, 8, 16, 32]),
       st.sampled_from([None, 5, 40, 300]))
@settings(max_examples=20, deadline=None)
def test_only_live_pages_are_copied(seed, bs, window):
    """The live slots are the one-page kernel's: slot j runs when
    ``j * bs < kv_len`` and its last key is inside the earliest row's
    window. Walking the grid in order, the pool row a compute block's slot
    names changes only to a live page of that slot — the pipeline, which
    copies a block when its index changes, copies no other page — and names
    every live page when its block runs."""
    import jax.numpy as jnp
    from repro.kernels.efta_paged import (NO_WINDOW, _live_slots,
                                          _source_pages, pages_per_block)

    rng = np.random.default_rng(seed)
    B, mb = 5, 21
    pages = pages_per_block(bs, mb)
    n_pad = -(-mb // pages) * pages
    kv = rng.integers(0, mb * bs + 1, size=B).astype(np.int32)
    ql = np.minimum(rng.integers(0, 3 * bs, size=B), kv).astype(np.int32)
    bt = np.zeros((B, n_pad), np.int32)
    bt[:, :mb] = rng.permutation(np.arange(1, B * mb + 1)).reshape(B, mb)
    win = NO_WINDOW if window is None else window
    lo, hi = _live_slots(jnp.asarray(kv), jnp.asarray(ql), jnp.int32(win),
                         block_size=bs)
    src = np.asarray(_source_pages(jnp.asarray(bt), lo, hi, pages))
    lo, hi = np.asarray(lo), np.asarray(hi)
    prev = [None] * pages
    for b in range(B):
        j = np.arange(n_pad)
        runs = (j * bs < kv[b]) & (kv[b] - ql[b] - (j * bs + bs - 1) < win)
        np.testing.assert_array_equal(runs, (j >= lo[b]) & (j < hi[b]))
        live_rows = {p: {int(bt[b, x]) for x in j[runs & (j % pages == p)]}
                     for p in range(pages)}
        for i in range(n_pad // pages):
            for p in range(pages):
                row = int(src[b, i * pages + p])
                if runs[i * pages + p]:
                    assert row == bt[b, i * pages + p]
                if row != prev[p]:
                    assert row in live_rows[p] or (
                        not live_rows[p] and row == 0), (b, i, p, row)
                prev[p] = row
