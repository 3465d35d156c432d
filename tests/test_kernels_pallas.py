"""Pallas EFTA kernel vs pure-jnp oracle (interpret mode), shape/dtype sweep
plus in-kernel fault injection at every site."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import EFTAConfig
from repro.kernels import efta_attention_pallas
from repro.kernels.ref import attention_ref


def qkv(b, h, hkv, s, d, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (b, h, s, d), dtype),
            jax.random.normal(ks[1], (b, hkv, s, d), dtype),
            jax.random.normal(ks[2], (b, hkv, s, d), dtype))


SWEEP = [
    # (b, h, hkv, s, d, block_q, block_kv, stride)
    (1, 2, 2, 128, 32, 64, 64, 8),
    (2, 4, 2, 256, 64, 128, 128, 8),
    (1, 4, 1, 256, 128, 128, 256, 128),
    (1, 2, 2, 512, 64, 128, 128, 16),
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,h,hkv,s,d,bq,bkv,stride", SWEEP)
def test_kernel_vs_oracle(b, h, hkv, s, d, bq, bkv, stride, dtype):
    q, k, v = qkv(b, h, hkv, s, d, dtype)
    cfg = EFTAConfig(mode="correct", stride=stride, block_kv=bkv)
    out, det = efta_attention_pallas(q, k, v, cfg=cfg, block_q=bq)
    ref = attention_ref(q, k, v)
    tol = 2e-6 if dtype == jnp.float32 else 2.5e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol)
    assert int(det.sum()) == 0


@pytest.mark.parametrize("causal,window", [(True, None), (True, 96),
                                           (False, None)])
def test_kernel_masks(causal, window):
    q, k, v = qkv(1, 2, 2, 256, 32, jnp.float32)
    cfg = EFTAConfig(mode="correct", stride=8, block_kv=64)
    out, _ = efta_attention_pallas(q, k, v, cfg=cfg, causal=causal,
                                   window=window, block_q=64)
    ref = attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(out, ref, atol=2e-6)


@pytest.mark.parametrize("site", [0, 1, 2, 3, 4])
def test_kernel_fault_injection(site):
    q, k, v = qkv(1, 4, 2, 256, 64, jnp.float32)
    cfg = EFTAConfig(mode="correct", stride=8, block_kv=128)
    ref = attention_ref(q, k, v)
    fault = jnp.array([site, 1, 2, 130, 21, 27, 1, 0], jnp.int32)
    out, det = efta_attention_pallas(q, k, v, cfg=cfg, fault=fault,
                                     block_q=128)
    err = float(jnp.max(jnp.abs(out - ref)))
    assert err < 5e-3, f"site {site}: err {err}"


@pytest.mark.parametrize("h,hkv", [(8, 2), (4, 1), (6, 3)])
def test_kernel_gqa_grouping(h, hkv):
    """GQA/MQA head grouping parity vs the oracle (previously only covered
    for the pure-JAX path in test_efta.py)."""
    q, k, v = qkv(2, h, hkv, 128, 32, jnp.float32, seed=3)
    cfg = EFTAConfig(mode="correct", stride=8, block_kv=64)
    out, det = efta_attention_pallas(q, k, v, cfg=cfg, block_q=64)
    ref = attention_ref(q, k, v)
    np.testing.assert_allclose(out, ref, atol=3e-6)
    assert int(det.sum()) == 0


@pytest.mark.parametrize("kv_len", [96, 200, 256])
@pytest.mark.parametrize("causal", [False, True])
def test_kernel_ragged_kv_len(kv_len, causal):
    """Serving-style ragged KV: the cache holds 256 block-aligned slots but
    only ``kv_len`` are valid. Must match the oracle's kv_len mask and keep
    a clean detection report (the masked tail is no false-positive source)."""
    from repro.core.efta import reference_attention
    q, k, v = qkv(1, 4, 2, 256, 64, jnp.float32, seed=4)
    cfg = EFTAConfig(mode="correct", stride=8, block_kv=64)
    out, det = efta_attention_pallas(q, k, v, cfg=cfg, causal=causal,
                                     kv_len=kv_len, block_q=128)
    ref = reference_attention(q, k, v, causal=causal, kv_len=kv_len)
    np.testing.assert_allclose(out, ref, atol=3e-6)
    assert int(det.sum()) == 0


def test_kernel_gqa_ragged_combined_matches_jnp_efta():
    """GQA + ragged kv_len together, cross-checked against the pure-JAX EFTA
    twin (both fault-tolerance paths active)."""
    from repro.core.efta import efta_attention
    q, k, v = qkv(1, 8, 2, 256, 32, jnp.float32, seed=5)
    cfg = EFTAConfig(mode="correct", stride=8, block_kv=64)
    out, det = efta_attention_pallas(q, k, v, cfg=cfg, kv_len=130,
                                     block_q=128)
    ref, rep = efta_attention(q, k, v, cfg=cfg, kv_len=130)
    np.testing.assert_allclose(out, ref, atol=3e-6)
    assert int(det.sum()) == 0 and int(rep.detected.sum()) == 0


def test_kernel_ragged_fault_still_corrected():
    """A GEMM1 SEU inside the valid ragged prefix is corrected as usual."""
    q, k, v = qkv(1, 4, 2, 256, 64, jnp.float32, seed=6)
    cfg = EFTAConfig(mode="correct", stride=8, block_kv=64)
    from repro.core.efta import reference_attention
    ref = reference_attention(q, k, v, kv_len=150)
    fault = jnp.array([0, 1, 2, 17, 21, 27, 1, 0], jnp.int32)
    out, det = efta_attention_pallas(q, k, v, cfg=cfg, kv_len=150,
                                     fault=fault, block_q=128)
    err = float(jnp.max(jnp.abs(out - ref)))
    assert err < 5e-3, err
    assert int(det.sum()) >= 1


def test_kernel_off_mode_is_plain_flash():
    q, k, v = qkv(1, 2, 2, 256, 32, jnp.float32)
    cfg = EFTAConfig(mode="off", stride=8, block_kv=64)
    out, det = efta_attention_pallas(q, k, v, cfg=cfg, block_q=64)
    np.testing.assert_allclose(out, attention_ref(q, k, v), atol=2e-6)
    assert int(det.sum()) == 0


def test_kernel_unified_vs_stepwise():
    q, k, v = qkv(1, 2, 2, 256, 32, jnp.float32)
    for unified in (True, False):
        cfg = EFTAConfig(mode="correct", stride=8, block_kv=64,
                         unified=unified)
        out, _ = efta_attention_pallas(q, k, v, cfg=cfg, block_q=64)
        np.testing.assert_allclose(out, attention_ref(q, k, v), atol=2e-6)


@pytest.mark.parametrize("stride,segments,pages", [
    (4, 4, 8),      # the paged kernel's compute block at block 16
    (16, 32, 1),    # the contiguous kernel's 512-key tile
    (3, 12, 2),     # segment counts that are not powers of two
    (8, 6, 3),
    (2, 7, 2),
    (4, 1, 5),
])
def test_fold_helpers_match_numpy(stride, segments, pages):
    """The shared fold helpers on a tile of ``pages`` pages: each fold
    index holds the sum, the (1..g)-weighted sum, the max and the product
    over its page's segments; the spread copies a fold value to every
    segment; and the correction puts a delta back into the segment it came
    from, in every page at once."""
    from jax.experimental import pallas as pl
    from repro.kernels.efta_attention import (_correct, _fold,
                                              _fold_index, _spread)

    w = stride * segments * pages
    rng = np.random.default_rng(stride * 100 + segments)
    x = rng.standard_normal((8, w)).astype(np.float32)

    def folds(x_ref, *outs):
        t = x_ref[...]
        for o, kw in zip(outs, (dict(), dict(weighted=True),
                                dict(op=jnp.maximum), dict(op=jnp.multiply))):
            o[...] = _fold(t, stride, segments, axis=1, **kw)
        at = _fold_index(t.shape, stride, stride * segments)
        outs[4][...] = _spread(jnp.where(at, t, 0.0), stride, segments)

    got = pl.pallas_call(folds, out_shape=[jax.ShapeDtypeStruct(
        x.shape, jnp.float32)] * 5, interpret=True)(x)
    seg = x.reshape(8, pages, segments, stride).astype(np.float64)
    weight = np.arange(1, segments + 1)[None, None, :, None]
    for out, want in zip(got[:4], (seg.sum(2), (seg * weight).sum(2),
                                   seg.max(2), seg.prod(2))):
        np.testing.assert_allclose(
            np.asarray(out).reshape(seg.shape)[:, :, 0], want, rtol=1e-5,
            atol=1e-5)
    np.testing.assert_array_equal(
        np.asarray(got[4]).reshape(seg.shape),
        np.broadcast_to(x.reshape(seg.shape)[:, :, :1], seg.shape))

    # one corrupted element in every page of row 0, deltas from clean folds
    y = x.copy()
    for p in range(pages):
        y[0, (p * segments + rng.integers(segments)) * stride
          + rng.integers(stride)] += 5.0
    clean = np.zeros((2,) + x.shape, np.float32)
    clean[0].reshape(seg.shape)[:, :, 0] = seg.sum(2)
    clean[1].reshape(seg.shape)[:, :, 0] = (seg * weight).sum(2)

    def fix(y_ref, c1_ref, c2_ref, o_ref):
        t = y_ref[...]
        d1 = c1_ref[...] - _fold(t, stride, segments, axis=1)
        d2 = c2_ref[...] - _fold(t, stride, segments, axis=1, weighted=True)
        bad = (jnp.abs(d1) > 1e-3) & _fold_index(t.shape, stride,
                                                  stride * segments)
        o_ref[...] = _correct(t, d1, d2, bad, stride, segments)

    fixed = pl.pallas_call(fix, out_shape=jax.ShapeDtypeStruct(
        x.shape, jnp.float32), interpret=True)(y, clean[0], clean[1])
    np.testing.assert_allclose(np.asarray(fixed), x, atol=1e-4)
