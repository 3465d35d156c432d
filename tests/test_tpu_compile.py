"""Compile-only checks of the Pallas kernels for a TPU v5e chip.

The interpret-mode tests cannot see what Mosaic refuses: block shapes off
the (8, 128) tiling, primitives it has no lowering for, more VMEM than a
kernel may use. These tests compile both kernels at GPT-2 widths for a
*described* v5e chip (``jax.experimental.topologies``) with
``interpret=False``, while the backend stays the CPU — no chip is needed,
and each compile takes a second or two.

The topology is described inside a module fixture, never at import: only
one process may load the TPU compiler library, and under pytest-xdist every
worker imports this module.
"""
import os
import re

import pytest


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    # the TPU compiler library would otherwise log under the temp dir
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler library on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _assert_kernel_named(hlo: str, name: str) -> None:
    """The program holds exactly one kernel custom call, and it carries
    ``name`` as a component of its ``op_name`` metadata, where a trace
    reader finds it."""
    calls = [line for line in hlo.splitlines()
             if "custom_call_target=\"tpu_custom_call\"" in line]
    assert len(calls) == 1, calls
    paths = [m for line in calls
             for m in re.findall(r'op_name="([^"]+)"', line)]
    assert paths and all(name in p.split("/") for p in paths), paths


def _spec(sharding, shape, dtype):
    import jax
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("heads,kv_heads,chunk,dtype", [
    (12, 12, 1, "bfloat16"),   # gpt2 decode step program (chunk width 1)
    (12, 12, 32, "bfloat16"),  # gpt2 chunked-prefill program (2 * block)
    (16, 4, 32, "bfloat16"),   # GQA: 4 query heads per KV head in the rows
    (12, 12, 32, "float32"),   # the gpt2-f32 cell's chunk-width program
    (25, 25, 32, "bfloat16"),  # gpt2-xl's chunk-width program: 25 KV heads
    (25, 25, 1, "bfloat16"),   # gpt2-xl's width-1 program
], ids=["gpt2-chunk1", "gpt2-chunk32", "gqa4-chunk32", "gpt2-f32-chunk32",
        "gpt2-xl-chunk32", "gpt2-xl-chunk1"])
def test_paged_kernel_compiles_for_v5e(one_chip, heads, kv_heads, chunk,
                                       dtype):
    """Head_dim 64, block 16, a 64-slot table: compute blocks of 8 pages
    (128 keys), so the multi-page kernel is what Mosaic compiles. Every
    page block and piece of state holds all KV heads, so gpt2-xl's 25 is
    the most scoped VMEM a served model asks of it."""
    import jax
    import jax.numpy as jnp
    from repro.core import checksum as cks
    from repro.core.efta import EFTAConfig
    from repro.kernels.efta_paged import (efta_paged_attention_pallas,
                                          pages_per_block)

    b, d, bs, mb, cs, nblocks = 8, 64, 16, 64, 8, 128
    assert pages_per_block(bs, mb) == 8
    cfg = EFTAConfig(mode="correct")

    def step(q, k, v, kc1, kc2, vc1, vc2, bt, kv_lens, q_lens, fault):
        rep = efta_paged_attention_pallas(
            q, k, v, cks.Checksums(kc1, kc2), cks.Checksums(vc1, vc2), bt,
            kv_lens, q_lens, cfg=cfg, fault=fault, interpret=False)
        return rep.out, rep.detected, rep.bad_blocks

    dt = jnp.dtype(dtype)
    pool = _spec(one_chip, (nblocks + 1, kv_heads, bs, d), dt)
    checks = _spec(one_chip, (nblocks + 1, kv_heads, cs, d), dt)
    args = [_spec(one_chip, (b, heads, chunk, d), dt), pool, pool,
            checks, checks, checks, checks,
            _spec(one_chip, (b, mb), jnp.int32),
            _spec(one_chip, (b,), jnp.int32), _spec(one_chip, (b,), jnp.int32),
            _spec(one_chip, (8,), jnp.int32)]
    compiled = jax.jit(step).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _assert_kernel_named(compiled.as_text(), "efta_paged")


def test_efta_attention_kernel_compiles_for_v5e(one_chip):
    import jax
    import jax.numpy as jnp
    from repro.core.efta import EFTAConfig
    from repro.kernels.efta_attention import efta_attention_pallas

    cfg = EFTAConfig(mode="correct")
    x = _spec(one_chip, (1, 12, 1024, 64), jnp.bfloat16)
    compiled = jax.jit(lambda q, k, v: efta_attention_pallas(
        q, k, v, cfg=cfg, causal=True, interpret=False)).lower(
            x, x, x).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _assert_kernel_named(compiled.as_text(), "efta_attention")
