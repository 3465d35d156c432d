"""Distribution: sharding rules, small-mesh SPMD train step, compressed
collectives. Runs in a subprocess with 8 forced host devices so the main
test process keeps its single-device view."""
import json
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.distributed import quantize_int8, spec_for_param
from repro.distributed.collectives import dequantize_int8


def test_param_spec_rules():
    from jax.sharding import PartitionSpec as P
    assert spec_for_param("blocks/attn/wq", 3) == P(None, "data", "model")
    assert spec_for_param("blocks/attn/wo", 2) == P("model", "data")
    assert spec_for_param("blocks/moe/wg", 3) == P("model", "data", None)
    assert spec_for_param("embed/table", 2) == P("model", None)
    assert spec_for_param("blocks/norm1/w", 1) == P(None)


def test_int8_quant_roundtrip():
    rng = np.random.default_rng(0)
    x = jax.numpy.asarray(rng.standard_normal((128,)) * 3)
    q, s = quantize_int8(x)
    err = np.abs(np.asarray(dequantize_int8(q, s)) - np.asarray(x)).max()
    assert err <= float(s) * 0.51 + 1e-6  # within half a quantization step


SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.configs import get_config
    from repro.models import build_model
    from repro.optim import AdamW
    from repro.train import init_state, make_train_step
    from repro.distributed.sharding import param_shardings, batch_sharding

    mesh = Mesh(np.array(jax.devices()).reshape(2, 2, 2),
                ("pod", "data", "model"))
    cfg = get_config("arctic-480b-smoke")
    model = build_model(cfg)
    opt = AdamW(lr=1e-3)
    state = init_state(model, opt, jax.random.PRNGKey(0))
    pshard = param_shardings(jax.eval_shape(lambda: state.params), mesh)
    state = state._replace(
        params=jax.device_put(state.params, pshard),
        opt=state.opt._replace(m=jax.device_put(state.opt.m, pshard),
                               v=jax.device_put(state.opt.v, pshard)))
    step = jax.jit(make_train_step(model, opt, mesh=mesh))
    batch = {
        "tokens": jax.device_put(jnp.ones((8, 32), jnp.int32),
                                 batch_sharding(mesh, 2)),
        "targets": jax.device_put(jnp.ones((8, 32), jnp.int32),
                                  batch_sharding(mesh, 2)),
    }
    with mesh:
        state, metrics = step(state, batch)
        state, metrics = step(state, batch)
    print(json.dumps({"loss": float(metrics["loss"]),
                      "finite": bool(jnp.isfinite(metrics["loss"]))}))
""")


def test_spmd_moe_train_step_8dev():
    out = subprocess.run(
        [sys.executable, "-c", SUBPROC], capture_output=True, text=True,
        timeout=560, env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                          "HOME": "/root"})
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["finite"]


COMPRESSED = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.distributed.collectives import compressed_psum

    mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("pod", "data"))
    x = jnp.arange(32, dtype=jnp.float32).reshape(4, 8) / 7.0
    err = jnp.zeros((1, 8), jnp.float32)

    def f(x, err):
        return compressed_psum(x, "pod", err)

    y, new_err = jax.shard_map(
        f, mesh=mesh, in_specs=(P("pod", "data"), P(None, "data")),
        out_specs=(P(None, "data"), P(None, "data")), check_vma=False)(x, err)
    ref = np.asarray(x).reshape(4, 1, 8).mean(0)
    got = np.asarray(y)[:1]
    print(json.dumps({"max_err": float(np.abs(got - ref).max()),
                      "scale": float(np.abs(ref).max())}))
""")


def test_compressed_psum_8dev():
    out = subprocess.run(
        [sys.executable, "-c", COMPRESSED], capture_output=True, text=True,
        timeout=560, env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                          "HOME": "/root"})
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["max_err"] <= 0.05 * max(res["scale"], 1e-6) + 0.05
