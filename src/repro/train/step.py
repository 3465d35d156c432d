"""Train-step factory: remat, microbatch gradient accumulation, FSDP/TP
sharding, and optional compressed cross-pod gradient sync.

``pod_sync``:
  * "dense"   — one global jit; GSPMD reduces gradients over all DP axes
                (pod included) in full precision.
  * "int8_ef" — shard_map over the ``pod`` axis: in-pod reduction stays full
                precision (fast ICI), the cross-pod hop carries int8 with
                error feedback (distributed-optimization trick; 4x fewer
                cross-DCN bytes).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.distributed.collectives import compressed_psum
from repro.distributed.sharding import dp_axes
from repro.models.api import Model
from repro.optim.adamw import AdamW
from repro.train.state import TrainState


def init_state(model: Model, optimizer: AdamW, rng, *, pod_sync="dense"):
    params = model.init(rng)
    ef = None
    if pod_sync == "int8_ef":
        ef = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.bfloat16), params)
    return TrainState(params=params, opt=optimizer.init(params),
                      step=jnp.zeros((), jnp.int32), ef=ef)


def make_train_step(model: Model, optimizer: AdamW, *, mesh=None,
                    microbatches: int = 1, pod_sync: str = "dense"):
    """Returns step(state, batch) -> (state, metrics). batch leaves are
    (global_batch, ...) arrays sharded over the DP axes."""

    def loss_fn(params, batch):
        return model.loss(params, batch, mesh=mesh)

    def grads_of(params, batch):
        if microbatches == 1:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
            return loss, metrics, grads
        # gradient accumulation: scan over microbatch slices
        def mb(carry, mb_batch):
            acc, loss_acc = carry
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, mb_batch)
            acc = jax.tree.map(jnp.add, acc, grads)
            return (acc, loss_acc + loss), metrics
        split = jax.tree.map(
            lambda x: x.reshape(microbatches, x.shape[0] // microbatches,
                                *x.shape[1:]), batch)
        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                             params)
        (grads, loss), metrics = jax.lax.scan(mb, (zeros, jnp.float32(0)),
                                              split)
        grads = jax.tree.map(lambda g: g / microbatches, grads)
        metrics = jax.tree.map(lambda m: m[-1], metrics)
        return loss / microbatches, metrics, grads

    if pod_sync == "int8_ef" and mesh is not None and "pod" in mesh.shape \
            and mesh.shape["pod"] > 1:
        def step(state: TrainState, batch):
            def per_pod(params, batch_l, ef):
                loss, metrics, grads = grads_of(params, batch_l)
                # cross-pod gradient mean: int8 + error feedback
                flat_g, tdef = jax.tree_util.tree_flatten(grads)
                flat_e = tdef.flatten_up_to(ef)
                out_g, out_e = [], []
                for g, e in zip(flat_g, flat_e):
                    gm, ne = compressed_psum(g, "pod", e)
                    out_g.append(gm)
                    out_e.append(ne)
                grads = tdef.unflatten(out_g)
                new_ef = tdef.unflatten(out_e)
                loss = jax.lax.pmean(loss, "pod")
                return grads, new_ef, loss, metrics

            grads, new_ef, loss, metrics = jax.shard_map(
                per_pod, mesh=mesh,
                in_specs=(P(), P("pod"), P()),
                out_specs=(P(), P(), P(), P()),
                check_vma=False,
            )(state.params, batch, state.ef)
            new_params, new_opt = optimizer.update(grads, state.opt,
                                                   state.params)
            return TrainState(new_params, new_opt, state.step + 1,
                              new_ef), metrics
        return step

    def step(state: TrainState, batch):
        loss, metrics, grads = grads_of(state.params, batch)
        new_params, new_opt = optimizer.update(grads, state.opt, state.params)
        return TrainState(new_params, new_opt, state.step + 1,
                          state.ef), metrics
    return step
