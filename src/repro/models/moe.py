"""Mixture-of-Experts FFN with expert parallelism.

Dispatch is sort-free-capacity ("bucketed scatter"): assignments are grouped
by expert with one argsort, ranked by position, and scattered into a static
(E_local, capacity, d) buffer — no GShard (tokens, E, capacity) dispatch
einsum (which is FLOPs-catastrophic at 128-384 experts), and no ragged shapes.

Expert parallelism (EP) maps experts onto the ``model`` mesh axis via
``shard_map``: activations arrive replicated across ``model`` (Megatron
pattern), each device filters the assignments that hit its local experts,
computes, and one ``psum`` over ``model`` combines — the only collective in
the layer. Load is balanced in expectation (tokens hash uniformly over E).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import MoECfg
from repro.models.layers import ACTS, dense_init


def moe_init(key, d: int, m: MoECfg, dtype):
    ks = jax.random.split(key, 4)
    e, f = m.num_experts, m.expert_d_ff
    scale = 1.0 / (d ** 0.5)
    def ew(k, a, b):
        return (jax.random.normal(k, (e, a, b), jnp.float32) / (a ** 0.5)).astype(dtype)
    return {
        "router": dense_init(ks[0], d, e, jnp.float32, scale=scale),
        "wg": ew(ks[1], d, f),
        "wu": ew(ks[2], d, f),
        "wd": ew(ks[3], f, d),
    }


def _moe_local(x2d, router_w, wg, wu, wd, *, top_k: int, e_start,
               e_count: int, capacity: int, act: str, num_experts: int):
    """Route + dispatch + compute for experts [e_start, e_start+e_count).

    x2d: (N, d). Returns (y (N, d), aux_loss scalar).
    """
    n, d = x2d.shape
    a = ACTS[act]
    logits = jnp.matmul(x2d.astype(jnp.float32), router_w)       # (N, E)
    probs = jax.nn.softmax(logits, axis=-1)
    vals, idx = jax.lax.top_k(probs, top_k)                      # (N, k)
    vals = vals / jnp.sum(vals, axis=-1, keepdims=True)

    # Load-balancing aux loss (Switch): E * sum_e f_e * p_e.
    top1 = idx[:, 0]
    f_e = jnp.mean(jax.nn.one_hot(top1, num_experts, dtype=jnp.float32), axis=0)
    p_e = jnp.mean(probs, axis=0)
    aux = num_experts * jnp.sum(f_e * p_e)

    eid = idx.reshape(-1)
    tid = jnp.repeat(jnp.arange(n, dtype=jnp.int32), top_k)
    w = vals.reshape(-1)
    local = (eid >= e_start) & (eid < e_start + e_count)
    eid_l = jnp.where(local, eid - e_start, e_count).astype(jnp.int32)

    order = jnp.argsort(eid_l)                                    # group by expert
    eid_s = eid_l[order]
    tid_s = tid[order]
    w_s = w[order]
    starts = jnp.searchsorted(eid_s, jnp.arange(e_count + 1, dtype=jnp.int32))
    rank = jnp.arange(n * top_k, dtype=jnp.int32) - starts[
        jnp.clip(eid_s, 0, e_count)]
    keep = (eid_s < e_count) & (rank < capacity)
    slot = jnp.where(keep, eid_s * capacity + rank, e_count * capacity)

    buf = jnp.zeros((e_count * capacity + 1, d), x2d.dtype)
    buf = buf.at[slot].set(x2d[tid_s], mode="drop")
    h = buf[: e_count * capacity].reshape(e_count, capacity, d)
    hidden = a(jnp.einsum("ecd,edf->ecf", h, wg,
                          preferred_element_type=jnp.float32).astype(x2d.dtype))
    hidden = hidden * jnp.einsum("ecd,edf->ecf", h, wu,
                                 preferred_element_type=jnp.float32).astype(x2d.dtype)
    y_buf = jnp.einsum("ecf,efd->ecd", hidden, wd,
                       preferred_element_type=jnp.float32).astype(x2d.dtype)
    y_buf = jnp.concatenate(
        [y_buf.reshape(e_count * capacity, d),
         jnp.zeros((1, d), x2d.dtype)], axis=0)
    contrib = y_buf[slot] * jnp.where(keep, w_s, 0.0)[:, None].astype(x2d.dtype)
    y = jnp.zeros((n, d), x2d.dtype).at[tid_s].add(contrib)
    return y, aux


def moe_apply(params, x, m: MoECfg, *, act: str = "silu",
              mesh=None, ep_axis: str = "model",
              dp_axes: tuple = ("pod", "data"), mode: str = "train"):
    """x: (B, S, d) -> (y, aux_loss). EP over ``ep_axis`` when a mesh with
    that axis (size > 1) is active; single-device path otherwise.

    Decode inference uses the all-device EP layout (inference_ep): expert
    weights stay fully sharded (E over 'data', ff over 'model'); the few
    decode tokens are all-gathered instead of gathering GBs of weights.
    """
    b, s, d = x.shape
    x2 = x.reshape(b * s, d)
    e = m.num_experts
    n_tokens = b * s

    ep = (mesh is not None and ep_axis in mesh.shape and mesh.shape[ep_axis] > 1)
    if (ep and m.inference_ep and mode == "decode"
            and "data" in mesh.shape and e % mesh.shape["data"] == 0):
        return _moe_inference_ep(params, x2, m, mesh=mesh, act=act,
                                 dp_axes=dp_axes, shape=(b, s, d))
    if not ep:
        cap = max(4, math.ceil(n_tokens * m.top_k / e * m.capacity_factor))
        y, aux = _moe_local(
            x2, params["router"], params["wg"], params["wu"], params["wd"],
            top_k=m.top_k, e_start=0, e_count=e, capacity=cap, act=act,
            num_experts=e)
        return y.reshape(b, s, d), aux

    msize = mesh.shape[ep_axis]
    e_count = e // msize
    dp = tuple(a for a in dp_axes if a in mesh.shape)
    dp_size = math.prod(mesh.shape[a] for a in dp) if dp else 1
    n_local = n_tokens // dp_size
    cap = max(4, math.ceil(n_local * m.top_k / e * m.capacity_factor))
    fsdp = "data" if "data" in mesh.shape and mesh.shape["data"] > 1 else None

    def inner(rw, wg, wu, wd, xl):
        me = jax.lax.axis_index(ep_axis)
        if fsdp is not None:
            # ZeRO-3 just-in-time gather of this device's expert shard along
            # the FSDP axis (weights stored P("model", "data", None)).
            wg = jax.lax.all_gather(wg, fsdp, axis=1, tiled=True)
            wu = jax.lax.all_gather(wu, fsdp, axis=1, tiled=True)
            wd = jax.lax.all_gather(wd, fsdp, axis=1, tiled=True)
        y, aux = _moe_local(
            xl, rw, wg, wu, wd, top_k=m.top_k, e_start=me * e_count,
            e_count=e_count, capacity=cap, act=act, num_experts=e)
        y = jax.lax.psum(y, ep_axis)
        if dp:
            aux = jax.lax.pmean(aux, dp)
        return y, aux

    wspec = P(ep_axis, fsdp, None)
    y, aux = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(P(), wspec, wspec, wspec, P(dp if dp else None)),
        out_specs=(P(dp if dp else None), P()),
        check_vma=False,
    )(params["router"], params["wg"], params["wu"], params["wd"], x2)
    return y.reshape(b, s, d), aux


def _moe_inference_ep(params, x2, m: MoECfg, *, mesh, act, dp_axes, shape):
    """Decode-path MoE: experts sharded E-over-'data' x ff-over-'model';
    tokens replicated (all-gather of KBs); single psum combines. No weight
    gathers — the collective-bytes hillclimb for decode_32k (§Perf)."""
    b, s, d = shape
    e = m.num_experts
    d_size = mesh.shape["data"]
    e_count = e // d_size
    n = x2.shape[0]
    cap = max(4, math.ceil(n * m.top_k / e * m.capacity_factor))

    def inner(rw, wg, wu, wd, xl):
        di = jax.lax.axis_index("data")
        y, aux = _moe_local(
            xl, rw, wg, wu, wd, top_k=m.top_k, e_start=di * e_count,
            e_count=e_count, capacity=cap, act=act, num_experts=e)
        # wd's contraction dim (ff) is sharded over 'model': partial sums —
        # one psum over (data, model) combines expert shards and partials.
        y = jax.lax.psum(y, ("data", "model"))
        return y, aux

    wspec_in = P("data", None, "model")   # wg, wu: (E@data, d, ff@model)
    wspec_out = P("data", "model", None)  # wd:     (E@data, ff@model, d)
    y, aux = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(P(), wspec_in, wspec_in, wspec_out, P()),
        out_specs=(P(), P()),
        check_vma=False,
    )(params["router"], params["wg"], params["wu"], params["wd"], x2)
    return y.reshape(b, s, d), aux
