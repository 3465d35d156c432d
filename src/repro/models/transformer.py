"""Unified model assembly for every assigned architecture family.

One scanned-block stack covers dense / MoE / hybrid / SSM / VLM / enc-dec
variants. Heterogeneity is handled without breaking scan uniformity:

  * per-layer *flags* (gemma3 5:1 local:global windows, hymba's 3 full-attn
    layers, per-layer rope theta) ride along as scan inputs;
  * the VLM's sparse cross-attention layers are grouped into uniform
    *superblocks* (cadence-1 dense layers + 1 cross layer) so cross-attn
    params exist only where used;
  * enc-dec (whisper/t5) runs a separate encoder scan; every decoder layer
    carries cross-attention uniformly.

All functions are pure; ``mesh`` is threaded for MoE expert parallelism and
activation sharding constraints.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.efta import FTReport
from repro.models import ssm as ssm_lib
from repro.models.attention import (KVCache, PagedKVCache, attn_apply,
                                    attn_init, init_cache)
from repro.models.layers import (embed_apply, embed_init, learned_pos_init,
                                 matmul, mlp_apply, mlp_init, norm_apply,
                                 norm_init, unembed)
from repro.models.moe import moe_apply, moe_init

DP_AXES = ("pod", "data")


def shard_act(x, mesh, spec=None):
    if mesh is None:
        return x
    dp = tuple(a for a in DP_AXES if a in mesh.shape)
    if not dp:
        return x
    if spec is None:
        spec = P(dp, *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# per-layer flags
# ---------------------------------------------------------------------------

def layer_flags(cfg: ModelConfig) -> dict[str, np.ndarray]:
    """Static per-layer arrays: is_global (full attention) and rope theta."""
    n = cfg.num_layers
    a = cfg.attn
    is_global = np.ones((n,), np.bool_)
    theta = np.full((n,), a.rope_theta if a else 1e4, np.float32)
    if a is not None and a.sliding_window is not None:
        if a.global_every:
            is_global = (np.arange(n) % a.global_every) == (a.global_every - 1)
        elif cfg.family == "hybrid":
            # hymba: full attention at first / middle / last layers
            is_global = np.zeros((n,), np.bool_)
            for i in (0, n // 2, n - 1):
                is_global[i] = True
        else:
            is_global = np.zeros((n,), np.bool_)
        theta = np.where(is_global, 1e6 if a.global_every else a.rope_theta,
                         a.rope_theta).astype(np.float32)
    return {"is_global": is_global, "theta": theta}


# ---------------------------------------------------------------------------
# block init/apply (uniform within a model; selected by family)
# ---------------------------------------------------------------------------

def _block_init(key, cfg: ModelConfig, *, cross: bool = False,
                causal: bool = True, kind: Optional[str] = None):
    kind = kind or cfg.family
    d, dtype = cfg.d_model, jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 8)
    p: dict[str, Any] = {}
    if kind == "ssm":  # rwkv6
        p["norm1"] = norm_init(cfg.norm, d, dtype)
        p["time_mix"] = ssm_lib.rwkv6_init(ks[0], d, cfg.ssm, dtype)
        p["norm2"] = norm_init(cfg.norm, d, dtype)
        return p
    p["norm1"] = norm_init(cfg.norm, d, dtype)
    p["attn"] = attn_init(ks[0], d, cfg.attn, dtype)
    if kind == "hybrid":
        p["mamba"] = ssm_lib.mamba_init(ks[1], d, cfg.ssm, dtype)
    if cross:
        p["norm_x"] = norm_init(cfg.norm, d, dtype)
        p["cross"] = attn_init(ks[2], d, cfg.attn, dtype, cross=True)
    p["norm2"] = norm_init(cfg.norm, d, dtype)
    if kind == "moe":
        p["moe"] = moe_init(ks[3], d, cfg.moe, dtype)
        if cfg.moe.num_shared_experts:
            p["shared"] = mlp_init(ks[4], d, cfg.moe.shared_d_ff, dtype,
                                   glu=cfg.glu)
        if cfg.moe.dense_d_ff:
            p["dense_res"] = mlp_init(ks[5], d, cfg.moe.dense_d_ff, dtype,
                                      glu=cfg.glu)
    else:
        p["mlp"] = mlp_init(ks[3], d, cfg.d_ff, dtype, glu=cfg.glu)
    return p


def _block_apply(params, x, *, cfg: ModelConfig, flags, cache, mode,
                 positions, memory, mesh, kind: Optional[str] = None,
                 causal: bool = True, fault=None):
    """One transformer block. Returns (x, report, aux, new_cache)."""
    kind = kind or cfg.family
    rep = FTReport.zero()
    aux = jnp.float32(0)
    new_cache = cache

    if kind == "ssm":  # rwkv6: time-mix + channel-mix
        h, st = ssm_lib.rwkv6_time_mix(
            params["time_mix"], norm_apply(cfg.norm, params["norm1"], x),
            cfg.ssm, state=cache if cache is not None
            else ssm_lib.rwkv_state_init(x.shape[0], cfg.d_model, cfg.ssm,
                                         x.dtype))
        x = x + h
        h, st = ssm_lib.rwkv6_channel_mix(
            params["time_mix"], norm_apply(cfg.norm, params["norm2"], x),
            state=st)
        x = x + h
        return x, rep, aux, (st if cache is not None else None)

    a = cfg.attn
    is_global = flags["is_global"]
    theta = flags["theta"]
    window = a.sliding_window
    acfg = dataclasses.replace(a, causal=causal)
    # per-layer window selection rides on a traced bool: implemented by
    # passing window and masking with where on the efta mask path would break
    # static masks, so we compute attention with the layer's static-ish flag
    # via lax.cond-free arithmetic: window=None case handled by huge window.
    eff_window = None
    if window is not None:
        big = 1 << 30
        eff_window = jnp.where(is_global, big, window)

    h_in = norm_apply(cfg.norm, params["norm1"], x)
    attn_cache = cache["attn"] if isinstance(cache, dict) else None
    acfg2 = dataclasses.replace(acfg, rope_theta=theta)
    h, rep_a, new_attn_cache = attn_apply(
        params["attn"], h_in, acfg=acfg2, ft=cfg.ft,
        window=eff_window, positions=positions, cache=attn_cache, mode=mode,
        fault=fault, mesh=mesh)
    rep = rep.merge(rep_a)

    if kind == "hybrid":
        mstate = cache["mamba"] if isinstance(cache, dict) else None
        hm, new_mstate = ssm_lib.mamba_apply(params["mamba"], h_in, cfg.ssm,
                                             state=mstate)
        h = 0.5 * (h + hm)
    x = x + h

    if "cross" in params:
        hx = norm_apply(cfg.norm, params["norm_x"], x)
        cross_cache = cache["attn"] if isinstance(cache, dict) else None
        hx, rep_x, cc = attn_apply(
            params["cross"], hx, acfg=dataclasses.replace(acfg, causal=False),
            ft=cfg.ft, positions=positions,
            cache=cross_cache, mode=mode, kv_x=memory, cross=True, mesh=mesh)
        rep = rep.merge(rep_x)
        if cc is not None and isinstance(cache, dict):
            new_attn_cache = new_attn_cache._replace(ck=cc.ck, cv=cc.cv) \
                if new_attn_cache is not None else cc
        x = x + hx

    h2 = norm_apply(cfg.norm, params["norm2"], x)
    if kind == "moe":
        y, aux = moe_apply(params["moe"], h2, cfg.moe, act=cfg.act, mesh=mesh,
                           mode=mode)
        if "shared" in params:
            y = y + mlp_apply(params["shared"], h2, act=cfg.act, glu=cfg.glu,
                              ff_abft=cfg.ft.ff_abft)
        if "dense_res" in params:
            y = y + mlp_apply(params["dense_res"], h2, act=cfg.act,
                              glu=cfg.glu, ff_abft=cfg.ft.ff_abft)
    else:
        y = mlp_apply(params["mlp"], h2, act=cfg.act, glu=cfg.glu,
                      ff_abft=cfg.ft.ff_abft)
    x = x + y

    if isinstance(cache, dict):
        new_cache = dict(cache)
        new_cache["attn"] = new_attn_cache
        if kind == "hybrid":
            new_cache["mamba"] = new_mstate
    return x, rep, aux, new_cache


# ---------------------------------------------------------------------------
# model: init
# ---------------------------------------------------------------------------

def _stack_init(key, n: int, one_init):
    keys = jax.random.split(key, n)
    return jax.vmap(one_init)(keys)


def init_params(key, cfg: ModelConfig):
    dtype = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 8)
    params: dict[str, Any] = {
        "embed": embed_init(ks[0], cfg.vocab_size, cfg.d_model, dtype),
        "final_norm": norm_init(cfg.norm, cfg.d_model, dtype),
    }
    if cfg.attn is not None and cfg.attn.pos == "learned":
        params["pos"] = learned_pos_init(ks[1], max(cfg.max_seq, 64),
                                         cfg.d_model, dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = embed_init(ks[2], cfg.vocab_size, cfg.d_model,
                                       dtype)

    cross_every = cfg.cross_attn_every
    if cfg.family == "vlm" and cross_every:
        n_super = cfg.num_layers // cross_every
        params["blocks"] = _stack_init(
            ks[3], n_super,
            lambda k: {
                "dense": _stack_init(
                    jax.random.fold_in(k, 0), cross_every - 1,
                    lambda kk: _block_init(kk, cfg, kind="dense")),
                "cross_blk": _block_init(jax.random.fold_in(k, 1), cfg,
                                         cross=True, kind="dense"),
            })
    elif cfg.family in ("audio", "encdec"):
        params["encoder"] = _stack_init(
            ks[4], cfg.encoder_layers,
            lambda k: _block_init(k, cfg, kind="dense", causal=False))
        params["enc_norm"] = norm_init(cfg.norm, cfg.d_model, dtype)
        params["blocks"] = _stack_init(
            ks[3], cfg.num_layers,
            lambda k: _block_init(k, cfg, cross=True, kind="dense"))
    elif cfg.family == "encoder":
        params["blocks"] = _stack_init(
            ks[3], cfg.num_layers,
            lambda k: _block_init(k, cfg, kind="dense", causal=False))
    else:
        params["blocks"] = _stack_init(
            ks[3], cfg.num_layers, lambda k: _block_init(k, cfg))
    return params


# ---------------------------------------------------------------------------
# model: forward
# ---------------------------------------------------------------------------

def _maybe_remat(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)


def _scan_blocks(params_stack, x, *, cfg, flags_np, cache_stack, mode,
                 positions, memory, mesh, kind=None, causal=True, fault=None):
    """lax.scan over stacked block params (+ optional stacked caches)."""
    flags_arrs = {k: jnp.asarray(v) for k, v in flags_np.items()}
    have_cache = cache_stack is not None

    sp_spec = None
    if cfg.seq_parallel and mesh is not None and "model" in mesh.shape:
        dp = tuple(a for a in DP_AXES if a in mesh.shape)
        sp_spec = P(dp if dp else None, "model", None)

    def body(carry, inp):
        x, rep = carry
        if have_cache:
            bp, fl, cch = inp
        else:
            bp, fl = inp
            cch = None
        x = shard_act(x, mesh, sp_spec)
        x, rep_b, aux, new_c = _block_apply(
            bp, x, cfg=cfg, flags=fl, cache=cch, mode=mode,
            positions=positions, memory=memory, mesh=mesh, kind=kind,
            causal=causal, fault=fault)
        return (x, rep.merge(rep_b)), (aux, new_c) if have_cache else (aux,)

    body = _maybe_remat(body, cfg)
    n = jax.tree.leaves(params_stack)[0].shape[0]
    flags_stack = {k: (v if v.shape and v.shape[0] == n else
                       jnp.broadcast_to(v, (n,) + v.shape))
                   for k, v in flags_arrs.items()}
    xs = (params_stack, flags_stack, cache_stack) if have_cache else (
        params_stack, flags_stack)
    rep0 = FTReport.zero()
    if have_cache and isinstance(cache_stack, dict) and \
            isinstance(cache_stack.get("attn"), PagedKVCache):
        # paged decode reports per request: carry a (B, 5) report so the
        # engine sees per-slot detections, as the vmapped path does
        rep0 = FTReport(jnp.zeros((x.shape[0], 5), jnp.int32),
                        jnp.zeros((x.shape[0], 5), jnp.int32),
                        jnp.zeros((3,), jnp.float32))
    (x, rep), ys = jax.lax.scan(body, (x, rep0), xs,
                                unroll=True if not cfg.scan_layers else 1)
    aux = jnp.sum(ys[0])
    new_cache = ys[1] if have_cache else None
    return x, rep, aux, new_cache


def forward(params, cfg: ModelConfig, batch: dict, *, mesh=None,
            cache=None, mode: str = "train", fault=None):
    """Returns (logits f32 (B, S, V), FTReport, aux_loss, new_cache).

    ``fault`` is a :class:`repro.core.fault.FaultSpec` injected into every
    decoder self-attention call (the SEU strikes each attention layer's
    matching (site, kv-block) — a superset of the paper's single-layer SEU,
    so detection/correction coverage is exercised at least as hard).
    """
    tokens = batch["tokens"]
    b, s = tokens.shape
    x = embed_apply(params["embed"], tokens)
    if cache is not None and mode == "decode" and cfg.family != "ssm":
        pos0 = _cache_pos(cache)
        # paged caches decode natively batched over ragged requests: the
        # position base is per-request (B,), making positions (B, S)
        base = pos0[:, None] if pos0.ndim else pos0
        positions = base + jnp.arange(s, dtype=jnp.int32)
    else:
        positions = jnp.arange(s, dtype=jnp.int32)
    if "pos" in params:
        pe = jnp.take(params["pos"]["pos"],
                      jnp.minimum(positions, params["pos"]["pos"].shape[0] - 1),
                      axis=0).astype(x.dtype)
        x = x + (pe if positions.ndim == 2 else pe[None, :, :])
    x = shard_act(x, mesh)

    memory = None
    rep = FTReport.zero()
    aux = jnp.float32(0)
    flags = layer_flags(cfg)

    if cfg.family in ("audio", "encdec"):
        if cache is not None and mode == "decode":
            memory = None  # cross K/V live in the cache
        else:
            if "frontend" in batch:           # audio: precomputed frames (stub)
                enc_x = batch["frontend"].astype(x.dtype)
            else:                              # t5: token encoder
                enc_x = embed_apply(params["embed"], batch["enc_tokens"])
            enc_flags = {"is_global": np.ones((cfg.encoder_layers,), bool),
                         "theta": np.full((cfg.encoder_layers,),
                                          cfg.attn.rope_theta, np.float32)}
            enc_pos = jnp.arange(enc_x.shape[1], dtype=jnp.int32)
            enc_x = shard_act(enc_x, mesh)
            enc_x, rep_e, _, _ = _scan_blocks(
                params["encoder"], enc_x, cfg=cfg, flags_np=enc_flags,
                cache_stack=None, mode="train", positions=enc_pos,
                memory=None, mesh=mesh, kind="dense", causal=False)
            memory = norm_apply(cfg.norm, params["enc_norm"], enc_x)
            rep = rep.merge(rep_e)
    elif cfg.family == "vlm":
        memory = batch["frontend"].astype(x.dtype) if "frontend" in batch else None

    if cfg.family == "vlm" and cfg.cross_attn_every:
        ce = cfg.cross_attn_every
        n_super = cfg.num_layers // ce

        def super_body(carry, inp):
            x, rep = carry
            sp, cch = inp if cache is not None else (inp[0], None)
            aux_t = jnp.float32(0)
            new_cs = []
            for i in range(ce - 1):
                sub = jax.tree.map(lambda t, i=i: t[i], sp["dense"])
                c_i = (jax.tree.map(lambda t, i=i: t[i], cch["dense"])
                       if cch is not None else None)
                x = shard_act(x, mesh)
                x, rb, a_i, nc = _block_apply(
                    sub, x, cfg=cfg, flags={"is_global": jnp.bool_(True),
                                            "theta": jnp.float32(
                                                cfg.attn.rope_theta)},
                    cache=c_i, mode=mode, positions=positions, memory=None,
                    mesh=mesh, kind="dense", fault=fault)
                rep = rep.merge(rb)
                aux_t += a_i
                new_cs.append(nc)
            c_x = cch["cross_blk"] if cch is not None else None
            x, rb, a_i, nc_x = _block_apply(
                sp["cross_blk"], x, cfg=cfg,
                flags={"is_global": jnp.bool_(True),
                       "theta": jnp.float32(cfg.attn.rope_theta)},
                cache=c_x, mode=mode, positions=positions, memory=memory,
                mesh=mesh, kind="dense")
            rep = rep.merge(rb)
            aux_t += a_i
            new_c = None
            if cache is not None:
                new_c = {"dense": jax.tree.map(
                    lambda *ts: jnp.stack(ts), *new_cs), "cross_blk": nc_x}
            return (x, rep), (aux_t, new_c) if cache is not None else (aux_t,)

        super_body = _maybe_remat(super_body, cfg)
        xs = (params["blocks"], cache) if cache is not None else (
            params["blocks"],)
        (x, rep2), ys = jax.lax.scan(super_body, (x, rep), xs,
                                     unroll=True if not cfg.scan_layers else 1)
        rep = rep2
        aux = jnp.sum(ys[0])
        new_cache = ys[1] if cache is not None else None
    else:
        kind = None
        causal = cfg.family != "encoder"
        if cfg.family in ("audio", "encdec"):
            kind = "dense"
        x, rep_b, aux, new_cache = _scan_blocks(
            params["blocks"], x, cfg=cfg, flags_np=flags, cache_stack=cache,
            mode=mode, positions=positions, memory=memory, mesh=mesh,
            kind=kind, causal=causal, fault=fault)
        rep = rep.merge(rep_b)

    x = norm_apply(cfg.norm, params["final_norm"], x)
    x = shard_act(x, mesh)
    table = params.get("lm_head", params["embed"])["table"]
    with jax.named_scope("lm_head"):
        logits = unembed(params["embed"], x, table=table)
    if mesh is not None and "model" in mesh.shape:
        dp = tuple(a for a in DP_AXES if a in mesh.shape)
        logits = shard_act(logits, mesh, P(dp, None, "model"))
    return logits, rep, aux, new_cache


def _cache_pos(cache) -> jax.Array:
    """Extract the position counter from a stacked cache pytree: a scalar
    for contiguous :class:`KVCache` rows, a per-request (B,) vector for the
    paged block pool (every layer shares one table, so layer 0's row is
    authoritative)."""
    def find(c):
        if isinstance(c, PagedKVCache):
            # stacked (L, B) -> (B,): per-request, stays a vector
            return c.pos[0] if c.pos.ndim > 1 else c.pos
        if isinstance(c, KVCache):
            return c.pos.reshape(-1)[0]
        if isinstance(c, dict):
            for v in c.values():
                r = find(v)
                if r is not None:
                    return r
        if isinstance(c, (list, tuple)) and not hasattr(c, "_fields"):
            for v in c:
                r = find(v)
                if r is not None:
                    return r
        if hasattr(c, "_fields"):  # other NamedTuples (ssm states) — no pos
            return None
        return None

    p = find(cache)
    if p is None:
        raise ValueError("cache has no position counter")
    return p
