"""Chip smoke test: serve GPT-2 at its published width on one TPU chip.

Drives the served path once, in this one process (which holds the chip):
requests arrive, ``PagedServeEngine`` schedules them, and the unified chunked
step runs the fused EFTA paged kernel with protection on until the last
token. It is the code ``python -m repro.launch.serve --arch gpt2
--chunked-prefill`` runs (12 layers, d_model 768, 12 heads x 64, d_ff 3072,
vocab 50257, bf16), with random weights made from ``--seed``.

Phases, one line of findings each; a failed phase exits non-zero:

  1. device    JAX must find a TPU.
  2. serve     8 requests on 8 slots, ragged 32..512-token prompts, 32 greedy
               tokens each; at most 2 step programs compiled, and the step
               program holds the compiled kernel (``tpu_custom_call``).
  3. clean     that run detected nothing: EFTA sites 1-5 and KV blocks (6).
  4. numerics  fused-path logits of two prompts against a float32 reference
               forward of the same weights, within a bound derived below
               from bf16 rounding.
  5. protect   the same requests again with one compute SEU and one resident
               KV bit flip: both detected and repaired, tokens unchanged.

The last line of stdout is the verdict as JSON, with the device as JAX
reports it. Run from the root of a checkout:

    python chip_smoke.py
"""
from __future__ import annotations

import dataclasses
import json
import math
import sys
import time
from pathlib import Path

SERVE_ARGV = ["--arch", "gpt2", "--chunked-prefill", "--requests", "8",
              "--slots", "8", "--min-prompt", "32", "--max-prompt", "512",
              "--gen", "32", "--seed", "0"]

# bf16 stores 8 mantissa bits: round to nearest leaves a relative error of
# at most 2^-9 per rounding.
BF16_UNIT_ROUNDOFF = 2.0 ** -9
# Top-1 agreement floor. With random weights the logits of a position are
# ~N(0, s^2) over 50257 tokens, so the gap between the two largest is about
# s / sqrt(2 ln V) ~ s / 4.7 on average; at the error bound below (s ~ 0.55,
# logit error sd ~ 0.01) about 1 position in 10 can swap its top two.
TOP1_FLOOR = 0.9


class PhaseFailed(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise PhaseFailed(what)


def say(phase: str, line: str) -> None:
    print(f"[{phase}] {line}", flush=True)


def phase_device(jax, cache_dir: str) -> dict:
    dev = jax.devices()[0]
    check(dev.platform == "tpu",
          f"device: JAX found platform {dev.platform!r}, not a TPU")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    say("device", f"platform={device['platform']} kind={device['kind']} "
                  f"count={device['count']} compile_cache={cache_dir}")
    return device


def phase_serve(jax):
    from repro.launch.serve import parse_args, serve

    args = parse_args(SERVE_ARGV)
    compiles = []        # (seconds, jitted function) per backend compile
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append((secs, kw.get("fun_name")))
        if event == "/jax/core/compile/backend_compile_duration" else None)
    t0 = time.perf_counter()
    run = serve(SERVE_ARGV)
    wall = time.perf_counter() - t0
    eng = run.engine
    lens = [len(p) for p in run.prompts]
    n_tok = {rid: len(t) for rid, t in run.outputs.items()}
    check(sorted(n_tok) == list(range(args.requests))
          and set(n_tok.values()) == {args.gen},
          f"serve: expected {args.requests} requests x {args.gen} tokens, "
          f"got {n_tok}")
    programs = eng._step_fused._cache_size()
    check(programs <= 2, f"serve: {programs} step programs compiled (> 2)")
    kernel = "tpu_custom_call" in eng.lower_step(eng.chunk_size).as_text()
    check(kernel, "serve: the step program holds no tpu_custom_call; the "
                  "paged kernel is not compiled")
    cfg = eng.model.cfg
    largest = ", ".join(f"{name} {secs:.1f}"
                        for secs, name in sorted(compiles, reverse=True)[:3])
    say("serve", f"{cfg.name}: {cfg.num_layers} layers d_model={cfg.d_model} "
                 f"heads={cfg.attn.num_heads}x{cfg.attn.head_dim} "
                 f"dtype={cfg.dtype}; {args.requests} requests prompts={lens} "
                 f"x {args.gen} tokens "
                 f"in {eng.stats.steps} steps; step programs={programs} "
                 f"tpu_custom_call={kernel}; informational only, not a "
                 f"benchmark: compile_s={sum(c for c, _ in compiles):.1f} "
                 f"over {len(compiles)} programs (largest: {largest}) "
                 f"wall_s={wall:.1f}")
    return run


def _detected(eng):
    import numpy as np
    per_site = np.zeros(6, np.int64)
    corrected = np.zeros(6, np.int64)
    for st in eng.telemetry.requests.values():
        per_site += np.asarray(st.detected, np.int64)
        corrected += np.asarray(st.corrected, np.int64)
    return per_site, corrected


def phase_clean(run) -> None:
    eng = run.engine
    det, _ = _detected(eng)
    ps = eng.paged_stats
    say("clean", f"detected per site [gemm1 exp rowmax rowsum gemm2 kv]="
                 f"{det.tolist()} kv_blocks={ps.kv_detected_blocks} "
                 f"retries={eng.stats.retries}")
    check(not det.any() and ps.kv_detected_blocks == 0
          and eng.stats.retries == 0,
          "clean: a fault-free run reported detections (false positives "
          "at bf16)")


def fused_prompt_logits(eng, prompts):
    """Per-position logits of ``prompts`` from the fused paged path: the
    served model's ``score`` over a fresh checksummed block pool, fed
    ``chunk_size`` tokens per call as the unified step feeds a prefill."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models.attention import PagedKVCache

    model, cfg = eng.model, eng.model.cfg
    a, L, B = cfg.attn, cfg.num_layers, len(prompts)
    bs, cs, chunk = eng.block_size, eng.check_stride, eng.chunk_size
    width = -(-max(len(p) for p in prompts) // chunk) * chunk
    mb = width // bs
    dt = jnp.dtype(cfg.dtype)
    kv = jnp.zeros((L, B * mb + 1, a.num_kv_heads, bs, a.head_dim), dt)
    ck = jnp.zeros((L, B * mb + 1, a.num_kv_heads, cs, a.head_dim), dt)
    pool = [kv, kv, ck, ck, ck, ck]
    bt = np.arange(1, B * mb + 1, dtype=np.int32).reshape(B, mb)
    lens = np.asarray([len(p) for p in prompts], np.int32)
    toks = np.zeros((B, width), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p

    def per_layer(x):
        return jnp.broadcast_to(jnp.asarray(x)[None], (L,) + np.shape(x))

    score = jax.jit(model.score)
    out, det = [], 0
    for c0 in range(0, width, chunk):
        cache = {"attn": PagedKVCache(
            *pool, bt=per_layer(bt), pos=per_layer(np.minimum(lens, c0)),
            q_len=per_layer(np.clip(lens - c0, 0, chunk)),
            bad=jnp.zeros((L, B, mb), jnp.int32))}
        logits, rep, new = score(eng.params, jnp.asarray(toks[:, c0:c0 + chunk]),
                                 cache)
        nc = new["attn"]
        pool = [nc.k, nc.v, nc.kc1, nc.kc2, nc.vc1, nc.vc2]
        det += int(rep.detected.sum()) + int(nc.bad.sum())
        out.append(logits)
    return jnp.concatenate(out, axis=1), toks, lens, det


def phase_numerics(run) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import build_model

    eng = run.engine
    cfg = eng.model.cfg
    order = np.argsort([len(p) for p in run.prompts])
    prompts = [run.prompts[order[0]], run.prompts[order[-1]]]
    fused, toks, lens, det = fused_prompt_logits(eng, prompts)

    ref_cfg = dataclasses.replace(
        cfg, dtype="float32",
        ft=dataclasses.replace(cfg.ft, attn_impl="reference"))
    params32 = jax.tree.map(lambda x: x.astype(jnp.float32), eng.params)
    with jax.default_matmul_precision("highest"):
        ref, _ = jax.jit(build_model(ref_cfg).logits)(
            params32, {"tokens": jnp.asarray(toks)})

    valid = jnp.asarray(np.arange(toks.shape[1])[None, :] < lens[:, None])
    err = jnp.abs(fused - ref) * valid[..., None]
    max_abs = float(err.max())
    rms = float(jnp.sqrt(jnp.sum(err ** 2) / (valid.sum() * cfg.vocab_size)))
    top1 = float(jnp.sum((fused.argmax(-1) == ref.argmax(-1)) & valid)
                 / valid.sum())

    # The bound. The fused path rounds to bf16 the residual stream twice per
    # layer and about five matmul outputs per layer (q, k, v, attention
    # output, MLP), plus the embedding sum and the final norm: n = 7 L + 2
    # roundings of relative size <= u. Independent roundings add in
    # quadrature, so the final hidden state h carries a relative error of
    # about sqrt(n) u. After the final LayerNorm |h| = sqrt(d), and a logit is
    # h . e_t with the tied embedding row e_t of i.i.d. entries of standard
    # deviation s_e, so the logit error has standard deviation
    # sigma = sqrt(n) u sqrt(d) s_e (0.010 for gpt2). The RMS error must stay
    # under sigma, and the largest of the ~10^7 compared logits under
    # 8 sigma (a Gaussian maximum over that many draws is ~5.5 sigma).
    # Computing in fp8 (u = 2^-4) would miss both by a factor of ~30.
    n_round = 7 * cfg.num_layers + 2
    s_e = float(jnp.std(eng.params["embed"]["table"].astype(jnp.float32)))
    sigma = (math.sqrt(n_round) * BF16_UNIT_ROUNDOFF * math.sqrt(cfg.d_model)
             * s_e)
    say("numerics", f"prompts of {lens.tolist()} tokens vs float32 reference "
                    f"(highest matmul precision): max_abs_diff={max_abs:.5f} "
                    f"(bound {8 * sigma:.5f}) rms={rms:.5f} (bound "
                    f"{sigma:.5f}) top1_agreement={top1:.4f} (floor "
                    f"{TOP1_FLOOR}) detections={det}")
    check(det == 0, "numerics: the fused prompt pass reported detections")
    check(max_abs <= 8 * sigma and rms <= sigma,
          "numerics: fused logits outside the bf16 rounding bound")
    check(top1 >= TOP1_FLOOR, "numerics: top-1 agreement below the floor")


def phase_protect(run) -> None:
    import numpy as np
    from repro.core import FaultSpec, Site
    from repro.launch.serve import build_engine, parse_args, submit_requests
    from repro.serve import batch_faults

    args = parse_args(SERVE_ARGV)
    eng = build_engine(args, run.engine.model, run.engine.params)
    prompts = submit_requests(eng, args, np.random.default_rng(args.seed))
    check(all(np.array_equal(p, q) for p, q in zip(prompts, run.prompts)),
          "protect: resubmitted prompts differ from the clean run's")
    # compute SEU: the exponent MSB of P = exp(S - m) at (slot 0, head 0,
    # chunk row 0, key 0) in every layer's first KV block of the first step
    seu = batch_faults(args.slots, {0: FaultSpec.single(
        Site.EXP, block=0, batch=0, head=0, row=0, col=0, bit=30)})
    # resident SEU after the second step: the exponent MSB (bit 14 of bf16)
    # of one K element in request 0's first block (filled by then)
    flip = dict(layer=5, head=3, row=7, col=11, into="k",
                bit=8 * eng.pool.state.k.dtype.itemsize - 2)
    step = 0
    while eng.scheduler.has_work:
        if step == 2:
            req = next(r for r in eng.scheduler.active_rows() if r.rid == 0)
            flip["block"] = req.block_ids[0]
            eng.inject_kv_fault(**flip)
        eng.step(faults=seu if step == 0 else None)
        step += 1
    outs = {r.rid: np.asarray(r.generated, np.int32)
            for r in eng.scheduler.finished}
    det, cor = _detected(eng)
    ps = eng.paged_stats
    same = sorted(outs) == sorted(run.outputs) and all(
        np.array_equal(outs[r], run.outputs[r]) for r in outs)
    say("protect", f"compute SEU (exp, bit 30) + KV flip (layer 5 block "
                   f"{flip['block']}, bit {flip['bit']}): detected per site "
                   f"[gemm1 exp rowmax rowsum gemm2 kv]={det.tolist()} "
                   f"corrected={cor.tolist()} kv_blocks detected="
                   f"{ps.kv_detected_blocks} repaired={ps.kv_repaired_blocks} "
                   f"retries={eng.stats.retries} tokens_equal_clean={same}")
    check(det[1] >= 1 and cor[1] == det[1],
          "protect: the compute SEU was not detected and corrected")
    check(ps.kv_detected_blocks >= 1 and ps.kv_repaired_blocks >= 1,
          "protect: the resident KV flip was not detected and repaired")
    check(same, "protect: tokens differ from the clean run")


def main() -> int:
    root = Path(__file__).resolve().parent
    if not (root / "src" / "repro").is_dir():
        print(f"chip_smoke: {root} holds no src/repro; run it from the root "
              f"of a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(root / "src"))
    from repro.utils.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax

    try:
        device = phase_device(jax, cache_dir)
        run = phase_serve(jax)
        phase_clean(run)
        phase_numerics(run)
        phase_protect(run)
    except PhaseFailed as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
