"""Serving launcher: fault-tolerant continuous batching over a KV-slot pool
or (``--paged``) the checksummed paged block pool with prefix caching.

CPU-scale demos:
  PYTHONPATH=src python -m repro.launch.serve --arch gpt2-smoke \
      --requests 8 --slots 4 --max-prompt 24 --gen 16 --inject-faults 3
  PYTHONPATH=src python -m repro.launch.serve --arch gpt2-smoke --paged \
      --shared-prefix 16 --kv-flips 2

Full width on a TPU (the path ``chip_smoke.py`` drives):
  PYTHONPATH=src python -m repro.launch.serve --arch gpt2 --chunked-prefill \
      --requests 8 --slots 8 --min-prompt 32 --max-prompt 512 --gen 32

:func:`serve` runs the whole command for a given ``argv`` and returns the
engine, the prompts and the outputs, so a caller can check what was served.
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Dict, List, NamedTuple, Optional, Sequence

import jax
import numpy as np

from repro.configs import get_config
from repro.core import FaultSpec, Site
from repro.models import build_model
from repro.serve import (PagedServeEngine, SamplingParams, ServeEngine,
                         batch_faults)
from repro.utils import get_logger
from repro.utils.compile_cache import enable_compile_cache


def _static_batch_serve(cfg, model, params, rng, args, log):
    """Fallback for families the engine does not batch continuously yet
    (vlm/audio frontends, ssm, enc-dec): the seed's static-batch loop."""
    from repro.serve import greedy_generate
    import jax.numpy as jnp

    tokens = jnp.asarray(rng.integers(
        0, cfg.vocab_size, size=(args.requests, args.max_prompt)), jnp.int32)
    kw = {}
    if cfg.family in ("vlm", "audio"):
        kw["frontend"] = jnp.asarray(rng.standard_normal(
            (args.requests, cfg.frontend_tokens, cfg.d_model)) * 0.1,
            jnp.float32)
    if cfg.family == "encdec":
        kw["enc_tokens"] = jnp.ones((args.requests, 8), jnp.int32)
    t0 = time.time()
    out, rep = greedy_generate(model, params, tokens, steps=args.gen, **kw)
    dt = time.time() - t0
    log.info("static-batch served %s tokens in %.2fs (%.1f tok/s); EFTA "
             "detected=%s", out.shape, dt, out.size / dt,
             np.asarray(rep.detected).tolist())
    print(np.asarray(out))


class ServeRun(NamedTuple):
    """What one :func:`serve` call built and produced."""

    engine: ServeEngine
    prompts: List[np.ndarray]      # in submission order (request id order)
    outputs: Dict[int, np.ndarray]  # request id -> generated tokens


def parse_args(argv: Optional[Sequence[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gpt2-smoke")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--min-prompt", type=int, default=2)
    ap.add_argument("--max-prompt", type=int, default=24)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--cache-len", type=int, default=0,
                    help="KV slots per request (0 = model max_seq)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--inject-faults", type=int, default=0,
                    help="number of decode steps hit by a random SEU")
    ap.add_argument("--paged", action="store_true",
                    help="serve from the checksummed paged KV block pool "
                         "(prefix caching + read-time corruption repair)")
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--num-blocks", type=int, default=0,
                    help="paged pool size (0 = ring-equivalent capacity)")
    ap.add_argument("--kernel", choices=("gather", "fused"), default=None,
                    help="paged decode backend: 'gather' materializes each "
                         "table as a contiguous view and verifies checksums "
                         "outside the kernel (portable baseline); 'fused' "
                         "consumes block tables directly in the paged EFTA "
                         "Pallas kernel with in-loop verification (interpret "
                         "mode off-TPU)")
    ap.add_argument("--kv-verify", choices=("always", "stamped"),
                    default="always",
                    help="gather-backend read-time verify policy: 'always' "
                         "folds every table block each step; 'stamped' skips "
                         "blocks untouched since their last verified read "
                         "(amortized checksums; detection of a flip in a "
                         "stamped block is deferred to its next write or "
                         "the next scrub pass)")
    ap.add_argument("--chunked-prefill", action="store_true",
                    help="serve through the unified multi-token step "
                         "(implies --paged --kernel fused): every engine "
                         "iteration is one mixed batch in which new prompts "
                         "prefill a chunk while running requests decode — "
                         "one compiled program instead of one per prompt "
                         "bucket, and long prompts never head-of-line-block "
                         "decodes")
    ap.add_argument("--chunk-size", type=int, default=0,
                    help="chunk width of the unified multi-token step "
                         "(0 = 2 * block_size); also the gather backend's "
                         "fixed prefill/extend/repair chunk width")
    ap.add_argument("--chunk-budget", type=int, default=0,
                    help="max prompt tokens processed per mixed step "
                         "(0 = unbounded); decodes always proceed")
    ap.add_argument("--scrub-interval", type=int, default=0,
                    help="with --kv-verify stamped: re-fold the oldest-"
                         "verified live blocks every N committed steps "
                         "(bounds the stamped policy's deferred-detection "
                         "window; 0 = off)")
    ap.add_argument("--speculate", choices=("off", "ngram", "draft"),
                    default="off",
                    help="speculative decoding through the propose→score→"
                         "accept step (implies --paged): 'ngram' self-drafts "
                         "by prompt lookup, 'draft' decodes a small draft "
                         "model (--draft-model) through the same EFTA path; "
                         "the unified chunk scores all K drafts in one "
                         "protected launch and rejected rows roll back with "
                         "checksum-verified truncation")
    ap.add_argument("--draft-len", type=int, default=4,
                    help="max draft tokens proposed per request per step")
    ap.add_argument("--draft-model", default="",
                    help="arch name of the draft model for --speculate "
                         "draft (defaults to the serving arch — pure "
                         "self-drafting, acceptance ~1)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="tokens of system prompt shared by every request "
                         "(exercises the prefix cache)")
    ap.add_argument("--kv-flips", type=int, default=0,
                    help="random resident KV-block bit flips injected "
                         "between decode steps (paged only)")
    ap.add_argument("--ft-mode", default=None,
                    help="override the config's EFTA mode (off/detect/correct)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if not 1 <= args.min_prompt <= args.max_prompt:
        ap.error("need 1 <= --min-prompt <= --max-prompt")
    if args.chunked_prefill:
        if args.kernel == "gather":
            ap.error("--chunked-prefill is the fused unified step; it "
                     "contradicts --kernel gather (the gather backend "
                     "chunks its prefill at admission instead)")
        args.paged = True
        args.kernel = "fused"
    if args.speculate != "off":
        args.paged = True              # the propose→score→accept step is
        #                                the paged engine's unified contract
    if not args.paged and (args.kernel is not None
                           or args.kv_verify != "always"
                           or args.chunk_size or args.chunk_budget
                           or args.scrub_interval):
        ap.error("--kernel/--kv-verify/--chunk-size/--chunk-budget/"
                 "--scrub-interval configure the paged engine; add --paged")
    args.kernel = args.kernel or "gather"
    return args


def build_engine(args: argparse.Namespace, model, params) -> ServeEngine:
    """The continuous-batching engine ``args`` asks for (dense / MoE)."""
    if args.paged:
        draft_model = draft_params = None
        if args.speculate == "draft":
            dcfg = get_config(args.draft_model or args.arch)
            if args.ft_mode:
                dcfg = dataclasses.replace(
                    dcfg, ft=dataclasses.replace(dcfg.ft, mode=args.ft_mode))
            draft_model = build_model(dcfg)
            if not args.draft_model or args.draft_model == args.arch:
                draft_params = params      # self-drafting: share weights
            else:
                draft_params = draft_model.init(
                    jax.random.PRNGKey(args.seed + 1))
        eng = PagedServeEngine(model, params, n_slots=args.slots,
                               cache_len=args.cache_len or None,
                               block_size=args.block_size,
                               num_blocks=args.num_blocks or None,
                               kernel=args.kernel, kv_verify=args.kv_verify,
                               chunk_size=args.chunk_size or None,
                               chunk_budget=args.chunk_budget or None,
                               scrub_interval=args.scrub_interval,
                               speculate=args.speculate,
                               draft_len=args.draft_len,
                               draft_model=draft_model,
                               draft_params=draft_params)
    else:
        eng = ServeEngine(model, params, n_slots=args.slots,
                          cache_len=args.cache_len or None)
    return eng


def submit_requests(eng: ServeEngine, args: argparse.Namespace,
                    rng: np.random.Generator) -> List[np.ndarray]:
    """Submit ``--requests`` seeded prompts (an optional shared prefix plus
    ``--min-prompt``..``--max-prompt`` random tokens); returns them."""
    sampling = SamplingParams(temperature=args.temperature,
                              top_k=args.top_k, seed=args.seed)
    vocab = eng.model.cfg.vocab_size
    shared = rng.integers(0, vocab, (args.shared_prefix,)).astype(np.int32)
    prompts = []
    for _ in range(args.requests):
        t = int(rng.integers(args.min_prompt, args.max_prompt + 1))
        prompt = np.concatenate(
            [shared, rng.integers(0, vocab, (t,)).astype(np.int32)])
        prompts.append(prompt)
        eng.submit(prompt, max_new_tokens=args.gen, sampling=sampling)
    return prompts


def serve(argv: Optional[Sequence[str]] = None) -> Optional[ServeRun]:
    """Build the model and engine for ``argv``, submit the seeded requests,
    drive them to completion and log the telemetry. Families without a
    continuous-batching engine run the static-batch loop and return None."""
    args = parse_args(argv)
    log = get_logger("serve")
    cfg = get_config(args.arch)
    if args.ft_mode:
        cfg = dataclasses.replace(
            cfg, ft=dataclasses.replace(cfg.ft, mode=args.ft_mode))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    rng = np.random.default_rng(args.seed)

    if cfg.family not in ("dense", "moe"):
        # frontend / ssm / encdec families predate the continuous-batching
        # engine: serve them through the static-batch greedy loop
        _static_batch_serve(cfg, model, params, rng, args, log)
        return None

    eng = build_engine(args, model, params)
    prompts = submit_requests(eng, args, rng)

    faults_by_step = {}
    n_faults = min(args.inject_faults, args.gen)
    # distinct steps so every requested SEU is actually injected
    for step in rng.choice(args.gen, size=n_faults, replace=False):
        slot = int(rng.integers(0, args.slots))
        spec = FaultSpec.single(
            Site(int(rng.choice([0, 2, 3, 4]))),
            block=0, batch=0, head=int(rng.integers(0, 4)),
            row=0, col=int(rng.integers(0, 16)),
            bit=int(rng.integers(22, 30)))
        faults_by_step[int(step)] = batch_faults(args.slots, {slot: spec})

    t0 = time.time()
    if args.paged and args.kv_flips:
        # drive manually so resident-state SEUs strike *between* steps
        outs, i, flips_left = {}, 0, args.kv_flips
        # an exponent bit of the pool's dtype (bits 24..30 of f32, 8..14 of
        # bf16) so every flip is a real, checksum-visible corruption
        kv_bits = 8 * eng.pool.state.k.dtype.itemsize
        while eng.scheduler.has_work:
            live = [r for r in eng.scheduler.active_rows()
                    if not r.is_done() and eng._pos[r.slot] > 0]
            if live and flips_left and rng.integers(0, 2):
                req = live[int(rng.integers(0, len(live)))]
                j = int(rng.integers(0, len(req.block_ids)))
                eng.inject_kv_fault(
                    layer=int(rng.integers(0, cfg.num_layers)),
                    block=req.block_ids[j],
                    head=int(rng.integers(0, cfg.attn.num_kv_heads)),
                    row=int(rng.integers(0, args.block_size)),
                    col=int(rng.integers(0, cfg.attn.head_dim)),
                    bit=int(rng.integers(kv_bits - 8, kv_bits - 1)),
                    into="k" if rng.integers(0, 2) else "v")
                flips_left -= 1
            eng.step(faults=faults_by_step.get(i))
            i += 1
        outs = {r.rid: np.asarray(r.generated, np.int32)
                for r in eng.scheduler.finished}
    else:
        outs = eng.run(faults_by_step)
    dt = time.time() - t0
    log.info("served %d requests (%d tokens) in %.2fs (%.1f tok/s) over "
             "%d slots in %d engine steps", len(outs), eng.stats.tokens, dt,
             eng.stats.tokens / dt, args.slots, eng.stats.steps)
    summ = eng.telemetry.summary()
    log.info("EFTA telemetry: detected=%d retries=%d status=%s",
             summ["detected"], summ["retries"], summ["status"])
    if args.paged:
        ps, xs = eng.paged_stats, eng.pool.prefix.stats
        log.info("paged cache: prefix hits=%d/%d tokens, kv detected=%d "
                 "repaired=%d scrubbed=%d preemptions=%d evictions=%d "
                 "chunked-prefill tokens=%d",
                 xs.hit_tokens, xs.lookup_tokens, ps.kv_detected_blocks,
                 ps.kv_repaired_blocks, ps.kv_scrubbed_blocks,
                 ps.preemptions, eng.pool.blocks.stats.evictions,
                 ps.chunked_prefill_tokens)
        if args.speculate != "off":
            log.info("speculation (%s): acceptance=%.2f (%d/%d drafts), "
                     "spec steps=%d, tokens/step=%.2f, rolled-back rows=%d, "
                     "rollback-guard detections=%d",
                     args.speculate, eng.acceptance_rate,
                     ps.spec_accepted_tokens, ps.spec_proposed_tokens,
                     ps.spec_steps,
                     eng.stats.tokens / max(eng.stats.steps, 1),
                     ps.spec_rolled_back_rows, ps.rollback_detected_blocks)
    for rid in sorted(outs):
        st = eng.telemetry.requests.get(rid)
        log.info("request %d: %d tokens, detected=%d corrected=%d retries=%d",
                 rid, len(outs[rid]), st.total_detected if st else 0,
                 st.total_corrected if st else 0, st.retries if st else 0)
    return ServeRun(eng, prompts, outs)


def main():
    enable_compile_cache()
    run = serve()
    if run is not None:
        print({rid: run.outputs[rid].tolist() for rid in sorted(run.outputs)})


if __name__ == "__main__":
    main()
