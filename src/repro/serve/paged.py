"""Fault-tolerant paged KV-cache serve engine (vLLM-style block pool).

Replaces the per-slot ring caches with a **global block pool**: KV lives in
fixed-size blocks ``(num_layers, num_blocks, Hkv, block_size, head_dim)``
addressed through per-request block tables, so long prompts draw from a
shared pool, identical prompt prefixes are stored once (hash-chain prefix
cache + refcounted copy-on-write sharing in ``repro.serve.blocks``), and a
preempted request frees exactly its blocks.

Two backends (``kernel=``), one program shape each:

  * ``"fused"`` runs a **unified batched step**: every engine iteration
    builds one mixed batch in which each slot feeds a chunk of up to
    ``chunk_size`` tokens — new prompts prefill chunk by chunk, resumed
    prompts extend, repairs re-prefill a block, and steady-state requests
    decode one token — all through the *same* multi-token fused
    paged-attention Pallas kernel (``repro.kernels.efta_paged`` via
    ``models.attention.PagedKVCache``). There are no per-bucket prefill
    programs and no separate extend jit: XLA compiles exactly two programs
    (chunk width ``chunk_size`` and width 1) regardless of prompt lengths.
    A scheduler ``chunk_budget`` bounds the prompt tokens per step so long
    prompts never head-of-line-block other requests' decodes.
  * ``"gather"`` (portable baseline) materializes each slot's table into the
    contiguous layout the ring engine already decodes
    (``repro.kernels.ops.gather_block_kv``) and vmaps the pure-JAX EFTA
    path; prompt prefill / prefix-extend / repair run through ONE
    fixed-width chunked ``Model.extend`` program (the former power-of-two
    prompt buckets — one compiled program per bucket size — are retired).

Both compute the same values at the same positions, so the paged engine is
**token-identical** to the ring engine and to per-request sequential
decoding on either backend.

Fault story (the paper's resident-state gap): EFTA protects the attention
*computation*, but KV sitting in HBM across thousands of decode steps is
unprotected memory — one SEU in a cached K row silently poisons every later
token. Here every block carries an ABFT-style checksum pair
(``repro.core.checksum.encode_kv`` along the token axis) written on append
and **verified at every read into a decode step** — on the gathered blocks
outside the kernel (``gather``), or in the same kernel pass that streams the
block (``fused``) — so a resident bit flip is detected *at read time* (site
``kv`` in the telemetry 6-vector). The repair is surgical: only the
poisoned block is re-prefilled — through the same unified chunked step
(``fused``: a single-slot chunk with the position rewound to the block
start, so repair can never recompile even under pool pressure) or the
fixed-width extend (``gather``) — then the step retries; a repaired shared
prefix block heals every request mapping it. ``kv_verify="stamped"``
amortizes the gather backend's checksum folds over per-block generation
stamps (``serve.blocks``): steady-state decode folds ~one tail block per
slot instead of the whole table, trading deferred detection of flips that
land in verified-and-untouched blocks. ``scrub_interval`` bounds that
deferral: every N committed steps a background scrub re-folds the
oldest-verified live blocks (``scrub_batch`` per pass), so a flip in a
stamped block is caught within ``interval * ceil(live / batch)`` steps
instead of waiting for the block's next write.

Prefix caching rides the same machinery: a prompt whose leading full blocks
hash-chain-match resident blocks skips straight to chunked extension over
its suffix (bit-identical to full prefill — masked cache slots contribute
exactly zero). Since PR 4 **decode-filled blocks register too**: whenever a
request's generation fills a block, the block joins the token-hash chain, so
n-best / self-consistency resampling of the same prompt + continuation
prefix hits cache instead of re-prefilling (appends to a registered block
copy-on-write-split as before — full blocks are immutable).

Since PR 5 the step is the general **propose→score→accept** contract
(speculative decoding): each slot proposes K candidate tokens
(``repro.serve.draft`` — n-gram prompt lookup or an EFTA-protected draft
model; K = 0 degenerates to plain decode, a prompt suffix to prefill), the
unified chunked program scores pending + drafts in one protected launch
returning per-row logits, and the acceptance stage
(``repro.serve.sampling.speculative_accept``) commits the longest valid
prefix. Rejected rows already appended to blocks are rewound by
fault-tolerant ``kv_len`` truncation (``models.attention.paged_rollback``):
touched tail blocks re-verify against their PRE-rollback checksums before
their checksums are re-generated over the truncated content, so corruption
landing mid-rollback is detected and repaired, never laundered into a
consistent state.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import checksum as cks
from repro.core.fault import FaultSpec, flip_bit_at
from repro.kernels.efta_paged import pages_per_block, paged_fault_descriptor
from repro.kernels.ops import gather_block_kv
from repro.models.api import Model
from repro.models.attention import KVCache, PagedKVCache, paged_rollback
from repro.serve.blocks import NULL_BLOCK, BlockPool, PrefixCache
from repro.serve.cache import add_unit_batch, drop_unit_batch
from repro.serve.draft import build_proposer
from repro.serve.engine import ServeEngine
from repro.serve.sampling import (request_key, sample_tokens,
                                  speculative_accept)
from repro.serve.scheduler import Request
from repro.serve.trace import span


class PagedKVState(NamedTuple):
    """Device-resident block pool. Row 0 of every array is the null block
    (scratch for padded table entries — never verified, never read back)."""

    k: jax.Array     # (L, num_blocks+1, Hkv, block_size, head_dim)
    v: jax.Array
    kc1: jax.Array   # (L, num_blocks+1, Hkv, check_stride, head_dim)
    kc2: jax.Array
    vc1: jax.Array
    vc2: jax.Array


@dataclasses.dataclass
class PagedCacheStats:
    kv_detected_blocks: int = 0    # block-checksum mismatches seen at read
    kv_repaired_blocks: int = 0    # blocks healed by re-prefill
    kv_verified_blocks: int = 0    # read-time fold verifications performed
    kv_verify_skips: int = 0       # verifies skipped by generation stamps
    kv_scrubbed_blocks: int = 0    # blocks re-folded by the background scrub
    preemptions: int = 0
    chunked_prefill_tokens: int = 0  # prompt tokens fed through mixed steps
    # speculative decoding (propose→score→accept)
    spec_steps: int = 0            # committed steps that scored >= 1 draft
    spec_proposed_tokens: int = 0  # draft tokens scored by the target
    spec_accepted_tokens: int = 0  # draft tokens committed
    spec_rolled_back_rows: int = 0  # rejected KV rows truncated by rollback
    rollback_detected_blocks: int = 0  # corruption caught by the rollback
    #                                    pre-restamp (anti-laundering) guard


class PagedKVPool:
    """Device arrays + host allocators for the paged cache.

    Mirrors :class:`repro.serve.cache.KVCachePool`'s slot interface (the
    engine still decodes a fixed ``n_slots``-wide batch) and adds the block
    pool, block tables and prefix cache behind it.
    """

    def __init__(self, model: Model, n_slots: int, cache_len: int,
                 block_size: int, num_blocks: int, check_stride: int):
        cfg = model.cfg
        a = cfg.attn
        if cache_len % block_size:
            raise ValueError("cache_len must be a multiple of block_size")
        dtype = jnp.dtype(cfg.dtype)
        L = cfg.num_layers
        self.n_slots = n_slots
        self.block_size = block_size
        self.max_blocks = cache_len // block_size
        self.num_blocks = num_blocks
        self.check_stride = check_stride
        kv_shape = (L, num_blocks + 1, a.num_kv_heads, block_size, a.head_dim)
        ck_shape = (L, num_blocks + 1, a.num_kv_heads, check_stride,
                    a.head_dim)
        self.state = PagedKVState(
            k=jnp.zeros(kv_shape, dtype), v=jnp.zeros(kv_shape, dtype),
            kc1=jnp.zeros(ck_shape, dtype), kc2=jnp.zeros(ck_shape, dtype),
            vc1=jnp.zeros(ck_shape, dtype), vc2=jnp.zeros(ck_shape, dtype))
        self.blocks = BlockPool(num_blocks, block_size)
        self.prefix = PrefixCache(self.blocks)
        self._free_slots: List[int] = list(range(n_slots))

    # -- slot lifetime (same contract as KVCachePool) -----------------------
    @property
    def free_slots(self) -> int:
        return len(self._free_slots)

    def alloc(self) -> Optional[int]:
        return self._free_slots.pop(0) if self._free_slots else None

    def release(self, slot: int) -> None:
        if slot in self._free_slots:
            raise ValueError(f"slot {slot} double-freed")
        self._free_slots.append(slot)
        self._free_slots.sort()


def _largest_divisor_leq(n: int, cap: int) -> int:
    s = min(cap, n)
    while n % s:
        s -= 1
    return s


class PagedServeEngine(ServeEngine):
    """Continuous-batching engine over a checksummed paged block pool.

    Drop-in for :class:`ServeEngine` (same ``submit``/``step``/``run``), plus
    ``inject_kv_fault`` for resident-state SEU campaigns. ``num_blocks``
    defaults to ring-equivalent capacity (``n_slots * cache_len /
    block_size``); give it headroom to keep evicted prompts' prefix blocks
    resident for longer.

    ``kernel``: ``"gather"`` (portable default) or ``"fused"`` (block-table
    Pallas kernel driving the unified mixed prefill/decode batched step;
    interpret mode off-TPU). ``chunk_size`` is the multi-token step's chunk
    width (>= ``block_size`` so one chunk re-prefills one block; default
    ``2 * block_size``); ``chunk_budget`` caps prompt tokens per mixed step
    (None = unbounded) so prompts never starve decodes. ``kv_verify``:
    ``"always"`` (full read-time coverage, default) or ``"stamped"``
    (generation-stamped fold skipping on the gather backend; the fused
    kernel's in-loop verify is already ~free) — with ``scrub_interval > 0``
    a background scrub re-folds the ``scrub_batch`` oldest-verified live
    blocks every that many committed steps, bounding the stamped policy's
    deferred-detection window. The fused backend reads its checksum
    threshold from ``repro.core.checksum.kv_block_threshold`` — a custom
    ``check_threshold`` only steers the gather-side verification.

    ``speculate`` turns the step into the full propose→score→accept
    contract: ``"ngram"`` self-drafts by prompt lookup, ``"draft"`` decodes
    a small draft model (``draft_model``/``draft_params``) through the same
    EFTA path; up to ``draft_len`` draft rows per slot ride the scored
    chunk (padded to the chunk width — the ≤ 2-compiled-programs invariant
    holds with speculation on), the acceptance stage commits the longest
    valid prefix, and rejected rows roll back from the paged blocks with
    checksum-verified truncation. Greedy speculation is token-identical to
    ``speculate="off"``.
    """

    def __init__(self, model: Model, params, *, n_slots: int = 8,
                 cache_len: Optional[int] = None, block_size: int = 16,
                 num_blocks: Optional[int] = None,
                 check_stride: Optional[int] = None,
                 check_threshold: Optional[float] = None,
                 max_retries: int = 2, retry_on_detect: bool = True,
                 chunk_size: Optional[int] = None,
                 chunk_budget: Optional[int] = None,
                 kernel: str = "gather", kv_verify: str = "always",
                 scrub_interval: int = 0, scrub_batch: int = 4,
                 speculate: str = "off", draft_len: int = 4,
                 draft_model: Optional[Model] = None, draft_params=None):
        if block_size < 1:
            raise ValueError("block_size must be >= 1")
        if kernel not in ("gather", "fused"):
            raise ValueError(f"kernel must be 'gather' or 'fused'; "
                             f"got {kernel!r}")
        if kv_verify not in ("always", "stamped"):
            raise ValueError(f"kv_verify must be 'always' or 'stamped'; "
                             f"got {kv_verify!r}")
        cl = cache_len or model.cfg.max_seq
        cl = -(-cl // block_size) * block_size     # round up to block grid
        self.block_size = block_size
        self.max_blocks = cl // block_size
        self.num_blocks = num_blocks or n_slots * self.max_blocks
        self.check_stride = check_stride or _largest_divisor_leq(block_size, 8)
        if block_size % self.check_stride:
            raise ValueError("check_stride must divide block_size")
        if check_threshold is None:
            check_threshold = cks.kv_block_threshold(model.cfg.dtype)
        self.check_threshold = check_threshold
        self.kernel = kernel
        self.kv_verify = kv_verify
        self.chunk_size = min(chunk_size or 2 * block_size, cl)
        if self.chunk_size < block_size:
            raise ValueError(
                f"chunk_size ({self.chunk_size}) must be >= block_size "
                f"({block_size}): block repair re-prefills one block per "
                f"chunk")
        if scrub_interval and kernel == "fused":
            raise ValueError(
                "scrub_interval is a gather/stamped amortization: the fused "
                "kernel re-verifies every streamed block in-loop each step, "
                "so a background scrub would never run there")
        self.scrub_interval = scrub_interval
        self.scrub_batch = scrub_batch
        if speculate not in ("off", "ngram", "draft"):
            raise ValueError(f"speculate must be 'off', 'ngram' or 'draft'; "
                             f"got {speculate!r}")
        self.speculate = speculate
        if speculate == "off":
            self.draft_len = 0
            self._proposer = None
        else:
            if draft_len < 1:
                raise ValueError("speculation needs draft_len >= 1")
            # the scored chunk is the pending token + K drafts, padded to
            # the chunk width (the ≤2-compiled-programs invariant)
            self.draft_len = min(draft_len, self.chunk_size - 1)
            if self.draft_len < 1:
                raise ValueError(
                    f"chunk_size ({self.chunk_size}) leaves no room for "
                    f"draft rows; speculation needs chunk_size >= 2")
            self._proposer = build_proposer(
                speculate, n_slots=n_slots, cache_len=cl,
                chunk_size=self.chunk_size, draft_model=draft_model,
                draft_params=draft_params)
        # fault-campaign hook: called after the scoring step committed and
        # before the KV rollback runs — lets tests strike resident state
        # mid-rollback and assert the pre-restamp guard catches it
        self._pre_rollback_hook = None
        super().__init__(model, params, n_slots=n_slots, cache_len=cl,
                         max_retries=max_retries,
                         retry_on_detect=retry_on_detect)
        self.scheduler.chunk_budget = chunk_budget
        self.paged_stats = PagedCacheStats()
        # host mirrors of the device block tables / positions, plus the
        # per-slot feed queue: tokens whose KV is not yet resident — the
        # prompt suffix while prefilling, exactly the pending token once
        # decoding. One rule drives the unified step: feed up to chunk_size
        # queue tokens; when the queue drains, sample (the sample becomes
        # the next queue entry).
        self._bt = np.zeros((n_slots, self.max_blocks), np.int32)
        self._pos = np.zeros((n_slots,), np.int32)
        self._queue: List[List[int]] = [[] for _ in range(n_slots)]
        self._admit_seq = 0
        # consecutive steps abandoned because corruption outlived repair
        self._poisoned_steps = 0
        # read-time verification selector: "always" folds every table entry;
        # "stamped" (gather backend only) folds just the entries whose block
        # generation moved since their last verified read, padded to a small
        # fixed width (full fallback when a step needs more — e.g. right
        # after admission). The fused kernel verifies in-loop for free.
        self._sel_all = np.broadcast_to(
            np.arange(self.max_blocks, dtype=np.int32),
            (n_slots, self.max_blocks)).copy()
        self._sel_width = min(4, self.max_blocks)
        if kernel == "fused":
            self._step_fused = jax.jit(self._step_fused_fn)
        else:
            self._score = jax.jit(self._score_fn)
        self._gather_ctx = jax.jit(self._gather_ctx_fn)
        self._extend = jax.jit(self._extend_fn)
        self._scatter = jax.jit(self._scatter_fn)
        self._scrub = jax.jit(self._scrub_fn)
        self._copy_block = jax.jit(self._copy_block_fn)
        self._rollback = jax.jit(self._rollback_fn)
        self._flip = jax.jit(self._flip_fn, static_argnames=("into",))

    def _make_pool(self) -> PagedKVPool:
        return PagedKVPool(self.model, self.n_slots, self.cache_len,
                           self.block_size, self.num_blocks,
                           self.check_stride)

    # -- jitted computations ------------------------------------------------

    def _verify_gathered(self, state: PagedKVState, bt: jax.Array,
                         sel: Optional[jax.Array] = None
                         ) -> Tuple[Any, Any, jax.Array]:
        """Gather K/V blocks for table ``bt`` (..., mb) and verify blocks
        against their resident checksums. Returns (k, v, bad): the contiguous
        KV views attention consumes, and ``bad`` (..., mb) flagging real
        (non-null) blocks with a mismatch in either operand's checksum pair.

        ``sel`` (ns, K) optionally restricts the fold recomputation to K
        table entries per slot (-1 = none): the generation-stamped policy's
        savings come from folding only the blocks whose content could have
        moved since their last verified read, instead of the whole table.
        """
        kraw, kg = gather_block_kv(state.k, bt)
        vraw, vg = gather_block_kv(state.v, bt)
        s = self.check_stride
        thr = self.check_threshold
        if sel is None:
            bad_k, _ = cks.verify_block(
                kraw, cks.Checksums(state.kc1[:, bt], state.kc2[:, bt]), s,
                threshold=thr)
            bad_v, _ = cks.verify_block(
                vraw, cks.Checksums(state.vc1[:, bt], state.vc2[:, bt]), s,
                threshold=thr)
            # reduce (L, ..., mb, Hkv) over layers and heads -> (..., mb)
            bad = jnp.any(bad_k | bad_v, axis=(0, -1)) & (bt > NULL_BLOCK)
            return kg, vg, bad
        selc = jnp.clip(sel, 0, None)                       # (ns, K)
        valid = sel >= 0
        btv = jnp.take_along_axis(bt, selc, axis=1)         # (ns, K)
        idx = selc[None, :, :, None, None, None]
        ksel = jnp.take_along_axis(kraw, idx, axis=2)
        vsel = jnp.take_along_axis(vraw, idx, axis=2)
        bad_k, _ = cks.verify_block(
            ksel, cks.Checksums(state.kc1[:, btv], state.kc2[:, btv]), s,
            threshold=thr)
        bad_v, _ = cks.verify_block(
            vsel, cks.Checksums(state.vc1[:, btv], state.vc2[:, btv]), s,
            threshold=thr)
        bad_sel = (jnp.any(bad_k | bad_v, axis=(0, -1))
                   & (btv > NULL_BLOCK) & valid)            # (ns, K)
        ns = bt.shape[0]
        bad = jnp.zeros(bt.shape, jnp.int32).at[
            jnp.arange(ns)[:, None], selc].max(bad_sel.astype(jnp.int32))
        return kg, vg, bad > 0

    def _decode_fn(self, params, tokens, state, bt, pos, faults, temps,
                   topks, seeds, rids, counters, verify_sel):
        """One batched paged decode step on the gather backend: gather-by-
        block-table, read-time checksum verify, vmapped EFTA decode, append
        + checksum update."""
        cfg = self.model.cfg
        a = cfg.attn
        L, ns, bs = cfg.num_layers, self.n_slots, self.block_size
        kg, vg, bad = self._verify_gathered(state, bt, verify_sel)
        czero = jnp.zeros((L, ns, a.num_kv_heads, 1, a.head_dim), kg.dtype)
        cache = {"attn": KVCache(
            k=kg, v=vg, pos=jnp.broadcast_to(pos[None], (L, ns)),
            ck=czero, cv=czero)}
        axes = jax.tree.map(lambda _: 1, cache)

        def one(tok, row, f):
            logits, rep, new_row = self.model.decode_step(
                params, tok[None, None], add_unit_batch(row), fault=f)
            return logits[0], rep, drop_unit_batch(new_row)

        logits, rep, new_cache = jax.vmap(
            one, in_axes=(0, axes, 0), out_axes=(0, 0, axes))(
                tokens, cache, faults)

        # append: pull the row each slot just wrote at its position and
        # scatter it into that slot's tail block, then refresh the tail
        # block's checksums (appends are writes; verification happens at the
        # *next* gather).
        idx = pos[None, :, None, None, None]
        row_k = jnp.take_along_axis(new_cache["attn"].k, idx, axis=3)[..., 0, :]
        row_v = jnp.take_along_axis(new_cache["attn"].v, idx, axis=3)[..., 0, :]
        tgt = jnp.take_along_axis(bt, (pos // bs)[:, None], axis=1)[:, 0]
        off = pos % bs
        new_k = state.k.at[:, tgt, :, off, :].set(row_k.transpose(1, 0, 2, 3))
        new_v = state.v.at[:, tgt, :, off, :].set(row_v.transpose(1, 0, 2, 3))
        ck = cks.encode_kv(new_k[:, tgt], self.check_stride)
        cv = cks.encode_kv(new_v[:, tgt], self.check_stride)
        new_state = PagedKVState(
            k=new_k, v=new_v,
            kc1=state.kc1.at[:, tgt].set(ck.c1),
            kc2=state.kc2.at[:, tgt].set(ck.c2),
            vc1=state.vc1.at[:, tgt].set(cv.c1),
            vc2=state.vc2.at[:, tgt].set(cv.c2))

        def key_of(seed, rid, counter):
            return jax.random.fold_in(
                jax.random.fold_in(jax.random.PRNGKey(seed), rid), counter)

        keys = jax.vmap(key_of)(seeds, rids, counters)
        next_tokens = sample_tokens(logits, temperature=temps, top_k=topks,
                                    keys=keys)
        return next_tokens, rep, bad, new_state

    def _step_fused_fn(self, params, tokens, state, bt, pos, q_lens, faults,
                       temps, topks, seeds, rids, counters):
        """One unified batched step on the fused backend: every slot feeds a
        chunk of ``q_lens[slot]`` tokens (0 = idle, 1 = decode, more =
        chunked prefill / prefix-extend / block repair / a pending token
        plus speculative draft rows) and the model's attention consumes the
        block pool *directly* through
        :class:`repro.models.attention.PagedKVCache` — one natively batched
        ragged multi-token kernel launch per layer, no contiguous gather,
        resident block checksums verified inside the kernel's KV streaming
        loop, chunk-appended rows checksum-encoded in the same step. The
        fault batch is translated to the kernel's single-SEU descriptor
        (striking chunk row 0 of its target slot). ``tokens.shape[1]`` is
        the only shape degree of freedom, so the engine compiles exactly two
        of these: width ``chunk_size`` and width 1.

        This is the *score* stage of propose→score→accept: the full per-row
        logits ``(ns, C, V)`` come back (f32) for the host acceptance stage
        — row ``c`` of a speculating slot is the target distribution its
        draft row ``c`` was proposed against — alongside the in-jit sampled
        ``next_tokens`` (each slot's logits at ``q_len - 1``), which
        non-speculating slots commit directly."""
        cfg = self.model.cfg
        L = cfg.num_layers
        ns = self.n_slots
        chunk = tokens.shape[1]
        grp = cfg.attn.num_heads // cfg.attn.num_kv_heads
        desc = paged_fault_descriptor(faults, grp, chunk=chunk)
        cache = {"attn": PagedKVCache(
            k=state.k, v=state.v, kc1=state.kc1, kc2=state.kc2,
            vc1=state.vc1, vc2=state.vc2,
            bt=jnp.broadcast_to(bt[None], (L,) + bt.shape),
            pos=jnp.broadcast_to(pos[None], (L,) + pos.shape),
            q_len=jnp.broadcast_to(q_lens[None], (L, ns)),
            bad=jnp.zeros((L, ns, self.max_blocks), jnp.int32))}
        logits, rep, new_cache = self.model.score(
            params, tokens, cache, fault=desc)
        nc = new_cache["attn"]
        bad = jnp.any(nc.bad > 0, axis=0)                  # (ns, mb)
        new_state = PagedKVState(k=nc.k, v=nc.v, kc1=nc.kc1, kc2=nc.kc2,
                                 vc1=nc.vc1, vc2=nc.vc2)

        def key_of(seed, rid, counter):
            return jax.random.fold_in(
                jax.random.fold_in(jax.random.PRNGKey(seed), rid), counter)

        with jax.named_scope("sample"):
            idx = jnp.clip(q_lens - 1, 0, chunk - 1)
            last = jnp.take_along_axis(logits, idx[:, None, None],
                                       axis=1)[:, 0]
            keys = jax.vmap(key_of)(seeds, rids, counters)
            next_tokens = sample_tokens(last, temperature=temps,
                                        top_k=topks, keys=keys)
        # the full per-row plane only leaves the program when an acceptance
        # stage will read it — with speculation off (trace-static) the
        # non-speculative hot path pays nothing for the generalization
        logits_out = logits.astype(jnp.float32) if self._proposer is not None \
            else jnp.zeros((0,), jnp.float32)
        return logits_out, next_tokens, rep, bad, new_state

    def _score_fn(self, params, tokens, state, bt, pos, q_lens, faults,
                  temps, topks, seeds, rids, counters, verify_sel):
        """Multi-token batched scoring step on the gather backend — the
        *score* stage of propose→score→accept for ``kernel="gather"``.

        Like :meth:`_decode_fn` but each slot feeds ``q_lens[slot]`` chunk
        rows (1 = plain decode riding along, more = a pending token plus
        draft rows; 0 = idle): gather-by-block-table with read-time checksum
        verify, a vmapped multi-token EFTA extend per slot (causal within
        the chunk, so row ``c`` conditions on rows ``< c`` exactly as
        sequential decoding would), every valid row's K/V scattered back
        into its block with the touched blocks' checksums regenerated, and
        the FULL per-row logits returned for host acceptance. Padding rows
        past ``q_len`` write to the null block and are causally invisible
        to valid rows. The in-jit ``next_tokens`` (row ``q_len - 1``) serve
        the non-speculating slots.

        One fixed width (``chunk_size``) keeps this a single compiled
        program; the engine only routes through it on steps where some slot
        actually speculates, so the K = 0 path stays byte-for-byte the
        PR-4 width-1 decode."""
        cfg = self.model.cfg
        a = cfg.attn
        L, ns, bs = cfg.num_layers, self.n_slots, self.block_size
        mb = self.max_blocks
        C = tokens.shape[1]
        kg, vg, bad = self._verify_gathered(state, bt, verify_sel)
        czero = jnp.zeros((L, ns, a.num_kv_heads, 1, a.head_dim), kg.dtype)
        cache = {"attn": KVCache(
            k=kg, v=vg, pos=jnp.broadcast_to(pos[None], (L, ns)),
            ck=czero, cv=czero)}
        axes = jax.tree.map(lambda _: 1, cache)

        def one(toks, row, f):
            logits, rep, new_row = self.model.score(
                params, toks[None], add_unit_batch(row), fault=f)
            return logits[0], rep, drop_unit_batch(new_row)

        logits, rep, new_cache = jax.vmap(
            one, in_axes=(0, axes, 0), out_axes=(0, 0, axes))(
                tokens, cache, faults)                      # (ns, C, V)

        # scatter the chunk's appended rows back into their blocks (padding
        # rows divert to the null scratch block), then regenerate exactly
        # the touched blocks' checksums — mirroring the fused append path
        node = new_cache["attn"]
        c_idx = jnp.arange(C, dtype=jnp.int32)
        p_abs = pos[:, None] + c_idx[None, :]               # (ns, C)
        valid = c_idx[None, :] < q_lens[:, None]
        p_clip = jnp.clip(p_abs, 0, self.cache_len - 1)
        take = p_clip[None, :, None, :, None]
        row_k = jnp.take_along_axis(node.k, take, axis=3)   # (L,ns,Hkv,C,hd)
        row_v = jnp.take_along_axis(node.v, take, axis=3)
        jrow = jnp.clip(p_abs // bs, 0, mb - 1)
        tgt_rows = jnp.where(valid, jnp.take_along_axis(bt, jrow, axis=1), 0)
        offs = jnp.where(valid, p_abs % bs, 0)
        vals_k = row_k.transpose(1, 3, 0, 2, 4)             # (ns,C,L,Hkv,hd)
        vals_v = row_v.transpose(1, 3, 0, 2, 4)
        new_k = state.k.at[:, tgt_rows, :, offs, :].set(vals_k)
        new_v = state.v.at[:, tgt_rows, :, offs, :].set(vals_v)
        nt = (C + bs - 2) // bs + 1
        j0 = pos // bs
        jt = j0[:, None] + jnp.arange(nt, dtype=jnp.int32)[None, :]
        last_j = (pos + jnp.maximum(q_lens, 1) - 1) // bs
        touched = (jt <= last_j[:, None]) & (q_lens[:, None] > 0)
        tid = jnp.where(
            touched, jnp.take_along_axis(bt, jnp.clip(jt, 0, mb - 1),
                                         axis=1), 0)
        ck = cks.encode_kv(new_k[:, tid], self.check_stride)
        cv = cks.encode_kv(new_v[:, tid], self.check_stride)
        new_state = PagedKVState(
            k=new_k, v=new_v,
            kc1=state.kc1.at[:, tid].set(ck.c1),
            kc2=state.kc2.at[:, tid].set(ck.c2),
            vc1=state.vc1.at[:, tid].set(cv.c1),
            vc2=state.vc2.at[:, tid].set(cv.c2))

        idx = jnp.clip(q_lens - 1, 0, C - 1)
        last = jnp.take_along_axis(logits, idx[:, None, None], axis=1)[:, 0]

        def key_of(seed, rid, counter):
            return jax.random.fold_in(
                jax.random.fold_in(jax.random.PRNGKey(seed), rid), counter)

        keys = jax.vmap(key_of)(seeds, rids, counters)
        next_tokens = sample_tokens(last, temperature=temps, top_k=topks,
                                    keys=keys)
        return (logits.astype(jnp.float32), next_tokens, rep, bad,
                new_state)

    def _rollback_fn(self, state, bt, keep_pos, old_pos):
        """Jitted fault-tolerant KV rollback (one program for every
        acceptance outcome — ``max_span`` is the static chunk width). See
        :func:`repro.models.attention.paged_rollback`."""
        k, v, kc1, kc2, vc1, vc2, bad = paged_rollback(
            state.k, state.v, state.kc1, state.kc2, state.vc1, state.vc2,
            bt, keep_pos, old_pos, check_stride=self.check_stride,
            threshold=self.check_threshold, max_span=self.chunk_size)
        return PagedKVState(k, v, kc1, kc2, vc1, vc2), bad

    def _gather_ctx_fn(self, state, bids, n_ctx):
        """Materialize a batch-1 contiguous context cache from ``bids`` (mb,)
        holding ``n_ctx`` tokens, verifying the blocks read."""
        cfg = self.model.cfg
        a = cfg.attn
        L = cfg.num_layers
        kg, vg, bad = self._verify_gathered(state, bids)
        in_ctx = jnp.arange(self.max_blocks) * self.block_size < n_ctx
        bad = bad & in_ctx
        kg = kg[:, None]                        # (L, 1, Hkv, cache_len, hd)
        vg = vg[:, None]
        czero = jnp.zeros((L, 1, a.num_kv_heads, 1, a.head_dim), kg.dtype)
        row = {"attn": KVCache(
            k=kg, v=vg, pos=jnp.full((L,), n_ctx, jnp.int32),
            ck=czero, cv=czero)}
        return row, bad

    def _extend_fn(self, params, tokens, row_cache, length, fault):
        return self.model.extend(params, tokens, row_cache,
                                 lengths=length, fault=fault)

    def _scatter_fn(self, state, row_cache, bids, length):
        """Write a batch-1 cache's rows into pool blocks. ``bids`` (mb,)
        names the destination of each block-sized row group (null entries
        discard that group); rows at positions >= ``length`` are zeroed, so a
        partial tail block is stored zero-padded and its checksums cover the
        padding deterministically."""
        mb, bs = self.max_blocks, self.block_size
        node = row_cache["attn"]
        mask = (jnp.arange(mb * bs) < length)[None, None, :, None]

        def blocks_of(x):      # (L, 1, Hkv, cache_len, hd) -> (L,mb,Hkv,bs,hd)
            x = jnp.where(mask, x[:, 0], 0.0)
            L, hkv, _, hd = x.shape
            return x.reshape(L, hkv, mb, bs, hd).transpose(0, 2, 1, 3, 4)

        kb = blocks_of(node.k)
        vb = blocks_of(node.v)
        ck = cks.encode_kv(kb, self.check_stride)
        cv = cks.encode_kv(vb, self.check_stride)
        return PagedKVState(
            k=state.k.at[:, bids].set(kb),
            v=state.v.at[:, bids].set(vb),
            kc1=state.kc1.at[:, bids].set(ck.c1),
            kc2=state.kc2.at[:, bids].set(ck.c2),
            vc1=state.vc1.at[:, bids].set(cv.c1),
            vc2=state.vc2.at[:, bids].set(cv.c2))

    def _scrub_fn(self, state, bids):
        """Background-scrub verify: re-fold the resident checksums of pool
        blocks ``bids`` (K,) straight off the pool (no gather, no attention)
        and flag mismatches. Null padding never flags."""
        s = self.check_stride
        thr = self.check_threshold
        bad_k, _ = cks.verify_block(
            state.k[:, bids],
            cks.Checksums(state.kc1[:, bids], state.kc2[:, bids]), s,
            threshold=thr)
        bad_v, _ = cks.verify_block(
            state.v[:, bids],
            cks.Checksums(state.vc1[:, bids], state.vc2[:, bids]), s,
            threshold=thr)
        return jnp.any(bad_k | bad_v, axis=(0, -1)) & (bids > NULL_BLOCK)

    def _copy_block_fn(self, state, src, dst):
        """Copy-on-write device copy: duplicate block ``src`` (data +
        checksums) into ``dst``."""
        return PagedKVState(*(arr.at[:, dst].set(arr[:, src])
                              for arr in state))

    def _flip_fn(self, state, layer, bid, head, row, col, bit, *, into):
        """Flip one bit of a resident pool block — an SEU striking KV state
        in HBM between decode steps."""
        arr = getattr(state, into)
        L, nb, hkv, bs, hd = arr.shape
        layer = jnp.clip(layer, 0, L - 1)
        bid = jnp.clip(bid, 0, nb - 1)
        head = jnp.clip(head, 0, hkv - 1)
        row = jnp.clip(row, 0, bs - 1)
        col = jnp.clip(col, 0, hd - 1)
        flat = (((layer * nb + bid) * hkv + head) * bs + row) * hd + col
        return state._replace(**{into: flip_bit_at(arr, flat, bit)})

    # -- resident-state fault injection -------------------------------------

    def inject_kv_fault(self, *, layer: int = 0, block: int = 1,
                        head: int = 0, row: int = 0, col: int = 0,
                        bit: int = 27, into: str = "k") -> None:
        """Flip one bit of pool block ``block`` (``into``: "k" | "v"). The
        corruption is persistent resident-state damage: it stays until the
        block checksums catch it at the next read and the engine re-prefills
        the block."""
        if into not in ("k", "v"):
            raise ValueError("into must be 'k' or 'v'")
        self.pool.state = self._flip(
            self.pool.state, jnp.int32(layer), jnp.int32(block),
            jnp.int32(head), jnp.int32(row), jnp.int32(col), jnp.int32(bit),
            into=into)

    # -- admission ----------------------------------------------------------

    def _resident_tokens(self, req: Request) -> np.ndarray:
        """Tokens whose KV this request keeps resident at steady state: the
        prompt plus all generated tokens except the pending one (written
        next step)."""
        gen = req.generated[:-1] if req.generated else []
        return np.concatenate([req.prompt,
                               np.asarray(gen, np.int32)]).astype(np.int32)

    def _feed_tokens(self, req: Request) -> np.ndarray:
        """Every token this request must feed through the model: the prompt
        plus all generated tokens (the last one is the pending decode
        input). The unified step consumes a chunk of these per iteration."""
        return np.concatenate([req.prompt, np.asarray(req.generated,
                                                      np.int32)
                               ]).astype(np.int32)

    def _pad_bids(self, bids: Sequence[int]) -> np.ndarray:
        out = np.zeros((self.max_blocks,), np.int32)
        out[:len(bids)] = bids
        return out

    def _try_admit(self, req: Request) -> Optional[int]:
        """Reserve a slot + KV blocks (prefix-cache hits first). All-or-
        nothing: on failure everything is rolled back and the request keeps
        its place at the head of the queue."""
        if self.pool.free_slots == 0:
            return None
        seq = self._resident_tokens(req)
        t_ctx = len(seq)
        resumed = req.num_generated > 0
        # a fresh prompt must compute >= 1 token to produce logits; a resumed
        # request already knows its pending token and may be fully cached
        max_hit = t_ctx // self.block_size if resumed \
            else (t_ctx - 1) // self.block_size
        hits = self.pool.prefix.match(seq, max_blocks=max_hit)
        for b in hits:                      # claim before alloc can evict
            self.pool.blocks.ref_inc(b)
        n_needed = -(-t_ctx // self.block_size) - len(hits)
        new_bids: List[int] = []
        for _ in range(n_needed):
            b = self.pool.blocks.alloc()
            if b is None:
                for nb in new_bids:
                    self.pool.blocks.ref_dec(nb)
                for h in hits:
                    self.pool.blocks.ref_dec(h)
                return None
            new_bids.append(b)
        slot = self.pool.alloc()
        req.block_ids = list(hits) + new_bids
        req.n_prefix_hit = len(hits)
        return slot

    def _release_request(self, req: Request) -> None:
        slot = req.slot
        for b in req.block_ids:
            self.pool.blocks.ref_dec(b)
        req.block_ids = []
        self._bt[slot] = 0
        self._pos[slot] = 0
        self._queue[slot] = []
        if self._proposer is not None:
            self._proposer.release(slot)
        self.pool.release(slot)

    def _admit(self, req: Request) -> None:
        if self.kernel == "fused":
            self._admit_unified(req)
        else:
            self._admit_gather(req)

    def _admit_unified(self, req: Request) -> None:
        """Admission on the unified backend reserves state only — no
        compute. The prompt suffix past the prefix hit goes on the slot's
        feed queue; the mixed batched step prefills it chunk by chunk
        (budgeted) alongside other slots' decodes, samples the first token
        the moment the queue drains, and from then on the queue holds
        exactly the pending decode token."""
        slot = req.slot
        t_hit = req.n_prefix_hit * self.block_size
        feed = self._feed_tokens(req)
        self._pos[slot] = t_hit
        self._bt[slot] = self._pad_bids(req.block_ids)
        self._queue[slot] = [int(t) for t in feed[t_hit:]]
        s = req.sampling
        self._temps[slot] = s.temperature
        self._topks[slot] = s.top_k
        self._seeds[slot] = s.seed
        self._rids[slot] = req.rid
        self._counters[slot] = req.num_generated
        req.admit_order = self._admit_seq
        self._admit_seq += 1
        self.stats.prefills += 1

    def _chunked_fill(self, row, toks: np.ndarray, start_pos: int,
                      det_acc, cor_acc) -> Tuple[Any, Any, int]:
        """Feed ``toks`` into a contiguous batch-1 row cache through the ONE
        fixed-width chunked extend program (chunks of exactly
        ``chunk_size`` tokens; only the final chunk is padded, and only a
        prompt running into ``cache_len`` narrows the width). Replaces the
        former power-of-two prompt buckets — one compiled program per
        bucket size — with a single program reused for prefill, prefix
        extension and block repair. Returns (last-chunk logits, row,
        retries)."""
        none = FaultSpec.none(1)
        C = self.chunk_size
        i, retries = 0, 0
        logits = None
        n = len(toks)
        while i < n:
            pos = start_pos + i
            w = min(C, self.cache_len - pos)
            fill = min(w, n - i)
            buf = np.zeros((1, w), np.int32)
            buf[0, :fill] = toks[i:i + fill]
            length = jnp.asarray([fill], jnp.int32)
            logits, rep, new_row = self._extend(
                self.params, jnp.asarray(buf), row, length, none)
            det_acc[:5] += np.asarray(rep.detected, np.int64).reshape(-1)[:5]
            cor_acc[:5] += np.asarray(rep.corrected, np.int64).reshape(-1)[:5]
            while self._needs_retry_rows(rep, rows=None) and \
                    retries < self.max_retries:
                retries += 1
                logits, rep, new_row = self._extend(
                    self.params, jnp.asarray(buf), row, length, none)
                det_acc[:5] += np.asarray(rep.detected).reshape(-1)[:5]
                cor_acc[:5] += np.asarray(rep.corrected).reshape(-1)[:5]
            row = new_row
            i += fill
        return logits, row, retries

    def _admit_gather(self, req: Request) -> None:
        seq = self._resident_tokens(req)
        t_ctx = len(seq)
        resumed = req.num_generated > 0
        n_hit = req.n_prefix_hit
        t_hit = n_hit * self.block_size
        slot = req.slot
        det_acc = np.zeros((6,), np.int64)
        cor_acc = np.zeros((6,), np.int64)
        retries = 0
        logits = None

        if t_hit == t_ctx:
            pass                            # resumed & fully cached: no math
        else:
            if n_hit == 0:
                row = self.model.init_cache(1, cache_len=self.cache_len)
            else:
                ctx_bids = jnp.asarray(self._pad_bids(req.block_ids[:n_hit]))
                while True:
                    row, bad = self._gather_ctx(self.pool.state, ctx_bids,
                                                jnp.int32(t_hit))
                    bad_idx = np.flatnonzero(np.asarray(bad))
                    if bad_idx.size == 0:
                        break
                    # a shared prefix block rotted in HBM: repair before use
                    det_acc[5] += bad_idx.size
                    cor_acc[5] += bad_idx.size
                    self.paged_stats.kv_detected_blocks += int(bad_idx.size)
                    self._repair_blocks(req, bad_idx, resident=seq)
            logits, row, retries = self._chunked_fill(
                row, seq[t_hit:], t_hit, det_acc, cor_acc)
            sc = [NULL_BLOCK] * n_hit + req.block_ids[n_hit:]
            self.pool.state = self._scatter(
                self.pool.state, row, jnp.asarray(self._pad_bids(sc)),
                jnp.int32(t_ctx))
            for wb in req.block_ids[n_hit:]:
                self.pool.blocks.note_write(wb)

        self.pool.prefix.insert(seq, req.block_ids)
        self.telemetry.observe_prefill(req.rid, det_acc, cor_acc,
                                       retries=retries)
        req.retries += retries
        req.admit_order = self._admit_seq
        self._admit_seq += 1
        self.stats.prefills += 1
        self.stats.retries += retries

        s = req.sampling
        if resumed:
            tok = req.generated[-1]
            self._counters[slot] = req.num_generated
        else:
            key = jax.random.fold_in(request_key(s, req.rid), 0)
            first = sample_tokens(
                logits.astype(jnp.float32),
                temperature=jnp.asarray([s.temperature], jnp.float32),
                top_k=jnp.asarray([s.top_k], jnp.int32), keys=key[None])
            tok = int(first[0])
            req.generated.append(tok)
            self._counters[slot] = 1
            self.stats.tokens += 1
        self._pending[slot] = tok
        self._temps[slot] = s.temperature
        self._topks[slot] = s.top_k
        self._seeds[slot] = s.seed
        self._rids[slot] = req.rid
        self._bt[slot] = self._pad_bids(req.block_ids)
        self._pos[slot] = t_ctx

    # -- pressure: tail blocks, COW, preemption -----------------------------

    def _preempt_for_blocks(self, needy: Request) -> bool:
        """Preempt the youngest other running request to free blocks."""
        victims = [r for r in self.scheduler.active_rows()
                   if r is not needy and not r.is_done()]
        if not victims:
            return False
        victim = max(victims, key=lambda r: r.admit_order)
        slot = victim.slot
        self.scheduler.preempt(victim)
        self._release_request(victim)
        victim.slot = None
        self.paged_stats.preemptions += 1
        return True

    def _alloc_block_or_preempt(self, req: Request) -> int:
        while True:
            b = self.pool.blocks.alloc()
            if b is not None:
                return b
            if not self._preempt_for_blocks(req):
                raise RuntimeError(
                    "paged KV pool exhausted: a single request needs more "
                    "blocks than the pool holds; raise num_blocks")

    def _ensure_capacity(self, req: Request, n_new: int) -> None:
        """Back the next ``n_new`` KV rows of ``req`` (positions ``pos ..
        pos + n_new - 1``) with writable private blocks: allocate fresh tail
        blocks, copy-on-write-split shared ones (a registered or
        prefix-shared block must not observe the append), preempting the
        youngest other request under pool pressure."""
        slot = req.slot
        pos = int(self._pos[slot])
        bs = self.block_size
        for bi in range(pos // bs, (pos + max(n_new, 1) - 1) // bs + 1):
            if req.slot is None:
                return              # preempted (cannot happen for req itself)
            if bi >= len(req.block_ids):
                b = self._alloc_block_or_preempt(req)
                req.block_ids.append(b)
                self._bt[slot, bi] = b
            else:
                tail = req.block_ids[bi]
                if self.pool.blocks.is_shared(tail):
                    wb, needs_copy = self.pool.blocks.cow(tail)
                    if wb is None:
                        wb = self._alloc_block_or_preempt(req)
                        self.pool.blocks.ref_dec(tail)
                        needs_copy = True
                    if needs_copy:
                        self.pool.state = self._copy_block(
                            self.pool.state, jnp.int32(tail), jnp.int32(wb))
                        self.pool.blocks.note_write(wb)
                    req.block_ids[bi] = wb
                    self._bt[slot, bi] = wb

    def _ensure_tail_blocks(self) -> None:
        """Before a gather decode step every active slot writes one KV row
        at its position — make sure a private tail block backs it."""
        for req in list(self.scheduler.active_rows()):
            if req.slot is None:
                continue        # preempted by an earlier request's alloc
            slot = req.slot
            if req.is_done():
                # finished at admission; decodes garbage until evicted next
                # iteration — point its writes at the null block
                self._bt[slot] = 0
                self._pos[slot] = 0
                continue
            self._ensure_capacity(req, 1)

    # -- prefix registration (prompt AND decode-filled blocks) --------------

    def _register_full_blocks(self, req: Request, old_pos: int,
                              new_pos: int) -> None:
        """Register every newly *completed* block of ``req`` in the
        token-hash-chain prefix cache. Beyond shared prompts, this covers
        decode-filled blocks: a later request replaying the same prompt +
        continuation prefix (n-best / self-consistency resampling) hits
        cache instead of re-prefilling. Full blocks are immutable — a
        subsequent append to a registered block copy-on-write-splits via
        the existing machinery."""
        bs = self.block_size
        if new_pos // bs <= old_pos // bs:
            return
        toks = self._feed_tokens(req)[:new_pos]
        self.pool.prefix.insert(toks, req.block_ids)

    # -- read-time verification policy --------------------------------------

    def _verify_selector(self):
        """Pick the table entries this gather decode attempt re-verifies.

        Returns ``(sel, folds, skips)``: ``sel`` is None for full coverage
        (the "always" policy), else an (n_slots, K) int32 selector (-1 =
        empty). Under the generation-stamped policy only blocks written
        since their last verified read need a fold — in steady-state decode
        that is one tail block per slot instead of the whole table, which is
        where the gather path's checksum overhead (the ~0.85x decode
        regression) goes. A step needing more than K folds per slot (e.g.
        right after an admission scattered a whole prompt) falls back to
        full coverage.
        """
        live = [r for r in self.scheduler.active_rows()
                if r.slot is not None and not r.is_done()]
        n_real = sum(len(r.block_ids) for r in live)
        if self.kv_verify == "always":
            return None, n_real, 0
        sel = np.full((self.n_slots, self._sel_width), -1, np.int32)
        need_total = 0
        for r in live:
            need = [j for j, bid in enumerate(r.block_ids)
                    if self.pool.blocks.needs_verify(bid)]
            if len(need) > self._sel_width:
                return self._sel_all, n_real, 0       # full-coverage fallback
            sel[r.slot, :len(need)] = need
            need_total += len(need)
        return sel, need_total, n_real - need_total

    # -- background scrub (bounds the stamped policy's deferred window) -----

    def _scrub_pass(self) -> None:
        """Re-fold the ``scrub_batch`` oldest-verified live blocks against
        their resident checksums — including blocks the stamped selector
        skips as verified-and-untouched, which is exactly where a deferred
        flip hides. A mismatch is repaired immediately through the normal
        block re-prefill path; clean blocks refresh their verification
        clock so the scrub cursor keeps rotating.

        Leftover batch capacity draws from the **parked prefix-cache
        blocks** (ref == 0, retained for future hits): they sit in no live
        table, so read-time verification never reaches them and a flip
        would otherwise wait for the next admission gather to surface. A
        corrupted parked block is discarded (prefix-cache entry forgotten,
        block freed) — detection-before-use repair for cache-only state:
        the next admission takes a clean miss and re-prefills."""
        live = {}
        for req in self.scheduler.active_rows():
            if req.slot is None or req.is_done():
                continue
            for j, bid in enumerate(req.block_ids):
                live.setdefault(bid, (req, j))
        order = sorted(live, key=self.pool.blocks.verified_at)
        batch = order[:self.scrub_batch]
        if len(batch) < self.scrub_batch:
            parked = sorted(self.pool.blocks.parked_blocks(),
                            key=self.pool.blocks.verified_at)
            batch = batch + parked[:self.scrub_batch - len(batch)]
        if not batch:
            return
        padded = batch + [NULL_BLOCK] * (self.scrub_batch - len(batch))
        bad = np.asarray(self._scrub(self.pool.state,
                                     jnp.asarray(padded, dtype=jnp.int32)))
        self.paged_stats.kv_scrubbed_blocks += len(batch)
        for bid, is_bad in zip(batch, bad[:len(batch)]):
            if bid in live:
                req, j = live[bid]
                if is_bad:
                    self.paged_stats.kv_detected_blocks += 1
                    six = np.zeros((6,), np.int64)
                    six[5] = 1
                    self.telemetry.observe_prefill(req.rid, six, six)
                    self._repair_blocks(req, [j])
                else:
                    self.pool.blocks.mark_verified(bid)
            else:                           # parked prefix-cache block
                if is_bad:
                    self.paged_stats.kv_detected_blocks += 1
                    self.telemetry.observe_scrub(1)
                    self.pool.blocks.discard_parked(bid)
                else:
                    self.pool.blocks.mark_verified(bid)

    # -- read-time repair ---------------------------------------------------

    def _repair_blocks(self, req: Request, bad_idx, *,
                       resident: Optional[np.ndarray] = None,
                       healed: Optional[set] = None) -> None:
        """Re-prefill the poisoned blocks of one request, left to right, so
        each repair runs against already-verified (or just-repaired) context.
        Shared blocks heal in place for every request mapping them (``healed``
        dedupes repairs of a shared block flagged from several slots). The
        fused backend routes every repair through the SAME unified chunked
        program the mixed batch runs; the gather backend through the same
        fixed-width extend as admission — either way repair never compiles
        anything new, even under pool pressure."""
        if self.kernel == "fused":
            self._repair_blocks_unified(req, bad_idx, resident=resident,
                                        healed=healed)
        else:
            self._repair_blocks_gather(req, bad_idx, resident=resident,
                                       healed=healed)

    def _repair_blocks_unified(self, req: Request, bad_idx, *,
                               resident: Optional[np.ndarray] = None,
                               healed: Optional[set] = None) -> None:
        slot = req.slot
        bs = self.block_size
        if resident is None:
            resident = self._feed_tokens(req)[:int(self._pos[slot])]
        for j in sorted(int(i) for i in bad_idx):
            start = j * bs
            n_fill = min(bs, len(resident) - start)
            if n_fill <= 0:
                continue
            if healed is not None:
                if req.block_ids[j] in healed:
                    continue
                healed.add(req.block_ids[j])
            # single-slot chunk with the position rewound to the block
            # start: the kernel recomputes exactly this block's rows against
            # the (verified) preceding context and the chunk scatter +
            # checksum refresh rewrites only block j. Other slots ride along
            # with q_len = 0 and are untouched.
            tokens = np.zeros((self.n_slots, self.chunk_size), np.int32)
            tokens[slot, :n_fill] = resident[start:start + n_fill]
            q_lens = np.zeros((self.n_slots,), np.int32)
            q_lens[slot] = n_fill
            pos_vec = self._pos.copy()
            pos_vec[slot] = start
            _, _, _, _, new_state = self._step_fused(*self._fused_args(
                jnp.asarray(tokens), pos_vec, jnp.asarray(q_lens),
                self._no_faults))
            self.pool.state = new_state
            self.pool.blocks.note_write(req.block_ids[j])
            self.paged_stats.kv_repaired_blocks += 1

    def _repair_blocks_gather(self, req: Request, bad_idx, *,
                              resident: Optional[np.ndarray] = None,
                              healed: Optional[set] = None) -> None:
        bs = self.block_size
        seq = self._resident_tokens(req) if resident is None else resident
        none = FaultSpec.none(1)
        for j in sorted(int(i) for i in bad_idx):
            start = j * bs
            n_fill = min(bs, len(seq) - start)
            if n_fill <= 0:
                continue
            if healed is not None:
                if req.block_ids[j] in healed:
                    continue
                healed.add(req.block_ids[j])
            ctx_bids = jnp.asarray(self._pad_bids(req.block_ids[:j]))
            row, _ = self._gather_ctx(self.pool.state, ctx_bids,
                                      jnp.int32(start))
            w = min(self.chunk_size, self.cache_len - start)
            toks = np.zeros((1, w), np.int32)
            toks[0, :n_fill] = seq[start:start + n_fill]
            _, _, new_row = self._extend(
                self.params, jnp.asarray(toks), row,
                jnp.asarray([n_fill], jnp.int32), none)
            sc = [NULL_BLOCK] * self.max_blocks
            sc[j] = req.block_ids[j]
            self.pool.state = self._scatter(
                self.pool.state, new_row, jnp.asarray(sc, dtype=jnp.int32),
                jnp.int32(start + n_fill))
            self.pool.blocks.note_write(req.block_ids[j])
            self.paged_stats.kv_repaired_blocks += 1

    # -- speculation: propose / accept / roll back --------------------------

    @property
    def acceptance_rate(self) -> float:
        """Fraction of scored draft tokens the target accepted."""
        ps = self.paged_stats
        return 0.0 if not ps.spec_proposed_tokens \
            else ps.spec_accepted_tokens / ps.spec_proposed_tokens

    def _spec_cap(self, req: Request) -> int:
        """Most draft rows this request may score this step: bounded by the
        configured draft length, the chunk width (the pending token takes
        one row), and the request's remaining token budget (a spec step
        commits at most K + 1 tokens, never past ``max_new_tokens``)."""
        return max(0, min(self.draft_len, self.chunk_size - 1,
                          req.max_new_tokens - req.num_generated - 1))

    def _propose_drafts(self, active_reqs: Sequence[Request],
                        draft_grants: Dict[int, int]
                        ) -> Dict[int, np.ndarray]:
        """Run the proposer for every slot granted draft budget. Returns
        slot -> draft tokens (slots with empty proposals are left out — the
        K = 0 degenerate path). Draft-pass EFTA telemetry (the draft
        model's own detections/retries) is folded into the per-request
        draft counters here."""
        spec: Dict[int, np.ndarray] = {}
        for r in active_reqs:
            kd = draft_grants.get(r.rid, 0)
            if kd <= 0 or r.slot is None:
                continue
            d = self._proposer.propose(r.slot, self._feed_tokens(r), kd)
            rep = self._proposer.drain_report()
            if rep is not None:
                det, cor, retries = rep
                six_d = np.concatenate([det, [0]]).astype(np.int64)
                six_c = np.concatenate([cor, [0]]).astype(np.int64)
                self.telemetry.observe_draft(r.rid, six_d, six_c,
                                             retries=retries)
            if len(d):
                spec[r.slot] = np.asarray(d, np.int32)
        return spec

    def _accept_slot(self, req: Request, rows: np.ndarray,
                     drafts: np.ndarray
                     ) -> Tuple[List[int], Optional[int]]:
        """Acceptance verdict for one slot's scored chunk. ``rows``:
        (k+1, V) target logits (row j scored draft j; row k feeds the bonus
        token). Returns ``(drafts_committed, bonus)`` — the accepted draft
        prefix (possibly EOS-truncated) and the follow-up token (``None``
        when an accepted EOS ends the request before the bonus row)."""
        s = req.sampling
        rng = None
        if s.temperature > 0.0:
            # per-(request, step) deterministic stream, independent of the
            # in-jit sampler's keys (greedy never consults it)
            rng = np.random.default_rng(
                (abs(int(s.seed)), int(req.rid), int(req.num_generated)))
        a, t_next = speculative_accept(
            rows, drafts, temperature=float(s.temperature),
            top_k=int(s.top_k), rng=rng)
        drafts_committed = [int(t) for t in drafts[:a]]
        bonus: Optional[int] = int(t_next)
        if req.eos_id is not None:
            for i, t in enumerate(drafts_committed):
                if t == req.eos_id:
                    drafts_committed = drafts_committed[:i + 1]
                    bonus = None
                    break
        return drafts_committed, bonus

    def _apply_rollback(self, rollback_plan: Dict[int, Tuple[int, int]],
                        by_slot: Dict[int, Request]) -> None:
        """Truncate the rejected draft rows of every speculating slot in one
        jitted pass (``kv_len`` truncation + tail-block checksum
        re-generation), with the anti-laundering guard: blocks that fail
        their PRE-rollback checksums are flagged, counted as site-6
        detections, and re-prefilled from committed tokens — corruption
        that struck between the scoring step's verify and this rollback is
        detected, never silently restamped into a consistent state."""
        if self._pre_rollback_hook is not None:
            self._pre_rollback_hook(self)
        keep = self._pos.copy()                 # already rewound to keep_pos
        oldp = self._pos.copy()
        for slot, (keep_pos, scored_pos) in rollback_plan.items():
            keep[slot] = keep_pos
            oldp[slot] = scored_pos
        if not (oldp > keep).any():
            return
        new_state, bad = self._rollback(
            self.pool.state, jnp.asarray(self._bt), jnp.asarray(keep),
            jnp.asarray(oldp))
        self.pool.state = new_state
        bs = self.block_size
        for slot, (keep_pos, scored_pos) in rollback_plan.items():
            if scored_pos <= keep_pos:
                continue
            req = by_slot[slot]
            self.paged_stats.spec_rolled_back_rows += scored_pos - keep_pos
            for bi in range(keep_pos // bs,
                            min((scored_pos - 1) // bs + 1,
                                len(req.block_ids))):
                self.pool.blocks.note_write(req.block_ids[bi])
        bad_np = np.asarray(bad)
        for slot in list(rollback_plan):
            idxs = np.flatnonzero(bad_np[slot])
            if idxs.size == 0:
                continue
            req = by_slot[slot]
            self.paged_stats.rollback_detected_blocks += int(idxs.size)
            self.paged_stats.kv_detected_blocks += int(idxs.size)
            six = np.zeros((6,), np.int64)
            six[5] = idxs.size
            self.telemetry.observe_prefill(req.rid, six, six)
            # blocks holding committed rows re-prefill from the committed
            # tokens (resident passed explicitly: after an accepted EOS
            # draft every generated token's KV row is resident, unlike the
            # non-speculative pending-token convention). A flagged block
            # wholly past the committed prefix needs no re-prefill — the
            # rollback just rewrote and restamped it and none of its rows
            # are reachable below kv_len — so the truncation IS its repair.
            keep_pos = int(self._pos[slot])
            resident = self._feed_tokens(req)[:keep_pos]
            trunc_only = sum(1 for j in idxs if j * bs >= keep_pos)
            self.paged_stats.kv_repaired_blocks += trunc_only
            self._repair_blocks(req, idxs, resident=resident)

    # -- stepping -----------------------------------------------------------

    def step(self, faults: Optional[FaultSpec] = None) -> List[Request]:
        """One engine iteration. EFTA in-compute SEUs behave exactly as in
        the ring engine; additionally every KV block read is checksum-
        verified, and a mismatch triggers block re-prefill + step retry
        before anything is committed. The fused backend runs the unified
        mixed prefill/decode batched step; the gather backend the
        single-token decode step (its prompts prefill at admission).

        The step runs inside the profiler span ``serve.step`` (see
        :mod:`repro.serve.trace`); on the fused backend its parts have
        spans of their own and ``serve.step`` carries the paged kernel's
        table slots a grid step (``kv_pages_per_block``), the program
        ``width``, the requests fed (``running``), the rows fed and the
        ``attempts``."""
        if self.kernel == "fused":
            with span("step", kv_pages_per_block=pages_per_block(
                    self.block_size, self.max_blocks)) as step_span:
                return self._step_unified(faults, step_span)
        with span("step"):
            return self._step_gather(faults)

    def lower_step(self, chunk: int):
        """Lower the fused unified step at chunk width ``chunk`` against the
        live pool, with the argument types :meth:`_step_unified` passes:
        the program the compiler is given (its ``as_text()`` shows whether
        the paged kernel is compiled, as a ``tpu_custom_call``)."""
        if self.kernel != "fused":
            raise ValueError("lower_step inspects the fused unified step")
        ns = self.n_slots
        return self._step_fused.lower(*self._fused_args(
            jnp.zeros((ns, chunk), jnp.int32), self._pos,
            jnp.zeros((ns,), jnp.int32), self._no_faults))

    def _fused_args(self, tokens, pos, q_lens, faults):
        """Arguments of ``_step_fused``: this step's token chunk, per-slot
        positions, chunk lengths and fault plan, plus the live pool, block
        tables and sampling state."""
        return (self.params, tokens, self.pool.state, jnp.asarray(self._bt),
                jnp.asarray(pos), q_lens, faults, jnp.asarray(self._temps),
                jnp.asarray(self._topks), jnp.asarray(self._seeds),
                jnp.asarray(self._rids), jnp.asarray(self._counters))

    def _step_unified(self, faults: Optional[FaultSpec],
                      step_span) -> List[Request]:
        with span("schedule"):
            decision = self.scheduler.step(self._try_admit,
                                           self._release_request)
            for req in decision.admitted:
                self._admit(req)
            finished = list(decision.evicted)
            for r in self.scheduler.active_rows():
                if r.is_done() and r.slot is not None:
                    # finished at admission; computes garbage until evicted
                    # next iteration — park its writes on the null block
                    self._bt[r.slot] = 0
                    self._pos[r.slot] = 0
                    self._queue[r.slot] = []
            active_reqs = [r for r in self.scheduler.active_rows()
                           if not r.is_done()]
            if not active_reqs:
                return finished

            # chunk plan: one token per request unconditionally (decodes
            # never starve), prompt surplus FCFS within the scheduler's
            # chunk budget; with speculation on, steady-state decodes
            # additionally propose up to draft_len candidate rows from the
            # leftover budget (prompt chunks rank first — speculation never
            # starves admissions)
            demands = [(r, len(self._queue[r.slot])) for r in active_reqs]
            spec_tokens: Dict[int, np.ndarray] = {}
            if self._proposer is not None:
                wants = {r.rid: self._spec_cap(r) for r in active_reqs}
                grants, draft_grants = self.scheduler.plan_chunks(
                    demands, self.chunk_size, draft_wants=wants)
                spec_tokens = self._propose_drafts(active_reqs, draft_grants)
            else:
                grants = self.scheduler.plan_chunks(demands, self.chunk_size)
            for r in list(active_reqs):
                need = grants[r.rid] + len(spec_tokens.get(r.slot, ()))
                if r.slot is not None and need > 0:
                    self._ensure_capacity(r, need)
            active_reqs = [r for r in active_reqs
                           if r.slot is not None and not r.is_done()]
            spec_tokens = {s: d for s, d in spec_tokens.items()
                           if any(r.slot == s for r in active_reqs)}
            if not active_reqs:
                return finished
        active = [r.slot for r in active_reqs]
        by_slot = {r.slot: r for r in active_reqs}

        with span("prepare"):
            # pure-decode steps run the width-1 program; any prefill surplus
            # or draft row promotes the step to the chunk-width program (the
            # only two shapes this engine ever compiles — draft K pads to
            # the chunk)
            chunk = self.chunk_size if (spec_tokens or any(
                grants[r.rid] > 1 for r in active_reqs)) else 1
            tokens = np.zeros((self.n_slots, chunk), np.int32)
            q_lens = np.zeros((self.n_slots,), np.int32)
            for r in active_reqs:
                g = grants[r.rid]
                tokens[r.slot, :g] = self._queue[r.slot][:g]
                d = spec_tokens.get(r.slot)
                if d is not None:
                    tokens[r.slot, g:g + len(d)] = d
                    g += len(d)
                q_lens[r.slot] = g
            tok_dev = jnp.asarray(tokens)
            qlen_dev = jnp.asarray(q_lens)
            args = self._fused_args(tok_dev, self._pos, qlen_dev,
                                    self._no_faults if faults is None
                                    else faults)

        kv_det = np.zeros((self.n_slots,), np.int64)
        kv_cor = np.zeros((self.n_slots,), np.int64)
        efta_retries = 0
        kv_retries = 0
        det_acc = np.zeros((self.n_slots, 5), np.int64)
        cor_acc = np.zeros((self.n_slots, 5), np.int64)
        redet_acc = np.zeros((self.n_slots, 5), np.int64)
        kv_redet = np.zeros((self.n_slots,), np.int64)
        seen_bad: set = set()
        while True:
            is_retry = (efta_retries + kv_retries) > 0
            if is_retry:
                with span("prepare"):
                    args = self._fused_args(tok_dev, self._pos, qlen_dev,
                                            self._no_faults)
            with span("dispatch"):
                logits, next_tokens, rep, bad, new_state = \
                    self._step_fused(*args)
            # drop this hold on the pre-step pool: ``self.pool.state`` keeps
            # it for a retry, and the commit frees it, well before the next
            # step allocates its own output pool
            del args
            with span("wait"):
                # every output the host reads is ready after this: the reads
                # below copy, they do not wait for the device
                jax.block_until_ready((logits, next_tokens, rep, bad))
            det_acc += np.asarray(rep.detected, np.int64)
            cor_acc += np.asarray(rep.corrected, np.int64)
            if is_retry:
                redet_acc += np.asarray(rep.detected, np.int64)
            bad_np = np.asarray(bad)
            kv_hit_slots = [s for s in active if bad_np[s].any()]
            if kv_hit_slots:
                # resident corruption: the attempt read poisoned KV — repair
                # the blocks, drop the attempt (nothing committed), retry.
                # KV retries have their own (>= 1) budget independent of the
                # EFTA one: committing an attempt derived from poisoned KV
                # would bake the corruption into the refreshed block
                # checksums and make it permanently undetectable.
                kv_det[kv_hit_slots] += bad_np[kv_hit_slots].sum(-1)
                if is_retry:
                    kv_redet[kv_hit_slots] += bad_np[kv_hit_slots].sum(-1)
                bad_bids = {by_slot[s].block_ids[j] for s in kv_hit_slots
                            for j in np.flatnonzero(bad_np[s])
                            if j < len(by_slot[s].block_ids)}
                self.paged_stats.kv_detected_blocks += \
                    len(bad_bids - seen_bad)
                seen_bad |= bad_bids
                healed: set = set()
                with span("repair"):
                    for s in kv_hit_slots:
                        idxs = np.flatnonzero(bad_np[s])
                        kv_cor[s] += idxs.size
                        self._repair_blocks(by_slot[s], idxs, healed=healed)
                if kv_retries < max(1, self.max_retries):
                    kv_retries += 1
                    continue
            if self._needs_retry_rows(rep, rows=active) and \
                    efta_retries < self.max_retries:
                efta_retries += 1
                continue
            break
        retries = efta_retries + kv_retries
        step_span.set_metadata(width=chunk, running=len(active_reqs),
                               rows=int(q_lens.sum()), attempts=retries + 1)

        if kv_hit_slots:
            # the FINAL attempt still read poisoned KV: see _step_gather —
            # commit nothing, keep repairs, escalate if it persists.
            per_request = {
                r.rid: (np.concatenate([det_acc[r.slot],
                                        kv_det[r.slot:r.slot + 1]]),
                        np.concatenate([cor_acc[r.slot],
                                        kv_cor[r.slot:r.slot + 1]]),
                        np.concatenate([redet_acc[r.slot],
                                        kv_redet[r.slot:r.slot + 1]]))
                for r in active_reqs}
            for r in active_reqs:
                r.retries += retries
            self.telemetry.observe_step(per_request, retries=retries)
            self.stats.retries += retries
            self._poisoned_steps += 1
            if self._poisoned_steps > 3:
                raise RuntimeError(
                    "resident KV corruption persists across block re-prefills "
                    "on consecutive steps — failing memory, not a transient "
                    "SEU; cordon this host and restart elsewhere")
            return finished

        with span("commit"):
            self._poisoned_steps = 0
            self.pool.state = new_state
            next_np = np.asarray(next_tokens)
            logits_np = np.asarray(logits) if spec_tokens else None
            per_request = {}
            rollback_plan: Dict[int, Tuple[int, int]] = {}
            bs = self.block_size
            for req in active_reqs:
                slot = req.slot
                g = int(q_lens[slot])
                old_pos = int(self._pos[slot])
                new_pos = old_pos + g
                req.retries += retries
                d = spec_tokens.get(slot)
                if d is not None:
                    # accept: commit the longest valid draft prefix + the
                    # bonus/resample token; rewind the slot past the
                    # rejected rows (the KV rollback below truncates them
                    # on-device)
                    k = len(d)
                    committed_drafts, bonus = self._accept_slot(
                        req, logits_np[slot, :k + 1], d)
                    a = len(committed_drafts)
                    keep_pos = old_pos + 1 + a   # pending + accepted rows
                    for bi in range(old_pos // bs,
                                    min((new_pos - 1) // bs + 1,
                                        len(req.block_ids))):
                        self.pool.blocks.note_write(req.block_ids[bi])
                    req.generated.extend(committed_drafts)
                    if bonus is not None:
                        req.generated.append(bonus)
                    self._queue[slot] = [] if bonus is None else [bonus]
                    self._counters[slot] = req.num_generated
                    self._pos[slot] = keep_pos
                    n_new = a + (0 if bonus is None else 1)
                    self.stats.tokens += n_new
                    self.paged_stats.spec_proposed_tokens += k
                    self.paged_stats.spec_accepted_tokens += a
                    self.telemetry.observe_draft(
                        req.rid, np.zeros(6, np.int64), np.zeros(6, np.int64),
                        proposed=k, accepted=a)
                    if keep_pos < new_pos:
                        rollback_plan[slot] = (keep_pos, new_pos)
                    self._register_full_blocks(req, old_pos, keep_pos)
                elif g:
                    if g > 1:
                        self.paged_stats.chunked_prefill_tokens += g
                    # the chunk rewrote these blocks: their generations move
                    # (and the prefix cache learns any block it completed)
                    for bi in range(old_pos // bs,
                                    min((new_pos - 1) // bs + 1,
                                        len(req.block_ids))):
                        self.pool.blocks.note_write(req.block_ids[bi])
                    del self._queue[slot][:g]
                    self._pos[slot] = new_pos
                    if not self._queue[slot]:
                        # queue drained: this chunk's last row produced the
                        # next token (first sample for a fresh prompt, the
                        # steady-state decode sample otherwise)
                        tok = int(next_np[slot])
                        req.generated.append(tok)
                        self._queue[slot] = [tok]
                        self._counters[slot] = req.num_generated
                        self.stats.tokens += 1
                    self._register_full_blocks(req, old_pos, new_pos)
                per_request[req.rid] = (
                    np.concatenate([det_acc[slot], kv_det[slot:slot + 1]]),
                    np.concatenate([cor_acc[slot], kv_cor[slot:slot + 1]]),
                    np.concatenate([redet_acc[slot], kv_redet[slot:slot + 1]]))
            if spec_tokens:
                self.paged_stats.spec_steps += 1
            if rollback_plan:
                self._apply_rollback(rollback_plan, by_slot)
            self.telemetry.observe_step(per_request, retries=retries)
            self.stats.steps += 1
            self.stats.retries += retries
        return finished

    def _step_gather(self, faults: Optional[FaultSpec] = None
                     ) -> List[Request]:
        decision = self.scheduler.step(self._try_admit, self._release_request)
        for req in decision.admitted:
            self._admit(req)
        finished = list(decision.evicted)
        self._ensure_tail_blocks()
        active_reqs = [r for r in self.scheduler.active_rows()
                       if not r.is_done()]
        if not active_reqs:
            return finished

        # speculation (gather): the chunk-wide scoring program writes C rows
        # into each slot's contiguous temp view, so it needs headroom
        # ``pos + C <= cache_len`` on every slot (a ring wrap in the temp
        # would clobber context rows); near the boundary the step falls back
        # to the K = 0 width-1 decode below.
        spec_tokens: Dict[int, np.ndarray] = {}
        if self._proposer is not None and all(
                int(self._pos[r.slot]) + self.chunk_size <= self.cache_len
                for r in active_reqs):
            wants = {r.rid: self._spec_cap(r) for r in active_reqs}
            _, draft_grants = self.scheduler.plan_chunks(
                [(r, 1) for r in active_reqs], self.chunk_size,
                draft_wants=wants)
            spec_tokens = self._propose_drafts(active_reqs, draft_grants)
            for r in list(active_reqs):
                d = spec_tokens.get(r.slot)
                if d is not None and r.slot is not None:
                    self._ensure_capacity(r, 1 + len(d))
            if spec_tokens:
                # capacity pressure may have preempted someone — refilter
                active_reqs = [r for r in self.scheduler.active_rows()
                               if not r.is_done() and r.slot is not None]
                spec_tokens = {s: d for s, d in spec_tokens.items()
                               if any(r.slot == s for r in active_reqs)}
                if not active_reqs:
                    return finished
        if spec_tokens:
            return self._step_gather_spec(faults, finished, active_reqs,
                                          spec_tokens)

        active = [r.slot for r in active_reqs]
        by_slot = {r.slot: r for r in active_reqs}

        if faults is None:
            faults = self._no_faults
        kv_det = np.zeros((self.n_slots,), np.int64)
        kv_cor = np.zeros((self.n_slots,), np.int64)
        efta_retries = 0
        kv_retries = 0
        attempt_faults = faults
        det_acc = np.zeros((self.n_slots, 5), np.int64)
        cor_acc = np.zeros((self.n_slots, 5), np.int64)
        redet_acc = np.zeros((self.n_slots, 5), np.int64)
        kv_redet = np.zeros((self.n_slots,), np.int64)
        seen_bad: set = set()
        while True:
            is_retry = (efta_retries + kv_retries) > 0
            sel, folds, skips = self._verify_selector()
            self.paged_stats.kv_verified_blocks += folds
            self.paged_stats.kv_verify_skips += skips
            args = (jnp.asarray(self._pending), self.pool.state,
                    jnp.asarray(self._bt), jnp.asarray(self._pos),
                    attempt_faults, jnp.asarray(self._temps),
                    jnp.asarray(self._topks), jnp.asarray(self._seeds),
                    jnp.asarray(self._rids), jnp.asarray(self._counters),
                    None if sel is None else jnp.asarray(sel))
            next_tokens, rep, bad, new_state = self._decode(self.params, *args)
            det_acc += np.asarray(rep.detected, np.int64)
            cor_acc += np.asarray(rep.corrected, np.int64)
            if is_retry:
                redet_acc += np.asarray(rep.detected, np.int64)
            bad_np = np.asarray(bad)
            kv_hit_slots = [s for s in active if bad_np[s].any()]
            if kv_hit_slots:
                # resident corruption: the attempt read poisoned KV — repair
                # the blocks, drop the attempt (nothing committed), retry.
                # KV retries have their own (>= 1) budget independent of the
                # EFTA one: committing an attempt derived from a poisoned
                # gather would bake the corruption into the tail block's
                # refreshed checksums and make it permanently undetectable.
                kv_det[kv_hit_slots] += bad_np[kv_hit_slots].sum(-1)
                if is_retry:
                    kv_redet[kv_hit_slots] += bad_np[kv_hit_slots].sum(-1)
                # pool-level stats count distinct *blocks*, once per step (a
                # shared prefix block flagged from several slots, or again on
                # a retry, is one corruption), so detected == repaired holds
                # under sharing; per-request telemetry above stays per-slot
                bad_bids = {by_slot[s].block_ids[j] for s in kv_hit_slots
                            for j in np.flatnonzero(bad_np[s])}
                self.paged_stats.kv_detected_blocks += \
                    len(bad_bids - seen_bad)
                seen_bad |= bad_bids
                healed: set = set()
                for s in kv_hit_slots:
                    idxs = np.flatnonzero(bad_np[s])
                    kv_cor[s] += idxs.size
                    self._repair_blocks(by_slot[s], idxs, healed=healed)
                if kv_retries < max(1, self.max_retries):
                    kv_retries += 1
                    attempt_faults = self._no_faults
                    continue
            if self._needs_retry_rows(rep, rows=active) and \
                    efta_retries < self.max_retries:
                efta_retries += 1
                attempt_faults = self._no_faults
                continue
            break
        retries = efta_retries + kv_retries

        if kv_hit_slots:
            # the FINAL attempt still read poisoned KV: a block that stays
            # corrupted through repeated re-prefills is being re-corrupted
            # underneath us (failing HBM, not a transient SEU). Committing
            # would bake the corruption into refreshed tail checksums and go
            # permanently silent — so commit nothing: repairs stay applied,
            # pending tokens are untouched, the next engine iteration
            # re-attempts, and the sustained detections drive the
            # FaultRateMonitor toward its "cordon" escalation.
            per_request = {
                r.rid: (np.concatenate([det_acc[r.slot],
                                        kv_det[r.slot:r.slot + 1]]),
                        np.concatenate([cor_acc[r.slot],
                                        kv_cor[r.slot:r.slot + 1]]),
                        np.concatenate([redet_acc[r.slot],
                                        kv_redet[r.slot:r.slot + 1]]))
                for r in active_reqs}
            for r in active_reqs:
                r.retries += retries
            self.telemetry.observe_step(per_request, retries=retries)
            self.stats.retries += retries
            self._poisoned_steps += 1
            if self._poisoned_steps > 3:
                raise RuntimeError(
                    "resident KV corruption persists across block re-prefills "
                    "on consecutive steps — failing memory, not a transient "
                    "SEU; cordon this host and restart elsewhere")
            return finished

        # commit
        self._poisoned_steps = 0
        self.pool.state = new_state
        if self.kv_verify == "stamped":
            # stamp what the committed attempt verified, BEFORE noting the
            # tail appends below (a stamp covers the pre-write generation)
            for req in active_reqs:
                entries = (range(len(req.block_ids)) if sel is None
                           or sel is self._sel_all
                           else [int(j) for j in sel[req.slot] if j >= 0])
                for j in entries:
                    if j < len(req.block_ids):
                        self.pool.blocks.mark_verified(req.block_ids[j])
        next_np = np.asarray(next_tokens)
        per_request = {}
        for req in active_reqs:
            slot = req.slot
            tok = int(next_np[slot])
            old_pos = int(self._pos[slot])
            req.generated.append(tok)
            req.retries += retries
            self._pending[slot] = tok
            self._counters[slot] += 1
            # the decode appended one KV row into the tail block: its
            # generation moves, so the stamp invalidates (re-verified next
            # read under the stamped policy)
            self.pool.blocks.note_write(
                req.block_ids[old_pos // self.block_size])
            self._pos[slot] += 1
            self._register_full_blocks(req, old_pos, old_pos + 1)
            per_request[req.rid] = (
                np.concatenate([det_acc[slot], kv_det[slot:slot + 1]]),
                np.concatenate([cor_acc[slot], kv_cor[slot:slot + 1]]),
                np.concatenate([redet_acc[slot], kv_redet[slot:slot + 1]]))
            self.stats.tokens += 1
        self.telemetry.observe_step(per_request, retries=retries)
        self.stats.steps += 1
        self.stats.retries += retries
        if self.kv_verify == "stamped" and self.scrub_interval and \
                self.stats.steps % self.scrub_interval == 0:
            self._scrub_pass()
        return finished

    def _step_gather_spec(self, faults, finished: List[Request],
                          active_reqs: List[Request],
                          spec_tokens: Dict[int, np.ndarray]
                          ) -> List[Request]:
        """Gather-backend propose→score→accept step: at least one slot
        scored draft rows, so the batch routes through the chunk-wide
        ``_score`` program (slots without drafts ride along with
        ``q_len = 1`` — their committed token is the in-jit sample of row 0,
        the same value the width-1 decode would produce). Mirrors
        :meth:`_step_gather`'s KV-repair/EFTA retry discipline, then runs
        the acceptance stage and the fault-tolerant KV rollback."""
        active = [r.slot for r in active_reqs]
        by_slot = {r.slot: r for r in active_reqs}
        C = self.chunk_size
        tokens = np.zeros((self.n_slots, C), np.int32)
        q_lens = np.zeros((self.n_slots,), np.int32)
        for r in active_reqs:
            slot = r.slot
            tokens[slot, 0] = self._pending[slot]
            g = 1
            d = spec_tokens.get(slot)
            if d is not None:
                tokens[slot, 1:1 + len(d)] = d
                g += len(d)
            q_lens[slot] = g

        if faults is None:
            faults = self._no_faults
        kv_det = np.zeros((self.n_slots,), np.int64)
        kv_cor = np.zeros((self.n_slots,), np.int64)
        efta_retries = 0
        kv_retries = 0
        attempt_faults = faults
        det_acc = np.zeros((self.n_slots, 5), np.int64)
        cor_acc = np.zeros((self.n_slots, 5), np.int64)
        redet_acc = np.zeros((self.n_slots, 5), np.int64)
        kv_redet = np.zeros((self.n_slots,), np.int64)
        seen_bad: set = set()
        tok_dev = jnp.asarray(tokens)
        qlen_dev = jnp.asarray(q_lens)
        while True:
            is_retry = (efta_retries + kv_retries) > 0
            sel, folds, skips = self._verify_selector()
            self.paged_stats.kv_verified_blocks += folds
            self.paged_stats.kv_verify_skips += skips
            logits, next_tokens, rep, bad, new_state = self._score(
                self.params, tok_dev, self.pool.state,
                jnp.asarray(self._bt), jnp.asarray(self._pos), qlen_dev,
                attempt_faults, jnp.asarray(self._temps),
                jnp.asarray(self._topks), jnp.asarray(self._seeds),
                jnp.asarray(self._rids), jnp.asarray(self._counters),
                None if sel is None else jnp.asarray(sel))
            det_acc += np.asarray(rep.detected, np.int64)
            cor_acc += np.asarray(rep.corrected, np.int64)
            if is_retry:
                redet_acc += np.asarray(rep.detected, np.int64)
            bad_np = np.asarray(bad)
            kv_hit_slots = [s for s in active if bad_np[s].any()]
            if kv_hit_slots:
                # same contract as _step_gather: repair, drop the attempt,
                # retry — never commit an attempt that read poisoned KV
                kv_det[kv_hit_slots] += bad_np[kv_hit_slots].sum(-1)
                if is_retry:
                    kv_redet[kv_hit_slots] += bad_np[kv_hit_slots].sum(-1)
                bad_bids = {by_slot[s].block_ids[j] for s in kv_hit_slots
                            for j in np.flatnonzero(bad_np[s])
                            if j < len(by_slot[s].block_ids)}
                self.paged_stats.kv_detected_blocks += \
                    len(bad_bids - seen_bad)
                seen_bad |= bad_bids
                healed: set = set()
                for s in kv_hit_slots:
                    idxs = np.flatnonzero(bad_np[s])
                    kv_cor[s] += idxs.size
                    self._repair_blocks(by_slot[s], idxs, healed=healed)
                if kv_retries < max(1, self.max_retries):
                    kv_retries += 1
                    attempt_faults = self._no_faults
                    continue
            if self._needs_retry_rows(rep, rows=active) and \
                    efta_retries < self.max_retries:
                efta_retries += 1
                attempt_faults = self._no_faults
                continue
            break
        retries = efta_retries + kv_retries

        if kv_hit_slots:
            # final attempt still read poisoned KV — commit nothing (see
            # _step_gather for the full rationale)
            per_request = {
                r.rid: (np.concatenate([det_acc[r.slot],
                                        kv_det[r.slot:r.slot + 1]]),
                        np.concatenate([cor_acc[r.slot],
                                        kv_cor[r.slot:r.slot + 1]]),
                        np.concatenate([redet_acc[r.slot],
                                        kv_redet[r.slot:r.slot + 1]]))
                for r in active_reqs}
            for r in active_reqs:
                r.retries += retries
            self.telemetry.observe_step(per_request, retries=retries)
            self.stats.retries += retries
            self._poisoned_steps += 1
            if self._poisoned_steps > 3:
                raise RuntimeError(
                    "resident KV corruption persists across block "
                    "re-prefills on consecutive steps — failing memory, not "
                    "a transient SEU; cordon this host and restart "
                    "elsewhere")
            return finished

        # commit
        self._poisoned_steps = 0
        self.pool.state = new_state
        if self.kv_verify == "stamped":
            for req in active_reqs:
                entries = (range(len(req.block_ids)) if sel is None
                           or sel is self._sel_all
                           else [int(j) for j in sel[req.slot] if j >= 0])
                for j in entries:
                    if j < len(req.block_ids):
                        self.pool.blocks.mark_verified(req.block_ids[j])
        next_np = np.asarray(next_tokens)
        logits_np = np.asarray(logits)
        per_request = {}
        rollback_plan: Dict[int, Tuple[int, int]] = {}
        bs = self.block_size
        for req in active_reqs:
            slot = req.slot
            old_pos = int(self._pos[slot])
            g = int(q_lens[slot])
            scored_pos = old_pos + g
            req.retries += retries
            d = spec_tokens.get(slot)
            if d is None:
                tok = int(next_np[slot])
                req.generated.append(tok)
                self._pending[slot] = tok
                self._counters[slot] += 1
                self.pool.blocks.note_write(
                    req.block_ids[old_pos // bs])
                self._pos[slot] = old_pos + 1
                self._register_full_blocks(req, old_pos, old_pos + 1)
                self.stats.tokens += 1
            else:
                k = len(d)
                committed_drafts, bonus = self._accept_slot(
                    req, logits_np[slot, :k + 1], d)
                a = len(committed_drafts)
                keep_pos = old_pos + 1 + a
                for bi in range(old_pos // bs,
                                min((scored_pos - 1) // bs + 1,
                                    len(req.block_ids))):
                    self.pool.blocks.note_write(req.block_ids[bi])
                req.generated.extend(committed_drafts)
                if bonus is not None:
                    req.generated.append(bonus)
                    self._pending[slot] = bonus
                self._counters[slot] = req.num_generated
                self._pos[slot] = keep_pos
                self.stats.tokens += a + (0 if bonus is None else 1)
                self.paged_stats.spec_proposed_tokens += k
                self.paged_stats.spec_accepted_tokens += a
                self.telemetry.observe_draft(
                    req.rid, np.zeros(6, np.int64), np.zeros(6, np.int64),
                    proposed=k, accepted=a)
                if keep_pos < scored_pos:
                    rollback_plan[slot] = (keep_pos, scored_pos)
                self._register_full_blocks(req, old_pos, keep_pos)
            per_request[req.rid] = (
                np.concatenate([det_acc[slot], kv_det[slot:slot + 1]]),
                np.concatenate([cor_acc[slot], kv_cor[slot:slot + 1]]),
                np.concatenate([redet_acc[slot], kv_redet[slot:slot + 1]]))
        self.paged_stats.spec_steps += 1
        if rollback_plan:
            self._apply_rollback(rollback_plan, by_slot)
        self.telemetry.observe_step(per_request, retries=retries)
        self.stats.steps += 1
        self.stats.retries += retries
        if self.kv_verify == "stamped" and self.scrub_interval and \
                self.stats.steps % self.scrub_interval == 0:
            self._scrub_pass()
        return finished
