"""Continuous batching scheduler — fused device-side decode ticks over
a shared paged-KV pool with radix-tree prefix caching.

Fixed decode batch of B slots over one shared KV cache. One scheduler
tick is ONE fused, jitted device step: decode + sampling + per-slot
EOS/length masking all run on device, and the host reads back a single
packed (B, 3) int32 array per tick — at most one host<->device token
transfer regardless of slot count (the seed read every slot's token
individually). An admission additionally reads its prefill token as one
scalar at admission time, so TTFT never waits for the next full tick.

**Position-stable chunked prefill.** Prompts prefill at absolute
positions 0..n-1 in page-aligned chunks (``repro.serving.pagepool.
chunk_plan``) — no left-padding, no power-of-two buckets — so a token
prefix always produces bitwise-identical KV regardless of how long the
rest of the conversation is. That is the property the prefix cache
trades on: admission looks up the longest cached page-aligned prefix of
the prompt in the radix tree (keyed by token-id pages under the
request's ``cache_salt``), splices the matching pool pages straight into
the admission cache, and chunked prefill starts *after* the cached
prefix. A multi-turn follow-up or a shared-system-prompt query prefills
only its suffix. Pages are published back to the tree as prefill
completes them and again at finish/cancel for the decoded extension, so
a session's KV outlives the session instead of being discarded with the
slot. Chunked pacing (one ``prefill_chunk`` worth of pages per tick)
still protects in-flight decodes from long admissions.

The finished batch=1 admission cache is spliced into its slot with a
**bucketed/paged copy**: only the pages actually used by the prompt are
written along every "kv_seq" axis (``repro.serving.pagepool.
SlotSplicer``); recurrent-state leaves (SSM, xLSTM conv windows) are
copied whole per slot. Per-slot positions ride in ``cache["pos"]`` as a
(B,) vector — all model decode paths accept either a scalar or a vector.

Straggler/fault hooks: a per-request deadline; requests that exceed it
are cancelled, their ``on_done`` fires with ``cancelled=True``, and the
slot is re-admitted *on the same tick* (the dual-channel relay reaps the
channel on its own timer — see repro.core.relay).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.ref import dequantize_kv, quantize_kv
from repro.models.common import cache_layout, has_state_leaves, round_up
from repro.models.layers import rope_shift
from repro.serving.pagepool import PagePool, SlotSplicer, chunk_plan
from repro.serving.prefix_cache import PrefixCache, PrefixLease
from repro.serving.sampler import (GenerationParams, StopMatcher,
                                   sample_slots, speculative_accept)
from repro.serving.speculative import ModelDrafter, NgramDrafter, SpecStats
from repro.serving.tokenizer import ByteTokenizer


def clip_prompt(ids, max_new_tokens: int, max_seq: int) -> tuple:
    """The one capacity rule, kept deliberately conservative: the budget
    charges the whole power-of-two BUCKET the prompt length rounds up to
    (a holdover from left-padded prefill; position-stable prefill only
    occupies ``len(ids)`` positions, so this over-reserves but can never
    let decode write past the seq axis), and decode writes
    ``max_new_tokens - 1`` more positions (the first token comes from
    the prefill logits), so the invariant is

        bucket(len(ids)) + max_new_tokens <= max_seq + 1

    Returns ``(ids, max_new_tokens)`` with the prompt clipped to the
    next bucket down and/or the budget clamped when the prompt cannot
    shrink further. Shared by generate(), the batcher admission path,
    and the broker's accounting."""
    ids = list(ids)

    def bucket(n):
        b = 16
        while b < n:
            b *= 2
        return min(b, max_seq - 1)

    min_bucket = min(16, max(max_seq - 1, 1))
    max_new = max(min(max_new_tokens, max_seq + 1 - min_bucket), 1)
    keep = min(len(ids), max(max_seq - max_new - 1, 1))
    while bucket(keep) + max_new > max_seq + 1 and keep > 1:
        keep = bucket(keep) // 2     # drop to the next smaller bucket
    return ids[:keep], max_new


@dataclass(frozen=True)
class WindowPolicy:
    """Attention-sink rolling window over a slot's paged KV.

    ``sink_pages`` pages are pinned for the session's life (the
    StreamingLLM attention sinks: the prompt head every later token
    attends to), ``window_pages`` roll: when the slot has filled sinks +
    window, the oldest ``roll_pages`` non-sink pages are evicted in
    place — block-table rewrite plus a per-slot ``pos_offset`` bump, no
    KV copies — and their token span is handed to the async span
    summarizer. Cached keys stay valid across a roll because rope
    positions are *slot-relative* (``pos - pos_offset``): sinks keep
    their original rotations and the retained window is re-rotated by
    exactly ``-roll_pages * page`` (rope composes, so this is the key a
    fresh prefill at the shifted position would have produced).

    A slot under a policy therefore decodes unboundedly at a flat
    ``cap_pages = sink_pages + window_pages + 1`` pages (the spare page
    keeps every decode/verify write inside the mapping between roll
    checks). Only the native paged path qualifies — recurrent families
    (SSM/xLSTM) have no page-granular state to evict and decline the
    policy, keeping append-only KV.
    """
    sink_pages: int = 1
    window_pages: int = 4
    roll_pages: int = 1

    def __post_init__(self):
        assert self.sink_pages >= 1 and self.window_pages >= 1
        assert 1 <= self.roll_pages <= self.window_pages

    @property
    def cap_pages(self) -> int:
        return self.sink_pages + self.window_pages + 1


@dataclass
class Request:
    rid: str
    prompt_ids: list
    max_new_tokens: int = 32
    on_token: Optional[Callable[[int, str], None]] = None
    on_done: Optional[Callable[["Request"], None]] = None
    deadline_s: float = 0.0          # 0 = none
    params: Optional[GenerationParams] = None   # per-request sampling/stop
    cache_salt: str = ""             # prefix-cache tenant key (gateway auth)
    prefix_hit_tokens: int = 0       # prefill tokens served from the cache
    submitted_at: float = field(default_factory=time.perf_counter)
    admitted_at: Optional[float] = None   # popped from the queue for admission
    output_ids: list = field(default_factory=list)
    done: bool = False
    cancelled: bool = False
    finish_reason: str = ""          # "stop" | "length" | "cancelled"
    error: Optional[str] = None      # set when a scheduler fault ended it
    _stop: Optional[StopMatcher] = None
    _lease: Optional[PrefixLease] = None   # pinned prefix-tree chain
    _kv_ids: Optional[list] = None         # clipped prompt (KV token basis)
    # paged decode mode: the slot's block-table mapping. _pages[p] is the
    # pool page backing token page p; _own[p] marks pages this session
    # allocated privately (freed or published at finish) vs matched tree
    # pages (pinned via the lease, never freed by the session)
    _pages: list = field(default_factory=list)
    _own: list = field(default_factory=list)
    _rolls: int = 0                  # window rolls this session has taken

    def _matcher(self) -> Optional[StopMatcher]:
        if self._stop is None and self.params and self.params.stop:
            self._stop = StopMatcher(self.params.stop)
        return self._stop

    def emit(self, tid: int, text: str) -> bool:
        """Deliver one decoded token through the stop matcher. Text that
        may begin a stop sequence is withheld until disambiguated (the
        delivered text can therefore lag the token that produced it);
        returns True when a stop sequence completed — the stop string
        itself is never delivered."""
        m = self._matcher()
        if m is None:
            if self.on_token:
                self.on_token(tid, text)
            return False
        d = m.feed(text)
        if d and self.on_token:
            self.on_token(tid, d)
        return m.stopped

    def flush_stop(self, deliver: bool = True):
        """Stream ended without a stop match: release the withheld tail
        (it is real output) before ``on_done`` fires."""
        m = self._stop
        if m is None or m.stopped:
            return
        d = m.flush()
        if d and deliver and self.on_token:
            self.on_token(-1, d)

    def final_text(self, tokenizer) -> str:
        """Response text honoring stop semantics: for a stopped request
        the text ends BEFORE the stop sequence (stream and non-stream
        responses agree); otherwise it is the full decoded output."""
        if self._stop is not None:
            return self._stop.text
        return tokenizer.decode(self.output_ids)


@dataclass
class _Admission:
    """An in-flight chunked prefill over the suffix the prefix cache
    could not serve. ``pieces`` are the remaining page-aligned chunk
    lengths; ``pos`` is the absolute prefill position (cached prefix
    included)."""
    req: Request
    slot: int
    cache: dict                      # batch=1 cache being filled
    ids: list                        # clipped prompt (absolute token basis)
    pieces: list                     # remaining chunk lengths
    pos: int = 0                     # tokens prefilled so far (incl. cached)
    poff: int = 0                    # tokens rolled out during this prefill
    lease: Optional[PrefixLease] = None
    temp: float = 0.0                # resolved per-request sampling params
    top_p: float = 1.0
    seed: int = -1                   # -1 -> shared per-tick rng


class ContinuousBatcher:
    def __init__(self, engine, *, slots: int = 4, max_seq: int | None = None,
                 prefill_chunk: int = 32, page: int = 16,
                 prefix_pages: int | None = None):
        self.engine = engine
        self.model = engine.model
        self.cfg = engine.cfg
        self.B = slots
        self.max_seq = max_seq or engine.max_seq
        self.tokenizer: ByteTokenizer = engine.tokenizer
        self.prefill_chunk = prefill_chunk
        self.page = page

        self._layout = cache_layout(self.model.cache_specs())
        self._splicer = SlotSplicer(self._layout)
        # shared paged-KV pool + radix-tree prefix cache. The pool — not
        # the slots — owns reusable KV memory; slots borrow pages at
        # admission and publish their extensions back at finish.
        if prefix_pages is None:
            prefix_pages = getattr(engine, "prefix_cache_pages", 0)
        # Quantized pools (engine.kv_dtype int8/fp8_e4m3) serve only the
        # native paged path — the copying splice path stays fp32 — so
        # pageability is decided before the pool is built and a pool
        # that will serve splices is always full-precision.
        will_page = (bool(prefix_pages)
                     and not has_state_leaves(self._layout)
                     and self.max_seq % page == 0
                     and prefix_pages >= self.max_seq // page
                     and getattr(engine, "paged_kv", True))
        kv_dtype = (getattr(engine, "kv_dtype", "fp32") or "fp32") \
            if will_page else "fp32"
        self.pool = (PagePool(self.model, page=page, capacity=prefix_pages,
                              kv_dtype=kv_dtype)
                     if prefix_pages else None)
        self.prefix = PrefixCache(self.pool) if self.pool is not None else None
        # Native paged decode: attention-only models serve every slot
        # straight out of the pool buffers through per-slot block tables
        # — admission of a cached prefix is a pointer write, publish is
        # an ownership transfer, and the splice copy disappears. Needs
        # max_seq page-aligned (gathered view == contiguous view, the
        # token-identity invariant) and the pool big enough for one
        # worst-case slot. Stateful families (SSM/xLSTM/cross-KV) keep
        # the contiguous splice path: their state has no page address.
        self.n_pages = self.max_seq // page
        self.paged = (self.pool is not None
                      and not self.pool.stateful
                      and self.max_seq % page == 0
                      and prefix_pages >= self.n_pages
                      and getattr(engine, "paged_kv", True))
        self.admissions = 0
        self._stall = False
        if self.paged:
            self.cache = self.pool.paged_cache(self.B, self.n_pages)
            self._bt = np.zeros((self.B, self.n_pages), np.int32)
            self._bt_dirty = False
            self._pool_keys = [k for k in self.cache
                               if k not in ("pos", "pos_offset",
                                            "block_tables")]
        else:
            self.cache = self.model.init_cache(self.B, self.max_seq)
            self.cache["pos"] = jnp.zeros((self.B,), jnp.int32)
        # rolling-window policy (unbounded sessions at bounded memory).
        # Needs the native paged path — the roll is pure block-table
        # surgery plus a pos_offset bump, and recurrent state has no
        # page address — so stateful families and the contiguous splice
        # path decline it and keep append-only KV.
        policy = getattr(engine, "window_policy", None)
        self.window: Optional[WindowPolicy] = (
            policy if (policy is not None and self.paged
                       and policy.cap_pages <= self.n_pages) else None)
        # async span-summarization sink: rolled-out token spans are
        # handed over per (rid, ids) off the decode path
        self.span_sink = getattr(engine, "span_summarizer", None)
        self.rolls = 0               # window rolls across all sessions
        self._poff = np.zeros(self.B, np.int64)   # host pos_offset mirror
        if self.window is not None:
            self._rope_leaves = self._roped_leaf_axes()
            self._shift_fns: dict[int, Callable] = {}
        self.active: list[Optional[Request]] = [None] * self.B
        self.queue: list[Request] = []
        self._adm: Optional[_Admission] = None
        self._freed = False
        self.tok = jnp.zeros((self.B, 1), jnp.int32)

        # host mirror of the device-side per-slot state (passed into the
        # fused step each tick; tiny int/bool vectors, not token traffic)
        self._active_m = np.zeros(self.B, bool)
        self._gen = np.zeros(self.B, np.int32)
        self._maxgen = np.full(self.B, 1, np.int32)
        # per-slot generation params (GenerationParams resolved against
        # the engine's SamplerConfig at admission time)
        sc = engine.sampler
        self._temp = np.full(self.B, sc.temperature, np.float32)
        self._topp = np.full(self.B, sc.top_p, np.float32)
        self._seed = np.full(self.B, -1, np.int32)

        self._prefill = jax.jit(self.model.prefill_chunk)
        self._fused = jax.jit(self._make_fused())
        self._first = jax.jit(self._make_first())
        self.transfers = 0           # packed reads; one per decode tick
        # live KV pages the paged decode kernel walks, summed over plain
        # decode ticks: the numerator of the share of the table walked,
        # against transfers * B * n_pages (host state only, no read)
        self.kv_pages_walked = 0

        # ---- speculative decoding (propose_k / verify_chunk contract).
        # A tick with drafts runs ONE fused verify step over a (B, W)
        # window (W = spec_k + 1: each slot's last emitted token plus its
        # draft) instead of a single-token decode, emitting the accepted
        # prefix plus the target's correction/bonus token. Families that
        # don't implement the contract (recurrent state can't roll back)
        # silently fall back to plain decode, as does any tick with no
        # drafts on offer. host mirror `_pos` tracks each slot's absolute
        # KV position for draft budgeting.
        spec_mode = getattr(engine, "speculative", "off") or "off"
        self.spec_k = min(int(getattr(engine, "spec_k", 4)), max(page - 1, 1))
        draft = getattr(engine, "drafter", None)
        ok = (spec_mode != "off" and self.spec_k > 0
              and hasattr(self.model, "verify_chunk"))
        if spec_mode == "model":
            ok = ok and draft is not None and hasattr(draft.model, "propose_k")
        self.spec_mode = spec_mode if ok else "off"
        self.spec = self.spec_mode != "off"
        self.spec_stats = SpecStats()
        # test/benchmark injection point: draft_hook(slot, req) -> list of
        # proposed token ids (forces exact acceptance patterns)
        self.draft_hook: Optional[Callable[[int, Request], list]] = None
        self._pos = np.zeros(self.B, np.int64)
        if self.spec:
            self._draft_len = np.zeros(self.B, np.int32)
            self._draft_host = np.zeros((self.B, self.spec_k), np.int32)
            self._verify = jax.jit(self._make_verify())
            self._ngram = (NgramDrafter(self.spec_k)
                           if self.spec_mode == "ngram" else None)
            self._drafter = (ModelDrafter(draft, self.B, self.max_seq,
                                          page=page, k=self.spec_k)
                             if self.spec_mode == "model" else None)

    # ------------------------------------------------------------ jitted fns
    def _make_fused(self):
        """One tick: decode all slots, sample, mask EOS/length per slot.

        Inputs beyond params/tok/cache are the per-slot state vectors:
        active, gen (tokens produced, incl. the prefill token), max_gen,
        and the per-slot sampling params temp/top_p/seed (each request in
        the shared batch samples with its own GenerationParams; ``gen``
        doubles as the per-request sample-stream step for seeded slots).
        Returns the next tok buffer, the cache, and a packed (B, 3)
        int32 [next, emitted, done] — the tick's single token transfer.
        (An admission's prefill token is emitted at admission time; see
        _advance_admissions.)
        """
        model, sampler = self.model, self.engine.sampler
        eos, pad = self.tokenizer.eos_id, self.tokenizer.pad_id

        def fused(params, tok, cache, active, gen, max_gen, temp, top_p,
                  seed, rng):
            run = active
            logits, cache = model.decode_step(params, tok, cache)
            nxt = sample_slots(logits, rng, sampler, temp, top_p, seed, gen)
            nxt = jnp.where(run, nxt, pad).astype(jnp.int32)
            gen2 = gen + run.astype(gen.dtype)
            done_now = run & ((nxt == eos) | (gen2 >= max_gen))
            alive = run & ~done_now
            # park finished/empty slots at pos 0 so their (masked, unread)
            # cache writes can never run off the end of the seq axis
            cache["pos"] = jnp.where(alive, cache["pos"], 0)
            if "pos_offset" in cache:
                cache["pos_offset"] = jnp.where(alive, cache["pos_offset"], 0)
            packed = jnp.stack(
                [nxt, run.astype(jnp.int32), done_now.astype(jnp.int32)],
                axis=1)
            return nxt[:, None], cache, packed

        return fused

    def _make_first(self):
        """Sample an admission's first token from its prefill logits and
        drop it into the tok buffer — device-side, no host read. Uses the
        admission's own params (step 0 of its sample stream)."""
        sampler = self.engine.sampler

        def first(tok, logits, slot, rng, temp, top_p, seed):
            t = sample_slots(logits, rng, sampler,
                             jnp.full((1,), temp, jnp.float32),
                             jnp.full((1,), top_p, jnp.float32),
                             jnp.full((1,), seed, jnp.int32),
                             jnp.zeros((1,), jnp.int32)).astype(tok.dtype)
            return jax.lax.dynamic_update_slice(tok, t[:, None], (slot, 0))

        return first

    def _make_verify(self):
        """One speculative tick: score the whole (B, W) window in one
        fused ``verify_chunk``, replay the target's sample stream over
        it (``speculative_accept``), and emit the accepted prefix plus
        the correction/bonus draw — n_acc + 1 tokens per slot, clamped
        by the slot's budget and truncated at the first EOS, exactly as
        plain decode would have produced them one tick at a time.

        Rollback is position arithmetic, not memory management: the
        cache pointer advances by ``n_emit`` only, so rejected window
        positions stay beyond ``pos`` — masked out of every later
        attention by ``kv_len`` and rewritten in place before ``pos``
        reaches them (paged slots' out-of-span window writes already
        self-redirect to the pool's trash page). No page is freed, no
        block-table entry beyond truncation survives, and tree-owned
        pages are never touched.

        Returns the next tok buffer, the cache, and a packed
        (B, W + 3) int32 [g_0..g_{W-1}, n_emit, done, n_acc] — still one
        host transfer per tick.
        """
        model, sampler = self.model, self.engine.sampler
        eos, pad = self.tokenizer.eos_id, self.tokenizer.pad_id
        W = self.spec_k + 1

        def verify(params, tok, drafts, draft_len, cache, active, gen,
                   max_gen, temp, top_p, seed, rng):
            run = active
            win = jnp.concatenate([tok, drafts], axis=1)          # (B, W)
            logits, cache = model.verify_chunk(params, win, cache)
            g, n_acc = speculative_accept(logits, drafts, draft_len, rng,
                                          sampler, temp, top_p, seed, gen)
            # budget first (>= 1: a run slot always emits its correction
            # token), then truncate at the first EOS inside the emission
            n_emit = jnp.minimum(n_acc + 1, jnp.maximum(max_gen - gen, 1))
            idx = jnp.arange(W)[None, :]
            eos_hit = (g == eos) & (idx < n_emit[:, None])
            any_eos = eos_hit.any(axis=1)
            first_eos = jnp.where(any_eos, jnp.argmax(eos_hit, axis=1), W)
            n_emit = jnp.minimum(n_emit, first_eos + 1)
            n_emit = jnp.where(run, n_emit, 0).astype(gen.dtype)
            gen2 = gen + n_emit
            done_now = run & (any_eos | (gen2 >= max_gen))
            alive = run & ~done_now
            # the rollback: pos advances past accepted tokens only;
            # finished/parked slots park at 0 (same as the plain tick)
            cache["pos"] = jnp.where(alive, cache["pos"] + n_emit, 0)
            if "pos_offset" in cache:
                cache["pos_offset"] = jnp.where(alive, cache["pos_offset"], 0)
            last = jnp.take_along_axis(
                g, jnp.maximum(n_emit - 1, 0)[:, None], axis=1)
            tok2 = jnp.where(run[:, None], last, pad).astype(jnp.int32)
            out = jnp.where(run[:, None], g, pad)
            packed = jnp.concatenate(
                [out, n_emit[:, None],
                 done_now.astype(jnp.int32)[:, None], n_acc[:, None]],
                axis=1).astype(jnp.int32)
            return tok2, cache, packed

        return verify

    def _prepare_drafts(self) -> bool:
        """Fill the per-slot draft buffers for this tick. Returns False
        when the tick should fall back to plain decode: nothing drafted
        anywhere, or (contiguous mode only) an active slot so close to
        the seq-axis end that a W-token window write would clip. Paged
        slots need no such gate — window positions beyond a slot's
        mapped pages scatter to the pool's trash page."""
        W = self.spec_k + 1
        self._draft_len[:] = 0
        any_draft = False
        for slot, req in enumerate(self.active):
            if req is None:
                continue
            if not self.paged and self._pos[slot] + W > self.max_seq:
                return False
            cap = min(self.spec_k, int(self._maxgen[slot]) -
                      int(self._gen[slot]) - 1)
            if self.window is not None:
                # clamp the verify window at the roll-trigger boundary:
                # a window that wrote past it would compute post-boundary
                # tokens with pre-roll context, diverging from the plain
                # path (which rolls first). With the clamp, rolls land at
                # the same positions as plain decode and speculative
                # emissions stay token-identical under rolling.
                bnd = (self.window.cap_pages - 1) * self.page
                spos = int(self._pos[slot]) - int(self._poff[slot])
                cap = min(cap, bnd - spos)
            if cap <= 0:
                continue
            if self.draft_hook is not None:
                d = list(self.draft_hook(slot, req))[:cap]
            elif self.spec_mode == "model":
                if self.window is not None and \
                        self._pos[slot] + W > self.max_seq:
                    # the drafter's contiguous (B, max_seq) cache can't
                    # hold a rolled session past max_seq — plain decode
                    # for this slot (its emissions are target-exact
                    # either way)
                    continue
                # device-side proposal for the whole batch (below);
                # only the per-slot clamp is decided here
                self._draft_len[slot] = cap
                any_draft = True
                continue
            else:
                history = (req._kv_ids or []) + req.output_ids
                d = self._ngram.propose(history)[:cap]
            if d:
                self._draft_host[slot, :len(d)] = d
                self._draft_len[slot] = len(d)
                any_draft = True
        return any_draft

    # ------------------------------------------------------------ rolling window
    def _roped_leaf_axes(self) -> list:
        """(cache key, pool axis) for every pooled leaf holding rope-
        rotated keys — the leaves a roll must re-rotate. GQA caches
        roped k; MLA ropes only the decoupled k_rope part (the latent
        c_kv is position-free). V is never rotated."""
        cfg = self.cfg
        out = []
        for key in self._pool_keys:
            if key == "k_rope" or key.endswith("_krope"):
                out.append((key, self._layout[key].batch_axis))
            elif cfg.use_rope and (key == "k" or key.endswith("_k")):
                out.append((key, self._layout[key].batch_axis))
        return out

    def _shift_pages(self, pids: list, delta: int):
        """Re-rotate the retained window's cached keys by ``-delta``
        positions, in place in the pool buffers. Exact, not approximate:
        rope rotations compose, so a key roped at position p rotated by
        -delta is bitwise the key a fresh prefill would rope at
        p - delta. One jitted dispatch per roll, touching only the
        retained pages (trailing unwritten pages ride along — their
        garbage is masked by kv_len until overwritten).

        Quantized pools dequantize the retained pages with their scale
        sidecar, rotate in float32, and requantize — one extra rounding
        per roll, bounded by the per-roll requant test."""
        if not pids or not self._rope_leaves:
            return
        qkeys = [f"{k}_qscale" if f"{k}_qscale" in self.cache else None
                 for k, _ in self._rope_leaves]
        fn = self._shift_fns.get(len(pids))
        if fn is None:
            theta = self.cfg.rope_theta
            axes = [ba for _, ba in self._rope_leaves]

            def shift(bufs, sbufs, pids, delta):
                out, sout = [], []
                for buf, sbuf, ba in zip(bufs, sbufs, axes):
                    pool = jnp.moveaxis(buf, ba, 0)
                    if sbuf is None:
                        rot = rope_shift(pool[pids], -delta, theta)
                        pool = pool.at[pids].set(rot.astype(buf.dtype))
                        sout.append(None)
                    else:
                        # scale sidecar shape = pool shape minus the
                        # trailing feature axis, so ba indexes the same
                        # page axis in both buffers
                        spool = jnp.moveaxis(sbuf, ba, 0)
                        vals = dequantize_kv(pool[pids], spool[pids])
                        rot = rope_shift(vals, -delta, theta)
                        qv, sc = quantize_kv(rot, buf.dtype)
                        pool = pool.at[pids].set(qv)
                        spool = spool.at[pids].set(sc)
                        sout.append(jnp.moveaxis(spool, 0, ba))
                    out.append(jnp.moveaxis(pool, 0, ba))
                return out, sout

            # donate: a roll must rotate its pages in place, not copy
            # the whole pool (the same argument as store_pages)
            fn = self._shift_fns[len(pids)] = jax.jit(shift,
                                                      donate_argnums=(0, 1))
        bufs = [self.cache[k] for k, _ in self._rope_leaves]
        sbufs = [self.cache[qk] if qk is not None else None for qk in qkeys]
        new, snew = fn(bufs, sbufs, jnp.asarray(pids, jnp.int32),
                       jnp.asarray(delta, jnp.int32))
        for (k, _), buf in zip(self._rope_leaves, new):
            self.cache[k] = buf
        for qk, sbuf in zip(qkeys, snew):
            if qk is not None:
                self.cache[qk] = sbuf

    def _roll_once(self, req: Request, poff: int) -> int:
        """One roll of ``req``'s mapping: evict the oldest non-sink
        pages, hand their token span to the summarizer, re-rotate the
        retained window, and append replacement pages at the tail.
        Returns the new pos_offset; the caller updates the device /
        host position state for wherever the mapping lives (decode slot
        or in-flight admission)."""
        w = self.window
        s, r = w.sink_pages, w.roll_pages
        delta = r * self.page
        evicted = req._pages[s:s + r]
        ev_own = req._own[s:s + r]
        retained = req._pages[s + r:]
        # a roll may only touch session-private pages past the sinks:
        # prefix matching and publishing are sink-capped for policy
        # sessions, so tree pages never sit in the rolling window (the
        # pool's free_guard would catch a violation anyway)
        assert all(req._own[s:]), \
            "tree-owned page inside the rolling window"
        # span ids BEFORE mutating state: slot-space [s, s+r) pages map
        # absolute tokens [s*page + poff, (s+r)*page + poff)
        full = (req._kv_ids or []) + req.output_ids
        lo = s * self.page + poff
        span = full[lo:lo + delta]
        # free-then-realloc: LIFO hands the same pids straight back as
        # the window's new tail, so pool occupancy is flat across a
        # roll and the re-allocation can never fail
        for pid, own in zip(evicted, ev_own):
            if own:
                self.pool.free(pid)
        fresh = self.prefix._alloc_many(len(evicted))
        assert len(fresh) == len(evicted), "roll re-allocation failed"
        req._pages = req._pages[:s] + retained + fresh
        req._own = req._own[:s] + req._own[s + r:] + [True] * len(fresh)
        self._shift_pages(retained, delta)
        if self.span_sink is not None and span:
            self.span_sink.submit(req.rid, span)
        req._rolls += 1
        self.rolls += 1
        return poff + delta

    def _maybe_roll_slots(self):
        """Roll any active slot whose next tick could write past its
        mapped cap. Runs before drafts are prepared, so a verify window
        (W <= page) can never straddle a roll boundary — the spare page
        in cap_pages absorbs the worst-case window between checks."""
        w = self.window
        if w is None:
            return
        cap_tok = w.cap_pages * self.page
        for slot, req in enumerate(self.active):
            if req is None:
                continue
            poff = int(self._poff[slot])
            rolled = False
            while int(self._pos[slot]) - poff + self.page > cap_tok:
                poff = self._roll_once(req, poff)
                rolled = True
            if rolled:
                self._poff[slot] = poff
                self.cache["pos_offset"] = \
                    self.cache["pos_offset"].at[slot].set(poff)
                self._bt[slot, :] = 0
                self._bt[slot, :len(req._pages)] = req._pages
                self._bt_dirty = True
                if self.spec:
                    # draft state from before the roll referenced the
                    # old window layout; drafts re-propose post-roll
                    self._draft_len[slot] = 0

    # ------------------------------------------------------------ admission
    def submit(self, req: Request):
        self.queue.append(req)

    def cancel(self, req: Request) -> bool:
        """Cancel one request wherever it currently lives: waiting in the
        queue, mid-chunked-prefill (the pages its prefill already
        published stay in the tree; its pins are released), or active in
        a decode slot (the slot is freed and re-admits the next queued
        request on the next tick). Fires ``on_done`` with
        ``cancelled=True``. Returns False if the request already
        finished. NOT thread-safe against a concurrent ``step()`` —
        callers serialize (see repro.serving.broker)."""
        if req.done:
            return False
        if req in self.queue:
            self.queue.remove(req)
        elif self._adm is not None and self._adm.req is req:
            adm = self._adm
            if self.paged and adm.lease is not None:
                # ORDER MATTERS: transfer the completed pages to the tree
                # FIRST, then free what the session still owns. The
                # transfer flips their _own flags, so the sweep below
                # cannot reclaim a page the tree now references — and
                # pool.free() asserts exactly that invariant. Rolling
                # sessions publish their sinks only (window pages are
                # position-shifted, not what a cold prefill computes).
                kv_pub = (adm.pos if self.window is None else
                          min(adm.pos, self.window.sink_pages * self.page))
                self.prefix.publish_paged(adm.lease, adm.ids, kv_pub,
                                          req._pages, req._own)
            elif adm.lease is not None and not self.pool.stateful:
                # stateless models defer publishing to admission end —
                # a cancelled prefill still publishes the pages it
                # completed before dying (tree, not trash)
                self.prefix.publish(adm.lease, adm.ids, adm.cache, 0,
                                    kv_n=adm.pos, state_at=-1)
            self._release_lease(req)
            if self.paged:
                for pid, own in zip(req._pages, req._own):
                    if own:
                        self.pool.free(pid)
                req._pages, req._own = [], []
            self._adm = None
        else:
            for slot, r in enumerate(self.active):
                if r is req:
                    self._finish(slot, cancelled=True)
                    return True
            return False
        req.done, req.cancelled = True, True
        req.finish_reason = "cancelled"
        if req.on_done:
            req.on_done(req)
        return True

    def _release_lease(self, req: Request):
        if req._lease is not None and self.prefix is not None:
            self.prefix.release(req._lease)
            req._lease = None

    def _advance_admissions(self):
        """Start or advance the in-flight admission by one tick's worth
        of prefill chunks (``prefill_chunk`` tokens of pages; ALL of them
        when the batch is idle — pacing only exists to protect in-flight
        decodes). Called at tick start and again after reaping, so a
        slot freed by cancellation is re-admitted on the same tick. A
        call that pops or advances an admission is one
        ``stream.batcher.admit`` span, tagged with the head request."""
        if self._adm is not None:
            head, slot = self._adm.req, None
        else:
            if not self.queue:
                return
            slot = next((s for s in range(self.B) if self.active[s] is None), None)
            if slot is None:
                return
            head = self.queue[0]
        with jax.profiler.TraceAnnotation("stream.batcher.admit", rid=head.rid):
            self._admit(slot)

    def _admit(self, slot):
        if self._adm is None:
            # expire deadlined requests at the pop — don't burn a full
            # prefill + splice (and emit a stale token) for a session
            # whose client already timed out waiting in the queue
            now = time.perf_counter()
            req = None
            while self.queue:
                cand = self.queue.pop(0)
                if cand.deadline_s and (now - cand.submitted_at) > cand.deadline_s:
                    cand.done, cand.cancelled = True, True
                    cand.finish_reason = "cancelled"
                    if cand.on_done:
                        cand.on_done(cand)
                    continue
                req = cand
                break
            if req is None:
                return
            req.admitted_at = now
            w = self.window
            if w is None:
                ids, req.max_new_tokens = clip_prompt(
                    req.prompt_ids, req.max_new_tokens, self.max_seq)
            else:
                # rolling-window sessions are unbounded: the prompt
                # rolls through the window during prefill and decode
                # rolls forever after, so the seq-axis capacity rule
                # does not apply
                ids = list(req.prompt_ids)
                req.max_new_tokens = max(int(req.max_new_tokens), 1)
            req._kv_ids = ids
            lease = None
            n_cached = 0
            if self.paged:
                # zero-copy admission: the prompt's cached prefix is
                # served by POINTING the slot's block table at the tree's
                # pages (no gather, no splice); the uncached suffix plus
                # the decode budget get private pages allocated UPFRONT,
                # so nothing inside the serving loop can run out of
                # memory mid-stream. The max written position is
                # len(ids) + max_new - 2 (the last sampled token is
                # never fed back), hence the page count below.
                # rolling sessions cap prefix matching (and, later,
                # publishing) to the sink region: everything past the
                # sinks gets evicted and re-rotated by rolls, which must
                # never touch a page the tree shares with other sessions
                match_ids = (ids if w is None
                             else ids[:w.sink_pages * self.page + 1])
                lease = self.prefix.begin(req.cache_salt, match_ids)
                need = -(-(len(ids) + req.max_new_tokens - 1) // self.page)
                if w is not None:
                    need = min(need, w.cap_pages)
                private = need - len(lease.chain)
                pids = self.prefix._alloc_many(private)
                if len(pids) < private:
                    # pool exhausted even after eviction (live slots pin
                    # their pages): put everything back and retry once a
                    # slot finishes — never admit a slot that could die
                    # of allocation failure mid-decode
                    for pid in pids:
                        self.pool.free(pid)
                    self.prefix.release(lease)
                    req._lease = None
                    self.queue.insert(0, req)
                    self._stall = True
                    return
                req._pages = [nd.page for nd in lease.chain] + pids
                req._own = [False] * len(lease.chain) + [True] * len(pids)
                n_cached = lease.n_cached
                row = np.zeros((1, self.n_pages), np.int32)
                row[0, :len(req._pages)] = req._pages
                one = {k: self.cache[k] for k in self._pool_keys}
                one["pos"] = jnp.asarray(n_cached, jnp.int32)
                one["pos_offset"] = jnp.zeros((), jnp.int32)
                one["block_tables"] = jnp.asarray(row)
            else:
                one = self.model.init_cache(1, self.max_seq)
                if self.prefix is not None:
                    # longest cached page-aligned prefix under this
                    # tenant's salt: splice its pool pages in and prefill
                    # only the suffix. The lease pins every matched page
                    # until the session finishes — eviction can never
                    # free a page a live slot still maps.
                    lease = self.prefix.begin(req.cache_salt, ids)
                    if lease.n_cached:
                        one = self.prefix.load_into(lease, one, 0)
                        n_cached = lease.n_cached
            req._lease = lease
            req.prefix_hit_tokens = n_cached
            p, sc = req.params, self.engine.sampler
            self._adm = _Admission(
                req=req, slot=slot, cache=one, ids=ids,
                pieces=chunk_plan(n_cached, len(ids), self.page),
                pos=n_cached, lease=lease,
                temp=(p.temperature if p and p.temperature is not None
                      else sc.temperature),
                top_p=p.top_p if p and p.top_p is not None else sc.top_p,
                # mask to int32: the gateway 400s oversized seeds, but a
                # programmatic submit() must not be able to fault the
                # SHARED batch (an OverflowError in the jitted step would
                # cancel every in-flight session)
                seed=(p.seed & 0x7FFFFFFF) if p and p.seed is not None else -1)
        adm = self._adm
        idle = not any(r is not None for r in self.active)
        budget = len(adm.ids) if idle else self.prefill_chunk
        logits = None
        while adm.pieces and budget > 0:
            n = adm.pieces.pop(0)
            if self.window is not None:
                # prompts longer than the window roll DURING prefill:
                # same mechanics as a decode-time roll, applied to the
                # admission's private block-table row before the chunk
                # whose write would overflow the mapped cap
                w, rolled = self.window, False
                while adm.pos - adm.poff + n > w.cap_pages * self.page:
                    adm.poff = self._roll_once(adm.req, adm.poff)
                    rolled = True
                if rolled:
                    row = np.zeros((1, self.n_pages), np.int32)
                    row[0, :len(adm.req._pages)] = adm.req._pages
                    adm.cache["block_tables"] = jnp.asarray(row)
                    adm.cache["pos_offset"] = jnp.asarray(adm.poff, jnp.int32)
            chunk = jnp.asarray([adm.ids[adm.pos:adm.pos + n]], jnp.int32)
            if self.paged:
                # the admission writes into the SAME pool buffers the
                # fused tick decodes from; interleaved ticks replace
                # them, so resync before and after every chunk. The
                # admission's block-table row is its own — it is not
                # installed into the decode tables until activation, so
                # parked slots' trash-page writes can never land on a
                # page this prefill (or the prefix tree) owns.
                for kk in self._pool_keys:
                    adm.cache[kk] = self.cache[kk]
            logits, adm.cache = self._prefill(self.engine.params, chunk,
                                              adm.cache)
            if self.paged:
                for kk in self._pool_keys:
                    self.cache[kk] = adm.cache[kk]
            adm.pos += n
            budget -= n
            if adm.lease is not None and self.pool.stateful:
                # recurrent models publish per completed page DURING
                # prefill: the state snapshot a node needs exists only
                # while the cache sits exactly at that page's boundary.
                # Attention-only models defer publishing until after the
                # first-token emission — off the TTFT path (below).
                self.prefix.publish(adm.lease, adm.ids, adm.cache, 0,
                                    kv_n=adm.pos, state_at=adm.pos)
        if adm.pieces:
            return
        # prefill complete. Sample + emit the prefill token FIRST — one
        # scalar read per ADMISSION (not per slot per tick) — and only
        # then pay for the paged splice: the first decode tick needs the
        # spliced cache, the first emission does not, so TTFT excludes
        # both the splice and a full fused tick.
        slot, req = adm.slot, adm.req
        slot_arr = jnp.asarray(slot, jnp.int32)
        self.engine.rng, k = jax.random.split(self.engine.rng)
        self.tok = self._first(self.tok, logits, slot_arr, k,
                               adm.temp, adm.top_p, adm.seed)
        self._adm = None
        first = int(self.tok[slot, 0])
        req.output_ids.append(first)
        stopped = req.emit(first, self.tokenizer.decode_token(first))
        # the emission just woke the session's consumer thread (gateway
        # SSE queue, relay producer); offer the GIL before paying the
        # splice below, or the consumer's TTFT silently re-absorbs the
        # splice + first fused tick this emission was moved ahead of
        time.sleep(0)
        self.admissions += 1
        if self.paged and adm.lease is not None:
            # paged publish is pure ownership transfer — the prompt's
            # full pages BECOME tree nodes (zero bytes moved); a dedupe
            # hit frees our duplicate and repoints the mapping at the
            # tree's bitwise-identical page (folded into req._pages).
            # Rolling sessions publish only their sink pages — the rest
            # of the mapping is about to roll and re-rotate in place,
            # which must never happen to a shared tree page.
            kv_pub = (adm.pos if self.window is None else
                      min(adm.pos, self.window.sink_pages * self.page))
            self.prefix.publish_paged(adm.lease, adm.ids, kv_pub,
                                      req._pages, req._own)
        elif adm.lease is not None and not self.pool.stateful:
            # attention-only models: publish the whole prompt's pages in
            # one batched device store, AFTER the first token left — the
            # publish never taxes TTFT (a same-prefix session can only
            # admit after this admission completes anyway)
            self.prefix.publish(adm.lease, adm.ids, adm.cache, 0,
                                kv_n=adm.pos, state_at=-1)
        if stopped or first == self.tokenizer.eos_id or req.max_new_tokens <= 1:
            req.done = True          # ended on its prefill token
            req.finish_reason = ("length" if (not stopped and
                                              first != self.tokenizer.eos_id)
                                 else "stop")
            self._release_lease(req)
            if self.paged:
                for pid, own in zip(req._pages, req._own):
                    if own:
                        self.pool.free(pid)
                req._pages, req._own = [], []
            req.flush_stop()
            if req.on_done:
                req.on_done(req)
            return
        if self.paged:
            # activation is two pointer writes: install the block-table
            # row into the decode tables and set the slot's position —
            # the contiguous path's per-admission splice copy is gone
            self._bt[slot, :] = 0
            self._bt[slot, :len(req._pages)] = req._pages
            self._bt_dirty = True
            self.cache["pos"] = self.cache["pos"].at[slot].set(len(adm.ids))
            self.cache["pos_offset"] = \
                self.cache["pos_offset"].at[slot].set(adm.poff)
            self._poff[slot] = adm.poff
        else:
            used = min(round_up(len(adm.ids), self.page), self.max_seq)
            self.cache = self._splicer(self.cache, adm.cache, slot, used)
        self.active[slot] = req
        self._active_m[slot] = True
        self._gen[slot] = 1          # the prefill token counts
        self._maxgen[slot] = req.max_new_tokens
        self._temp[slot] = adm.temp
        self._topp[slot] = adm.top_p
        self._seed[slot] = adm.seed
        self._pos[slot] = len(adm.ids)
        if self.spec:
            self._draft_len[slot] = 0
            if self._drafter is not None and (
                    self.window is None or len(adm.ids) <= self.max_seq):
                # the drafter ingests the prompt off the TTFT path (the
                # first token already left); its splice traffic is
                # accounted on the drafter, not the admission contract.
                # (A rolling session's prompt can exceed the drafter's
                # contiguous cache — such slots simply never draft.)
                self._drafter.admit(slot, adm.ids)

    # ------------------------------------------------------------ tick
    def _finish(self, slot: int, cancelled=False):
        req = self.active[slot]
        if req is None:
            return
        req.done, req.cancelled = True, cancelled
        if cancelled:
            req.finish_reason = "cancelled"
        elif not req.finish_reason:
            req.finish_reason = ("length" if self._gen[slot] >= self._maxgen[slot]
                                 else "stop")
        # publish the session's decoded extension back to the tree before
        # the slot can be re-spliced (cancelled sessions included): the
        # next turn of this conversation prefixes with exactly these
        # tokens. KV exists for the prompt plus every output token but
        # the last (the final sampled token was never fed back through
        # decode). Recurrent-state snapshots are not available mid-decode
        # (state_at=-1): those nodes become resumable once a later
        # prefill re-crosses them at an aligned boundary and upgrades
        # them in place.
        if self.paged:
            if req._lease is not None and req._kv_ids is not None \
                    and req._rolls == 0:
                kv_n = len(req._kv_ids) + max(len(req.output_ids) - 1, 0)
                # ownership transfer again: the decoded extension's pages
                # join the tree in place. MUST precede the owned-page
                # sweep below (pool.free asserts the ordering). A session
                # that rolled skips this: its non-sink pages hold
                # position-shifted KV, not the bitwise cold-prefill pages
                # the tree's token keys promise (sinks were published at
                # admission; the roll spans live in the summarizer).
                self.prefix.publish_paged(req._lease,
                                          req._kv_ids + req.output_ids,
                                          kv_n, req._pages, req._own)
            self._release_lease(req)
            for pid, own in zip(req._pages, req._own):
                if own:
                    self.pool.free(pid)
            req._pages, req._own = [], []
            self._bt[slot, :] = 0     # next mapping installs fresh
            self._bt_dirty = True
        else:
            if req._lease is not None and self.prefix is not None and \
                    req._kv_ids is not None:
                kv_n = len(req._kv_ids) + max(len(req.output_ids) - 1, 0)
                self.prefix.publish(req._lease, req._kv_ids + req.output_ids,
                                    self.cache, slot, kv_n=kv_n, state_at=-1)
            self._release_lease(req)
        req.flush_stop(deliver=not cancelled)
        if req.on_done:
            req.on_done(req)
        self.active[slot] = None
        self._active_m[slot] = False
        self._pos[slot] = 0
        self._poff[slot] = 0
        if self.spec:
            # release draft state (cancel mid-verify lands here too):
            # the slot re-admits with a clean window
            self._draft_len[slot] = 0
        self._freed = True

    def _in_flight(self) -> int:
        return (sum(r is not None for r in self.active)
                + (self._adm is not None))

    def pool_stats(self):
        """Point-in-time PoolStats for the shared page pool (None when
        prefix caching is disabled). Flat ``high_water`` across a long
        rolling session is the bounded-memory headline the longcontext
        benchmark gates on."""
        return self.pool.stats() if self.pool is not None else None

    def bytes_copied_per_admission(self) -> float:
        """Device bytes moved per admitted session by splice/store/load
        KV plumbing (attention math itself excluded). The headline
        number for the paged decode path: contiguous serving pays a
        whole-prompt splice (plus pool stores) per admission; paged
        serving re-points block tables, so this is ~0."""
        total = self._splicer.bytes_copied
        if self.pool is not None:
            total += self.pool.bytes_copied
        return total / max(self.admissions, 1)

    def _spec_tick(self, rng):
        """One speculative tick (drafts already prepared): propose —
        verify — emit. Mixed batches come for free: a slot with
        ``draft_len == 0`` rides the same window as a plain decode (its
        window is just its input token plus dead padding; it still emits
        exactly its one target draw)."""
        W = self.spec_k + 1
        if self.spec_mode == "model" and self.draft_hook is None:
            drafts = self._drafter.propose(self.tok, self.cache["pos"])
        else:
            drafts = jnp.asarray(self._draft_host)
        lens = self._draft_len.copy()
        with jax.profiler.TraceAnnotation("stream.batcher.decode"):
            self.tok, self.cache, packed = self._verify(
                self.engine.params, self.tok, drafts, jnp.asarray(lens),
                self.cache, self._active_m, self._gen, self._maxgen,
                self._temp, self._topp, self._seed, rng)
            packed = np.asarray(packed)  # still the tick's one token transfer
        self.transfers += 1
        self.spec_stats.spec_ticks += 1
        with jax.profiler.TraceAnnotation("stream.batcher.emit"):
            self._emit_window(packed, lens, W)

    def _emit_window(self, packed, lens, W: int):
        now = time.perf_counter()
        for slot, req in enumerate(self.active):
            if req is None:
                continue
            n_emit = int(packed[slot, W])
            done = int(packed[slot, W + 1])
            self.spec_stats.proposed += int(lens[slot])
            self.spec_stats.accepted += int(packed[slot, W + 2])
            self.spec_stats.emitted += n_emit
            self._pos[slot] += n_emit
            self._gen[slot] += n_emit
            stopped = False
            for j in range(n_emit):
                t = int(packed[slot, j])
                req.output_ids.append(t)
                if req.emit(t, self.tokenizer.decode_token(t)):
                    # stop completed mid-window: later window tokens are
                    # discarded — plain decode would never have produced
                    # them (output_ids records through the stop token,
                    # matching the plain path)
                    req.finish_reason = "stop"
                    self._finish(slot)
                    stopped = True
                    break
            if stopped:
                continue
            over = req.deadline_s and (now - req.submitted_at) > req.deadline_s
            if done or over:
                self._finish(slot, cancelled=bool(over))

    def step(self) -> int:
        """One scheduler tick: admit (one chunk), fused decode, emit, reap,
        re-admit. Returns the number of requests still in flight (active
        slots plus a mid-prefill admission), so callers may loop on it.
        The tick is one ``stream.batcher.tick`` span holding its
        ``admit``, ``decode`` and ``emit`` spans."""
        with jax.profiler.TraceAnnotation("stream.batcher.tick"):
            return self._tick()

    def _tick(self) -> int:
        self._freed = False
        self._stall = False
        idle = not any(r is not None for r in self.active)
        self._advance_admissions()
        if idle:
            # cold-start burst: with no in-flight decodes, one-chunk-per-
            # tick pacing protects nothing — run prefills to completion
            # until the free slots are filled (or the queue drains), so N
            # simultaneous arrivals don't serialize their admissions
            # across N*chunks ticks before the batch even starts.
            while (self._adm is not None
                   or (self.queue and not self._stall
                       and any(r is None for r in self.active))):
                self._advance_admissions()
        if not any(r is not None for r in self.active):
            return self._in_flight()
        self._maybe_roll_slots()
        if self.paged and self._bt_dirty:
            self.cache["block_tables"] = jnp.asarray(self._bt)
            self._bt_dirty = False
        self.engine.rng, k = jax.random.split(self.engine.rng)
        if self.spec and self._prepare_drafts():
            self._spec_tick(k)
            if self._freed and self._adm is None:
                self._advance_admissions()
            return self._in_flight()
        if self.spec:
            self.spec_stats.plain_ticks += 1
        if self.paged:
            live = (self._pos + 1 - self._poff)[self._active_m]
            self.kv_pages_walked += int((-(-live // self.page)).sum())
        with jax.profiler.TraceAnnotation("stream.batcher.decode"):
            self.tok, self.cache, packed = self._fused(
                self.engine.params, self.tok, self.cache,
                self._active_m, self._gen, self._maxgen,
                self._temp, self._topp, self._seed, k)
            packed = np.asarray(packed)  # the tick's one token transfer
        self.transfers += 1
        with jax.profiler.TraceAnnotation("stream.batcher.emit"):
            self._emit(packed)
        # same-tick reuse of reaped slots — but never advance an already
        # in-flight admission a second chunk (one chunk per tick)
        if self._freed and self._adm is None:
            self._advance_admissions()
        return self._in_flight()

    def _emit(self, packed):
        """Deliver one decode tick's tokens and reap finished slots."""
        now = time.perf_counter()
        for slot, req in enumerate(self.active):
            if req is None:
                continue
            nxt, emitted, done = (int(v) for v in packed[slot])
            if emitted:
                req.output_ids.append(nxt)
                self._gen[slot] += 1
                self._pos[slot] += 1
                if req.emit(nxt, self.tokenizer.decode_token(nxt)):
                    # a stop sequence completed: it (and anything after
                    # it) is recorded in output_ids but never delivered
                    req.finish_reason = "stop"
                    self._finish(slot)
                    continue
            over = req.deadline_s and (now - req.submitted_at) > req.deadline_s
            if done or over:
                self._finish(slot, cancelled=bool(over))

    def run_until_drained(self, max_steps: int = 10000):
        steps = 0
        while (self.queue or self._adm is not None
               or any(r is not None for r in self.active)) and steps < max_steps:
            self.step()
            steps += 1
        return steps
