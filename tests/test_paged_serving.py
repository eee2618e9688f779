"""Native paged decode: block-table serving edge cases and the
release-ordering contract between the pool and the prefix tree.

The batcher runs in paged mode for attention-only models (max_seq
page-aligned, pool >= one worst-case slot): slots decode straight out
of pool buffers through per-slot block tables, admission points at tree
pages instead of splicing, publish transfers page ownership, and the
per-admission device copy drops to zero. Everything here checks the
edges of that mapping — partial pages, full tables, shared-then-
divergent tables, pinned leaves under eviction pressure — plus the
satellite regression: cancel mid-publish must never leave the tree
holding a block-table reference to a reclaimed page.
"""

import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.serving import (ContinuousBatcher, PagePool, PrefixCache, Request,
                           ServingEngine)

PROMPT = "hello paged world, this is a longer shared prompt for caching!"


@pytest.fixture(scope="module")
def engine():
    cfg = get_smoke_config("minitron-8b").replace(vocab_size=300, vocab_pad_to=64)
    e = ServingEngine(cfg, max_seq=96)
    e.warmup()
    yield e
    e.shutdown()


def run_one(cb, engine, prompt, max_new=6, params=None):
    out = {}
    cb.submit(Request(rid="r", prompt_ids=engine.tokenizer.encode(prompt),
                      max_new_tokens=max_new, params=params,
                      on_done=lambda r: out.update(tokens=r.output_ids,
                                                   hit=r.prefix_hit_tokens,
                                                   reason=r.finish_reason)))
    cb.run_until_drained()
    return out


# ------------------------------------------------------------ mode gating
def test_paged_mode_active_for_attention_models(engine):
    cb = ContinuousBatcher(engine, slots=2, max_seq=96, prefix_pages=64)
    assert cb.paged
    assert "block_tables" in cb.cache and cb.n_pages == 6


def test_paged_mode_requires_aligned_max_seq(engine):
    # 90 % 16 != 0: the gathered view could not equal the contiguous
    # view, so the batcher must fall back to the splice path
    cb = ContinuousBatcher(engine, slots=2, max_seq=90, prefix_pages=64)
    assert not cb.paged
    assert run_one(cb, engine, PROMPT, max_new=4)["tokens"]


def test_stateful_families_stay_contiguous():
    cfg = get_smoke_config("zamba2-7b").replace(vocab_size=300, vocab_pad_to=64)
    e = ServingEngine(cfg, max_seq=96)
    cb = ContinuousBatcher(e, slots=2, max_seq=96, prefix_pages=64)
    assert not cb.paged              # SSM state has no page address
    e.shutdown()


def test_paged_kv_flag_pins_contiguous_path():
    cfg = get_smoke_config("minitron-8b").replace(vocab_size=300, vocab_pad_to=64)
    e = ServingEngine(cfg, max_seq=96, paged_kv=False)
    cb = ContinuousBatcher(e, slots=2, max_seq=96, prefix_pages=64)
    assert not cb.paged
    e.shutdown()


# ------------------------------------------------------- block-table edges
def test_single_partially_filled_page(engine):
    """Prompt + budget fit inside ONE page: the block table maps a single
    page, decode masks everything beyond kv_len."""
    solo = engine.generate("hi", max_new_tokens=4)
    cb = ContinuousBatcher(engine, slots=2, max_seq=96, prefix_pages=64)
    out = run_one(cb, engine, "hi", max_new=4)
    assert out["tokens"] == solo.tokens
    cold = cb.pool.bytes_copied + cb._splicer.bytes_copied
    assert cold == 0                 # no splice, no store: pure pointers


def test_slot_spans_entire_block_table(engine):
    """len(prompt) + max_new - 1 == max_seq: every page of the table is
    mapped and the last written position is the last slot of the last
    page. Must finish by length without tripping the trash-page or
    free-list guards."""
    ids = list(range(2, 2 + 64))     # 64 prompt tokens (4 full pages)
    cb = ContinuousBatcher(engine, slots=2, max_seq=96, prefix_pages=64)
    out = {}
    req = Request(rid="full", prompt_ids=ids, max_new_tokens=33,
                  on_done=lambda r: out.update(tokens=r.output_ids,
                                               reason=r.finish_reason))
    cb.submit(req)
    cb.step()
    assert len(req._pages) == cb.n_pages       # table fully mapped
    cb.run_until_drained()
    assert out["reason"] in ("length", "stop")
    if out["reason"] == "length":
        assert len(out["tokens"]) == 33


def test_shared_prefix_diverging_last_page(engine):
    """Two concurrent slots whose block tables share every prefix page
    and diverge only in the final page: ref-counted pages are mapped by
    both tables at once, yet each slot decodes exactly its solo tokens
    (shared pages are read-only by construction — each slot's writes go
    to its own private tail page)."""
    base = PROMPT + " shared tail padding so the prefix covers pages"
    a_prompt, b_prompt = base + " AAAA", base + " BBBB"
    solo_a = engine.generate(a_prompt, max_new_tokens=5).tokens
    solo_b = engine.generate(b_prompt, max_new_tokens=5).tokens
    cb = ContinuousBatcher(engine, slots=2, max_seq=96, prefix_pages=64)
    run_one(cb, engine, base, max_new=2)       # seed the shared pages
    out = {}
    for rid, prompt in (("a", a_prompt), ("b", b_prompt)):
        cb.submit(Request(rid=rid, prompt_ids=engine.tokenizer.encode(prompt),
                          max_new_tokens=5,
                          on_done=lambda r, rid=rid: out.update(
                              {rid: (r.output_ids, r.prefix_hit_tokens)})))
    # step until both are active, then check their tables overlap
    for _ in range(200):
        cb.step()
        if all(r is not None for r in cb.active):
            break
    if all(r is not None for r in cb.active):
        t0, t1 = cb._bt[0], cb._bt[1]
        shared = set(t0[t0 != 0]) & set(t1[t1 != 0])
        assert shared                # prefix pages mapped by BOTH tables
        assert not np.array_equal(t0, t1)      # ...diverging at the tail
    cb.run_until_drained()
    assert out["a"][0] == solo_a and out["b"][0] == solo_b
    assert out["a"][1] > 0 and out["b"][1] > 0


def test_eviction_refused_while_block_table_pins_leaf(engine):
    """A live slot's block table maps tree pages through its lease pins:
    allocation pressure from other admissions must evict around the
    pinned chain (or stall the admission) — a mapped page id must never
    reach the free list while the slot decodes from it."""
    cb = ContinuousBatcher(engine, slots=2, max_seq=96, prefix_pages=6)
    assert cb.paged
    run_one(cb, engine, PROMPT, max_new=2)     # seed the tree
    live = Request(rid="live", prompt_ids=engine.tokenizer.encode(PROMPT),
                   max_new_tokens=10)
    cb.submit(live)
    cb.step()
    assert live._lease is not None and live._lease.chain
    mapped = set(cb._bt[0][cb._bt[0] != 0]) | set(live._pages)
    for i in range(4):
        cb.submit(Request(
            rid=f"churn{i}",
            prompt_ids=engine.tokenizer.encode(
                f"unrelated churn prompt number {i} padding text"),
            max_new_tokens=2))
    while not live.done:
        cb.step()
        if not live.done:
            assert not (mapped & set(cb.pool._free))
    cb.run_until_drained()


# ------------------------------------------------ release-ordering guard
def test_cancel_during_publish_keeps_tree_pages(engine):
    """THE satellite regression: cancel mid-chunked-prefill transfers
    the completed pages to the tree FIRST, then frees only what the
    session still owns. Afterwards no tree-referenced page may sit on
    the free list, and a warm admission must decode from the surviving
    pages without faulting."""
    cb = ContinuousBatcher(engine, slots=2, max_seq=96, prefill_chunk=16,
                           prefix_pages=64)
    bg = Request(rid="bg", prompt_ids=engine.tokenizer.encode("background"),
                 max_new_tokens=40)
    cb.submit(bg)
    cb.step()                        # keep a decode live: pacing applies
    victim = Request(rid="victim", prompt_ids=engine.tokenizer.encode(PROMPT),
                     max_new_tokens=8)
    cb.submit(victim)
    cb.step()                        # one chunk -> mid-admission
    assert cb._adm is not None and cb._adm.req is victim
    done_pages = cb._adm.pos // cb.page
    assert done_pages >= 1
    assert cb.cancel(victim)
    # ownership transferred, private tail freed, nothing double-owned
    assert victim._pages == [] and victim._own == []
    tree_pids = set(cb.prefix._pids)
    assert not (tree_pids & set(cb.pool._free))
    assert cb.prefix.stats.published_pages >= done_pages
    cb.run_until_drained()
    warm = run_one(cb, engine, PROMPT, max_new=4)
    assert warm["hit"] >= done_pages * cb.page


def test_pool_free_asserts_release_ordering(engine):
    """pool.free() on a page the tree still references must trip the
    guard — the bug class this orders out is a cancelled session
    reclaiming a page it already published, leaving the tree pointing
    at memory the next admission overwrites."""
    pool = PagePool(engine.model, page=16, capacity=4)
    pc = PrefixCache(pool)
    cache = engine.model.init_cache(1, 96)
    ids = list(range(2, 2 + 32))
    lease = pc.begin("s", ids + [9])
    pc.publish(lease, ids, cache, 0, kv_n=32, state_at=-1)
    owned_pid = lease.chain[0].page
    with pytest.raises(AssertionError):
        pool.free(owned_pid)         # tree still references it
    # legal order: evict (tree drops the reference) -> the free inside
    # eviction succeeds; freeing it AGAIN is a double free
    pc.release(lease)
    assert pc.evict_one() and pc.evict_one()
    freed = lease.chain[1].page
    with pytest.raises(AssertionError):
        pool.free(freed)
    with pytest.raises(AssertionError):
        pool.free(0)                 # the trash page is never freeable


# ------------------------------------------------- paged vs contiguous
@pytest.mark.parametrize("arch", ["minitron-8b", "deepseek-v2-lite-16b"])
def test_paged_token_identical_to_contiguous(arch):
    """THE acceptance criterion: for every attention-bearing family
    (dense GQA and MLA), paged decode produces bit-for-bit the tokens
    of the contiguous splice path — greedy AND seeded."""
    from repro.serving import GenerationParams

    cfg = get_smoke_config(arch).replace(vocab_size=300, vocab_pad_to=64)
    e = ServingEngine(cfg, max_seq=96)
    seeded = GenerationParams(max_tokens=6, temperature=0.9, seed=77)
    try:
        outs = {}
        for mode, paged in (("paged", True), ("contiguous", False)):
            e.paged_kv = paged
            cb = ContinuousBatcher(e, slots=2, max_seq=96, prefix_pages=64)
            assert cb.paged is paged, (arch, mode)
            outs[mode] = {
                "greedy": run_one(cb, e, PROMPT, max_new=6)["tokens"],
                "seeded": run_one(cb, e, PROMPT + " x", max_new=6,
                                  params=seeded)["tokens"],
            }
        assert outs["paged"]["greedy"] == outs["contiguous"]["greedy"], arch
        assert outs["paged"]["seeded"] == outs["contiguous"]["seeded"], arch
    finally:
        e.shutdown()


# ------------------------------------------------------- zero-copy metric
def test_bytes_copied_per_admission_is_zero_paged(engine):
    cb = ContinuousBatcher(engine, slots=2, max_seq=96, prefix_pages=64)
    for prompt in (PROMPT, PROMPT, PROMPT + " more"):
        run_one(cb, engine, prompt, max_new=4)
    assert cb.admissions == 3
    assert cb.bytes_copied_per_admission() == 0.0


def test_kv_pages_walked_counts_live_pages_per_tick(engine):
    """Each plain decode tick adds the pages the kernel walks: for every
    active slot, ceil(live length / page), where the live length is the
    prompt plus the tokens emitted so far (the tick writes and attends
    the newest one). Two slots of different lengths cross a page
    boundary while the counter is watched."""
    cb = ContinuousBatcher(engine, slots=2, max_seq=96, prefix_pages=64)
    for rid, prompt in (("a", "x" * 14), ("b", PROMPT)):
        cb.submit(Request(rid=rid, prompt_ids=engine.tokenizer.encode(prompt),
                          max_new_tokens=12))
    while cb.queue or cb._adm is not None:
        cb.step()
    ticks = 0
    while all(r is not None for r in cb.active):
        want = sum(-(-(len(r.prompt_ids) + len(r.output_ids)) // cb.page)
                   for r in cb.active)
        before, t = cb.kv_pages_walked, cb.transfers
        cb.step()
        assert cb.transfers == t + 1
        assert cb.kv_pages_walked - before == want
        ticks += 1
    assert ticks >= 4
    assert 0 < cb.kv_pages_walked < cb.transfers * cb.B * cb.n_pages


def test_bytes_copied_per_admission_positive_contiguous():
    cfg = get_smoke_config("minitron-8b").replace(vocab_size=300, vocab_pad_to=64)
    e = ServingEngine(cfg, max_seq=96, paged_kv=False)
    cb = ContinuousBatcher(e, slots=2, max_seq=96, prefix_pages=64)
    run_one(cb, e, PROMPT, max_new=4)
    assert cb.bytes_copied_per_admission() > 0
    e.shutdown()


# ------------------------------------------------- quantized KV pages
def _quant_batcher(engine, kv_dtype, **kw):
    """A batcher with a quantized page pool over a module engine: the
    scheduler reads engine.kv_dtype once, at pool construction."""
    prev, engine.kv_dtype = engine.kv_dtype, kv_dtype
    try:
        cb = ContinuousBatcher(engine, slots=2, max_seq=96, **kw)
    finally:
        engine.kv_dtype = prev
    return cb


def test_int8_pages_token_identical_to_fp32():
    """Acceptance: int8 pages with in-kernel dequant decode the exact
    greedy tokens of the fp32 pool — at <= 0.55x the pool bytes, sidecar
    included — and admissions stay pure pointer ops (bytes copied
    exactly 0, quantized or not).

    Pinned on the GQA family at f32 compute, where int8's ~0.4%
    relative error sits below greedy argmax gaps. MLA quantizes the
    compressed latent (error amplifies through the up-projection into
    near-tie flips), so that family is held to the bounded-logit-error
    contract below instead."""
    cfg = get_smoke_config("minitron-8b").replace(
        vocab_size=300, vocab_pad_to=64, compute_dtype="float32")
    e = ServingEngine(cfg, max_seq=96)
    try:
        outs, pool_bytes = {}, {}
        for dt in ("fp32", "int8"):
            cb = _quant_batcher(e, dt, prefix_pages=64)
            assert cb.paged
            outs[dt] = run_one(cb, e, PROMPT, max_new=8)["tokens"]
            pool_bytes[dt] = cb.pool.pool_bytes
            assert cb.bytes_copied_per_admission() == 0.0, dt
        assert outs["int8"] == outs["fp32"]
        assert pool_bytes["int8"] < pool_bytes["fp32"] * 0.55
    finally:
        e.shutdown()


@pytest.mark.parametrize("arch", ["minitron-8b", "deepseek-v2-lite-16b"])
@pytest.mark.parametrize("kv_dtype", ["int8", "fp8_e4m3"])
def test_quantized_logit_error_bounded(arch, kv_dtype):
    """Per-family error contract: teacher-forced chunked prefill through
    a quantized pool keeps every logit within 0.25 of the fp32-pool
    logits (measured 0.004-0.06 across families/dtypes; ~5x headroom)."""
    import jax
    import jax.numpy as jnp

    from repro.models import build_model
    from repro.serving import PagePool

    cfg = get_smoke_config(arch).replace(vocab_size=300, vocab_pad_to=64,
                                         compute_dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    ids = [104, 101, 108, 108, 111, 32] * 10 + list(range(2, 12))  # 70 toks

    def paged_logits(dt):
        pool = PagePool(model, page=16, capacity=64, kv_dtype=dt)
        cache = pool.paged_cache(1, 6)
        pids = [pool.alloc() for _ in range(6)]
        cache["block_tables"] = jnp.asarray([pids], jnp.int32)
        out, pos = [], 0
        while pos < len(ids):
            chunk = ids[pos:pos + 16]
            cache["pos"] = jnp.asarray([pos], jnp.int32)
            logits, cache = model.prefill_chunk(
                params, jnp.asarray([chunk], jnp.int32), cache)
            pos += len(chunk)
            out.append(np.asarray(logits[0]).reshape(-1))
        return np.stack(out)

    err = np.abs(paged_logits(kv_dtype) - paged_logits("fp32")).max()
    assert err < 0.25, (arch, kv_dtype, err)


@pytest.mark.parametrize("arch", ["minitron-8b", "deepseek-v2-lite-16b"])
def test_fp8_pages_generate_with_shrunk_pool(arch):
    """fp8_e4m3 trades the int8 token-identity guarantee for wider
    dynamic range (greedy tokens may diverge on some families); it must
    still decode to completion deterministically at the same <=0.55
    pool-bytes ratio."""
    cfg = get_smoke_config(arch).replace(vocab_size=300, vocab_pad_to=64,
                                         compute_dtype="float32")
    e = ServingEngine(cfg, max_seq=96)
    try:
        runs = []
        for _ in range(2):
            cb = _quant_batcher(e, "fp8_e4m3", prefix_pages=64)
            assert cb.paged
            out = run_one(cb, e, PROMPT, max_new=8)
            assert len(out["tokens"]) == 8
            assert all(0 <= t < 300 for t in out["tokens"])
            runs.append(out["tokens"])
        assert runs[0] == runs[1]            # deterministic quantization
        fp32 = ContinuousBatcher(e, slots=2, max_seq=96, prefix_pages=64)
        assert cb.pool.pool_bytes < fp32.pool.pool_bytes * 0.55
    finally:
        e.shutdown()


@pytest.mark.parametrize("kv_dtype", ["int8", "fp8_e4m3"])
def test_quantized_warm_prefix_reuse(engine, kv_dtype):
    """Quantization is position-stable (per-position amax, no history
    dependence), so the prefix-cache contract survives: a warm request
    hits the tree's quantized pages and decodes the exact cold tokens,
    and a third request behaves like the second."""
    cb = _quant_batcher(engine, kv_dtype, prefix_pages=64)
    cold = run_one(cb, engine, PROMPT, max_new=6)
    assert cold["hit"] == 0
    warm = run_one(cb, engine, PROMPT, max_new=6)
    assert warm["hit"] > 0
    assert warm["tokens"] == cold["tokens"]
    third = run_one(cb, engine, PROMPT, max_new=6)
    assert third["hit"] == warm["hit"] and third["tokens"] == warm["tokens"]


def test_quantized_pool_and_sidecar_invariants(engine):
    """White-box: quantized pool leaves store the narrow dtype with an
    f32 per-position scale sidecar shaped like the pool minus the head
    dim; every sidecar value stays finite (the scale-0 guard means even
    the trash page — which absorbs idle-slot writes by design — can be
    dequantized without NaN); splice-path pools never quantize."""
    import jax.numpy as jnp

    cb = _quant_batcher(engine, "int8", prefix_pages=64)
    run_one(cb, engine, PROMPT, max_new=6)
    for name in ("k", "v"):
        buf, sc = cb.cache[name], cb.cache[f"{name}_qscale"]
        assert buf.dtype == jnp.int8
        assert sc.dtype == jnp.float32 and sc.shape == buf.shape[:-1]
        assert np.isfinite(np.asarray(sc)).all()
        assert np.asarray(sc[:, 1:]).any()           # real pages scaled
    # contiguous path refuses quantized storage: the pool is built fp32
    prev, engine.paged_kv = engine.paged_kv, False
    try:
        splice = _quant_batcher(engine, "int8", prefix_pages=64)
    finally:
        engine.paged_kv = prev
    assert not splice.paged
    assert splice.pool.kv_dtype == "fp32"
    assert "k_qscale" not in splice.cache


def test_quantized_rolling_window_requant():
    """Rolls under a quantized pool dequantize -> re-rotate -> requantize
    the retained window in place (both value and scale buffers). The
    session must roll at flat occupancy, finish all tokens, and be
    deterministic across runs."""
    from repro.serving import WindowPolicy

    pol = WindowPolicy(sink_pages=1, window_pages=2, roll_pages=1)
    cfg = get_smoke_config("minitron-8b").replace(vocab_size=300,
                                                  vocab_pad_to=64)
    e = ServingEngine(cfg, max_seq=96, kv_dtype="int8", window_policy=pol)
    try:
        runs = []
        for _ in range(2):
            cb = ContinuousBatcher(e, slots=2, max_seq=96, prefix_pages=64)
            assert cb.paged and cb.window is pol
            req = Request(rid="roll", prompt_ids=e.tokenizer.encode(PROMPT),
                          max_new_tokens=90)
            cb.submit(req)
            cb.run_until_drained()
            assert req._rolls >= 2 and len(req.output_ids) == 90
            assert cb.pool_stats().high_water <= pol.cap_pages
            runs.append((req.output_ids, req._rolls))
        assert runs[0] == runs[1]
    finally:
        e.shutdown()


# ---------------------------------------------- speculative rollback edges
def _spec_batcher(engine, **kw):
    """A speculating batcher over the module engine (which defaults to
    speculation off): flip the flag only for construction."""
    engine.speculative = "ngram"
    try:
        cb = ContinuousBatcher(engine, slots=2, max_seq=96, **kw)
    finally:
        engine.speculative = "off"
    assert cb.spec
    return cb


def test_spec_rejection_on_page_boundary(engine):
    """Rejection landing EXACTLY on a page boundary: the window wrote
    K/V into a freshly mapped page, the rejected tail position sits as
    the new page's first entry, and rollback is pure position
    arithmetic — no page is freed or remapped, the free-guard never
    trips, and the emitted tokens match plain decode bitwise."""
    ids = list(range(2, 2 + 20))             # pos starts at 20, page=16
    ref = engine.generate(ids, max_new_tokens=14, stop_on_eos=False).tokens
    cb = _spec_batcher(engine, prefix_pages=64)
    # script acceptance per tick so pos crosses 32 mid-window:
    # tick1 full accept (pos 20->25), tick2 reject at 2 (25->28),
    # tick3 reject at 3 (28->32): position 32 -- page 2's first slot --
    # holds the REJECTED draft's K/V and must be rewritten in place
    corrupt_at = {1: None, 6: 2, 9: 3}
    def hook(slot, req):
        pos = len(req.output_ids)
        d = list(ref[pos:pos + cb.spec_k])
        at = corrupt_at.get(pos, None)
        if at is not None and len(d) > at:
            d[at] = (d[at] + 1) % 300
        return d
    cb.draft_hook = hook
    req = Request(rid="pb", prompt_ids=ids, max_new_tokens=14)
    cb.submit(req)
    cb.step()                                # admission + first token
    mapped = set(cb._bt[0][cb._bt[0] != 0])
    while not req.done:
        cb.step()
        if not req.done:
            # rollback never frees a mapped page (truncation, not free)
            assert not (mapped & set(cb.pool._free))
            mapped |= set(cb._bt[0][cb._bt[0] != 0])
    assert req.output_ids == ref
    assert cb.spec_stats.accepted > 0


def test_spec_rejection_never_touches_tree_pages(engine):
    """A warm speculating session decodes on top of prefix-cache pages
    its block table maps read-only. Forced rejections every tick must
    roll back only the slot's private tail — afterwards the tree's
    pages are still intact (a third, plain request hits the cache and
    decodes the exact cold tokens) and none sit on the free list."""
    cb = _spec_batcher(engine, prefix_pages=64)
    cold = run_one(cb, engine, PROMPT, max_new=6)
    assert cold["hit"] == 0
    ref = list(cold["tokens"])
    def hook(slot, req):
        pos = len(req.output_ids)
        return [(t + 1) % 300 for t in ref[pos:pos + cb.spec_k]]  # all wrong
    cb.draft_hook = hook
    warm = run_one(cb, engine, PROMPT, max_new=6)
    assert warm["hit"] > 0                   # decoding over tree pages
    assert warm["tokens"] == ref             # identity despite rejections
    assert cb.spec_stats.accepted == 0
    tree_pids = set(cb.prefix._pids)
    assert tree_pids and not (tree_pids & set(cb.pool._free))
    cb.draft_hook = None
    third = run_one(cb, engine, PROMPT, max_new=6)
    assert third["hit"] > 0 and third["tokens"] == ref


def test_cancel_mid_verify_releases_draft_state(engine):
    """Cancel while a slot is actively speculating: the slot is
    reclaimed, its draft state is cleared, no page is leaked or double-
    freed, and the next session reuses the slot cleanly."""
    cb = _spec_batcher(engine, prefix_pages=64)
    n_free0 = len(cb.pool._free)
    req = Request(rid="v", prompt_ids=engine.tokenizer.encode(PROMPT),
                  max_new_tokens=40)
    cb.submit(req)
    while cb.spec_stats.spec_ticks == 0 and not req.done:
        cb.step()                            # at least one verify ran
    slot = cb.active.index(req)
    assert cb._draft_len[slot] >= 0
    assert cb.cancel(req)
    assert req.cancelled and req.finish_reason == "cancelled"
    assert cb.active[slot] is None
    assert cb._draft_len[slot] == 0
    cb.run_until_drained()
    # every non-tree page is back on the free list, none twice
    free = list(cb.pool._free)
    assert len(free) == len(set(free))
    assert len(free) + len(cb.prefix._pids) == n_free0
    out = run_one(cb, engine, PROMPT + " again", max_new=4)
    assert len(out["tokens"]) == 4
