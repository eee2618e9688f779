"""The served path's own profiler spans and request stamps.

The batcher and the relay name their host work as
``jax.profiler.TraceAnnotation`` spans (``stream.*``) in the profiler's
trace, and stamp each request's admission on the ``submitted_at`` clock.
These tests take a trace of a few test-size ticks, read it back with
``jax.profiler.ProfileData``, and check that the spans are there, nest as
documented in docs/serving.md, and change nothing that is served.
"""

import glob
import os
import threading

import jax
import pytest

from repro.configs import get_smoke_config
from repro.core.crypto import new_key
from repro.core.data_plane import TokenProducer, consume_tokens
from repro.core.relay import Relay, new_channel_id
from repro.serving import ContinuousBatcher, Request, ServingEngine
from repro.serving.broker import SessionBroker

TICK, ADMIT, DECODE, EMIT = (f"stream.batcher.{p}"
                             for p in ("tick", "admit", "decode", "emit"))
PROMPTS = ("spans one", "spans two, a little longer than the first",
           "spans three", "spans four: the longest prompt of these by some way")


@pytest.fixture(scope="module")
def engine():
    cfg = get_smoke_config("minitron-8b").replace(vocab_size=300, vocab_pad_to=64)
    e = ServingEngine(cfg, max_seq=96)
    e.warmup()
    return e


def host_spans(log_dir, prefix="stream."):
    """(line, name, start_ns, end_ns, stats) of the trace's host events
    whose name starts with ``prefix``; ``line`` tells thread lines apart."""
    path = sorted(glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                            recursive=True))[-1]
    pd = jax.profiler.ProfileData.from_file(path)
    return [((plane.name, i), e.name, e.start_ns, e.start_ns + e.duration_ns,
             dict(e.stats))
            for plane in pd.planes if plane.name.startswith("/host:")
            for i, line in enumerate(plane.lines) for e in line.events
            if e.name.startswith(prefix)]


def serve(engine, log_dir=None, spec=None):
    """Run the PROMPTS through a fresh 2-slot batcher (so admissions
    queue and interleave with decode ticks), under a profiler trace
    into ``log_dir`` when given. Returns (batcher, requests)."""
    if spec is not None:
        engine.speculative = spec
    try:
        cb = ContinuousBatcher(engine, slots=2, max_seq=96, prefill_chunk=16)
    finally:
        engine.speculative = "off"
    if spec is not None:
        # a fixed draft every tick, so every decode tick is a verify tick
        cb.draft_hook = lambda slot, req: [7, 8]
    reqs = [Request(rid=f"r{i}", prompt_ids=engine.tokenizer.encode(p),
                    max_new_tokens=6 + i)
            for i, p in enumerate(PROMPTS)]
    for r in reqs:
        cb.submit(r)
    if log_dir is None:
        cb.run_until_drained()
    else:
        with jax.profiler.trace(str(log_dir)):
            cb.run_until_drained()
    assert all(r.done and not r.cancelled for r in reqs)
    return cb, reqs


@pytest.mark.parametrize("spec", [None, "ngram"], ids=["plain", "speculative"])
def test_batcher_spans_nest_inside_ticks(engine, tmp_path, spec):
    cb, reqs = serve(engine, tmp_path, spec)
    # verify ticks when drafting (a slot one token from its end drafts none)
    assert (cb.spec_stats.spec_ticks > 0) == (spec is not None)
    spans = host_spans(tmp_path)
    names = {n for _, n, *_ in spans}
    assert {TICK, ADMIT, DECODE, EMIT} <= names
    ticks = [s for s in spans if s[1] == TICK]
    for line, name, a, b, _ in spans:
        if name in (ADMIT, DECODE, EMIT):
            assert any(t[0] == line and t[2] <= a and b <= t[3] for t in ticks), name
    # one decode span per packed token read, i.e. per decode tick
    assert sum(s[1] == DECODE for s in spans) == cb.transfers > 0
    # each admission span names the request it popped or advanced
    admits = [s[4] for s in spans if s[1] == ADMIT]
    assert {st["rid"] for st in admits} == {r.rid for r in reqs}


def test_spans_change_no_served_token(engine, tmp_path):
    _, quiet = serve(engine)
    _, traced = serve(engine, tmp_path)
    assert [r.output_ids for r in traced] == [r.output_ids for r in quiet]
    assert all(len(r.output_ids) > 1 for r in quiet)


def test_requests_are_stamped_at_the_queue_pop(engine):
    _, reqs = serve(engine)
    for r in reqs:
        assert r.submitted_at <= r.admitted_at
    # two slots: the third request waits for a slot to free
    assert reqs[2].admitted_at > max(reqs[0].admitted_at, reqs[1].admitted_at)


def test_relay_spans_one_per_message(tmp_path):
    relay, ch, key = Relay("span-secret"), new_channel_id(), new_key()
    tokens = [(5, "a"), (6, "b"), (7, "c")]

    def produce():
        prod = TokenProducer(relay, ch, "span-secret", key)
        prod.meta({"prefix_hit_tokens": 0})
        for t in tokens:
            prod.push(*t)
        prod.done()

    with jax.profiler.trace(str(tmp_path)):
        th = threading.Thread(target=produce)
        th.start()
        got = list(consume_tokens(relay, ch, "span-secret", key))
        th.join()
    assert [(p["id"], p["text"]) for p in got if p["t"] == "token"] == tokens
    spans = host_spans(tmp_path, "stream.relay.")
    n_msgs = 1 + len(tokens) + 1                   # meta, tokens, done
    enc = [s for s in spans if s[1] == "stream.relay.encrypt"]
    dec = [s for s in spans if s[1] == "stream.relay.decrypt"]
    assert len(enc) == len(dec) == n_msgs
    # producer and consumer run on different threads
    assert {s[0] for s in enc}.isdisjoint({s[0] for s in dec})


def test_session_stamps_split_ttft_into_wait_and_admission(engine):
    """A session queued behind a long admission on a one-slot broker
    waits in the queue, then is admitted; both stamps lie on the clock
    of ``submitted_at`` and order as submit <= admit <= first token."""
    broker = SessionBroker(engine, slots=1, max_seq=96, prefill_chunk=16)
    try:
        long_prompt = "x" * 60
        h1 = broker.submit(long_prompt, max_new_tokens=8)
        h2 = broker.submit("queued behind", max_new_tokens=4)
        for h in (h1, h2):
            res = h.result(timeout=120)
            assert not res.cancelled and res.n_generated >= 1
            assert h.submitted_at <= h.admitted_at <= h.first_token_at
            assert h.ttft_s == pytest.approx(h.first_token_at - h.submitted_at)
        assert h2.admitted_at - h2.submitted_at > 0
        # the second session's admission waited out the first's decode
        assert h2.admitted_at >= h1.first_token_at
    finally:
        broker.shutdown()
