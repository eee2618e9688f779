"""The system under test, built as a configuration file states it.

``build`` passes every ``build_system`` argument from the file's
``build`` group and every model field from its ``model`` group, so a
changed program default does not move the yardstick. The served tier's
weights are then replaced by the benchmark's own (``benchlib.weights``),
before any request reaches the engine.
"""

from __future__ import annotations

import gc
import operator
import threading
import time

import jax
import numpy as np

from benchlib import weights
from benchlib.client import send
from benchlib.traffic import Request
from benchlib.text import prompt_text

BOS, SPECIAL = 0, 3   # the byte tokenizer: id = byte + 3, id 0 opens a prompt


def prompt_ids(text: str) -> list[int]:
    return [BOS] + [b + SPECIAL for b in text.encode("utf-8")]


def token_text(tid: int) -> str:
    """What one token id reads as at the client: its byte, or nothing."""
    if SPECIAL <= tid < SPECIAL + 256:
        return bytes([tid - SPECIAL]).decode("utf-8", errors="replace")
    return ""


class SubmitRecorder:
    """Wraps one engine's ``submit`` so that every session's handle is
    kept by its prompt text: after the window it yields the token ids the
    engine produced for each request the client read."""

    def __init__(self, engine):
        self._submit = engine.submit
        self.handles: dict[str, object] = {}
        engine.submit = self

    def __call__(self, prompt, **kw):
        h = self._submit(prompt, **kw)
        self.handles[prompt] = h
        return h


def plan_bytes(config: dict, ref) -> int:
    """Planned device bytes of the served tier (chip_smoke's rule):
    weights (the layout of the configuration's reference module ``ref``),
    plus the KV page pool twice over for attention models (the
    fused tick does not donate it, so two copies are live), or the state
    snapshot pool plus two copies of the slot states for recurrent ones."""
    from repro.models import build_model
    from repro.models.common import ModelConfig, cache_layout, has_state_leaves

    m, b = config["model"], config["build"]
    model = build_model(ModelConfig(**m))
    nbytes = lambda tree: int(sum(np.prod(x.shape) * x.dtype.itemsize
                                  for x in jax.tree.leaves(tree) if x.ndim))
    params = nbytes(jax.eval_shape(
        lambda: weights.make(ref.weight_spec(m), m["param_dtype"], 0)))
    page, pages, slots = 16, b["prefix_cache_pages"], b["scheduler_slots"]
    if has_state_leaves(cache_layout(model.cache_specs())):
        state = nbytes(jax.eval_shape(lambda: model.init_cache(1, 1)))
        return params + (pages + 1) * state + 2 * slots * state
    slot_page = nbytes(jax.eval_shape(lambda: model.init_cache(1, page)))
    return params + 2 * (pages + 1) * slot_page


def build(config: dict, ref, seed: int, tier: str):
    """The whole system, the engine of ``tier`` (the traffic's) built at
    the configuration's sizes and holding the benchmark's weights for
    ``seed``, in the layout of the configuration's reference module
    ``ref``; the other tiers stay at the program's smoke size. Returns
    (system, engine, params)."""
    from repro.core import build_system

    system = build_system(**config["build"],
                          **{f"{tier}_overrides": dict(config["model"])},
                          seed=seed % (2 ** 31 - 1))
    engine = system.engines[tier]
    shapes = jax.eval_shape(engine.model.init, jax.random.PRNGKey(0))
    engine.params = None          # the program's own draw is not served
    gc.collect()
    m = config["model"]
    params = weights.make(ref.weight_spec(m), m["param_dtype"], seed)
    bad = weights.same_layout(params, shapes)
    if bad:
        raise RuntimeError(f"benchmark weights do not fit the model: {bad[:5]}")
    engine.params = params
    return system, engine, params


def warm(system, bearer: str, config: dict, traffic: dict, seed: int,
         compiles=None, log=None) -> list:
    """Drive the served engine's batcher through every program shape the
    window will use before the window opens: the ``idle`` requests one
    at a time (admissions into an empty batch prefill in one go), then
    the ``busy`` ones all at once (admissions interleave with decode
    ticks). Lengths come from the configuration file; text from the
    seed. ``log`` gets each phase's time, the first request's time to
    its first token and the compilations (``compiles.n``) it caused.
    Returns the errors of the warm-up requests that failed."""
    import random
    rng = random.Random(f"warm:{seed}")
    tier, alias = traffic["tier"], traffic["alias"]
    mk = lambda i, n, out: Request(index=-1 - i, principal=0,
                                   prompt=prompt_text(rng, n - 1),
                                   prompt_tokens=n, max_tokens=out)
    w = config["warmup"]
    built = lambda: (compiles.n, compiles.hits) if compiles else (0, 0)
    b0, t0 = built(), time.perf_counter()
    recs = [send(system.gateway, bearer, mk(i, n, out), alias, tier)
            for i, (n, out) in enumerate(w["idle"])]
    b1, t1 = built(), time.perf_counter()
    busy = [mk(1000 + i, n, out) for i, (n, out) in enumerate(w["busy"])]
    recs += [None] * len(busy)

    def one(i):
        recs[len(w["idle"]) + i] = send(system.gateway, bearer, busy[i],
                                        alias, tier)

    threads = [threading.Thread(target=one, args=(i,)) for i in range(len(busy))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if log is not None:
        first = recs[0].ttft_s * 1e3 if recs and recs[0].ok else float("nan")
        b2 = built()
        log(f"warm-up: idle {t1 - t0:.3f} s (first token {first:.1f} ms, "
            f"{b1[0] - b0[0]} programs, {b1[1] - b0[1]} from the cache), "
            f"busy {time.perf_counter() - t1:.3f} s ({b2[0] - b1[0]} programs, "
            f"{b2[1] - b1[1]} from the cache)")
    return [r.error or f"status {r.status}" for r in recs if not r.ok]


def batcher_counters(engine, named: dict | None = None) -> dict:
    """The served engine's batcher counters (decode ticks ``transfers``,
    ``admissions``, ``slots``), plus each of ``named`` ({name: dotted
    attribute path on the engine}, a configuration's ``counters``), e.g.
    ``{"pages_walked": "scheduler.batcher.kv_pages_walked"}``."""
    b = engine.scheduler.batcher if engine.scheduler is not None else None
    out = ({"transfers": 0, "admissions": 0, "slots": 0} if b is None else
           {"transfers": b.transfers, "admissions": b.admissions, "slots": b.B})
    for name, path in (named or {}).items():
        out[name] = operator.attrgetter(path)(engine)
    return out


def annotate_host():
    """Name the program's host work in the profiler's trace: wrap the
    batcher tick, its admissions, and the relay's encryption and
    decryption in ``jax.profiler.TraceAnnotation`` spans ("bench.*").
    Only the traced run calls this."""
    from repro.core import data_plane
    from repro.serving.scheduler import ContinuousBatcher

    def wrap(owner, attr, name):
        fn = getattr(owner, attr)

        def wrapped(*a, **kw):
            with jax.profiler.TraceAnnotation(name):
                return fn(*a, **kw)
        setattr(owner, attr, wrapped)

    wrap(ContinuousBatcher, "step", "bench.batcher.tick")
    wrap(ContinuousBatcher, "_advance_admissions", "bench.batcher.admit")
    wrap(data_plane, "encrypt_envelope", "bench.relay.encrypt")
    wrap(data_plane, "decrypt_envelope", "bench.relay.decrypt")


def shutdown(system):
    for e in system.engines.values():
        e.shutdown()
    system.gateway.shutdown()
    system.endpoint.shutdown()
    for _ in system.endpoint._workers:   # end the control plane's workers
        system.endpoint._q.put(None)


def wait_idle(engine, timeout: float = 60.0):
    """Until the engine's broker has nothing queued or in flight."""
    t_end = time.perf_counter() + timeout
    while time.perf_counter() < t_end:
        b = engine.scheduler
        if b is None or b.depth() == 0:
            return
        time.sleep(0.01)
