#!/usr/bin/env python3
"""One-chip smoke run of the served STREAM path at Minitron-8B widths.

    python chip_smoke.py [--seed N]

Builds the whole system through ``build_system``: the hpc tier serves
Minitron-8B at its published widths (depth cut to fit one 16 GB TPU
v5e, bf16 weights, Pallas kernels on) and the local tier serves the
whole published xLSTM-125M. Weights are random, drawn from ``--seed``;
prompts are byte-tokenized text, so nothing is downloaded. Then:

  * streamed requests go through the OpenAI gateway to ``stream-hpc``
    (control plane, then the encrypted relay), ``stream-local`` and
    ``stream-auto``; each must come back from the tier it was sent to,
    with no fallback, no error frame and the full token count;
  * the kernel path's logits are checked against the jnp reference
    path, and int8 KV pages against bf16 pages, teacher-forced through
    the paged cache on the chip.

Times printed on the way are smoke readings of one run, not benchmark
results. The last line of stdout is one JSON object naming the device;
it is printed only when every check passed. Without a TPU the script
exits non-zero before building anything.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import build_system  # noqa: E402
from repro.core.sse import parse_sse  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.models.common import cache_layout, has_state_leaves  # noqa: E402
from repro.serving import PagePool, ServingEngine  # noqa: E402
from repro.serving.pagepool import chunk_plan  # noqa: E402

HPC_ARCH, LOCAL_ARCH = "minitron-8b", "xlstm-125m"
HPC_LAYERS = 16          # of Minitron's 32: the deepest cut that fits 16 GB
PAGE = 16
MAX_SEQ = 256
SLOTS = 4
# pool pages per tier engine: twice one worst-case slot (MAX_SEQ / PAGE
# pages). xLSTM's pool keeps a 21 MB recurrent-state snapshot per page,
# so this budget also bounds the local tier's snapshot memory.
PREFIX_PAGES = 2 * MAX_SEQ // PAGE
MAX_TOKENS = 16
N_FORCED = 16
HEADROOM = 0.9           # plan at most this share of the device's memory

# Kernel vs reference, both computing in bf16: the two paths round at
# different points (the Pallas rmsnorm and paged attention keep float32
# until their output; the reference rounds around each jnp op), so each
# layer's output may differ by a few bf16 roundings (unit roundoff
# 2**-8 = 0.39%). Over 16 residual layers such differences add up like a
# random walk, about sqrt(16) * 2**-8 = 1.6% of the logit scale. The
# bound allows three times that: 5% of the reference's largest |logit|.
KERNEL_REL_BOUND = 0.05
# int8 pages vs bf16 pages: per-position amax quantization moves each K/V
# element by up to half a step, 1/254 of its row's amax — an error of
# the same order as a bf16 rounding but relative to the row's largest
# element, on top of the rounding-order differences above. Twice the
# kernel bound.
INT8_REL_BOUND = 0.10

PROMPTS = {
    "stream-hpc": ["Explain how MPI collectives relate to GPU memory "
                   "hierarchies and compare their trade-offs.",
                   "Compare and contrast hash tables with database indexing."],
    "stream-local": ["What is the capital of France?",
                     "Define entropy in one sentence."],
    "stream-auto": ["Compare and contrast hash tables with database indexing.",
                    "What is the capital of France?"],
}
ALIAS_TIER = {"stream-hpc": "hpc", "stream-local": "local"}


def hpc_config(n_layers: int = HPC_LAYERS):
    """Minitron-8B at its published widths, ``n_layers`` deep."""
    return get_config(HPC_ARCH, n_layers=n_layers, param_dtype="bfloat16",
                      use_kernels=True)


def local_config():
    return get_config(LOCAL_ARCH)


def sizing(hpc_cfg, local_cfg, *, slots=SLOTS, max_seq=MAX_SEQ,
           prefix_pages=PREFIX_PAGES, page=PAGE) -> dict:
    """Planned device bytes of both tier engines, from shapes alone."""
    def nbytes(tree):   # scalar leaves ("pos") are not pooled
        return int(sum(np.prod(x.shape) * x.dtype.itemsize
                       for x in jax.tree.leaves(tree) if x.ndim))

    out = {}
    for tier, cfg in (("hpc", hpc_cfg), ("local", local_cfg)):
        model = build_model(cfg)
        params = nbytes(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
        slot_cache = nbytes(jax.eval_shape(lambda: model.init_cache(1, page)))
        state = nbytes(jax.eval_shape(lambda: model.init_cache(1, 1)))
        # attention caches pool pages of ``page`` tokens and decode
        # straight from the pool, which the fused tick does not donate:
        # two live copies. Recurrent caches pool one whole-state snapshot
        # per page (stored in place) and decode from a B-slot cache that
        # the tick does not donate.
        stateful = has_state_leaves(cache_layout(model.cache_specs()))
        if stateful:
            pool = (prefix_pages + 1) * state
            slots_b = 2 * slots * nbytes(jax.eval_shape(
                lambda: model.init_cache(1, max_seq)))
        else:
            pool, slots_b = 2 * (prefix_pages + 1) * slot_cache, 0
        out[tier] = {"params": params, "pool": pool, "slots": slots_b}
    kv = hpc_cfg.n_kv_heads * hpc_cfg.head_dim * 2 * 2   # K and V, bf16
    out["kv_bytes_per_token_per_layer"] = kv
    out["total"] = sum(v["params"] + v["pool"] + v["slots"]
                       for v in out.values() if isinstance(v, dict))
    return out


def build(hpc_cfg, local_cfg, *, seed=0, max_seq=MAX_SEQ, slots=SLOTS,
          prefix_pages=PREFIX_PAGES):
    """The whole STREAM system with both tier configs taken field for
    field (``build_system`` starts from smoke configs; the overrides
    replace every field)."""
    return build_system(hpc_arch=HPC_ARCH, local_arch=LOCAL_ARCH,
                        hpc_overrides=dataclasses.asdict(hpc_cfg),
                        local_overrides=dataclasses.asdict(local_cfg),
                        max_seq=max_seq, scheduler_slots=slots,
                        prefix_cache_pages=prefix_pages, seed=seed,
                        relay_enabled=True, encrypt=True)


def warm(system, max_tokens=MAX_TOKENS) -> float:
    """One session through each tier engine's broker, so the batcher's
    programs compile before any gateway request is timed."""
    t0 = time.perf_counter()
    for tier in ("hpc", "local"):
        res = system.engines[tier].submit(
            "warm up", max_new_tokens=max_tokens,
            params={"max_tokens": max_tokens, "temperature": 0.0}).result()
        assert res.n_generated >= 1, (tier, res)
    return time.perf_counter() - t0


def serve(system, *, max_tokens=MAX_TOKENS, prompts=PROMPTS) -> list:
    """Streamed gateway requests; asserts the contract of each one and
    returns its readings."""
    bearer = system.globus.issue_token("smoke@uic.edu")
    relay0 = system.relay.stats["channels_created"]
    n_hpc, readings = 0, []
    for alias, queries in prompts.items():
        for q in queries:
            want = ALIAS_TIER.get(alias) or system.handler.route_only(q)
            t0 = time.perf_counter()
            resp = system.gateway.handle_chat_completions(
                {"model": alias, "messages": [{"role": "user", "content": q}],
                 "max_tokens": max_tokens, "temperature": 0.0,
                 "stream": True, "stream_options": {"include_usage": True}},
                bearer=bearer)
            ttft = time.perf_counter() - t0
            assert resp.status == 200, (alias, resp.status, resp.body)
            text = "".join(resp.stream)
            total = time.perf_counter() - t0
            chunks = parse_sse(text)
            errors = [c for c in chunks if "error" in c]
            assert not errors, (alias, errors)
            h = resp.headers
            assert h["x-stream-tier"] == want, (alias, want, h)
            assert h["x-stream-fallback-depth"] == "0", (alias, h)
            usage, meta = chunks[-1]["usage"], chunks[-1]["stream"]
            assert meta["tier"] == want and meta["fallback_depth"] == 0, meta
            finish = [c["choices"][0]["finish_reason"] for c in chunks
                      if c.get("choices") and c["choices"][0].get("finish_reason")]
            n = usage["completion_tokens"]
            assert n == max_tokens or finish == ["stop"], (alias, usage, finish)
            n_hpc += want == "hpc"
            readings.append({"alias": alias, "tier": want, "ttft_s": ttft,
                             "tok_per_s": (n - 1) / max(total - ttft, 1e-9),
                             "completion_tokens": n})
    # every hpc answer crossed the relay on a channel of its own
    assert system.relay.stats["channels_created"] - relay0 == n_hpc, \
        (system.relay.stats, n_hpc)
    return readings


def paged_logits(engine, ids, forced, *, page=PAGE):
    """Teacher-force ``ids`` then ``forced`` through ``engine``'s paged
    path: chunked prefill into a private pool, then one decode step per
    forced token. Returns (1 + len(forced), vocab) float32 logits."""
    n_pages = -(-(len(ids) + len(forced)) // page)
    pool = PagePool(engine.model, page=page, capacity=n_pages,
                    kv_dtype=engine.kv_dtype)
    cache = pool.paged_cache(1, n_pages)
    cache["block_tables"] = jnp.asarray([[pool.alloc() for _ in range(n_pages)]],
                                        jnp.int32)
    cache["pos"] = jnp.zeros((), jnp.int32)
    cache["pos_offset"] = jnp.zeros((), jnp.int32)
    off, out = 0, []
    for n in chunk_plan(0, len(ids), page):
        logits, cache = engine._prefill_chunk(
            engine.params, jnp.asarray([ids[off:off + n]], jnp.int32), cache)
        off += n
    out.append(logits)
    cache["pos"] = jnp.asarray([len(ids)], jnp.int32)
    cache["pos_offset"] = jnp.zeros((1,), jnp.int32)
    for t in forced:
        logits, cache = engine._decode(engine.params,
                                       jnp.asarray([[t]], jnp.int32), cache)
        out.append(logits)
    V = engine.cfg.vocab_size
    return np.stack([np.asarray(l[0, :V], np.float32) for l in out])


def compare(system, *, seed=0, n_forced=N_FORCED) -> dict:
    """Kernel path vs reference path, and int8 pages vs bf16 pages, on
    the hpc tier's own parameters (shared, never copied)."""
    hpc = system.engines["hpc"]
    assert hpc.cfg.use_kernels, "the hpc tier must serve through the kernels"
    mk = lambda cfg, kv: ServingEngine(cfg, params=hpc.params,
                                       max_seq=hpc.max_seq, kv_dtype=kv)
    ref = mk(hpc.cfg.replace(use_kernels=False), hpc.kv_dtype)
    int8 = mk(hpc.cfg, "int8")
    # a whole number of pages, so each engine compiles one chunk shape
    ids = hpc.tokenizer.encode(PROMPTS["stream-hpc"][0])
    ids = ids[:len(ids) // PAGE * PAGE]
    forced = np.random.default_rng(seed).integers(3, 259, n_forced).tolist()
    base = paged_logits(hpc, ids, forced)
    assert np.isfinite(base).all(), "kernel path"
    out = {"logit_scale": float(np.abs(base).max())}
    for name, other in (("kernel_vs_ref", ref), ("int8_vs_bf16", int8)):
        got = paged_logits(other, ids, forced)
        assert np.isfinite(got).all(), name
        out[name] = float(np.abs(got - base).max())
    return out


def check_bounds(errs: dict):
    scale = errs["logit_scale"]
    assert errs["kernel_vs_ref"] <= KERNEL_REL_BOUND * scale, errs
    assert errs["int8_vs_bf16"] <= INT8_REL_BOUND * scale, errs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and forced tokens")
    args = ap.parse_args(argv)

    devs = jax.devices()
    dev = devs[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devs)}", flush=True)
    if dev.platform != "tpu":
        print("chip_smoke: no TPU found; this script runs only on the chip",
              file=sys.stderr)
        return 2

    print(f"compile cache: {enable_compile_cache()}")

    limit = dev.memory_stats()["bytes_limit"]
    layers = HPC_LAYERS
    while True:
        plan = sizing(hpc_config(layers), local_config())
        if plan["total"] <= HEADROOM * limit or layers == 1:
            break
        layers -= 1
    cut = ("" if layers == HPC_LAYERS else
           f" (cut from {HPC_LAYERS}: {HPC_LAYERS} layers plan more than "
           f"{HEADROOM:.0%} of the device's memory)")
    print(f"sizing: hpc {HPC_ARCH} n_layers={layers}{cut}; "
          f"device bytes_limit={limit} planned={plan['total']} "
          f"hpc params={plan['hpc']['params']} hpc pool={plan['hpc']['pool']} "
          f"local params={plan['local']['params']} "
          f"local pool+slots={plan['local']['pool'] + plan['local']['slots']} "
          f"kv bytes/token/layer={plan['kv_bytes_per_token_per_layer']}", flush=True)
    assert plan["total"] <= HEADROOM * limit, plan

    t0 = time.perf_counter()
    system = build(hpc_config(layers), local_config(), seed=args.seed)
    build_s = time.perf_counter() - t0
    try:
        warm_s = warm(system)
        print(f"smoke reading: build (init + warmup compile) {build_s:.1f} s, "
              f"first broker sessions (batcher compile) {warm_s:.1f} s",
              flush=True)
        readings = serve(system)
        for alias in PROMPTS:
            rs = [r for r in readings if r["alias"] == alias]
            print(f"smoke reading: {alias:>12s} tier={rs[0]['tier']} "
                  f"ttft_ms={statistics.median(r['ttft_s'] for r in rs) * 1e3:.1f} "
                  f"tok/s={statistics.median(r['tok_per_s'] for r in rs):.1f} "
                  f"tokens={[r['completion_tokens'] for r in rs]}", flush=True)
        errs = compare(system, seed=args.seed)
        print(f"logits: max|logit| {errs['logit_scale']:.4f}; kernel vs "
              f"reference max|dlogit| {errs['kernel_vs_ref']:.6f} (bound "
              f"{KERNEL_REL_BOUND * errs['logit_scale']:.4f}); int8 vs bf16 "
              f"pages max|dlogit| {errs['int8_vs_bf16']:.6f} (bound "
              f"{INT8_REL_BOUND * errs['logit_scale']:.4f})", flush=True)
        check_bounds(errs)
    finally:
        for eng in system.engines.values():
            eng.shutdown()
        system.gateway.shutdown()
        system.endpoint.shutdown()
    print(f"peak_bytes_in_use={dev.memory_stats()['peak_bytes_in_use']}")
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
