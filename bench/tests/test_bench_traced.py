"""A traced run of the HPC-tier cell at a test size on the CPU: the
program's own ``stream.*`` spans, its sessions and the counters at the
traced window's ends reach the per-layer readers, while the breakdown
still reads the benchmark's ``bench.*`` spans. (The CPU trace holds no
device plane, so the device readers have nothing to read here.)"""

from __future__ import annotations

import json
import os
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import benchfaults as bf  # noqa: E402
from benchlib import harness, registry  # noqa: E402

CELL = "mistral7b-hpc-api"
PROGRAM_METRICS = ("queue_wait_ms.api", "admit_ms.api", "tick_admit_ms.api",
                   "tick_decode_ms.api", "tick_emit_ms.api", "relay_encrypt_us",
                   "relay_decrypt_us")


@pytest.fixture
def root(tmp_path):
    """The test-size checkout, its HPC cell given the benchmark's own
    per-layer entries of the program's spans and sessions."""
    root = bf.tiny_root(tmp_path)
    ours = {m["name"]: m for m in registry.load_benchmark(bf.ROOT)["per_layer"]}
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["per_layer"] += [ours[n] for n in PROGRAM_METRICS + ("idle_emit_share.api",)]
    with open(path, "w") as f:
        json.dump(bench, f)
    return root


def test_traced_run_hands_program_spans_sessions_and_counters_to_readers(root, monkeypatch):
    from repro.core import data_plane
    from repro.serving.scheduler import ContinuousBatcher
    # the traced run wraps these in bench.* spans; undo that afterwards
    for owner, attr in ((ContinuousBatcher, "step"),
                        (ContinuousBatcher, "_advance_admissions"),
                        (data_plane, "encrypt_envelope"),
                        (data_plane, "decrypt_envelope")):
        monkeypatch.setattr(owner, attr, getattr(owner, attr))
    ctxs, hosts = {}, []
    reader = harness.metric_reader

    def spy(name, r):
        def read(ctx):
            ctxs[name] = ctx
            return reader(name, r)(ctx)
        return read

    monkeypatch.setattr(harness, "metric_reader", spy)
    idle_gaps = harness.tracemod.idle_gaps

    def gaps(tr, *a):
        hosts.extend(n for n, _, _ in tr["host"])
        return idle_gaps(tr, *a)

    monkeypatch.setattr(harness.tracemod, "idle_gaps", gaps)
    res = harness.run_cell(registry.find_cell(CELL, root), bf.SEED, 2.5, True,
                           t_start=time.perf_counter(), compile_cache=False)
    assert res["correct"], res["checks"]
    for name in PROGRAM_METRICS:
        assert res["metrics"][name]["value"] > 0, name
    # the breakdown reads the benchmark's spans; the readers the program's
    assert hosts and all(n.startswith("bench.") for n in hosts)
    ctx = ctxs["tick_decode_ms.api"]
    names = {n for n, _, _ in ctx.extra["program_spans"]}
    assert {"stream.batcher.tick", "stream.batcher.decode", "stream.relay.encrypt"} <= names
    assert all(n.startswith("stream.") for n in names)
    assert ctx.extra["sessions"] and ctx.reference is not None
    # the traced counters lie inside the window's, and the three tick
    # phases fit in the traced seconds per traced tick
    ticks = ctx.trace_counters1["transfers"] - ctx.trace_counters0["transfers"]
    assert 0 < ticks <= ctx.counters1["transfers"] - ctx.counters0["transfers"]
    phases = sum(res["metrics"][f"tick_{p}_ms.api"]["value"]
                 for p in ("admit", "decode", "emit"))
    assert 0 < phases <= 1.1 * (ctx.trace_t1 - ctx.trace_t0) / ticks * 1e3
    assert "idle_emit_share.api" not in res["metrics"]    # no device plane
