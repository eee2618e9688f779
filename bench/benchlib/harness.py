"""One run of one cell: build, warm up, measure a window, check.

``run_cell`` is the whole run after the look for a chip; ``bench/run.py``
makes that look and then calls it. Set-up (``setup_s``) is everything
from process start to the start of load: imports, building the system
and drawing weights (compiling on a cold cache), and warm-up traffic
through every program shape the window uses. Load then runs a lead-in
of ``lead_s`` (the traffic file's) before the measured window opens.
"""

from __future__ import annotations

import gc
import json
import os
import shutil
import sys
import tempfile
import threading
import time
from dataclasses import dataclass, field

import jax

from benchlib import check, system as sysmod, trace as tracemod
from benchlib.load import Load
from benchlib.peaks import peaks
from benchlib.registry import ROOT, Cell, metric_reader, reference_module
from benchlib.stats import percentile
from benchlib.traffic import generate

CACHE_DIR = os.path.join(ROOT, ".bench_cache", "jax")
DRAIN_S = 90.0         # a request still streaming this long after the close failed

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    """Counts the programs JAX builds (``n``: compiled, or loaded from the
    persistent cache) and of those the cache loads (``hits``), from its
    monitoring events."""

    def __init__(self):
        self.n = self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_):
        if event == BACKEND_COMPILE:
            self.n += 1

    def _on_event(self, event: str, **_):
        if event == CACHE_HIT:
            self.hits += 1


def enable_compile_cache():
    """JAX's persistent compilation cache at a fixed path in the checkout,
    holding every program however small, so that only a checkout's first
    run compiles."""
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_enable_compilation_cache", True)


@dataclass
class Context:
    """What a per-layer metric reader may read.

    ``counters0``/``counters1`` are ``system.batcher_counters`` (with the
    configuration's named ``counters``) at the window's open and close,
    ``trace_counters0``/``trace_counters1`` the same at the traced
    window's start and end (``trace_t0``, ``trace_t1``). In a traced run
    ``extra`` holds ``program_spans``, the program's own ``stream.*``
    host spans ([name, start_ns, dur_ns], on the trace's clock), and
    ``sessions``, every ``SessionHandle`` the served engine returned
    (stamps ``submitted_at``, ``admitted_at``, ``first_token_at`` on the
    host's ``perf_counter``, the clock of ``t0``)."""
    model: dict
    records: list
    t0: float                     # window open (host perf_counter)
    seconds: float
    wall0: float                  # window open (time.time, control plane clock)
    counters0: dict
    counters1: dict
    task_records: list
    peaks: dict
    trace: dict | None = None     # reduced trace of the window's last seconds
    trace_t0: float = 0.0
    trace_t1: float = 0.0
    page: int = 16
    extra: dict = field(default_factory=dict)
    reference: object = None      # the configuration's reference module
    trace_counters0: dict = field(default_factory=dict)
    trace_counters1: dict = field(default_factory=dict)

    def tokens_between(self, a: float, b: float):
        """(record, index) of every token event the client read in [a, b)."""
        for r in self.records:
            for i, t in enumerate(r.token_times):
                if a <= t < b:
                    yield r, i


def in_window(records, t0: float) -> list:
    """The requests sent (closed loop) or due (open loop) in the window,
    leaving out those of the lead-in."""
    return [r for r in records if r.start >= t0]


def end_to_end(records, t0: float, seconds: float) -> dict:
    """Client-side metrics: latencies over every request of the window (a
    failed one counts as missing every limit: its TTFT is the drain
    deadline), the rate over every token read inside the window."""
    t_end = t0 + seconds
    miss = seconds + DRAIN_S
    out_tok = sum(1 for r in records for t in r.token_times if t0 <= t < t_end)
    records = in_window(records, t0)
    ttft = [r.ttft_s if r.ok else miss for r in records]
    itl = [b - a for r in records if r.ok
           for a, b in zip(r.token_times, r.token_times[1:])]
    return {"ttft_p50_ms": percentile(ttft, 50) * 1e3,
            "ttft_p90_ms": percentile(ttft, 90) * 1e3,
            "itl_p99_ms": percentile(itl, 99) * 1e3 if itl else miss * 1e3,
            "output_tok_s": out_tok / seconds}


def _log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, compile_cache: bool = True,
             control: str | None = None,
             dump_trace: str | None = None) -> dict:
    """Run ``cell`` once and return the result line's object.

    Options a benchmark run never uses: ``control`` ("fp8") puts
    the plain reference computed in that lower precision in the
    program's place for the comparison, so the run must come out not
    correct (the program's own reading is still logged beside it: one
    run gives both readings a limit is set from); ``dump_trace`` writes
    a summary of the traced window's profile to that path for reading
    by hand."""
    cfg, traffic = cell.config, cell.traffic
    ref = reference_module(cfg["reference"], cell.root)
    counters = lambda: sysmod.batcher_counters(engine, cfg.get("counters"))
    compiles = CompileCounter()
    tier = traffic["tier"]
    if compile_cache:
        enable_compile_cache()
    devs = jax.devices()
    kind = devs[0].device_kind
    pk = peaks(kind) if devs[0].platform == "tpu" else {}

    stats0 = devs[0].memory_stats() or {}
    if "bytes_limit" in stats0:
        plan = sysmod.plan_bytes(cfg, ref)
        limit = cfg["memory_headroom"] * stats0["bytes_limit"]
        _log(f"sizing: planned {plan} B of {stats0['bytes_limit']} B "
             f"(headroom {cfg['memory_headroom']})")
        if plan > limit:
            raise RuntimeError(f"planned {plan} B exceeds {limit:.0f} B")

    system, engine, params = sysmod.build(cfg, ref, seed, tier)
    recorder = sysmod.SubmitRecorder(engine)
    users = int(traffic.get("clients") or traffic.get("users"))
    bearers = [system.globus.issue_token(f"user{i}@uic.edu")
               for i in range(users)]
    warm_bearer = system.globus.issue_token("warmup@uic.edu")
    t_build = time.perf_counter()
    warm_failed = sysmod.warm(system, warm_bearer, cfg, traffic, seed,
                              compiles=compiles, log=_log)
    if warm_failed:
        raise RuntimeError(f"{len(warm_failed)} warm-up requests failed: "
                           f"{warm_failed[:3]}")
    sysmod.wait_idle(engine)
    counters()                    # a named counter that is not there fails here
    reqs = generate(traffic, seed)
    if trace:
        sysmod.annotate_host()
    load = Load(system.gateway, bearers, traffic, tier)
    setup_s = time.perf_counter() - t_start
    _log(f"setup: {setup_s:.3f} s (build {t_build - t_start:.3f} s, "
         f"warm-up {time.perf_counter() - t_build:.3f} s)")

    # ---------------------------------------------------------- window
    # load starts now; the window opens after the lead-in
    t0 = time.perf_counter() + 0.05 + load.lead_s
    wall0 = time.time() + 0.05 + load.lead_s
    snap = {}
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    # the traced run records the window's last seconds; how many is the
    # configuration's, since reading the trace takes time per device op
    trace_s = min(float(cfg["trace_seconds"]), seconds)

    def watch():
        while time.perf_counter() < t0:
            time.sleep(0.001)
        snap["c0"], snap["n0"] = counters(), compiles.n
        if trace:
            time.sleep(max(t0 + seconds - trace_s - time.perf_counter(), 0))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            snap["tt0"], snap["tc0"] = time.perf_counter(), counters()
        time.sleep(max(t0 + seconds - time.perf_counter(), 0))
        snap["c1"], snap["n1"] = counters(), compiles.n
        if trace:
            snap["tt1"], snap["tc1"] = time.perf_counter(), counters()
            jax.profiler.stop_trace()

    watcher = threading.Thread(target=watch, daemon=True)
    watcher.start()
    records = load.run(reqs, t0, seconds, DRAIN_S)
    watcher.join()
    sysmod.wait_idle(engine)
    n_compiles = snap["n1"] - snap["n0"]
    late = load.lateness or [0.0]
    window = in_window(records, t0)
    print(f"window: {seconds} s, requests {len(window)} (lead-in "
          f"{len(records) - len(window)}), programs built in window "
          f"{n_compiles}, generator lateness p99 "
          f"{percentile(late, 99) * 1e3:.3f} ms max {max(late) * 1e3:.3f} ms",
          flush=True)

    mem_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devs)
    device = {"platform": devs[0].platform, "kind": kind, "count": len(devs),
              "memory_peak_bytes": int(mem_peak)}
    failed = sum(not r.ok for r in window)

    metrics, breakdown = {}, None
    if not trace:
        e2e = end_to_end(records, t0, seconds)
        e2e["setup_s"] = setup_s
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    else:
        # one read of the trace: the benchmark's own bench.* spans stay
        # the breakdown's, the program's stream.* spans go to the readers
        tr = tracemod.load(trace_dir, host_prefixes=("bench.", "stream."))
        program_spans = [e for e in tr["host"] if e[0].startswith("stream.")]
        tr["host"] = [e for e in tr["host"] if e[0].startswith("bench.")]
        if dump_trace:
            with open(dump_trace, "w") as f:
                json.dump({"summary": tracemod.summarize(trace_dir),
                           "slice": tracemod.cut(tr, 0.5, 0.02)}, f)
        shutil.rmtree(trace_dir, ignore_errors=True)
        window_s = snap["tt1"] - snap["tt0"]
        device["busy_s"] = tracemod.busy_seconds(tr)
        device["window_s"] = window_s
        breakdown = {"device_ops": tracemod.top_ops(tr),
                     "idle_gaps": tracemod.idle_gaps(tr)}
        ctx = Context(model=cfg["model"], records=records, t0=t0,
                      seconds=seconds, wall0=wall0, counters0=snap["c0"],
                      counters1=snap["c1"],
                      task_records=system.endpoint.task_records(), peaks=pk,
                      trace=tr, trace_t0=snap["tt0"], trace_t1=snap["tt1"],
                      reference=ref, trace_counters0=snap["tc0"],
                      trace_counters1=snap["tc1"])
        ctx.extra["device_busy_s"] = device["busy_s"]
        ctx.extra["window_s"] = window_s
        ctx.extra["program_spans"] = program_spans
        ctx.extra["sessions"] = list(recorder.handles.values())
        for m in cell.per_layer:
            v = metric_reader(m["name"], cell.root)(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        _log("trace lines: " + ", ".join(
            f"{k} ({len(v)})" for k, v in tr["device"].items()))
        _log(f"traced window: {window_s:.6f} s, decode ticks "
             f"{snap['tc1']['transfers'] - snap['tc0']['transfers']}, "
             f"program spans {len(program_spans)}")

    # ---------------------------------------------------------- check
    mismatch = check.stream_mismatches(records, recorder.handles)
    chk = cfg["check"]
    samples = check.choose(records, recorder.handles, seed,
                           chk["sample_tokens"], chk["max_sequences"])
    sysmod.shutdown(system)
    del system, engine, recorder.handles
    gc.collect()
    t_ref = time.perf_counter()
    t_pad = (traffic["prompt_tokens"]["max"] + traffic["output_tokens"]["max"])
    g = check.gaps(ref, params, cfg["model"], samples, t_pad,
                   chk["batch"], controls=(control,) if control else ())
    _log(f"reference: {len(samples)} requests, {len(g['served'])} served "
         f"tokens, {time.perf_counter() - t_ref:.3f} s")
    for k, v in g.items():
        _log(f"reading {k} " + json.dumps(check.summary(v)))
    judged = check.summary(g[control] if control else g["served"])
    checks = {"failed": {"value": sum(not r.ok for r in records), "limit": 0},
              "stream_mismatch": {"value": mismatch, "limit": 0}}
    for name, limit in chk["limits"].items():
        checks[name] = {"value": judged[name], "limit": limit}
    correct = (len(samples) > 0
               and all(v["value"] <= v["limit"] for v in checks.values()))
    for k, v in checks.items():
        _log(f"check {k} {v['value']} limit {v['limit']}")
    out = {"correct": bool(correct), "attempted": len(window), "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out

