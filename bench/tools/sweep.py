#!/usr/bin/env python3
"""Find the highest open-loop rate a cell sustains: one build, one
warm-up, then a measured window at each rate in turn.

    python3 bench/tools/sweep.py --workload <open-loop cell> --seed <n> \
        --seconds 30 --rates 2,3,4,5

For each rate it prints one JSON line: the client-side TTFT and ITL
percentiles over the window, the rate of requests served, and whether
the backlog grew: the median TTFT of the requests due in the window's
last third against its first third, and how many requests due in the
window had no first token when it closed. A cell's rate is then fixed
in its traffic file (at about four fifths of the knee); the benchmark
never searches for one. Needs the chip, like ``bench/run.py``.
"""

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

from benchlib.registry import find_cell, reference_module  # noqa: E402


def backlog(records, t0: float, seconds: float) -> dict:
    from benchlib.stats import percentile
    from benchlib.harness import in_window
    records = in_window(records, t0)
    third = seconds / 3
    first = [r.ttft_s for r in records if r.ok and r.start < t0 + third]
    last = [r.ttft_s for r in records if r.ok and r.start >= t0 + 2 * third]
    close = t0 + seconds
    waiting = sum(1 for r in records
                  if not r.token_times or r.token_times[0] > close)
    return {"ttft_p50_first_third_ms": percentile(first, 50) * 1e3 if first else None,
            "ttft_p50_last_third_ms": percentile(last, 50) * 1e3 if last else None,
            "no_first_token_at_close": waiting}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", required=True, help="comma-separated requests/s")
    args = ap.parse_args(argv)
    cell = find_cell(args.workload)
    if cell.traffic["loop"] != "open":
        print("sweep: only an open-loop cell has a rate", file=sys.stderr)
        return 2

    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 2
    from benchlib import harness, system as sysmod
    from benchlib.load import Load
    from benchlib.traffic import generate

    harness.enable_compile_cache()
    cfg, traffic = cell.config, cell.traffic
    ref = reference_module(cfg["reference"], cell.root)
    system, engine, _ = sysmod.build(cfg, ref, args.seed, traffic["tier"])
    try:
        bearers = [system.globus.issue_token(f"user{i}@uic.edu")
                   for i in range(int(traffic["users"]))]
        failed = sysmod.warm(system, system.globus.issue_token("warmup@uic.edu"),
                             cfg, traffic, args.seed)
        if failed:
            raise RuntimeError(f"warm-up failed: {failed[:3]}")
        for rate in (float(r) for r in args.rates.split(",")):
            sysmod.wait_idle(engine)
            t = {**traffic, "rate_per_s": rate,
                 "requests": int(rate * (args.seconds + traffic.get("lead_s", 0)) * 3)
                 + traffic["stratum"]}
            load = Load(system.gateway, bearers, t, traffic["tier"])
            t0 = time.perf_counter() + 0.05 + load.lead_s
            records = load.run(generate(t, args.seed), t0, args.seconds,
                               harness.DRAIN_S)
            e2e = harness.end_to_end(records, t0, args.seconds)
            done = sum(1 for r in records
                       if r.ok and t0 <= r.done <= t0 + args.seconds)
            window = harness.in_window(records, t0)
            print(json.dumps({"rate_per_s": rate, "attempted": len(window),
                              "failed": sum(not r.ok for r in window),
                              "served_per_s": done / args.seconds,
                              **{k: e2e[k] for k in ("ttft_p50_ms", "ttft_p90_ms",
                                                     "itl_p99_ms", "output_tok_s")},
                              **backlog(records, t0, args.seconds)}), flush=True)
    finally:
        sysmod.shutdown(system)
    return 0


if __name__ == "__main__":
    sys.exit(main())
