#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (configuration x traffic) is looked up by name in
``BENCHMARK.json``. With ``--trace 0`` the last line of standard output
reports the cell's end-to-end metrics; with ``--trace 1`` its per-layer
metrics, read from a profiler trace of the window's last seconds. Either
way the run checks what the timed path produced against the plain
reference, and prints each number compared beside its limit as the last
lines of standard error. Without a TPU, or with fewer chips than the
cell asks for, it exits non-zero and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

from benchlib.registry import find_cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default=None, choices=("fp8",),
                    help="judge the plain reference in this lower precision in "
                         "the program's place: a control run, which must come "
                         "out not correct (for setting and proving limits)")
    ap.add_argument("--dump-trace", default=None,
                    help="with --trace 1: write a summary of the profile here")
    args = ap.parse_args(argv)
    cell = find_cell(args.workload)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"bench: cell {cell.name} needs {cell.chips} TPU chip(s); "
              f"found {len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return 2

    from benchlib.harness import run_cell
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      t_start=T_START,
                      control=args.control,
                      dump_trace=args.dump_trace)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
