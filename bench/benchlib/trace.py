"""Reduction of a profiler trace to device busy time, per-program and
per-op device time, and idle gaps attributed to host spans.

``load`` turns the profiler's ``.xplane.pb`` into a plain dict, so the
reduction below runs the same on a trace read from disk and on a small
recorded fixture:

    {"device": {line_name: [[name, start_ns, dur_ns], ...]},   # first chip
     "host":   [[name, start_ns, dur_ns], ...]}                # all threads

Device lines are those of the first ``/device:TPU:<n>`` plane (op events
on "XLA Ops", program executions on "XLA Modules"). Host events are
kept when their name starts with one of ``host_prefixes`` (the
benchmark's own spans), so the dict stays small.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def load(log_dir: str, host_prefixes=("bench.",), device_prefix="/device:TPU:"):
    import jax
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = jax.profiler.ProfileData.from_file(paths[-1])
    dev_planes = sorted((p for p in pd.planes if p.name.startswith(device_prefix)
                         and p.name[len(device_prefix):].isdigit()),
                        key=lambda p: int(p.name[len(device_prefix):]))
    out = {"device": {}, "host": [], "planes": [p.name for p in pd.planes]}
    if dev_planes:
        for line in dev_planes[0].lines:
            out["device"][line.name] = [[e.name, e.start_ns, e.duration_ns]
                                        for e in line.events]
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(host_prefixes):
                        out["host"].append([e.name, e.start_ns, e.duration_ns])
    return out


def summarize(log_dir: str, top: int = 40) -> dict:
    """What a trace holds, for reading by hand: every plane with its
    lines, and for each line of the first TPU plane (and the host's
    ``bench.`` spans) the event count and the names that took the most
    time, with a few events' stats."""
    import jax
    path = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    pd = jax.profiler.ProfileData.from_file(path)
    out = {"planes": {}}
    for plane in pd.planes:
        lines = {}
        for line in plane.lines:
            evs = list(line.events)
            by = {}
            for e in evs:
                by[e.name] = by.get(e.name, 0) + e.duration_ns
            lines[line.name] = {
                "events": len(evs),
                "top_ns": sorted(by.items(), key=lambda x: -x[1])[:top],
                "sample": [{"name": e.name, "start_ns": e.start_ns,
                            "duration_ns": e.duration_ns,
                            "stats": [[str(k), str(v)] for k, v in e.stats]}
                           for e in evs[:3]]}
        out["planes"][plane.name] = lines
    return out


def cut(tr: dict, at: float, seconds: float) -> dict:
    """The part of a loaded trace that lies in ``seconds`` starting at
    fraction ``at`` of its device span (a small recorded fixture)."""
    ops = tr["device"].get(OPS_LINE, [])
    if not ops:
        return {"device": {}, "host": []}
    t0 = min(s for _, s, _ in ops)
    t1 = max(s + d for _, s, d in ops)
    a = t0 + at * (t1 - t0)
    b = a + seconds * 1e9
    keep = lambda evs: [e for e in evs if a <= e[1] and e[1] + e[2] <= b]
    return {"device": {k: keep(v) for k, v in tr["device"].items()},
            "host": keep(tr["host"])}


def merge(intervals):
    """Union of [start, end) intervals, sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def device_intervals(tr: dict):
    line = tr["device"].get(OPS_LINE) or tr["device"].get(MODULES_LINE) or []
    return merge([[s, s + d] for _, s, d in line])


def busy_seconds(tr: dict) -> float:
    return sum(e - s for s, e in device_intervals(tr)) / 1e9


def totals(tr: dict, line: str, pattern=None) -> tuple[float, int]:
    """(seconds, count) of the events on device ``line`` whose name
    matches the regular expression ``pattern`` (every event when None)."""
    rx = re.compile(pattern) if pattern is not None else None
    evs = [(s, d) for n, s, d in tr["device"].get(line, [])
           if rx is None or rx.search(n)]
    return sum(d for _, d in evs) / 1e9, len(evs)


# An op on the "XLA Ops" line is named by its HLO instruction, e.g.
# "%fusion.139 = bf16[16,16384]{...} fusion(...), kind=..., calls=...".
# Control flow ops (a scanned layer stack is a while loop) hold the ops
# they run, which have events of their own.
_CONTAINERS = {"while", "conditional", "call"}


def op_parts(name: str) -> tuple[str, str, str]:
    """(instruction, opcode, result shape) of an HLO op's trace name."""
    inst, _, rest = name.partition(" = ")
    if rest.startswith("("):                      # tuple-shaped result
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        shape, rest = rest[:i + 1], rest[i + 1:].lstrip()
    else:
        shape, _, rest = rest.partition(" ")
    return inst, rest.split("(", 1)[0], re.sub(r"\{[^}]*\}", "", shape)


def top_ops(tr: dict, k: int = 10) -> list:
    """The ``k`` device ops with the most total time, leaving out the
    control-flow ops that hold others: [["%inst opcode shape", seconds]]."""
    by = {}
    for n, _, d in tr["device"].get(OPS_LINE, []):
        inst, opcode, shape = op_parts(n)
        if opcode in _CONTAINERS:
            continue
        key = f"{inst} {opcode} {shape}"[:160]
        by[key] = by.get(key, 0) + d
    return [[n, t / 1e9] for n, t in sorted(by.items(), key=lambda x: -x[1])[:k]]


def idle_gaps(tr: dict, k: int = 10) -> list:
    """Device idle time between busy intervals, by what the host was
    doing: each instant of a gap goes to the innermost of the host spans
    open then (the one opened last), or to "unattributed" when none is.
    Summed by span name: [[name, seconds]], largest first."""
    busy = device_intervals(tr)
    host = sorted(([s, s + d, n] for n, s, d in tr["host"]), key=lambda x: x[0])
    starts = [h[0] for h in host]
    longest = max((h[1] - h[0] for h in host), default=0)
    by = {}
    for (_, a), (b, _) in zip(busy, busy[1:]):
        if b <= a:
            continue
        lo = bisect.bisect_left(starts, a - longest)
        hi = bisect.bisect_right(starts, b)
        spans = [h for h in host[lo:hi] if h[1] > a and h[0] < b]
        cuts = sorted({a, b, *(min(max(h[0], a), b) for h in spans),
                       *(min(max(h[1], a), b) for h in spans)})
        for x, y in zip(cuts, cuts[1:]):
            open_ = [h for h in spans if h[0] <= x and h[1] >= y]
            name = max(open_, key=lambda h: h[0])[2] if open_ else "unattributed"
            by[name] = by.get(name, 0) + (y - x)
    return [[n, t / 1e9] for n, t in sorted(by.items(), key=lambda x: -x[1])[:k]]
