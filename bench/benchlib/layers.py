"""Arithmetic shared by the per-layer metric readers (``bench/layer_metrics``)."""

from __future__ import annotations

from benchlib import trace as tracemod


def tick_ms(ctx):
    """Window seconds per decode tick of the served engine's batcher."""
    ticks = ctx.counters1["transfers"] - ctx.counters0["transfers"]
    return ctx.seconds / ticks * 1e3 if ticks > 0 else None


# Jitted programs are named on the "XLA Modules" line by their Python
# function: "jit_fused(<hash>)" for the batcher's fused decode tick,
# "jit_prefill_chunk(<hash>)" for a dense model's prefill chunk (one hash
# per chunk shape), "jit_prefill(<hash>)" for a recurrent model's, whose
# prefill_chunk is its prefill.
FUSED_TICK = r"^jit_fused\("
PREFILL_CHUNK = r"^jit_prefill(_chunk)?\("


def module_ms(ctx, pattern: str):
    """Mean device time of one execution of the jitted programs whose
    name matches ``pattern``, in the traced window."""
    if ctx.trace is None:
        return None
    s, n = tracemod.totals(ctx.trace, tracemod.MODULES_LINE, pattern)
    return s / n * 1e3 if n else None


def decode_tokens(ctx, a: float, b: float):
    """(record, kv_len) of every token the client read in [a, b) that a
    decode tick produced (not an admission's first token): its query
    saw the prompt and every earlier output token."""
    for r, i in ctx.tokens_between(a, b):
        if i > 0:
            yield r, r.req.prompt_tokens + i


def first_tokens(ctx, a: float, b: float):
    """Records whose first token the client read in [a, b)."""
    for r, i in ctx.tokens_between(a, b):
        if i == 0:
            yield r


def model_flops(ctx, a: float, b: float) -> float:
    """Model operations of the tokens the client read in [a, b): each
    decoded token at its kv length and each prompt's prefill, counted by
    the configuration's reference module (``decode_flops``,
    ``prefill_flops``), so a new model family brings its own counts."""
    m, ref = ctx.model, ctx.reference
    dec = sum(ref.decode_flops(m, kv) for _, kv in decode_tokens(ctx, a, b))
    pre = sum(ref.prefill_flops(m, 0, r.req.prompt_tokens)
              for r in first_tokens(ctx, a, b))
    return dec + pre


# ---------------------------------------------------------------- program spans
# The program's own host spans (``ctx.extra["program_spans"]``, the
# traced run's ``stream.*`` events: [name, start_ns, dur_ns]) and session
# stamps (``ctx.extra["sessions"]``). Per-tick figures divide by the
# decode ticks counted over the same traced seconds
# (``trace_counters0``/``trace_counters1``).

def spans_named(ctx, name: str) -> list:
    """[start_ns, dur_ns] of every program span called ``name`` in the
    traced window; empty when the run was not traced."""
    return [[s, d] for n, s, d in ctx.extra.get("program_spans", ()) if n == name]


def traced_ticks(ctx) -> int:
    """Decode ticks of the served engine's batcher in the traced window."""
    c0, c1 = ctx.trace_counters0, ctx.trace_counters1
    return c1["transfers"] - c0["transfers"] if c0 and c1 else 0


def span_ms_per_tick(ctx, name: str):
    """Time inside spans ``name`` over the traced window's decode ticks."""
    spans, ticks = spans_named(ctx, name), traced_ticks(ctx)
    if not spans or ticks <= 0:
        return None
    return sum(d for _, d in spans) / 1e6 / ticks


def span_mean_us(ctx, name: str):
    """Mean duration of one span ``name`` in the traced window."""
    spans = spans_named(ctx, name)
    return sum(d for _, d in spans) / len(spans) / 1e3 if spans else None


def window_sessions(ctx) -> list:
    """The served engine's sessions submitted inside the window, with
    their stamps set (on the ``perf_counter`` clock of ``ctx.t0``)."""
    a, b = ctx.t0, ctx.t0 + ctx.seconds
    return [h for h in ctx.extra.get("sessions", ())
            if a <= h.submitted_at < b and h.admitted_at is not None
            and h.first_token_at is not None]


def _overlap(xs, ys) -> float:
    """Total length shared by two sorted lists of disjoint intervals."""
    i = j = 0
    total = 0.0
    while i < len(xs) and j < len(ys):
        lo, hi = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        total += max(hi - lo, 0)
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_share_inside(ctx, name: str):
    """Share of the device's idle gaps (between the traced window's busy
    intervals) that lies inside program spans ``name``, in percent."""
    if ctx.trace is None:
        return None
    busy = tracemod.device_intervals(ctx.trace)
    gaps = [[a, b] for (_, a), (b, _) in zip(busy, busy[1:]) if b > a]
    idle = sum(b - a for a, b in gaps)
    spans = tracemod.merge([[s, s + d] for s, d in spans_named(ctx, name)])
    if idle <= 0 or not spans:
        return None
    return 100.0 * _overlap(gaps, spans) / idle
