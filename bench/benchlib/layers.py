"""Arithmetic shared by the per-layer metric readers (``bench/layer_metrics``)."""

from __future__ import annotations

from benchlib import costs, trace as tracemod


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
    m = ctx.model
    dec = sum(costs.decode_flops(m, kv) for _, kv in decode_tokens(ctx, a, b))
    pre = sum(costs.prefill_flops(m, 0, r.req.prompt_tokens)
              for r in first_tokens(ctx, a, b))
    return dec + pre
