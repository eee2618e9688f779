"""Batcher: share of decode-slot steps that produced a token, over the
whole window: tokens the client read from decode ticks over (ticks x slots)."""

from benchlib.layers import decode_tokens


def read(ctx):
    ticks = ctx.counters1["transfers"] - ctx.counters0["transfers"]
    slots = ctx.counters1["slots"]
    if ticks <= 0 or not slots:
        return None
    n = sum(1 for _ in decode_tokens(ctx, ctx.t0, ctx.t0 + ctx.seconds))
    return 100.0 * n / (ticks * slots)
