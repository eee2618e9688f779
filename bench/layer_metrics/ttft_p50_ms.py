"""The window's `ttft_p50_ms` as the clients read it, for a cell where that tail
swings too widely run to run to carry a bound (a closed loop always at
capacity, or a 90th percentile of a few tens of requests) and so stands
beside the cell's bounded end-to-end metric. Serves `ttft_p50_ms.<cell>`."""

from benchlib.harness import end_to_end


def read(ctx):
    return end_to_end(ctx.records, ctx.t0, ctx.seconds)["ttft_p50_ms"] if ctx.records else None
