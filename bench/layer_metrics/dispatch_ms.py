"""Control plane (core/control_plane.py): median wait of the window's
tasks from submission to a worker starting them (the simulated dispatch
latency plus any wait for a free worker), from the endpoint's task records."""

import statistics


def read(ctx):
    w0, w1 = ctx.wall0, ctx.wall0 + ctx.seconds
    waits = [(t.started_at - t.submitted_at) * 1e3 for t in ctx.task_records
             if t.started_at is not None and w0 <= t.submitted_at < w1]
    return statistics.median(waits) if waits else None
