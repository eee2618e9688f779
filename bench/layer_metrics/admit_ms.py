"""Batcher (serving/scheduler.py): median time from a session's pop off
the queue to its first token's delivery, the whole admission: prefill
chunks interleaved with decode ticks (``SessionHandle.first_token_at -
admitted_at``). Serves ``admit_ms.<cell>``."""

import statistics

from benchlib.layers import window_sessions


def read(ctx):
    times = [(h.first_token_at - h.admitted_at) * 1e3 for h in window_sessions(ctx)]
    return statistics.median(times) if times else None
