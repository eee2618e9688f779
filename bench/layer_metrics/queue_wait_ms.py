"""Batcher (serving/broker.py, serving/scheduler.py): median time a
session of the window waited in the broker's mailbox and the batcher's
queue, from ``engine.submit`` to the pop for admission
(``SessionHandle.admitted_at - submitted_at``). Serves
``queue_wait_ms.<cell>``."""

import statistics

from benchlib.layers import window_sessions


def read(ctx):
    waits = [(h.admitted_at - h.submitted_at) * 1e3 for h in window_sessions(ctx)]
    return statistics.median(waits) if waits else None
