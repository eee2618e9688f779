"""Batcher (serving/scheduler.py): time in ``stream.batcher.admit`` spans
(admission prefill chunks and splices) per decode tick over the traced
seconds. Serves ``tick_admit_ms.<cell>``."""

from benchlib.layers import span_ms_per_tick


def read(ctx):
    return span_ms_per_tick(ctx, "stream.batcher.admit")
