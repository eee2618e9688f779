"""Batcher (serving/scheduler.py): time in ``stream.batcher.emit`` spans
(token delivery, relay encryption, reaping) per decode tick over the
traced seconds. Serves ``tick_emit_ms.<cell>``."""

from benchlib.layers import span_ms_per_tick


def read(ctx):
    return span_ms_per_tick(ctx, "stream.batcher.emit")
