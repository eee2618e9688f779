"""Batcher (serving/scheduler.py): time in ``stream.batcher.decode`` spans
(the fused tick's dispatch through its one token transfer) per decode
tick over the traced seconds. Serves ``tick_decode_ms.<cell>``."""

from benchlib.layers import span_ms_per_tick


def read(ctx):
    return span_ms_per_tick(ctx, "stream.batcher.decode")
