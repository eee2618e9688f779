"""Batcher (serving/scheduler.py): share of the device's idle gaps in the traced
window that lie inside ``stream.batcher.emit`` spans, where the broker
thread delivers and encrypts tokens while the chip waits for the next
tick. Serves ``idle_emit_share.<cell>``."""

from benchlib.layers import idle_share_inside


def read(ctx):
    return idle_share_inside(ctx, "stream.batcher.emit")
