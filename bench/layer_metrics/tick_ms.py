"""Batcher (serving/scheduler.py): window seconds over the window's
decode ticks (the served engine's batcher ``transfers`` counter). Serves
``tick_ms.<cell>`` of every cell."""

from benchlib.layers import tick_ms


def read(ctx):
    return tick_ms(ctx)
