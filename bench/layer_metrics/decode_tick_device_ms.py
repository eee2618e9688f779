"""Model step (the batcher's jitted fused decode tick): mean device time
of one tick execution in the traced window."""

from benchlib.layers import FUSED_TICK, module_ms


def read(ctx):
    return module_ms(ctx, FUSED_TICK)
