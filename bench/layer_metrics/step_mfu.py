"""Model step, whole: model operations of the decode ticks and prompt
prefills in the traced window (counted by the configuration's reference
module: ``benchlib.layers.model_flops``) over the device time
of the fused-tick and prefill-chunk programs, as a share of the chip's
bf16 peak."""

from benchlib import trace as tracemod
from benchlib.layers import FUSED_TICK, PREFILL_CHUNK, model_flops


def read(ctx):
    if ctx.trace is None or not ctx.peaks:
        return None
    t = sum(tracemod.totals(ctx.trace, tracemod.MODULES_LINE, m)[0]
            for m in (FUSED_TICK, PREFILL_CHUNK))
    flops = model_flops(ctx, ctx.trace_t0, ctx.trace_t1)
    if t <= 0 or flops <= 0:
        return None
    return 100.0 * flops / t / ctx.peaks["bf16_flops_s"]
