"""Model step (models/*.py via the batcher's jitted ``prefill_chunk``):
mean device time of one prefill-chunk execution in the traced window.
Serves ``prefill_chunk_ms.<cell>`` of every cell."""

from benchlib.layers import PREFILL_CHUNK, module_ms


def read(ctx):
    return module_ms(ctx, PREFILL_CHUNK)
