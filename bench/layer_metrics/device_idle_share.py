"""Device: share of the traced window in which no operation ran on the
chip (1 - union of device-op intervals / window). Nothing to read when
the trace holds no device operation. Serves ``device_idle_share.<cell>``
of every cell."""


def read(ctx):
    w, busy = ctx.extra.get("window_s"), ctx.extra.get("device_busy_s")
    if not w or not busy:
        return None
    return 100.0 * (1.0 - busy / w)
