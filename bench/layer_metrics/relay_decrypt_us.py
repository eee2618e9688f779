"""Relay and crypto (core/data_plane.py): mean time of one
``stream.relay.decrypt`` span, the consumer's opening and checking of one
relay message (the wait in ``recv`` left out), in the traced window."""

from benchlib.layers import span_mean_us


def read(ctx):
    return span_mean_us(ctx, "stream.relay.decrypt")
