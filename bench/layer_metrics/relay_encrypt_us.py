"""Relay and crypto (core/data_plane.py): mean time of one
``stream.relay.encrypt`` span, the producer's AES-GCM sealing and send of
one relay message on the broker thread, in the traced window."""

from benchlib.layers import span_mean_us


def read(ctx):
    return span_mean_us(ctx, "stream.relay.encrypt")
