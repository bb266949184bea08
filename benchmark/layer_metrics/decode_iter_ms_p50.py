"""p50 of the ``serving.decode_iter`` spans (host side of one step)."""

from ..reading import p50_ms


def read(inputs):
    return p50_ms(inputs, "serving.decode_iter")
