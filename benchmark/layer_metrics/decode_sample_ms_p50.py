"""p50 of the ``serving.decode_iter.sample`` spans: the host's argmax over
the logits, retirements and their callbacks, between the end of one
iteration span and the point where the next iteration can be prepared."""

from ..reading import p50_ms


def read(inputs):
    return p50_ms(inputs, "serving.decode_iter.sample")
