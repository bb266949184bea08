"""p50 of the requests' ``queue_wait`` phase (enqueue to slot)."""

from ..reading import p50_ms


def read(inputs):
    return p50_ms(inputs, "serving.queue_wait")
