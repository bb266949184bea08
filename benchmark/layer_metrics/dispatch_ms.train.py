"""p50 of the executor's own ``executor.dispatch`` spans in the window."""

from ..reading import p50_ms


def read(inputs):
    return p50_ms(inputs, "executor.dispatch")
