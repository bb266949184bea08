"""Device-busy time of the profiler's window per decode iteration that
ended in it."""

from ..reading import busy_per, in_trace_window


def read(inputs):
    return busy_per(inputs, in_trace_window(inputs, "serving.decode_iter"))
