"""Device-busy time (union of the operation intervals on the device plane,
mean over chips) of the profiler's window per training step dispatched in
it."""

from ..reading import busy_per


def read(inputs):
    return busy_per(inputs, inputs["counters"].get("steps_traced"))
