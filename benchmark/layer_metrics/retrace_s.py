"""Seconds JAX spent tracing, lowering and compiling again inside later
dispatches of a training block the executor had compiled already (the
arguments kept their shapes and changed layout or sharding), from
``paddle_tpu_compile_phase_seconds{phase="retrace",block="train"}``: 0 where
the step compiled once."""

from ..program_counters import compile_phase_seconds


def read(inputs):
    return compile_phase_seconds(inputs, ("retrace",))
