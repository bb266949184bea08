"""Seconds of the training step's first call between the end of lowering and
the end of JAX's backend-compile event: cache-key hashing, the persistent
cache's read or XLA's compile, from
``paddle_tpu_compile_phase_seconds{phase="backend",block="train"}``."""

from ..program_counters import compile_phase_seconds


def read(inputs):
    return compile_phase_seconds(inputs, ("backend",))
