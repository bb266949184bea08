"""Seconds of the training step's first call that are the program's own:
``prepare`` (run() entry to the jit call: fusion and verification passes,
persistable classification, feed staging), ``trace`` (the block's ops
lowered to a jaxpr), ``lower`` (jaxpr to MLIR) and ``first_run`` (the rest
of the call), from ``paddle_tpu_compile_phase_seconds{block="train"}``.
With ``first_step_backend_s`` it is the harness's "compile or cache load +
first step" phase less the first step's own device time."""

from ..program_counters import compile_phase_seconds


def read(inputs):
    return compile_phase_seconds(
        inputs, ("prepare", "trace", "lower", "first_run"))
