"""Backend compiles of training blocks in this process that the persistent
compile cache did not serve
(``paddle_tpu_compile_total{persist="miss", block="train"}``, fed by
``jax.monitoring``'s cache events): 0 in a run beside a warm cache, the number
of the step's compiles in a cold one."""

from ..step_plans import cache_misses


def read(inputs):
    return cache_misses(inputs)
