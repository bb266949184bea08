"""GB of arguments in the memory plan of the training step the window ran
(``paddle_tpu_step_hbm_plan_bytes{block="train", part="arguments"}``): the
state the step takes and gives back in place, and one batch."""

from ..step_plans import part_gb


def read(inputs):
    return part_gb(inputs, "arguments")
