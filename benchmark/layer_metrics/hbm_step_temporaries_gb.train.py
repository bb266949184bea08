"""GB of temporaries in the memory plan of the training step the window ran
(``paddle_tpu_step_hbm_plan_bytes{block="train", part="temporaries"}``):
what the forward keeps for the backward, gradients, casts, scratch; the
number that recomputation, rematerialisation and a kernel's residuals move."""

from ..step_plans import part_gb


def read(inputs):
    return part_gb(inputs, "temporaries")
