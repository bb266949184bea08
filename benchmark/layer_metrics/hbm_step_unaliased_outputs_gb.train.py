"""GB of outputs that donation did not put in an argument's buffer, in the
memory plan of the training step the window ran (``part="outputs"`` less
``part="aliased"`` of ``paddle_tpu_step_hbm_plan_bytes{block="train"}``): the
fetches, the probe; a step that returns its state beside its state shows
here first."""

from ..step_plans import unaliased_outputs_gb


def read(inputs):
    return unaliased_outputs_gb(inputs)
