"""``peak_hbm_gb`` less the whole memory plan of the training step the window
ran (arguments + temporaries + outputs - aliased + code of
``paddle_tpu_step_hbm_plan_bytes{block="train"}``): the ring of batches,
fetches in flight, other loaded programs, the allocator's own."""

from ..step_plans import outside_step_gb


def read(inputs):
    return outside_step_gb(inputs)
