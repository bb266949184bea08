"""p50 of the ``serving.decode_step.logits_to_host`` spans (a child of
``serving.decode_iter``; see ``DecodeScheduler._emit_step_phases``)."""

from ..reading import p50_ms


def read(inputs):
    return p50_ms(inputs, "serving.decode_step.logits_to_host")
