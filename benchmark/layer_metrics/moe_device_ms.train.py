"""Device milliseconds per traced step under ``moe_ffn`` and its grad op:
router, dispatch, the expert matmuls and combine, forward and backward."""

from .. import part_scopes


def read(inputs):
    parts = part_scopes.moe_seconds(inputs)
    steps = inputs["counters"].get("steps_traced")
    if not parts or not steps:
        return None
    return sum(parts.values()) / steps * 1e3
