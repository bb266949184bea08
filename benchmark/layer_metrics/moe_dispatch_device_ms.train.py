"""Of ``moe_device_ms.train``, what is not the expert matmuls: the router,
the sort and the row gathers of dispatch, combine, and whatever of the op
lies under none of its parts' scopes."""

from .. import part_scopes


def read(inputs):
    parts = part_scopes.moe_seconds(inputs)
    steps = inputs["counters"].get("steps_traced")
    if not parts or not steps:
        return None
    return sum(s for p, s in parts.items() if p != "experts") / steps * 1e3
