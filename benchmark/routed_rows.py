"""What the program counted of the routing, for the readers that need it
(``paddle_tpu_moe_routed_rows_total{where}``: of each ExpertLoad the adapter
handed to ``ops/moe_ops.py:record_expert_load``, fetched right after the
window over the weights as the window left them, the slots on the experts
held here and all slots)."""


def held_share():
    """Of all routed slots the share on the held experts, of 1; None where
    the program has no such counter or counted nothing."""
    from paddle_tpu import monitor
    fam = monitor.REGISTRY.get("paddle_tpu_moe_routed_rows_total")
    if fam is None:
        return None
    rows = {labels.get("where"): cell.get() for labels, cell in fam.series()}
    if not rows.get("all"):
        return None
    return rows.get("held", 0.0) / rows["all"]
