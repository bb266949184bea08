"""Of all the routed slots (tokens x experts per token, summed over the
expert layers), the share that landed on the experts this chip holds:
``paddle_tpu_moe_routed_rows_total{where="held"}`` over ``{where="all"}``,
which the program counts from each ExpertLoad handed to it.  The adapter
hands over one load a layer, fetched right after the window with the weights
as the window left them (the routing of its last steps, the traced ones; a
forward-only program, never inside the window).  12.5 is even routing over
16 of 128; the router is trained, so the share moves during a run.  It
describes the traffic the experts saw and has no better direction of its
own (``BENCHMARK.json`` wants one named): it says how many rows
``moe_share_experts_roofline`` may count as work.  Nothing to read where the
program has no such counter or counted nothing."""


def read(inputs):
    from paddle_tpu import monitor
    fam = monitor.REGISTRY.get("paddle_tpu_moe_routed_rows_total")
    if fam is None:
        return None
    rows = {labels.get("where"): cell.get() for labels, cell in fam.series()}
    if not rows.get("all"):
        return None
    return 100.0 * rows.get("held", 0.0) / rows["all"]
