"""Share of their roofline the held experts' matmuls reach: the least time
the chip could take for the nine grouped matmuls of each expert layer in one
step over the rows routed to the experts held here
(``trinity_flops.held_experts_matmuls``; the rows are the share the program
counted right after the window, ``moe_local_rows_share``, of all slots, so
the rows the traced steps were given, or even routing's where it counted
nothing) times the traced steps, over the device time under the
``experts`` scope of ``moe_ffn`` and its grad op.  The static buffer behind
the held rows is time, not work.  Nothing to read for a configuration
without ``assumed.router_outputs`` (every expert held: ``moe_experts_
roofline``)."""

import importlib

from .. import flops, part_scopes, trinity_flops


def read(inputs):
    c, t = inputs["config"], inputs["traffic"]
    routed_over = c.get("assumed", {}).get("router_outputs")
    parts = part_scopes.moe_seconds(inputs)
    peaks = inputs["peaks"]
    steps = inputs["counters"].get("steps_traced")
    if not routed_over or not parts or not peaks or not steps \
            or not parts.get("experts"):
        return None
    share = importlib.import_module(
        "benchmark.layer_metrics.moe_local_rows_share").read(inputs)
    share = c["num_experts"] / float(routed_over) if share is None \
        else share / 100.0
    rows = (inputs["facts"]["batch"] // inputs["facts"]["chips"]
            * t["seq_len"] * c["num_experts_per_tok"] * share)
    least = sum(flops.roofline_seconds(fl, by, peaks)[0]
                for fl, by in trinity_flops.held_experts_matmuls(
                    rows, c["hidden_size"], c["moe_intermediate_size"],
                    c["num_experts"]))
    layers = c["num_hidden_layers"] - c["num_dense_layers"]
    return 100.0 * least * layers * steps / parts["experts"]
