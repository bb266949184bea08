"""Share of their roofline the expert matmuls reach: the least time the chip
could take for the nine grouped matmuls of each layer in one step (three
forward, three for the rows' gradients, three for the weights'; each the
larger of FLOPs over the bf16 peak and bytes over the HBM bandwidth, from
shapes: ``olmoe_flops.moe_experts_matmuls``) times the traced steps, over the
device time under the ``experts`` scope of ``moe_ffn`` and its grad op.  The
gate's elementwise arithmetic lies under the same scope and is counted in the
time, not in the work."""

from .. import flops, olmoe_flops, part_scopes


def read(inputs):
    parts = part_scopes.moe_seconds(inputs)
    peaks = inputs["peaks"]
    steps = inputs["counters"].get("steps_traced")
    if not parts or not peaks or not steps or not parts.get("experts"):
        return None
    c, t = inputs["config"], inputs["traffic"]
    rows = (inputs["facts"]["batch"] // inputs["facts"]["chips"]
            * t["seq_len"] * c["num_experts_per_tok"])
    least = sum(flops.roofline_seconds(fl, by, peaks)[0]
                for fl, by in olmoe_flops.moe_experts_matmuls(
                    rows, c["hidden_size"], c["intermediate_size"],
                    c["num_experts"]))
    return 100.0 * least * c["num_hidden_layers"] * steps / parts["experts"]
