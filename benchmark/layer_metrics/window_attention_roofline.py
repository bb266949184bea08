"""Share of their roofline the flash attention ops of a model with window and
full layers mixed reach: the least time the chip could take for each layer's
forward and backward on its live band (a full layer's is the causal half;
``trinity_flops.flash_layer_kernels``: the larger of FLOPs over the bf16 peak
and bytes over the HBM bandwidth, the backward's recomputed scores not
counted) times the traced steps, over the device time under the program ops
``flash_attention`` and ``flash_attention_grad``.  Read by program op, not by
kernel name (PERF.md section 4): whatever implements the ops is measured
against the same needed work.  Nothing to read where the configuration has
no ``layer_types`` or the trace holds no such op."""

from .. import flops, op_scopes, trinity_flops


def read(inputs):
    peaks, c = inputs["peaks"], inputs["config"]
    steps = inputs["counters"].get("steps_traced")
    if not peaks or not steps or "layer_types" not in c:
        return None
    ms = op_scopes.train_ms_of_ops(inputs, ("flash_attention",))
    if not ms:
        return None
    batch = inputs["facts"]["batch"] // inputs["facts"]["chips"]
    least = batch * sum(
        flops.roofline_seconds(fl, by, peaks)[0]
        for layer in trinity_flops.flash_kernels_of_model(
            c, inputs["traffic"]["seq_len"]) for fl, by in layer)
    return 100.0 * least / (ms / 1e3)
