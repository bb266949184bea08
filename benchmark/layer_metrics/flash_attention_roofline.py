"""Share of their roofline the flash attention kernels reach: the least time
the chip could take for the causal forward and backward of every layer in
one step (``olmoe_flops.flash_attention_kernels``: needed FLOPs on the causal
half, the backward's recomputed scores not counted) times the traced steps,
over the summed device time of the trace events named ``*flash_fwd*`` and
``*flash_bwd*``.  Nothing to read when the trace holds no such event."""

from .. import flops, olmoe_flops

KERNELS = ("flash_fwd", "flash_bwd")


def read(inputs):
    red, peaks = inputs.get("trace"), inputs["peaks"]
    steps = inputs["counters"].get("steps_traced")
    if not red or not peaks or not steps:
        return None
    spent = sum(s for name, s in red["ops"].items()
                if any(k in name for k in KERNELS))
    if spent <= 0:
        return None
    c, t = inputs["config"], inputs["traffic"]
    batch = inputs["facts"]["batch"] // inputs["facts"]["chips"]
    least = sum(flops.roofline_seconds(fl, by, peaks)[0]
                for fl, by in olmoe_flops.flash_attention_kernels(
                    batch * c["num_attention_heads"], t["seq_len"],
                    c["hidden_size"] // c["num_attention_heads"]))
    return 100.0 * least * c["num_hidden_layers"] * steps / spent
