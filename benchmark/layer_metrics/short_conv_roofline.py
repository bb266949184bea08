"""Share of its roofline the gated short convolution reaches: the least time
the chip could take for every ``short_conv`` call of one sample's training
step (the hook ``short_conv_work(config, traffic)`` of the module the
configuration file names under ``flops_module``: (FLOPs, least bytes) of each
conv layer's forward and backward — the gates, the taps, their backward; the
two projections not counted, nor what the backward or recomputation computes
again; the larger of FLOPs over the bf16 peak and bytes over the HBM
bandwidth, and it is the bytes: three [T, d] streams in and one out, seven in
the backward) times the samples of the traced steps, over the device time
under the program ops ``short_conv`` and ``short_conv_grad`` in any role.
Read by program op, not by kernel name: whatever implements the op (jnp that
XLA fuses, or a kernel) is measured against the same needed work.  Nothing to
read where the configuration names no such module, the module has no such
hook or the trace holds no such op."""

from .. import flops, op_scopes
from .flash_roofline import work_hook


def read(inputs):
    peaks = inputs["peaks"]
    steps = inputs["counters"].get("steps_traced")
    work = work_hook(inputs["config"], "short_conv_work")
    if not peaks or not steps or work is None:
        return None
    ms = op_scopes.train_ms_of_ops(inputs, ("short_conv",))
    if not ms:
        return None
    batch = inputs["facts"]["batch"] // inputs["facts"]["chips"]
    least = batch * sum(flops.roofline_seconds(fl, by, peaks)[0]
                        for fl, by in work(inputs["config"],
                                           inputs["traffic"]))
    return 100.0 * least / (ms / 1e3)
