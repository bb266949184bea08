"""Share of its roofline the chunked state-space scan reaches: the least time
the chip could take for every ``ssd_scan`` call of one sample's training step
(the hook ``ssd_work(config, seq_len)`` of the module the configuration file
names under ``flops_module``: (FLOPs, least bytes) of each Mamba-2 block's
forward and backward, the FLOPs of the chunked ALGORITHM at the
configuration's ``chunk_size``, the bytes of the op's input and output
streams and one float32 state a chunk and head; what the backward or
recomputation computes again not counted; the larger of FLOPs over the bf16
peak and bytes over the HBM bandwidth) times the samples of the traced steps,
over the device time under the program ops ``ssd_scan`` and ``ssd_scan_grad``
in any role.  Read by program op, not by kernel name: whatever implements the
op is measured against the same needed work, so a later kernel is judged by
this number.  Nothing to read where the configuration names no such module,
the module has no such hook or the trace holds no such op."""

from .. import flops, op_scopes
from .flash_roofline import work_hook


def read(inputs):
    peaks = inputs["peaks"]
    steps = inputs["counters"].get("steps_traced")
    work = work_hook(inputs["config"], "ssd_work")
    if not peaks or not steps or work is None:
        return None
    ms = op_scopes.train_ms_of_ops(inputs, ("ssd_scan",))
    if not ms:
        return None
    batch = inputs["facts"]["batch"] // inputs["facts"]["chips"]
    least = batch * sum(
        flops.roofline_seconds(fl, by, peaks)[0]
        for fl, by in work(inputs["config"], inputs["traffic"]["seq_len"]))
    return 100.0 * least / (ms / 1e3)
