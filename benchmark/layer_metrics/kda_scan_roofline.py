"""Share of its roofline the chunked gated delta rule reaches: the least time
the chip could take for every ``kda_scan`` call of one sample's training step
(the hook ``kda_work(config, seq_len, chunk)`` of the module the
configuration file names under ``flops_module``: (FLOPs, least bytes) of each
KDA layer's forward and backward at the heads held, from the op's input and
output streams and one float32 state a chunk and head; what the backward or
recomputation computes again not counted; the larger of FLOPs over the bf16
peak and bytes over the HBM bandwidth, and it is the bytes) times the samples
of the traced steps, over the device time under the program ops ``kda_scan``
and ``kda_scan_grad`` in any role.  Read by program op, not by kernel name:
whatever implements the op is measured against the same needed work, so a
later kernel is judged by this number.  Nothing to read where the
configuration names no such module, the module has no such hook or the trace
holds no such op."""

from .. import flops, op_scopes
from .flash_roofline import work_hook


def read(inputs):
    peaks = inputs["peaks"]
    steps = inputs["counters"].get("steps_traced")
    work = work_hook(inputs["config"], "kda_work")
    if not peaks or not steps or work is None:
        return None
    ms = op_scopes.train_ms_of_ops(inputs, ("kda_scan",))
    if not ms:
        return None
    batch = inputs["facts"]["batch"] // inputs["facts"]["chips"]
    chunk = inputs["config"].get("assumed", {}).get("kda_chunk", 64)
    least = batch * sum(
        flops.roofline_seconds(fl, by, peaks)[0]
        for fl, by in work(inputs["config"], inputs["traffic"]["seq_len"],
                           chunk))
    return 100.0 * least / (ms / 1e3)
