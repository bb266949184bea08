"""Share of their roofline the hyper-connection ops reach: the least time the
chip could take for every ``hc_pre`` and ``hc_post`` call of one sample's
training step, forward and backward (the hook ``hc_work(config, traffic)`` of
the module the configuration file names under ``flops_module``: (FLOPs, least
bytes) of each call; the larger of FLOPs over the bf16 peak and bytes over
the HBM bandwidth, and it is the bytes: one read of the widened stream and a
sublayer-wide write for ``hc_pre``, a read and a write of the stream for
``hc_post``, twice that backward; what the backward or recomputation
computes again not counted) times the samples of the traced steps, over the
device time under the four program ops in any role.  Read by program op, not
by kernel name: whatever implements the ops (jnp that XLA fuses, or a kernel)
is measured against the same needed work.  Nothing to read where the
configuration has no ``hc_mult``, names no such module, the module has no
such hook or the trace holds no such op."""

from .. import flops, op_scopes
from .flash_roofline import work_hook


def read(inputs):
    peaks, c = inputs["peaks"], inputs["config"]
    steps = inputs["counters"].get("steps_traced")
    work = work_hook(c, "hc_work")
    if not peaks or not steps or work is None or "hc_mult" not in c:
        return None
    ms = op_scopes.train_ms_of_ops(inputs, ("hc_pre", "hc_post"))
    if not ms:
        return None
    batch = inputs["facts"]["batch"] // inputs["facts"]["chips"]
    least = batch * sum(flops.roofline_seconds(fl, by, peaks)[0]
                        for fl, by in work(c, inputs["traffic"]))
    return 100.0 * least / (ms / 1e3)
