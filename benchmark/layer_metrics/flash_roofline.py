"""Share of their roofline the flash attention ops reach, for whatever
configuration says what its flash kernels need: the least time the chip could
take for every flash kernel call of one sample's training step (the hook
``flash_work(config, traffic)`` of the module the configuration file names
under ``flops_module``: (FLOPs, least bytes) a call, on the live pairs only,
the backward's recomputed scores not counted; the larger of FLOPs over the
bf16 peak and bytes over the HBM bandwidth) times the samples of the traced
steps, over the device time under the program ops ``flash_attention`` and
``flash_attention_grad``.  Read by program op, not by kernel name: whatever
implements the ops is measured against the same needed work.  One reader for
every configuration (``window_attention_roofline``, ``latent_attention_
roofline`` and ``flash_attention_roofline`` are three forks of it, PERF.md
section 7 row 30).  Nothing to read where the configuration names no such
module, the module has no such hook or the trace holds no such op."""

import importlib

from .. import flops, op_scopes


def work_hook(config, name):
    """The function ``name`` of ``benchmark/<config["flops_module"]>.py``,
    None where either is missing."""
    module = config.get("flops_module")
    if not module:
        return None
    try:
        return getattr(importlib.import_module("benchmark." + module), name,
                       None)
    except ImportError:
        return None


def read(inputs):
    peaks = inputs["peaks"]
    steps = inputs["counters"].get("steps_traced")
    work = work_hook(inputs["config"], "flash_work")
    if not peaks or not steps or work is None:
        return None
    ms = op_scopes.train_ms_of_ops(inputs, ("flash_attention",))
    if not ms:
        return None
    batch = inputs["facts"]["batch"] // inputs["facts"]["chips"]
    least = batch * sum(flops.roofline_seconds(fl, by, peaks)[0]
                        for fl, by in work(inputs["config"],
                                           inputs["traffic"]))
    return 100.0 * least / (ms / 1e3)
