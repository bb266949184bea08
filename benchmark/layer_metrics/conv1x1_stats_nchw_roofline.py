"""Share of its roofline the fused conv1x1 + BN-statistics Mosaic kernel
reaches: the least time the chip could take for the 36 sites of one step
(the larger of FLOPs over peak and bytes over bandwidth, from shapes) times
the steps in the profiler's window, over the summed device time of the trace
events named ``conv1x1_stats_nchw``.  Nothing to read when the trace holds no
such event."""

from .. import flops

KERNEL = "conv1x1_stats_nchw"


def read(inputs):
    red, peaks = inputs.get("trace"), inputs["peaks"]
    steps = inputs["counters"].get("steps_traced")
    if not red or not peaks or not steps:
        return None
    spent = sum(s for name, s in red["ops"].items() if KERNEL in name)
    if spent <= 0:
        return None
    batch = inputs["facts"]["batch"] // inputs["facts"]["chips"]
    least = 0.0
    for site in flops.conv1x1_stats_sites(inputs["config"]["image_size"]):
        fl, by = flops.conv1x1_stats_flops_bytes(
            batch, site["cin"], site["cout"], site["hout"] ** 2)
        least += flops.roofline_seconds(fl, by, peaks)[0]
    return 100.0 * least * steps / spent
