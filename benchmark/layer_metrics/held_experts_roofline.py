"""Share of their roofline the held experts' grouped matmuls reach, for
whatever configuration says what they need: the least time the chip could
take for every grouped matmul of one sample's training step over the rows
routed to the experts held here (the hook ``held_experts_work(config,
traffic, rows_share)`` of the module the configuration file names under
``flops_module``; ``rows_share``: of all routed slots the share that the
program counted on the held experts, ``routed_rows.held_share``, what
``moe_local_rows_share`` reports in percent, fetched right after the
window, or None, even routing's, where it counted nothing) times the samples
of the traced steps, over the device time under the ``experts`` scope of
``moe_ffn`` and its grad op.  The static buffer behind the held rows is time,
not work.  One reader for every configuration (``moe_share_experts_roofline``
and ``moe_experts_roofline`` are forks of it, PERF.md section 7 row 30).
Nothing to read where the configuration names no such module, the module has
no such hook or the trace holds no ``experts`` scope."""

from .. import flops, part_scopes, routed_rows
from .flash_roofline import work_hook


def read(inputs):
    peaks = inputs["peaks"]
    steps = inputs["counters"].get("steps_traced")
    work = work_hook(inputs["config"], "held_experts_work")
    if not peaks or not steps or work is None:
        return None
    parts = part_scopes.moe_seconds(inputs)
    if not parts or not parts.get("experts"):
        return None
    batch = inputs["facts"]["batch"] // inputs["facts"]["chips"]
    least = batch * sum(flops.roofline_seconds(fl, by, peaks)[0]
                        for fl, by in work(inputs["config"],
                                           inputs["traffic"],
                                           routed_rows.held_share()))
    return 100.0 * least * steps / parts["experts"]
