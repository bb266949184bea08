"""Readers of what the program keeps in its own metrics registry
(``paddle_tpu.monitor.REGISTRY``) for the per-layer metrics whose source is
``program_counter`` and whose spans the window's opening has already cleared
from the tracer ring."""

from __future__ import annotations

from typing import Optional, Sequence

COMPILE_PHASES = "paddle_tpu_compile_phase_seconds"


def compile_phase_seconds(inputs: dict, phases: Sequence[str],
                          block: str = "train") -> Optional[float]:
    """Seconds the executor spent in ``phases`` of the first call (or, for
    ``retrace``, of a later call) of the blocks of kind ``block``, from the
    histogram's sums in this process.  None where the run reports no set-up
    to move, where the program has no such histogram (a commit before PR 24)
    or where no such block compiled."""
    if "setup_s" not in inputs.get("e2e", {}):
        return None
    from paddle_tpu import monitor
    fam = monitor.REGISTRY.get(COMPILE_PHASES)
    if fam is None:
        return None
    compiled, total = 0, 0.0
    for labels, cell in fam.series():
        if labels.get("block") != block:
            continue
        _, seconds, count = cell.snapshot()
        compiled += count
        if labels.get("phase") in phases:
            total += seconds
    return total if compiled else None
