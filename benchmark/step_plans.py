"""Readers of the compiled blocks' memory plans and compile outcomes that the
program keeps in its metrics registry since PR 51
(``paddle_tpu_step_hbm_plan_bytes{block, tag, part}``, set where a block
compiles, with ``paddle_tpu_step_hbm_plan_compiled_at_seconds{block, tag}``
beside it, and ``paddle_tpu_compile_total{persist, block}``), for the
per-layer metrics that move ``peak_hbm_gb`` and ``setup_s``."""

from __future__ import annotations

from typing import Dict, Optional

PLAN_BYTES = "paddle_tpu_step_hbm_plan_bytes"
PLAN_COMPILED_AT = "paddle_tpu_step_hbm_plan_compiled_at_seconds"
COMPILES = "paddle_tpu_compile_total"
PARTS = ("arguments", "outputs", "aliased", "temporaries", "code")


def window_plan(inputs: dict, block: str = "train") -> Optional[Dict[str, float]]:
    """Bytes by part of the plan the traced window ran: of the blocks of
    kind ``block``, the one compiled last before the profiler's window opened
    (both times are ``perf_counter``).  Not the newest of the process: checks
    after the window compile further blocks, and a data-parallel step runs
    its second compile.  None where the run reports no ``peak_hbm_gb`` to
    split, off the chip (no peaks: as a device metric), where the program
    keeps no such family (a commit before PR 51) and where no such block
    compiled before a window that other blocks did precede."""
    if "peak_hbm_gb" not in inputs.get("e2e", {}) or not inputs.get("peaks"):
        return None
    window = inputs.get("trace_window")
    if not window or window[0] is None:
        return None
    from paddle_tpu import memory, monitor
    memory.hbm_plans()      # a plan the program deferred is recorded now
    plans = monitor.REGISTRY.get(PLAN_BYTES)
    times = monitor.REGISTRY.get(PLAN_COMPILED_AT)
    if plans is None or times is None:
        return None
    compiled = [(cell.get(), labels) for labels, cell in times.series()]
    train = [(at, labels["tag"]) for at, labels in compiled
             if labels.get("block") == block]
    before = [p for p in train if p[0] <= window[0]]
    if not before and train and window[1] is not None and \
            window[1] < min(at for at, _ in compiled):
        # the window closed before this process compiled anything at all: it
        # is not on this process's clock (a recorded trace handed to the
        # readers by a test), and the newest such block stands in
        before = train
    if not before:
        return None
    newest = max(before)
    parts = {labels["part"]: float(cell.get())
             for labels, cell in plans.series()
             if labels.get("block") == block and labels["tag"] == newest[1]}
    return parts if all(p in parts for p in PARTS) else None


def part_gb(inputs: dict, part: str) -> Optional[float]:
    plan = window_plan(inputs)
    return None if plan is None else plan[part] / 1e9


def unaliased_outputs_gb(inputs: dict) -> Optional[float]:
    plan = window_plan(inputs)
    return None if plan is None else (
        plan["outputs"] - plan["aliased"]) / 1e9


def outside_step_gb(inputs: dict) -> Optional[float]:
    """``peak_hbm_gb`` less the whole plan (arguments + temporaries +
    outputs - aliased + code): what the chip holds that the step's
    executable does not plan."""
    plan = window_plan(inputs)
    if plan is None:
        return None
    planned = (plan["arguments"] + plan["temporaries"] + plan["outputs"]
               - plan["aliased"] + plan["code"])
    return inputs["e2e"]["peak_hbm_gb"] - planned / 1e9


def cache_misses(inputs: dict, block: str = "train") -> Optional[float]:
    """Backend compiles of the blocks of kind ``block`` in this process that
    the persistent compile cache did not serve.  None where the run reports
    no set-up to move, where the program's counter has no ``block`` label (a
    commit before PR 51) and where no such block compiled."""
    if "setup_s" not in inputs.get("e2e", {}):
        return None
    from paddle_tpu import monitor
    fam = monitor.REGISTRY.get(COMPILES)
    if fam is None or "block" not in fam.labelnames:
        return None
    compiled = missed = 0
    for labels, cell in fam.series():
        if labels.get("block") != block:
            continue
        compiled += cell.get()
        if labels.get("persist") == "miss":
            missed += cell.get()
    return float(missed) if compiled else None
