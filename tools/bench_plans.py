"""Runs one cell of the benchmark as ``benchmark/run.py`` does (same
arguments, same last line on standard output) and then prints, on standard
error, what the HBM plane knew during the run: every compiled block's memory
plan with when it compiled and what the hook cost (``memory.hbm_plans()``:
``block``, ``compiled_at`` as seconds after process start, ``hook_ms``, the
arguments by class), the compile counter by cache outcome, and the
accountant's gauges as a thread sampled them twice a second (live bytes, the
dispatched block's ``step_temporaries``, headroom), beside the allocator's
own counters.  For the builder's log: which compile a window ran, whether
the published headroom is what the chip has free.

    chiprun -- python3 tools/bench_plans.py --workload <cell> --seed 7 \
        --seconds 20 --trace 1
"""

import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402  (starts the clock)


def _sampler(stop, rows):
    from paddle_tpu import monitor
    reg = monitor.REGISTRY
    last = None
    while not stop.wait(0.5):
        try:
            row = (int(reg.get("paddle_tpu_hbm_live_bytes").value()),
                   int(reg.get("paddle_tpu_hbm_class_bytes").value(
                       cls="step_temporaries")),
                   int(reg.get("paddle_tpu_hbm_headroom_bytes").value()),
                   int(reg.get("paddle_tpu_hbm_budget_bytes").value()))
        except Exception:
            continue
        if row != last:          # the gauges move with the dispatched block
            rows.append((round(time.perf_counter()
                               - bench_run.T_PROCESS_START, 1),) + row)
            last = row


def main() -> int:
    stop, rows = threading.Event(), []
    thread = threading.Thread(target=_sampler, args=(stop, rows), daemon=True)
    thread.start()
    try:
        rc = bench_run.main()
    finally:
        stop.set()
        thread.join(2.0)
    import jax
    from paddle_tpu import memory, monitor
    t0 = bench_run.T_PROCESS_START
    plans = [dict(p, tag=tag[:48], compiled_at=round(p["compiled_at"] - t0, 2),
                  hook_ms=round(p.get("hook_ms", -1.0), 2))
             for tag, p in memory.hbm_plans().items()]
    for p in plans:
        print("plans: " + json.dumps(p), file=sys.stderr)
    compiles = monitor.REGISTRY.get("paddle_tpu_compile_total")
    phases = monitor.REGISTRY.get("paddle_tpu_compile_phase_seconds")
    print("plans: summary " + json.dumps({
        "hook_ms_max": max((p["hook_ms"] for p in plans), default=None),
        "hook_ms_train": [p["hook_ms"] for p in plans
                          if p["block"] == "train"],
        "compile_total": {f"{l.get('block', '?')}/{l['persist']}": c.get()
                          for l, c in compiles.series()},
        "retrace_s": {l["block"]: round(c.snapshot()[1], 2)
                      for l, c in phases.series() if l["phase"] == "retrace"},
        "allocator": {k: int(v) for k, v in
                      (jax.local_devices()[0].memory_stats() or {}).items()
                      if k in ("bytes_in_use", "peak_bytes_in_use",
                               "bytes_reserved", "peak_bytes_reserved",
                               "bytes_limit")},
        "gauges_columns": ["s_after_start", "live", "step_temporaries",
                           "headroom", "budget"],
        "gauges": rows}), file=sys.stderr)
    return rc


if __name__ == "__main__":
    sys.exit(main())
