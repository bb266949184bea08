"""Training traffic: a ring of device-resident batches made from the seed,
steps dispatched as a trainer does (``exe.run(..., return_numpy=False)``
under the executor's own in-flight throttle), the last one blocked on.

The traffic file gives the batch per chip, the ring length and the model's
own sizes (sequence length, masked positions, learning rate).  The input
pipeline is excluded: the batches live on the device before the window.
The rate is all the steps of the window over all its seconds, from the first
dispatch (the device is idle then) to the moment the last step's loss is on
the host.
"""

from __future__ import annotations

import time

import numpy as np

from .. import harness


def run(ctx) -> dict:
    import jax
    from ..models import _train

    t = ctx.traffic
    # the CPU rehearsals run one device unless the toy traffic says otherwise
    chips = int(t.get("chips_override",
                      ctx.cell["chips"] if ctx.on_chip else 1))
    m = ctx.model.build_train(ctx.config, t, ctx.seed, chips, ctx.on_chip)
    exe, scope, program, loss = m["exe"], m["scope"], m["program"], m["loss"]
    ctx.clock.mark("build+startup")

    pre = ctx.model.check_before_window(ctx.config, t, m, ctx.seed,
                                        ctx.reference, chips)
    harness.log(f"reference check: {pre['detail']}")
    ctx.clock.mark("reference check")

    ring = _train.put_ring(m["ring"], chips)
    jax.block_until_ready(ring)
    ctx.clock.mark("ring to device")

    def step(i, sync=False):
        out, = exe.run(program, feed=ring[i % len(ring)], fetch_list=[loss],
                       scope=scope, return_numpy=False)
        return float(np.asarray(out)) if sync else out

    first_loss = step(0, sync=True)                 # compiles or loads
    ctx.clock.mark("compile or cache load + first step")
    warm = [step(i) for i in range(1, 1 + int(t["warmup_steps"]))]
    warm_losses = [float(np.asarray(h)) for h in warm]
    ctx.clock.mark("warm-up steps")

    stats0 = exe.dispatch_stats()
    compiles0 = ctx.meter.compiles
    ctx.spans.open()
    handles = []
    t_open = time.perf_counter()
    setup_s = t_open - ctx.clock.t0
    t_deadline = t_open + ctx.seconds
    t_trace = t_deadline - ctx.trace_seconds if ctx.trace else None
    n = 1 + len(warm)
    steps_traced = 0
    # a traced run records at least one step: where a step runs from before
    # t_trace to after the deadline, the window is that one step longer
    while time.perf_counter() < t_deadline or t_trace is not None:
        if t_trace is not None and time.perf_counter() >= t_trace:
            # the profiler's window starts on an idle device, like the
            # measured one: finish what is in flight, then record
            if handles:
                np.asarray(handles[-1])
            ctx.device_trace.start()
            t_trace = None
            steps_traced = -len(handles)
        handles.append(step(n))
        n += 1
    last = float(np.asarray(handles[-1]))           # the closing sync
    t_close = time.perf_counter()
    if ctx.trace:
        steps_traced += len(handles)
        ctx.device_trace.stop()
    spans = ctx.spans.close()
    stats1 = exe.dispatch_stats()
    compiled_in_window = ctx.meter.compiles - compiles0
    traced_in_window = stats1["traces"] - stats0["traces"]

    losses = [first_loss] + warm_losses + \
        [float(np.asarray(h)) for h in handles]
    finite = bool(np.all(np.isfinite(losses)))
    window_s = t_close - t_open
    steps = len(handles)
    rate = steps * m["batch"] / window_s
    memory_peak = harness.memory_peak_bytes()     # before the reference runs
    checks = [pre]
    if hasattr(ctx.model, "check_first_loss"):
        post = ctx.model.check_first_loss(ctx.config, t, m, first_loss,
                                          m["ring"][0], ctx.reference)
        harness.log(f"reference check: {post['detail']}")
        checks.append(post)

    ok_devices, device_note = True, ""
    if chips > 1:
        span = len(handles[-1].sharding.device_set) \
            if hasattr(handles[-1], "sharding") else 0
        in_use = [int((d.memory_stats() or {}).get("bytes_in_use", 0))
                  for d in jax.local_devices()]
        # the largest parameter of the program: replicated over the chips
        name = max(m["parameters"], key=lambda v: int(np.prod(v.shape))).name
        w_span = len(scope.find_var(name).sharding.device_set)
        ok_devices = (w_span == chips and
                      (not ctx.on_chip or all(b > 0 for b in in_use)))
        device_note = (f"; loss fetch spans {span} device(s), parameter "
                       f"{name} spans {w_span}, "
                       f"bytes in use per chip {in_use}")
    # for a run that reads slow (PERF.md section 7, row 24): one long stall
    # or every step slower?  The throttle paces the dispatches at the
    # device's rate, so their intervals are the steps as the host saw them
    starts = np.sort([s[1] for s in spans if s[0] == "executor.dispatch"])
    if len(starts) > 2:
        gaps = np.diff(starts) * 1e3
        harness.log(
            f"dispatch intervals: median {np.median(gaps):.2f} ms, longest "
            f"{gaps.max():.2f} ms (after step {int(gaps.argmax()) + 1} of "
            f"{len(starts)}), {int((gaps > 1.5 * np.median(gaps)).sum())} "
            "over 1.5x the median")
    harness.log(
        f"window {window_s:.3f}s (asked {ctx.seconds}s, closed "
        f"{t_close - t_deadline:+.3f}s after the deadline); {steps} steps of "
        f"{m['batch']} samples; loss first {first_loss:.4f} last {last:.4f};"
        f" compiles in window {compiled_in_window}, traces "
        f"{traced_in_window}{device_note}")
    correct = (all(c["ok"] for c in checks) and finite and steps > 0
               and compiled_in_window == 0 and traced_in_window == 0
               and ok_devices)
    verdict = (f"{'correct' if correct else 'NOT correct'}: "
               f"checks={[c['ok'] for c in checks]} finite={finite} "
               f"steps={steps} (> 0) compiled_in_window={compiled_in_window}"
               f" (0) traced_in_window={traced_in_window} (0) "
               f"devices_ok={ok_devices}")
    if not correct:
        harness.log(verdict)
    return {
        "correct": correct, "attempted": steps,
        "compared": [c["detail"] for c in checks] + [verdict],
        "failed": int(sum(1 for x in losses if not np.isfinite(x))),
        "setup_s": setup_s,
        "e2e": {"train_samples_per_s": rate,
                "peak_hbm_gb": memory_peak / 1e9},
        "memory_peak_bytes": memory_peak,
        "spans": spans,
        "counters": {"steps": steps, "steps_traced": steps_traced},
        "facts": {"batch": m["batch"], "chips": chips, "window_s": window_s,
                  "samples_per_s": rate,
                  "flops_per_sample": m["flops_per_sample"]},
    }
