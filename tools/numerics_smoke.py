#!/usr/bin/env python
"""Numerics-plane smoke (wired into tools/ci.sh): the end-to-end gates
of the value-domain observability plane.

1. **Steady-state cleanliness**: a lazy-fetch train loop with
   ``FLAGS_numerics=sentinel`` must add ZERO host blocks on the training
   thread — the stats ride the PR-1 lazy-fetch path (``dispatch_stats``
   materialize/throttle deltas stay flat across the steady window, and
   the engine's forced-sync counter stays 0).

2. **Poison drill**: an injected NaN (``FLAGS_fault_inject`` site
   ``numerics.poison``) must be DETECTED within 2 steps (anomaly record
   + ``numerics.anomaly`` trace instant), must open a profiler capture
   window whose manifest entry carries ``trigger: "anomaly"``, and must
   QUARANTINE the checkpoint plane: the CheckpointDaemon holds every
   later commit, so the manifest stays at the last healthy step.

3. **Loss parity**: the stats output is a pure observer — the loss
   trajectory fingerprints identically with the plane on and off
   (``numerics.loss_fingerprint`` of both trajectories must be equal).
"""

import json
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402


def fail(msg):
    print(f"NUMERICS SMOKE FAILED: {msg}")
    sys.exit(1)


def _build(scope, seed=11):
    import paddle_tpu as pt
    from paddle_tpu import layers
    pt.default_main_program().random_seed = seed
    pt.default_startup_program().random_seed = seed
    x = layers.data("x", shape=[16], dtype="float32")
    h = layers.fc(x, size=32, act="relu",
                  param_attr=pt.ParamAttr(name="ns_w0"),
                  bias_attr=pt.ParamAttr(name="ns_b0"))
    loss = layers.mean(layers.fc(h, size=8,
                                 param_attr=pt.ParamAttr(name="ns_w1"),
                                 bias_attr=pt.ParamAttr(name="ns_b1")))
    pt.optimizer.SGD(0.05).minimize(loss)
    exe = pt.Executor()
    exe.run(pt.default_startup_program(), scope=scope)
    return exe, loss


def check_steady_state_and_parity():
    """Gates 1 + 3: zero added training-thread host blocks, identical
    loss trajectory with the plane on."""
    import paddle_tpu as pt
    from paddle_tpu.framework import (Program, Scope, program_guard,
                                      scope_guard)
    from paddle_tpu.analysis import numerics

    feed = {"x": np.linspace(-1, 1, 8 * 16,
                             dtype=np.float32).reshape(8, 16)}

    def run_loop(mode):
        pt.set_flags({"FLAGS_numerics": mode})
        scope = Scope()
        with scope_guard(scope), program_guard(Program(), Program()):
            exe, loss = _build(scope)
            handles = []
            # warmup: compile + let the pipeline reach steady state
            for _ in range(5):
                h, = exe.run(feed=feed, fetch_list=[loss.name],
                             scope=scope, return_numpy=False)
                handles.append(h)
            forced0 = numerics.FORCED_SYNC_CTR.value()
            s0 = exe.dispatch_stats()
            for _ in range(25):
                h, = exe.run(feed=feed, fetch_list=[loss.name],
                             scope=scope, return_numpy=False)
                handles.append(h)
            s1 = exe.dispatch_stats()
            forced1 = numerics.FORCED_SYNC_CTR.value()
            # single pipeline-bounding sync, then materialize the rest
            handles[-1].numpy()
            losses = [float(h.numpy()) for h in handles]
            numerics.ENGINE.poll(force=True)
            return (numerics.loss_fingerprint(losses),
                    {k: s1[k] - s0[k] for k in s1 if k in s0},
                    forced1 - forced0)

    fp_off, _, _ = run_loop("off")
    fp_on, delta, forced = run_loop("sentinel")

    if delta.get("fetch_materializations", 1) != 0:
        fail("sentinel loop materialized fetches mid-steady-state: "
             f"{delta}")
    if delta.get("materialize_block_us", 1) != 0:
        fail("sentinel loop spent host-block time materializing in the "
             f"steady window: {delta}")
    if forced != 0:
        fail(f"numerics engine forced {forced} backlog syncs on the "
             "training thread")
    if fp_off != fp_on:
        fail(f"loss trajectory diverged with the plane on: {fp_off} != "
             f"{fp_on}")
    if numerics.ENGINE.frames_processed <= 0:
        fail("sentinel loop processed no stats frames")
    print("numerics smoke 1 OK: zero added steady-state host blocks "
          f"(delta={ {k: v for k, v in delta.items() if v} }), loss "
          "parity holds")


def check_poison_quarantine():
    """Gate 2: injected NaN -> anomaly within 2 steps, profiler window
    with trigger:'anomaly', manifest held at the last healthy step."""
    import paddle_tpu as pt
    from paddle_tpu import monitor
    from paddle_tpu.checkpoint import CheckpointManager
    from paddle_tpu.framework import (Program, Scope, program_guard,
                                      scope_guard)
    from paddle_tpu.resilience import CheckpointDaemon
    from paddle_tpu.analysis import numerics
    from paddle_tpu.profiler import SAMPLER

    poison_at = 5          # 5th maybe_inject("numerics.poison") call
    total_steps = 10
    prof_dir = tempfile.mkdtemp(prefix="pt_numerics_prof_")
    ckpt_dir = tempfile.mkdtemp(prefix="pt_numerics_ckpt_")
    numerics.ENGINE.reset()
    pt.set_flags({
        "FLAGS_numerics": "sentinel",
        "FLAGS_profile_sample_dir": prof_dir,
        # the poison site is called once per dispatch INCLUDING the
        # startup run below (the flag is already armed), so once@N
        # fires at training step N-1 — the detection gate is written
        # in loop-step space and tolerates the offset
        "FLAGS_fault_inject": f"numerics.poison:once@{poison_at}",
    })
    scope = Scope()
    try:
        with scope_guard(scope), program_guard(Program(), Program()):
            exe, loss = _build(scope)
            ckpt = CheckpointManager(ckpt_dir, max_to_keep=20)
            daemon = CheckpointDaemon(
                ckpt, program=pt.default_main_program(), scope=scope,
                interval_steps=1).start()
            feed = {"x": np.linspace(-1, 1, 8 * 16, dtype=np.float32)
                    .reshape(8, 16)}
            anomaly_step = None
            try:
                for step in range(1, total_steps + 1):
                    exe.run(feed=feed, fetch_list=[loss.name],
                            scope=scope, return_numpy=False)
                    daemon.step_completed(step, scope=scope)
                    if anomaly_step is None and numerics.is_poisoned():
                        anomaly_step = step
                    # drain each clearly-healthy commit so the held-vs-
                    # committed ledger below is exact, not timing-bound
                    if anomaly_step is None and step <= poison_at - 2 \
                            and not daemon.wait_committed(step,
                                                          timeout_s=60):
                        fail(f"healthy step {step} did not commit")
            finally:
                last = daemon.stop(final_step=total_steps)
            exe.drain()
            numerics.ENGINE.poll(force=True)

            # -- detection within 2 steps --------------------------------
            recs = [r for r in numerics.ENGINE.anomalies
                    if r["kind"] == "nonfinite"]
            if not recs:
                fail("poison was never detected (no nonfinite anomaly "
                     "record)")
            # the record's `step` is the process-global executor step id
            # (for device-trace correlation); detection LATENCY is gated
            # in loop-step space: the quarantine flag must flip within 2
            # training steps of the poison (the poisoned step's OWN
            # stats frame carries the NaN, and the daemon's capture gate
            # force-polls — so detection is typically same-step)
            det = anomaly_step
            if det is None or det > poison_at + 2:
                fail(f"poison armed at call {poison_at} detected at "
                     f"loop step {det} (> +2 steps)")
            instants = [e for e in monitor.TRACER.chrome_events()
                        if e.get("name") == "numerics.anomaly"]
            if not instants:
                fail("no numerics.anomaly trace instant recorded")

            # -- quarantine: manifest parks at the last healthy step -----
            if not numerics.is_poisoned():
                fail("engine is not quarantined after the poison")
            # the poisoned step itself must never commit: the manifest
            # parks EXACTLY one step before the first poisoned frame
            healthy = det - 1
            if last != healthy:
                fail(f"daemon manifest at {last}, expected the last "
                     f"healthy step {healthy}")
            if ckpt.latest_step() != healthy:
                fail(f"checkpoint manifest at {ckpt.latest_step()} != "
                     f"last healthy step {healthy}")
            held = monitor.counter_totals().get(
                "paddle_tpu_checkpoint_quarantine_holds_total", 0)
            if held <= 0:
                fail("quarantine hold counter never bumped")

            # -- profiler window with trigger:'anomaly' ------------------
            SAMPLER.close()
            manifest_path = os.path.join(prof_dir, "manifest.json")
            if not os.path.exists(manifest_path):
                fail("no profiler window manifest was written")
            with open(manifest_path) as f:
                windows = json.load(f).get("windows", [])
            if not any(w.get("trigger") == "anomaly" for w in windows):
                fail(f"no anomaly-triggered window in manifest: "
                     f"{windows}")
            ckpt.close()
            print(f"numerics smoke 2 OK: poison@{poison_at} detected at "
                  f"step {det}, manifest held at {ckpt.latest_step()} "
                  f"(holds={held}), anomaly capture window present")
    finally:
        pt.set_flags({"FLAGS_fault_inject": "", "FLAGS_numerics": "off",
                      "FLAGS_profile_sample_dir": ""})
        numerics.ENGINE.reset()
        shutil.rmtree(prof_dir, ignore_errors=True)
        shutil.rmtree(ckpt_dir, ignore_errors=True)


def main():
    check_steady_state_and_parity()
    check_poison_quarantine()
    print("NUMERICS SMOKE OK")


if __name__ == "__main__":
    main()
