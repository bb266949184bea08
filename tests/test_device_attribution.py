"""Device-time attribution (this PR's observability tentpole): the
analytic per-op flops/bytes cost model (analysis/cost.py), the
executor's live MFU / step-time gauges and step-id-keyed dispatch spans,
the compile span's per-pass lowering-time attribution, the
FLAGS_cost_crosscheck parity gate against XLA's cost_analysis(), the
sampling profiler's bounded rotating windows, and the timeline
--rank-lanes gang merge."""

import json
import os
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, monitor, profiler
from paddle_tpu.analysis import plan_cost, verify_program
from paddle_tpu.analysis.cost import device_peak_flops, xla_cost_totals
from paddle_tpu.framework import Executor
from paddle_tpu.framework.core import Program, program_guard
from paddle_tpu.framework.scope import Scope, scope_guard

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))
import timeline  # noqa: E402


def _mlp(in_dim=64, hidden=128, out=32):
    x = layers.data("x", shape=[in_dim], dtype="float32")
    h = layers.fc(x, size=hidden, act="relu")
    loss = layers.mean(layers.fc(h, size=out))
    fluid.optimizer.SGD(0.01).minimize(loss)
    return loss


# ---------------------------------------------------------------------------
# analytic cost model
# ---------------------------------------------------------------------------

def test_matmul_flops_exact_with_grad_inheritance():
    """fwd matmuls count 2·M·K·N; their grads count 2x — the standard
    1:2 fwd:bwd ratio, so a train step's matmul class totals 3x fwd."""
    with scope_guard(Scope()), program_guard(Program(), Program()):
        loss = _mlp()
        batch = 16
        plan = plan_cost(fluid.default_main_program(), (loss.name,),
                         batch_size=batch)
        fwd = 2 * batch * 64 * 128 + 2 * batch * 128 * 32
        assert plan.per_class["matmul"] == 3 * fwd
        assert plan.flops > plan.per_class["matmul"]  # elementwise too
        assert plan.bytes > 0
        share = plan.share()
        assert abs(sum(share.values()) - 1.0) < 1e-9
        assert share["matmul"] > 0.9          # MLP is matmul-dominated


def test_conv_flops_match_bench_formula():
    """conv2d uses the 2·MAC rule ``benchmark/flops.py`` applies to ResNet."""
    with scope_guard(Scope()), program_guard(Program(), Program()):
        img = layers.data("img", shape=[3, 8, 8], dtype="float32")
        out = layers.conv2d(img, num_filters=4, filter_size=3, padding=1)
        plan = plan_cost(fluid.default_main_program(), (out.name,),
                         batch_size=2)
        # out [2, 4, 8, 8]; filter [4, 3, 3, 3]
        expect = 2 * (2 * 4 * 8 * 8) * 3 * 3 * 3
        conv = [r for r in plan.per_op if r[1] == "conv2d"]
        assert conv and conv[0][3] == expect
        assert plan.per_class["conv"] >= expect


def test_cost_plan_cached_on_fingerprint():
    with scope_guard(Scope()), program_guard(Program(), Program()):
        loss = _mlp()
        prog = fluid.default_main_program()
        p1 = plan_cost(prog, (loss.name,), batch_size=4)
        p2 = plan_cost(prog, (loss.name,), batch_size=4)
        assert p1 is p2
        p3 = plan_cost(prog, (loss.name,), batch_size=8)
        assert p3 is not p1 and p3.flops > p1.flops


def test_verifier_stamps_cost_attrs():
    """verify_program stamps _attrs['verify']['cost'] (batch=1 baseline)
    and the attrs ride clone onto optimized programs."""
    with scope_guard(Scope()), program_guard(Program(), Program()):
        loss = _mlp()
        prog = fluid.default_main_program()
        verify_program(prog, (loss.name,))
        cost = prog._attrs["verify"]["cost"]
        assert cost["flops"] > 0 and cost["bytes"] > 0
        assert cost["per_class"]["matmul"] > 0
        assert cost["intensity"] > 0
        clone = prog.clone()
        assert clone._attrs["verify"]["cost"] == cost


def test_lookup_table_is_zero_flop_bytes_heavy():
    with scope_guard(Scope()), program_guard(Program(), Program()):
        ids = layers.data("ids", shape=[1], dtype="int64")
        emb = layers.embedding(ids, size=[1000, 64])
        plan = plan_cost(fluid.default_main_program(), (emb.name,),
                         batch_size=4)
        rows = [r for r in plan.per_op if r[1].startswith("lookup_table")]
        assert rows and rows[0][3] == 0 and rows[0][4] > 0
        assert rows[0][2] == "embedding"


def test_device_peak_flops_cpu_nominal():
    assert device_peak_flops() == 1e12      # CPU smoke constant


def test_xla_cost_totals_shapes():
    assert xla_cost_totals({"flops": 5.0, "bytes accessed": 7.0}) == \
        (5.0, 7.0)
    assert xla_cost_totals([{"flops": 5.0}]) == (5.0, 0.0)
    assert xla_cost_totals([]) == (0.0, 0.0)
    assert xla_cost_totals(None) == (0.0, 0.0)


# ---------------------------------------------------------------------------
# executor: live MFU gauges + step-keyed spans + crosscheck
# ---------------------------------------------------------------------------

def _run_loop(steps=10, batch=16):
    scope = Scope()
    with scope_guard(scope), program_guard(Program(), Program()):
        loss = _mlp()
        exe = Executor()
        exe.run(fluid.default_startup_program(), scope=scope)
        feed = {"x": np.ones((batch, 64), np.float32)}
        h = None
        for _ in range(steps):
            h, = exe.run(feed=feed, fetch_list=[loss.name], scope=scope,
                         return_numpy=False)
        h.numpy()
        return exe


def test_live_mfu_and_step_time_gauges():
    exe = _run_loop(steps=12)
    serial = str(exe._stats.serial)
    ms = monitor.REGISTRY.get("paddle_tpu_step_device_ms")
    mfu = monitor.REGISTRY.get("paddle_tpu_step_mfu")
    assert ms.value(executor=serial) > 0
    assert 0 < mfu.value(executor=serial) < 1
    share = monitor.REGISTRY.get("paddle_tpu_step_flops_share")
    assert share.value(op_class="matmul") > 0.9
    # retirement drops the gauge series (a dead executor's last step
    # time is meaningless) while the counter series fold as before
    exe._stats.retire()
    labels = [lbl for lbl, _ in ms.series()]
    assert {"executor": serial} not in labels


def test_dispatch_spans_are_step_keyed():
    monitor.TRACER.clear()
    _run_loop(steps=6)
    steps = [args.get("step")
             for ph, name, cat, tid, t0, dur, args in
             list(monitor.TRACER._events)
             if name == "executor.dispatch" and args]
    assert len(steps) >= 6
    assert all(isinstance(s, int) for s in steps)
    assert steps == sorted(set(steps))     # unique, increasing


def test_cost_crosscheck_ok_on_matmul_program():
    fluid.set_flags({"FLAGS_cost_crosscheck": True})
    try:
        before = monitor.telemetry_snapshot()
        _run_loop(steps=3)
        after = monitor.telemetry_snapshot()

        def d(verdict):
            k = f'paddle_tpu_cost_crosscheck_total{{verdict="{verdict}"}}'
            return after.get(k, 0) - before.get(k, 0)
        assert d("ok") >= 1
        assert d("divergent") == 0
        assert monitor.REGISTRY.get(
            "paddle_tpu_xla_step_flops").value() > 0
    finally:
        fluid.set_flags({"FLAGS_cost_crosscheck": False})


def test_cost_crosscheck_skips_non_mxu_program():
    """An elementwise-only program (no dominant matmul/conv work) is
    'skipped', never 'divergent' — XLA bills transcendentals, the
    analytic model bills elements, and the two legitimately differ."""
    fluid.set_flags({"FLAGS_cost_crosscheck": True})
    try:
        before = monitor.telemetry_snapshot()
        scope = Scope()
        with scope_guard(scope), program_guard(Program(), Program()):
            x = layers.data("x", shape=[32], dtype="float32")
            y = layers.mean(layers.tanh(layers.scale(x, scale=2.0)))
            exe = Executor()
            feed = {"x": np.ones((4, 32), np.float32)}
            exe.run(feed=feed, fetch_list=[y.name], scope=scope)
        after = monitor.telemetry_snapshot()
        k = 'paddle_tpu_cost_crosscheck_total{verdict="divergent"}'
        assert after.get(k, 0) == before.get(k, 0)
    finally:
        fluid.set_flags({"FLAGS_cost_crosscheck": False})


def test_compile_span_carries_pass_attribution():
    monitor.TRACER.clear()
    scope = Scope()
    with scope_guard(scope), program_guard(Program(), Program()):
        loss = _mlp()
        cp = fluid.CompiledProgram(fluid.default_main_program())
        exe = Executor()
        exe.run(fluid.default_startup_program(), scope=scope)
        feed = {"x": np.ones((4, 64), np.float32)}
        exe.run(cp, feed=feed, fetch_list=[loss.name], scope=scope)
    events = {name: args for ph, name, cat, tid, t0, dur, args in
              list(monitor.TRACER._events)}
    assert "compiler.pass.program_verify" in events
    assert "compiler.pass.dead_op_eliminate" in events
    opt = events.get("compiler.optimize")
    assert opt and opt.get("passes_ms")
    assert "program_verify" in opt["passes_ms"]


# ---------------------------------------------------------------------------
# sampling profiler
# ---------------------------------------------------------------------------

def test_sampling_profiler_rotation_and_manifest(tmp_path):
    sdir = str(tmp_path / "samples")
    fluid.set_flags({"FLAGS_profile_sample_every_n_steps": 3,
                     "FLAGS_profile_sample_window_steps": 2,
                     "FLAGS_profile_sample_dir": sdir,
                     "FLAGS_profile_sample_max_windows": 2})
    try:
        _run_loop(steps=25)
        profiler.SAMPLER.close()
        assert profiler.last_window_error() is None
        wdirs = sorted(d for d in os.listdir(sdir)
                       if d.startswith("window_"))
        assert 1 <= len(wdirs) <= 2          # the rotation bound
        with open(os.path.join(sdir, "manifest.json")) as f:
            manifest = json.load(f)
        windows = manifest["windows"]
        assert len(windows) == len(wdirs)
        for w in windows:
            # full windows span window_steps; the final window may be
            # truncated (the loop ended mid-window) but never empty —
            # close() abandons zero-step windows outright
            assert 1 <= w["end_step"] - w["start_step"] <= 2
            assert os.path.basename(w["dir"]) in wdirs
            assert w["wall_end"] >= w["wall_start"]
    finally:
        fluid.set_flags({"FLAGS_profile_sample_every_n_steps": 0})


def test_sampling_profiler_disabled_is_noop(tmp_path):
    sdir = str(tmp_path / "off")
    fluid.set_flags({"FLAGS_profile_sample_every_n_steps": 0,
                     "FLAGS_profile_sample_dir": sdir})
    _run_loop(steps=5)
    assert not os.path.exists(os.path.join(sdir, "manifest.json"))


# ---------------------------------------------------------------------------
# timeline --rank-lanes gang merge
# ---------------------------------------------------------------------------

def test_rank_lanes_merge_strict_valid(tmp_path):
    monitor.TRACER.clear()
    _run_loop(steps=4)
    trace = str(tmp_path / "r.json")
    from paddle_tpu import profiler as _prof
    _prof.chrome_trace(trace)
    out = str(tmp_path / "lanes.json")
    timeline.merge(f"0={trace},1={trace}", out, align=True,
                   rank_lanes=True)
    stats = timeline.validate(out, strict=True)   # raises on malformed
    with open(out) as f:
        events = json.load(f)["traceEvents"]
    pids = {ev["pid"] for ev in events}
    assert pids == {0, 1}                    # one integer lane per rank
    lane_names = {ev["pid"]: ev["args"]["name"] for ev in events
                  if ev.get("name") == "process_name"}
    assert lane_names == {0: "rank 0", 1: "rank 1"}
    sort_rows = [ev for ev in events
                 if ev.get("name") == "process_sort_index"]
    assert {ev["args"]["sort_index"] for ev in sort_rows} == {0, 1}
    # alignment: earliest event at t=0
    ts = [ev["ts"] for ev in events if "ts" in ev]
    assert min(ts) == 0
    assert stats["events"] == len(events)


def test_flops_share_series_cleared_on_new_program():
    """The share family reports the most recently planned step only: a
    conv model's classes must not linger once a matmul-only program is
    planned (review finding: mixed shares summed to ~2)."""
    scope = Scope()
    with scope_guard(scope), program_guard(Program(), Program()):
        img = layers.data("img", shape=[3, 8, 8], dtype="float32")
        loss = layers.mean(layers.conv2d(img, num_filters=4,
                                         filter_size=3, padding=1))
        fluid.optimizer.SGD(0.01).minimize(loss)
        exe = Executor()
        exe.run(fluid.default_startup_program(), scope=scope)
        feed = {"img": np.ones((2, 3, 8, 8), np.float32)}
        exe.run(feed=feed, fetch_list=[loss.name], scope=scope)
    share = monitor.REGISTRY.get("paddle_tpu_step_flops_share")
    assert share.value(op_class="conv") > 0
    _run_loop(steps=2)                        # matmul-only program
    classes = {lbl["op_class"] for lbl, _ in share.series()}
    assert "conv" not in classes
    assert "matmul" in classes
    total = sum(cell.get() for _, cell in share.series())
    assert abs(total - 1.0) < 1e-6


def test_interval_window_is_per_executor_not_per_block():
    """An executor alternating two compiled blocks (train + eval fetch
    sets) must measure the dispatch cadence, not each block's full
    A->B->A cycle (review finding: 2x-inflated step time)."""
    scope = Scope()
    with scope_guard(scope), program_guard(Program(), Program()):
        loss = _mlp()
        exe = Executor()
        exe.run(fluid.default_startup_program(), scope=scope)
        feed = {"x": np.ones((8, 64), np.float32)}
        prog = fluid.default_main_program()
        blk = prog.global_block()
        other = [v for v in blk.vars
                 if v.endswith(".tmp_2")][:1] or [loss.name]
        h = None
        for _ in range(12):                   # alternating fetch sets
            h, = exe.run(feed=feed, fetch_list=[loss.name], scope=scope,
                         return_numpy=False)
            exe.run(feed=feed, fetch_list=[other[0]], scope=scope,
                    return_numpy=False)
        h.numpy()
        assert len(exe._step_win) > 0         # executor-level window
        ms = monitor.REGISTRY.get("paddle_tpu_step_device_ms")
        assert ms.value(executor=str(exe._stats.serial)) > 0


def test_failed_window_dir_removed(tmp_path, monkeypatch):
    """A start_trace failure must not leave an un-manifested window dir
    behind — rotation can only reclaim manifest-listed dirs (review
    finding: the disk bound broke on recurring capture errors)."""
    import jax
    sdir = str(tmp_path / "errwin")

    def boom(*a, **k):
        raise RuntimeError("no profiler session for you")
    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    profiler.SAMPLER.configure(1, 2, sdir, 2)
    try:
        profiler.SAMPLER.on_step(1)
        assert "no profiler session" in profiler.last_window_error()
        assert not [d for d in os.listdir(sdir)
                    if d.startswith("window_")]
    finally:
        profiler.SAMPLER.configure(0, 2, sdir, 2)


# ---------------------------------------------------------------------------
# PR-9 satellites: regression auto-trigger, per-op-class crosscheck
# breakdown, real-batch HBM restamp
# ---------------------------------------------------------------------------

def test_sampling_profiler_regress_trigger(tmp_path):
    """A windowed-median regression past FLAGS_profile_sample_regress_frac
    opens a capture window IMMEDIATELY (trigger='regress' in the
    manifest), and hysteresis keeps a sustained slowdown at one window."""
    sdir = str(tmp_path / "regress")
    fluid.set_flags({"FLAGS_profile_sample_every_n_steps": 0,
                     "FLAGS_profile_sample_window_steps": 2,
                     "FLAGS_profile_sample_dir": sdir,
                     "FLAGS_profile_sample_max_windows": 4,
                     "FLAGS_profile_sample_regress_frac": 0.5})
    try:
        step = 0
        for _ in range(10):                    # healthy baseline, 10 ms
            step += 1
            profiler.SAMPLER.on_step(step, 10.0)
        assert profiler.SAMPLER._active is None
        for _ in range(6):                     # sustained 2x regression
            step += 1
            profiler.SAMPLER.on_step(step, 20.0)
        profiler.SAMPLER.close()
        # (last_window_error is a sticky last-FAILURE note — an earlier
        # test's injected capture failure legitimately lingers there)
        with open(os.path.join(sdir, "manifest.json")) as f:
            windows = json.load(f)["windows"]
        regress = [w for w in windows if w.get("trigger") == "regress"]
        assert len(regress) == 1               # hysteresis: one window
        assert windows == regress              # no periodic windows
        # the window opened AT the regressed step, not on a cadence
        assert regress[0]["start_step"] >= 11
    finally:
        fluid.set_flags({"FLAGS_profile_sample_regress_frac": 0.0,
                         "FLAGS_profile_sample_every_n_steps": 0})


def test_sampling_profiler_regress_rearms_after_recovery(tmp_path):
    sdir = str(tmp_path / "rearm")
    fluid.set_flags({"FLAGS_profile_sample_every_n_steps": 0,
                     "FLAGS_profile_sample_window_steps": 1,
                     "FLAGS_profile_sample_dir": sdir,
                     "FLAGS_profile_sample_max_windows": 4,
                     "FLAGS_profile_sample_regress_frac": 0.5})
    try:
        step = 0
        for ms in [10.0] * 10 + [20.0] * 3 + [10.0] * 3 + [20.0] * 3:
            step += 1
            profiler.SAMPLER.on_step(step, ms)
        profiler.SAMPLER.close()
        with open(os.path.join(sdir, "manifest.json")) as f:
            windows = json.load(f)["windows"]
        regress = [w for w in windows if w.get("trigger") == "regress"]
        assert len(regress) == 2       # recovered in between: re-armed
    finally:
        fluid.set_flags({"FLAGS_profile_sample_regress_frac": 0.0,
                         "FLAGS_profile_sample_every_n_steps": 0})


def test_xla_cost_breakdown_parsing():
    """The crosscheck consumes the per-operand utilization/bytes keys,
    not just the totals (PR-8 follow-on)."""
    from paddle_tpu.analysis.cost import xla_cost_breakdown
    ca = {"flops": 100.0, "transcendentals": 7.0, "bytes accessed": 50.0,
          "bytes accessed0{}": 20.0, "bytes accessedout{}": 10.0,
          "utilization0{}": 2.0, "utilization1{}": 1.0}
    out = xla_cost_breakdown([ca])          # list form tolerated
    assert out["flops"] == 100.0
    assert out["transcendentals"] == 7.0
    assert out["bytes_accessed"] == 50.0
    assert out["operand_bytes"] == {"0": 20.0, "out": 10.0}
    assert out["operand_utilization"] == {"0": 2.0, "1": 1.0}
    assert xla_cost_breakdown(None) == {}


def test_memory_restamped_at_real_feed_batch():
    """PR-7 follow-on: once a dispatch plan exists, the verify-time HBM
    stamp is re-planned at the REAL feed batch (not the batch=1 lower
    bound) on the optimized program."""
    from paddle_tpu.compiler import CompiledProgram
    scope = Scope()
    with scope_guard(scope), program_guard(Program(), Program()):
        loss = _mlp(in_dim=8, hidden=16, out=4)
        exe = Executor()
        exe.run(fluid.default_startup_program(), scope=scope)
        cp = CompiledProgram(fluid.default_main_program())
        feed = {"x": np.zeros((4, 8), np.float32)}
        exe.run(cp, feed=feed, fetch_list=[loss.name], scope=scope)
        optprog = cp._optimized((loss.name,), feed_shapes={"x": (4, 8)})
        mem = optprog._attrs["verify"]["memory"]
        assert mem["batch"] == 4
        from paddle_tpu.analysis import plan_memory
        base = plan_memory(optprog, (loss.name,), batch_size=1)
        assert mem["peak_bytes"] > base.peak_bytes
