"""Runtime HBM observability plane (``paddle_tpu.hbm``): the off-thread
accountant's gauges and class attribution, plan-vs-measured drift on the
bench workloads, OOM forensics (injected drill and real
RESOURCE_EXHAUSTED), checkpoint-capture attribution, per-tenant KV-page
retirement, the fleet digest keys, and the timeline memory lane."""

import glob
import json
import os
import re
import sys

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import hbm, layers, monitor
from paddle_tpu.framework import (Executor, Program, program_guard)
from paddle_tpu.framework.scope import Scope, scope_guard

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "tools"))


def _train_loop(scope, steps=5, size=32, feed_batch=8, opt="adam"):
    x = layers.data("x", shape=[16], dtype="float32")
    h = layers.fc(x, size=size, act="relu")
    loss = layers.mean(layers.fc(h, size=8))
    (pt.optimizer.Adam(1e-3) if opt == "adam"
     else pt.optimizer.SGD(0.1)).minimize(loss)
    exe = Executor()
    exe.run(pt.default_startup_program(), scope=scope)
    feed = {"x": np.linspace(-1, 1, feed_batch * 16,
                             dtype=np.float32).reshape(feed_batch, 16)}
    handles = []
    for _ in range(steps):
        hd, = exe.run(feed=feed, fetch_list=[loss.name], scope=scope,
                      return_numpy=False)
        handles.append(hd)
    handles[-1].numpy()
    exe.drain()
    return exe, loss


def test_accountant_publishes_gauges_and_class_attribution():
    scope = Scope()
    with scope_guard(scope), program_guard(Program(), Program()):
        _train_loop(scope)
        assert hbm.ACCOUNTANT.drain(30)
        reg = monitor.REGISTRY
        live = reg.get("paddle_tpu_hbm_live_bytes").value()
        peak = reg.get("paddle_tpu_hbm_peak_bytes").value()
        assert live > 0
        assert peak >= live * 0.99   # watermark covers the last sample
        cls = {lbl["cls"]: c.get() for lbl, c in
               reg.get("paddle_tpu_hbm_class_bytes").series()}
        # Adam state (moments) is non-parameter persistable state
        assert cls.get("params", 0) > 0
        assert cls.get("opt_state", 0) > 0
        # in no live array: the temporaries region of the train step's
        # compiled plan, which the executor recorded where it compiled
        temps = cls.pop("step_temporaries")
        plan = [p for p in pt.memory.hbm_plans().values()
                if p["block"] == "train"][-1]
        assert temps == plan["temp_bytes"] > 0
        # attribution partitions the live set: classes never exceed it
        assert sum(cls.values()) <= live * 1.01
        tot = monitor.counter_totals()
        assert tot.get("paddle_tpu_hbm_samples_total", 0) > 0


@pytest.mark.parametrize("workload", ["mlp_adam", "wide_embedding"])
def test_plan_vs_measured_drift_band(workload):
    """The bench workloads' plan-vs-measured ratio (via the shared
    hbm.measure_live_bytes reader) stays inside the planner's
    established band — the regression gate for both the planner and the
    accountant's join."""
    import gc
    import jax
    hbm.ACCOUNTANT.drain(10)   # no in-flight note may pin a dead scope
    gc.collect()
    base = hbm.measure_live_bytes()
    scope = Scope()
    with scope_guard(scope), program_guard(Program(), Program()):
        if workload == "mlp_adam":
            x = layers.data("x", shape=[256], dtype="float32")
            h = layers.fc(x, size=1024, act="relu")
            h = layers.fc(h, size=1024, act="relu")
            loss = layers.mean(layers.fc(h, size=256))
            pt.optimizer.Adam(1e-3).minimize(loss)
            feed_np = {"x": np.random.RandomState(0).rand(
                64, 256).astype(np.float32)}
        else:
            ids = layers.data("ids", shape=[1], dtype="int64")
            emb = layers.embedding(ids, size=[20000, 128])
            loss = layers.mean(layers.fc(emb, size=1))
            pt.optimizer.SGD(0.1).minimize(loss)
            feed_np = {"ids": np.random.RandomState(0).randint(
                0, 20000, (64, 1)).astype(np.int64)}
        prog = pt.default_main_program()
        exe = Executor()
        exe.run(pt.default_startup_program(), scope=scope)
        feed = {k: jax.device_put(v) for k, v in feed_np.items()}
        lv = None
        for _ in range(3):
            lv, = exe.run(pt.CompiledProgram(prog), feed=feed,
                          fetch_list=[loss.name], scope=scope,
                          return_numpy=False)
        lv.numpy()
        exe.drain()
        from paddle_tpu.analysis import plan_memory
        batch = next(iter(feed_np.values())).shape[0]
        plan = plan_memory(prog, (loss.name,), batch_size=batch)
        gc.collect()
        measured = hbm.measure_live_bytes() - base
        assert measured > 0
        ratio = plan.steady_bytes / measured
        # planner's established band is 1.000-1.006; allow test-suite
        # noise (stray small arrays from neighboring tests)
        assert 0.90 <= ratio <= 1.10, (
            f"{workload}: plan {plan.steady_bytes} vs measured "
            f"{measured} (ratio {ratio:.4f}) left the band")


def test_oom_forensics_injected_drill(tmp_path):
    dump_dir = str(tmp_path / "dumps")
    prof_dir = str(tmp_path / "prof")
    oom0 = monitor.counter_totals().get("paddle_tpu_oom_total", 0)
    pt.set_flags({"FLAGS_oom_dump_dir": dump_dir,
                  "FLAGS_profile_sample_dir": prof_dir,
                  "FLAGS_memory_budget_mb": 2,
                  "FLAGS_fault_inject": "memory.oom:once@3"})
    scope = Scope()
    try:
        with scope_guard(scope), program_guard(Program(), Program()):
            x = layers.data("x", shape=[16], dtype="float32")
            loss = layers.mean(layers.fc(
                x, size=32, param_attr=pt.ParamAttr(name="oomt_w")))
            pt.optimizer.SGD(0.1).minimize(loss)
            exe = Executor()
            exe.run(pt.default_startup_program(), scope=scope)
            feed = {"x": np.ones((4, 16), np.float32)}
            tripped = after = 0
            for _ in range(6):
                try:
                    exe.run(feed=feed, fetch_list=[loss.name],
                            scope=scope)
                    if tripped:
                        after += 1
                except Exception as e:
                    assert "memory.oom" in str(e)
                    assert "oom forensics dump:" in str(e)
                    tripped += 1
            assert tripped == 1
            assert after >= 2      # the drill never evicts the block
        dumps = glob.glob(os.path.join(dump_dir, "paddle_tpu_oom_*.txt"))
        assert len(dumps) == 1
        txt = open(dumps[0]).read()
        assert "=== hbm oom forensics ===" in txt
        assert "oomt_w" in txt           # names the top live tensors
        vals = {k: int(re.search(rf"^{k}: (-?\d+)$", txt, re.M).group(1))
                for k in ("budget_bytes", "plan_peak_bytes",
                          "measured_bytes", "requested_bytes",
                          "measured_plus_requested", "deficit_bytes")}
        assert vals["measured_plus_requested"] == \
            vals["measured_bytes"] + vals["requested_bytes"]
        assert vals["deficit_bytes"] == \
            vals["measured_plus_requested"] - vals["budget_bytes"]
        assert vals["budget_bytes"] == 2 << 20
        assert vals["plan_peak_bytes"] > 0
        assert monitor.counter_totals().get(
            "paddle_tpu_oom_total", 0) - oom0 == 1
        assert [e for e in monitor.TRACER.chrome_events()
                if e.get("name") == "memory.oom"]
        from paddle_tpu.profiler import SAMPLER
        SAMPLER.close()
        with open(os.path.join(prof_dir, "manifest.json")) as f:
            windows = json.load(f)["windows"]
        assert any(w.get("trigger") == "oom" for w in windows)
    finally:
        pt.set_flags({"FLAGS_fault_inject": "",
                      "FLAGS_memory_budget_mb": 0,
                      "FLAGS_oom_dump_dir": "",
                      "FLAGS_profile_sample_dir": ""})


def test_oom_forensics_real_resource_exhausted(tmp_path, monkeypatch):
    """A real RESOURCE_EXHAUSTED out of the dispatched step parses the
    requested bytes into the dump and still surfaces the residency
    summary in the raised error (test_memory.py's contract)."""
    from paddle_tpu.framework import executor as ex_mod
    pt.set_flags({"FLAGS_oom_dump_dir": str(tmp_path)})
    scope = Scope()
    try:
        with scope_guard(scope), program_guard(Program(), Program()):
            x = layers.data("x", shape=[8], dtype="float32")
            y = layers.fc(x, size=4, name="oomr_fc")
            exe = Executor()
            exe.run(pt.default_startup_program(), scope=scope)

            def boom(self, feeds, ro, rw, seed):
                raise RuntimeError(
                    "RESOURCE_EXHAUSTED: Out of memory while trying to "
                    "allocate 123456789 bytes")
            monkeypatch.setattr(ex_mod._CompiledBlock, "__call__", boom)
            with pytest.raises(RuntimeError) as ei:
                exe.run(feed={"x": np.ones((2, 8), np.float32)},
                        fetch_list=[y.name], scope=scope)
        msg = str(ei.value)
        assert "device memory summary" in msg
        assert "oom forensics dump:" in msg
        path = msg.split("oom forensics dump: ")[1].splitlines()[0]
        txt = open(path).read()
        assert re.search(r"^requested_bytes: 123456789$", txt, re.M)
        assert "oomr_fc" in txt
    finally:
        pt.set_flags({"FLAGS_oom_dump_dir": ""})


def test_parse_requested_bytes_units():
    p = hbm.parse_requested_bytes
    assert p("Out of memory allocating 123 bytes") == 123
    assert p("while trying to allocate 2.5KiB of memory") == 2560
    assert p("failed to allocate 1.5G") == int(1.5 * (1 << 30))
    assert p("shape mismatch") == 0


def test_ckpt_capture_attributed_not_leak():
    """An unstarted daemon's capture holds device-side copies: the
    accountant's ckpt_capture class carries them until the daemon-side
    save materializes (here: until stop drains it)."""
    from paddle_tpu.checkpoint import CheckpointManager
    from paddle_tpu.resilience import CheckpointDaemon
    import tempfile
    import shutil
    ckpt_dir = tempfile.mkdtemp(prefix="pt_hbm_ckpt_")
    scope = Scope()
    try:
        with scope_guard(scope), program_guard(Program(), Program()):
            _train_loop(scope, steps=2)
            daemon = CheckpointDaemon(
                CheckpointManager(ckpt_dir), interval_steps=1,
                program=pt.default_main_program(), scope=scope)
            assert daemon.capture(1, scope=scope)
            cell = monitor.REGISTRY.get("paddle_tpu_hbm_class_bytes")
            cls = {lbl["cls"]: c.get() for lbl, c in cell.series()}
            assert cls.get("ckpt_capture", 0) > 0
            daemon.start()
            daemon.stop(final_step=1)
            cls = {lbl["cls"]: c.get() for lbl, c in cell.series()}
            assert cls.get("ckpt_capture", 1) == 0
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)


def test_kv_tenant_series_retire_on_churn():
    """10-tenant churn: per-tenant KV gauges/counters stay exact and
    fold on eviction (PR-2 semantics — bounded registry,
    counter_totals() exact)."""
    fam_pages = monitor.SERVING_KV_TENANT_PAGES
    fam_frag = monitor.SERVING_KV_TENANT_FRAG
    fam_ctr = monitor.SERVING_KV_TENANT_ALLOC_CTR
    before = monitor.counter_totals().get(
        "paddle_tpu_serving_kv_tenant_pages_total", 0)
    tenants = [f"kvchurn{i}" for i in range(10)]
    for t in tenants:
        fam_ctr.inc(3, tenant=t)
        fam_pages.set(3.0, tenant=t)
        fam_frag.set(0.5, tenant=t)
    assert monitor.counter_totals().get(
        "paddle_tpu_serving_kv_tenant_pages_total", 0) == before + 30
    for t in tenants:
        monitor.retire_tenant_series(t)
    live_rows = [lbl for lbl, _c in fam_ctr.series()
                 if lbl["tenant"].startswith("kvchurn")]
    assert not live_rows
    assert not [lbl for lbl, _c in fam_pages.series()
                if lbl["tenant"].startswith("kvchurn")]
    assert not [lbl for lbl, _c in fam_frag.series()
                if lbl["tenant"].startswith("kvchurn")]
    # totals exact across the fold
    assert monitor.counter_totals().get(
        "paddle_tpu_serving_kv_tenant_pages_total", 0) == before + 30


def test_digest_carries_hbm_and_priority():
    scope = Scope()
    pt.set_flags({"FLAGS_memory_budget_mb": 64})
    try:
        with scope_guard(scope), program_guard(Program(), Program()):
            _train_loop(scope, steps=3)
            assert hbm.ACCOUNTANT.drain(30)
        d = monitor.metrics_digest()
        assert "hbm" in d and d["hbm"] > 0
        assert "hdrm" in d   # budget known -> headroom rides
        assert d["hbm"] + d["hdrm"] == 64 << 20
        # the capped digest sheds hbm/hdrm AFTER the straggler inputs
        # but BEFORE mfu-and-below; hbm outranks hdrm because a lone
        # hdrm renders nothing in gangtop (HDRM% needs both keys)
        pri = monitor._DIGEST_PRIORITY
        assert pri.index("hbm") < pri.index("hdrm") < pri.index("mfu")
        assert pri.index("step_ms") < pri.index("hbm")
        capped = monitor.capped_digest(dict(d), max_bytes=10_000)
        assert capped == d
    finally:
        pt.set_flags({"FLAGS_memory_budget_mb": 0})


def test_coordinator_folds_hbm_digest_keys():
    from paddle_tpu.distributed.coordinator import GangCoordinator
    GangCoordinator._fold_digest(
        GangCoordinator, 7, {"hbm": 1234.0, "hdrm": 99.0})
    assert monitor.GANG_RANK_HBM.value(rank="7") == 1234.0
    assert monitor.GANG_RANK_HDRM.value(rank="7") == 99.0
    # key stops riding -> series drops (frozen values never haunt a
    # router)
    GangCoordinator._fold_digest(GangCoordinator, 7, {})
    assert not [lbl for lbl, _c in monitor.GANG_RANK_HBM.series()
                if lbl.get("rank") == "7"]
    monitor.retire_gang_rank_series(7)


def test_gangtop_hbm_columns_and_oom_risk_flag():
    import gangtop
    status = {
        "ranks": {
            "0": {"alive": True, "cur_step": 5, "step": 4, "deaths": 0,
                  "age_s": 0.2,
                  "digest": {"step_ms": 10.0, "hbm": 15 << 30,
                             "hdrm": 1 << 30}},
            "1": {"alive": True, "cur_step": 5, "step": 4, "deaths": 0,
                  "age_s": 0.2,
                  "digest": {"step_ms": 10.0, "hbm": 8 << 30,
                             "hdrm": 8 << 30}},
        },
        "aggregates": {"straggler": -1}, "dead": [], "status": "ready",
    }
    out = gangtop.render(status)
    assert "HBM" in out and "HDRM%" in out
    lines = {l.split()[0]: l for l in out.splitlines() if
             l.strip().startswith(("0 ", "1 ")) or
             l.strip().split()[:1] in (["0"], ["1"])}
    assert "<-- OOM-RISK" in lines["0"]       # 1/16 = 6.25% headroom
    assert "<-- OOM-RISK" not in lines["1"]   # 50% headroom
    assert gangtop.oom_risk({"hbm": 100, "hdrm": 5})
    assert not gangtop.oom_risk({"hbm": 100, "hdrm": 50})
    assert not gangtop.oom_risk({"hbm": 100})   # no budget -> no flag


def test_timeline_memory_lane(tmp_path):
    import timeline
    src = tmp_path / "r0.json"
    events = [
        {"name": "hbm.sample", "ph": "i", "s": "t", "cat": "memory",
         "pid": 1, "tid": 777, "ts": 10.0},
        {"name": "hbm.live_bytes", "ph": "C", "cat": "memory",
         "pid": 1, "tid": 777, "ts": 11.0, "args": {"value": 123.0}},
        {"name": "executor.dispatch", "ph": "X", "cat": "dispatch",
         "pid": 1, "tid": 777, "ts": 10.0, "dur": 5.0},
    ]
    src.write_text(json.dumps({"traceEvents": events}))
    out = tmp_path / "merged.json"
    timeline.merge(f"0={src}", str(out), rank_lanes=True)
    merged = json.loads(out.read_text())["traceEvents"]
    mem = [e for e in merged if e.get("cat") == "memory"]
    assert mem and all(e["tid"] == timeline.MEM_LANE_TID for e in mem)
    names = [e for e in merged if e.get("ph") == "M"
             and e.get("name") == "thread_name"
             and e.get("tid") == timeline.MEM_LANE_TID]
    assert names and names[0]["args"]["name"] == "hbm"
    disp = [e for e in merged if e.get("name") == "executor.dispatch"]
    assert disp[0]["tid"] == 777        # compute rows stay put
    timeline.validate(str(out), strict=True)


def test_record_xla_plan_routes_through_shared_store():
    from paddle_tpu import memory as mem

    class _MA:
        argument_size_in_bytes = 100
        output_size_in_bytes = 40
        temp_size_in_bytes = 20
        alias_size_in_bytes = 30
        generated_code_size_in_bytes = 1
    entry = hbm.record_xla_plan("test_hbm_plan_tag", _MA(), block="train",
                                compiled_at=12.5,
                                classes={"params": 60, "feeds": 40})
    assert entry["peak_bytes"] == 100 + 40 + 20 + 1 - 30
    stored = mem.hbm_plans()["test_hbm_plan_tag"]
    assert (stored["block"], stored["compiled_at"]) == ("train", 12.5)
    assert stored["argument_classes"] == {"params": 60, "feeds": 40}
    lbl = {"block": "train", "tag": "test_hbm_plan_tag"}
    parts = monitor.REGISTRY.get("paddle_tpu_step_hbm_plan_bytes")
    assert {p: parts.value(part=p, **lbl) for p in hbm.PLAN_PARTS} == {
        "arguments": 100, "outputs": 40, "aliased": 30, "temporaries": 20,
        "code": 1}
    assert monitor.REGISTRY.get(
        "paddle_tpu_step_hbm_plan_compiled_at_seconds").value(**lbl) == 12.5
    assert monitor.REGISTRY.get(
        "paddle_tpu_step_hbm_argument_bytes").value(cls="params", **lbl) == 60
    # a second executable under the same fetch list keeps the first
    again = hbm.record_xla_plan("test_hbm_plan_tag", _MA())
    assert again["tag"] == "test_hbm_plan_tag#2" and again["block"] == "other"
    # the residency summary prints both through the one formatter
    assert "unaliased outputs" in mem.format_plan(stored)
    assert "params 60" in mem.format_plan(stored)


def test_headroom_regress_trigger_opens_window(tmp_path):
    """The headroom-regression trigger mirrors
    FLAGS_profile_sample_regress_frac: shrinking headroom past the
    fraction opens exactly one window (hysteresis re-arms only on
    recovery)."""
    from paddle_tpu.profiler import SAMPLER
    pt.set_flags({"FLAGS_profile_sample_dir": str(tmp_path),
                  "FLAGS_memory_budget_mb": 1,
                  "FLAGS_hbm_headroom_regress_frac": 0.3})
    try:
        acc = hbm.ACCOUNTANT
        base = 1000.0
        with acc._cv:
            opened = []
            for i, headroom in enumerate(
                    [base] * acc._REGRESS_WARMUP   # warmup at best
                    + [base * 0.5, base * 0.5,     # regressed: one trip
                       base, base * 0.5]):         # recover, trip again
                opened.append(acc._observe_headroom_locked(headroom))
        assert opened.count(True) == 2
        # the two trips bracket the recovery: sustained regression costs
        # one window, not one per sample
        first = opened.index(True)
        assert opened[first + 1] is False
    finally:
        pt.set_flags({"FLAGS_profile_sample_dir": "",
                      "FLAGS_memory_budget_mb": 0,
                      "FLAGS_hbm_headroom_regress_frac": 0.0})
        SAMPLER.close()
