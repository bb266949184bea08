"""Unified runtime telemetry (paddle_tpu/monitor.py): metrics registry
semantics + thread safety, step-tracer spans across all four pipeline
layers in one chrome trace, registry-backed dispatch counters as the one
source of truth, multi-executor aggregation, per-rank fetch
materialization, and the dedicated fetch-less throttle probe."""

import json
import os
import sys
import threading

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, monitor, profiler
from paddle_tpu.framework import Executor
from paddle_tpu.framework.core import Program, program_guard
from paddle_tpu.framework.executor import aggregate_dispatch_stats
from paddle_tpu.framework.scope import Scope, scope_guard

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))
import timeline  # noqa: E402  (tools/timeline.py: merge + validators)


def _build_train_step(scope):
    x = layers.data("x", shape=[8], dtype="float32")
    h = layers.fc(x, size=16, act="relu")
    loss = layers.mean(layers.fc(h, size=4))
    fluid.optimizer.SGD(0.01).minimize(loss)
    exe = Executor()
    exe.run(fluid.default_startup_program(), scope=scope)
    return exe, loss


FEED = {"x": np.ones((4, 8), np.float32)}


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_registry_counter_gauge_histogram_basics():
    reg = monitor.MetricsRegistry()
    c = reg.counter("t_requests", "requests", ("code",))
    c.inc(1, code="200")
    c.inc(2, code="200")
    c.inc(1, code="500")
    assert c.value(code="200") == 3
    assert c.value(code="500") == 1

    g = reg.gauge("t_depth", "queue depth")
    g.set(4)
    g.inc(2)
    assert g.value() == 6

    h = reg.histogram("t_lat_us", "latency", buckets=(10.0, 100.0, 1000.0))
    for v in (5, 50, 500, 5000):
        h.observe(v)
    s = [m for m in reg.collect() if m["name"] == "t_lat_us"][0]["series"][0]
    assert s["counts"] == [1, 1, 1, 1]      # one per bucket + one overflow
    assert s["count"] == 4 and s["sum"] == 5555

    # get-or-create returns the same family; a kind clash is an error
    assert reg.counter("t_requests", labelnames=("code",)) is c
    with pytest.raises(ValueError):
        reg.gauge("t_requests")
    with pytest.raises(ValueError):
        c.inc(1, wrong_label="x")


def test_registry_prometheus_and_json_export_parse():
    reg = monitor.MetricsRegistry()
    c = reg.counter("t_total", "help with \\ and\nnewline", ("mode",))
    c.inc(3, mode='we"ird')
    h = reg.histogram("t_hist_us", "h", buckets=(1.0, 10.0))
    h.observe(0.5)
    h.observe(99)
    prom = reg.to_prometheus()
    n = timeline.validate_prometheus(prom)      # raises on malformed lines
    # counter sample + 3 buckets + sum + count
    assert n == 6
    assert 'le="+Inf"} 2' in prom
    assert "t_hist_us_sum" in prom

    data = json.loads(reg.to_json())
    by_name = {m["name"]: m for m in data["metrics"]}
    assert by_name["t_total"]["series"][0]["value"] == 3
    assert by_name["t_hist_us"]["type"] == "histogram"


def test_registry_thread_safety_exact_counts():
    """Concurrent inc() from many threads must not lose updates, and
    concurrent exporters must not crash or corrupt state (the registry is
    bumped from run() threads, producer threads, and consumer threads)."""
    reg = monitor.MetricsRegistry()
    c = reg.counter("t_conc", "", ("who",))
    h = reg.histogram("t_conc_h", "", buckets=(10.0, 100.0))
    N, T = 5000, 8
    errs = []

    def bump(i):
        try:
            cell = c.labels(who=str(i % 2))
            for _ in range(N):
                cell.inc()
                h.observe(50)
        except Exception as e:              # pragma: no cover
            errs.append(e)

    def export():
        try:
            for _ in range(50):
                reg.to_prometheus()
                reg.to_json()
        except Exception as e:              # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=bump, args=(i,)) for i in range(T)]
    threads.append(threading.Thread(target=export))
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs, errs
    assert c.value(who="0") + c.value(who="1") == N * T
    s = [m for m in reg.collect()
         if m["name"] == "t_conc_h"][0]["series"][0]
    assert s["count"] == N * T


# ---------------------------------------------------------------------------
# dispatch counters: registry as the one source of truth
# ---------------------------------------------------------------------------

def test_dispatch_counters_one_source_of_truth():
    """`Executor.dispatch_stats()`, the profiler aggregate, and the
    registry export must agree EXACTLY — they read one store."""
    scope = Scope()
    with scope_guard(scope), program_guard(Program(), Program()):
        exe, loss = _build_train_step(scope)
        for _ in range(5):
            exe.run(feed=FEED, fetch_list=[loss.name], scope=scope)
        stats = exe.dispatch_stats()
        serial = str(exe._stats.serial)

        by_name = {m["name"]: m
                   for m in json.loads(monitor.REGISTRY.to_json())["metrics"]}
        for f in ("steps_dispatched", "cache_hits", "cache_misses",
                  "traces", "eager_fetch_steps", "fetch_materializations"):
            fam = by_name["paddle_tpu_executor_" + f]
            mine = [s for s in fam["series"]
                    if s["labels"]["executor"] == serial]
            assert len(mine) == 1
            assert mine[0]["value"] == stats[f], f

        prom = monitor.REGISTRY.to_prometheus()
        assert (f'paddle_tpu_executor_steps_dispatched'
                f'{{executor="{serial}"}} '
                f'{stats["steps_dispatched"]}') in prom


def test_aggregate_dispatch_stats_multi_executor_and_reset():
    """Aggregation across multiple LIVE executors, after a per-executor
    reset, and after one executor dies (live-executor semantics)."""
    scope = Scope()
    with scope_guard(scope), program_guard(Program(), Program()):
        exe1, loss = _build_train_step(scope)
        exe2 = Executor()
        for _ in range(3):
            exe1.run(feed=FEED, fetch_list=[loss.name], scope=scope)
        for _ in range(2):
            exe2.run(feed=FEED, fetch_list=[loss.name], scope=scope)
        s1, s2 = exe1.dispatch_stats(), exe2.dispatch_stats()
        agg = aggregate_dispatch_stats()
        assert agg["executors"] >= 2
        # the aggregate is the exact sum over live executors (other tests'
        # executors are dead: _EXECUTORS is weak)
        assert agg["steps_dispatched"] >= \
            s1["steps_dispatched"] + s2["steps_dispatched"]
        assert profiler.dispatch_stats() == aggregate_dispatch_stats()

        base_steps = agg["steps_dispatched"]
        exe2.reset_dispatch_stats()
        assert exe2.dispatch_stats()["steps_dispatched"] == 0
        assert exe1.dispatch_stats()["steps_dispatched"] == \
            s1["steps_dispatched"]          # exe1 untouched by exe2 reset
        agg2 = aggregate_dispatch_stats()
        assert agg2["steps_dispatched"] == \
            base_steps - s2["steps_dispatched"]

        # a dead executor leaves the live aggregate; its series folds into
        # executor="retired" so process-lifetime totals stay exact while
        # registry growth stays bounded under executor churn
        serial1 = str(exe1._stats.serial)
        tot_before = monitor.counter_totals()[
            "paddle_tpu_executor_steps_dispatched"]
        del exe1
        import gc
        gc.collect()
        agg3 = aggregate_dispatch_stats()
        assert agg3["steps_dispatched"] <= agg2["steps_dispatched"]
        flat = monitor.telemetry_snapshot()
        key = ('paddle_tpu_executor_steps_dispatched'
               f'{{executor="{serial1}"}}')
        assert key not in flat               # per-serial series retired
        assert monitor.counter_totals()[
            "paddle_tpu_executor_steps_dispatched"] == tot_before
        assert flat['paddle_tpu_executor_steps_dispatched'
                    '{executor="retired"}'] >= s1["steps_dispatched"]


def test_a_finalizer_that_folds_a_family_under_its_own_lock_does_not_hang():
    """A collection may start between two bytecodes of ``series`` (or any
    holder of a family's lock) and run a dead executor's finalizer, whose
    ``retire`` takes the same family's lock in the same thread: tier-1 hung
    there once (PR 48: ``test_coordinator`` inside ``counter_totals``).  The
    lock is re-entrant, so the fold goes through and the totals stay exact."""
    fam = monitor.Counter("pr48_reentrant_total", "t", ("executor",))
    fam.labels(executor="7").inc(3)
    done = []

    def finalizer():                    # what _ExecStats.retire does
        fam.labels(executor="retired")
        fam.fold({"executor": "7"}, {"executor": "retired"})
        done.append(True)
    worker = threading.Thread(target=lambda: _under(fam, finalizer),
                              daemon=True)
    worker.start()
    worker.join(10)
    assert done and not worker.is_alive()
    assert {tuple(k.items()): c.get() for k, c in fam.series()} == \
        {(("executor", "retired"),): 3}


def _under(fam, fn):
    with fam._mu:
        fn()


def test_dispatch_stats_concurrent_run_threads_exact():
    """Registry-backed counters under concurrent run() threads: the final
    counts must be exact (lost updates would silently undercount)."""
    scope = Scope()
    with scope_guard(scope), program_guard(Program(), Program()):
        x = layers.data("x", shape=[6], dtype="float32")
        y = layers.mean(layers.fc(x, size=3))
        exe = Executor()
        exe.run(fluid.default_startup_program(), scope=scope)
        feed = {"x": np.ones((2, 6), np.float32)}
        exe.run(feed=feed, fetch_list=[y.name], scope=scope)
        base = exe.dispatch_stats()
        errs = []

        def worker():
            try:
                for _ in range(25):
                    exe.run(feed=feed, fetch_list=[y.name], scope=scope,
                            return_numpy=False)
            except Exception as e:          # pragma: no cover
                errs.append(e)

        threads = [threading.Thread(target=worker) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs, errs
        s = exe.dispatch_stats()
        assert s["steps_dispatched"] - base["steps_dispatched"] == 100
        assert s["lazy_fetch_steps"] - base["lazy_fetch_steps"] == 100


# ---------------------------------------------------------------------------
# step tracer + end-to-end four-layer trace
# ---------------------------------------------------------------------------

def test_tracer_disabled_records_nothing():
    fluid.set_flags({"FLAGS_telemetry": False})
    try:
        assert not monitor.TRACER.enabled
        n0 = len(monitor.TRACER)
        with monitor.span("t.should_not_appear", "test"):
            pass
        assert len(monitor.TRACER) == n0
    finally:
        fluid.set_flags({"FLAGS_telemetry": True})
    assert monitor.TRACER.enabled


def test_end_to_end_four_layer_trace_and_matching_export(tmp_path):
    """Acceptance demo: one training loop through the prefetching
    dataloader produces a chrome trace with spans from all four layers
    (dataloader staging, compile, dispatch/throttle, fetch
    materialization) in a single timeline, and a JSON+Prometheus export
    whose dispatch counters match Executor.dispatch_stats() exactly."""
    from paddle_tpu.data.dataloader import _prefetch_to_device

    fluid.set_flags({"FLAGS_telemetry": True})
    scope = Scope()
    with scope_guard(scope), program_guard(Program(), Program()):
        exe, loss = _build_train_step(scope)

        def batches():
            for i in range(6):
                yield {"x": np.full((4, 8), 0.1 * i, np.float32)}

        h = None
        for feed in _prefetch_to_device(batches, capacity=2):
            h, = exe.run(feed=feed, fetch_list=[loss.name], scope=scope,
                         return_numpy=False)
        assert np.isfinite(h.numpy())
        stats = exe.dispatch_stats()
        serial = str(exe._stats.serial)

    paths = monitor.export(str(tmp_path))
    tstats = timeline.validate(paths["trace"])
    assert {"dataloader", "compile", "dispatch", "fetch"} <= tstats["cats"]
    for name in ("dataloader.stage_batch", "xla.compile",
                 "executor.dispatch", "fetch.materialize"):
        assert name in tstats["names"], name

    # compile spans carry the persistent-cache outcome
    evs = json.load(open(paths["trace"]))["traceEvents"]
    compile_evs = [e for e in evs if e["name"] == "xla.compile"]
    assert compile_evs and all(
        e["args"]["persist_cache"] in ("off", "hit", "miss")
        for e in compile_evs)

    # exported dispatch counters == dispatch_stats(), exactly
    by_name = {m["name"]: m
               for m in json.load(open(paths["json"]))["metrics"]}
    for f in ("steps_dispatched", "cache_hits", "traces",
              "lazy_fetch_steps", "fetch_materializations",
              "throttle_waits"):
        series = [s for s in by_name["paddle_tpu_executor_" + f]["series"]
                  if s["labels"]["executor"] == serial]
        assert series[0]["value"] == stats[f], f

    timeline.validate_prometheus(open(paths["prom"]).read())

    # per-rank merge stacks into one timeline with rank-prefixed pids
    merged = str(tmp_path / "merged.json")
    timeline.merge(f"0={paths['trace']},1={paths['trace']}", merged,
                   align=True)
    mstats = timeline.validate(merged)
    assert mstats["events"] == 2 * tstats["events"]
    pids = {e["pid"] for e in json.load(open(merged))["traceEvents"]}
    assert any(str(p).startswith("rank0:") for p in pids)
    assert any(str(p).startswith("rank1:") for p in pids)


def test_profiler_chrome_trace_merges_record_events_and_spans(tmp_path):
    """RecordEvent profiler events and tracer spans land in ONE file."""
    scope = Scope()
    with scope_guard(scope), program_guard(Program(), Program()):
        exe, loss = _build_train_step(scope)
        profiler.start_profiler()
        try:
            with profiler.RecordEvent("user_marked_region"):
                exe.run(feed=FEED, fetch_list=[loss.name], scope=scope)
        finally:
            profiler.stop_profiler()
    path = str(tmp_path / "trace.json")
    profiler.chrome_trace(path)
    names = timeline.validate(path)["names"]
    assert "user_marked_region" in names     # profiler source
    assert "executor.dispatch" in names      # tracer source


def test_queue_depth_metrics_populated():
    """Per-pipeline occupancy series exist while iterating and fold into
    pipeline="retired" when the pipeline ends (totals preserved)."""
    from paddle_tpu.data.dataloader import _prefetch_to_device

    def totals():
        t = monitor.counter_totals()
        return (t.get("paddle_tpu_dataloader_queue_occupancy_count", 0),
                t.get("paddle_tpu_dataloader_batches_staged", 0))

    occ0, staged0 = totals()

    def gen():
        for i in range(5):
            yield {"x": np.zeros((2, 2), np.float32)}

    for _ in _prefetch_to_device(gen, capacity=2):
        pass
    occ1, staged1 = totals()
    # one occupancy sample per consumer get: 5 batches + the end sentinel
    assert occ1 - occ0 == 6
    assert staged1 - staged0 == 5
    # the finished pipeline's series were folded into "retired"
    occ = monitor.REGISTRY.get("paddle_tpu_dataloader_queue_occupancy")
    labels = [s["labels"]["pipeline"]
              for m in monitor.REGISTRY.collect()
              if m["name"] == occ.name for s in m["series"]]
    assert "retired" in labels


def test_assemble_local_shards_multi_axis():
    """local_numpy's shard assembly: rectangular tilings over one OR two
    axes paste into the local bounding box (a single-axis concatenate
    would silently mis-stack 2-D tilings), replicated copies dedupe, and
    slice keys are hashable on every Python version."""
    from paddle_tpu.framework.executor import _assemble_local_shards

    class FakeShard:
        def __init__(self, index, data):
            self.index, self.data = index, data

    class FakeArray:
        def __init__(self, shape, shards):
            self.shape, self.addressable_shards = shape, shards

    full = np.arange(16, dtype=np.float32).reshape(4, 4)
    # 2x2 tiling over BOTH axes, with one replicated duplicate
    shards = [FakeShard((slice(r, r + 2), slice(c, c + 2)),
                        full[r:r + 2, c:c + 2])
              for r in (0, 2) for c in (0, 2)]
    shards.append(FakeShard((slice(0, 2), slice(0, 2)), full[0:2, 0:2]))
    np.testing.assert_array_equal(
        _assemble_local_shards(FakeArray((4, 4), shards)), full)

    # this process holds only the lower-right half: bbox-local assembly
    sub = [FakeShard((slice(2, 4), slice(2, 4)), full[2:4, 2:4])]
    np.testing.assert_array_equal(
        _assemble_local_shards(FakeArray((4, 4), sub)), full[2:4, 2:4])

    # 1-axis sharding with slice(None) on the replicated axis
    rows = [FakeShard((slice(r, r + 2), slice(None)), full[r:r + 2])
            for r in (2, 0)]
    np.testing.assert_array_equal(
        _assemble_local_shards(FakeArray((4, 4), rows)), full)

    # NON-contiguous local shards (interleaved process layout): no dense
    # local array exists — must refuse, not return np.empty garbage
    gap = [FakeShard((slice(r, r + 1), slice(None)), full[r:r + 1])
           for r in (0, 3)]
    with pytest.raises(ValueError, match="contiguously tile"):
        _assemble_local_shards(FakeArray((4, 4), gap))


# ---------------------------------------------------------------------------
# satellites: throttle probe, local_numpy, compile telemetry
# ---------------------------------------------------------------------------

def test_fetchless_loop_has_waitable_probe_and_throttle_engages():
    """A fetch-less lazy loop (train_from_dataset without fetch_list) used
    to fall back to rw-state probes that the next step donates; the
    dedicated probe output is never donated, so the throttle always has a
    live waitable array and its wait histogram populates."""
    scope = Scope()
    with scope_guard(scope), program_guard(Program(), Program()):
        exe, loss = _build_train_step(scope)
        base = exe.dispatch_stats()
        fluid.set_flags({"FLAGS_executor_max_inflight_steps": 1})
        try:
            for _ in range(5):
                out = exe.run(feed=FEED, scope=scope, return_numpy=False)
                assert out == []             # fetch-less
            with exe._lock:
                probes = list(exe._inflight)
            assert probes, "fetch-less steps left no throttle probe"
            for p in probes:
                assert hasattr(p, "block_until_ready")
                assert not p.is_deleted()    # never donated away
                p.block_until_ready()
            s = exe.dispatch_stats()
            assert s["throttle_waits"] - base["throttle_waits"] >= 3
            assert s["steps_in_flight"] <= 1
        finally:
            fluid.set_flags({"FLAGS_executor_max_inflight_steps": 2})


def test_train_from_dataset_fetchless_throttled():
    scope = Scope()
    with scope_guard(scope), program_guard(Program(), Program()):
        exe, loss = _build_train_step(scope)
        batches = [{"x": np.full((4, 8), i, np.float32)} for i in range(6)]
        base = exe.dispatch_stats()
        exe.train_from_dataset(fluid.default_main_program(),
                               dataset=iter(batches), scope=scope)
        s = exe.dispatch_stats()
        assert s["steps_dispatched"] - base["steps_dispatched"] == 6
        assert s["throttle_waits"] - base["throttle_waits"] >= 3
        assert s["steps_in_flight"] == 0     # loop end drains probes


def test_local_numpy_matches_numpy_single_process():
    scope = Scope()
    with scope_guard(scope), program_guard(Program(), Program()):
        x = layers.data("x", shape=[4], dtype="float32")
        y = layers.scale(x, scale=3.0)
        exe = Executor()
        exe.run(fluid.default_startup_program(), scope=scope)
        h, = exe.run(feed={"x": np.ones((2, 4), np.float32)},
                     fetch_list=[y.name], scope=scope, return_numpy=False)
        np.testing.assert_allclose(h.local_numpy(), np.full((2, 4), 3.0))
        np.testing.assert_allclose(h.local_numpy(), h.numpy())


def test_compile_telemetry_counts_and_persist_label(tmp_path):
    """Every fresh lowering records one compile event, labelled by what
    the persistent cache did, as jax.monitoring says: it is always placed
    (device.place_compile_cache), so the label is hit/miss, never 'off'."""
    ctr = monitor.REGISTRY.get("paddle_tpu_compile_total")

    def total():
        return sum(s["value"] for m in monitor.REGISTRY.collect()
                   if m["name"] == "paddle_tpu_compile_total"
                   for s in m["series"])

    n0 = total()
    off0 = ctr.value(persist="off")
    scope = Scope()
    with scope_guard(scope), program_guard(Program(), Program()):
        exe, loss = _build_train_step(scope)   # 2 fresh lowerings
        exe.run(feed=FEED, fetch_list=[loss.name], scope=scope)
    assert total() - n0 == 2
    assert ctr.value(persist="off") == off0

    hist = monitor.REGISTRY.get("paddle_tpu_compile_ms")
    _, s, c = hist.labels().snapshot()
    assert c >= 2 and s > 0
