"""The compiled blocks' memory plans (PR 51): every block's
``memory_analysis()`` recorded where it compiles, always and at no compile's
cost; the arguments by the program's own classes; the accountant's
``step_temporaries`` class and what it takes from the headroom; and
``paddle_tpu_compile_total{persist, block}`` fed by ``jax.monitoring``'s
cache events."""

import os
import time

import jax
import jax.monitoring
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import hbm, layers, memory, monitor
from paddle_tpu.framework import Executor, executor as E
from paddle_tpu.framework.scope import global_scope

LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
#: JAX's duration events while a test listens (jax.monitoring has no scoped
#: listener: this one is registered once and hears only while armed)
_heard = None


def _listen(event, secs, **_):
    if _heard is not None:
        _heard.append(event)


jax.monitoring.register_event_duration_secs_listener(_listen)


@pytest.fixture
def hook_events(monkeypatch):
    """JAX's duration events fired from inside the executor's plan hook,
    one list a call of the hook."""
    calls = []
    real = hbm.record_compiled_plan

    def watched(*args, **kw):
        global _heard
        _heard = []
        try:
            return real(*args, **kw)
        finally:
            calls.append(_heard)
            _heard = None

    monkeypatch.setattr(hbm, "record_compiled_plan", watched)
    return calls


def _mlp(feed_batch=8):
    x = layers.data("x", shape=[16], dtype="float32")
    h = layers.fc(x, size=32, act="relu")
    loss = layers.mean(layers.fc(h, size=8))
    pt.optimizer.Adam(1e-3).minimize(loss)
    exe = Executor()
    exe.run(pt.default_startup_program())
    feed = {"x": np.linspace(-1, 1, feed_batch * 16,
                             dtype=np.float32).reshape(feed_batch, 16)}
    return exe, loss, feed


def _train_plans():
    return {t: p for t, p in memory.hbm_plans().items()
            if p["block"] == "train"}


def _gauge(name, **labels):
    return monitor.REGISTRY.get(name).value(**labels)


# (a) ------------------------------------------------------------------------

@pytest.mark.parametrize("parallel", [False, True],
                         ids=["donated", "data_parallel_2"])
def test_first_run_records_a_train_plan_and_compiles_nothing(
        parallel, hook_events):
    exe, loss, feed = _mlp()
    before = set(_train_plans())
    n_startup = len(hook_events)
    assert n_startup == 1                 # the startup program's block
    prog = pt.default_main_program()
    if parallel:
        prog = pt.CompiledProgram(prog).with_data_parallel(
            loss_name=loss.name, places=2)
    retraces0 = _retraces()
    errors0 = _gauge("paddle_tpu_step_hbm_plan_records_total",
                     outcome="error")
    t0 = time.perf_counter()
    for _ in range(3):
        exe.run(prog, feed=feed, fetch_list=[loss.name])
    new = {t: p for t, p in _train_plans().items() if t not in before}
    # one plan a compile: the first call's, and one more where JAX compiled
    # the block again in a later call (new argument shardings)
    assert len(new) == 1 + (_retraces() - retraces0) == \
        len(hook_events) - n_startup
    # tagged by the fetch list, '#n' from the second under one list on (the
    # store is the process's: earlier tests' steps fetched this name too)
    assert {t.split("#")[0] for t in new} == {loss.name}
    for tag, plan in new.items():
        for part, key in hbm.PLAN_PARTS.items():
            assert _gauge("paddle_tpu_step_hbm_plan_bytes", block="train",
                          tag=tag, part=part) == plan[key]
        assert plan["argument_bytes"] > 0 and plan["temp_bytes"] > 0
        # the rw state is donated: the outputs that took an argument's
        # buffer are (nearly) all of them
        assert plan["alias_bytes"] > 0.9 * plan["output_bytes"]
        assert t0 < plan["compiled_at"] < time.perf_counter()
        assert _gauge("paddle_tpu_step_hbm_plan_compiled_at_seconds",
                      block="train", tag=tag) == plan["compiled_at"]
        assert 0 < plan["hook_ms"] < 1000
    # the hook's second lowering is served by JAX's caches: nothing is
    # lowered, nothing reaches the backend
    for events in hook_events:
        assert LOWER not in events and BACKEND not in events, events
    assert _gauge("paddle_tpu_step_hbm_plan_records_total",
                  outcome="error") == errors0


def _retraces():
    fam = monitor.REGISTRY.get("paddle_tpu_compile_phase_seconds")
    return sum(cell.snapshot()[2] for labels, cell in fam.series()
               if labels["phase"] == "retrace")


def test_cost_crosscheck_compiles_the_step_once(hook_events):
    """The cross-check's AOT compile and the jitted call share JAX's
    caches too: one backend compile for the block, with the check on."""
    global _heard
    exe, loss, feed = _mlp()
    pt.set_flags({"FLAGS_cost_crosscheck": True})
    try:
        _heard = heard = []
        exe.run(feed=feed, fetch_list=[loss.name])
    finally:
        _heard = None
        pt.set_flags({"FLAGS_cost_crosscheck": False})
    assert heard.count(BACKEND) == 1 and heard.count(LOWER) == 1


def test_a_failing_hook_costs_the_plan_not_the_step(monkeypatch):
    exe, loss, feed = _mlp()
    errors0 = _gauge("paddle_tpu_step_hbm_plan_records_total",
                     outcome="error")
    monkeypatch.setattr(hbm, "compiled_plan",
                        lambda *a, **k: 1 / 0)
    before = set(_train_plans())
    out, = exe.run(feed=feed, fetch_list=[loss.name])
    assert np.isfinite(out)
    assert set(_train_plans()) == before
    assert _gauge("paddle_tpu_step_hbm_plan_records_total",
                  outcome="error") == errors0 + 1


# (b) ------------------------------------------------------------------------

def test_argument_classes_add_up_to_the_plans_arguments():
    exe, loss, feed = _mlp(feed_batch=64)
    exe.run(feed=feed, fetch_list=[loss.name])
    tag, plan = list(_train_plans().items())[-1]
    classes = plan["argument_classes"]
    assert set(classes) == set(hbm.ARGUMENT_CLASSES)
    assert abs(sum(classes.values()) - plan["argument_bytes"]) \
        <= 0.01 * plan["argument_bytes"]
    for cls, n in classes.items():
        assert _gauge("paddle_tpu_step_hbm_argument_bytes", block="train",
                      tag=tag, cls=cls) == n
    # and to what the scope holds of the block's persistables, the feed and
    # the seed
    cb = [c for c in exe._cache.values() if c.fetch_names == (loss.name,)][-1]
    scope = global_scope()
    block = pt.default_main_program().global_block()
    held = {"params": 0, "opt_state": 0}
    for n in cb.persist_ro + cb.persist_rw:
        cls = "params" if block.var(n).is_parameter else "opt_state"
        held[cls] += scope.find_var(n).nbytes
    assert classes["params"] == held["params"] > 0
    assert classes["opt_state"] == held["opt_state"] > 0
    assert classes["feeds"] == feed["x"].nbytes
    assert classes["other"] == 4                       # the uint32 seed
    assert abs(sum(held.values()) + feed["x"].nbytes + 4
               - plan["argument_bytes"]) <= 0.01 * plan["argument_bytes"]


def test_a_feed_the_step_shards_counts_its_shard():
    """Per device, as memory_analysis() is: under data parallel the host
    batch enters whole and the executable takes a half of it a device."""
    exe, loss, feed = _mlp(feed_batch=64)
    prog = pt.CompiledProgram(pt.default_main_program()).with_data_parallel(
        loss_name=loss.name, places=2)
    exe.run(prog, feed=feed, fetch_list=[loss.name])
    plan = list(_train_plans().values())[-1]
    assert plan["argument_classes"]["feeds"] == feed["x"].nbytes // 2
    assert abs(sum(plan["argument_classes"].values())
               - plan["argument_bytes"]) <= 0.01 * plan["argument_bytes"]


# (e) ------------------------------------------------------------------------

def _sampled(info, budget_mb=64):
    """The accountant's gauges after one sample of ``info``."""
    pt.set_flags({"FLAGS_memory_budget_mb": budget_mb})
    try:
        acc = hbm.ACCOUNTANT
        assert acc.drain(30)
        acc.note_step(1, None, info)
        assert acc.drain(30)
        cls = {lbl["cls"]: c.get() for lbl, c in
               monitor.REGISTRY.get("paddle_tpu_hbm_class_bytes").series()}
        return (cls, _gauge("paddle_tpu_hbm_live_bytes"),
                _gauge("paddle_tpu_hbm_headroom_bytes"), acc.last_sample)
    finally:
        pt.set_flags({"FLAGS_memory_budget_mb": 0})


def test_accountant_counts_the_plans_temporaries_against_the_budget():
    keep = jax.device_put(np.ones(1024, np.float32))      # something live
    budget = 64 << 20
    cls, live, headroom, last = _sampled(
        {"params": (), "opt_state": (), "step_temporaries": 5 << 20})
    assert cls["step_temporaries"] == 5 << 20
    assert live >= keep.nbytes
    assert headroom == budget - live - (5 << 20)
    # the digest's pair still adds up to the budget (gangtop's HDRM%)
    assert last == (live + (5 << 20), headroom)
    # no plan (a block whose hook failed, a foreign program): as before
    for info in ({"params": (), "opt_state": ()}, None):
        cls, live, headroom, last = _sampled(info)
        assert cls["step_temporaries"] == 0
        assert headroom == budget - live and last == (live, headroom)
    del keep


def test_the_executors_samples_carry_their_blocks_plan():
    exe, loss, feed = _mlp()
    for _ in range(2):
        exe.run(feed=feed, fetch_list=[loss.name])
    assert hbm.ACCOUNTANT.drain(30)
    cb = [c for c in exe._cache.values() if c.fetch_names == (loss.name,)][-1]
    plan = list(_train_plans().values())[-1]
    assert cb.hbm_info["step_temporaries"] == plan["temp_bytes"] > 0
    assert _gauge("paddle_tpu_hbm_class_bytes", cls="step_temporaries") \
        == plan["temp_bytes"]


def test_a_jit_with_compiler_options_has_its_plan_read_on_demand(
        monkeypatch, hook_events):
    """JAX keeps no executable compiled under options: each ``.compile()``
    of such a lowering builds the wrapper again (seconds on a four-chip
    TPU), so the executor leaves that block's plan to the first reader,
    the newest compile of the block standing for the older."""
    monkeypatch.setattr(
        E, "dp_overlap_options", lambda mesh, platform:
        ({"xla_cpu_enable_fast_math": False}, "test") if mesh is not None
        else (None, "no_mesh"))
    exe, loss, feed = _mlp()
    prog = pt.CompiledProgram(pt.default_main_program()).with_data_parallel(
        loss_name=loss.name, places=2)
    before = len(memory.hbm_plans())
    n_hooks = len(hook_events)
    t0 = time.perf_counter()
    for _ in range(3):
        exe.run(prog, feed=feed, fetch_list=[loss.name])
    t1 = time.perf_counter()
    assert hbm.ACCOUNTANT.drain(30)
    cb = [c for c in exe._cache.values() if c.fetch_names == (loss.name,)][-1]
    assert id(cb.jitted) in E._OPTION_JITS
    # nothing read where it compiled: the samples carry no temporaries yet
    assert all(not events for events in hook_events[n_hooks:])
    assert len(memory._HBM_PLANS) == before
    assert "step_temporaries" not in cb.hbm_info
    n_hooks = len(hook_events)
    plans = memory.hbm_plans()                      # the first reader
    # (a worker whose earlier test files filled the store's window keeps its
    # length: the new plan pushes the oldest out)
    grown = min(before + 1, memory.MAX_HBM_PLANS)
    assert len(plans) == grown
    # read late it is still served by JAX's caches, the seed a host scalar
    # in the device array's place (the deferred call pins no device buffer)
    late, = hook_events[n_hooks:]
    assert LOWER not in late and BACKEND not in late, late
    plan = list(plans.values())[-1]
    assert plan["block"] == "train" and plan["temp_bytes"] > 0
    assert t0 < plan["compiled_at"] < t1            # when it compiled
    assert cb.hbm_info["step_temporaries"] == plan["temp_bytes"]
    assert len(memory.hbm_plans()) == grown         # and once


def test_the_plan_store_is_a_window(monkeypatch):
    class _MA:
        argument_size_in_bytes = 8
        output_size_in_bytes = temp_size_in_bytes = 0
        alias_size_in_bytes = generated_code_size_in_bytes = 0
    monkeypatch.setattr(memory, "MAX_HBM_PLANS", len(memory.hbm_plans()) + 2)
    tags = [hbm.record_xla_plan(f"window_test_{i}", _MA(),
                                classes={"other": 8})["tag"]
            for i in range(3)]
    plans = memory.hbm_plans()
    assert len(plans) == memory.MAX_HBM_PLANS
    assert tags[1] in plans and tags[2] in plans
    # what left the window took its series along: the registry is bounded
    for fam in ("paddle_tpu_step_hbm_plan_bytes",
                "paddle_tpu_step_hbm_argument_bytes",
                "paddle_tpu_step_hbm_plan_compiled_at_seconds"):
        tagged = {lbl["tag"] for lbl, _ in
                  monitor.REGISTRY.get(fam).series()}
        assert tagged == set(plans), fam
    assert len(memory.summary().split("hbm plan [")) - 1 \
        == memory.SUMMARY_PLANS
    long = hbm.record_xla_plan("x" * 200, _MA())["tag"]
    assert len(long) == memory.MAX_TAG_CHARS


# -- paddle_tpu_compile_total{persist, block} ----------------------------------

@pytest.mark.parametrize("fired,want", [
    ((), "off"),
    (("compile_requests_use_cache", "cache_hits"), "hit"),
    (("compile_requests_use_cache",), "miss"),      # under the threshold
    (("compile_requests_use_cache", "cache_misses"), "miss"),
    (("cache_misses",), "miss"),
    (("compile_requests_use_cache", "cache_hits",
      "compile_requests_use_cache", "cache_misses"), "miss"),
])
def test_cache_outcome_from_jax_monitorings_events(fired, want):
    E._install_phase_listener()
    E._phase_sink.events = sink = []
    try:
        for name in fired:
            jax.monitoring.record_event("/jax/compilation_cache/" + name)
        jax.monitoring.record_event("/jax/compilation_cache/tasks_using_cache")
    finally:
        E._phase_sink.events = None
    assert len(sink) == len(fired)
    assert E._cache_outcome(sink) == want
    # and nothing is heard outside a dispatch
    jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    assert len(sink) == len(fired)


def test_a_miss_beside_a_full_directory_reads_miss(tmp_path):
    """The parent's counter compared the cache directory's listing before
    and after, and read ``hit`` wherever it did not grow.  Here it cannot
    grow (these compiles are under JAX's persist threshold), it holds
    entries, and XLA compiles: ``miss``, on the counter and on the spans."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    for i in range(3):
        (tmp_path / f"jit_step-{i}-cache").write_bytes(b"x" * 64)
    old = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    cc.reset_cache()
    ctr = monitor.REGISTRY.get("paddle_tpu_compile_total")
    miss0 = ctr.value(persist="miss", block="train")
    hit0 = ctr.value(persist="hit")
    monitor.TRACER.clear()
    pt.set_flags({"FLAGS_telemetry": True})
    try:
        exe, loss, feed = _mlp()
        exe.run(feed=feed, fetch_list=[loss.name])
    finally:
        jax.config.update("jax_compilation_cache_dir", old)
        cc.reset_cache()
    assert sorted(os.listdir(tmp_path)) == [
        f"jit_step-{i}-cache" for i in range(3)]
    assert ctr.value(persist="miss", block="train") == miss0 + 1
    assert ctr.value(persist="hit") == hit0
    spans = [e for e in monitor.TRACER.chrome_events()
             if e.get("name") in ("xla.compile", "compile.backend")]
    assert len(spans) == 4            # the startup block's and the step's
    assert {e["args"]["persist_cache"] for e in spans} == {"miss"}


# -- which number "temporaries" is ---------------------------------------------

@pytest.mark.parametrize("peak,want_temp,why", [
    # Trinity-Mini's step as the TPU compiler reports it (PERF.md section 6,
    # PR 51), MB: temp_size_in_bytes 8603 beside a peak of 15495 that holds
    # 8466 of arguments: 7029 live at the peak, which is what fits the chip
    (15495, 7029, "the temporaries at the executable's peak"),
    # the CPU backend's peak is arguments + outputs and no more
    (8466 + 8466, 8603, "a peak that says nothing of temporaries"),
    (0, 8603, "a backend that reports no peak"),
    (8466 + 9000, 8603, "a peak over what the parts add up to"),
])
def test_temporaries_are_those_at_the_executables_peak(peak, want_temp, why):
    class _MA:
        argument_size_in_bytes = 8466
        output_size_in_bytes = 8466
        alias_size_in_bytes = 8466
        temp_size_in_bytes = 8603
        generated_code_size_in_bytes = 61
        peak_memory_in_bytes = peak
    parts = memory.plan_parts(_MA())
    assert parts["temp_bytes"] == want_temp, why
    assert (parts["xla_temp_bytes"], parts["xla_peak_bytes"]) == (8603, peak)
    assert parts["peak_bytes"] == 8466 + want_temp + 61
