"""The per-layer metrics that move ``peak_hbm_gb`` and the compile-cache
counter's reader (``benchmark/step_plans.py``, PR 51): which plan a reader
takes, its arithmetic, where it reads nothing, and the entries' listing.
Nothing here is a device number: the registry is filled by hand or by a toy
step on the CPU."""

import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness, step_plans  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import test_benchmark_rehearsal as rehearsal  # noqa: E402

SPEC = harness.load_spec()
HBM = ("hbm_step_arguments_gb.train", "hbm_step_temporaries_gb.train",
       "hbm_step_unaliased_outputs_gb.train", "hbm_outside_step_gb.train")
MISSES = "train_step_cache_misses"
GB = 10 ** 9


class _Plan:
    """A ``memory_analysis()`` of round numbers."""

    def __init__(self, arguments, temporaries, outputs, aliased, code):
        self.argument_size_in_bytes = arguments
        self.temp_size_in_bytes = temporaries
        self.output_size_in_bytes = outputs
        self.alias_size_in_bytes = aliased
        self.generated_code_size_in_bytes = code


def _read(name, inputs):
    return harness.load_module("layer_metrics", name).read(inputs)


def _inputs(window, peak_gb=16.0, **over):
    inputs = {"spans": [], "counters": {}, "facts": {},
              "e2e": {"peak_hbm_gb": peak_gb, "setup_s": 30.0},
              "trace": None, "trace_window": window, "config": {},
              "traffic": {}, "peaks": {"flops": 1.0}, "chips": 1}
    inputs.update(over)
    return inputs


@pytest.fixture
def clean_registry(monkeypatch):
    """The program's registry with the plan and compile families empty, as
    a fresh process has them (this process's other tests compiled too)."""
    import collections
    from paddle_tpu import hbm, memory, monitor
    from paddle_tpu.framework import executor as E
    for fam in (hbm.STEP_PLAN_GAUGE, hbm.STEP_PLAN_AT_GAUGE,
                hbm.STEP_ARGUMENT_GAUGE, E._COMPILE_CTR):
        monkeypatch.setattr(fam, "_series", {})
    monkeypatch.setattr(memory, "_HBM_PLANS", collections.OrderedDict())
    return monitor.REGISTRY


# -- the listing ---------------------------------------------------------------

def test_the_five_entries_are_listed_as_the_issue_says():
    e2e = harness.find(SPEC["end_to_end"], "peak_hbm_gb", "metric")
    assert len(e2e["workloads"]) == 10
    for name in HBM:
        m = harness.find(SPEC["per_layer"], name, "metric")
        assert (m["unit"], m["better"], m["source"], m["layer"],
                m["moves"]) == ("GB", "lower", "program_counter",
                                "compiled step", "peak_hbm_gb")
        # every cell that reports peak_hbm_gb, SmallThinker's among them
        assert m["workloads"] == e2e["workloads"]
        assert "smallthinker_21b_a3b_lm_s16384" in m["workloads"]
    m = harness.find(SPEC["per_layer"], MISSES, "metric")
    assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) == (
        "count", "lower", "program_counter", "executor dispatch", "setup_s")
    assert "workloads" not in m          # like the three first_step_* entries
    assert [e["name"] for e in SPEC["per_layer"][-5:]] == list(HBM) + [MISSES]


# -- which plan a reader takes ---------------------------------------------------

def test_readers_take_the_train_plan_newest_when_the_window_opened(
        clean_registry):
    from paddle_tpu import hbm
    t = time.perf_counter()
    # the step's first compile, then the compile that the window ran (a
    # re-trace: the same fetch list, so its tag gets '#2'), an eval block
    # newer than both, and a train block that the checks after the window
    # compile under another fetch list
    hbm.record_xla_plan("loss", _Plan(9 * GB, 4 * GB, 9 * GB, 9 * GB, 0),
                        block="train", compiled_at=t + 1)
    second = hbm.record_xla_plan(
        "loss", _Plan(10 * GB, 5 * GB, 10 * GB + 4096, 10 * GB, GB // 100),
        block="train", compiled_at=t + 2)
    assert second["tag"] == "loss#2"
    hbm.record_xla_plan("acc", _Plan(GB, GB, GB, 0, 0), block="other",
                        compiled_at=t + 3)
    hbm.record_xla_plan("loss,hidden", _Plan(12 * GB, 3 * GB, 0, 0, 0),
                        block="train", compiled_at=t + 6)
    inputs = _inputs((t + 5, t + 8), peak_gb=15.5)
    plan = step_plans.window_plan(inputs)
    assert plan == {"arguments": 10 * GB, "temporaries": 5 * GB,
                    "outputs": 10 * GB + 4096, "aliased": 10 * GB,
                    "code": GB // 100}
    # (d) each reader's arithmetic
    assert _read(HBM[0], inputs) == 10.0
    assert _read(HBM[1], inputs) == 5.0
    assert _read(HBM[2], inputs) == pytest.approx(4096 / GB)
    outside = _read(HBM[3], inputs)
    assert outside == pytest.approx(15.5 - 10.0 - 5.0 - 4096 / GB - 0.01)
    # by construction: the parts and what stands outside are the peak
    assert sum(_read(n, inputs) for n in HBM) + 0.01 == pytest.approx(15.5)
    # a window that opened before the re-trace ran the first executable
    assert _read(HBM[1], _inputs((t + 1.5, t + 8))) == 4.0
    # and after the checks' block it would be that one: the readers go by
    # the window's opening, not by "the most recent"
    assert _read(HBM[0], _inputs((t + 7, t + 8))) == 12.0


def test_on_a_real_step_the_later_train_block_is_not_taken(clean_registry):
    """The registry filled by the executor itself: a training step, a mark,
    then the same program under a second fetch list (what the decoder
    cells' checks after the window do)."""
    import paddle_tpu as pt
    from paddle_tpu import layers, memory
    x = layers.data("x", shape=[16], dtype="float32")
    h = layers.fc(x, size=32, act="relu")
    loss = layers.mean(layers.fc(h, size=8))
    pt.optimizer.Adam(1e-3).minimize(loss)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    feed = {"x": np.ones((8, 16), np.float32)}
    exe.run(feed=feed, fetch_list=[loss.name])
    mark = time.perf_counter()
    exe.run(feed=feed, fetch_list=[loss.name, h.name])
    timed, checks = [p for p in memory.hbm_plans().values()
                     if p["block"] == "train"][-2:]
    assert timed["compiled_at"] < mark < checks["compiled_at"]
    assert checks["output_bytes"] > timed["output_bytes"]
    plan = step_plans.window_plan(_inputs((mark, mark + 1)))
    assert plan == {part: timed[key] for part, key in
                    pt.hbm.PLAN_PARTS.items()}
    # the startup program's block is no train block: before the step
    # compiled there is nothing to take
    assert step_plans.window_plan(
        _inputs((timed["compiled_at"] - 1e-4, mark))) is None


# -- (d) where a reader has nothing to read --------------------------------------

def test_readers_return_none_in_each_of_the_four_cases(clean_registry,
                                                       monkeypatch):
    from paddle_tpu import hbm
    t = time.perf_counter()
    hbm.record_xla_plan("loss", _Plan(GB, GB, GB, GB, 0), block="train",
                        compiled_at=t)
    good = _inputs((t + 1, t + 2))
    assert all(_read(n, good) is not None for n in HBM)
    cases = {
        "the run reports no peak_hbm_gb": _inputs(
            (t + 1, t + 2), e2e={"setup_s": 30.0}),
        "off the chip": _inputs((t + 1, t + 2), peaks=None),
        "no train block compiled before the window": _inputs((t - 1, t + 2)),
        "an untraced run": _inputs(None),
    }
    for why, inputs in cases.items():
        for n in HBM:
            assert _read(n, inputs) is None, (why, n)
    # a parent tree: the program has no such family
    real = clean_registry.get
    monkeypatch.setattr(
        clean_registry, "get",
        lambda name: None if name.startswith("paddle_tpu_step_hbm")
        else real(name))
    for n in HBM:
        assert _read(n, good) is None, n


def test_cache_misses_counts_the_train_blocks_the_cache_did_not_serve(
        clean_registry, monkeypatch):
    from paddle_tpu.framework import executor as E
    inputs = _inputs(None)
    assert _read(MISSES, inputs) is None          # nothing compiled
    E._COMPILE_CTR.inc(1, persist="miss", block="other")
    assert _read(MISSES, inputs) is None          # no train block did
    E._COMPILE_CTR.inc(1, persist="hit", block="train")
    assert _read(MISSES, inputs) == 0.0           # a warm run
    E._COMPILE_CTR.inc(2, persist="miss", block="train")
    assert _read(MISSES, inputs) == 2.0           # dp4's two, cold
    assert _read(MISSES, _inputs(None, e2e={})) is None
    # a parent tree: its counter has no ``block`` label, and says nothing
    # of which compile missed
    from paddle_tpu import monitor
    old = monitor.Counter("paddle_tpu_compile_total", "", ("persist",))
    old.inc(3, persist="hit")
    real = clean_registry.get
    monkeypatch.setattr(clean_registry, "get",
                        lambda name: old if name == step_plans.COMPILES
                        else real(name))
    assert _read(MISSES, inputs) is None


# -- (f) the traced rehearsal ---------------------------------------------------

def test_the_toy_traced_run_reports_the_counter_and_no_device_split():
    """On the CPU the line holds ``train_step_cache_misses`` (a count, like
    the first-step seconds) and none of the four GB readings (device
    numbers: the run has no peaks); the registry they would read is filled
    all the same, by the run's own compiles."""
    cell = "bert_base_mlm_s128"
    config, traffic = rehearsal.toy_bert()
    result = harness.run_cell(cell, seed=rehearsal.BIG_SEED, seconds=1.0,
                              trace=True, on_chip=False, config=config,
                              traffic=traffic, spec=rehearsal.SPEC_ALL)
    line = rehearsal.check_contract_line(result, cell, trace=1)
    assert line["correct"] is True
    # a count of the PROCESS's train compiles that the cache did not serve
    # (this process's other tests compiled too)
    assert line["metrics"][MISSES]["value"] >= 0.0
    assert not set(HBM) & set(line["metrics"])
    from paddle_tpu import memory
    newest = [p for p in memory.hbm_plans().values()
              if p["block"] == "train"][-1]
    window = (time.perf_counter(), time.perf_counter() + 1)
    plan = step_plans.window_plan(_inputs(window))
    assert plan["temporaries"] == newest["temp_bytes"] > 0
    assert plan["arguments"] == newest["argument_bytes"] > 0
