"""xprof measured device-time attribution (this PR's tentpole): the
chrome-trace parser and dependency-free xplane.pb wire reader, the
paddle_tpu.step step-join, HLO-kernel -> cost-model op-class
attribution, measured MFU / idle fraction, the SamplingProfiler
post-close summary hook (never raises, publishes
paddle_tpu_step_mfu_measured + the mfu_m digest key), and the manifest
dedupe/prune fix."""

import gzip
import json
import os
import shutil
import sys
import time

import numpy as np

import paddle_tpu as fluid
from paddle_tpu import layers, monitor, profiler
from paddle_tpu.analysis import device_profile as dp
from paddle_tpu.framework import Executor
from paddle_tpu.framework.core import Program, program_guard
from paddle_tpu.framework.scope import Scope, scope_guard

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))
import xprof  # noqa: E402

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "xprof_window")
FIXTURE_RUN = os.path.join(FIXTURE, "plugins", "profile",
                           "2026_01_01_00_00_00")


def _mlp(in_dim=64, hidden=64, out=16):
    x = layers.data("x", shape=[in_dim], dtype="float32")
    h = layers.fc(x, size=hidden, act="relu")
    loss = layers.mean(layers.fc(h, size=out))
    fluid.optimizer.SGD(0.01).minimize(loss)
    return loss


def _run_loop(steps):
    scope = Scope()
    with scope_guard(scope), program_guard(Program(), Program()):
        loss = _mlp()
        exe = Executor()
        exe.run(fluid.default_startup_program(), scope=scope)
        feed = {"x": np.ones((4, 64), np.float32)}
        for _ in range(steps):
            exe.run(feed=feed, fetch_list=[loss.name], scope=scope)


# ---------------------------------------------------------------------------
# kernel classification
# ---------------------------------------------------------------------------

def test_classify_kernel_ladder():
    cases = {
        "dot.5": "matmul", "%dot.12": "matmul", "gemm_fusion": "matmul",
        "convolution.3": "conv", "conv2d_fwd": "conv",
        "all-reduce.1": "collective", "reduce-scatter.2": "collective",
        "all-gather.7": "collective", "collective-permute.1": "collective",
        "infeed.0": "infeed", "copy-start.4": "infeed",
        "flash_attention_fwd": "attention", "fused_attention": "attention",
        "gather.9": "embedding", "dynamic-update-slice.2": "embedding",
        "fusion.17": "elementwise", "add.6": "elementwise",
        "broadcast.1": "elementwise", "reduce.4": "elementwise",
        "wat.unknown_thing": "other",
    }
    for name, want in cases.items():
        assert dp.classify_kernel(name) == want, name


def test_collective_beats_embedded_keywords():
    # 'reduce-scatter' contains both 'reduce' (elementwise) and
    # 'scatter' (embedding): the collective rule must win
    assert dp.classify_kernel("reduce-scatter.1") == "collective"
    assert dp.classify_kernel("all-gather.2") == "collective"


# ---------------------------------------------------------------------------
# fixture parse / step join / attribution (exact numbers by design —
# see tests/fixtures/make_xprof_fixture.py)
# ---------------------------------------------------------------------------

def test_fixture_attribution_exact():
    s = dp.summarize_window(FIXTURE)
    assert s is not None
    assert s["n_steps"] == 2
    assert [r["step"] for r in s["steps"]] == [100, 101]
    # per-class totals across both steps
    assert s["per_class_ms"] == {"collective": 0.1, "elementwise": 0.2,
                                 "infeed": 0.05, "matmul": 0.9}
    assert abs(s["device_ms_total"] - 1.25) < 1e-9
    assert abs(s["per_class_share"]["matmul"] - 0.9 / 1.25) < 1e-9
    # the ThreadpoolListener infra span did NOT count as device time
    s100, s101 = s["steps"]
    assert abs(s100["device_ms"] - 0.6) < 1e-9
    assert abs(s100["idle_frac"] - 0.4) < 1e-9
    assert abs(s101["device_ms"] - 0.55) < 1e-9
    # window idle: 1 - 1.15/2.0
    assert abs(s["idle_frac"] - 0.425) < 1e-9
    # the out-of-step kernel landed in unattributed, not in a step
    assert abs(s["unattributed_ms"] - 0.1) < 1e-9


def test_kernels_behind_their_steps_spans_divide_by_the_window(tmp_path):
    """Dispatch is asynchronous: on a loaded host every kernel of a window
    can run after its step's host span has closed, and no step row holds
    device time.  The window's own busy time over its steps is then the
    denominator (``mfu_basis: "window"``), not ``None``."""
    with gzip.open(os.path.join(FIXTURE_RUN, "fix.trace.json.gz"),
                   "rt") as f:
        trace = json.load(f)
    for ev in trace["traceEvents"]:
        if ev.get("name") == "paddle_tpu.step":
            ev["ts"] -= 5000                     # both spans closed early
    run = tmp_path / "plugins" / "profile" / "2026_01_01_00_00_00"
    run.mkdir(parents=True)
    with gzip.open(str(run / "late.trace.json.gz"), "wt") as f:
        json.dump(trace, f)
    s = dp.summarize_window(str(tmp_path), flops_per_step=6.25e8,
                            peak_flops=1e12)
    assert [r["device_ms"] for r in s["steps"]] == [0.0, 0.0]
    assert abs(s["unattributed_ms"] - s["device_ms_total"]) < 1e-9
    # 1.25 ms of kernels, none overlapping, over two steps: 0.625 ms a step
    assert abs(s["device_busy_ms"] - 1.25) < 1e-9
    assert abs(s["measured"]["mfu_measured"] - 1.0) < 1e-6
    assert s["measured"]["mfu_basis"] == "window"


def test_fixture_xplane_cross_check():
    km = dp.xplane_kernel_ms(os.path.join(FIXTURE_RUN, "fix.xplane.pb"))
    assert km == {"dot.1": 0.9, "fusion.2": 0.2}


def test_fixture_measured_mfu_and_divergence():
    s = dp.summarize_window(
        FIXTURE, flops_per_step=5.75e8, peak_flops=1e12,
        analytic_share={"matmul": 0.8, "norm": 0.1, "softmax": 0.1})
    # mean busy = (0.6 + 0.55)/2 ms = 0.575 ms -> 5.75e8 / 5.75e8 = 1.0
    assert abs(s["measured"]["mfu_measured"] - 1.0) < 1e-6
    assert s["measured"]["mfu_basis"] == "steps"
    div = s["divergence"]
    by_cls = {r["op_class"]: r for r in div["per_class"]}
    # norm/softmax fold into the measured elementwise bucket
    assert abs(by_cls["elementwise"]["analytic_flop_share"] - 0.2) < 1e-9
    assert abs(by_cls["matmul"]["analytic_flop_share"] - 0.8) < 1e-9
    # collectives carry no analytic flops
    assert by_cls["collective"]["analytic_flop_share"] == 0.0
    ranking = div["wasted_headroom"]
    assert ranking == sorted(ranking, key=lambda r: -r["wasted_ms"])
    dot = next(r for r in ranking if r["kernel"] == "dot.1")
    # dot.1: 0.45 ms/step measured, roofline min = 0.8*5.75e8/1e12 s
    assert abs(dot["ms_per_step"] - 0.45) < 1e-9
    assert abs(dot["roofline_min_ms"] - 0.46) < 1e-6
    assert dot["wasted_ms"] < 0.0


def test_step_join_collapses_duplicate_annotations():
    trace = {"events": [
        {"name": "paddle_tpu.step", "pid": 2, "tid": 1, "ts": 100.0,
         "dur": 50.0, "args": {"step_num": "7"}},
        {"name": "paddle_tpu.step", "pid": 2, "tid": 1, "ts": 120.0,
         "dur": 80.0, "args": {"step_num": "7"}},
    ], "processes": {}, "threads": {}}
    ivs = dp.step_intervals(trace)
    assert ivs == [{"step": 7, "ts": 100.0, "dur": 100.0}]


def test_cpu_fallback_lane_selection():
    # no /device: process -> the XLA client threads are the device
    # lanes; the codegen (compile) thread never is
    trace = {"events": [], "processes": {1: "python"},
             "threads": {(1, 10): "tf_XLATfrtCpuClient/123",
                         (1, 11): "tf_xla-cpu-llvm-codegen/456",
                         (1, 12): "python"}}
    assert dp.device_lanes(trace) == [(1, 10)]


# ---------------------------------------------------------------------------
# malformed / truncated captures: warn + skip, NEVER raise
# ---------------------------------------------------------------------------

def _copy_fixture(tmp_path):
    wdir = str(tmp_path / "window_00000042")
    shutil.copytree(FIXTURE, wdir)
    return wdir, os.path.join(wdir, "plugins", "profile",
                              "2026_01_01_00_00_00")


def test_truncated_gzip_warns_and_skips(tmp_path):
    wdir, run = _copy_fixture(tmp_path)
    p = os.path.join(run, "fix.trace.json.gz")
    blob = open(p, "rb").read()
    with open(p, "wb") as f:
        f.write(blob[:len(blob) // 2])        # truncated mid-stream
    assert dp.summarize_window(wdir) is None  # warned, not raised


def test_non_json_trace_warns_and_skips(tmp_path):
    wdir, run = _copy_fixture(tmp_path)
    with gzip.open(os.path.join(run, "fix.trace.json.gz"), "wt") as f:
        f.write("not json at all {{{")
    assert dp.summarize_window(wdir) is None


def test_truncated_xplane_returns_none(tmp_path):
    wdir, run = _copy_fixture(tmp_path)
    p = os.path.join(run, "fix.xplane.pb")
    blob = open(p, "rb").read()
    with open(p, "wb") as f:
        f.write(blob[:-7])                    # truncated wire stream
    assert dp.read_xplane(p) is None
    # the window summary still stands on the JSON trace alone
    s = dp.summarize_window(wdir)
    assert s is not None and "xplane" not in s


def test_empty_window_returns_none(tmp_path):
    wdir = str(tmp_path / "window_empty")
    os.makedirs(wdir)
    assert dp.summarize_window(wdir) is None


def test_publish_hook_never_raises(tmp_path):
    # a window dir that does not even exist: warn + skip + counted
    ctr = monitor.REGISTRY.get("paddle_tpu_profile_summaries_total")
    before = ctr.value(outcome="empty")
    assert dp.summarize_and_publish(str(tmp_path / "nope")) is None
    assert ctr.value(outcome="empty") == before + 1


# ---------------------------------------------------------------------------
# post-close hook end to end: live loop -> captured window ->
# summary.json + measured gauges + mfu_m digest key
# ---------------------------------------------------------------------------

def test_post_close_hook_publishes_measured_mfu(tmp_path):
    sdir = str(tmp_path / "samples")
    fluid.set_flags({"FLAGS_profile_sample_every_n_steps": 2,
                     "FLAGS_profile_sample_window_steps": 2,
                     "FLAGS_profile_sample_dir": sdir,
                     "FLAGS_profile_sample_max_windows": 2})
    try:
        _run_loop(steps=8)
        profiler.SAMPLER.close()
        with open(os.path.join(sdir, "manifest.json")) as f:
            windows = json.load(f)["windows"]
        assert windows
        summaries = [os.path.join(w["dir"], "summary.json")
                     for w in windows
                     if os.path.exists(os.path.join(w["dir"],
                                                    "summary.json"))]
        assert summaries, "post-close hook wrote no summary.json"
        with open(summaries[-1]) as f:
            s = json.load(f)
        for key in ("steps", "per_class_ms", "per_class_share",
                    "idle_frac", "kernels", "measured"):
            assert key in s, key
        assert s["n_steps"] >= 1
        assert s["device_ms_total"] > 0
        # the live analytic gauges were populated by the loop, so the
        # hook could compute measured MFU and publish the gauge
        assert s["measured"]["flops_per_step"] > 0
        # by the steps' own device time or, where the loaded host's kernels
        # all ran behind their steps' spans, by the window's
        assert s["measured"]["mfu_basis"] in ("steps", "window")
        assert s["measured"]["mfu_measured"] > 0
        fam = monitor.REGISTRY.get("paddle_tpu_step_mfu_measured")
        assert fam is not None and fam.value() > 0
        assert dp.last_publish_wall > 0
        # ... and the digest carries mfu_m while fresh
        digest = monitor.metrics_digest()
        assert digest.get("mfu_m") == round(float(fam.value()), 5)
        # stale publish ages the key out (frozen-value discipline)
        saved = dp.last_publish_wall
        try:
            dp.last_publish_wall = time.time() - 10 * 600.0
            assert "mfu_m" not in monitor.metrics_digest()
        finally:
            dp.last_publish_wall = saved
    finally:
        fluid.set_flags({"FLAGS_profile_sample_every_n_steps": 0})


def test_mfu_m_rides_behind_mfu_in_digest_priority():
    pri = monitor._DIGEST_PRIORITY
    assert "mfu_m" in pri
    assert pri.index("mfu_m") == pri.index("mfu") + 1


# ---------------------------------------------------------------------------
# manifest dedupe/prune (satellite: window_00000007 listed 3x)
# ---------------------------------------------------------------------------

def test_manifest_dedupes_reused_window_dir(tmp_path):
    s = profiler.SamplingProfiler()
    s.base_dir = str(tmp_path)
    s.max_windows = 8
    wdir = os.path.join(s.base_dir, "window_00000007")
    os.makedirs(wdir)
    # three captures re-using one dir (anomaly re-trigger at one step
    # id) — exactly the duplication shipped in pt_profile_samples
    for i in range(3):
        s._rotate_and_manifest_locked(
            {"dir": wdir, "start_step": 8, "end_step": 10,
             "wall_start": 100.0 + i, "wall_end": 101.0 + i,
             "trigger": "anomaly"})
    with open(os.path.join(s.base_dir, "manifest.json")) as f:
        windows = json.load(f)["windows"]
    assert len(windows) == 1
    assert windows[0]["wall_end"] == 103.0      # newest entry won


def test_manifest_prunes_missing_dirs(tmp_path):
    s = profiler.SamplingProfiler()
    s.base_dir = str(tmp_path)
    s.max_windows = 8
    gone = os.path.join(s.base_dir, "window_00000001")
    kept = os.path.join(s.base_dir, "window_00000005")
    os.makedirs(kept)
    with open(os.path.join(s.base_dir, "manifest.json"), "w") as f:
        json.dump({"windows": [
            {"dir": gone, "start_step": 1, "end_step": 3,
             "wall_start": 1.0, "wall_end": 2.0, "trigger": "periodic"},
        ]}, f)
    s._rotate_and_manifest_locked(
        {"dir": kept, "start_step": 5, "end_step": 7,
         "wall_start": 3.0, "wall_end": 4.0, "trigger": "periodic"})
    with open(os.path.join(s.base_dir, "manifest.json")) as f:
        windows = json.load(f)["windows"]
    assert [os.path.basename(w["dir"]) for w in windows] == \
        ["window_00000005"]


# ---------------------------------------------------------------------------
# xprof CLI (the CI smoke's assertions, in-process)
# ---------------------------------------------------------------------------

def test_xprof_cli_json_on_fixture(tmp_path, capsys):
    rc = xprof.main(["--window", FIXTURE, "--flops_per_step", "5.75e8",
                     "--peak_flops", "1e12", "--json"])
    assert rc == 0
    out = json.loads(capsys.readouterr().out)
    assert out["per_class_share"]["matmul"] > 0.7
    assert abs(out["measured"]["mfu_measured"] - 1.0) < 1e-6
    assert out["idle_frac"] == 0.425


def test_xprof_cli_table_and_write(tmp_path, capsys):
    wdir, _ = _copy_fixture(tmp_path)
    rc = xprof.main(["--window", wdir, "--write"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "OP CLASS" in out and "matmul" in out and "idle" in out
    assert os.path.exists(os.path.join(wdir, "summary.json"))


def test_xprof_cli_unparseable_window_exits_1(tmp_path, capsys):
    wdir = str(tmp_path / "window_bad")
    os.makedirs(os.path.join(wdir, "plugins", "profile", "r1"))
    assert xprof.main(["--window", wdir]) == 1
