"""``benchmark/op_scopes.py`` on the CPU: the reading of an ``.xplane.pb``
file's wire format, the attribution of device time to the program's scopes,
and the per-layer readers built on it, against hand-worked numbers and a
recorded v5e slice.  No number in here is a speed."""

import json
import math
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness, op_scopes, trace_reduce  # noqa: E402

D0, D1 = "/device:TPU:0", "/device:TPU:1"


@pytest.mark.parametrize("op_name,want", [
    ("jit(step)/pt.fwd/mul/dot_general:", ("fwd", "mul", False)),
    ("jit(step)/pt.bwd/mul_grad/transpose(jvp())/dot_general:",
     ("bwd", "mul_grad", False)),
    # forward work the generic vjp lowered again inside the backward
    ("jit(step)/pt.bwd/softmax_grad/jvp()/exp:", ("bwd", "softmax_grad",
                                                    True)),
    # a sub-block's ops nest under their parent: the outermost scope owns
    ("jit(step)/pt.fwd/while/while/body/pt.fwd/mul/dot_general:",
     ("fwd", "while", False)),
    ("jit(step)/pt.fwd/fused_lm_head_ce/while/body/closed_call/checkpoint/"
     "dot_general:", ("fwd", "fused_lm_head_ce", False)),
    ("jit(_step_impl)/pt.decode/kv_write/scatter:",
     ("decode", "kv_write", False)),
    ("jit(step)/pt.opt/adam/mul:", ("opt", "adam", False)),
    ("jit(step)/pt.amp/cast/convert_element_type:", ("amp", "cast", False)),
    ("jit(step)/jit(main)/dot_general:", None),       # the parent commit
    ("", None),
])
def test_program_scope_of_an_op_name(op_name, want):
    assert op_scopes.program_scope(op_name) == want


def test_each_instant_goes_to_the_innermost_event():
    ev = [(0, 100, "while"), (10, 30, "a"), (40, 50, "b"), (45, 48, "c"),
          (120, 130, "a"),
          (200, 220, "x"), (210, 240, "y")]        # overlap, not nested
    got = op_scopes.self_times(ev)
    assert got == {"while": 100 - 20 - 10, "a": 20 + 10, "b": 10 - 3,
                   "c": 3, "x": 10, "y": 30}
    assert sum(got.values()) == trace_reduce.total(trace_reduce.union(
        [(a, b) for a, b, _ in ev]))
    assert op_scopes.self_times([]) == {}


def _ev(plane, name, start, dur, scope=""):
    return {"plane": plane, "line": "XLA Ops", "name": name,
            "start_ns": start, "dur_ns": dur, "scope": scope}


HAND = [
    _ev(D0, "fusion.1", 0, 400, "jit(step)/pt.fwd/mul/dot_general:"),
    _ev(D0, "copy-done.2", 400, 100),
    _ev(D0, "while.3", 500, 300),
    _ev(D0, "fusion.4", 550, 200,
        "jit(step)/pt.bwd/fused_lm_head_ce_grad/transpose(jvp())/while/"
        "body/dot_general:"),
    _ev(D0, "fusion.5", 900, 100, "jit(step)/pt.bwd/relu_grad/jvp()/max:"),
    _ev(D1, "fusion.1", 0, 600, "jit(step)/pt.fwd/mul/dot_general:"),
    _ev(D1, "fusion.9", 600, 200, "jit(step)/pt.opt/adam/mul:"),
]


def test_reduction_by_scope_of_a_hand_made_trace():
    red = op_scopes.reduce_scopes(HAND, (0, 1000))
    assert red["n_devices"] == 2
    # mean over the two chips, seconds
    assert red["scoped"] == {
        "fwd/mul": pytest.approx((400 + 600) / 2 * 1e-9),
        "bwd/fused_lm_head_ce_grad": pytest.approx(200 / 2 * 1e-9),
        "bwd/relu_grad": pytest.approx(100 / 2 * 1e-9),
        "opt/adam": pytest.approx(200 / 2 * 1e-9)}
    assert red["forward_again"] == {
        "bwd/relu_grad": pytest.approx(50e-9)}
    # the while's own time is what its body does not cover
    assert red["unscoped"] == {"copy-done": pytest.approx(50e-9),
                               "while": pytest.approx(100 / 2 * 1e-9)}
    assert red["busy_s"] == pytest.approx((900 + 800) / 2 * 1e-9)
    same = trace_reduce.reduce_events(HAND, (0, 1000))
    assert red["busy_s"] == pytest.approx(same["busy_s"])
    # a window cuts the events it crosses
    cut = op_scopes.reduce_scopes(HAND, (300, 700))
    assert cut["scoped"]["fwd/mul"] == pytest.approx((100 + 300) / 2 * 1e-9)
    assert cut["busy_s"] == pytest.approx((400 + 400) / 2 * 1e-9)


# -- the wire format ---------------------------------------------------------------

def _vi(n):
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _f(no, val):
    """One field: an int as a varint, bytes or str length-delimited."""
    if isinstance(val, int):
        return _vi(no << 3) + _vi(val)
    if isinstance(val, str):
        val = val.encode()
    return _vi(no << 3 | 2) + _vi(len(val)) + val


def _xspace(planes):
    """A small XSpace: ``planes`` = [(plane name, line name, timestamp_ns,
    [(hlo name, display name, tf_op or None, offset_ps, duration_ps)])]."""
    out = b""
    for pname, lname, t_line, events in planes:
        stat_meta = _f(5, _f(1, 7) + _f(2, _f(1, 7) + _f(2, "tf_op"))) + \
            _f(5, _f(1, 8) + _f(2, _f(1, 8) + _f(2, "flops")))
        metas, evs = b"", b""
        for i, (hlo, display, scope, off, dur) in enumerate(events, 1):
            m = _f(1, i) + _f(2, hlo) + _f(4, display) + \
                _f(5, _f(1, 8) + _f(3, 123))           # another stat first
            if scope is not None:
                m += _f(5, _f(1, 7) + _f(5, scope))
            metas += _f(4, _f(1, i) + _f(2, m))
            evs += _f(4, _f(1, i) + _f(2, off) + _f(3, dur))
        line = _f(1, 1) + _f(2, lname) + _f(3, t_line) + evs
        out += _f(1, _f(1, 1) + _f(2, pname) + _f(3, line) + metas
                  + stat_meta)
    return out


def test_the_xplane_files_wire_format_is_read(tmp_path):
    space = _xspace([
        (D0, "XLA Ops", 1000, [
            ("%fusion.7 = f32[8]{0} fusion(...)", "fusion.7",
             "jit(step)/pt.fwd/mul/dot_general:", 5_000, 2_000_000),
            ("%copy-done.2 = f32[8]{0} copy-done(...)", "", None,
             3_000_000, 500_000)]),
        (D0, "Steps", 1000, [("1", "1", None, 0, 9_000_000)]),
        ("/host:CPU", "python3", 0, [("bench_mark", "", None, 0, 0)]),
    ])
    path = tmp_path / "toy.xplane.pb"
    path.write_bytes(space)
    evs = op_scopes.load_scoped_events(str(path))
    assert [dict(e) for e in evs] == [
        {"plane": D0, "line": "XLA Ops", "name": "fusion.7",
         "start_ns": 1005, "dur_ns": 2000,
         "scope": "jit(step)/pt.fwd/mul/dot_general:"},
        # no display name: the HLO text's own, as trace_reduce classes it
        {"plane": D0, "line": "XLA Ops", "name": "copy-done",
         "start_ns": 4000, "dur_ns": 500, "scope": ""}]
    # and the same file through jax's own reader gives the same times
    ref = [e for e in trace_reduce.load_xplane(str(path))
           if e["name"] != trace_reduce.MARK]
    assert [(e["start_ns"], e["dur_ns"]) for e in ref] == \
        [(e["start_ns"], e["dur_ns"]) for e in evs]


# -- the readers, over a synthetic run ------------------------------------------------

def _inputs(tmp_path, events, steps=2):
    """A traced run's ``inputs`` whose trace file holds ``events`` (scope,
    offset and duration in ns on chip 0), window = the whole of it."""
    space = _xspace([(D0, "XLA Ops", 0, [
        (f"%{n} = ...", n, sc, a * 1000, d * 1000)
        for n, sc, a, d in events])])
    path = tmp_path / "run.xplane.pb"
    path.write_bytes(space)
    return {"spans": [("serving.decode_iter", 0.0, 0.5e-6, {}),
                      ("serving.decode_iter", 0.5e-6, 1.0e-6, {})],
            "counters": {"steps_traced": steps}, "facts": {}, "e2e": {},
            "trace": {"path": str(path), "offset_ns": 0.0, "n_devices": 1},
            "trace_window": (0.0, 1e-6), "config": {}, "traffic": {},
            "peaks": None, "chips": 1}


def _read(metric, inputs):
    return harness.load_module("layer_metrics", metric).read(inputs)


def test_train_readers_sum_the_scopes_they_name(tmp_path):
    inputs = _inputs(tmp_path, [
        ("fusion.1", "jit(step)/pt.fwd/matmul/dot_general:", 0, 100),
        ("fusion.2", "jit(step)/pt.fwd/dropout/select_n:", 100, 60),
        ("fusion.3", "jit(step)/pt.bwd/dropout_grad/mul:", 160, 40),
        ("fusion.4", "jit(step)/pt.bwd/softmax_grad/jvp()/exp:", 200, 50),
        ("fusion.5", "jit(step)/pt.fwd/fused_lm_head_ce/while/body/dot:",
         250, 150),
        ("fusion.6", "jit(step)/pt.bwd/fused_conv1x1_bn_grad/jvp()/"
         "conv1x1_stats_nchw:", 400, 200),
        ("fusion.7", "jit(step)/pt.opt/momentum/sub:", 600, 100),
        ("copy-done.8", None, 700, 100),
        ("fusion.9", "jit(step)/pt.bwd/flash_attention_grad/x:", 800, 100),
    ])
    ms = 1e-6                                  # ns -> ms, and 2 steps
    assert _read("op_scoped_share.train", inputs) == pytest.approx(
        100 * 800 / 900)
    assert _read("fwd_device_ms.train", inputs) == pytest.approx(
        310 * ms / 2)
    assert _read("bwd_device_ms.train", inputs) == pytest.approx(
        390 * ms / 2)
    assert _read("opt_device_ms.train", inputs) == pytest.approx(
        100 * ms / 2)
    assert _read("dropout_device_ms.train", inputs) == pytest.approx(
        100 * ms / 2)
    assert _read("attention_device_ms.train", inputs) == pytest.approx(
        (100 + 50 + 100) * ms / 2)
    assert _read("lm_head_device_ms.train", inputs) == pytest.approx(
        150 * ms / 2)
    assert _read("conv_bn_relu_device_ms.train", inputs) == pytest.approx(
        200 * ms / 2)
    assert _read("kv_write_device_ms", inputs) == pytest.approx(0.0)


# -- ResNet-50's conv + BN + ReLU work, whatever lowers it --------------------------
#
# (XLA name, scope, start, duration in ns); the fused kind is what the default
# ``conv_bn_relu`` rewrite compiles to (PERF.md section 5), the unfused kind
# what ``FLAGS_graph_fusion=0`` or a tree without the rewrite does.

_J = "jit(step)/pt."
FUSED_STEP = [
    ("convolution_convert_fusion.1", _J + "fwd/conv2d/conv_general_dilated:",
     0, 70),
    ("conv1x1_stats_nchw.2", _J + "fwd/fused_conv1x1_bn/pallas_call:",
     70, 120),
    ("broadcast_maximum_fusion.3", _J + "fwd/relu/max:", 190, 50),
    ("fusion.4", _J + "fwd/pool2d/reduce_window:", 240, 30),
    ("copy.5", _J + "bwd/fused_conv1x1_bn_grad/transpose(jvp())/transpose:",
     270, 200),
    ("jvp_conv1x1_stats_nchw_.6",
     _J + "bwd/fused_conv1x1_bn_grad/jvp()/pallas_call:", 470, 110),
    ("broadcast_compare_fusion.7", _J + "bwd/relu_grad/select_n:", 580, 40),
    ("fusion.8",
     _J + "bwd/conv2d_grad/transpose(jvp())/conv_general_dilated:", 620, 90),
    ("multiply_reduce_fusion.9",
     _J + "bwd/batch_norm_explicit_grad/reduce_sum:", 710, 25),
    ("fusion.10", _J + "opt/momentum/sub:", 735, 15),
    ("copy-done.11", None, 750, 10),
]
UNFUSED_STEP = [
    ("convolution_convert_fusion.1", _J + "fwd/conv2d/conv_general_dilated:",
     0, 150),
    ("convert_reduce_fusion.2", _J + "fwd/batch_norm/reduce_sum:", 150, 60),
    ("broadcast_maximum_fusion.3", _J + "fwd/relu/max:", 210, 50),
    ("fusion.4", _J + "fwd/pool2d/reduce_window:", 260, 30),
    ("add_add_fusion.5", _J + "fwd/elementwise_add/add:", 290, 12),
    ("fusion.6", _J + "bwd/pool2d_grad/select_and_scatter_add:", 302, 20),
    ("broadcast_compare_fusion.7", _J + "bwd/relu_grad/select_n:", 322, 40),
    ("multiply_reduce_fusion.8",
     _J + "bwd/batch_norm_explicit_grad/reduce_sum:", 362, 80),
    ("fusion.9",
     _J + "bwd/conv2d_grad/transpose(jvp())/conv_general_dilated:", 442, 200),
    ("fusion.10", _J + "opt/momentum/sub:", 642, 15),
]
NEITHER = [
    ("fusion.1", _J + "fwd/pool2d/reduce_window:", 0, 30),
    ("fusion.2", _J + "bwd/pool2d_grad/select_and_scatter_add:", 30, 20),
    ("fusion.3", _J + "bwd/mul_grad/transpose(jvp())/dot_general:", 50, 12),
    ("fusion.4", _J + "opt/momentum/sub:", 62, 15),
]


@pytest.mark.parametrize("events,want_ns", [
    # conv2d 70 + fused 120 + relu 50 + fused grad 200 + 110 + relu_grad 40
    # + conv2d_grad 90 + batch_norm_explicit_grad 25; not pool2d, momentum
    # or the scopeless copy-done
    (FUSED_STEP, 70 + 120 + 50 + 200 + 110 + 40 + 90 + 25),
    # conv2d 150 + batch_norm 60 + relu 50 + the residual add 12 (which
    # carries the ReLU after it where XLA roots their fusion in the add)
    # + relu_grad 40 + batch_norm_explicit_grad 80 + conv2d_grad 200
    (UNFUSED_STEP, 150 + 60 + 50 + 12 + 40 + 80 + 200),
    # pool2d, the classifier's mul_grad and momentum are none of the work:
    # a number all the same, since the trace is scoped
    (NEITHER, 0),
], ids=["fused", "unfused", "unrelated_scopes_only"])
def test_conv_bn_relu_reads_the_work_whatever_lowers_it(tmp_path, events,
                                                        want_ns):
    got = _read("conv_bn_relu_device_ms.train",
                _inputs(tmp_path, events, steps=2))
    assert got is not None
    assert got == pytest.approx(want_ns * 1e-6 / 2)


# the regression test of PR 25's refusal (ledger, PR 25: output_malformed,
# "metrics lacks conv1x1_stats_nchw_roofline"): a traced run of a step that
# holds no fused op and no Mosaic kernel reports every metric the cell lists

RESNET_CELL = "resnet50_imagenet_b256"
#: the recorded fused slice's XLA operation classes, as the unfused step
#: names the same work
AS_UNFUSED = {
    "conv1x1_stats_nchw": ("convolution_convert_fusion", "fwd/conv2d"),
    "jvp_conv1x1_stats_nchw_": ("convolution_convert_fusion",
                                "bwd/conv2d_grad"),
    "broadcast_maximum_fusion": (None, "fwd/relu"),
    "add_maximum_fusion": ("add_add_fusion", "fwd/elementwise_add"),
    "broadcast_compare_fusion": (None, "bwd/relu_grad"),
    "convert_reduce_fusion": (None, "fwd/batch_norm"),
    "multiply_reduce_fusion": (None, "bwd/batch_norm_explicit_grad"),
    "fusion": (None, "bwd/conv2d_grad"),
    "copy": (None, "bwd/conv2d_grad"),
    "convert_element_type": (None, "amp/cast"),
}
#: by hand from the slice's durations per class, in the order of AS_UNFUSED
#: less the cast: the residual blocks' nanoseconds of the renamed slice
RENAMED_SLICE_NS = (7654325 + 3480609 + 7033666 + 1366256 + 3481165
                    + 3220148 + 13968 + 3562516 + 4568775)


@pytest.fixture(scope="module")
def unfused_run_inputs(tmp_path_factory):
    """The ``inputs`` of a traced ``resnet50_imagenet_b256`` run whose step
    is of the unfused kind: the device events of the recorded v5e slice
    (``fixtures/v5e_resnet50_step.json``, left as recorded) with the Mosaic
    kernel's events renamed and every class given the scope the unfused step
    would carry; one step; the host's side as a run has it."""
    import numpy as np
    import paddle_tpu as pt
    from paddle_tpu import layers
    from paddle_tpu.framework import (Program, Scope, program_guard,
                                      scope_guard)
    from benchmark import flops
    with open(os.path.join(ROOT, "benchmark", "fixtures",
                           "v5e_resnet50_step.json")) as f:
        dev = [e for e in json.load(f)["events"]
               if trace_reduce.is_device_plane(e["plane"])]
    w0 = min(e["start_ns"] for e in dev)
    w1 = max(e["start_ns"] + e["dur_ns"] for e in dev)
    renamed, rows = [], []
    for i, e in enumerate(dev):
        cls = trace_reduce.op_class(e["name"])
        name, scope = AS_UNFUSED.get(cls, (None, None))
        name = f"{name or cls}.{i}"
        renamed.append(dict(e, name=name, start_ns=e["start_ns"] - w0))
        rows.append((f"%{name} = ...", name,
                     f"jit(step)/pt.{scope}/x:" if scope else None,
                     (e["start_ns"] - w0) * 1000, e["dur_ns"] * 1000))
    path = tmp_path_factory.mktemp("unfused") / "run.xplane.pb"
    path.write_bytes(_xspace([(D0, "XLA Ops", 0, rows)]))
    red = trace_reduce.reduce_events(renamed)
    red.update(path=str(path), offset_ns=0.0)
    # the first-step readers take the program's own histogram: compile one
    # training block in this process, as a run's set-up does
    scope, main, startup = Scope(), Program(), Program()
    with scope_guard(scope), program_guard(main, startup):
        x = layers.data("x", shape=[6], dtype="float32")
        loss = layers.mean(layers.fc(x, size=3))
        pt.optimizer.SGD(0.1).minimize(loss)
        exe = pt.Executor()
        exe.run(startup, scope=scope)
        exe.run(main, feed={"x": np.ones((4, 6), np.float32)},
                fetch_list=[loss], scope=scope)
    return {"spans": [("executor.dispatch", 0.1 * i, 0.1 * i + 0.003, {})
                      for i in range(5)],
            "counters": {"steps": 180, "steps_traced": 1},
            "facts": {"batch": 256, "chips": 1, "window_s": 20.0,
                      "samples_per_s": 2200.0, "flops_per_sample":
                      flops.resnet50_train_flops_per_sample()},
            "e2e": {"train_samples_per_s": 2200.0, "peak_hbm_gb": 9.7,
                    "setup_s": 35.0},
            "trace": red, "trace_window": (0.0, (w1 - w0) / 1e9),
            "config": harness.load_json("benchmark/configs/resnet50.json"),
            "traffic": harness.load_traffic("imagenet_b256"),
            "peaks": flops.load_peaks("TPU v5 lite"), "chips": 1}


def test_the_renamed_slice_holds_no_fused_scope_and_no_mosaic_event(
        unfused_run_inputs):
    evs = op_scopes.load_scoped_events(unfused_run_inputs["trace"]["path"])
    assert len(evs) == 500
    assert not any("conv1x1_stats_nchw" in e["name"] or "fused_" in e["scope"]
                   for e in evs)
    assert not any("conv1x1_stats_nchw" in k
                   for k in unfused_run_inputs["trace"]["ops"])
    assert _read("conv_bn_relu_device_ms.train", unfused_run_inputs) == \
        pytest.approx(RENAMED_SLICE_NS / 1e6)


@pytest.mark.parametrize("metric", [
    m["name"] for m in harness.metrics_of_cell(
        harness.load_spec(), "per_layer", RESNET_CELL)])
def test_every_resnet50_metric_reads_a_number_from_an_unfused_step(
        unfused_run_inputs, metric):
    value = _read(metric, unfused_run_inputs)
    assert value is not None, f"{metric} pins an implementation of the step"
    assert math.isfinite(value)


def test_decode_readers_divide_by_the_iterations_in_the_window(tmp_path):
    inputs = _inputs(tmp_path, [
        ("copy.1", "jit(_step_impl)/pt.decode/kv_write/scatter:", 0, 300),
        ("fusion.2", "jit(_step_impl)/pt.decode/kv_gather/gather:", 300,
         200),
        ("fusion.3", "jit(_step_impl)/pt.decode/attention/exp:", 500, 100),
        ("fusion.4", "jit(_step_impl)/pt.decode/ffn/dot_general:", 600, 50),
    ])
    assert _read("kv_write_device_ms", inputs) == pytest.approx(300e-6 / 2)
    assert _read("kv_gather_device_ms", inputs) == pytest.approx(200e-6 / 2)
    assert _read("decode_attention_device_ms", inputs) == pytest.approx(
        100e-6 / 2)


def test_a_trace_without_scopes_gives_the_readers_nothing(tmp_path):
    """A commit before PR 24, or an executable a compile cache kept from
    one: every operation is there, none is named."""
    inputs = _inputs(tmp_path, [
        ("fusion.1", "jit(step)/jit(main)/dot_general:", 0, 100),
        ("copy.2", None, 100, 100)])
    for m in ("op_scoped_share.train", "fwd_device_ms.train",
              "dropout_device_ms.train", "kv_write_device_ms"):
        assert _read(m, inputs) is None


def test_span_readers_of_the_decode_iteration_and_the_tokens():
    spans = []
    for i in range(4):
        t = i * 0.050
        spans += [
            ("serving.decode_iter", t, t + 0.046, {"iter": i}),
            ("serving.decode_step.dispatch", t, t + 0.001, {"iter": i}),
            ("serving.decode_step.device_wait", t + 0.001, t + 0.043,
             {"iter": i}),
            ("serving.decode_step.logits_to_host", t + 0.043, t + 0.046,
             {"iter": i}),
            ("serving.decode_iter.sample", t + 0.046, t + 0.048,
             {"iter": i})]
    spans += [("serving.decode", 0.0, 0.2, {
        "generated": 3, "ttft_ms": 100.0, "token_ms": [100.0, 150.0, 205.0]}),
        ("serving.decode", 0.0, 0.1, {
            "generated": 2, "ttft_ms": 40.0, "token_ms": [40.0, 90.0]})]
    inputs = {"spans": spans}
    assert _read("decode_dispatch_ms_p50", inputs) == pytest.approx(1.0)
    assert _read("decode_device_wait_ms_p50", inputs) == pytest.approx(42.0)
    assert _read("decode_logits_to_host_ms_p50", inputs) == \
        pytest.approx(3.0)
    assert _read("decode_sample_ms_p50", inputs) == pytest.approx(2.0)
    assert _read("ttft_p90_ms", inputs) == 100.0
    # gaps 50, 55, 50: nearest-rank p90 of three
    assert _read("itl_p90_ms", inputs) == pytest.approx(55.0)


# -- the decode cell's new entries, in a pending file of their own -----------------------

#: PR 24's per-layer entries of the pending decode cell; a PR that changes the
#: program adds benchmark files and edits none, so they wait beside
#: ``gpt1_decode_closed.json`` and not in it
PHASES = harness.load_json("benchmark/pending/gpt1_decode_closed.phases.json")
PENDING = harness.load_json(PHASES["extends"])


def test_the_phases_file_only_appends_to_the_pending_cell():
    spec = harness.load_spec()
    taken = {m["name"] for m in spec["per_layer"] + PENDING["per_layer"]}
    names = [m["name"] for m in PHASES["per_layer"]]
    assert len(names) == len(set(names)) and not taken & set(names)
    cell = [w["name"] for w in PENDING["workloads"]]
    moved = {m["name"] for m in PENDING["end_to_end"]}
    layers = {m["layer"] for m in PENDING["per_layer"]}
    for m in PHASES["per_layer"]:
        assert sorted(m) == ["better", "layer", "moves", "name", "source",
                             "unit", "workloads"]
        assert m["workloads"] == cell and m["moves"] in moved
        assert m["layer"] in layers
        assert callable(harness.load_module("layer_metrics", m["name"]).read)


@pytest.mark.parametrize("metric", [m["name"] for m in PHASES["per_layer"]])
def test_decode_reader_with_nothing_to_read_returns_nothing(metric):
    empty = {"spans": [], "counters": {"generated_tokens": 0,
                                       "kv_pages_peak": 0},
             "facts": {"slots": 4, "batch": 8, "chips": 1},
             "e2e": {}, "trace": None, "trace_window": None,
             "config": {}, "traffic": {}, "peaks": None, "chips": 1}
    assert harness.load_module("layer_metrics", metric).read(empty) is None


# -- a recorded v5e slice ---------------------------------------------------------------

def test_reduction_by_scope_of_a_recorded_v5e_slice():
    """``benchmark/fixtures/v5e_bert_step_scoped.json`` (this PR's chip run):
    the sums against a brute-force attribution over the slice's elementary
    intervals, and a few of them against numbers read off the slice by
    hand."""
    with open(os.path.join(ROOT, "benchmark", "fixtures",
                           "v5e_bert_step_scoped.json")) as f:
        fx = json.load(f)
    events = fx["events"]
    assert 200 <= len(events) <= 600
    red = op_scopes.reduce_scopes(events)
    edges = sorted({e["start_ns"] for e in events}
                   | {e["start_ns"] + e["dur_ns"] for e in events})
    brute = {}
    for a, b in zip(edges, edges[1:]):
        cover = [e for e in events
                 if e["start_ns"] <= a and e["start_ns"] + e["dur_ns"] >= b]
        if not cover:
            continue
        inner = max(cover, key=lambda e: (e["start_ns"], -e["dur_ns"]))
        sc = op_scopes.program_scope(inner["scope"])
        key = f"{sc[0]}/{sc[1]}" if sc else \
            "-" + trace_reduce.op_class(inner["name"])
        brute[key] = brute.get(key, 0) + (b - a)
    for name, s in red["scoped"].items():
        assert s == pytest.approx(brute[name] / 1e9)
    for name, s in red["unscoped"].items():
        assert s == pytest.approx(brute["-" + name] / 1e9)
    assert red["busy_s"] == pytest.approx(fx["expect"]["busy_s"])
    for key, want in fx["expect_scopes"].items():
        kind, name = key.split(":", 1)
        assert red[kind][name] == pytest.approx(want, rel=1e-9)
    # an event with no scope, a fusion with one, and a loop around its body
    assert any(not e["scope"] and e["name"].startswith("copy-done")
               for e in events)
    assert any(e["name"].startswith("fusion") and "pt.fwd/" in e["scope"]
               for e in events)
    # by hand: a loop keeps what its body's events leave of it (while.9, the
    # forward head: 1332307 ns less 30 events of 1331834 ns = 473 ns; while.10
    # is cut by the slice's end and keeps 573521 ns)
    own = 0
    for loop in (e for e in events if e["name"].startswith("while")):
        inside = [e for e in events if e is not loop
                  and loop["start_ns"] <= e["start_ns"]
                  and e["start_ns"] + e["dur_ns"]
                  <= loop["start_ns"] + loop["dur_ns"]]
        assert inside and all("fused_lm_head_ce" in e["scope"]
                              for e in inside if e["scope"])
        own += loop["dur_ns"] - sum(e["dur_ns"] for e in inside)
    assert own == 473 + 573521
    assert red["unscoped"]["while"] == pytest.approx(own / 1e9)
