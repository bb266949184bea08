"""The three kinds of work a training step does twice, as the benchmark
reads them from a trace (PR 36), on the CPU against hand-worked numbers: the
program's own recomputation (``pt.rc/*`` scopes), XLA's rematerialised
instructions (``*.remat*`` event names) and forward work a generic vjp
lowered again inside a grad op (``jvp(`` outside ``transpose(`` under
``pt.bwd``).  Nothing here is a speed number."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness, op_scopes, remat_scopes  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import test_op_scopes as scopes_test  # noqa: E402

SPEC = harness.load_spec()
NEW = ("recompute_device_ms.train", "xla_remat_device_ms.train",
       "vjp_forward_again_device_ms.train")
TRAINING = ["bert_base_mlm_s128", "resnet50_imagenet_b256",
            "bert_base_mlm_s128_dp4", "olmoe_1b_7b_lm_s4096",
            "trinity_mini_lm_s8192", "joyai_llm_flash_lm_mtp_s8192"]

FWD, BWD, RC = ("jit(step)/pt.fwd/", "jit(step)/pt.bwd/", "jit(step)/pt.rc/")

#: (event name, op_name, start ns, duration ns) on one chip, two steps
CHANGE = [
    ("flash_fwd.1", FWD + "flash_attention/pallas_call:", 0, 40),
    ("fusion.2", FWD + "mul/mla_proj/dot_general:", 40, 30),
    # XLA rematerialised a forward projection next to its use in the
    # backward: the clone keeps the original's op_name
    ("convolution_bitcast_fusion.5.remat2", FWD + "mul/dot_general:", 70, 20),
    # the program's own second forward, tagged like the first
    ("flash_fwd.3", RC + "flash_attention/mtp/pallas_call:", 100, 60),
    ("fusion.4", RC + "mul/mtp.mla_proj/dot_general:", 160, 25),
    ("fusion.6", RC + "optimization_barrier/optimization_barrier:", 185, 5),
    # a loop of the backward (200..500): its body holds a rematerialised
    # norm and forward work the generic vjp lowered again
    ("while.7", BWD + "fused_lm_head_ce_grad/while:", 200, 300),
    ("fusion.8.remat", FWD + "rms_norm/mul:", 220, 50),
    ("fusion.9", BWD + "moe_ffn_grad/jvp(router)/dot_general:", 300, 70),
    ("fusion.10", BWD + "moe_ffn_grad/transpose(jvp(experts))/dot:", 370,
     80),
    # XLA's own, rematerialised and without a scope
    ("copy.11.remat", None, 500, 10),
    ("fusion.12", BWD + "gelu_grad/jvp()/exp:", 510, 14),
    ("fusion.13", "jit(step)/pt.opt/adamw/mul:", 524, 76),
]


def _read(metric, inputs):
    return harness.load_module("layer_metrics", metric).read(inputs)


def test_the_three_readers_on_a_hand_made_trace(tmp_path):
    inputs = scopes_test._inputs(tmp_path, CHANGE, steps=2)
    # rc: 60 + 25 + 5 ns over 2 steps
    assert _read("recompute_device_ms.train", inputs) == pytest.approx(
        90e-9 / 2 * 1e3)
    # .remat: 20 + 50 (its own time inside the loop, not the loop's) + 10
    assert _read("xla_remat_device_ms.train", inputs) == pytest.approx(
        80e-9 / 2 * 1e3)
    # jvp( outside transpose( under bwd: 70 + 14
    assert _read("vjp_forward_again_device_ms.train", inputs) == \
        pytest.approx(84e-9 / 2 * 1e3)
    # the first forward no longer holds the second: 40 + 30 + 20 + 50
    assert _read("fwd_device_ms.train", inputs) == pytest.approx(
        140e-9 / 2 * 1e3)
    # what is picked by op type or tag reads whatever the role
    assert _read("attention_device_ms.train", inputs) == pytest.approx(
        100e-9 / 2 * 1e3)
    assert _read("mtp_device_ms.train", inputs) == pytest.approx(
        85e-9 / 2 * 1e3)
    assert _read("op_scoped_share.train", inputs) == pytest.approx(
        100 * (580 - 10) / 580)          # two gaps of 10 ns in the 600
    by = remat_scopes.seconds_by_op(inputs)
    assert by == {"fwd/mul": pytest.approx(20e-9),
                  "fwd/rms_norm": pytest.approx(50e-9),
                  "": pytest.approx(10e-9)}
    red = op_scopes.of_run(inputs)
    assert red["scoped"]["rc/flash_attention"] == pytest.approx(60e-9)
    assert red["scoped"]["bwd/fused_lm_head_ce_grad"] == pytest.approx(
        100e-9)                              # the loop less its body


def _as_the_parent(events):
    """The same step from a tree before the role: the second forward reads
    ``pt.fwd`` like the first; nothing else differs."""
    return [(n, sc.replace("/pt.rc/", "/pt.fwd/") if sc else sc, a, d)
            for n, sc, a, d in events]


def test_a_trace_of_the_parents_kind_reads_zero_for_the_role(tmp_path):
    change = scopes_test._inputs(tmp_path, CHANGE, steps=2)
    (tmp_path / "parent").mkdir()
    parent = scopes_test._inputs(tmp_path / "parent",
                                 _as_the_parent(CHANGE), steps=2)
    assert _read("recompute_device_ms.train", parent) == 0.0
    for metric in NEW[1:]:
        assert _read(metric, parent) == pytest.approx(_read(metric, change))
    # fwd + rc of the change is the parent's fwd; every other role as it was
    assert _read("fwd_device_ms.train", parent) == pytest.approx(
        _read("fwd_device_ms.train", change)
        + _read("recompute_device_ms.train", change))
    for metric in ("bwd_device_ms.train", "opt_device_ms.train",
                   "op_scoped_share.train", "attention_device_ms.train",
                   "mtp_device_ms.train", "mla_proj_device_ms.train"):
        assert _read(metric, parent) == pytest.approx(_read(metric, change))


def test_a_step_that_repeats_nothing_reads_three_zeros(tmp_path):
    inputs = scopes_test._inputs(tmp_path, [
        ("fusion.1", FWD + "mul/dot_general:", 0, 100),
        ("fusion.2", BWD + "mul_grad/transpose(jvp())/dot_general:", 100,
         200),
        ("copy-done.3", None, 300, 50)], steps=1)
    for metric in NEW:
        assert _read(metric, inputs) == 0.0, metric
    assert remat_scopes.seconds_by_op(inputs) == {}


def test_nothing_to_read_without_scopes_steps_or_a_trace(tmp_path):
    bare = scopes_test._inputs(tmp_path, [("fusion.1.remat", None, 0, 100)])
    (tmp_path / "b").mkdir()
    no_steps = scopes_test._inputs(tmp_path / "b", CHANGE, steps=0)
    none = dict(bare, trace=None, trace_window=None)
    for inputs in (bare, no_steps, none):
        for metric in NEW:
            assert _read(metric, inputs) is None, metric
    assert remat_scopes.seconds_by_op(bare) is None


def test_two_chips_are_a_mean_and_a_window_cuts(tmp_path):
    d0, d1 = scopes_test.D0, scopes_test.D1
    rows = lambda evs: [(f"%{n} = ...", n, sc, a * 1000, d * 1000)  # noqa
                        for n, sc, a, d in evs]
    space = scopes_test._xspace([
        (d0, "XLA Ops", 0, rows([("fusion.1.remat", FWD + "mul/x:", 0,
                                  100)])),
        (d1, "XLA Ops", 0, rows([("fusion.1.remat", FWD + "mul/x:", 0, 60),
                                 ("fusion.2", FWD + "mul/x:", 60, 40)]))])
    path = tmp_path / "two.xplane.pb"
    path.write_bytes(space)
    assert remat_scopes.reduce_remat(str(path), (0, 1000)) == {
        "fwd/mul": pytest.approx((100 + 60) / 2 * 1e-9)}
    assert remat_scopes.reduce_remat(str(path), (50, 1000)) == {
        "fwd/mul": pytest.approx((50 + 10) / 2 * 1e-9)}


@pytest.mark.parametrize("name,want", [
    ("convolution_bitcast_fusion.5.remat2", True),    # Trinity's trace
    ("convolution_bitcast_fusion.remat", True),
    ("fusion.812.remat", True),
    ("copy.12.remat.3", True),                        # a serial number behind
    ("fusion.7.remat3.clone", True),
    ("all-gather.4.remat_compressed", True),
    ("fusion.9.remat_uncompressed.1", True),
    ("fusion.812", False),
    ("broadcast.5821.clone", False),
    ("rematerialized_fusion.3", False),
    ("remat", False),
    ("flash_fwd.11", False),
])
def test_a_rematerialised_instructions_name(name, want):
    assert remat_scopes.is_remat(name) is want


def test_the_three_metrics_are_listed_with_their_cells():
    by_name = {m["name"]: m for m in SPEC["per_layer"]}
    assert by_name[NEW[0]]["workloads"] == [
        "joyai_llm_flash_lm_mtp_s8192", "trinity_mini_lm_s8192"]
    assert by_name[NEW[1]]["workloads"] == TRAINING
    assert by_name[NEW[2]]["workloads"] == TRAINING
    assert TRAINING == [w["name"] for w in SPEC["workloads"]]
    names = [m["name"] for m in SPEC["per_layer"]]
    assert names[-3:] == list(NEW) and len(set(names)) == len(names)
    for name in NEW:
        m = by_name[name]
        assert {k: m[k] for k in ("unit", "better", "source", "layer",
                                  "moves")} == {
            "unit": "ms", "better": "lower", "source": "device_trace",
            "layer": "compiled step", "moves": "train_samples_per_s"}
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert callable(harness.load_module("layer_metrics", name).read)
    # the role's sibling readers list the same six cells
    assert by_name["fwd_device_ms.train"]["workloads"] == TRAINING


def test_trace_by_op_prints_the_role_and_the_rematerialised_ops(
        tmp_path, monkeypatch, capsys):
    """The operator's tool over a recording: ``rc/`` rows among the scopes
    and the tagged scopes, ``remat_pct`` by program op beside
    ``forward_again_pct``."""
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import trace_by_op as tool
    inputs = scopes_test._inputs(tmp_path, CHANGE, steps=2)
    prof = tmp_path / "traces" / "toy_cell" / "plugins" / "profile" / "t0"
    prof.mkdir(parents=True)
    os.replace(inputs["trace"]["path"], prof / "host.xplane.pb")
    monkeypatch.setattr(tool.harness, "TRACE_DIR", str(tmp_path / "traces"))
    monkeypatch.setattr(tool, "ROOT", str(tmp_path))
    monkeypatch.setattr(sys, "argv", ["trace_by_op.py", "toy_cell"])
    tool.main()
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    with open(tmp_path / "chiprun_out" / "trace_by_op.toy_cell.json") as f:
        assert json.load(f) == out
    busy = 580.0                        # two gaps of 10 ns in the 600
    pct = lambda ns: round(100 * ns / busy, 3)  # noqa: E731
    assert out["scoped_pct"]["rc/flash_attention"] == pct(60)
    assert out["scoped_pct"]["rc/mul"] == pct(25)
    assert out["scoped_pct"]["rc/optimization_barrier"] == pct(5)
    assert out["tagged_pct"]["rc/flash_attention/mtp"] == pct(60)
    assert out["tagged_pct"]["rc/mul/mtp.mla_proj"] == pct(25)
    assert out["remat_pct"] == {"fwd/rms_norm": pct(50), "fwd/mul": pct(20),
                                "-": pct(10)}
    assert out["forward_again_pct"] == {"bwd/moe_ffn_grad": pct(70),
                                        "bwd/gelu_grad": pct(14)}
