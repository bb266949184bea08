"""The SmallThinker cell (``smallthinker_21b_a3b_lm_s16384``) rehearsed on the
CPU at toy widths: its files, entries and metrics picked by name, the
configuration file against the catalog row, the parameter count and the FLOPs
by part by hand, the three new readers on a hand-made trace and with nothing
to read, the cell end to end to the contract's last line, what the traffic
decides (the weights are the model, ``--seed`` is the ids; the rate warms up
inside the program), and what the lowered step names.  Nothing here is a
speed number."""

import copy
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import (harness, part_scopes, smallthinker_flops,  # noqa: E402
                       trinity_flops)

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import test_benchmark_rehearsal as rehearsal  # noqa: E402
import test_op_scopes as scopes_test  # noqa: E402
import test_program_scopes as program_scopes_test  # noqa: E402

CELL = "smallthinker_21b_a3b_lm_s16384"
CONFIG = "smallthinker_21b_a3b"
SPEC = harness.load_spec()
FILE = harness.load_json(f"benchmark/configs/{CONFIG}.json")
TRAFFIC = harness.load_traffic("lm_s16384")
NEW = ("flash_roofline", "held_experts_roofline",
       "moe_router_device_ms.train")
REDUCED = ["num_hidden_layers", "moe_num_primary_experts", "vocab_size",
           "rope_layout", "sliding_window_layout"]


def toy_smallthinker(**traffic):
    c = copy.deepcopy(FILE)
    c.update(hidden_size=64, num_attention_heads=14, num_key_value_heads=2,
             head_dim=8, moe_ffn_hidden_size=32, moe_num_primary_experts=4,
             moe_num_active_primary_experts=2, vocab_size=128,
             sliding_window_size=8)
    c["assumed"].update(router_outputs=8, expert_offset=2)
    # toy widths: the fused head's bf16 products move the loss by 1e-4 and
    # bf16 AMP by 1e-2; the chip's limits are set at the real widths
    c["loss_tolerance"] = {"relative": 2e-3, "hidden_relative": 1e-3,
                           "top_k_differ_share": 0.02,
                           "first_training_loss_relative": 5e-2,
                           "first_hidden_relative": 5e-2,
                           "first_gradient_rest_relative": 0.5,
                           "first_gradient_experts_relative": 0.5,
                           "first_gradient_router_relative": 0.5,
                           "first_gradient_all_relative": 0.5,
                           "replayed_update_relative": 1e-3,
                           "reason": "toy widths"}
    t = copy.deepcopy(TRAFFIC)
    t.update(batch_per_chip=2, seq_len=32, ring=2, warmup_steps=1,
             check_batch=2, reference_q_block=16)
    t.update(traffic)
    return c, t


# -- BENCHMARK.json ----------------------------------------------------------

def test_the_cell_is_listed_with_its_files_and_metrics():
    cell = harness.find(SPEC["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "lm_s16384", 1)
    assert len(cell["why"]) <= 200
    cfg = harness.find(SPEC["configs"], CONFIG, "config")
    assert FILE["reduced"] == cfg["reduced"] == REDUCED
    assert FILE["source"] == cfg["source"] and len(cfg["why"]) <= 200
    assert cfg["file"] == f"benchmark/configs/{CONFIG}.json"
    for kind, fn in (("models", "build_train"), ("reference", "loss")):
        assert callable(getattr(harness.load_module(kind, CONFIG), fn))
    # the driver refused train_samples_per_s in this cell (it spreads 1 % over
    # seeds under ISSUE 38's traffic, against the 0.5 % a new cell is admitted
    # under: PERF.md section 6), and a per-layer metric's cell reports the
    # end-to-end metric it moves: the cell is listed for memory, set-up and
    # ``correct``; the rate and the readers below wait for a benchmark issue
    e2e = {m["name"] for m in harness.metrics_of_cell(SPEC, "end_to_end",
                                                      CELL)}
    assert e2e == {"peak_hbm_gb", "setup_s"}
    layer = harness.metrics_of_cell(SPEC, "per_layer", CELL)
    assert {m["name"] for m in layer} >= {
        "first_step_program_s", "first_step_backend_s", "retrace_s"}
    assert {m["moves"] for m in layer} <= e2e
    listed = {m["name"] for m in SPEC["per_layer"]}
    for name in NEW:
        assert name not in listed
        assert callable(harness.load_module("layer_metrics", name).read)


def test_the_traffic_file_says_what_the_issue_fixed():
    t = TRAFFIC
    assert (t["kind"], t["batch_per_chip"], t["seq_len"], t["warmup_steps"],
            t["check_batch"]) == ("train_ring", 1, 16384, 3, 1)
    assert (t["learning_rate"], t["lr_start"], t["weight_decay"],
            t["weights_seed"], t["reference_q_block"]) \
        == (4e-4, 0.0, 0.1, 1, 512)
    assert (t["ring"], t["lr_warmup_steps"], t["recompute"]) \
        == (4, 2000, False)
    assert "2000 steps" in FILE["assumed"]["optimizer"]
    assert t["seq_len"] == FILE["max_position_embeddings"]


def test_the_config_file_holds_the_catalogs_numbers():
    """Every key of the catalog entry's ``config`` under the same key; the
    keys that differ are the five listed, no width among them."""
    layout = [0, 1, 1, 1] * 13
    catalog = {
        "head_dim": 128, "hidden_size": 2560,
        "max_position_embeddings": 16384,
        "model_name": "smallthinker_21b_instruct",
        "moe_ffn_hidden_size": 768, "moe_num_active_primary_experts": 6,
        "moe_num_primary_experts": 64,
        "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
        "num_attention_heads": 28, "num_hidden_layers": 52,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_layout": layout, "rope_scaling": None, "rope_theta": 1500000,
        "sliding_window_layout": layout, "sliding_window_size": 4096,
        "tie_word_embeddings": False, "vocab_size": 151936}
    differ = sorted(k for k, v in catalog.items() if FILE[k] != v)
    assert differ == sorted(FILE["reduced"]) == sorted(REDUCED)
    assert FILE["rope_layout"] == FILE["sliding_window_layout"] == layout[:4]
    assert (FILE["num_hidden_layers"], FILE["moe_num_primary_experts"],
            FILE["vocab_size"]) == (4, 8, 18992)
    a = FILE["assumed"]
    assert a["router_outputs"] == 64 and a["expert_offset"] == 0
    assert FILE["vocab_size"] * 8 == catalog["vocab_size"]
    for assumption in ("router_reads_normed_input", "router_softmax",
                       "bias_free", "rotate_half", "relu_gate",
                       "primary_experts_only", "optimizer", "weights"):
        assert len(a[assumption]) > 40, assumption
    assert "8 chips" in FILE["deployment"]
    assert "370.6 M" in a["parameters"] and "5.93 GB" in a["parameters"]
    assert FILE["flops_module"] == "smallthinker_flops"
    for key in ("relative", "hidden_relative", "top_k_differ_share",
                "first_training_loss_relative", "first_hidden_relative",
                "first_gradient_rest_relative",
                "first_gradient_experts_relative",
                "first_gradient_router_relative",
                "first_gradient_all_relative"):
        assert 0 < FILE["loss_tolerance"][key] < 1, key


def test_the_parameters_are_370_6_million_from_the_shapes_held():
    """370.6 M parameters at 16 bytes: 5.93 GB, from the shapes the program
    holds and, by part, from ``smallthinker_flops.parameters``."""
    from paddle_tpu.framework import Program, program_guard
    from paddle_tpu.models import transformer as T
    model = harness.load_module("models", CONFIG)
    main = Program()
    with program_guard(main, Program()):
        T.build_smallthinker_pretrain(model.smallthinker_config(FILE), 16384)
    shapes = {p.name: tuple(p.shape) for p in main.all_parameters()}
    assert shapes["dec_0.attn.qkv.w"] == (2560, 4608)
    assert shapes["dec_0.attn.out.w"] == (3584, 2560)
    assert shapes["dec_3.moe.router.w"] == (2560, 64)
    assert shapes["dec_3.moe.gate.w"] == (8, 2560, 768)
    assert shapes["dec_3.moe.down.w"] == (8, 768, 2560)
    assert shapes["lm_out.w"] == (2560, 18992)
    assert not any(n.endswith((".b", "select_bias", "_norm.w"))
                   and n != "final_norm.w" for n in shapes)
    n = sum(int(np.prod(s)) for s in shapes.values())
    by_part = smallthinker_flops.parameters(FILE)
    assert n == sum(by_part.values()) == 370_547_200
    assert by_part["attention"] == 4 * (2560 * 4608 + 3584 * 2560)
    assert by_part["experts"] == 4 * 8 * 3 * 2560 * 768
    assert round(16 * n / 1e9, 2) == 5.93
    assert 16 * n / 16.9e9 > 0.25


# -- the yardstick's arithmetic ----------------------------------------------

def test_forward_flops_by_part_by_hand():
    parts = smallthinker_flops.forward_flops_by_part(FILE, 16384)
    t, d = 16384, 2560
    assert set(parts) == {"attention_projections", "attention_scores",
                          "routed_experts", "router", "head"}
    assert parts["attention_projections"] == \
        4 * (2 * t * d * (3584 + 2 * 512) + 2 * t * 3584 * d)
    band = 4096 * 4097 // 2 + 12288 * 4096          # 58,722,304 pairs
    half = 16384 * 16385 // 2                       # 134,225,920
    assert smallthinker_flops.live_pairs(16384, 4096) == band
    assert smallthinker_flops.live_pairs(16384) == half
    assert band / half == pytest.approx(0.4375, abs=1e-3)    # 56 % saved
    assert parts["attention_scores"] == 4 * 128 * 28 * (3 * band + half)
    assert parts["routed_experts"] == 4 * 6 * (t * 6 * 8 / 64) * d * 768
    assert t * 6 * 8 / 64 == 12288 and 12288 / 8 == 1536
    assert parts["router"] == 4 * 2 * t * d * 64
    assert parts["head"] == 2 * t * d * 18992
    total = sum(parts.values())
    assert total == pytest.approx(9.39e12, rel=1e-3)
    assert parts["attention_scores"] / total == pytest.approx(0.47, abs=0.005)
    assert parts["attention_projections"] / total == pytest.approx(
        0.29, abs=0.005)
    assert parts["head"] / total == pytest.approx(0.17, abs=0.005)
    assert parts["routed_experts"] / total == pytest.approx(0.06, abs=0.005)
    assert smallthinker_flops.train_flops_per_sample(FILE, 16384) == 3 * total


def test_the_hooks_count_each_kernel_call_by_hand():
    work = smallthinker_flops.flash_work(FILE, TRAFFIC)
    band, half = 58722304, 134225920
    assert len(work) == 8                   # four layers, forward and backward
    assert [fl for fl, _ in work] == [
        m * 128 * 28 * p for p in (half, band, band, band) for m in (4, 8)]
    q, kv, lse = 28 * 16384 * 128 * 2, 4 * 16384 * 128 * 2, 28 * 16384 * 4
    assert [by for _, by in work] == [2 * q + 2 * kv + lse,
                                      4 * q + 4 * kv + lse] * 4
    even = smallthinker_flops.held_experts_work(FILE, TRAFFIC, None)
    assert len(even) == 36 and even == smallthinker_flops.held_experts_work(
        FILE, TRAFFIC, 8 / 64)
    assert all(fl == 2 * 12288 * 2560 * 768 for fl, _ in even)
    rows, w16 = 12288 * (2560 + 768) * 2, 8 * 2560 * 768 * 2
    assert [by for _, by in even[:9]] == 6 * [rows + w16] \
        + 3 * [rows + 2 * w16]
    half_the_rows = smallthinker_flops.held_experts_work(FILE, TRAFFIC, 1 / 16)
    assert half_the_rows[0][0] == even[0][0] / 2
    assert even[:9] == trinity_flops.held_experts_matmuls(12288, 2560, 768, 8)


# -- the readers on a hand-made trace -----------------------------------------

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _inputs(tmp_path, events, steps=2, config=FILE):
    inputs = scopes_test._inputs(tmp_path, events, steps)
    inputs.update(config=config, traffic={"seq_len": 16384}, peaks=PEAKS,
                  facts={"batch": 1, "chips": 1})
    return inputs


def _read(metric, inputs):
    return harness.load_module("layer_metrics", metric).read(inputs)


def _count(monkeypatch, held, all_):
    from paddle_tpu import monitor
    ctr = monitor.Counter("paddle_tpu_moe_routed_rows_total", "", ("where",))
    real = monitor.REGISTRY.get
    monkeypatch.setattr(
        monitor.REGISTRY, "get", lambda name: ctr
        if name == "paddle_tpu_moe_routed_rows_total" else real(name))
    if all_:
        ctr.inc(all_, where="all")
        ctr.inc(held, where="held")


def test_the_three_readers_on_a_hand_made_trace(tmp_path, monkeypatch):
    fwd, bwd = "jit(step)/pt.fwd/", "jit(step)/pt.bwd/"
    inputs = _inputs(tmp_path, [
        ("flash_fwd.1", fwd + "flash_attention/attn/window/pallas_call:", 0,
         40),
        ("flash_fwd.2", fwd + "flash_attention/attn/pallas_call:", 40, 60),
        ("flash_bwd.3", bwd + "flash_attention_grad/attn/"
         "transpose(jvp(window))/pallas_call:", 100, 120),
        ("fusion.4", bwd + "flash_attention_grad/attn/reduce:", 220, 30),
        ("gmm.5", fwd + "moe_ffn/experts/jit(gmm)/pallas_call:", 300, 50),
        ("gmm.6", bwd + "moe_ffn_grad/transpose(jvp(experts))/pallas_call:",
         350, 100),
        ("fusion.7", fwd + "moe_ffn/dispatch/gather:", 450, 70),
        ("fusion.8", fwd + "moe_ffn/router/dot_general:", 520, 30),
        ("fusion.9", bwd + "moe_ffn_grad/transpose(jvp(router))/dot_general:",
         550, 50),
        ("fusion.10", fwd + "mul/attn/dot_general:", 600, 80),
    ])
    # flash: every kernel call compute-bound, one sample a step, over the
    # 250 ns under the two ops in 2 steps (125 ns a step)
    pairs = [134225920] + 3 * [58722304]
    least = sum(12 * 128 * 28 * p / 197e12 for p in pairs)
    assert 4 * 128 * 28 * pairs[1] / 197e12 > \
        (2 * 28 * 16384 * 256 + 2 * 4 * 16384 * 256 + 28 * 16384 * 4) / 819e9
    assert _read("flash_roofline", inputs) == pytest.approx(
        100 * least / 125e-9)
    # the router: 80 ns under its scope, forward and grad op, in 2 steps
    assert _read("moe_router_device_ms.train", inputs) == pytest.approx(
        40e-9 * 1e3)
    # the experts: 9000 of the 98304 slots a layer landed here
    _count(monkeypatch, 9000 * 4, 98304 * 4)
    fl = 2 * 9000 * 2560 * 768
    through = max(fl / 197e12,
                  (9000 * 3328 * 2 + 8 * 2560 * 768 * 2) / 819e9)
    to = max(fl / 197e12, (9000 * 3328 * 2 + 8 * 2560 * 768 * 4) / 819e9)
    assert _read("held_experts_roofline", inputs) == pytest.approx(
        100 * (6 * through + 3 * to) * 4 * 2 / 150e-9)
    # nothing counted: even routing's 12288 rows a layer
    _count(monkeypatch, 0, 0)
    fl = 2 * 12288 * 2560 * 768
    through = max(fl / 197e12,
                  (12288 * 3328 * 2 + 8 * 2560 * 768 * 2) / 819e9)
    to = max(fl / 197e12, (12288 * 3328 * 2 + 8 * 2560 * 768 * 4) / 819e9)
    assert _read("held_experts_roofline", inputs) == pytest.approx(
        100 * (6 * through + 3 * to) * 4 * 2 / 150e-9)


def test_the_readers_return_nothing_with_nothing_to_read(tmp_path,
                                                         monkeypatch):
    """A trace of another program, a trace without scopes, no trace at all,
    a configuration that names no module of hooks (every older cell's), one
    that names a module without them, and a program without the counter."""
    other = _inputs(tmp_path, [
        ("fusion.1", "jit(step)/pt.fwd/mul/dot_general:", 0, 100)])
    (tmp_path / "b").mkdir()
    bare = _inputs(tmp_path / "b", [("fusion.1", None, 0, 100)])
    none = dict(other, trace=None, trace_window=None)
    _count(monkeypatch, 0, 0)
    for inputs in (other, bare, none):
        for metric in NEW:
            assert _read(metric, inputs) is None, metric
    events = [
        ("flash_fwd.1", "jit(step)/pt.fwd/flash_attention/pallas_call:", 0,
         40),
        ("gmm.2", "jit(step)/pt.fwd/moe_ffn/experts/pallas_call:", 40, 40)]
    for i, config in enumerate((
            harness.load_json("benchmark/configs/trinity_mini.json"),
            dict(FILE, flops_module="olmoe_flops"),
            dict(FILE, flops_module="no_such_module"))):
        (tmp_path / str(i)).mkdir()
        older = _inputs(tmp_path / str(i), events, config=config)
        for metric in ("flash_roofline", "held_experts_roofline"):
            assert _read(metric, older) is None, (i, metric)
        # the trace holds experts but no router scope
        assert _read("moe_router_device_ms.train", older) is None
    from paddle_tpu import monitor
    (tmp_path / "d").mkdir()
    counted = _inputs(tmp_path / "d", events)
    monkeypatch.setattr(monitor.REGISTRY, "get", lambda name: None)
    assert _read("held_experts_roofline", counted) is not None  # even routing


@pytest.mark.parametrize("metric", NEW)
def test_unlisted_reader_with_nothing_to_read_returns_nothing(metric):
    """What ``test_benchmark_rehearsal`` asks of every listed per-layer
    metric, for the three that are in the tree and not in
    ``BENCHMARK.json``: inputs without trace, spans or counts."""
    empty = {"spans": [], "counters": {}, "e2e": {}, "trace": None,
             "facts": {"batch": 1, "chips": 1, "flops_per_sample": 1.0,
                       "samples_per_s": 1.0},
             "trace_window": None, "config": {}, "traffic": {},
             "peaks": None, "chips": 1}
    assert harness.load_module("layer_metrics", metric).read(empty) is None


# -- the cell end to end -----------------------------------------------------

def test_cell_end_to_end_on_cpu():
    config, traffic = toy_smallthinker()
    result = harness.run_cell(CELL, seed=rehearsal.BIG_SEED, seconds=0.5,
                              trace=True, on_chip=False, config=config,
                              traffic=traffic, spec=SPEC)
    line = rehearsal.check_contract_line(result, CELL, 1)
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"     # and so: not a result
    assert len(line["compared"]) >= 2
    assert line["compared"][-1].startswith("correct: ")
    assert "gradient against jax.grad of the reference" in line["compared"][1]


@pytest.mark.slow
def test_cell_end_to_end_on_cpu_untraced():
    config, traffic = toy_smallthinker()
    result = harness.run_cell(CELL, seed=7, seconds=0.5, trace=False,
                              on_chip=False, config=config, traffic=traffic,
                              spec=SPEC)
    line = rehearsal.check_contract_line(result, CELL, 0)
    assert line["correct"] is True


# -- what the traffic decides ------------------------------------------------

def _built(seed, **traffic):
    config, t = toy_smallthinker(**traffic)
    model = harness.load_module("models", CONFIG)
    return model, model.build_train(config, t, seed, 1, False)


def _weights(m):
    return {p.name: np.asarray(m["scope"].find_var(p.name))
            for p in m["parameters"]}


def test_the_weights_are_the_model_and_the_seed_is_the_traffic():
    """Two values of ``--seed``: the same weights (``weights_seed``), other
    token ids; another ``weights_seed`` (the control set's overlay), other
    weights."""
    _, a = _built(11)
    _, b = _built(rehearsal.BIG_SEED)
    wa, wb = _weights(a), _weights(b)
    assert all(np.array_equal(wa[n], wb[n]) for n in wa)
    assert not np.array_equal(a["ring"][0]["src_ids"], b["ring"][0]["src_ids"])
    ids = a["ring"][0]["src_ids"]
    assert ids.min() >= 1 and ids.max() < 128
    np.testing.assert_array_equal(a["ring"][0]["lm_label"][:, :-1],
                                  ids[:, 1:])
    _, c = _built(11, weights_seed=12)
    assert not np.array_equal(wa["dec_0.moe.router.w"],
                              _weights(c)["dec_0.moe.router.w"])


def test_the_rate_warms_up_inside_the_program():
    """Step 0 runs at rate 0: AdamW's moments take the gradient and no
    parameter moves; by step 3 the rate is 3 / 2000 of 4e-4 and they have
    moved, hundreds of times less than at 4e-4 from step 0 (the control
    set: ``lr_start`` at the rate itself)."""
    from benchmark.models import _train

    def moved(**traffic):
        _, m = _built(11, **traffic)
        before = _weights(m)
        feed = _train.put_ring(m["ring"], 1)[0]
        out = []
        for _ in range(4):
            m["exe"].run(m["program"], feed=feed, fetch_list=[m["loss"]],
                         scope=m["scope"])
            after = _weights(m)
            out.append(max(float(np.abs(after[n] - before[n]).max())
                           for n in before))
        return out, m

    warm, m = moved()
    types = [op.type for op in m["program"].global_block().ops]
    assert "increment" in types and "less_than" in types    # the schedule
    assert warm[0] == 0.0 and 0 < warm[3] < 5e-6
    moment = np.asarray(m["scope"].find_var(
        m["moment1"]["dec_0.attn.out.w"]))
    assert np.abs(moment).max() > 0
    flat, _ = moved(lr_start=4e-4)
    assert flat[0] > 1e-4 and flat[3] > 100 * warm[3]


def test_the_references_adamw_by_hand():
    """Two steps on one weight by hand: the epsilon beside the uncorrected
    second moment, the decay decoupled and of the weight before the step."""
    ref = harness.load_module("reference", CONFIG)
    assert ref.warmup_rate(0, 4e-4, 2000, 0.0) == 0.0
    assert ref.warmup_rate(1000, 4e-4, 2000, 0.0) == pytest.approx(2e-4)
    assert ref.warmup_rate(2000, 4e-4, 2000, 0.0) == 4e-4
    g, p0 = 0.5, 2.0
    m1, v1 = 0.1 * g, 0.001 * g * g
    p1 = p0 - 0.0 * p0
    m2, v2 = 0.9 * m1 + 0.1 * g, 0.999 * v1 + 0.001 * g * g
    p2 = p1 - 2e-4 * (1 - 0.999 ** 2) ** 0.5 / (1 - 0.9 ** 2) * m2 \
        / (v2 ** 0.5 + 1e-8) - 2e-4 * 0.1 * p1
    got = ref.adamw(np.float32([p0]), [(0.0, [g]), (2e-4, [g])], 0.1)
    assert got[0] == pytest.approx(p2, rel=1e-14)
    assert p0 - p2 == pytest.approx(2e-4 * (1 + 0.2), rel=1e-6)


class _Swapped:
    """``obj`` with some attributes replaced."""

    def __init__(self, obj, **swap):
        self.__dict__.update(swap)
        self._obj = obj

    def __getattr__(self, name):
        return getattr(self._obj, name)


@pytest.mark.parametrize("fault", [
    None, "state left unchanged", "no decay", "the optimizer's default decay",
    "the rate of step 1"])
def test_the_replayed_update_against_the_references_adamw(fault):
    """The step once more half-way up the warm-up moves every parameter as
    the reference's AdamW does; a state left unchanged reads 1, and an
    optimizer that differs from the reference's by the decay or by the rate
    reads over the limit on some leaf."""
    model, m = _built(11)
    ref = harness.load_module("reference", CONFIG)
    config, t = toy_smallthinker()
    feed = m["ring"][0]
    _, grads = model._trinity._replayed_first_step(m, feed)
    if fault == "state left unchanged":
        m["exe"] = _Swapped(m["exe"], run=lambda *a, **k: None)
    elif fault == "no decay":
        ref = _Swapped(ref, adamw=lambda p, steps, decay: ref._obj.adamw(
            p, steps, 0.0))
    elif fault == "the optimizer's default decay":   # 0.01, not the traffic's
        ref = _Swapped(ref, adamw=lambda p, steps, decay: ref._obj.adamw(
            p, steps, 0.01))
    elif fault == "the rate of step 1":
        ref = _Swapped(ref, warmup_rate=lambda step, *a: ref._obj.warmup_rate(
            min(step, 1), *a))
    got = model._replayed_update(m, t, feed, grads, ref)
    limit = config["loss_tolerance"]["replayed_update_relative"]
    assert got["rate"] == pytest.approx(
        2e-7 if fault == "the rate of step 1" else 2e-4)
    if fault is None:
        assert got["worst"][0] <= limit and got["all"] <= limit / 10
    elif fault == "state left unchanged":
        assert got["all"] == 1.0 and got["worst"][0] == 1.0
    else:
        assert got["worst"][0] > limit, got


def test_the_builders_checkpoints_are_the_four_block_outputs():
    """ISSUE 38's one fallback, which the cell did not need and its adapter
    does not build: ``build_smallthinker_pretrain(checkpoints=[])`` fills
    the list with the four block outputs and nothing finer, so the backward
    runs a block's forward again (its flash op among it) for every block but
    the one it starts from."""
    import paddle_tpu as pt
    from paddle_tpu import optimizer as opt
    from paddle_tpu.framework import Program, program_guard
    from paddle_tpu.models import transformer as T
    config, t = toy_smallthinker()
    cfg = harness.load_module("models", CONFIG).smallthinker_config(config)
    main = Program()
    with program_guard(main, Program()):
        checkpoints = []
        _, _, loss = T.build_smallthinker_pretrain(cfg, t["seq_len"],
                                                   checkpoints=checkpoints)
        assert len(checkpoints) == cfg.n_layer == 4
        stepper = opt.RecomputeOptimizer(opt.AdamWOptimizer(1e-3))
        stepper._set_checkpoints(checkpoints)
        pt.amp.decorate(stepper).minimize(loss)
    flash = [op for op in main.global_block().ops
             if op.type == "flash_attention"]
    again = [op for op in flash
             if op.output("Out")[0].endswith("@RECOMPUTE")]
    assert len(flash) - len(again) == 4 and len(again) == 3


# -- what the lowered step names ---------------------------------------------

def test_the_lowered_step_names_the_window_the_routers_input_and_relu():
    """What the readers and the by-op breakdown depend on: a ``window``
    scope under the windowed layers' ``flash_attention`` and none under the
    full layer's, the four parts of ``moe_ffn`` under it and its grad op,
    the ``attn`` tag behind the attention's ops; and the counters name what
    was lowered: groups of 7, ``act=relu``, the router's own input."""
    import jax.numpy as jnp
    from benchmark.models import _train
    from paddle_tpu.ops import attention_ops, moe_ops
    config, traffic = toy_smallthinker()
    model = harness.load_module("models", CONFIG)
    labels = dict(impl="ragged_dot", experts="8", top_k="2", held="4",
                  score_func="softmax", act="relu", router_input="own")
    lowered = moe_ops.MOE_LOWERINGS_CTR.value(**labels)
    grouped = attention_ops.FLASH_LOWERINGS_CTR.value(window="8",
                                                      kv_groups="7")
    m = model.build_train(config, traffic, 11, 1, False)
    exe, scope = m["exe"], m["scope"]
    feed = _train.put_ring(m["ring"], 1)[0]
    exe.run(m["program"], feed=feed, fetch_list=[m["loss"]], scope=scope)
    assert moe_ops.MOE_LOWERINGS_CTR.value(**labels) >= lowered + 4
    assert attention_ops.FLASH_LOWERINGS_CTR.value(
        window="8", kv_groups="7") >= grouped + 3
    ops = m["program"].global_block().ops
    assert all(op.attrs.get("act") == "relu" and op.input("RouterX")
               and op.input("RouterX") != op.input("X")
               for op in ops if op.type == "moe_ffn")
    assert [int(op.attrs.get("window") or 0) for op in ops
            if op.type == "flash_attention"] == [0, 8, 8, 8]
    cb = next(p for p in exe._plans.values()
              if p.cb.fetch_names == (m["loss"],)).cb
    args = ([feed[n] for n in cb.feed_names],
            [scope.find_var(n) for n in cb.persist_ro],
            [scope.find_var(n) for n in cb.persist_rw], jnp.uint32(1))
    jaxpr = cb.jitted.trace(*args).jaxpr
    stacks = {s for s, _ in program_scopes_test._eqn_scopes(
        getattr(jaxpr, "jaxpr", jaxpr))}

    def under(prefix):
        return [s[len(prefix):] for s in stacks if s.startswith(prefix)]

    for role_op in ("pt.fwd/flash_attention/attn",
                    "pt.bwd/flash_attention_grad/attn"):
        seen = {part_scopes.part_of(r, ("window",)) for r in under(role_op)}
        assert seen == {"window", ""}, (role_op, seen)    # both kinds of layer
    for role_op in ("pt.fwd/moe_ffn/", "pt.bwd/moe_ffn_grad/"):
        seen = {part_scopes.part_of(r, part_scopes.MOE_PARTS)
                for r in under(role_op)}
        assert seen >= set(part_scopes.MOE_PARTS), (role_op, seen)
    assert under("pt.fwd/mul/attn") and under("pt.bwd/mul_grad/attn")
    assert under("pt.fwd/rope/attn")
    for op in ("pt.fwd/rms_norm", "pt.opt/adamw", "pt.fwd/fused_lm_head_ce"):
        assert any(s == op or s.startswith(op + "/") for s in stacks), op
    assert json.dumps(sorted(stacks))       # names only, nothing device-bound
