"""The LFM2 cell (``lfm2_8b_a1b_lm_s16384_r64``) rehearsed on the CPU at toy
widths: its files, entries and metrics picked by name, the configuration file
against the catalog row, the parameter count from the program and the FLOPs by
part by hand, the three hooks, the two new readers on a hand-made trace and
with nothing to read, the cell end to end to the contract's last line, what
the traffic decides, planted faults against the cell's own limits, and what
the lowered step names.  Nothing here is a speed number."""

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

from benchmark import harness, lfm2_flops, trinity_flops  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import test_benchmark_rehearsal as rehearsal  # noqa: E402
import test_op_scopes as scopes_test  # noqa: E402
import test_program_scopes as program_scopes_test  # noqa: E402

CELL = "lfm2_8b_a1b_lm_s16384_r64"
CONFIG = "lfm2_8b_a1b"
SPEC = harness.load_spec()
FILE = harness.load_json(f"benchmark/configs/{CONFIG}.json")
TRAFFIC = harness.load_traffic("lm_s16384_r64")
NEW = ("short_conv_device_ms.train", "short_conv_roofline")
REDUCED = ["num_hidden_layers", "num_dense_layers", "layer_types",
           "num_experts", "vocab_size"]
KINDS = ["conv", "full_attention", "conv", "conv", "conv"]


def toy_lfm2(**traffic):
    c = copy.deepcopy(FILE)
    c.update(hidden_size=64, num_attention_heads=8, num_key_value_heads=2,
             intermediate_size=96, moe_intermediate_size=32, num_experts=4,
             num_experts_per_tok=2, vocab_size=128)
    c["assumed"].update(router_outputs=8, expert_offset=2, head_dim=8)
    # toy widths: the fused head's bf16 products move the loss by 1e-4 and
    # bf16 AMP by 1e-2; the chip's limits are set at the real widths
    c["loss_tolerance"] = {"relative": 2e-3, "hidden_relative": 1e-3,
                           "top_k_differ_share": 0.02,
                           "first_hidden_relative": 5e-2,
                           "first_gradient_rest_relative": 0.12,
                           "first_gradient_experts_relative": 0.15,
                           "first_gradient_router_relative": 0.3,
                           "first_gradient_all_relative": 0.1,
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
        (CONFIG, "lm_s16384_r64", 1)
    assert len(cell["why"]) <= 200
    cfg = harness.find(SPEC["configs"], CONFIG, "config")
    assert FILE["reduced"] == cfg["reduced"] == REDUCED
    assert FILE["source"] == cfg["source"] and len(cfg["why"]) <= 200
    assert cfg["file"] == f"benchmark/configs/{CONFIG}.json"
    for kind, fn in (("models", "build_train"), ("reference", "loss")):
        assert callable(getattr(harness.load_module(kind, CONFIG), fn))
    e2e = {m["name"] for m in harness.metrics_of_cell(SPEC, "end_to_end",
                                                      CELL)}
    assert {"peak_hbm_gb", "setup_s"} <= e2e
    layer = harness.metrics_of_cell(SPEC, "per_layer", CELL)
    names = {m["name"] for m in layer}
    assert names >= {"first_step_program_s", "first_step_backend_s",
                     "retrace_s"}
    # a per-layer metric's cell reports the end-to-end metric it moves
    assert {m["moves"] for m in layer} <= e2e
    for name in NEW:
        assert callable(harness.load_module("layer_metrics", name).read)
        listed = [m for m in SPEC["per_layer"] if m["name"] == name]
        if "train_samples_per_s" in e2e:         # the rate was admitted
            assert listed and listed[0]["workloads"] == [CELL], name
            assert name in names
        else:                                    # in the tree, unlisted
            assert not listed, name
    # PR 38's three readers read this cell too (lfm2_flops has their hooks)
    # and stay unlisted: tests/benchmark/test_smallthinker_cell.py, which
    # passes, pins them as not in BENCHMARK.json (PERF.md section 7)
    for name in ("flash_roofline", "held_experts_roofline",
                 "moe_router_device_ms.train"):
        assert callable(harness.load_module("layer_metrics", name).read)
        assert not [m for m in SPEC["per_layer"] if m["name"] == name]
    assert callable(lfm2_flops.flash_work)
    assert callable(lfm2_flops.held_experts_work)
    # nothing of this cell rides a list a passing test pins
    pinned, = [m for m in SPEC["per_layer"]
               if m["name"] == "moe_local_rows_share"]
    assert CELL not in pinned["workloads"]


def test_the_traffic_file_says_what_the_issue_fixed():
    t = TRAFFIC
    assert (t["kind"], t["batch_per_chip"], t["seq_len"], t["warmup_steps"],
            t["check_batch"]) == ("train_ring", 1, 16384, 3, 1)
    assert (t["learning_rate"], t["lr_start"], t["weight_decay"],
            t["weights_seed"], t["reference_q_block"]) \
        == (4e-4, 0.0, 0.1, 1, 512)
    assert (t["ring"], t["lr_warmup_steps"]) == (64, 2000)
    assert t["recompute"] in (False, True)
    if t["recompute"]:
        assert len(t["recompute_why"]) > 40
    assert "2000 steps" in FILE["assumed"]["optimizer"]


def test_the_config_file_holds_the_catalogs_numbers():
    """Every key of the catalog entry's ``config`` under the same key; the
    keys that differ are the five listed, no width among them."""
    kinds = ["full_attention" if i in (2, 6, 10, 14, 18, 21) else "conv"
             for i in range(24)]
    catalog = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 7168, "layer_types": kinds,
        "max_position_embeddings": 128000, "model_type": "lfm2_moe",
        "moe_intermediate_size": 1792, "norm_eps": 1e-05,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
        "num_hidden_layers": 24, "num_key_value_heads": 8,
        "rope_theta": 1000000, "routed_scaling_factor": 1,
        "use_expert_bias": True, "vocab_size": 65536}
    differ = sorted(k for k, v in catalog.items() if FILE[k] != v)
    assert differ == sorted(FILE["reduced"]) == sorted(REDUCED)
    assert FILE["layer_types"] == kinds[1:6] == KINDS
    assert (FILE["num_hidden_layers"], FILE["num_dense_layers"],
            FILE["num_experts"], FILE["vocab_size"]) == (5, 1, 8, 16384)
    a = FILE["assumed"]
    assert a["router_outputs"] == 32 and a["expert_offset"] == 0
    assert a["tie_word_embeddings"] is True and a["head_dim"] == 64
    assert FILE["vocab_size"] * 4 == catalog["vocab_size"]
    for assumption in ("tie_word_embeddings_note", "short_conv",
                       "qk_norm_then_rope", "block", "router", "experts",
                       "optimizer", "weights", "data"):
        assert len(a[assumption]) > 40, assumption
    assert "4 chips" in FILE["deployment"]
    assert "507,820,288" in a["parameters"] and "8.13 GB" in a["parameters"]
    assert FILE["flops_module"] == "lfm2_flops"
    # no first_training_loss_relative: the accepted 1.6e-4 would leave the
    # largest reading 1.5 times of room (first_training_loss_reason)
    assert "first_training_loss_relative" not in FILE["loss_tolerance"]
    for key in ("relative", "hidden_relative", "top_k_differ_share",
                "first_hidden_relative",
                "first_gradient_rest_relative",
                "first_gradient_experts_relative",
                "first_gradient_router_relative",
                "first_gradient_all_relative", "replayed_update_relative"):
        assert 0 < FILE["loss_tolerance"][key] < 1, key
    for key in ("reason", "first_training_loss_reason",
                "first_gradient_reason", "replayed_update_reason"):
        assert "control" in FILE["loss_tolerance"][key] \
            or "unchanged" in FILE["loss_tolerance"][key], key


def test_the_parameters_are_507_820_288_counted_from_the_program():
    """507,820,288 parameters at 16 bytes: 8.13 GB, from the shapes the
    program holds and, by part, from ``lfm2_flops.parameters``."""
    from paddle_tpu.framework import Program, program_guard
    from paddle_tpu.models import transformer as T
    model = harness.load_module("models", CONFIG)
    main = Program()
    with program_guard(main, Program()):
        T.build_lfm2_pretrain(model.lfm2_config(FILE), 16384)
    shapes = {p.name: tuple(p.shape) for p in main.all_parameters()}
    assert shapes["dec_0.conv.in_proj.w"] == (2048, 6144)
    assert shapes["dec_0.conv.filter"] == (2048, 3)
    assert shapes["dec_0.conv.out_proj.w"] == (2048, 2048)
    assert shapes["dec_0.ffn.gate_up.w"] == (2048, 14336)
    assert shapes["dec_1.attn.qkv.w"] == (2048, 3072)
    assert shapes["dec_1.attn.q_norm.w"] == (64,)
    assert shapes["dec_4.moe.router.w"] == (2048, 32)
    assert shapes["dec_4.moe.select_bias"] == (32,)
    assert shapes["dec_4.moe.gate.w"] == (8, 2048, 1792)
    assert shapes["dec_4.moe.down.w"] == (8, 1792, 2048)
    assert shapes["word_embedding"] == (16384, 2048)
    assert "lm_out.w" not in shapes and "dec_0.moe.router.w" not in shapes
    assert not any(n.endswith(".b") for n in shapes)

    def layer(i):
        return sum(int(np.prod(s)) for n, s in shapes.items()
                   if n.startswith(f"dec_{i}."))
    assert [layer(i) for i in range(5)] == [
        60_827_648, 98_635_936, 104_933_408, 104_933_408, 104_933_408]
    n = sum(int(np.prod(s)) for s in shapes.values())
    by_part = lfm2_flops.parameters(FILE)
    assert n == sum(by_part.values()) == 507_820_288
    assert by_part["conv_operators"] == 4 * 16_783_360
    assert by_part["attention"] == 10_485_888
    assert by_part["dense_ffn"] == 44_040_192
    assert by_part["experts"] == 4 * 88_080_384
    assert by_part["router"] == 4 * 65_568
    assert by_part["table"] == 33_554_432
    assert round(16 * n / 1e9, 2) == 8.13
    assert 16 * n / 16.9e9 > 0.25


# -- the yardstick's arithmetic ----------------------------------------------

def test_forward_flops_by_part_by_hand():
    parts = lfm2_flops.forward_flops_by_part(FILE, 16384)
    t, d = 16384, 2048
    assert set(parts) == {"conv_projections", "conv_core",
                          "attention_projections", "attention_scores",
                          "dense_ffn", "routed_experts", "router", "head"}
    assert parts["conv_projections"] == 4 * (2 * t * d * 6144 + 2 * t * d * d)
    assert parts["conv_core"] == 4 * t * d * (2 * 3 + 2)
    assert parts["attention_projections"] == \
        2 * t * d * (2048 + 2 * 512) + 2 * t * 2048 * d
    half = 16384 * 16385 // 2
    assert parts["attention_scores"] == 4 * 64 * 32 * half
    assert parts["dense_ffn"] == 6 * t * d * 7168
    assert t * 4 * 8 / 32 == 16384 and 16384 / 8 == 2048   # rows an expert
    assert parts["routed_experts"] == 4 * 6 * 16384 * d * 1792
    assert parts["router"] == 4 * 2 * t * d * 32
    assert parts["head"] == 2 * t * d * 16384
    total = sum(parts.values())
    assert total / t == pytest.approx(466.2e6, rel=1e-3)     # a token
    assert total == pytest.approx(7.64e12, rel=1e-3)
    conv = parts["conv_projections"] + parts["conv_core"]
    for part, share in ((conv, 0.288), (parts["dense_ffn"], 0.189),
                        (parts["routed_experts"], 0.189),
                        (parts["attention_scores"], 0.144),
                        (parts["attention_projections"], 0.045),
                        (parts["head"], 0.144)):
        assert part / total == pytest.approx(share, abs=0.001)
    # the first cell in which attention is under a fifth of the step
    assert (parts["attention_scores"] + parts["attention_projections"]) \
        / total < 0.2
    assert lfm2_flops.train_flops_per_sample(FILE, 16384) == 3 * total
    assert 3 * total == pytest.approx(22.9e12, rel=1e-3)


def test_the_hooks_count_each_call_by_hand():
    half = 134225920
    work = lfm2_flops.flash_work(FILE, TRAFFIC)
    assert len(work) == 2                   # one layer, forward and backward
    assert [fl for fl, _ in work] == [m * 64 * 32 * half for m in (4, 8)]
    q, kv, lse = 32 * 16384 * 64 * 2, 8 * 16384 * 64 * 2, 32 * 16384 * 4
    assert [by for _, by in work] == [2 * q + 2 * kv + lse,
                                      4 * q + 4 * kv + lse]
    even = lfm2_flops.held_experts_work(FILE, TRAFFIC, None)
    assert len(even) == 36 and even == lfm2_flops.held_experts_work(
        FILE, TRAFFIC, 8 / 32)
    assert all(fl == 2 * 16384 * 2048 * 1792 for fl, _ in even)
    assert even[:9] == trinity_flops.held_experts_matmuls(16384, 2048, 1792,
                                                          8)
    assert lfm2_flops.held_experts_work(FILE, TRAFFIC, 1 / 8)[0][0] \
        == even[0][0] / 2
    conv = lfm2_flops.short_conv_work(FILE, TRAFFIC)
    assert len(conv) == 8                   # four layers, forward and backward
    stream, filt = 16384 * 2048 * 2, 2048 * 3 * 4
    assert conv[0] == (16384 * 2048 * 8.0, 4 * stream + filt)
    assert conv[1] == (16384 * 2048 * 16.0, 7 * stream + 2 * filt)
    # the bytes set the least time, not the FLOPs: 0.33 and 0.57 ms a layer
    for fl, by in conv:
        assert by / 819e9 > 50 * fl / 197e12
    assert conv[0][1] / 819e9 == pytest.approx(0.328e-3, rel=1e-2)
    assert conv[1][1] / 819e9 == pytest.approx(0.574e-3, rel=1e-2)


# -- the readers on a hand-made trace -----------------------------------------

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _inputs(tmp_path, events, steps=2, config=FILE):
    inputs = scopes_test._inputs(tmp_path, events, steps)
    inputs.update(config=config, traffic={"seq_len": 16384}, peaks=PEAKS,
                  facts={"batch": 1, "chips": 1})
    return inputs


def _read(metric, inputs):
    return harness.load_module("layer_metrics", metric).read(inputs)


def test_the_two_readers_on_a_hand_made_trace(tmp_path):
    fwd, bwd, rc = ("jit(step)/pt.%s/" % r for r in ("fwd", "bwd", "rc"))
    inputs = _inputs(tmp_path, [
        ("fusion.1", fwd + "short_conv/conv_operator/mul:", 0, 40),
        ("fusion.2", bwd + "short_conv_grad/conv_operator/reduce:", 40, 100),
        ("fusion.3", rc + "short_conv/conv_operator/mul:", 140, 60),
        ("fusion.4", fwd + "mul/conv_operator/dot_general:", 200, 500),
        ("fusion.5", bwd + "mul_grad/conv_operator/dot_general:", 700, 900),
    ])
    # 200 ns under the op in its three roles in 2 steps; the projections'
    # 1400 ns are `mul`'s and not in here
    assert _read("short_conv_device_ms.train", inputs) == pytest.approx(
        100e-9 * 1e3)
    stream, filt = 16384 * 2048 * 2, 2048 * 3 * 4
    least = 4 * ((4 * stream + filt) + (7 * stream + 2 * filt)) / 819e9
    assert _read("short_conv_roofline", inputs) == pytest.approx(
        100 * least / 100e-9)


def test_the_readers_return_nothing_with_nothing_to_read(tmp_path):
    """A trace of another program (every older cell's, the parent's), a
    trace without scopes, no trace at all, a configuration that names no
    module of hooks, and one that names a module without the hook."""
    other = _inputs(tmp_path, [
        ("fusion.1", "jit(step)/pt.fwd/mul/dot_general:", 0, 100)])
    (tmp_path / "b").mkdir()
    bare = _inputs(tmp_path / "b", [("fusion.1", None, 0, 100)])
    none = dict(other, trace=None, trace_window=None)
    for inputs in (other, bare, none):
        for metric in NEW:
            assert _read(metric, inputs) is None, metric
    events = [("fusion.1", "jit(step)/pt.fwd/short_conv/mul:", 0, 40)]
    for i, config in enumerate((
            harness.load_json("benchmark/configs/trinity_mini.json"),
            dict(FILE, flops_module="smallthinker_flops"),
            dict(FILE, flops_module="no_such_module"))):
        (tmp_path / str(i)).mkdir()
        older = _inputs(tmp_path / str(i), events, config=config)
        assert _read("short_conv_roofline", older) is None, i
        assert _read("short_conv_device_ms.train", older) is not None


@pytest.mark.parametrize("metric", NEW)
def test_reader_without_inputs_returns_nothing(metric):
    empty = {"spans": [], "counters": {}, "e2e": {}, "trace": None,
             "facts": {"batch": 1, "chips": 1, "flops_per_sample": 1.0,
                       "samples_per_s": 1.0},
             "trace_window": None, "config": {}, "traffic": {},
             "peaks": None, "chips": 1}
    assert harness.load_module("layer_metrics", metric).read(empty) is None


# -- the cell end to end -----------------------------------------------------

def test_cell_end_to_end_on_cpu():
    config, traffic = toy_lfm2()
    result = harness.run_cell(CELL, seed=rehearsal.BIG_SEED, seconds=0.5,
                              trace=True, on_chip=False, config=config,
                              traffic=traffic, spec=SPEC)
    line = rehearsal.check_contract_line(result, CELL, 1)
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"     # and so: not a result
    assert len(line["compared"]) >= 2
    assert line["compared"][-1].startswith("correct: ")
    detail = line["compared"][1]
    assert "gradient against jax.grad of the reference" in detail
    assert "['lookup_table', 'fused_lm_head_ce'] (tied: True)" in detail
    assert "a state left unchanged reads 1" in detail


@pytest.mark.slow
def test_cell_end_to_end_on_cpu_with_the_recompute_fallback():
    config, traffic = toy_lfm2(recompute=True)
    result = harness.run_cell(CELL, seed=7, seconds=0.5, trace=False,
                              on_chip=False, config=config, traffic=traffic,
                              spec=SPEC)
    line = rehearsal.check_contract_line(result, CELL, 0)
    assert line["correct"] is True


# -- what the traffic decides ------------------------------------------------

def _built(seed, config=None, **traffic):
    c, t = toy_lfm2(**traffic)
    model = harness.load_module("models", CONFIG)
    return model, model.build_train(config or c, t, seed, 1, False), t


def _weights(m):
    return {p.name: np.asarray(m["scope"].find_var(p.name))
            for p in m["parameters"]}


def test_the_weights_are_the_model_and_the_seed_is_the_traffic():
    """Two values of ``--seed``: the same weights (``weights_seed``), other
    token ids, 64 sequences of the ring each of its own; the filter drawn as
    a depthwise Conv1d's; the fallback builds the same model."""
    _, a, _ = _built(11, ring=64)
    _, b, _ = _built(rehearsal.BIG_SEED)
    wa, wb = _weights(a), _weights(b)
    assert all(np.array_equal(wa[n], wb[n]) for n in wa)
    assert not np.array_equal(a["ring"][0]["src_ids"], b["ring"][0]["src_ids"])
    assert len(a["ring"]) == 64 and len({
        r["src_ids"].tobytes() for r in a["ring"]}) == 64
    ids = a["ring"][0]["src_ids"]
    assert ids.min() >= 1 and ids.max() < 128
    np.testing.assert_array_equal(a["ring"][0]["lm_label"][:, :-1],
                                  ids[:, 1:])
    filt = wa["dec_0.conv.filter"]
    assert filt.shape == (64, 3) and np.abs(filt).max() <= 3 ** -0.5
    assert np.abs(filt).max() > 0.9 * 3 ** -0.5
    assert np.all(wa["dec_1.moe.select_bias"] == 0)
    _, c, _ = _built(11, recompute=True)
    wc = _weights(c)
    assert all(np.array_equal(wa[n], wc[n]) for n in wa)
    types = [op.type for op in c["program"].global_block().ops]
    assert types.count("short_conv") == 4 + 3       # the clones of blocks 0-3
    assert a["table_reads"] == c["table_reads"] == ["lookup_table",
                                                    "fused_lm_head_ce"]


def test_the_rate_warms_up_inside_the_program():
    from benchmark.models import _train
    _, m, _ = _built(11)
    before = _weights(m)
    feed = _train.put_ring(m["ring"], 1)[0]
    moved = []
    for _ in range(4):
        m["exe"].run(m["program"], feed=feed, fetch_list=[m["loss"]],
                     scope=m["scope"])
        after = _weights(m)
        moved.append(max(float(np.abs(after[n] - before[n]).max())
                         for n in before))
    types = [op.type for op in m["program"].global_block().ops]
    assert "increment" in types and "less_than" in types    # the schedule
    assert moved[0] == 0.0 and 0 < moved[3] < 5e-6
    assert np.abs(np.asarray(m["scope"].find_var(
        m["moment1"]["word_embedding"]))).max() > 0
    assert "dec_1.moe.select_bias" not in m["moment1"]


# -- planted faults against the cell's own comparisons ------------------------

class _Swapped:
    """``obj`` with some attributes replaced."""

    def __init__(self, obj, **swap):
        self.__dict__.update(swap)
        self._obj = obj

    def __getattr__(self, name):
        return getattr(self._obj, name)


@pytest.mark.parametrize("fault", [
    None, "state left unchanged", "a decay left out",
    "the optimizer's default decay"])
def test_the_replayed_update_against_the_references_adamw(fault):
    """The step once more half-way up the warm-up moves every trained
    parameter as the reference's AdamW does; a state left unchanged reads
    1, a decay left out or left at the optimizer's default reads over the
    limit on some leaf."""
    model, m, t = _built(11)
    ref = harness.load_module("reference", CONFIG)
    config, _ = toy_lfm2()
    feed = m["ring"][0]
    _, grads = model._trinity._replayed_first_step(m, feed)
    if fault == "state left unchanged":
        m["exe"] = _Swapped(m["exe"], run=lambda *a, **k: None)
    elif fault == "a decay left out":
        ref = _Swapped(ref, adamw=lambda p, steps, decay: ref._obj.adamw(
            p, steps, 0.0))
    elif fault == "the optimizer's default decay":   # 0.01, not the traffic's
        ref = _Swapped(ref, adamw=lambda p, steps, decay: ref._obj.adamw(
            p, steps, 0.01))
    trained = [v for v in m["parameters"] if v.name in m["moment1"]]
    got = model._small._replayed_update(dict(m, parameters=trained), t, feed,
                                        grads, ref)
    limit = config["loss_tolerance"]["replayed_update_relative"]
    assert got["rate"] == pytest.approx(2e-4)
    if fault is None:
        assert got["worst"][0] <= limit and got["all"] <= limit / 10
    elif fault == "state left unchanged":
        assert got["all"] == 1.0 and got["worst"][0] == 1.0
    else:
        assert got["worst"][0] > limit, got


def _gradient_reading(model, m, t, ref, cfg):
    """The cell's own gradient comparison at toy widths: the timed step's
    first gradient against ``jax.grad`` of ``ref``."""
    import jax.numpy as jnp
    feed = m["ring"][0]
    _, grads = model._trinity._replayed_first_step(m, feed)
    model._trinity._initial_state(m)
    params = model.reference_params(
        lambda n: jnp.asarray(m["scope"].find_var(n), jnp.float32), cfg)
    _, g_ref = model.reference_gradient(ref, params, feed, cfg, 16)
    return model.gradient_difference(
        g_ref, model.reference_params(grads.__getitem__, cfg,
                                      select_bias=False))


@pytest.mark.parametrize("fault", [
    None, "the table untied", "the convolution shifted by one",
    "the bias added to the gates"])
def test_the_steps_gradient_check_catches(fault):
    """The step's first gradient against the reference's, by the cell's own
    comparison and the toy limits: within them as built; a reference whose
    head has a weight of its own (the table's leaf loses the head's part), a
    convolution that reads one position further back, or a selection bias
    that joins the weights and not the choice alone, is over a limit."""
    import jax
    import jax.numpy as jnp
    model, m, t = _built(11)
    real = harness.load_module("reference", CONFIG)
    ref = real
    if fault == "the table untied":
        ref = _Swapped(real, loss=lambda p, ids, lab, **kw: real.loss(
            dict(p, head_w=jax.lax.stop_gradient(p["wte"].T)), ids, lab,
            **kw))
    elif fault == "the convolution shifted by one":
        def short_conv(z, blk):
            b_, c_, u = jnp.split(z @ blk["in_w"], 3, axis=-1)
            taps = blk["conv_w"].shape[1]
            c = sum(blk["conv_w"][:, j] * real.shifted(b_ * u, taps - j)
                    for j in range(taps))
            return (c_ * c) @ blk["out_w"]
        ref = _Patched(real, short_conv=short_conv)
    elif fault == "the bias added to the gates":
        def route(mm, blk, top_k, route_scale):
            s = jax.nn.sigmoid(mm.astype(jnp.float32)
                               @ blk["router_w"].astype(jnp.float32))
            _, top_e = jax.lax.top_k(s, top_k)
            biased = s + 0.3 * jnp.cos(jnp.arange(s.shape[-1]))
            kept = biased * jnp.sum(jax.nn.one_hot(
                top_e, s.shape[-1], dtype=s.dtype), axis=1)
            return kept / (jnp.sum(kept, -1, keepdims=True)
                           + real.NORM_EPS) * route_scale, top_e
        ref = _Patched(real, route=route)
    with ref if isinstance(ref, _Patched) else _null():
        off = _gradient_reading(model, m, t,
                                real if isinstance(ref, _Patched) else ref,
                                m["cfg"])
    limits = toy_lfm2()[0]["loss_tolerance"]
    within = all(off[k][model.DECIDES[k]]
                 <= limits[f"first_gradient_{k}_relative"]
                 for k in ("rest", "experts", "router")) \
        and off["all"] <= limits["first_gradient_all_relative"]
    assert within is (fault is None), (fault, off)


class _Patched:
    """A context that swaps attributes of a module and puts them back (the
    reference's functions call one another by their module's names)."""

    def __init__(self, module, **swap):
        self.module, self.swap, self.saved = module, swap, {}

    def __enter__(self):
        for k, v in self.swap.items():
            self.saved[k] = getattr(self.module, k)
            setattr(self.module, k, v)

    def __exit__(self, *exc):
        for k, v in self.saved.items():
            setattr(self.module, k, v)


class _null:
    def __enter__(self):
        pass

    def __exit__(self, *exc):
        pass


# -- what the lowered step names ---------------------------------------------

def test_the_lowered_step_names_the_operators_the_op_and_the_tied_head():
    """What the readers and the by-op breakdown depend on: ``short_conv``
    and its grad op under their own names with the ``conv_operator`` tag
    behind, the ``attention_operator`` tag behind the attention's ops, the
    four parts of ``moe_ffn``; and the counters name what was lowered:
    groups of 4 at the toy width, sigmoid scores over a share, the head
    reading a table that has two readers."""
    import jax.numpy as jnp
    from benchmark import part_scopes
    from benchmark.models import _train
    from paddle_tpu.ops import attention_ops, moe_ops, nn_ops
    config, traffic = toy_lfm2()
    model = harness.load_module("models", CONFIG)
    labels = dict(impl="ragged_dot", experts="8", top_k="2", held="4",
                  score_func="sigmoid", act="silu", router_input="x")
    lowered = moe_ops.MOE_LOWERINGS_CTR.value(**labels)
    grouped = attention_ops.FLASH_LOWERINGS_CTR.value(
        window="none", kv_groups="4", widths="8/8")
    tied = nn_ops.TIED_HEAD_LOWERINGS_CTR.value(table_reads="2")
    m = model.build_train(config, traffic, 11, 1, False)
    exe, scope = m["exe"], m["scope"]
    feed = _train.put_ring(m["ring"], 1)[0]
    exe.run(m["program"], feed=feed, fetch_list=[m["loss"]], scope=scope)
    assert moe_ops.MOE_LOWERINGS_CTR.value(**labels) >= lowered + 4
    assert attention_ops.FLASH_LOWERINGS_CTR.value(
        window="none", kv_groups="4", widths="8/8") >= grouped + 1
    assert nn_ops.TIED_HEAD_LOWERINGS_CTR.value(table_reads="2") >= tied + 1
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

    for op in ("pt.fwd/short_conv/conv_operator",
               "pt.bwd/short_conv_grad/conv_operator",
               "pt.fwd/mul/conv_operator", "pt.bwd/mul_grad/conv_operator",
               "pt.fwd/flash_attention/attention_operator",
               "pt.bwd/flash_attention_grad/attention_operator",
               "pt.fwd/mul/attention_operator", "pt.fwd/rope/attention_operator",
               "pt.fwd/mul/dense_ffn", "pt.fwd/rms_norm", "pt.opt/adamw",
               "pt.fwd/fused_lm_head_ce", "pt.fwd/lookup_table"):
        assert any(s == op or s.startswith(op + "/") for s in stacks), op
    for role_op in ("pt.fwd/moe_ffn/", "pt.bwd/moe_ffn_grad/"):
        seen = {part_scopes.part_of(r, part_scopes.MOE_PARTS)
                for r in under(role_op)}
        assert seen >= set(part_scopes.MOE_PARTS), (role_op, seen)
    assert json.dumps(sorted(stacks))       # names only, nothing device-bound
