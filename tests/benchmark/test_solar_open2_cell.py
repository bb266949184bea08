"""The Solar-Open2 cell (``solar_open2_250b_lm_s8192_r64``) rehearsed on the
CPU at toy widths: its files, entries and metrics picked BY NAME (never by
position), the configuration file against the catalog row, the parameter
count from the program, the FLOPs by part and ``kda_work``'s bytes by hand,
the three new readers on a hand-made trace and with nothing to read, the cell
end to end to the contract's last line, what the traffic decides, planted
faults against the cell's own limits, and what the lowered step names.
Nothing here is a speed number."""

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

from benchmark import harness, solar_open2_flops, trinity_flops  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import test_benchmark_rehearsal as rehearsal  # noqa: E402
import test_lfm2_cell as lfm2_test  # noqa: E402
import test_op_scopes as scopes_test  # noqa: E402
import test_program_scopes as program_scopes_test  # noqa: E402

CELL = "solar_open2_250b_lm_s8192_r64"
CONFIG = "solar_open2_250b"
SPEC = harness.load_spec()
FILE = harness.load_json(f"benchmark/configs/{CONFIG}.json")
TRAFFIC = harness.load_traffic("lm_s8192_r64")
NEW = ("kda_device_ms.train", "kda_scan_device_ms.train", "kda_scan_roofline")
REDUCED = ["num_hidden_layers", "gqa_layers", "num_attention_heads",
           "num_key_value_heads", "linear_attn_config", "n_routed_experts",
           "vocab_size"]
#: the per-layer lists the cell joins (ISSUE 49); ``recompute_device_ms.
#: train`` among them: the step recomputes (the traffic file's
#: ``recompute_why``)
LISTS = (
    "dispatch_ms.train", "step_device_ms.train", "train_mfu",
    "train_device_idle_share", "op_scoped_share.train", "fwd_device_ms.train",
    "bwd_device_ms.train", "opt_device_ms.train", "xla_remat_device_ms.train",
    "vjp_forward_again_device_ms.train", "attention_device_ms.train",
    "lm_head_device_ms.train", "moe_device_ms.train",
    "moe_dispatch_device_ms.train", "recompute_device_ms.train",
    "short_conv_device_ms.train", "short_conv_roofline") + NEW
_Swapped = lfm2_test._Swapped


def toy_solar(**traffic):
    c = copy.deepcopy(FILE)
    c.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
             head_dim=16, moe_intermediate_size=32, n_routed_experts=4,
             num_experts_per_tok=2, vocab_size=128, num_hidden_layers=3)
    c["linear_attn_config"].update(num_heads=2, head_dim=16)
    c["assumed"].update(router_outputs=8, expert_offset=2, kda_gate_rank=8,
                        kda_chunk=16)
    # toy widths: the fused head's bf16 products move the loss by 1e-4 and
    # bf16 AMP by 1e-2; the chip's limits are set at the real widths
    c["loss_tolerance"] = {"relative": 2e-3, "hidden_relative": 1e-3,
                           "top_k_differ_share": 0.02,
                           "first_hidden_relative": 5e-2,
                           "first_gradient_rest_relative": 0.12,
                           "first_gradient_experts_relative": 0.3,
                           "first_gradient_router_relative": 0.5,
                           "first_gradient_kda_relative": 0.6,
                           "first_gradient_all_relative": 0.1,
                           "replayed_update_relative": 3e-3,
                           "reason": "toy widths"}
    t = copy.deepcopy(TRAFFIC)
    t.update(batch_per_chip=2, seq_len=40, ring=2, warmup_steps=1,
             check_batch=2, reference_q_block=8)
    t.update(traffic)
    return c, t


# -- BENCHMARK.json ----------------------------------------------------------

def test_the_cell_is_listed_with_its_files_and_metrics():
    cell = harness.find(SPEC["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "lm_s8192_r64", 1)
    assert len(cell["why"]) <= 200
    cfg = harness.find(SPEC["configs"], CONFIG, "config")
    assert FILE["reduced"] == cfg["reduced"] == REDUCED
    assert FILE["source"] == cfg["source"] and len(cfg["why"]) <= 200
    assert cfg["file"] == f"benchmark/configs/{CONFIG}.json"
    for kind, fn in (("models", "build_train"), ("reference", "loss")):
        assert callable(getattr(harness.load_module(kind, CONFIG), fn))
    e2e = {m["name"] for m in harness.metrics_of_cell(SPEC, "end_to_end",
                                                      CELL)}
    assert e2e == {"train_samples_per_s", "peak_hbm_gb", "setup_s"}
    layer = harness.metrics_of_cell(SPEC, "per_layer", CELL)
    names = {m["name"] for m in layer}
    assert names >= {"first_step_program_s", "first_step_backend_s",
                     "retrace_s"}
    # a per-layer metric's cell reports the end-to-end metric it moves
    assert {m["moves"] for m in layer} <= e2e
    listed = {m["name"] for m in SPEC["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == set(LISTS)
    for name in NEW:
        assert callable(harness.load_module("layer_metrics", name).read)
        m, = [m for m in SPEC["per_layer"] if m["name"] == name]
        assert m["workloads"] == [CELL] and m["layer"] == "compiled step"
        assert m["moves"] == "train_samples_per_s"
        assert m["source"] == "device_trace"
    # nothing of this cell rides a list a passing test pins
    pinned, = [m for m in SPEC["per_layer"]
               if m["name"] == "moe_local_rows_share"]
    assert CELL not in pinned["workloads"]


def test_the_traffic_file_says_what_the_issue_fixed():
    t = TRAFFIC
    assert (t["kind"], t["batch_per_chip"], t["seq_len"], t["warmup_steps"],
            t["check_batch"]) == ("train_ring", 1, 8192, 3, 1)
    assert (t["learning_rate"], t["lr_start"], t["weight_decay"],
            t["weights_seed"]) == (4e-4, 0.0, 0.1, 1)
    assert (t["ring"], t["lr_warmup_steps"]) == (64, 2000)
    # by the issue's rule: true only because the TPU compiler refuses the
    # plain step; both peaks stand in recompute_why
    assert t["recompute"] is True
    assert "19.90" in t["recompute_why"] and "GB" in t["recompute_why"]
    assert "2000 steps" in FILE["assumed"]["optimizer"]


CATALOG = {
    "model_type": "solar_open2", "partial_rotary_factor": 1,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                           "num_heads": 64, "num_kv_heads": None},
    "hidden_size": 4096, "num_hidden_layers": 48, "num_attention_heads": 64,
    "head_dim": 128, "num_key_value_heads": 8, "vocab_size": 196608,
    "intermediate_size": 10240, "moe_intermediate_size": 1280,
    "rms_norm_eps": 1e-05, "rope_theta": 10000, "tie_word_embeddings": False,
    "max_position_embeddings": 1048576, "first_k_dense_replace": 0,
    "use_rope": False, "gqa_interval": 3,
    "gqa_layers": [0, 4, 8, 12, 16, 20, 24, 28, 32, 36, 40, 44],
    "use_gqa_gate": True, "kda_use_full_proj": False,
    "kda_allow_neg_eigval": True, "n_routed_experts": 320,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "num_experts_per_tok": 8}


def test_the_config_file_holds_the_catalogs_numbers():
    """Every key of the catalog entry's ``config`` under the same key; the
    keys that differ are the seven listed, no width among them, and inside
    the one nested group only the count of heads."""
    differ = sorted(k for k, v in CATALOG.items() if FILE[k] != v)
    assert differ == sorted(FILE["reduced"]) == sorted(REDUCED)
    lin, was = FILE["linear_attn_config"], CATALOG["linear_attn_config"]
    assert [k for k in was if lin[k] != was[k]] == ["num_heads"]
    assert (FILE["num_hidden_layers"], FILE["gqa_layers"],
            FILE["num_attention_heads"], FILE["num_key_value_heads"],
            lin["num_heads"], FILE["n_routed_experts"],
            FILE["vocab_size"]) == (4, [0], 8, 1, 8, 8, 24576)
    # the heads' share is the vocabulary's and the K/V heads': an eighth
    assert FILE["vocab_size"] * 8 == CATALOG["vocab_size"]
    assert FILE["num_attention_heads"] * 8 == CATALOG["num_attention_heads"]
    a = FILE["assumed"]
    assert a["router_outputs"] == 320 and a["expert_offset"] == 0
    assert a["kda_gate_rank"] == 128 and a["kda_chunk"] == 64
    for assumption in ("published", "heads_note", "kda_equations",
                       "gqa_equations", "routing", "initial_values",
                       "intermediate_size_note", "optimizer", "weights",
                       "data", "reduced_note", "kda_no_bias"):
        assert len(a[assumption]) > 40, assumption
    for said in ("40 chips", "8-way", "five such groups", "40-way",
                 "44 layers"):
        assert said in FILE["deployment"], said
    assert "840,872,600" in a["parameters"] and "13.45 GB" in a["parameters"]
    assert FILE["flops_module"] == "solar_open2_flops"
    tol = FILE["loss_tolerance"]
    for key in ("relative", "hidden_relative", "top_k_differ_share",
                "first_hidden_relative", "first_gradient_rest_relative",
                "first_gradient_experts_relative",
                "first_gradient_router_relative",
                "first_gradient_kda_relative",
                "first_gradient_all_relative", "replayed_update_relative"):
        assert 0 < tol[key] < 1, key
    for key in ("reason", "first_gradient_reason", "replayed_update_reason"):
        assert "control" in tol[key] or "unchanged" in tol[key], key


def test_the_parameters_are_840_872_600_counted_from_the_program():
    """840,872,600 parameters at 16 bytes: 13.45 GB, from the shapes the
    program holds and, by part, from ``solar_open2_flops.parameters``."""
    from paddle_tpu.framework import Program, program_guard
    from paddle_tpu.models import transformer as T
    model = harness.load_module("models", CONFIG)
    main = Program()
    with program_guard(main, Program()):
        T.build_solar_open2_pretrain(model.solar_config(FILE), 8192)
    shapes = {p.name: tuple(p.shape) for p in main.all_parameters()}
    # the first caller whose heads are narrower than the hidden size
    assert shapes["dec_0.attn.qkv.w"] == (4096, 1024 + 128 + 128 + 1024)
    assert shapes["dec_0.attn.out.w"] == (1024, 4096)
    assert shapes["dec_1.kda.in_proj.w"] == (4096, 3 * 1024 + 128 + 128 + 8)
    assert shapes["dec_1.kda.conv.filter"] == (3072, 4)
    assert shapes["dec_2.kda.f_up.w"] == shapes["dec_2.kda.g_up.w"] \
        == (128, 1024)
    assert shapes["dec_3.kda.A_log"] == (8,)
    assert shapes["dec_3.kda.dt_bias"] == (1024,)
    assert shapes["dec_3.kda.o_norm.w"] == (128,)
    assert shapes["dec_3.kda.out.w"] == (1024, 4096)
    assert shapes["dec_0.shared.gate_up.w"] == (4096, 2560)
    assert shapes["dec_3.moe.router.w"] == (4096, 320)
    assert shapes["dec_3.moe.select_bias"] == (320,)
    assert shapes["dec_3.moe.gate.w"] == (8, 4096, 1280)
    assert shapes["word_embedding"] == shapes["lm_out.w"][::-1] \
        == (24576, 4096)
    assert "dec_0.kda.in_proj.w" not in shapes
    assert "dec_1.attn.qkv.w" not in shapes
    assert not any(n.endswith(".b") for n in shapes)

    def layer(i):
        return sum(int(np.prod(s)) for n, s in shapes.items()
                   if n.startswith(f"dec_{i}."))
    experts = 8 * 3 * 4096 * 1280
    assert [layer(i) - experts for i in range(4)] == \
        [30_679_360] + [35_182_024] * 3
    n = sum(int(np.prod(s)) for s in shapes.values())
    by_part = solar_open2_flops.parameters(FILE)
    assert n == sum(by_part.values()) == 840_872_600
    assert by_part["kda"] == 3 * 18_134_152
    assert by_part["attention"] == 13_631_488
    assert by_part["shared_expert"] == 4 * 15_728_640
    assert by_part["experts"] == 4 * experts == 503_316_480
    assert by_part["router"] == 4 * 1_311_040
    assert by_part["embedding_and_head"] == 201_326_592
    assert round(16 * n / 1e9, 2) == 13.45
    assert 16 * n / 16.9e9 > 0.25


# -- the yardstick's arithmetic ----------------------------------------------

def test_forward_flops_by_part_by_hand():
    parts = solar_open2_flops.forward_flops_by_part(FILE, 8192)
    t, d = 8192, 4096
    assert set(parts) == {"kda_projections", "kda_conv", "kda_scan",
                          "attention_projections", "attention_scores",
                          "shared_expert", "routed_experts", "router", "head"}
    assert parts["kda_projections"] == 3 * 2 * t * (
        d * 3336 + 2 * 128 * 1024 + 1024 * d)
    assert parts["kda_conv"] == 3 * t * 3072 * 2 * 4
    assert parts["attention_projections"] == 2 * t * (d * 2304 + 1024 * d)
    assert parts["attention_scores"] == 4 * 128 * 8 * (8192 * 8193 // 2)
    assert parts["shared_expert"] == 4 * 6 * t * d * 1280
    assert t * 8 * 8 / 320 == 1638.4 and 1638.4 / 8 == 204.8  # rows an expert
    assert parts["routed_experts"] == 4 * 6 * 1638.4 * d * 1280
    assert parts["router"] == 4 * 2 * t * d * 320
    assert parts["head"] == 2 * t * d * 24576
    # the scan: 139 kFLOP a token and head, three layers of eight heads
    per_chunk = solar_open2_flops.scan_flops_per_chunk(64, 128, 128)
    assert per_chunk == 2 * 64 * 64 * 128 + 64 * 64 * 256 \
        + 6 * 64 * 128 * 128 + 64 * 64 * 128
    assert per_chunk / 64 == pytest.approx(139e3, rel=5e-3)
    assert parts["kda_scan"] == 3 * 8 * 128 * per_chunk
    total = sum(parts.values())
    assert total == pytest.approx(4.25e12, rel=2e-3)
    assert parts["kda_scan"] / total == pytest.approx(0.0064, abs=2e-4)
    assert solar_open2_flops.train_flops_per_sample(FILE, 8192) == 3 * total


def test_the_hooks_count_each_call_by_hand():
    work = solar_open2_flops.flash_work(FILE, TRAFFIC)
    assert work == trinity_flops.flash_layer_kernels(8, 1, 8192, 128)
    even = solar_open2_flops.held_experts_work(FILE, TRAFFIC, None)
    assert len(even) == 36 and even == solar_open2_flops.held_experts_work(
        FILE, TRAFFIC, 8 / 320)
    assert all(fl == 2 * 1638.4 * 4096 * 1280 for fl, _ in even)
    kda = solar_open2_flops.kda_work(FILE, 8192, 64)
    assert len(kda) == 6                  # three layers, forward and backward
    t, h, dk = 8192, 8, 128
    stream = t * h * dk
    qkv, out, gates = 3 * stream * 2, stream * 2, t * h * (dk + 1) * 4
    states = 128 * h * dk * dk * 4
    fwd, bwd = kda[:2]
    assert fwd == (128 * 8 * solar_open2_flops.scan_flops_per_chunk(
        64, 128, 128), qkv + gates + out + states)
    assert bwd == (2 * fwd[0], 2 * (qkv + gates + out) + states)
    assert states == 67_108_864
    # the bytes set the least time, not the FLOPs
    for fl, by in kda:
        assert by / 819e9 > 3 * fl / 197e12
    least = sum(by for _, by in kda) / 819e9
    assert least == pytest.approx(1.60e-3, rel=2e-2)
    # a ragged length counts its last chunk whole
    assert solar_open2_flops.kda_work(FILE, 100, 64)[0][0] == \
        2 * 8 * solar_open2_flops.scan_flops_per_chunk(64, 128, 128)


# -- the readers on a hand-made trace -----------------------------------------

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _inputs(tmp_path, events, steps=2, config=FILE):
    inputs = scopes_test._inputs(tmp_path, events, steps)
    inputs.update(config=config, traffic={"seq_len": 8192}, peaks=PEAKS,
                  facts={"batch": 1, "chips": 1})
    return inputs


def _read(metric, inputs):
    return harness.load_module("layer_metrics", metric).read(inputs)


def test_the_three_readers_on_a_hand_made_trace(tmp_path):
    fwd, bwd, rc = ("jit(step)/pt.%s/" % r for r in ("fwd", "bwd", "rc"))
    inputs = _inputs(tmp_path, [
        ("fusion.1", fwd + "mul/kda/dot_general:", 0, 100),
        ("fusion.2", fwd + "short_conv/kda/mul:", 100, 20),
        ("fusion.3", fwd + "kda_scan/kda/while:", 120, 60),
        ("fusion.4", bwd + "kda_scan_grad/kda/while:", 180, 140),
        ("fusion.5", rc + "kda_scan/kda/while:", 320, 60),
        ("fusion.6", bwd + "mul_grad/kda/dot_general:", 380, 200),
        ("fusion.7", fwd + "mul/attn/dot_general:", 580, 500),
        ("fusion.8", bwd + "moe_ffn_grad/experts/gmm:", 1080, 900),
    ])
    # 580 ns under the tag in 2 steps; 260 of them under the scan's two ops
    assert _read(NEW[0], inputs) == pytest.approx(290e-9 * 1e3)
    assert _read(NEW[1], inputs) == pytest.approx(130e-9 * 1e3)
    least = sum(max(fl / 197e12, by / 819e9)
                for fl, by in solar_open2_flops.kda_work(FILE, 8192, 64))
    assert _read(NEW[2], inputs) == pytest.approx(100 * least / 130e-9)


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
    events = [("fusion.1", "jit(step)/pt.fwd/kda_scan/kda/while:", 0, 40)]
    for i, config in enumerate((
            harness.load_json("benchmark/configs/lfm2_8b_a1b.json"),
            dict(FILE, flops_module="no_such_module"))):
        (tmp_path / str(i)).mkdir()
        older = _inputs(tmp_path / str(i), events, config=config)
        assert _read(NEW[2], older) is None, i
        assert _read(NEW[1], older) is not None


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
    config, traffic = toy_solar()
    assert traffic["recompute"] is True          # as the chip runs it
    result = harness.run_cell(CELL, seed=rehearsal.BIG_SEED, seconds=0.5,
                              trace=True, on_chip=False, config=config,
                              traffic=traffic, spec=SPEC)
    line = rehearsal.check_contract_line(result, CELL, 1)
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"     # and so: not a result
    assert len(line["compared"]) >= 2
    assert line["compared"][-1].startswith("correct: ")
    detail = line["compared"][1]
    assert "gradient against jax.grad of the reference" in detail
    assert "kda: worst leaf" in detail
    assert "a state left unchanged reads 1" in detail
    assert "limits exceeded: none" in detail


@pytest.mark.slow
def test_cell_end_to_end_on_cpu_without_recomputation():
    config, traffic = toy_solar(recompute=False)
    result = harness.run_cell(CELL, seed=7, seconds=0.5, trace=False,
                              on_chip=False, config=config, traffic=traffic,
                              spec=SPEC)
    line = rehearsal.check_contract_line(result, CELL, 0)
    assert line["correct"] is True


# -- what the traffic decides ------------------------------------------------

def _built(seed, **traffic):
    c, t = toy_solar(**traffic)
    model = harness.load_module("models", CONFIG)
    return model, model.build_train(c, t, seed, 1, False), t


def _weights(m):
    return {p.name: np.asarray(m["scope"].find_var(p.name))
            for p in m["parameters"]}


def test_the_weights_are_the_model_and_the_seed_is_the_traffic():
    """Two values of ``--seed``: the same weights (``weights_seed``), other
    token ids, 64 sequences of the ring each of its own; the KDA layer's
    initial values; the plain step builds the same model and computes
    nothing again."""
    _, a, _ = _built(11, ring=64)
    _, b, _ = _built(rehearsal.BIG_SEED, recompute=False)
    wa, wb = _weights(a), _weights(b)
    assert all(np.array_equal(wa[n], wb[n]) for n in wa)
    assert not np.array_equal(a["ring"][0]["src_ids"], b["ring"][0]["src_ids"])
    assert len(a["ring"]) == 64 and len({
        r["src_ids"].tobytes() for r in a["ring"]}) == 64
    ids = a["ring"][0]["src_ids"]
    assert ids.min() >= 1 and ids.max() < 128
    np.testing.assert_array_equal(a["ring"][0]["lm_label"][:, :-1],
                                  ids[:, 1:])
    a_log, dt_bias = wa["dec_1.kda.A_log"], wa["dec_1.kda.dt_bias"]
    assert np.all((a_log >= 0) & (a_log <= np.log(16.0)))
    dt = np.log1p(np.exp(dt_bias.astype(np.float64)))     # softplus
    assert np.all((dt >= 1e-3 * 0.999) & (dt <= 1e-1 * 1.001))
    assert not np.array_equal(a_log, wa["dec_2.kda.A_log"])
    assert np.abs(wa["dec_1.kda.conv.filter"]).max() <= 0.5
    assert np.all(wa["dec_1.kda.o_norm.w"] == 1)
    assert np.all(wa["dec_1.moe.select_bias"] == 0)
    assert 0.01 < wa["dec_0.moe.gate.w"].std() < 0.03
    types = [[op.type for op in m["program"].global_block().ops]
             for m in (a, b)]
    # three blocks, two of them KDA: all computed again, or none
    assert [t.count("kda_scan") for t in types] == [2 + 2, 2]
    assert [t.count("kda_scan_grad") for t in types] == [2, 2]
    assert [t.count("flash_attention") for t in types] == [1 + 1, 1]


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
    assert moved[0] == 0.0 and 0 < moved[3] < 5e-5
    for name in ("word_embedding", "dec_1.kda.A_log", "dec_2.kda.dt_bias",
                 "dec_1.kda.conv.filter", "dec_0.attn.qkv.w"):
        assert np.abs(np.asarray(m["scope"].find_var(
            m["moment1"][name]))).max() > 0, name
    assert "dec_1.moe.select_bias" not in m["moment1"]


# -- planted faults against the cell's own comparisons ------------------------

@pytest.mark.parametrize("fault", [None, "state left unchanged",
                                   "a decay left out"])
def test_the_replayed_update_against_the_references_adamw(fault):
    """The step once more half-way up the warm-up moves every trained
    parameter as the reference's AdamW does; a state left unchanged reads
    1, a decay left out reads over the limit on some leaf."""
    model, m, t = _built(11)
    ref = harness.load_module("reference", CONFIG)
    config, _ = toy_solar()
    feed = m["ring"][0]
    _, grads = model._trinity._replayed_first_step(m, feed)
    if fault == "state left unchanged":
        m["exe"] = _Swapped(m["exe"], run=lambda *a, **k: None)
    elif fault == "a decay left out":
        ref = _Swapped(ref, adamw=lambda p, steps, decay: ref._obj.adamw(
            p, steps, 0.0))
    trained = [v for v in m["parameters"] if v.name in m["moment1"]]
    got = model._xing._replayed_update(dict(m, parameters=trained), t, feed,
                                       grads, ref)
    limit = config["loss_tolerance"]["replayed_update_relative"]
    assert got["rate"] == pytest.approx(2e-4)
    if fault is None:
        assert got["worst"][0] <= limit and got["all"] <= limit / 10
    elif fault == "state left unchanged":
        assert got["all"] == 1.0 and got["worst"][0] == 1.0
    else:
        assert got["worst"][0] > limit, got


@pytest.mark.parametrize("reading, limit", [
    ("f32_hidden", "hidden_relative"), ("gradient_kda",
                                        "first_gradient_kda_relative"),
    ("update", "replayed_update_relative"), ("replay", "replay"),
    ("dropless", "dropless")])
def test_decide_names_the_limit_a_reading_exceeds(reading, limit):
    model = harness.load_module("models", CONFIG)
    tol = toy_solar()[0]["loss_tolerance"]
    sound = dict(f32_loss=0.0, f32_share=0.0, f32_hidden=0.0,
                 first_hidden=0.0, update=0.0, first_loss=0.0,
                 first_forward=0.0, replay=0.0, dropless=True,
                 gradient_all=0.0, **{f"gradient_{k}": 0.0
                                      for k in model.KINDS})
    assert model.decide(tol, sound) == (True, [])
    off = dict(sound, **{reading: False if reading == "dropless"
                         else float("nan")})
    assert model.decide(tol, off) == (False, [limit])


@pytest.mark.parametrize("leaf", ["a_log", "dt_bias", "o_norm_w", "conv_k"])
def test_a_small_kda_leaf_gone_wrong_exceeds_the_kinds_limit(leaf):
    """The kind ``kda`` is held to its WORST leaf: a gradient wrong in
    ``A_log``'s 8 numbers alone (a per-head decay for a per-channel one in
    ``kda_scan_grad``) hardly moves the kind's leaves together, beside the
    down-projections' 500k numbers each, and is over the configuration
    file's limit on its own leaf."""
    model = harness.load_module("models", CONFIG)
    r = np.random.RandomState(5)
    shapes = {"wf_down": (4096, 128), "wg_down": (4096, 128),
              "wf_up": (128, 1024), "a_log": (8,), "dt_bias": (1024,),
              "o_norm_w": (128,), "conv_k": (1024, 4), "wq": (64, 64)}
    ref = {"blocks": [{k: r.randn(*v) for k, v in shapes.items()}]}
    got = {"blocks": [{k: v.astype(np.float32) * (-1.0 if k == leaf else 1.0)
                       for k, v in ref["blocks"][0].items()}]}
    assert model.DECIDES["kda"] == 1
    out = model.gradient_difference(ref, got)
    together, worst, name = out["kda"]
    limit = FILE["loss_tolerance"]["first_gradient_kda_relative"]
    assert name.endswith(f"['{leaf}']") and worst == pytest.approx(2.0)
    assert together < limit < worst
    assert out["rest"][1] < 1e-6 and out["all"] < limit


# -- what the lowered step names ---------------------------------------------

def test_the_lowered_step_names_the_ops_their_roles_and_the_tag():
    """What the readers and the by-op breakdown depend on: ``kda_scan`` and
    its grad op under ``pt.fwd``, ``pt.bwd`` and ``pt.rc``, the ``kda``,
    ``attn`` and ``shared_expert`` tags, ``moe_ffn``'s parts; and the
    counters name what was lowered."""
    import jax.numpy as jnp
    from benchmark import part_scopes
    from benchmark.models import _train
    from paddle_tpu.framework.recompute import RECOMPUTE_OPS_CTR
    from paddle_tpu.ops import kda_ops, sequence_ops
    config, traffic = toy_solar()
    model = harness.load_module("models", CONFIG)
    labels = dict(heads="2", head_dim="16", chunk="16", impl="xla",
                  neg_eigval="true")
    conv = dict(taps="4", gated="false", act="silu")
    kda = kda_ops.KDA_LOWERINGS_CTR.value(**labels)
    convs = sequence_ops.SHORT_CONV_LOWERINGS_CTR.value(**conv)
    again = RECOMPUTE_OPS_CTR.value(op="kda_scan")
    m = model.build_train(config, traffic, 11, 1, False)
    exe, scope = m["exe"], m["scope"]
    feed = _train.put_ring(m["ring"], 1)[0]
    exe.run(m["program"], feed=feed, fetch_list=[m["loss"]], scope=scope)
    # two KDA layers: forward, again, backward
    assert kda_ops.KDA_LOWERINGS_CTR.value(**labels) >= kda + 6
    assert sequence_ops.SHORT_CONV_LOWERINGS_CTR.value(**conv) >= convs + 6
    assert RECOMPUTE_OPS_CTR.value(op="kda_scan") >= again + 2
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

    for op in ("pt.fwd/kda_scan/kda", "pt.bwd/kda_scan_grad/kda",
               "pt.rc/kda_scan/kda", "pt.fwd/kda_gate/kda",
               "pt.bwd/kda_gate_grad/kda", "pt.fwd/short_conv/kda",
               "pt.bwd/short_conv_grad/kda", "pt.rc/short_conv/kda",
               "pt.fwd/mul/kda", "pt.rc/mul/kda", "pt.fwd/rms_norm/kda",
               "pt.fwd/flash_attention/attn",
               "pt.bwd/flash_attention_grad/attn", "pt.fwd/mul/attn",
               "pt.fwd/mul/shared_expert", "pt.fwd/rms_norm",
               "pt.opt/adamw", "pt.fwd/fused_lm_head_ce",
               "pt.fwd/lookup_table"):
        assert any(s == op or s.startswith(op + "/") for s in stacks), op
    for role_op in ("pt.fwd/moe_ffn/", "pt.bwd/moe_ffn_grad/"):
        seen = {part_scopes.part_of(r, part_scopes.MOE_PARTS)
                for r in under(role_op)}
        assert seen >= set(part_scopes.MOE_PARTS), (role_op, seen)
    assert json.dumps(sorted(stacks))       # names only, nothing device-bound
