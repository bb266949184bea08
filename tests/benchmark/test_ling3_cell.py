"""The Ling-3.0-flash-VL cell (``ling3_flash_vl_lm_s8192_r64``) rehearsed on
the CPU at toy widths: its files, entries and metrics picked BY NAME (never by
position) and held by MEMBERSHIP (a later cell may join the same lists), the
configuration file against the catalog row, the parameter count from the
program, the FLOPs by part and the hooks by hand, the three newly listed
readers on a hand-made trace and with nothing to read, the cell end to end to
the contract's last line, what the traffic decides, planted faults against the
cell's own limits, and what the lowered step names and counts.  Nothing here
is a speed number."""

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

from benchmark import (harness, joyai_flops, ling3_flops,  # noqa: E402
                       solar_open2_flops, trinity_flops)

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import test_benchmark_rehearsal as rehearsal  # noqa: E402
import test_lfm2_cell as lfm2_test  # noqa: E402
import test_op_scopes as scopes_test  # noqa: E402
import test_program_scopes as program_scopes_test  # noqa: E402

CELL = "ling3_flash_vl_lm_s8192_r64"
CONFIG = "ling3_flash_vl"
SPEC = harness.load_spec()
FILE = harness.load_json(f"benchmark/configs/{CONFIG}.json")
TRAFFIC = harness.load_traffic("lm_s8192_r64")
#: the three per-layer entries this cell brings: a new reader and two that
#: stood unlisted
NEW = ("kda_gate_device_ms.train", "moe_router_device_ms.train",
       "flash_roofline")
REDUCED = ["num_hidden_layers", "first_k_dense_replace",
           "num_attention_heads", "num_key_value_heads", "num_experts",
           "vocab_size", "expert_swiglu_limit_list",
           "share_expert_swiglu_limit_list"]
#: the per-layer lists the cell joins (ISSUE 55)
LISTS = (
    "dispatch_ms.train", "step_device_ms.train", "train_mfu",
    "train_device_idle_share", "op_scoped_share.train", "fwd_device_ms.train",
    "bwd_device_ms.train", "opt_device_ms.train", "xla_remat_device_ms.train",
    "vjp_forward_again_device_ms.train", "attention_device_ms.train",
    "lm_head_device_ms.train", "moe_device_ms.train",
    "moe_dispatch_device_ms.train", "recompute_device_ms.train",
    "short_conv_device_ms.train", "short_conv_roofline",
    "kda_device_ms.train", "kda_scan_device_ms.train", "kda_scan_roofline",
    "mla_proj_device_ms.train", "hbm_step_arguments_gb.train",
    "hbm_step_temporaries_gb.train", "hbm_step_unaliased_outputs_gb.train",
    "hbm_outside_step_gb.train") + NEW
_Swapped = lfm2_test._Swapped


def toy_ling(**traffic):
    c = copy.deepcopy(FILE)
    c.update(hidden_size=64, num_attention_heads=2, num_key_value_heads=2,
             head_dim=64, kv_lora_rank=16, qk_nope_head_dim=16,
             qk_rope_head_dim=8, v_head_dim=16, intermediate_size=96,
             moe_intermediate_size=32, moe_shared_expert_intermediate_size=32,
             num_experts=4, num_experts_per_tok=4, n_group=4, topk_group=2,
             vocab_size=128, rope_theta=10000)
    c["assumed"].update(router_outputs=16, expert_offset=4, kda_chunk=16)
    # the KDA heads keep 64 channels: at 16 the keys of a chunk are far from
    # orthogonal, the chunk's unit-triangular solve amplifies what bf16
    # rounds off q, k and v, and the AMP step's gradient through six such
    # layers reads anything from 0.1 to 7 of the float32 one, by the seed.
    # Toy widths: the fused head's bf16 products move the loss by 1e-4 and
    # bf16 AMP by 1e-2; the chip's limits are set at the real widths
    c["loss_tolerance"] = {"relative": 2e-3, "hidden_relative": 1e-3,
                           "top_k_differ_share": 0.02,
                           "first_hidden_relative": 8e-2,
                           "first_gradient_rest_relative": 0.25,
                           "first_gradient_experts_relative": 0.3,
                           "first_gradient_router_relative": 0.5,
                           # worst leaf: A_log's TWO numbers here, each a
                           # sum over 80 positions of terms of either sign
                           "first_gradient_kda_relative": 10.0,
                           "first_gradient_mla_relative": 0.3,
                           "first_gradient_all_relative": 0.2,
                           "replayed_update_relative": 6e-3,
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
    assert len(cell["why"]) <= 200 and "TODO" not in cell["why"]
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
                     "retrace_s", "train_step_cache_misses"}
    # a per-layer metric's cell reports the end-to-end metric it moves
    assert {m["moves"] for m in layer} <= e2e
    # membership, by name: the cell is on every list the issue names and on
    # no other; what else those lists hold is theirs
    listed = {m["name"] for m in SPEC["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == set(LISTS)
    for name in NEW:
        assert callable(harness.load_module("layer_metrics", name).read)
        m, = [m for m in SPEC["per_layer"] if m["name"] == name]
        assert CELL in m["workloads"]
        assert m["moves"] == "train_samples_per_s"
        assert m["source"] == "device_trace"
    layers_of = {m["name"]: m["layer"] for m in SPEC["per_layer"]}
    assert layers_of["flash_roofline"] == layers_of["latent_attention_roofline"]
    assert layers_of["kda_gate_device_ms.train"] == \
        layers_of["kda_device_ms.train"]
    # JoyAI's reader counts a latent-attention layer a block: not this cell's
    for name in ("latent_attention_roofline", "moe_local_rows_share",
                 "mtp_device_ms.train"):
        m, = [m for m in SPEC["per_layer"] if m["name"] == name]
        assert CELL not in m["workloads"]
    # the traffic file is Solar-Open2's, shared and unedited
    sharing = [w["name"] for w in SPEC["workloads"]
               if w["traffic"] == "lm_s8192_r64"]
    assert "solar_open2_250b_lm_s8192_r64" in sharing and CELL in sharing


def test_the_traffic_file_is_as_it_stood():
    t = TRAFFIC
    assert (t["kind"], t["batch_per_chip"], t["seq_len"], t["warmup_steps"],
            t["check_batch"]) == ("train_ring", 1, 8192, 3, 1)
    assert (t["learning_rate"], t["lr_start"], t["weight_decay"],
            t["weights_seed"]) == (4e-4, 0.0, 0.1, 1)
    assert (t["ring"], t["lr_warmup_steps"], t["recompute"]) == \
        (64, 2000, True)
    # this model's own peaks, plain and recomputed, are the file's
    assert "GB" in FILE["assumed"]["recompute"]
    assert "2000 steps" in FILE["assumed"]["optimizer"]


def _catalog():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        return None
    with open(path) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return next(r for r in rows if r["name"] == "Ling-3.0-flash-VL")


#: the catalog row's numbers that the cut changes, as published
PUBLISHED = {"num_hidden_layers": 42, "first_k_dense_replace": 2,
             "num_attention_heads": 32, "num_key_value_heads": 32,
             "num_experts": 512, "vocab_size": 157184}
#: every width of the row, which no cut may touch
WIDTHS = {"hidden_size": 2560, "intermediate_size": 6144,
          "moe_intermediate_size": 768,
          "moe_shared_expert_intermediate_size": 768, "head_dim": 128,
          "kv_lora_rank": 512, "qk_nope_head_dim": 128,
          "qk_rope_head_dim": 64, "v_head_dim": 128, "rotary_dim": 64,
          "num_experts_per_tok": 8, "n_group": 8, "topk_group": 4,
          "short_conv_kernel_size": 4, "q_lora_rank": None,
          "kda_lower_bound": -5, "routed_scaling_factor": 2.5,
          "rope_theta": 6000000, "rms_norm_eps": 1e-06,
          "layer_group_size": 6}


def test_the_config_file_holds_the_catalogs_numbers():
    """Every key of the catalog entry's ``config`` under the same key; the
    keys that differ are the eight listed, no width among them; the two
    limit lists are the published lists' slice of layers 1-7, all 0."""
    for k, v in WIDTHS.items():
        assert FILE[k] == v, k
    row = _catalog()
    if row is not None:
        assert FILE["source"] == row["source_url"]
        want = row["config"]
        assert set(want) <= set(FILE)
        differ = sorted(k for k, v in want.items() if FILE[k] != v)
        assert differ == sorted(REDUCED)
        assert {k: want[k] for k in PUBLISHED} == PUBLISHED
        for k in ("expert_swiglu_limit_list",
                  "share_expert_swiglu_limit_list"):
            assert FILE[k] == want[k][1:8] == [0] * 7
    assert (FILE["num_hidden_layers"], FILE["first_k_dense_replace"],
            FILE["num_attention_heads"], FILE["num_key_value_heads"],
            FILE["num_experts"], FILE["vocab_size"]) == \
        (7, 1, 16, 16, 8, 19648)
    assert FILE["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert FILE["num_attention_heads"] * 2 == PUBLISHED["num_attention_heads"]
    assert FILE["num_experts"] * 64 == PUBLISHED["num_experts"]
    a = FILE["assumed"]
    assert (a["router_outputs"], a["expert_offset"], a["first_layer"],
            a["kda_gate_rank"], a["kda_chunk"]) == (512, 0, 1, None, 64)
    for assumption in ("vision_tower", "published", "layers_note",
                       "heads_note", "kda_equations", "mla_equations",
                       "qk_norm_placement", "routing", "initial_values",
                       "swiglu_limits_note", "mtp_note", "optimizer",
                       "weights", "data", "reduced_note", "kda_no_bias",
                       "recompute", "block", "rotary_note"):
        assert len(a[assumption]) > 40, assumption
    assert "NOT built" in a["vision_tower"]
    for said in ("64 chips", "2-way", "32 such pairs", "64-way", "8-way",
                 "35 layers"):
        assert said in FILE["deployment"], said
    assert "680,065,120" in a["parameters"] and "10.88 GB" in a["parameters"]
    assert FILE["flops_module"] == "ling3_flops"
    tol = FILE["loss_tolerance"]
    for key in ("relative", "hidden_relative", "top_k_differ_share",
                "first_hidden_relative", "first_gradient_rest_relative",
                "first_gradient_experts_relative",
                "first_gradient_router_relative",
                "first_gradient_kda_relative", "first_gradient_mla_relative",
                "first_gradient_all_relative", "replayed_update_relative"):
        assert 0 < tol[key] < 1, key
    for key in ("reason", "first_gradient_reason", "replayed_update_reason"):
        assert "control" in tol[key] or "unchanged" in tol[key], key


def test_the_parameters_are_680_065_120_counted_from_the_program():
    """680,065,120 parameters at 16 bytes: 10.88 GB, from the shapes the
    program holds and, by part, from ``ling3_flops.parameters``."""
    from paddle_tpu.framework import Program, program_guard
    from paddle_tpu.models import transformer as T
    model = harness.load_module("models", CONFIG)
    cfg = model.ling_config(FILE)
    assert (cfg.dense_layers, cfg.mla_layers) == ([0], [4])
    main = Program()
    with program_guard(main, Program()):
        T.build_ling_pretrain(cfg, 8192)
    shapes = {p.name: tuple(p.shape) for p in main.all_parameters()}
    assert shapes["dec_0.kda.in_proj.w"] == (2560, 5 * 2048 + 16) \
        == (2560, 10256)
    assert shapes["dec_0.kda.conv.filter"] == (6144, 4)
    assert shapes["dec_0.kda.A_log"] == (16,)
    assert shapes["dec_0.kda.dt_bias"] == (2048,)
    assert shapes["dec_0.kda.o_norm.w"] == (128,)
    assert shapes["dec_0.kda.out.w"] == (2048, 2560)
    assert shapes["dec_0.ffn.gate_up.w"] == (2560, 2 * 6144)
    assert shapes["dec_4.attn.q.w"] == (2560, 16 * 192)
    assert shapes["dec_4.attn.a.w"] == (2560, 512 + 64)
    assert shapes["dec_4.attn.kv_norm.w"] == (512,)
    assert shapes["dec_4.attn.kv_b.w"] == (512, 16 * 256)
    assert shapes["dec_4.attn.q_nope_norm.w"] == \
        shapes["dec_4.attn.k_nope_norm.w"] == (128,)
    assert shapes["dec_4.attn.gate.w"] == (2560, 16)
    # the first latent-attention caller that holds a share of the heads
    assert shapes["dec_4.attn.out.w"] == (2048, 2560)
    assert shapes["dec_1.shared.gate_up.w"] == (2560, 2 * 768)
    assert shapes["dec_6.moe.router.w"] == (2560, 512)
    assert shapes["dec_6.moe.select_bias"] == (512,)
    assert shapes["dec_6.moe.gate.w"] == (8, 2560, 768)
    assert shapes["word_embedding"] == shapes["lm_out.w"][::-1] \
        == (19648, 2560)
    assert not any(n.endswith((".b", "f_up.w", "g_up.w", "q_norm.w",
                               "q_b.w")) for n in shapes)
    assert "dec_0.moe.router.w" not in shapes
    assert "dec_4.kda.in_proj.w" not in shapes

    def layer(i):
        return sum(int(np.prod(s)) for n, s in shapes.items()
                   if n.startswith(f"dec_{i}."))
    assert [layer(i) for i in range(7)] == \
        [78_716_048] + [85_925_520] * 3 + [71_121_152] + [85_925_520] * 2
    n = sum(int(np.prod(s)) for s in shapes.values())
    by_part = ling3_flops.parameters(FILE)
    assert n == sum(by_part.values()) == 680_065_120
    assert by_part["kda"] == 6 * 31_525_008
    assert by_part["attention"] == 16_720_640
    assert by_part["dense_ffn"] == 47_185_920
    assert by_part["shared_expert"] == 6 * 5_898_240
    assert by_part["experts"] == 6 * 47_185_920
    assert by_part["router"] == 6 * 1_311_232
    assert by_part["embedding_and_head"] == 100_597_760
    assert by_part["norms"] == 7 * 5120 + 2560
    assert round(16 * n / 1e9, 2) == 10.88
    assert 16 * n / 16.9e9 > 0.25


# -- the yardstick's arithmetic ----------------------------------------------

def test_forward_flops_by_part_by_hand():
    parts = ling3_flops.forward_flops_by_part(FILE, 8192)
    t, d = 8192, 2560
    assert ling3_flops.layers(FILE) == {"mla": 1, "kda": 6, "dense": 1,
                                        "expert": 6}
    assert set(parts) == {"kda_projections", "kda_conv", "kda_scan",
                          "attention_projections", "attention_scores",
                          "dense_ffn", "shared_expert", "routed_experts",
                          "router", "head"}
    assert parts["kda_projections"] == 6 * 2 * t * (d * 10256 + 2048 * d)
    assert parts["kda_conv"] == 6 * t * 6144 * 2 * 4
    assert parts["attention_projections"] == 2 * t * (
        d * (3072 + 576 + 16) + 512 * 4096 + 2048 * d)
    assert parts["attention_scores"] == 2 * (192 + 128) * 16 \
        * (8192 * 8193 // 2)
    assert parts["dense_ffn"] == 6 * t * d * 6144
    assert parts["shared_expert"] == 6 * 6 * t * d * 768
    assert t * 8 * 8 / 512 == 1024 and 1024 / 8 == 128    # rows an expert
    assert parts["routed_experts"] == 6 * 6 * 1024 * d * 768
    assert parts["router"] == 6 * 2 * t * d * 512
    assert parts["head"] == 2 * t * d * 19648
    per_chunk = solar_open2_flops.scan_flops_per_chunk(64, 128, 128)
    assert parts["kda_scan"] == 6 * 16 * 128 * per_chunk
    total = sum(parts.values())
    assert total == pytest.approx(6.20e12, rel=5e-3)
    assert ling3_flops.train_flops_per_sample(FILE, 8192) == 3 * total


def test_the_hooks_count_each_call_by_hand():
    # ONE latent-attention layer of seven, at the 16 heads held
    work = ling3_flops.flash_work(FILE, TRAFFIC)
    assert work == joyai_flops.latent_flash_layer_kernels(16, 8192, 128, 64,
                                                          128)
    assert len(work) == 2
    # what JoyAI's reader would count here: a layer a block, seven
    with pytest.raises(KeyError):         # JoyAI's count reads JoyAI's keys
        joyai_flops.flash_kernels_of_model(FILE, 8192)
    even = ling3_flops.held_experts_work(FILE, TRAFFIC, None)
    assert len(even) == 6 * 9 and even == ling3_flops.held_experts_work(
        FILE, TRAFFIC, 8 / 512)
    assert all(fl == 2 * 1024 * 2560 * 768 for fl, _ in even)
    assert even[:9] == trinity_flops.held_experts_matmuls(1024, 2560, 768, 8)
    kda = ling3_flops.kda_work(FILE, 8192, 64)
    assert len(kda) == 12                 # six layers, forward and backward
    t, h, dk = 8192, 16, 128
    stream = t * h * dk
    qkv, out, gates = 3 * stream * 2, stream * 2, t * h * (dk + 1) * 4
    states = 128 * h * dk * dk * 4
    fwd, bwd = kda[:2]
    assert fwd == (128 * 16 * solar_open2_flops.scan_flops_per_chunk(
        64, 128, 128), qkv + gates + out + states)
    assert bwd == (2 * fwd[0], 2 * (qkv + gates + out) + states)
    for fl, by in kda:                    # the bytes set the least time
        assert by / 819e9 > 3 * fl / 197e12
    conv = ling3_flops.short_conv_work(FILE, TRAFFIC)
    assert len(conv) == 12
    assert conv[0] == (t * 6144 * (2.0 * 4 + 4.0),
                       float(2 * t * 6144 * 2 + 6144 * 4 * 4))


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
    # the window of the hand-made trace is its first 1000 ns
    inputs = _inputs(tmp_path, [
        ("fusion.1", fwd + "mul/kda/dot_general:", 0, 100),
        ("fusion.2", fwd + "kda_gate/kda/logistic:", 100, 30),
        ("fusion.3", rc + "kda_gate/kda/logistic:", 130, 30),
        ("fusion.4", bwd + "kda_gate_grad/kda/mul:", 160, 50),
        ("fusion.5", fwd + "kda_scan/kda/while:", 210, 60),
        ("fusion.6", fwd + "flash_attention/flash_fwd:", 270, 100),
        ("fusion.7", bwd + "flash_attention_grad/flash_bwd:", 370, 200),
        ("fusion.8", fwd + "moe_ffn/router/top_k:", 570, 80),
        ("fusion.9", bwd + "moe_ffn_grad/transpose(jvp(router))/"
         "dot_general:", 650, 120),
        ("fusion.10", bwd + "moe_ffn_grad/experts/gmm:", 770, 200),
    ])
    # 2 steps: 110 ns under the gate's ops, 200 under the router's scope
    assert _read(NEW[0], inputs) == pytest.approx(55e-9 * 1e3)
    assert _read(NEW[1], inputs) == pytest.approx(100e-9 * 1e3)
    least = sum(max(fl / 197e12, by / 819e9)
                for fl, by in ling3_flops.flash_work(FILE, TRAFFIC))
    assert _read(NEW[2], inputs) == pytest.approx(100 * least / 150e-9)


def test_the_readers_return_nothing_with_nothing_to_read(tmp_path):
    """A trace of another program (the parent's: no ``kda_gate`` under a
    program without the layer), a trace without scopes, no trace at all, and
    a configuration that names no module of hooks."""
    other = _inputs(tmp_path, [
        ("fusion.1", "jit(step)/pt.fwd/mul/dot_general:", 0, 100)])
    (tmp_path / "b").mkdir()
    bare = _inputs(tmp_path / "b", [("fusion.1", None, 0, 100)])
    none = dict(other, trace=None, trace_window=None)
    for inputs in (other, bare, none):
        for metric in NEW:
            assert _read(metric, inputs) is None, metric
    events = [("fusion.1", "jit(step)/pt.fwd/flash_attention/k:", 0, 40)]
    (tmp_path / "c").mkdir()
    older = _inputs(tmp_path / "c", events,
                    config=dict(FILE, flops_module="no_such_module"))
    assert _read("flash_roofline", older) is None


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
    config, traffic = toy_ling()
    assert traffic["recompute"] is True          # as the chip runs it
    result = harness.run_cell(CELL, seed=rehearsal.BIG_SEED, seconds=0.5,
                              trace=True, on_chip=False, config=config,
                              traffic=traffic, spec=SPEC)
    line = rehearsal.check_contract_line(result, CELL, 1)
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"     # and so: not a result
    assert line["compared"][-1].startswith("correct: ")
    detail = line["compared"][1]
    assert "gradient against jax.grad of the reference" in detail
    assert "kda: worst leaf" in detail and "mla: worst leaf" in detail
    assert "a state left unchanged reads 1" in detail
    assert "limits exceeded: none" in detail


@pytest.mark.slow
def test_cell_end_to_end_on_cpu_without_recomputation():
    config, traffic = toy_ling(recompute=False)
    result = harness.run_cell(CELL, seed=7, seconds=0.5, trace=False,
                              on_chip=False, config=config, traffic=traffic,
                              spec=SPEC)
    line = rehearsal.check_contract_line(result, CELL, 0)
    assert line["correct"] is True


# -- what the traffic decides ------------------------------------------------

def _built(seed, **traffic):
    c, t = toy_ling(**traffic)
    model = harness.load_module("models", CONFIG)
    return model, model.build_train(c, t, seed, 1, False), t


def _weights(m):
    return {p.name: np.asarray(m["scope"].find_var(p.name))
            for p in m["parameters"]}


def test_the_weights_are_the_model_and_the_seed_is_the_traffic():
    """Two values of ``--seed``: the same weights (``weights_seed``), other
    token ids, 64 sequences of the ring each of its own; the KDA layers'
    initial values under the bounded gate; the plain step builds the same
    model and computes nothing again."""
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
    # at the initial values the strongest decay is exp(-5 sigmoid(.)) with
    # the argument below 0: under exp(-2.5) a position, and in fact mild
    g0 = -5.0 / (1.0 + np.exp(-np.exp(a_log.astype(np.float64))[:, None]
                              * dt_bias.astype(np.float64).reshape(2, 64)))
    assert -0.5 < g0.min() and g0.max() < 0
    assert not np.array_equal(a_log, wa["dec_2.kda.A_log"])
    assert np.abs(wa["dec_1.kda.conv.filter"]).max() <= 0.5
    assert np.all(wa["dec_4.attn.q_nope_norm.w"] == 1)
    assert np.all(wa["dec_1.moe.select_bias"] == 0)
    assert 0.01 < wa["dec_1.moe.gate.w"].std() < 0.03
    types = [[op.type for op in m["program"].global_block().ops]
             for m in (a, b)]
    # seven blocks, six of them KDA: all computed again, or none
    assert [t.count("kda_scan") for t in types] == [6 + 6, 6]
    assert [t.count("kda_scan_grad") for t in types] == [6, 6]
    assert [t.count("flash_attention") for t in types] == [1 + 1, 1]
    assert [t.count("moe_ffn") for t in types] == [6 + 6, 6]


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
                 "dec_1.kda.conv.filter", "dec_4.attn.q.w",
                 "dec_4.attn.gate.w", "dec_4.attn.k_nope_norm.w",
                 "dec_0.ffn.gate_up.w"):
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
    config, _ = toy_ling()
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
    ("f32_hidden", "hidden_relative"), ("f32_share", "top_k_differ_share"),
    ("gradient_kda", "first_gradient_kda_relative"),
    ("gradient_mla", "first_gradient_mla_relative"),
    ("update", "replayed_update_relative"), ("replay", "replay"),
    ("dropless", "dropless")])
def test_decide_names_the_limit_a_reading_exceeds(reading, limit):
    model = harness.load_module("models", CONFIG)
    tol = toy_ling()[0]["loss_tolerance"]
    sound = dict(f32_loss=0.0, f32_share=0.0, f32_hidden=0.0,
                 first_hidden=0.0, update=0.0, first_loss=0.0,
                 first_forward=0.0, replay=0.0, dropless=True,
                 gradient_all=0.0, **{f"gradient_{k}": 0.0
                                      for k in model.KINDS})
    assert model.decide(tol, sound) == (True, [])
    off = dict(sound, **{reading: False if reading == "dropless"
                         else float("nan")})
    assert model.decide(tol, off) == (False, [limit])


@pytest.mark.parametrize("kind, leaf", [
    ("kda", "a_log"), ("kda", "dt_bias"), ("kda", "o_norm_w"),
    ("kda", "conv_k"), ("mla", "qn_w"), ("mla", "kn_w"),
    ("mla", "w_hgate")])
def test_a_small_leaf_gone_wrong_exceeds_its_kinds_limit(kind, leaf):
    """The kinds ``kda`` and ``mla`` are held to their WORST leaf: a gradient
    wrong in ``A_log``'s 16 numbers or a content norm's 128 alone hardly
    moves the kind's leaves together, beside the gates' 5M numbers each, and
    is over the configuration file's limit on its own leaf."""
    model = harness.load_module("models", CONFIG)
    r = np.random.RandomState(5)
    shapes = {"wf": (2560, 256), "wg": (2560, 256), "a_log": (16,),
              "dt_bias": (2048,), "o_norm_w": (128,), "conv_k": (2048, 4),
              "qn_w": (128,), "kn_w": (128,), "kv_norm_w": (512,),
              "w_hgate": (2560, 16), "wq": (64, 64)}
    ref = {"blocks": [{k: r.randn(*v) for k, v in shapes.items()}]}
    got = {"blocks": [{k: v.astype(np.float32) * (-1.0 if k == leaf else 1.0)
                       for k, v in ref["blocks"][0].items()}]}
    assert model.DECIDES[kind] == 1
    out = model.gradient_difference(ref, got)
    together, worst, name = out[kind]
    limit = FILE["loss_tolerance"][f"first_gradient_{kind}_relative"]
    assert name.endswith(f"['{leaf}']") and worst == pytest.approx(2.0)
    assert limit < worst
    if leaf != "w_hgate":          # the head gate is most of its kind
        assert together < limit
    assert out["rest"][1] < 1e-6
    assert out["all"] < limit or leaf == "w_hgate"


# -- what the lowered step names and counts ------------------------------------

def test_the_lowered_step_names_the_ops_their_roles_and_the_tags():
    """What the readers and the by-op breakdown depend on: ``kda_gate`` and
    its grad op under ``pt.fwd``, ``pt.bwd`` and ``pt.rc``, the ``kda``,
    ``mla_proj``, ``dense_ffn`` and ``shared_expert`` tags, ``moe_ffn``'s
    parts with the group selection inside ``router``; and the counters name
    what was lowered: 18 ``kda_scan`` lowerings a step (six layers: forward,
    forward again, backward), the bounded gate at full rank, the routing
    groups, the one latent-attention layer at its two widths (forward,
    forward again, backward fused) and nothing else."""
    import jax.numpy as jnp
    from benchmark import part_scopes
    from benchmark.models import _train
    from paddle_tpu.framework.recompute import RECOMPUTE_OPS_CTR
    from paddle_tpu.ops import attention_ops, kda_ops, moe_ops
    config, traffic = toy_ling()
    model = harness.load_module("models", CONFIG)
    scan = dict(heads="2", head_dim="64", chunk="16", impl="xla",
                neg_eigval="false")
    gate = dict(form="bounded", rank="full")
    moe = dict(experts="16", top_k="4", held="4", score_func="sigmoid",
               groups="4/2")
    widths = dict(widths="16+8/16")

    def flash_now():
        return (attention_ops.FLASH_LOWERINGS_CTR.value(window="none"),
                attention_ops.FLASH_LOWERINGS_CTR.value(**widths),
                attention_ops.FLASH_GRAD_LOWERINGS_CTR.value(window="none"),
                attention_ops.FLASH_GRAD_LOWERINGS_CTR.value(**widths))

    before = (kda_ops.KDA_LOWERINGS_CTR.value(**scan),
              kda_ops.KDA_GATE_LOWERINGS_CTR.value(**gate),
              moe_ops.MOE_LOWERINGS_CTR.value(**moe),
              RECOMPUTE_OPS_CTR.value(op="kda_gate"), flash_now())
    m = model.build_train(config, traffic, 11, 1, False)
    exe, scope = m["exe"], m["scope"]
    feed = _train.put_ring(m["ring"], 1)[0]
    exe.run(m["program"], feed=feed, fetch_list=[m["loss"]], scope=scope)
    assert kda_ops.KDA_LOWERINGS_CTR.value(**scan) == before[0] + 18
    # the gate's grad op is the registry's vjp of the lowering: 6 forward,
    # 6 again, 6 inside the backward
    assert kda_ops.KDA_GATE_LOWERINGS_CTR.value(**gate) >= before[1] + 12
    assert moe_ops.MOE_LOWERINGS_CTR.value(**moe) == before[2] + 12
    assert RECOMPUTE_OPS_CTR.value(op="kda_gate") >= before[3] + 6
    all_fwd, ours_fwd, all_bwd, ours_bwd = (
        now - was for now, was in zip(flash_now(), before[4]))
    assert (all_fwd, ours_fwd, all_bwd, ours_bwd) == (2, 2, 1, 1)
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
               "pt.bwd/kda_gate_grad/kda", "pt.rc/kda_gate/kda",
               "pt.fwd/short_conv/kda", "pt.bwd/short_conv_grad/kda",
               "pt.fwd/mul/kda", "pt.rc/mul/kda", "pt.fwd/rms_norm/kda",
               "pt.fwd/flash_attention", "pt.rc/flash_attention",
               "pt.bwd/flash_attention_grad", "pt.fwd/mul/mla_proj",
               "pt.fwd/rms_norm/mla_proj", "pt.fwd/rope/mla_proj",
               "pt.fwd/sigmoid/mla_proj", "pt.bwd/rope_grad/mla_proj",
               "pt.fwd/mul/shared_expert", "pt.fwd/mul/dense_ffn",
               "pt.fwd/rms_norm", "pt.opt/adamw", "pt.fwd/fused_lm_head_ce",
               "pt.fwd/lookup_table"):
        assert any(s == op or s.startswith(op + "/") for s in stacks), op
    for role_op in ("pt.fwd/moe_ffn/", "pt.bwd/moe_ffn_grad/",
                    "pt.rc/moe_ffn/"):
        seen = {part_scopes.part_of(r, part_scopes.MOE_PARTS)
                for r in under(role_op)}
        assert seen >= set(part_scopes.MOE_PARTS), (role_op, seen)
    assert json.dumps(sorted(stacks))       # names only, nothing device-bound
