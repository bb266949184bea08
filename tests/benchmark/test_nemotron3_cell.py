"""The Nemotron-3-Nano cell (``nemotron3_nano_30b_a3b_lm_s8192_r64``)
rehearsed on the CPU at toy widths: its files, entries and metrics picked BY
NAME (never by position) and held by MEMBERSHIP (a later cell may join the
same lists), the configuration file against the catalog row, the parameter
count from the program, the FLOPs by part and the hooks by hand, the three
new readers on a hand-made trace and with nothing to read, the cell end to
end to the contract's last line, what the traffic decides, planted faults
against the cell's own limits, and what the lowered step names and counts.
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

from benchmark import harness, nemotron3_flops, trinity_flops  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import test_benchmark_rehearsal as rehearsal  # noqa: E402
import test_lfm2_cell as lfm2_test  # noqa: E402
import test_op_scopes as scopes_test  # noqa: E402
import test_program_scopes as program_scopes_test  # noqa: E402

CELL = "nemotron3_nano_30b_a3b_lm_s8192_r64"
CONFIG = "nemotron3_nano_30b_a3b"
SPEC = harness.load_spec()
FILE = harness.load_json(f"benchmark/configs/{CONFIG}.json")
TRAFFIC = harness.load_traffic("lm_s8192_r64")
#: the three per-layer entries this cell brings
NEW = ("mamba_device_ms.train", "ssd_scan_device_ms.train",
       "ssd_scan_roofline")
REDUCED = ["num_hidden_layers", "hybrid_override_pattern",
           "n_routed_experts", "vocab_size"]
#: the per-layer lists the cell joins (ISSUE 57)
LISTS = (
    "dispatch_ms.train", "step_device_ms.train", "train_mfu",
    "train_device_idle_share", "op_scoped_share.train", "fwd_device_ms.train",
    "bwd_device_ms.train", "opt_device_ms.train", "xla_remat_device_ms.train",
    "vjp_forward_again_device_ms.train", "attention_device_ms.train",
    "lm_head_device_ms.train", "moe_device_ms.train",
    "moe_dispatch_device_ms.train", "moe_router_device_ms.train",
    "recompute_device_ms.train", "short_conv_device_ms.train",
    "short_conv_roofline", "flash_roofline", "hbm_step_arguments_gb.train",
    "hbm_step_temporaries_gb.train", "hbm_step_unaliased_outputs_gb.train",
    "hbm_outside_step_gb.train") + NEW
_Swapped = lfm2_test._Swapped


def toy_nemotron(**traffic):
    c = copy.deepcopy(FILE)
    c.update(hidden_size=64, mamba_num_heads=4, mamba_head_dim=16, n_groups=2,
             ssm_state_size=16, chunk_size=16, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16, intermediate_size=32,
             moe_intermediate_size=32, moe_shared_expert_intermediate_size=48,
             n_routed_experts=4, num_experts_per_tok=2, vocab_size=128)
    c["assumed"].update(router_outputs=16, expert_offset=4, d_inner=64)
    # toy widths: the fused head's bf16 products move the loss by 1e-4 and
    # bf16 AMP by 1e-2; the chip's limits are set at the real widths
    c["loss_tolerance"] = {"relative": 2e-3, "hidden_relative": 1e-3,
                           "top_k_differ_share": 0.02,
                           "first_hidden_relative": 8e-2,
                           "first_gradient_rest_relative": 0.25,
                           "first_gradient_experts_relative": 0.3,
                           "first_gradient_router_relative": 0.5,
                           "first_gradient_mamba_relative": 0.5,
                           "first_gradient_attention_relative": 0.3,
                           "first_gradient_all_relative": 0.2,
                           "replayed_update_relative": 6e-3,
                           "reason": "toy widths"}
    t = copy.deepcopy(TRAFFIC)
    t.update(batch_per_chip=2, seq_len=40, ring=2, warmup_steps=1,
             check_batch=2, reference_q_block=8, reference_scan_block=8)
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
    # membership, by name: the cell is on every list the issue names; what
    # else those lists hold is theirs
    listed = {m["name"] for m in SPEC["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed >= set(LISTS)
    for name in NEW:
        assert callable(harness.load_module("layer_metrics", name).read)
        m, = [m for m in SPEC["per_layer"] if m["name"] == name]
        assert CELL in m["workloads"]
        assert m["moves"] == "train_samples_per_s"
        assert m["source"] == "device_trace"
    layers_of = {m["name"]: m["layer"] for m in SPEC["per_layer"]}
    assert layers_of["ssd_scan_roofline"] == layers_of["kda_scan_roofline"]
    # another scan's readers, and a gated expert's count, are not this cell's
    for name in ("kda_device_ms.train", "kda_scan_roofline",
                 "mla_proj_device_ms.train", "latent_attention_roofline"):
        m, = [m for m in SPEC["per_layer"] if m["name"] == name]
        assert CELL not in m["workloads"]
    # the traffic file is Solar-Open2's and Ling's, shared and unedited
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
    return next(r for r in rows
                if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")


PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
#: the catalog row's numbers that the cut changes, as published
PUBLISHED = {"num_hidden_layers": 52, "hybrid_override_pattern": PATTERN,
             "n_routed_experts": 128, "vocab_size": 131072}
#: every width of the row, which no cut may touch
WIDTHS = {"hidden_size": 2688, "mamba_num_heads": 64, "mamba_head_dim": 64,
          "n_groups": 8, "ssm_state_size": 128, "conv_kernel": 4,
          "chunk_size": 128, "expand": 2, "num_attention_heads": 32,
          "num_key_value_heads": 2, "head_dim": 128,
          "intermediate_size": 1856, "moe_intermediate_size": 1856,
          "moe_shared_expert_intermediate_size": 3712,
          "num_experts_per_tok": 6, "n_group": 1, "topk_group": 1,
          "routed_scaling_factor": 2.5, "norm_eps": 1e-05,
          "mlp_hidden_act": "relu2", "use_conv_bias": True}


def test_the_config_file_holds_the_catalogs_numbers():
    """Every key of the catalog entry's ``config`` under the same key; the
    keys that differ are the four listed, no width among them; the pattern
    kept is the published pattern's first nine letters."""
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
    assert (FILE["num_hidden_layers"], FILE["hybrid_override_pattern"],
            FILE["n_routed_experts"], FILE["vocab_size"]) == \
        (9, "MEMEM*EME", 8, 16384)
    assert PATTERN.startswith(FILE["hybrid_override_pattern"])
    assert (PATTERN.count("M"), PATTERN.count("E"), PATTERN.count("*")) == \
        (23, 23, 6) and len(PATTERN) == 52
    assert FILE["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert FILE["n_routed_experts"] * 16 == PUBLISHED["n_routed_experts"]
    a = FILE["assumed"]
    assert (a["router_outputs"], a["expert_offset"], a["d_inner"]) == \
        (128, 0, 64 * 64)
    for assumption in ("published", "reduced_note", "block", "d_inner_note",
                       "mamba_equations", "time_step_limit", "chunk_note",
                       "attention_equations", "routing", "experts",
                       "initial_values", "optimizer", "weights", "data",
                       "mtp_note", "num_logits_to_keep_note", "recompute",
                       "parameters"):
        assert len(a[assumption]) > 40, assumption
    assert "rope_theta" in a["attention_equations"] and \
        "partial_rotary_factor" in a["attention_equations"]
    assert "rescale_prenorm_residual" in a["initial_values"]
    for said in ("16 chips", "16-way", "8-way", "43 blocks", "whole"):
        assert said in FILE["deployment"], said
    assert "666,963,456" in a["parameters"] and "10.67 GB" in a["parameters"]
    assert FILE["flops_module"] == "nemotron3_flops"
    tol = FILE["loss_tolerance"]
    for key in ("relative", "hidden_relative", "top_k_differ_share",
                "first_hidden_relative", "first_gradient_rest_relative",
                "first_gradient_experts_relative",
                "first_gradient_router_relative",
                "first_gradient_mamba_relative",
                "first_gradient_attention_relative",
                "first_gradient_all_relative", "replayed_update_relative"):
        assert 0 < tol[key] < 1, key
    for key in ("reason", "first_gradient_reason", "replayed_update_reason"):
        assert "control" in tol[key] or "unchanged" in tol[key], key


def test_the_parameters_are_666_963_456_counted_from_the_program():
    """666,963,456 parameters at 16 bytes: 10.67 GB, from the shapes the
    program holds and, by part, from ``nemotron3_flops.parameters``; a Mamba
    block 38,744,896 with its norm."""
    from paddle_tpu.framework import Program, program_guard
    from paddle_tpu.models import transformer as T
    model = harness.load_module("models", CONFIG)
    cfg = model.nemotron_config(FILE)
    assert cfg.pattern == "MEMEM*EME" and cfg.n_layer == 9
    main = Program()
    with program_guard(main, Program()):
        T.build_nemotron_h_pretrain(cfg, 8192)
    shapes = {p.name: tuple(p.shape) for p in main.all_parameters()}
    assert shapes["dec_0.mamba.in_proj.w"] == (2688, 4096 + 6144 + 64) \
        == (2688, 10304)
    assert shapes["dec_0.mamba.conv.filter"] == (6144, 4)
    assert shapes["dec_0.mamba.conv.bias"] == (6144,)
    assert shapes["dec_0.mamba.A_log"] == shapes["dec_0.mamba.D"] == \
        shapes["dec_0.mamba.dt_bias"] == (64,)
    assert shapes["dec_0.mamba.norm.w"] == (4096,)
    assert shapes["dec_0.mamba.out.w"] == (4096, 2688)
    assert shapes["dec_5.attn.qkv.w"] == (2688, 4096 + 2 * 256)
    assert shapes["dec_5.attn.out.w"] == (4096, 2688)
    assert shapes["dec_1.shared.up.w"] == (2688, 3712)
    assert shapes["dec_1.shared.down.w"] == (3712, 2688)
    assert shapes["dec_1.moe.router.w"] == (2688, 128)
    assert shapes["dec_1.moe.select_bias"] == (128,)
    assert shapes["dec_1.moe.up.w"] == (8, 2688, 1856)
    assert shapes["dec_1.moe.down.w"] == (8, 1856, 2688)
    assert shapes["word_embedding"] == shapes["lm_out.w"][::-1] \
        == (16384, 2688)
    # no bias on a projection, no gate branch, ONE norm a block
    assert not any(n.endswith((".b", "gate.w", "gate_up.w", "ln2.w"))
                   for n in shapes)
    assert sum(n.startswith("dec_") and n.endswith(".norm.w")
               and ".mamba." not in n for n in shapes) == 9

    def block(i):
        return sum(int(np.prod(s)) for n, s in shapes.items()
                   if n.startswith(f"dec_{i}."))
    mamba, attention, expert = 38_744_896, 23_399_040, 100_125_440
    assert [block(i) for i in range(9)] == [
        mamba, expert, mamba, expert, mamba, attention, expert, mamba,
        expert]
    n = sum(int(np.prod(s)) for s in shapes.values())
    by_part = nemotron3_flops.parameters(FILE)
    assert n == sum(by_part.values()) == 666_963_456
    assert by_part["mamba"] == 4 * mamba
    assert by_part["attention"] == attention
    assert by_part["shared_expert"] == 4 * 19_955_712
    assert by_part["experts"] == 4 * 8 * 9_977_856
    assert by_part["router"] == 4 * (344_064 + 128)
    assert by_part["embedding_and_head"] == 88_080_384
    assert round(16 * n / 1e9, 2) == 10.67
    assert 16 * n / 16.9e9 > 0.25


# -- the yardstick's arithmetic ----------------------------------------------

def test_forward_flops_by_part_by_hand():
    parts = nemotron3_flops.forward_flops_by_part(FILE, 8192)
    t, d = 8192, 2688
    assert nemotron3_flops.layers(FILE) == {"mamba": 4, "attention": 1,
                                            "expert": 4}
    assert set(parts) == {"mamba_projections", "mamba_conv", "ssd_scan",
                          "attention_projections", "attention_scores",
                          "shared_expert", "routed_experts", "router",
                          "head"}
    assert parts["mamba_projections"] == 4 * 2 * t * (d * 10304 + 4096 * d)
    assert parts["mamba_conv"] == 4 * t * 6144 * 2 * 4
    assert parts["attention_projections"] == 2 * t * (d * 4608 + 4096 * d)
    assert parts["attention_scores"] == 4 * 128 * 32 * (8192 * 8193 // 2)
    # TWO products an expert, not three
    assert parts["shared_expert"] == 4 * 4 * t * d * 3712
    assert t * 6 * 8 / 128 == 3072 and 3072 / 8 == 384    # rows an expert
    assert parts["routed_experts"] == 4 * 4 * 3072 * d * 1856
    assert parts["router"] == 4 * 2 * t * d * 128
    assert parts["head"] == 2 * t * d * 16384
    per_chunk = nemotron3_flops.ssd_flops_per_chunk(128, 64, 64, 8, 128)
    assert per_chunk == 8 * 2 * 128 * 128 * 128 + 64 * (
        2 * 128 * 128 * 64 + 2 * 128 * 128 + 4 * 128 * 64 * 128
        + 2 * 64 * 128)
    assert parts["ssd_scan"] == 4 * 64 * per_chunk
    total = sum(parts.values())
    assert total == pytest.approx(5.88e12, rel=5e-3)
    assert nemotron3_flops.train_flops_per_sample(FILE, 8192) == 3 * total


def test_the_hooks_count_each_call_by_hand():
    # ONE attention block of nine, 32 query heads over 2
    work = nemotron3_flops.flash_work(FILE, TRAFFIC)
    assert work == trinity_flops.flash_layer_kernels(32, 2, 8192, 128)
    assert len(work) == 2
    even = nemotron3_flops.held_experts_work(FILE, TRAFFIC, None)
    # six grouped matmuls a block: an un-gated expert has two products
    assert len(even) == 4 * 6 and even == nemotron3_flops.held_experts_work(
        FILE, TRAFFIC, 8 / 128)
    assert all(fl == 2 * 3072 * 2688 * 1856 for fl, _ in even)
    nine = trinity_flops.held_experts_matmuls(3072, 2688, 1856, 8)
    assert even[:6] == nine[:4] + nine[-2:]
    ssd = nemotron3_flops.ssd_work(FILE, 8192)
    assert len(ssd) == 8                  # four blocks, forward and backward
    t, h, p, g, n = 8192, 64, 64, 8, 128
    streams = t * (h * p + 2 * g * n + h) * 2
    out, states = t * h * p * 2, 64 * h * p * n * 4
    fwd, bwd = ssd[:2]
    assert fwd == (64 * nemotron3_flops.ssd_flops_per_chunk(128, h, p, g, n),
                   streams + out + states)
    assert bwd == (2 * fwd[0], 2 * (streams + out) + states)
    for fl, by in ssd:                    # the bytes set the least time
        assert by / 819e9 > fl / 197e12
    conv = nemotron3_flops.short_conv_work(FILE, TRAFFIC)
    assert len(conv) == 8
    assert conv[0] == (t * 6144 * (2.0 * 4 + 5.0),
                       float(2 * t * 6144 * 2 + 6144 * 5 * 4))


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
        ("fusion.1", fwd + "mul/mamba/dot_general:", 0, 100),
        ("fusion.2", fwd + "short_conv/mamba/mul:", 100, 30),
        ("fusion.3", fwd + "ssd_scan/mamba/while:", 130, 60),
        ("fusion.4", rc + "ssd_scan/mamba/while:", 190, 60),
        ("fusion.5", bwd + "ssd_scan_grad/mamba/while:", 250, 180),
        ("fusion.6", bwd + "gated_rms_norm_grad/mamba/mul:", 430, 50),
        ("fusion.7", fwd + "flash_attention/attn/flash_fwd:", 480, 100),
        ("fusion.8", fwd + "mul/shared_expert/dot_general:", 580, 80),
        ("fusion.9", bwd + "moe_ffn_grad/experts/gmm:", 660, 200),
    ])
    # 2 steps: 480 ns under the tag, 300 under the scan's two ops
    assert _read(NEW[0], inputs) == pytest.approx(240e-9 * 1e3)
    assert _read(NEW[1], inputs) == pytest.approx(150e-9 * 1e3)
    least = sum(max(fl / 197e12, by / 819e9)
                for fl, by in nemotron3_flops.ssd_work(FILE, 8192))
    assert _read(NEW[2], inputs) == pytest.approx(100 * least / 150e-9)


def test_the_readers_return_nothing_with_nothing_to_read(tmp_path):
    """A trace of another program (the parent's: no ``ssd_scan`` and no
    ``mamba`` tag under a program without the sublayer), a trace without
    scopes, no trace at all, and a configuration that names no module of
    hooks."""
    other = _inputs(tmp_path, [
        ("fusion.1", "jit(step)/pt.fwd/mul/kda/dot_general:", 0, 100)])
    (tmp_path / "b").mkdir()
    bare = _inputs(tmp_path / "b", [("fusion.1", None, 0, 100)])
    none = dict(other, trace=None, trace_window=None)
    for inputs in (other, bare, none):
        for metric in NEW:
            assert _read(metric, inputs) is None, metric
    events = [("fusion.1", "jit(step)/pt.fwd/ssd_scan/mamba/k:", 0, 40)]
    (tmp_path / "c").mkdir()
    older = _inputs(tmp_path / "c", events,
                    config=dict(FILE, flops_module="no_such_module"))
    assert _read("ssd_scan_roofline", older) is None
    assert _read("ssd_scan_device_ms.train", older) is not None


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
    config, traffic = toy_nemotron()
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
    assert "mamba: worst leaf" in detail and \
        "attention: worst leaf" in detail
    assert "a state left unchanged reads 1" in detail
    assert "limits exceeded: none" in detail


@pytest.mark.slow
def test_cell_end_to_end_on_cpu_without_recomputation():
    config, traffic = toy_nemotron(recompute=False)
    result = harness.run_cell(CELL, seed=7, seconds=0.5, trace=False,
                              on_chip=False, config=config, traffic=traffic,
                              spec=SPEC)
    line = rehearsal.check_contract_line(result, CELL, 0)
    assert line["correct"] is True


# -- what the traffic decides ------------------------------------------------

def _built(seed, **traffic):
    c, t = toy_nemotron(**traffic)
    model = harness.load_module("models", CONFIG)
    return model, model.build_train(c, t, seed, 1, False), t


def _weights(m):
    return {p.name: np.asarray(m["scope"].find_var(p.name))
            for p in m["parameters"]}


def test_the_weights_are_the_model_and_the_seed_is_the_traffic():
    """Two values of ``--seed``: the same weights (``weights_seed``), other
    token ids, 64 sequences of the ring each of its own; the Mamba blocks'
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
    np.testing.assert_allclose(wa["dec_0.mamba.A_log"],
                               np.log(np.arange(1, 5)), rtol=1e-6)
    assert np.all(wa["dec_0.mamba.D"] == 1)
    # softplus(dt_bias) is the initial step: log-uniform in [1e-3, 1e-1]
    step = np.log1p(np.exp(wa["dec_0.mamba.dt_bias"].astype(np.float64)))
    assert 0.9e-3 < step.min() and step.max() < 0.11
    assert not np.array_equal(wa["dec_0.mamba.dt_bias"],
                              wa["dec_2.mamba.dt_bias"])
    assert np.abs(wa["dec_0.mamba.conv.filter"]).max() <= 0.5
    assert np.all(wa["dec_0.mamba.conv.bias"] == 0)
    assert np.all(wa["dec_0.mamba.norm.w"] == 1)
    assert np.all(wa["dec_1.moe.select_bias"] == 0)
    assert 0.01 < wa["dec_1.moe.up.w"].std() < 0.03
    types = [[op.type for op in m["program"].global_block().ops]
             for m in (a, b)]
    # nine blocks, four of them Mamba: all computed again, or none
    assert [t.count("ssd_scan") for t in types] == [4 + 4, 4]
    assert [t.count("ssd_scan_grad") for t in types] == [4, 4]
    assert [t.count("flash_attention") for t in types] == [1 + 1, 1]
    assert [t.count("moe_ffn") for t in types] == [4 + 4, 4]


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
    for name in ("word_embedding", "dec_0.mamba.A_log", "dec_2.mamba.D",
                 "dec_2.mamba.dt_bias", "dec_0.mamba.conv.filter",
                 "dec_0.mamba.conv.bias", "dec_0.mamba.norm.w",
                 "dec_5.attn.qkv.w", "dec_1.shared.up.w", "dec_1.moe.up.w"):
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
    config, _ = toy_nemotron()
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
    ("gradient_mamba", "first_gradient_mamba_relative"),
    ("gradient_attention", "first_gradient_attention_relative"),
    ("update", "replayed_update_relative"), ("replay", "replay"),
    ("dropless", "dropless")])
def test_decide_names_the_limit_a_reading_exceeds(reading, limit):
    model = harness.load_module("models", CONFIG)
    tol = toy_nemotron()[0]["loss_tolerance"]
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
    ("mamba", "a_log"), ("mamba", "d_skip"), ("mamba", "dt_bias"),
    ("mamba", "conv_b"), ("mamba", "gnorm_w"), ("attention", "wk")])
def test_a_small_leaf_gone_wrong_exceeds_its_kinds_limit(kind, leaf):
    """The kinds ``mamba`` and ``attention`` are held to their WORST leaf: a
    gradient wrong in ``A_log``'s 64 numbers alone hardly moves the kind's
    leaves together, beside the filter's 24576, and is over the
    configuration file's limit on its own leaf."""
    model = harness.load_module("models", CONFIG)
    r = np.random.RandomState(5)
    shapes = {"conv_w": (6144, 4), "conv_b": (6144,), "a_log": (64,),
              "d_skip": (64,), "dt_bias": (64,), "gnorm_w": (4096,),
              "wq": (256, 512), "wk": (256, 32), "wv": (256, 32),
              "wo": (512, 256), "w_in": (64, 64)}
    ref = {"blocks": [{k: r.randn(*v) for k, v in shapes.items()}]}
    got = {"blocks": [{k: v.astype(np.float32) * (-1.0 if k == leaf else 1.0)
                       for k, v in ref["blocks"][0].items()}]}
    assert model.DECIDES[kind] == 1
    out = model.gradient_difference(ref, got)
    together, worst, name = out[kind]
    limit = FILE["loss_tolerance"][f"first_gradient_{kind}_relative"]
    assert name.endswith(f"['{leaf}']") and worst == pytest.approx(2.0)
    assert limit < worst
    # the filter's bias and the gated norm's scale are a fifth of their kind
    # each, W_k a thirty-fourth of its: over the limit together as well
    if leaf not in ("conv_b", "gnorm_w", "wk"):
        assert together < limit
    assert out["rest"][1] < 1e-6


# -- what the lowered step names and counts -----------------------------------

def test_the_lowered_step_names_the_ops_their_roles_and_the_tags():
    """What the readers and the by-op breakdown depend on: ``ssd_scan`` and
    its grad op under ``pt.fwd``, ``pt.bwd`` and ``pt.rc``, the ``mamba``,
    ``attn`` and ``shared_expert`` tags, ``moe_ffn``'s parts; and the
    counters, read as DIFFERENCES, name what was lowered: 12 ``ssd_scan``
    lowerings a step (four blocks: forward, forward again, backward), 12
    biased ungated convolutions, 8 un-gated ``moe_ffn`` forward lowerings,
    the one attention block at 4 / 2 heads (forward, forward again, backward)
    and nothing else through the flash kernels."""
    import jax.numpy as jnp
    from benchmark import part_scopes
    from benchmark.models import _train
    from paddle_tpu.framework.recompute import RECOMPUTE_OPS_CTR
    from paddle_tpu.ops import attention_ops, moe_ops, sequence_ops, ssd_ops
    config, traffic = toy_nemotron()
    model = harness.load_module("models", CONFIG)
    scan = dict(impl="xla", chunk="16")
    conv = dict(taps="4", gated="false", act="silu", bias="true")
    moe = dict(experts="16", top_k="2", held="4", score_func="sigmoid",
               groups="1/1", act="relu2", gated="0")
    widths = dict(kv_groups="2", widths="16/16")

    def now():
        return (ssd_ops.SSD_LOWERINGS_CTR.value(**scan),
                sequence_ops.SHORT_CONV_LOWERINGS_CTR.value(**conv),
                moe_ops.MOE_LOWERINGS_CTR.value(**moe),
                RECOMPUTE_OPS_CTR.value(op="ssd_scan"),
                attention_ops.FLASH_LOWERINGS_CTR.value(window="none"),
                attention_ops.FLASH_LOWERINGS_CTR.value(**widths),
                attention_ops.FLASH_GRAD_LOWERINGS_CTR.value(window="none"),
                attention_ops.FLASH_GRAD_LOWERINGS_CTR.value(**widths),
                moe_ops.MOE_LOWERINGS_CTR.value(gated="1"),
                sequence_ops.SHORT_CONV_LOWERINGS_CTR.value(bias="false"))

    before = now()
    m = model.build_train(config, traffic, 11, 1, False)
    exe, scope = m["exe"], m["scope"]
    feed = _train.put_ring(m["ring"], 1)[0]
    exe.run(m["program"], feed=feed, fetch_list=[m["loss"]], scope=scope)
    moved = tuple(b - a for a, b in zip(before, now()))
    assert moved[:4] == (12, 12, 8, 4), moved
    assert moved[4:8] == (2, 2, 1, 1), moved
    assert moved[8:] == (0, 0), moved           # no gated expert, no bare conv
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

    for op in ("pt.fwd/ssd_scan/mamba", "pt.bwd/ssd_scan_grad/mamba",
               "pt.rc/ssd_scan/mamba", "pt.fwd/short_conv/mamba",
               "pt.bwd/short_conv_grad/mamba", "pt.rc/short_conv/mamba",
               "pt.fwd/gated_rms_norm/mamba",
               "pt.bwd/gated_rms_norm_grad/mamba", "pt.fwd/mul/mamba",
               "pt.rc/mul/mamba", "pt.bwd/mul_grad/mamba",
               "pt.fwd/flash_attention/attn", "pt.rc/flash_attention/attn",
               "pt.bwd/flash_attention_grad/attn", "pt.fwd/mul/attn",
               "pt.fwd/mul/shared_expert", "pt.fwd/relu/shared_expert",
               "pt.fwd/square/shared_expert", "pt.fwd/rms_norm",
               "pt.opt/adamw", "pt.fwd/fused_lm_head_ce",
               "pt.fwd/lookup_table"):
        assert any(s == op or s.startswith(op + "/") for s in stacks), op
    for role_op in ("pt.fwd/moe_ffn/", "pt.bwd/moe_ffn_grad/",
                    "pt.rc/moe_ffn/"):
        seen = {part_scopes.part_of(r, part_scopes.MOE_PARTS)
                for r in under(role_op)}
        assert seen >= set(part_scopes.MOE_PARTS), (role_op, seen)
    # a one-sublayer block: no rope, no second norm's tag, no gate branch
    assert not any("/rope" in s or "/kda" in s for s in stacks)
    assert json.dumps(sorted(stacks))       # names only, nothing device-bound
