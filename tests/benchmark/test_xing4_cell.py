"""The Xing4.0 cell (``xing4_29b_a4b_lm_s4096_r64``) rehearsed on the CPU at
toy widths: its files, entries and metrics picked by name, the configuration
file against the catalog row, the parameter count from the program, the FLOPs
by part and ``hc_work``'s bytes by hand, the two new readers on a hand-made
trace and with nothing to read, the cell end to end to the contract's last
line, what the traffic decides, planted faults against the cell's own limits,
and what the lowered step names.  Nothing here is a speed number."""

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

from benchmark import harness, joyai_flops, xing4_flops  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import test_benchmark_rehearsal as rehearsal  # noqa: E402
import test_lfm2_cell as lfm2_test  # noqa: E402
import test_op_scopes as scopes_test  # noqa: E402
import test_program_scopes as program_scopes_test  # noqa: E402

CELL = "xing4_29b_a4b_lm_s4096_r64"
CONFIG = "xing4_29b_a4b"
SPEC = harness.load_spec()
FILE = harness.load_json(f"benchmark/configs/{CONFIG}.json")
TRAFFIC = harness.load_traffic("lm_s4096_r64")
NEW = ("hyper_connection_device_ms.train", "hyper_connection_roofline")
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
           "vocab_size", "num_nextn_predict_layers"]
#: the per-layer lists the cell joins if its rate is admitted (ISSUE 45);
#: not ``recompute_device_ms.train``: the step runs without recomputation
#: (the traffic file's ``recompute_why``), so its reader finds nothing
RATE_LISTS = (
    "dispatch_ms.train", "step_device_ms.train", "train_mfu",
    "train_device_idle_share", "op_scoped_share.train", "fwd_device_ms.train",
    "bwd_device_ms.train", "opt_device_ms.train", "xla_remat_device_ms.train",
    "vjp_forward_again_device_ms.train", "attention_device_ms.train",
    "lm_head_device_ms.train", "moe_device_ms.train",
    "moe_dispatch_device_ms.train", "mla_proj_device_ms.train",
    "latent_attention_roofline") + NEW
_Swapped, _Patched = lfm2_test._Swapped, lfm2_test._Patched


def toy_xing(**traffic):
    c = copy.deepcopy(FILE)
    c.update(hidden_size=64, num_attention_heads=4, q_lora_rank=24,
             kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
             v_head_dim=12, intermediate_size=96, moe_intermediate_size=32,
             n_routed_experts=4, num_experts_per_tok=2, vocab_size=128,
             num_hidden_layers=3)
    c["assumed"].update(router_outputs=8, expert_offset=2)
    # toy widths: the fused head's bf16 products move the loss by 1e-4 and
    # bf16 AMP by 1e-2; the chip's limits are set at the real widths
    c["loss_tolerance"] = {"relative": 2e-3, "hidden_relative": 1e-3,
                           "top_k_differ_share": 0.02, "h_res_sums": 2.4e-4,
                           "first_hidden_relative": 5e-2,
                           "first_gradient_rest_relative": 0.12,
                           # 3 of 64 tokens choose another expert under
                           # bf16 at these widths: a twentieth of the rows
                           "first_gradient_experts_relative": 0.3,
                           "first_gradient_router_relative": 0.5,
                           "first_gradient_maps_relative": 0.15,
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
        (CONFIG, "lm_s4096_r64", 1)
    assert len(cell["why"]) <= 200
    cfg = harness.find(SPEC["configs"], CONFIG, "config")
    assert FILE["reduced"] == cfg["reduced"] == REDUCED
    assert FILE["source"] == cfg["source"] and len(cfg["why"]) <= 200
    assert cfg["file"] == f"benchmark/configs/{CONFIG}.json"
    assert SPEC["workloads"][-1] is cell and SPEC["configs"][-1] is cfg
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
    listed = {m["name"] for m in SPEC["per_layer"]
              if CELL in m.get("workloads", ())}
    if "train_samples_per_s" in e2e:             # the rate was admitted
        assert listed == set(RATE_LISTS)
        for name in NEW:
            m, = [m for m in SPEC["per_layer"] if m["name"] == name]
            assert m["workloads"] == [CELL] and m is SPEC["per_layer"][
                -2 + NEW.index(name)]
    else:                     # in the tree, unlisted, as SmallThinker's is
        assert not listed
        assert not [m for m in SPEC["per_layer"] if m["name"] in NEW]
    # nothing of this cell rides a list a passing test pins
    pinned, = [m for m in SPEC["per_layer"]
               if m["name"] == "moe_local_rows_share"]
    assert CELL not in pinned["workloads"]


def test_the_traffic_file_says_what_the_issue_fixed():
    t = TRAFFIC
    assert (t["kind"], t["batch_per_chip"], t["seq_len"], t["warmup_steps"],
            t["check_batch"]) == ("train_ring", 1, 4096, 3, 1)
    assert (t["learning_rate"], t["lr_start"], t["weight_decay"],
            t["weights_seed"], t["reference_q_block"]) \
        == (4e-4, 0.0, 0.1, 1, 512)
    assert (t["ring"], t["lr_warmup_steps"]) == (64, 2000)
    # false only because the TPU compiler takes the plain step: both peaks
    assert t["recompute"] is False
    assert "14.81 GB" in t["recompute_why"] and "14.59 GB" in t[
        "recompute_why"]
    assert "2000 steps" in FILE["assumed"]["optimizer"]


def test_the_config_file_holds_the_catalogs_numbers():
    """Every key of the catalog entry's ``config`` under the same key; the
    keys that differ are the five listed, no width, no ``hc_*`` and no
    ``rope_scaling`` value among them."""
    catalog = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
        "hidden_act": "silu", "hidden_size": 3584, "intermediate_size": 9216,
        "kv_lora_rank": 512, "max_position_embeddings": 262144,
        "model_type": "xing4_0", "moe_intermediate_size": 1024,
        "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 4,
        "num_hidden_layers": 40, "num_key_value_heads": 32,
        "num_nextn_predict_layers": 1, "hc_mult": 4, "hc_sinkhorn_iters": 20,
        "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30,
        "mhc_h_res_clamp_max": 30, "q_lora_rank": 768,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_theta": 10000,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                         "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 4096,
                         "type": "yarn"},
        "routed_scaling_factor": 2, "scoring_func": "sigmoid",
        "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072}
    differ = sorted(k for k, v in catalog.items() if FILE[k] != v)
    assert differ == sorted(FILE["reduced"]) == sorted(REDUCED)
    assert (FILE["num_hidden_layers"], FILE["first_k_dense_replace"],
            FILE["n_routed_experts"], FILE["vocab_size"],
            FILE["num_nextn_predict_layers"]) == (5, 1, 8, 16384, 0)
    a = FILE["assumed"]
    assert a["router_outputs"] == 64 and a["expert_offset"] == 0
    assert a["rope_interleave"] is True
    assert FILE["vocab_size"] * 8 == catalog["vocab_size"]
    for assumption in ("hc_entry_exit", "hc_equations", "sinkhorn",
                       "hc_initialisation", "rope_interleave_note", "yarn",
                       "latent_norms", "shared_rotary_key", "optimizer",
                       "weights", "data", "num_nextn_predict_layers_note"):
        assert len(a[assumption]) > 40, assumption
    assert "8 chips" in FILE["deployment"]
    assert "759,346,446" in a["parameters"] and "12.15 GB" in a["parameters"]
    assert FILE["flops_module"] == "xing4_flops"
    tol = FILE["loss_tolerance"]
    for key in ("relative", "hidden_relative", "top_k_differ_share",
                "h_res_sums", "first_hidden_relative",
                "first_gradient_rest_relative",
                "first_gradient_experts_relative",
                "first_gradient_router_relative",
                "first_gradient_maps_relative",
                "first_gradient_all_relative", "replayed_update_relative"):
        assert 0 < tol[key] < 1, key
    for key in ("reason", "first_gradient_reason", "replayed_update_reason"):
        assert "control" in tol[key] or "unchanged" in tol[key], key


def test_the_parameters_are_759_346_446_counted_from_the_program():
    """759,346,446 parameters at 16 bytes: 12.15 GB, from the shapes the
    program holds and, by part, from ``xing4_flops.parameters``."""
    from paddle_tpu.framework import Program, program_guard
    from paddle_tpu.models import transformer as T
    model = harness.load_module("models", CONFIG)
    main = Program()
    with program_guard(main, Program()):
        T.build_joyai_pretrain(model.xing_config(FILE), 4096)
    shapes = {p.name: tuple(p.shape) for p in main.all_parameters()}
    assert shapes["dec_0.attn.a.w"] == (3584, 1344)
    assert shapes["dec_0.attn.q_b.w"] == (768, 6144)
    assert shapes["dec_0.attn.kv_b.w"] == (512, 8192)
    assert shapes["dec_0.attn.out.w"] == (4096, 3584)
    assert shapes["dec_0.hc_attn.phi"] == (14336, 24)
    assert shapes["dec_4.hc_ffn.alpha"] == (3,)
    assert shapes["dec_4.hc_ffn.bias"] == (24,)
    assert shapes["dec_0.ffn.gate_up.w"] == (3584, 18432)
    assert shapes["dec_1.shared.gate_up.w"] == (3584, 2048)
    assert shapes["dec_4.moe.router.w"] == (3584, 64)
    assert shapes["dec_4.moe.select_bias"] == (64,)
    assert shapes["dec_4.moe.gate.w"] == (8, 3584, 1024)
    assert shapes["word_embedding"] == shapes["lm_out.w"][::-1] \
        == (16384, 3584)
    assert "dec_0.moe.router.w" not in shapes
    assert not any(n.startswith("mtp_") or n.endswith(".b") for n in shapes)

    def layer(i):
        return sum(int(np.prod(s)) for n, s in shapes.items()
                   if n.startswith(f"dec_{i}."))
    assert [layer(i) for i in range(5)] == [128_196_918] + [128_426_358] * 4
    n = sum(int(np.prod(s)) for s in shapes.values())
    by_part = xing4_flops.parameters(FILE)
    assert n == sum(by_part.values()) == 759_346_446
    assert by_part["latent_attention"] == 5 * 28_411_136
    assert by_part["hyper_connections"] == 5 * 688_182
    assert by_part["dense_ffn"] == 99_090_432
    assert by_part["shared_expert"] == 4 * 11_010_048
    assert by_part["experts"] == 4 * 88_080_384
    assert by_part["router"] == 4 * 229_440
    assert by_part["embedding_and_head"] == 117_440_512
    assert round(16 * n / 1e9, 2) == 12.15
    assert 16 * n / 16.9e9 > 0.25


# -- the yardstick's arithmetic ----------------------------------------------

def test_forward_flops_by_part_by_hand():
    parts = xing4_flops.forward_flops_by_part(FILE, 4096)
    t, d = 4096, 3584
    assert set(parts) == {"attention_projections", "attention_scores",
                          "dense_ffn", "shared_expert", "routed_experts",
                          "router", "head", "hyper_connections"}
    assert parts["attention_projections"] == 5 * 2 * t * (
        d * 1344 + 768 * 6144 + 512 * 8192 + 4096 * d)
    half = 4096 * 4097 // 2
    assert parts["attention_scores"] == 5 * 2 * (192 + 128) * 32 * half
    assert parts["dense_ffn"] == 6 * t * d * 9216
    assert parts["shared_expert"] == 4 * 6 * t * d * 1024
    assert t * 4 * 8 / 64 == 2048 and 2048 / 8 == 256      # rows an expert
    assert parts["routed_experts"] == 4 * 6 * 2048 * d * 1024
    assert parts["router"] == 4 * 2 * t * d * 64
    assert parts["head"] == 2 * t * d * 16384
    # ten sublayers: the [14336, 24] projection, the read mix, the write mix
    assert parts["hyper_connections"] == 10 * t * (
        2 * 14336 * 24 + 2 * 14336 + 2 * 20 * d)
    total = sum(parts.values())
    assert total / t == pytest.approx(952.0e6, rel=1e-3)     # a token
    assert total == pytest.approx(3.90e12, rel=1e-3)
    for name, share in (("attention_projections", 0.298),
                        ("attention_scores", 0.220), ("dense_ffn", 0.208),
                        ("head", 0.123), ("shared_expert", 0.093),
                        ("routed_experts", 0.046),
                        ("hyper_connections", 0.009), ("router", 0.002)):
        assert parts[name] / total == pytest.approx(share, abs=0.001), name
    assert xing4_flops.train_flops_per_sample(FILE, 4096) == 3 * total
    assert 3 * total == pytest.approx(11.7e12, rel=1e-3)


def test_the_hooks_count_each_call_by_hand():
    work = xing4_flops.flash_work(FILE, TRAFFIC)
    assert len(work) == 10                  # five blocks, forward and backward
    assert work[:2] == joyai_flops.latent_flash_layer_kernels(
        32, 4096, 128, 64, 128)
    # what latent_attention_roofline counts: five blocks and no module
    assert len(joyai_flops.flash_kernels_of_model(FILE, 4096)) == 5
    even = xing4_flops.held_experts_work(FILE, TRAFFIC, None)
    assert len(even) == 36 and even == xing4_flops.held_experts_work(
        FILE, TRAFFIC, 8 / 64)
    assert all(fl == 2 * 2048 * 3584 * 1024 for fl, _ in even)
    hc = xing4_flops.hc_work(FILE, TRAFFIC)
    assert len(hc) == 40                    # ten sublayers, four ops each
    t, d = 4096, 3584
    stream, one = t * 4 * d * 2, t * d * 2
    maps, phi = t * 20 * 4, 14336 * 24 * 4
    pre, post, pre_bwd, post_bwd = hc[:4]
    assert pre == (t * (2.0 * 14336 * 24 + 2 * 14336),
                   stream + one + phi + maps)
    assert post == (t * 2.0 * 20 * d, 2 * stream + one + maps)
    assert pre_bwd == (2 * pre[0], 2 * pre[1])
    assert post_bwd == (2 * post[0], 2 * post[1])
    # the bytes set the least time, not the FLOPs
    for fl, by in hc:
        assert by / 819e9 > 5 * fl / 197e12
    least = sum(by for _, by in hc) / 819e9
    assert least == pytest.approx(15.13e-3, rel=1e-2)    # of a ~300 ms step
    assert sum(fl for fl, _ in hc) == pytest.approx(
        3 * xing4_flops.forward_flops_by_part(FILE, 4096)[
            "hyper_connections"])


# -- the readers on a hand-made trace -----------------------------------------

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _inputs(tmp_path, events, steps=2, config=FILE):
    inputs = scopes_test._inputs(tmp_path, events, steps)
    inputs.update(config=config, traffic={"seq_len": 4096}, peaks=PEAKS,
                  facts={"batch": 1, "chips": 1})
    return inputs


def _read(metric, inputs):
    return harness.load_module("layer_metrics", metric).read(inputs)


def test_the_two_readers_on_a_hand_made_trace(tmp_path):
    fwd, bwd, rc = ("jit(step)/pt.%s/" % r for r in ("fwd", "bwd", "rc"))
    inputs = _inputs(tmp_path, [
        ("fusion.1", fwd + "hc_pre/dot_general:", 0, 40),
        ("fusion.2", fwd + "hc_post/mul:", 40, 30),
        ("fusion.3", bwd + "hc_post_grad/reduce:", 70, 60),
        ("fusion.4", bwd + "hc_pre_grad/dot_general:", 130, 90),
        ("fusion.5", rc + "hc_pre/dot_general:", 220, 40),
        ("fusion.6", rc + "hc_post/mul:", 260, 40),
        ("fusion.7", fwd + "rms_norm/mul:", 300, 500),
        ("fusion.8", bwd + "sum/add:", 800, 900),
    ])
    # 300 ns under the four ops in their roles in 2 steps; the norm and the
    # sum of a stream's two gradients are other ops
    assert _read(NEW[0], inputs) == pytest.approx(150e-9 * 1e3)
    least = sum(by for _, by in xing4_flops.hc_work(
        FILE, {"seq_len": 4096})) / 819e9
    assert _read(NEW[1], inputs) == pytest.approx(100 * least / 150e-9)


def test_the_readers_return_nothing_with_nothing_to_read(tmp_path):
    """A trace of another program (every older cell's, the parent's), a
    trace without scopes, no trace at all, a configuration without
    ``hc_mult``, one that names no module of hooks, and one that names a
    module without the hook."""
    other = _inputs(tmp_path, [
        ("fusion.1", "jit(step)/pt.fwd/mul/dot_general:", 0, 100)])
    (tmp_path / "b").mkdir()
    bare = _inputs(tmp_path / "b", [("fusion.1", None, 0, 100)])
    none = dict(other, trace=None, trace_window=None)
    for inputs in (other, bare, none):
        for metric in NEW:
            assert _read(metric, inputs) is None, metric
    events = [("fusion.1", "jit(step)/pt.fwd/hc_pre/mul:", 0, 40)]
    for i, config in enumerate((
            harness.load_json("benchmark/configs/joyai_llm_flash.json"),
            dict(FILE, flops_module="joyai_flops"),
            dict(FILE, flops_module="no_such_module"))):
        (tmp_path / str(i)).mkdir()
        older = _inputs(tmp_path / str(i), events, config=config)
        assert _read(NEW[1], older) is None, i
        assert _read(NEW[0], older) is not None


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
    config, traffic = toy_xing()
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
    assert "maps: worst leaf" in detail
    assert "row and column sums within" in detail
    assert "a state left unchanged reads 1" in detail
    assert "limits exceeded: none" in detail


@pytest.mark.slow
def test_cell_end_to_end_on_cpu_with_the_recompute_fallback():
    config, traffic = toy_xing(recompute=True)
    result = harness.run_cell(CELL, seed=7, seconds=0.5, trace=False,
                              on_chip=False, config=config, traffic=traffic,
                              spec=SPEC)
    line = rehearsal.check_contract_line(result, CELL, 0)
    assert line["correct"] is True


# -- what the traffic decides ------------------------------------------------

def _built(seed, **traffic):
    c, t = toy_xing(**traffic)
    model = harness.load_module("models", CONFIG)
    return model, model.build_train(c, t, seed, 1, False), t


def _weights(m):
    return {p.name: np.asarray(m["scope"].find_var(p.name))
            for p in m["parameters"]}


def test_the_weights_are_the_model_and_the_seed_is_the_traffic():
    """Two values of ``--seed``: the same weights (``weights_seed``), other
    token ids, 64 sequences of the ring each of its own; the
    hyper-connections start as the plain residual they replace; the
    fallback builds the same model and computes two of three blocks again."""
    _, a, _ = _built(11, ring=64, recompute=True)
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
    bias = wa["dec_1.hc_ffn.bias"]
    np.testing.assert_allclose(bias[:4], -np.log(3.0), rtol=1e-6)
    assert np.all(bias[4:8] == 0)
    np.testing.assert_array_equal(bias[8:].reshape(4, 4), 4 * np.eye(4))
    assert np.all(wa["dec_1.hc_ffn.alpha"] == np.float32(0.01))
    assert 0.01 < wa["dec_0.hc_attn.phi"].std() < 0.03
    assert np.all(wa["dec_1.moe.select_bias"] == 0)
    types = [[op.type for op in m["program"].global_block().ops]
             for m in (a, b)]
    # three blocks: two computed again (the last one's backward comes first)
    assert [t.count("hc_pre") for t in types] == [6 + 4, 6]
    assert [t.count("hc_post_grad") for t in types] == [6, 6]


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
    for name in ("word_embedding", "dec_0.hc_attn.phi", "dec_2.hc_ffn.alpha"):
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
    config, _ = toy_xing()
    feed = m["ring"][0]
    _, grads = model._trinity._replayed_first_step(m, feed)
    if fault == "state left unchanged":
        m["exe"] = _Swapped(m["exe"], run=lambda *a, **k: None)
    elif fault == "a decay left out":
        ref = _Swapped(ref, adamw=lambda p, steps, decay: ref._obj.adamw(
            p, steps, 0.0))
    trained = [v for v in m["parameters"] if v.name in m["moment1"]]
    got = model._replayed_update(dict(m, parameters=trained), t, feed, grads,
                                 ref)
    limit = config["loss_tolerance"]["replayed_update_relative"]
    assert got["rate"] == pytest.approx(2e-4)
    if fault is None:
        assert got["worst"][0] <= limit and got["all"] <= limit / 10
    elif fault == "state left unchanged":
        assert got["all"] == 1.0 and got["worst"][0] == 1.0
    else:
        assert got["worst"][0] > limit, got


def _forward_readings(model, m, t, ref):
    """The cell's own float32 forward comparison at toy widths: ``(loss,
    hidden, H_res sums)`` as :func:`check_first_loss` reads them."""
    import jax
    import jax.numpy as jnp
    cfg, scope, feed = m["cfg"], m["scope"], m["ring"][0]
    fwd = model._forward_program(cfg, t["seq_len"], scope, amp=False)
    with jax.default_matmul_precision("highest"):
        got, hidden, _, top, h_res = model._run_forward(
            m["exe"], scope, fwd, feed, cfg)
    params = model.reference_params(
        lambda n: jnp.asarray(scope.find_var(n), jnp.float32), cfg)
    # traced anew: a planted fault must not meet an earlier trace
    fresh = _Swapped(ref, sequence_sums=lambda p, ids, labels, **kw: jax.jit(
        lambda p, ids, labels: ref.batch_sums(p, ids, labels, **kw))(
            p, ids, labels))
    want, ref_top, per_token = model.reference_loss(
        fresh, params, feed, cfg, hidden=hidden, q_block=16)
    differ = model._olmoe.tokens_that_differ(top, ref_top)
    return (abs(got - want) / want,
            model._olmoe.hidden_difference(per_token, ~differ), h_res)


@pytest.mark.parametrize("fault", [
    None, "Sinkhorn stopped at 1 iteration", "H_post without its factor 2",
    "the softmax scale without YaRN's factor",
    "the entry not copied to all four streams"])
def test_the_forward_check_catches(fault, monkeypatch):
    """The float32 forward program against the reference by the cell's own
    readings and the toy limits: within them as built.  Sinkhorn-Knopp cut
    short in the program leaves ``H_res``'s columns off 1 by more than the
    limit (and the loss and the output inside theirs: the reason the sums
    are compared); the other three, planted in the reference, move the
    final-norm output by over ten times its limit."""
    import jax.numpy as jnp
    from paddle_tpu.ops import hc_ops
    model, m, t = _built(11)
    real = harness.load_module("reference", CONFIG)
    limits = toy_xing()[0]["loss_tolerance"]
    patch = _Patched(real)
    if fault == "Sinkhorn stopped at 1 iteration":
        whole = hc_ops.sinkhorn
        monkeypatch.setattr(hc_ops, "sinkhorn",
                            lambda a, n, iters, eps: whole(a, n, 1, eps))
    elif fault == "H_post without its factor 2":
        plain = real.hc_maps

        def hc_maps(*a):
            h_pre, h_post, h_res = plain(*a)
            return h_pre, h_post / 2.0, h_res
        patch = _Patched(real, hc_maps=hc_maps)
    elif fault == "the softmax scale without YaRN's factor":
        patch = _Patched(real, softmax_scale=lambda dn, dr, yarn:
                         (dn + dr) ** -0.5)
    elif fault == "the entry not copied to all four streams":
        def entry(e, hc_mult):                   # stream 0 alone
            return jnp.zeros((e.shape[0], hc_mult, e.shape[1]),
                             e.dtype).at[:, 0].set(e)
        patch = _Patched(real, entry=entry)
    with patch:
        loss, hidden, h_res = _forward_readings(model, m, t, real)
    if fault is None:
        assert loss <= limits["relative"]
        assert h_res <= limits["h_res_sums"] / 3
        assert hidden <= limits["hidden_relative"]
    elif fault.startswith("Sinkhorn"):
        # a map near the identity converges slowly: 20 iterations leave
        # 6e-5 at these widths and one leaves 9e-4; the limit lies 4 times
        # from each
        assert h_res > 3 * limits["h_res_sums"], h_res
        assert hidden <= limits["hidden_relative"]
    else:
        assert hidden > 10 * limits["hidden_relative"], (fault, hidden)


# -- what the lowered step names ---------------------------------------------

def test_the_lowered_step_names_the_ops_their_roles_and_the_table_form():
    """What the readers and the by-op breakdown depend on: the four
    hyper-connection ops under ``pt.fwd``, ``pt.bwd`` and ``pt.rc``, the
    ``mla_proj``, ``dense_ffn`` and ``shared_expert`` tags, ``moe_ffn``'s
    parts; and the counters name what was lowered: four streams, 20
    iterations, the rotary slice by a frequency table."""
    import jax.numpy as jnp
    from benchmark import part_scopes
    from benchmark.models import _train
    from paddle_tpu.framework.recompute import RECOMPUTE_OPS_CTR
    from paddle_tpu.ops import attention_ops, hc_ops
    config, traffic = toy_xing(recompute=True)     # the fallback: pt.rc too
    model = harness.load_module("models", CONFIG)
    hc = {op: hc_ops.HC_LOWERINGS_CTR.value(
        op=op, n="4", sinkhorn_iters="20", impl="xla")
        for op in ("hc_pre", "hc_post", "hc_pre_grad", "hc_post_grad")}
    table = attention_ops.ROPE_LOWERINGS_CTR.value(
        pairing="interleaved", width="8", frequencies="table")
    again = {op: RECOMPUTE_OPS_CTR.value(op=op) for op in ("hc_pre",
                                                           "hc_post")}
    m = model.build_train(config, traffic, 11, 1, False)
    exe, scope = m["exe"], m["scope"]
    feed = _train.put_ring(m["ring"], 1)[0]
    exe.run(m["program"], feed=feed, fetch_list=[m["loss"]], scope=scope)
    for op, before in hc.items():
        assert hc_ops.HC_LOWERINGS_CTR.value(
            op=op, n="4", sinkhorn_iters="20", impl="xla") >= before + 6, op
    # three blocks, two rotations each, forward, again and backward
    assert attention_ops.ROPE_LOWERINGS_CTR.value(
        pairing="interleaved", width="8", frequencies="table") >= table + 16
    assert all(RECOMPUTE_OPS_CTR.value(op=op) >= n + 4
               for op, n in again.items())
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

    for op in ("pt.fwd/hc_pre", "pt.fwd/hc_post", "pt.bwd/hc_pre_grad",
               "pt.bwd/hc_post_grad", "pt.rc/hc_pre", "pt.rc/hc_post",
               "pt.fwd/mul/mla_proj", "pt.rc/mul/mla_proj",
               "pt.fwd/rope/mla_proj", "pt.bwd/rope_grad/mla_proj",
               "pt.fwd/flash_attention", "pt.bwd/flash_attention_grad",
               "pt.fwd/mul/dense_ffn", "pt.fwd/mul/shared_expert",
               "pt.fwd/rms_norm", "pt.opt/adamw", "pt.fwd/fused_lm_head_ce",
               "pt.fwd/lookup_table", "pt.fwd/scale", "pt.fwd/sum"):
        assert any(s == op or s.startswith(op + "/") for s in stacks), op
    for role_op in ("pt.fwd/moe_ffn/", "pt.bwd/moe_ffn_grad/"):
        seen = {part_scopes.part_of(r, part_scopes.MOE_PARTS)
                for r in under(role_op)}
        assert seen >= set(part_scopes.MOE_PARTS), (role_op, seen)
    assert json.dumps(sorted(stacks))       # names only, nothing device-bound
