"""The JoyAI-LLM-Flash cell (``joyai_llm_flash_lm_mtp_s8192``) rehearsed on
the CPU at toy widths: how it is listed, the configuration file against the
catalog's numbers, the cell end to end to the contract's last line, the bf16
control of what decides ``correct``, its three per-layer readers on a
hand-made trace, the FLOP and byte counts against hand-worked numbers, and
the scopes its readers depend on in the lowered step.  Nothing here is a
speed number."""

import copy
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness, joyai_flops, part_scopes  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import test_benchmark_rehearsal as rehearsal  # noqa: E402
import test_op_scopes as scopes_test  # noqa: E402
import test_program_scopes as program_scopes_test  # noqa: E402

CELL = "joyai_llm_flash_lm_mtp_s8192"
SPEC = harness.load_spec()
FILE = harness.load_json("benchmark/configs/joyai_llm_flash.json")
NEW = ("latent_attention_roofline", "mla_proj_device_ms.train",
       "mtp_device_ms.train")


def toy_joyai():
    c = copy.deepcopy(FILE)
    c.update(hidden_size=64, num_attention_heads=4, q_lora_rank=24,
             kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
             qk_head_dim=24, v_head_dim=12, intermediate_size=96,
             moe_intermediate_size=32, n_routed_experts=4,
             num_experts_per_tok=2, vocab_size=128, num_hidden_layers=2)
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
                           "first_update_rest_relative": 0.9,
                           "first_update_experts_relative": 0.9,
                           "first_update_router_relative": 0.9,
                           "first_update_all_relative": 0.9,
                           "first_update_of_gradient_relative": 1e-2,
                           "reason": "toy widths"}
    t = copy.deepcopy(harness.load_traffic("lm_mtp_s8192"))
    t.update(batch_per_chip=2, seq_len=32, ring=2, warmup_steps=1,
             check_batch=2, reference_q_block=16)
    return c, t


# -- BENCHMARK.json ---------------------------------------------------------------

def test_the_cell_is_listed_with_its_files_and_metrics():
    cell = harness.find(SPEC["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("joyai_llm_flash", "lm_mtp_s8192", 1)
    assert len(cell["why"]) <= 200
    cfg = harness.find(SPEC["configs"], "joyai_llm_flash", "config")
    assert FILE["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert FILE["source"] == cfg["source"] and len(cfg["why"]) <= 200
    for kind, fn in (("models", "build_train"), ("reference", "loss")):
        assert callable(getattr(harness.load_module(kind, "joyai_llm_flash"),
                                fn))
    t = harness.load_traffic("lm_mtp_s8192")
    assert "same_as" not in harness.load_json(
        "benchmark/traffic/lm_mtp_s8192.json")
    assert (t["kind"], t["batch_per_chip"], t["seq_len"], t["ring"],
            t["warmup_steps"], t["check_batch"], t["mtp_loss_weight"],
            t["learning_rate"], t["weight_decay"]) == \
        ("train_ring", 1, 8192, 4, 3, 1, 0.3, 4e-4, 0.1)
    e2e = {m["name"] for m in harness.metrics_of_cell(SPEC, "end_to_end",
                                                      CELL)}
    assert e2e == {"train_samples_per_s", "peak_hbm_gb", "setup_s"}
    layer = {m["name"] for m in harness.metrics_of_cell(SPEC, "per_layer",
                                                        CELL)}
    assert layer >= set(NEW) | {
        "moe_device_ms.train", "moe_dispatch_device_ms.train",
        "attention_device_ms.train", "lm_head_device_ms.train",
        "op_scoped_share.train", "train_mfu", "step_device_ms.train",
        "fwd_device_ms.train", "bwd_device_ms.train", "opt_device_ms.train",
        "train_device_idle_share", "dispatch_ms.train"}
    # the other cells' rooflines count their own work from their own keys
    assert not layer & {"flash_attention_roofline", "moe_experts_roofline",
                        "window_attention_roofline",
                        "moe_share_experts_roofline", "moe_local_rows_share"}
    assert [m["name"] for m in SPEC["per_layer"][-3:]] == list(NEW)
    for m in SPEC["per_layer"][-3:]:
        assert m["workloads"] == [CELL]
        assert m["moves"] == "train_samples_per_s"
        assert callable(harness.load_module("layer_metrics", m["name"]).read)
    assert SPEC["workloads"][-1]["name"] == CELL
    assert SPEC["configs"][-1]["name"] == "joyai_llm_flash"


def test_the_config_file_holds_the_catalogs_numbers():
    """Every key of the catalog entry's ``config`` under the same key; the
    keys that differ are the three listed, no width among them."""
    catalog = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
        "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 7168, "kv_lora_rank": 512,
        "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
        "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
        "n_routed_experts": 256, "n_shared_experts": 1,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 8, "num_hidden_layers": 40,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 1,
        "q_lora_rank": 1536, "qk_head_dim": 192, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_interleave": True, "rope_scaling": None,
        "rope_theta": 32000000, "routed_scaling_factor": 2.5,
        "scoring_func": "sigmoid", "tie_word_embeddings": False,
        "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128,
        "vocab_size": 129280}
    differ = sorted(k for k, v in catalog.items() if FILE[k] != v)
    assert differ == sorted(FILE["reduced"])
    assert (FILE["num_hidden_layers"], FILE["n_routed_experts"],
            FILE["vocab_size"]) == (5, 16, 16160)
    assert FILE["vocab_size"] * 8 == catalog["vocab_size"]
    a = FILE["assumed"]
    assert a["router_outputs"] == 256 and a["expert_offset"] == 0
    for mechanism in ("latent_norms", "shared_rotary_key", "route_norm_eps",
                      "selection_bias", "mtp_wiring", "mtp_loss_weight",
                      "fused_down_projection", "optimizer", "weights",
                      "data", "parameters"):
        assert len(a[mechanism]) > 40, mechanism
    assert "16 chips" in FILE["deployment"]


def test_the_parameters_fill_two_thirds_of_the_chip():
    """680.4 M parameters at 16 bytes: 10.89 GB of 16.9, from the shapes the
    program holds (the driver's floor for a new cell is a quarter)."""
    from paddle_tpu.framework import Program, program_guard
    from paddle_tpu.models import transformer as T
    model = harness.load_module("models", "joyai_llm_flash")
    main = Program()
    with program_guard(main, Program()):
        T.build_joyai_pretrain(model.joyai_config(FILE), 8192)
    shapes = {p.name: tuple(p.shape) for p in main.all_parameters()}
    assert shapes["dec_1.attn.a.w"] == (2048, 1536 + 512 + 64)
    assert shapes["dec_1.attn.q_b.w"] == (1536, 32 * 192)
    assert shapes["dec_1.attn.kv_b.w"] == (512, 32 * 256)
    assert shapes["dec_1.attn.out.w"] == (32 * 128, 2048)
    assert shapes["dec_1.moe.router.w"] == (2048, 256)
    assert shapes["mtp_0.moe.gate.w"] == (16, 2048, 768)
    assert shapes["dec_0.ffn.gate_up.w"] == (2048, 2 * 7168)
    assert shapes["mtp_0.eh_proj.w"] == (4096, 2048)
    assert shapes["lm_out.w"] == (2048, 16160)
    assert shapes["word_embedding"] == (16160, 2048)
    n = sum(int(np.prod(s)) for s in shapes.values())
    assert n == 680_441_088, n
    assert 0.64 < 16 * n / 16.9e9 < 0.65


# -- the cell end to end ----------------------------------------------------------

@pytest.mark.parametrize("trace", [
    # the untraced run returns before the readers: the traced one covers it
    pytest.param(0, marks=pytest.mark.slow), 1])
def test_cell_end_to_end_on_cpu(trace):
    config, traffic = toy_joyai()
    result = harness.run_cell(CELL, seed=rehearsal.BIG_SEED, seconds=0.5,
                              trace=bool(trace), on_chip=False,
                              config=config, traffic=traffic, spec=SPEC)
    line = rehearsal.check_contract_line(result, CELL, trace)
    assert line["correct"] is True, result["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"     # and so: not a result
    from paddle_tpu import monitor
    fam = monitor.REGISTRY.get("paddle_tpu_moe_routed_rows_total")
    rows = {labels.get("where"): cell.get() for labels, cell in fam.series()}
    assert rows["all"] > 0 and 0 < rows["held"] < rows["all"]


def test_the_reference_in_bf16_in_the_programs_place_is_not_correct():
    """The controls through the cell's own decision at toy widths (``tools/
    joyai_tolerance_probe.py`` runs it at the real ones): the reference with
    every weight, and so every activation, in bf16 in the float32 program's
    place, the one over float8 weights in the AMP step's and a step that
    keeps its new parameters in bf16 come out not correct, by the two normed
    outputs and by what the step wrote."""
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import joyai_tolerance_probe as probe
    config, traffic = toy_joyai()
    out = probe.one_seed(rehearsal.BIG_SEED, config, traffic, False)
    tol = config["loss_tolerance"]
    assert min(out["bf16"]["hidden_rel_others"]) > tol["hidden_relative"]
    assert min(out["fp8_weights_bf16"]["hidden_rel_all"]) > \
        max(out["bf16"]["hidden_rel_all"])
    control = out["control"]
    assert control["ok"] is False
    assert {"hidden_relative", "first_update_of_gradient_relative"} <= \
        set(control["failed"]), control
    # a norm's scale is 1 and bf16 keeps steps of 2^-7 there: the update of
    # 4.4e-4 is lost whole, which is what an unchanged state reads
    assert control["readings"]["update_of_gradient"] == pytest.approx(1.0)


def test_an_unwritten_parameter_is_not_correct(monkeypatch):
    """The fault the loss and the gradient cannot see: one AdamW op whose
    ``ParamOut`` goes nowhere (its moments are written, so the gradient
    read from them is sound).  The cell comes out not correct, by that
    leaf's change against the reference's step."""
    config, traffic = toy_joyai()
    model = harness.load_module("models", "joyai_llm_flash")
    build = model.build_train

    def planted(*args):
        m = build(*args)
        block = m["program"].global_block()
        op = next(op for op in block.ops if op.type == "adamw"
                  and op.inputs["Param"] == ["dec_1.attn.q_b.w"])
        like = block.var("dec_1.attn.q_b.w")
        block.create_var(name="unwritten", shape=like.shape,
                         dtype=like.dtype)
        op.outputs["ParamOut"] = ["unwritten"]
        return m

    monkeypatch.setattr(model, "build_train", planted)
    result = harness.run_cell(CELL, seed=11, seconds=0.2, trace=False,
                              on_chip=False, config=config, traffic=traffic,
                              spec=SPEC)
    assert result["correct"] is False
    said = result["compared"][1]
    assert "worst rest leaf 1.000e+00 at ['blocks'][1]['w_qb']" in said
    exceeded = said[said.index("limits exceeded:"):]
    assert "first_update_rest_relative" in exceeded
    assert "first_update_of_gradient_relative" in exceeded
    assert "gradient" not in exceeded.replace("update_of_gradient", "")


@pytest.mark.parametrize("fault, reads", [
    ("sound", 0.0), ("rate_halved", 0.5), ("rate_tenfold", 9.0),
    ("decay_dropped", 0.1 / 1.01 ** 0.5), ("kept_in_bf16", 1.0),
    ("unchanged", 1.0)])
def test_the_update_reading_by_fault(fault, reads):
    """``update_difference``'s second number, the step's change against
    the reference's AdamW step from the gradient the step itself read, on a
    norm's scale (1 everywhere, lr 4e-4, decay 0.1: a step of 4e-4 and a
    decay of 4e-5), by hand for each fault; the cell's limit is 1e-2."""
    from benchmark.reference import joyai_llm_flash as reference
    import jax.numpy as jnp
    rng = np.random.RandomState(3)
    theta = {"final_norm_w": np.ones(4096, np.float32)}
    g = {"final_norm_w": rng.randn(4096).astype(np.float32) * 1e-3}
    adamw = dict(lr=4e-4, weight_decay=0.1)
    wrote = dict(adamw, **{
        "rate_halved": {"lr": 2e-4}, "rate_tenfold": {"lr": 4e-3},
        "decay_dropped": {"weight_decay": 0.0},
        "kept_in_bf16": {"store": jnp.bfloat16}}.get(fault, {}))
    delta = {"final_norm_w": reference.adamw_first_step(
        theta["final_norm_w"], g["final_norm_w"], **wrote)}
    if fault == "unchanged":
        delta["final_norm_w"] = np.zeros(4096, np.float32)
    model = harness.load_module("models", "joyai_llm_flash")
    off, own = model.update_difference(reference, theta, delta, g, g, adamw)
    assert own == pytest.approx(reads, rel=0.03, abs=1e-6)
    assert off["rest"][0] == pytest.approx(own) and (own > 1e-2) == (
        fault != "sound")


def test_the_lowered_step_names_the_projections_the_module_and_the_parts():
    """What the new readers and the by-op breakdown depend on: ``mla_proj``
    after the dense ops' own scope and none under the flash op, ``mtp`` over
    the module's ops (its flash op, ``moe_ffn`` and head pass among them,
    ``mtp.mla_proj`` inside), the four parts of ``moe_ffn``."""
    import jax.numpy as jnp
    from benchmark.models import _train
    config, traffic = toy_joyai()
    model = harness.load_module("models", "joyai_llm_flash")
    m = model.build_train(config, traffic, 11, 1, False)
    exe, scope = m["exe"], m["scope"]
    feed = _train.put_ring(m["ring"], 1)[0]
    exe.run(m["program"], feed=feed, fetch_list=[m["loss"]], scope=scope)
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

    tags = harness.load_module("layer_metrics", "mtp_device_ms.train").TAGS
    # every tag the program nests under the module's is one the reader knows
    import re
    assert {t for s in stacks
            for t in re.findall(r"(?<=[/(])mtp[\w.]*", s)} == set(tags)
    for role_op in ("pt.fwd/flash_attention", "pt.bwd/flash_attention_grad"):
        seen = {part_scopes.part_of(r, tags) for r in under(role_op)}
        assert seen == {"mtp", ""}, (role_op, seen)
    for role_op in ("pt.fwd/mul", "pt.bwd/mul_grad"):
        seen = {part_scopes.part_of(r, tags + ("mla_proj", "dense_ffn",
                                               "shared_expert"))
                for r in under(role_op)}
        assert seen >= {"mla_proj", "mtp.mla_proj", "mtp.shared_expert",
                        "mtp", "dense_ffn", "shared_expert"}, seen
    for role_op in ("pt.fwd/moe_ffn/", "pt.bwd/moe_ffn_grad/"):
        seen = {part_scopes.part_of(r, part_scopes.MOE_PARTS)
                for r in under(role_op)}
        assert seen >= set(part_scopes.MOE_PARTS), (role_op, seen)
    assert under("pt.fwd/fused_lm_head_ce/mtp")
    assert under("pt.fwd/rope/mla_proj") and under("pt.fwd/concat/mla_proj")
    assert any(s == "pt.opt/adamw" or s.startswith("pt.opt/adamw/")
               for s in stacks)


# -- the readers on a hand-made trace ------------------------------------------------

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _inputs(tmp_path, events, steps=2):
    inputs = scopes_test._inputs(tmp_path, events, steps)
    inputs.update(config=FILE, traffic={"seq_len": 8192}, peaks=PEAKS,
                  facts={"batch": 1, "chips": 1})
    return inputs


def _read(metric, inputs):
    return harness.load_module("layer_metrics", metric).read(inputs)


def test_the_three_readers_on_a_hand_made_trace(tmp_path):
    fwd, bwd = "jit(step)/pt.fwd/", "jit(step)/pt.bwd/"
    inputs = _inputs(tmp_path, [
        ("flash_fwd.1", fwd + "flash_attention/pallas_call:", 0, 40),
        ("flash_fwd.2", fwd + "flash_attention/mtp/pallas_call:", 40, 60),
        ("flash_bwd.3",
         bwd + "flash_attention_grad/mtp/transpose(jvp())/pallas_call:", 100,
         120),
        ("fusion.4", bwd + "flash_attention_grad/reduce:", 220, 30),
        ("fusion.5", fwd + "mul/mla_proj/dot_general:", 300, 50),
        ("fusion.6", bwd + "mul_grad/mtp.mla_proj/transpose(jvp())/dot:",
         350, 100),
        ("fusion.7", fwd + "concat/mla_proj/concatenate:", 450, 70),
        ("fusion.8", fwd + "mul/mtp.shared_expert/dot_general:", 520, 80),
        ("fusion.9", fwd + "fused_lm_head_ce/mtp/dot_general:", 600, 25),
        ("fusion.10", fwd + "mul/shared_expert/dot_general:", 625, 10),
        ("gmm.11", fwd + "moe_ffn/mtp/experts/pallas_call:", 635, 15),
    ])
    # flash: six blocks, forward and backward compute-bound, x 2 steps, over
    # the 250 ns under the two ops
    half = 8192 * 8193 / 2
    least = 6 * (2 * 320 * 32 * half + 2 * 640 * 32 * half) / 197e12 * 2
    assert _read("latent_attention_roofline", inputs) == pytest.approx(
        100 * least / 250e-9)
    # mla_proj: 50 + 100 + 70 ns over 2 steps; mtp: 60 + 120 + 100 + 80 + 25
    # + 15
    assert _read("mla_proj_device_ms.train", inputs) == pytest.approx(
        220e-9 / 2 * 1e3)
    assert _read("mtp_device_ms.train", inputs) == pytest.approx(
        400e-9 / 2 * 1e3)


def test_the_readers_return_nothing_with_nothing_to_read(tmp_path):
    """A trace of another program, a trace without scopes, no trace at all,
    and another configuration's keys."""
    other = _inputs(tmp_path, [
        ("fusion.1", "jit(step)/pt.fwd/mul/dot_general:", 0, 100)])
    (tmp_path / "b").mkdir()
    bare = _inputs(tmp_path / "b", [("fusion.1", None, 0, 100)])
    none = dict(other, trace=None, trace_window=None)
    for inputs in (other, bare, none):
        for metric in NEW:
            assert _read(metric, inputs) is None, metric
    (tmp_path / "c").mkdir()
    trinity = _inputs(tmp_path / "c", [
        ("flash_fwd.1", "jit(step)/pt.fwd/flash_attention/pallas_call:", 0,
         40)])
    trinity["config"] = harness.load_json(
        "benchmark/configs/trinity_mini.json")
    for metric in NEW:
        assert _read(metric, trinity) is None, metric


# -- the yardstick's arithmetic ------------------------------------------------------

def test_forward_flops_by_part_by_hand():
    parts = joyai_flops.forward_flops_by_part(FILE, 8192)
    t, d = 8192, 2048
    mla = 2 * t * (d * 2112 + 1536 * 32 * 192 + 512 * 32 * 256 + 4096 * d)
    assert parts["attention_projections"] == 6 * mla
    half = 8192 * 8193 // 2                         # 33,558,528
    assert parts["attention_scores"] == 6 * 2 * (192 + 128) * 32 * half
    assert parts["dense_ffn"] == 6 * t * d * 7168
    assert parts["shared_expert"] == 5 * 6 * t * d * 768
    assert parts["routed_experts"] == 5 * 6 * (t * 8 * 16 / 256) * d * 768
    assert parts["router"] == 5 * 2 * t * d * 256
    assert parts["mtp_eh_proj"] == 2 * t * 4096 * d
    assert parts["head"] == 2 * 2 * t * d * 16160
    total = sum(parts.values())
    assert total == pytest.approx(9.28e12, rel=2e-3)
    assert parts["attention_scores"] / total == pytest.approx(0.44, abs=0.005)
    assert parts["attention_projections"] / total == pytest.approx(
        0.28, abs=0.005)
    assert parts["head"] / total == pytest.approx(0.117, abs=0.002)
    assert joyai_flops.train_flops_per_sample(FILE, 8192) == 3 * total
    assert 3 * total == pytest.approx(27.8e12, rel=2e-3)


def test_latent_flash_kernel_counts_by_hand():
    fwd, bwd = joyai_flops.latent_flash_layer_kernels(32, 8192, 128, 64, 128)
    half = 33558528
    assert fwd[0] == 2 * 320 * 32 * half and bwd[0] == 2 * fwd[0]
    q = 32 * 8192 * 192 * 2
    k = (32 * 128 + 64) * 8192 * 2           # the rotary key at one head
    v = 32 * 8192 * 128 * 2
    lse = 32 * 8192 * 4
    assert fwd[1] == q + k + 2 * v + lse
    assert bwd[1] == 2 * q + 2 * k + 4 * v + lse
    # compute-bound on a v5e, forward and backward
    assert fwd[0] / 197e12 > fwd[1] / 819e9 and bwd[0] / 197e12 > bwd[1] / 819e9
    assert fwd[0] / 197e12 == pytest.approx(3.49e-3, rel=2e-3)
    layers = joyai_flops.flash_kernels_of_model(FILE, 8192)
    assert len(layers) == 6 and all(layer == [fwd, bwd] for layer in layers)
