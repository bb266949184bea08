"""The Trinity-Mini cell (``trinity_mini_lm_s8192``) rehearsed on the CPU at
toy widths: the cell end to end to the contract's last line, what decides
``correct`` at the cell's own limits, its three per-layer readers on a
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

from benchmark import harness, part_scopes, trinity_flops  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import test_benchmark_rehearsal as rehearsal  # noqa: E402
import test_op_scopes as scopes_test  # noqa: E402
import test_program_scopes as program_scopes_test  # noqa: E402

CELL = "trinity_mini_lm_s8192"
SPEC = harness.load_spec()
FILE = harness.load_json("benchmark/configs/trinity_mini.json")
NEW = ("window_attention_roofline", "moe_share_experts_roofline",
       "moe_local_rows_share")


def toy_trinity():
    c = copy.deepcopy(FILE)
    c.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
             head_dim=16, intermediate_size=96, moe_intermediate_size=32,
             num_experts=4, num_experts_per_tok=2, vocab_size=128,
             sliding_window=8, num_hidden_layers=3, layer_types=[
                 "sliding_attention", "sliding_attention", "full_attention"])
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
                           "reason": "toy widths"}
    t = copy.deepcopy(harness.load_traffic("lm_s8192"))
    t.update(batch_per_chip=2, seq_len=32, ring=2, warmup_steps=1,
             check_batch=2, reference_q_block=16)
    return c, t


# -- BENCHMARK.json ---------------------------------------------------------------

def test_the_cell_is_listed_with_its_files_and_metrics():
    cell = harness.find(SPEC["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("trinity_mini", "lm_s8192", 1)
    assert len(cell["why"]) <= 200
    cfg = harness.find(SPEC["configs"], "trinity_mini", "config")
    assert FILE["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "layer_types",
        "num_experts", "vocab_size"]
    assert FILE["source"] == cfg["source"] and len(cfg["why"]) <= 200
    for kind, fn in (("models", "build_train"), ("reference", "loss")):
        assert callable(getattr(harness.load_module(kind, "trinity_mini"),
                                fn))
    t = harness.load_traffic("lm_s8192")
    assert (t["kind"], t["batch_per_chip"], t["seq_len"], t["ring"],
            t["warmup_steps"], t["check_batch"]) == \
        ("train_ring", 1, 8192, 4, 3, 1)
    e2e = {m["name"] for m in harness.metrics_of_cell(SPEC, "end_to_end",
                                                      CELL)}
    assert e2e == {"train_samples_per_s", "peak_hbm_gb", "setup_s"}
    layer = {m["name"] for m in harness.metrics_of_cell(SPEC, "per_layer",
                                                        CELL)}
    assert layer >= set(NEW) | {
        "moe_device_ms.train", "moe_dispatch_device_ms.train",
        "attention_device_ms.train", "lm_head_device_ms.train",
        "op_scoped_share.train", "train_mfu", "step_device_ms.train",
        "train_device_idle_share", "dispatch_ms.train"}
    # OLMoE's two rooflines count OLMoE's work from OLMoE's keys
    assert not layer & {"flash_attention_roofline", "moe_experts_roofline"}
    for m in SPEC["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "train_samples_per_s" and m["unit"] == "%"
            assert callable(
                harness.load_module("layer_metrics", m["name"]).read)


def test_the_config_file_holds_the_catalogs_numbers():
    """Every key of the catalog entry's ``config`` under the same key; the
    keys that differ are the five listed, no width among them."""
    types = ["sliding_attention"] * 3 + ["full_attention"]
    catalog = {
        "global_attn_every_n_layers": 4, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "layer_types": types * 8, "load_balance_coeff": 0.001,
        "max_position_embeddings": 131072, "model_type": "afmoe",
        "moe_intermediate_size": 1024, "mup_enabled": True, "n_group": 1,
        "num_attention_heads": 32, "num_dense_layers": 2,
        "num_expert_groups": 1, "num_experts": 128,
        "num_experts_per_tok": 8, "num_hidden_layers": 32,
        "num_key_value_heads": 4, "num_limited_groups": 1,
        "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
        "rope_theta": 10000, "route_norm": True, "route_scale": 2.826,
        "score_func": "sigmoid", "sliding_window": 2048,
        "tie_word_embeddings": False, "topk_group": 1,
        "use_grouped_mm": True, "vocab_size": 200192}
    differ = sorted(k for k, v in catalog.items() if FILE[k] != v)
    assert differ == sorted(FILE["reduced"])
    assert FILE["layer_types"] == catalog["layer_types"][1:6]
    assert (FILE["num_hidden_layers"], FILE["num_dense_layers"],
            FILE["num_experts"], FILE["vocab_size"]) == (5, 1, 16, 25024)
    a = FILE["assumed"]
    assert a["router_outputs"] == 128 and a["expert_offset"] == 0
    assert FILE["vocab_size"] * 8 == catalog["vocab_size"]
    for mechanism in ("output_gate", "qk_norm", "four_norms",
                      "rope_on_sliding_layers_only", "selection_bias",
                      "route_norm_eps", "embedding_scale", "shared_expert"):
        assert "modeling_afmoe" in a[mechanism] or mechanism in (
            "embedding_scale", "shared_expert")
    assert "8 chips" in FILE["deployment"]


def test_the_parameters_fill_two_thirds_of_the_chip():
    """705.5 M parameters at 16 bytes: 11.29 GB of 16.9, from the shapes the
    program holds (the driver's floor for a new cell is a quarter)."""
    from paddle_tpu.framework import Program, program_guard
    from paddle_tpu.models import transformer as T
    model = harness.load_module("models", "trinity_mini")
    main = Program()
    with program_guard(main, Program()):
        T.build_trinity_pretrain(model.trinity_config(FILE), 8192)
    shapes = {p.name: tuple(p.shape) for p in main.all_parameters()}
    assert shapes["dec_1.attn.qkv.w"] == (2048, 9216)
    assert shapes["dec_1.moe.router.w"] == (2048, 128)
    assert shapes["dec_1.moe.gate.w"] == (16, 2048, 1024)
    assert shapes["dec_0.ffn.gate_up.w"] == (2048, 12288)
    assert shapes["lm_out.w"] == (2048, 25024)
    n = sum(int(np.prod(s)) for s in shapes.values())
    assert n == 705_474_304, n
    assert 16 * n / 16.9e9 > 0.66


# -- the cell end to end ----------------------------------------------------------

@pytest.mark.parametrize("trace", [
    # the untraced run returns before the readers: the traced one covers it
    pytest.param(0, marks=pytest.mark.slow), 1])
def test_cell_end_to_end_on_cpu(trace):
    config, traffic = toy_trinity()
    result = harness.run_cell(CELL, seed=rehearsal.BIG_SEED, seconds=0.5,
                              trace=bool(trace), on_chip=False,
                              config=config, traffic=traffic, spec=SPEC)
    line = rehearsal.check_contract_line(result, CELL, trace)
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"     # and so: not a result
    if trace:                  # the counter's reader needs no device trace
        assert 0 < line["metrics"]["moe_local_rows_share"]["value"] < 100


# -- what decides ``correct`` -----------------------------------------------------

TOL = FILE["loss_tolerance"]


@pytest.mark.parametrize("flipped,off_flipped,off_rest,lost_rows,ok", [
    (0, 0.0, 2e-6, 0, True),         # a sound run
    (2, 0.3, 2e-6, 0, True),         # two tokens at a tie
    (60, 0.2, 2e-6, 0, False),       # more ties than chance gives
    (400, 0.2, 1e-2, 0, False),      # the control: the reference in bf16
    (0, 0.0, 1e-2, 0, False),        # bf16 arithmetic that flipped nobody
    (0, 0.0, 2e-6, 8, False),        # a token dropped
])
def test_the_float32_verdict_at_the_cells_size(flipped, off_flipped,
                                               off_rest, lost_rows, ok):
    """The cell's own limits on hand-made readings of one 8192-token
    sequence: a token at a tie may choose the other expert without the run
    being called not correct; many such tokens, a worse output on the
    others, or a lost row may not."""
    model = harness.load_module("models", "olmoe_1b_7b")
    tokens, k = 8192, 8
    ref_top = np.tile(np.arange(k), (4, tokens, 1))
    top = ref_top.copy()
    top[0, :flipped, 0] = 127
    size2 = np.full(tokens, 2048.0)
    off = np.full(tokens, off_rest)
    off[:flipped] = off_flipped
    load = np.zeros(128, np.int64)
    load[0] = tokens * k - lost_rows
    out = model.before_window_verdict(
        TOL, 10.13, 10.13, (np.square(off) * size2, size2), top, ref_top,
        [load] * 4, 1)
    assert out["ok"] is ok, out["detail"]


def test_the_reference_in_bf16_in_the_programs_place_is_not_correct():
    """The control of the float32 check at toy widths: the reference with
    every weight, and so every activation, in bf16 takes the program's
    place and comes out not correct; in float32 it comes out correct."""
    import jax
    import jax.numpy as jnp
    from benchmark.models import _train
    config, traffic = toy_trinity()
    model = harness.load_module("models", "trinity_mini")
    olmoe = harness.load_module("models", "olmoe_1b_7b")
    reference = harness.load_module("reference", "trinity_mini")
    for seed in (rehearsal.BIG_SEED,):
        m = model.build_train(config, traffic, seed, 1, False)
        cfg, scope = m["cfg"], m["scope"]
        feed = model.make_batch(_train.rng_of(seed, 7), cfg, 2,
                                traffic["seq_len"])
        params = model.reference_params(
            lambda n: jnp.asarray(scope.find_var(n), jnp.float32), cfg)
        verdicts = {}
        for name, dtype in (("float32", jnp.float32),
                            ("bfloat16", jnp.bfloat16)):
            p = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
            s = reference.sequence_sums(
                p, jnp.asarray(feed["src_ids"]),
                jnp.asarray(feed["lm_label"]),
                **model.reference_kw(cfg, 16))
            got = float(reference.loss_of_sums(s)["loss"])
            top = np.asarray(s["top_e"])
            want, ref_top, per_token = model.reference_loss(
                reference, params, feed, cfg,
                hidden=np.asarray(s["hidden"], np.float32), q_block=16)
            load = [np.bincount(t.ravel(), minlength=cfg.n_experts)
                    for t in top]
            verdicts[name] = olmoe.before_window_verdict(
                config["loss_tolerance"], got, want, per_token, top, ref_top,
                load, 2)
        assert verdicts["float32"]["ok"], verdicts["float32"]["detail"]
        assert not verdicts["bfloat16"]["ok"], verdicts["bfloat16"]["detail"]


def test_the_lowered_step_names_the_windows_the_parts_and_the_dense_ffns():
    """What the new readers and the by-op breakdown depend on: a ``window``
    scope under the windowed layers' ``flash_attention`` and none under the
    full layer's, the four parts of ``moe_ffn`` under it and its grad op,
    and ``shared_expert`` / ``dense_ffn`` after the dense ops' own scope."""
    import jax.numpy as jnp
    from benchmark.models import _train
    config, traffic = toy_trinity()
    model = harness.load_module("models", "trinity_mini")
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

    for role_op in ("pt.fwd/flash_attention/", "pt.bwd/flash_attention_grad/"):
        seen = {part_scopes.part_of(r, ("window",)) for r in under(role_op)}
        assert seen == {"window", ""}, (role_op, seen)    # both kinds of layer
    for role_op in ("pt.fwd/moe_ffn/", "pt.bwd/moe_ffn_grad/"):
        seen = {part_scopes.part_of(r, part_scopes.MOE_PARTS)
                for r in under(role_op)}
        assert seen >= set(part_scopes.MOE_PARTS), (role_op, seen)
    for tag in ("shared_expert", "dense_ffn"):
        assert under(f"pt.fwd/mul/{tag}") and under(f"pt.bwd/mul_grad/{tag}")
        assert under(f"pt.fwd/swish/{tag}")
    for op in ("pt.fwd/rms_norm", "pt.fwd/rope", "pt.fwd/sigmoid",
               "pt.opt/adamw"):
        assert any(s == op or s.startswith(op + "/") for s in stacks), op


# -- the readers on a hand-made trace ------------------------------------------------

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _inputs(tmp_path, events, steps=2):
    inputs = scopes_test._inputs(tmp_path, events, steps)
    inputs.update(config=FILE, traffic={"seq_len": 8192}, peaks=PEAKS,
                  facts={"batch": 1, "chips": 1})
    return inputs


def _read(metric, inputs):
    return harness.load_module("layer_metrics", metric).read(inputs)


def _count(monkeypatch, held, all_):
    from paddle_tpu import monitor
    from paddle_tpu.ops import moe_ops
    ctr = monitor.Counter("paddle_tpu_moe_routed_rows_total", "", ("where",))
    monkeypatch.setattr(moe_ops, "MOE_ROUTED_ROWS_CTR", ctr)
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
        ("flash_fwd.1", fwd + "flash_attention/window/pallas_call:", 0, 40),
        ("flash_fwd.2", fwd + "flash_attention/pallas_call:", 40, 60),
        ("flash_bwd.3",
         bwd + "flash_attention_grad/transpose(jvp(window))/pallas_call:",
         100, 120),
        ("fusion.4", bwd + "flash_attention_grad/reduce:", 220, 30),
        ("gmm.5", fwd + "moe_ffn/experts/jit(gmm)/pallas_call:", 300, 50),
        ("gmm.6", bwd + "moe_ffn_grad/transpose(jvp(experts))/pallas_call:",
         350, 100),
        ("fusion.7", fwd + "moe_ffn/dispatch/gather:", 450, 70),
        ("fusion.8", fwd + "mul/shared_expert/dot_general:", 520, 80),
    ])
    # flash: each layer the larger of its two bounds, forward and backward
    # (all compute-bound), x 2 steps, over the 250 ns under the two ops
    pairs = {True: 2048 * 2049 / 2 + 6144 * 2048, False: 8192 * 8193 / 2}
    least = sum(12 * 128 * 32 * pairs[kind == "sliding_attention"] / 197e12
                for kind in FILE["layer_types"]) * 2
    assert _read("window_attention_roofline", inputs) == pytest.approx(
        100 * least / 250e-9)
    # the counter: 9000 of 65536 slots a layer landed here
    _count(monkeypatch, 9000 * 4, 65536 * 4)
    assert _read("moe_local_rows_share", inputs) == pytest.approx(
        100 * 9000 / 65536)
    # nine matmuls of 2 * 9000 * 2048 * 1024 FLOPs (0.19 ms) against their
    # bytes (16 experts' weights and 9000 rows: 0.13 and 0.23 ms): the six
    # through the weights are compute-bound, the three to them memory-bound
    through = max(2 * 9000 * 2048 * 1024 / 197e12,
                  (9000 * 3072 * 2 + 16 * 2048 * 1024 * 2) / 819e9)
    to = max(2 * 9000 * 2048 * 1024 / 197e12,
             (9000 * 3072 * 2 + 16 * 2048 * 1024 * 4) / 819e9)
    assert _read("moe_share_experts_roofline", inputs) == pytest.approx(
        100 * (6 * through + 3 * to) * 4 * 2 / 150e-9)
    # nothing counted: even routing's 8192 rows
    _count(monkeypatch, 0, 0)
    assert _read("moe_local_rows_share", inputs) is None
    through = (8192 * 3072 * 2 + 16 * 2048 * 1024 * 2) / 819e9
    assert 2 * 8192 * 2048 * 1024 / 197e12 > through
    to = (8192 * 3072 * 2 + 16 * 2048 * 1024 * 4) / 819e9
    assert _read("moe_share_experts_roofline", inputs) == pytest.approx(
        100 * (6 * 2 * 8192 * 2048 * 1024 / 197e12 + 3 * to) * 4 * 2
        / 150e-9)


def test_the_readers_return_nothing_with_nothing_to_read(tmp_path,
                                                         monkeypatch):
    """A trace of another program, a trace without scopes, no trace at all,
    another configuration's keys, and a program without the counter."""
    other = _inputs(tmp_path, [
        ("fusion.1", "jit(step)/pt.fwd/mul/dot_general:", 0, 100)])
    (tmp_path / "b").mkdir()
    bare = _inputs(tmp_path / "b", [("fusion.1", None, 0, 100)])
    none = dict(other, trace=None, trace_window=None)
    _count(monkeypatch, 0, 0)
    for inputs in (other, bare, none):
        for metric in NEW:
            assert _read(metric, inputs) is None, metric
    (tmp_path / "c").mkdir()
    olmoe = _inputs(tmp_path / "c", [
        ("flash_fwd.1", "jit(step)/pt.fwd/flash_attention/pallas_call:", 0,
         40),
        ("gmm.2", "jit(step)/pt.fwd/moe_ffn/experts/pallas_call:", 40, 40)])
    olmoe["config"] = harness.load_json("benchmark/configs/olmoe_1b_7b.json")
    for metric in ("window_attention_roofline", "moe_share_experts_roofline"):
        assert _read(metric, olmoe) is None, metric
    from paddle_tpu import monitor
    monkeypatch.setattr(monitor.REGISTRY, "get", lambda name: None)
    assert _read("moe_local_rows_share", other) is None


# -- the yardstick's arithmetic ------------------------------------------------------

def test_forward_flops_by_part_by_hand():
    parts = trinity_flops.forward_flops_by_part(FILE, 8192)
    t, d = 8192, 2048
    assert parts["attention_projections"] == \
        5 * (2 * t * d * (2 * 4096 + 2 * 512) + 2 * t * 4096 * d)
    band = 2048 * 2049 // 2 + 6144 * 2048           # 14,681,088 pairs
    half = 8192 * 8193 // 2                         # 33,558,528
    assert trinity_flops.live_pairs(8192, 2048) == band
    assert trinity_flops.live_pairs(8192) == half
    assert trinity_flops.live_pairs(64, 64) == trinity_flops.live_pairs(64)
    assert band / half == pytest.approx(0.4375, abs=1e-3)
    assert parts["attention_scores"] == 4 * 128 * 32 * (4 * band + half)
    assert parts["dense_ffn"] == 6 * t * d * 6144
    assert parts["shared_expert"] == 4 * 6 * t * d * 1024
    assert parts["routed_experts"] == 4 * 6 * (t * 8 * 16 / 128) * d * 1024
    assert parts["router"] == 4 * 2 * t * d * 128
    assert parts["head"] == 2 * t * d * 25024
    total = sum(parts.values())
    assert total == pytest.approx(6.045e12, rel=1e-3)
    mixed = parts["attention_projections"] + parts["attention_scores"]
    assert mixed / total == pytest.approx(0.62, abs=0.005)
    assert trinity_flops.train_flops_per_sample(FILE, 8192) == 3 * total
    # without the skipping the four window layers would cost a full layer's
    assert 4 * 128 * 32 * 5 * half == pytest.approx(2.75e12, rel=2e-3)


def test_flash_and_expert_kernel_counts_by_hand():
    (wf, wb), (ff, fb) = (trinity_flops.flash_layer_kernels(
        32, 4, 8192, 128, w) for w in (2048, 0))
    band, half = 14681088, 33558528
    assert wf[0] == 4 * 128 * 32 * band and wb[0] == 2 * wf[0]
    assert ff[0] == 4 * 128 * 32 * half and fb[0] == 2 * ff[0]
    q, kv, lse = 32 * 8192 * 128 * 2, 4 * 8192 * 128 * 2, 32 * 8192 * 4
    assert wf[1] == ff[1] == 2 * q + 2 * kv + lse
    assert wb[1] == fb[1] == 4 * q + 4 * kv + lse
    layers = trinity_flops.flash_kernels_of_model(FILE, 8192)
    assert [k[0][0] for k in layers] == [wf[0], wf[0], ff[0], wf[0], wf[0]]
    mm = trinity_flops.held_experts_matmuls(8192, 2048, 1024, 16)
    assert len(mm) == 9
    assert all(fl == 2 * 8192 * 2048 * 1024 for fl, _ in mm)
    rows, w16 = 8192 * 3072 * 2, 16 * 2048 * 1024 * 2
    assert [by for _, by in mm] == 6 * [rows + w16] + 3 * [rows + 2 * w16]
