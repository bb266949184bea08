"""The OLMoE cell (``olmoe_1b_7b_lm_s4096``) rehearsed on the CPU at toy
widths: the cell end to end to the contract's last line, its four per-layer
readers on a hand-made trace, the FLOP and byte counts against hand-worked
numbers, and the scopes its readers depend on in the lowered step.  Nothing
here is a speed number."""

import copy
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness, olmoe_flops, part_scopes  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import test_benchmark_rehearsal as rehearsal  # noqa: E402
import test_op_scopes as scopes_test  # noqa: E402
import test_program_scopes as program_scopes_test  # noqa: E402

CELL = "olmoe_1b_7b_lm_s4096"
SPEC = harness.load_spec()


def toy_olmoe():
    c = copy.deepcopy(harness.load_json("benchmark/configs/olmoe_1b_7b.json"))
    c.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
             intermediate_size=32, num_experts=8, num_experts_per_tok=2,
             num_hidden_layers=2, vocab_size=128)
    # at these widths the fused head's bf16 products move the loss by 1e-4
    # and bf16 AMP by 1e-2; the chip's tolerances are set at the real widths
    c["loss_tolerance"] = {"relative": 2e-3, "hidden_relative": 1e-3,
                           "top_k_differ_share": 0.02,
                           "first_training_loss_relative": 5e-2,
                           "first_hidden_relative": 5e-2,
                           "reason": "toy widths"}
    t = copy.deepcopy(harness.load_traffic("lm_s4096"))
    t.update(batch_per_chip=2, seq_len=32, ring=2, warmup_steps=1,
             check_batch=2)
    return c, t


# -- BENCHMARK.json ---------------------------------------------------------------

def test_the_cell_is_listed_with_its_files_and_metrics():
    cell = harness.find(SPEC["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("olmoe_1b_7b", "lm_s4096", 1)
    assert len(cell["why"]) <= 200
    cfg = harness.find(SPEC["configs"], "olmoe_1b_7b", "config")
    body = harness.load_json(cfg["file"])
    assert body["reduced"] == cfg["reduced"] == ["num_hidden_layers"]
    assert body["source"] == cfg["source"]
    for kind in ("models", "reference"):
        assert callable(getattr(harness.load_module(kind, "olmoe_1b_7b"),
                                {"models": "build_train",
                                 "reference": "loss"}[kind]))
    assert harness.load_traffic("lm_s4096")["kind"] == "train_ring"
    e2e = {m["name"] for m in harness.metrics_of_cell(SPEC, "end_to_end",
                                                      CELL)}
    assert e2e == {"train_samples_per_s", "peak_hbm_gb", "setup_s"}
    layer = {m["name"] for m in harness.metrics_of_cell(SPEC, "per_layer",
                                                        CELL)}
    assert layer >= {"moe_device_ms.train", "moe_dispatch_device_ms.train",
                     "moe_experts_roofline", "flash_attention_roofline",
                     "attention_device_ms.train", "lm_head_device_ms.train",
                     "op_scoped_share.train", "train_mfu",
                     "step_device_ms.train"}
    for m in SPEC["per_layer"][-4:]:           # the four this cell brought
        assert m["workloads"] == [CELL]
        assert callable(harness.load_module("layer_metrics", m["name"]).read)


def test_the_config_file_holds_the_catalogs_numbers():
    """Every key of the catalog entry's ``config`` under the same key;
    only ``num_hidden_layers`` differs, and it is listed."""
    catalog = {"attention_bias": False, "clip_qkv": None,
               "hidden_act": "silu", "hidden_size": 2048,
               "intermediate_size": 1024, "max_position_embeddings": 4096,
               "model_type": "olmoe", "norm_topk_prob": False,
               "num_attention_heads": 16, "num_experts": 64,
               "num_experts_per_tok": 8, "num_hidden_layers": 16,
               "num_key_value_heads": 16, "rms_norm_eps": 1e-05,
               "rope_scaling": None, "rope_theta": 10000,
               "tie_word_embeddings": False, "vocab_size": 50304}
    body = harness.load_json("benchmark/configs/olmoe_1b_7b.json")
    differ = [k for k, v in catalog.items() if body[k] != v]
    assert differ == body["reduced"] == ["num_hidden_layers"]
    assert body["num_hidden_layers"] == 1


# -- the cell end to end ----------------------------------------------------------

@pytest.mark.parametrize("trace", [0, 1])
def test_cell_end_to_end_on_cpu(trace):
    config, traffic = toy_olmoe()
    result = harness.run_cell(CELL, seed=rehearsal.BIG_SEED, seconds=1.0,
                              trace=bool(trace), on_chip=False,
                              config=config, traffic=traffic, spec=SPEC)
    line = rehearsal.check_contract_line(result, CELL, trace)
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"     # and so: not a result


# -- what decides ``correct`` -----------------------------------------------------

#: the configuration's own limits, at the cell's own size: 2 x 4096 tokens
TOL = harness.load_json("benchmark/configs/olmoe_1b_7b.json")["loss_tolerance"]


@pytest.mark.parametrize("flipped,off_flipped,off_rest,lost_rows,ok", [
    (0, 0.0, 1.6e-6, 0, True),       # a sound run, as 38 of 40 seeds read
    (1, 0.37, 1.6e-6, 0, True),      # one token at a tie (PR 30's refusal)
    (3, 0.37, 1.6e-6, 0, True),
    (30, 0.2, 1.6e-6, 0, False),     # more ties than chance gives
    (433, 0.2, 1.1e-2, 0, False),    # the control: the reference in bf16
    (0, 0.0, 1.1e-2, 0, False),      # bf16 arithmetic that flipped nobody
    (0, 0.0, 1.6e-6, 8, False),      # a token dropped
])
def test_the_float32_verdict_at_the_cells_size(flipped, off_flipped,
                                               off_rest, lost_rows, ok):
    """``before_window_verdict`` on hand-made readings of 8192 tokens: a
    token whose 8th and 9th router probabilities tie may choose the other
    expert and be a third off without the run being called not correct (over
    all tokens it reads 4e-3, above the limit of 1e-3 that used to hold
    that number); many such tokens, a worse output on the others, or a
    lost row may not."""
    import numpy as np
    model = harness.load_module("models", "olmoe_1b_7b")
    tokens, k = 8192, 8
    ref_top = np.tile(np.arange(k), (1, tokens, 1))
    top = ref_top.copy()
    top[0, :flipped, 0] = 63                   # another expert, same count
    size2 = np.full(tokens, 2048.0)
    off = np.full(tokens, off_rest)
    off[:flipped] = off_flipped
    load = np.zeros(64, np.int64)
    load[0] = tokens * k - lost_rows
    out = model.before_window_verdict(
        TOL, 10.97, 10.97, (np.square(off) * size2, size2), top, ref_top,
        [load], 2)
    assert out["ok"] is ok, out["detail"]
    if flipped == 1:
        assert model.hidden_difference((np.square(off) * size2, size2)) \
            > TOL["hidden_relative"]
    for limit in ("relative", "hidden_relative", "top_k_differ_share"):
        assert f"(tolerance {TOL[limit]}" in out["detail"]


def test_the_reference_in_bf16_in_the_programs_place_is_not_correct():
    """The control of the float32 check at toy widths: the reference with
    every weight, and so every activation, in bf16 takes the program's
    place and comes out not correct; the same reference in float32 comes
    out correct."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark.models import _train
    config, traffic = toy_olmoe()
    model = harness.load_module("models", "olmoe_1b_7b")
    reference = harness.load_module("reference", "olmoe_1b_7b")
    for seed in (5, rehearsal.BIG_SEED, 3000000017):
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
            got, top, _ = model.reference_loss(reference, p, feed, cfg)
            s = reference.batch_sums(
                p, jnp.asarray(feed["src_ids"]),
                jnp.asarray(feed["lm_label"]), **model._reference_kw(cfg))
            want, ref_top, per_token = model.reference_loss(
                reference, params, feed, cfg,
                hidden=np.asarray(s["hidden"], np.float32))
            load = [np.bincount(t.ravel(), minlength=cfg.n_experts)
                    for t in top]
            verdicts[name] = model.before_window_verdict(
                config["loss_tolerance"], got, want, per_token, top, ref_top,
                load, 2)
        assert verdicts["float32"]["ok"], verdicts["float32"]["detail"]
        assert not verdicts["bfloat16"]["ok"], verdicts["bfloat16"]["detail"]


def test_a_step_over_other_weights_than_the_seeds_is_not_correct(monkeypatch):
    """The rest of a run over a broken timed path: between the float32 check
    and the first step the head's weight is scaled by 8, so the timed step
    trains another model than the seed's, which the reference holds; every
    reading before the window is sound and ``correct`` comes out false on
    the step's own first loss.  (The labels will not do for this: the loss
    of fresh weights is ln V plus little whatever they are.)"""
    model = harness.load_module("models", "olmoe_1b_7b")
    check = model.check_before_window

    def then_break(config, traffic, built, *rest):
        out = check(config, traffic, built, *rest)
        w = built["scope"].find_var("lm_out.w")
        built["scope"].set_vars({"lm_out.w": w * 8.0})
        return out

    monkeypatch.setattr(model, "check_before_window", then_break)
    config, traffic = toy_olmoe()
    result = harness.run_cell(CELL, seed=rehearsal.BIG_SEED + 1, seconds=0.5,
                              trace=False, on_chip=False, config=config,
                              traffic=traffic, spec=SPEC)
    assert result["correct"] is False
    assert result["attempted"] > 0 and result["failed"] == 0
    assert "checks=[True, False]" in result["compared"][-1]


def test_the_lowered_step_names_the_new_ops_and_the_parts_of_moe_ffn():
    """What the new readers depend on: ``pt.<role>/<op>`` scopes for the three
    new ops, and the four parts of ``moe_ffn`` under it and under its grad
    op; the grad op holds no second sort and no forward matmul."""
    import jax.numpy as jnp
    from benchmark.models import _train
    config, traffic = toy_olmoe()
    model = harness.load_module("models", "olmoe_1b_7b")
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
    for scope_name in ("pt.fwd/rms_norm", "pt.fwd/rope", "pt.fwd/moe_ffn",
                       "pt.fwd/flash_attention", "pt.bwd/rms_norm_grad",
                       "pt.bwd/rope_grad", "pt.bwd/moe_ffn_grad",
                       "pt.opt/adamw"):
        assert any(s == scope_name or s.startswith(scope_name + "/")
                   for s in stacks), scope_name
    for role_op in ("pt.fwd/moe_ffn/", "pt.bwd/moe_ffn_grad/"):
        rests = [s[len(role_op):] for s in stacks if s.startswith(role_op)]
        seen = {part_scopes.part_of(r, part_scopes.MOE_PARTS) for r in rests}
        assert seen >= set(part_scopes.MOE_PARTS), (role_op, seen)
    eqns = list(program_scopes_test._eqn_scopes(getattr(jaxpr, "jaxpr",
                                                        jaxpr)))
    sorts = [s for s, prim in eqns if prim == "sort"
             and "moe_ffn" in s]
    assert len(sorts) == 2 and all(s.startswith("pt.fwd/moe_ffn/dispatch")
                                   for s in sorts), sorts   # one per layer


# -- the readers on a hand-made trace ------------------------------------------------

@pytest.mark.parametrize("rest,want", [
    ("experts/jit(gmm)/pallas_call:", "experts"),
    ("transpose(jvp(experts))/pallas_call:", "experts"),
    ("jvp(dispatch)/gather:", "dispatch"),
    ("router/dot_general:", "router"),
    ("transpose(jvp(combine))/mul:", "combine"),
    ("reshape:", ""),
    ("experts_of_something/x:", ""),
])
def test_part_of_an_op_name(rest, want):
    assert part_scopes.part_of(rest, part_scopes.MOE_PARTS) == want


PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _inputs(tmp_path, events, steps=2):
    inputs = scopes_test._inputs(tmp_path, events, steps)
    inputs.update(
        config={"hidden_size": 2048, "intermediate_size": 1024,
                "num_experts": 64, "num_experts_per_tok": 8,
                "num_hidden_layers": 1, "num_attention_heads": 16},
        traffic={"seq_len": 4096}, peaks=PEAKS,
        facts={"batch": 4, "chips": 1})
    inputs["trace"]["ops"] = {}
    for name, _, _, dur in events:
        cls = name.split(".")[0]
        inputs["trace"]["ops"][cls] = inputs["trace"]["ops"].get(cls, 0.0) \
            + dur * 1e-9
    return inputs


def _read(metric, inputs):
    return harness.load_module("layer_metrics", metric).read(inputs)


def test_the_four_readers_on_a_hand_made_trace(tmp_path):
    fwd, bwd = "jit(step)/pt.fwd/moe_ffn/", "jit(step)/pt.bwd/moe_ffn_grad/"
    inputs = _inputs(tmp_path, [
        ("fusion.1", fwd + "router/dot_general:", 0, 10),
        ("sort.2", fwd + "dispatch/sort:", 10, 20),
        ("gmm.3", fwd + "experts/jit(gmm)/pallas_call:", 30, 300),
        ("fusion.4", fwd + "combine/mul:", 330, 30),
        ("fusion.5", fwd + "reshape:", 360, 5),
        ("gmm.6", bwd + "transpose(jvp(experts))/pallas_call:", 400, 200),
        ("fusion.7", bwd + "jvp(experts)/mul:", 600, 40),   # forward again
        ("fusion.8", bwd + "transpose(jvp(dispatch))/gather:", 640, 35),
        ("flash_fwd.9", "jit(step)/pt.fwd/flash_attention/pallas_call:",
         700, 60),
        ("flash_bwd_combined.10",
         "jit(step)/pt.bwd/flash_attention_grad/pallas_call:", 760, 140),
        ("fusion.11", "jit(step)/pt.fwd/mul/dot_general:", 900, 100),
    ])
    ms = 1e-6                                   # ns -> ms; two steps
    assert _read("moe_device_ms.train", inputs) == pytest.approx(
        (10 + 20 + 300 + 30 + 5 + 200 + 40 + 35) * ms / 2)
    assert _read("moe_dispatch_device_ms.train", inputs) == pytest.approx(
        (10 + 20 + 30 + 5 + 35) * ms / 2)
    # nine matmuls of 2 * 131072 * 2048 * 1024 FLOPs at 197 TFLOP/s (each
    # is compute-bound: 2.79 ms against 1.25 or 1.56 ms of bytes), two steps
    least = 9 * 2 * 131072 * 2048 * 1024 / 197e12 * 2
    assert _read("moe_experts_roofline", inputs) == pytest.approx(
        100 * least / (540 * 1e-9))
    # flash: (4 + 8) * 64 * 4096^2 / 2 * 128 FLOPs, two steps
    least = 12 * 64 * 4096 * 4096 / 2 * 128 / 197e12 * 2
    assert _read("flash_attention_roofline", inputs) == pytest.approx(
        100 * least / (200 * 1e-9))
    assert _read("attention_device_ms.train", inputs) == pytest.approx(
        200 * ms / 2)


def test_the_readers_return_nothing_with_nothing_to_read(tmp_path):
    """A trace of another program (no ``moe_ffn``, no flash kernel), a trace
    without scopes, and no trace at all."""
    other = _inputs(tmp_path, [
        ("fusion.1", "jit(step)/pt.fwd/mul/dot_general:", 0, 100)])
    (tmp_path / "b").mkdir()
    bare = _inputs(tmp_path / "b", [("fusion.1", None, 0, 100)])
    none = dict(other, trace=None, trace_window=None)
    for inputs in (other, bare, none):
        for metric in ("moe_device_ms.train", "moe_dispatch_device_ms.train",
                       "moe_experts_roofline", "flash_attention_roofline"):
            assert _read(metric, inputs) is None, metric


# -- the yardstick's arithmetic ------------------------------------------------------

def test_flops_per_token_and_sequence_by_hand():
    per_token = olmoe_flops.olmoe_forward_flops_per_token(
        2048, 1, 64, 8, 1024, 50304, 4096)
    # projections 8 d^2 = 33,554,432; causal attention 2 T d = 16,777,216;
    # router 2 d E = 262,144; experts 6 k d f = 100,663,296;
    # head 2 d V = 206,045,184
    assert per_token == 33554432 + 16777216 + 262144 + 100663296 + 206045184
    assert per_token == 357302272
    assert 206045184 / per_token == pytest.approx(0.5767, abs=1e-4)
    per_seq = olmoe_flops.olmoe_train_flops_per_sample(
        2048, 1, 64, 8, 1024, 50304, 4096)
    assert per_seq == 3 * 4096 * 357302272 == 4390530318336
    # sixteen layers: the head is 1 of 16 layers' worth again
    full = olmoe_flops.olmoe_forward_flops_per_token(
        2048, 16, 64, 8, 1024, 50304, 4096)
    assert full == 16 * 151257088 + 206045184


def test_expert_matmul_counts_by_hand():
    mm = olmoe_flops.moe_experts_matmuls(131072, 2048, 1024, 64)
    assert len(mm) == 9
    assert all(fl == 2 * 131072 * 2048 * 1024 == 549755813888 for fl, _ in mm)
    wide, thin = 131072 * 2048 * 2, 131072 * 1024 * 2
    w16, w32 = 64 * 2048 * 1024 * 2, 64 * 2048 * 1024 * 4
    assert [by for _, by in mm] == 6 * [wide + thin + w16] + \
        3 * [wide + thin + w32]
    assert wide + thin + w16 == 1073741824


def test_flash_kernel_counts_by_hand():
    fwd, bwd = olmoe_flops.flash_attention_kernels(64, 4096, 128)
    half = 4096 * 4096 // 2
    assert fwd[0] == 64 * 4 * half * 128 == 274877906944
    assert bwd[0] == 2 * fwd[0]
    tensor, lse = 64 * 4096 * 128 * 2, 64 * 4096 * 4
    assert fwd[1] == 4 * tensor + lse and bwd[1] == 8 * tensor + lse
