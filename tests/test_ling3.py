"""Ling-3.0-flash's parts (``ops/kda_ops.py``: ``kda_gate``'s bounded form;
``ops/moe_ops.py``: group-limited selection; ``models/transformer.py``:
``latent_attention`` without a Q latent, with QK-norm and a head-wise gate,
``kda_attention`` with full-rank gates, ``LingConfig`` under ``decoder_block``,
``build_ling_pretrain``) at a toy size on the CPU against the plain float32
reference (``benchmark/reference/ling3_flash_vl.py``: the recurrence token by
token, the literal softmax, the router by ``argsort``): the bounded gate and
its gradient, the group-limited router forward and backward at 4 groups / 2
kept and at 1 / 1, the toy model's loss and every leaf's gradient, the
recomputed step against the plain one, the head- and expert-shares' sum,
planted faults, scopes and counters."""

import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import test_joyai as joyai_test  # noqa: E402
import test_olmoe as olmoe_test  # noqa: E402
import paddle_tpu as pt  # noqa: E402
from benchmark.models import ling3_flash_vl as adapter  # noqa: E402
from benchmark.reference import ling3_flash_vl as ref  # noqa: E402
from paddle_tpu import layers, optimizer as opt  # noqa: E402
from paddle_tpu.framework import (Executor, Program, Scope,  # noqa: E402
                                  program_guard, scope_guard)
from paddle_tpu.framework.backward import append_backward  # noqa: E402
from paddle_tpu.framework.core import grad_var_name  # noqa: E402
from paddle_tpu.models import transformer as T  # noqa: E402
from paddle_tpu.ops import attention_ops, kda_ops, moe_ops  # noqa: E402

_close = olmoe_test._close
_rel = joyai_test._rel
_randomise = joyai_test._randomise_norms
LOSS_TOL, GRAD_TOL = olmoe_test.LOSS_TOL, olmoe_test.GRAD_TOL
HIDDEN_TOL = joyai_test.HIDDEN_TOL
SEQ = 40                      # no multiple of the toy chunk 16


def toy_cfg(**kw):
    """Published layers 1-7 at toy widths: layer 0 dense, layer 4 latent
    attention; 16 experts in 4 groups of which 2 are kept, 4 a token."""
    kw = dict(dict(vocab_size=96, d_model=32, n_layer=7, n_head=4,
                   n_kda_head=4, d_head=8, kv_lora_rank=16, d_nope=8,
                   d_rope=4, d_v=8, d_inner=48, d_expert=16, d_shared=24,
                   n_experts=16, top_k=4, n_group=4, topk_group=2,
                   n_dense_layer=2, first_layer=1, kda_chunk=16,
                   rope_theta=10000.0), **kw)
    return T.LingConfig(**kw)


def test_the_layers_follow_the_published_numbers():
    assert T.LingConfig().mla_layers == [5, 11, 17, 23, 29, 35, 41]
    assert T.LingConfig().dense_layers == [0, 1]
    cut = T.LingConfig(n_layer=7, first_layer=1)
    assert (cut.dense_layers, cut.mla_layers) == ([0], [4])
    assert cut.q_lora_rank is None and cut.kda_gate_rank is None
    assert (cut.qk_norm, cut.head_gate, cut.kda_neg_eigval) == \
        (True, True, False)
    # JoyAI's and Solar-Open2's configurations switch none of it on
    for other in (T.JoyaiConfig(), T.XingConfig(), T.SolarOpen2Config()):
        assert not getattr(other, "qk_norm", False)
        assert not getattr(other, "head_gate", False)
        assert getattr(other, "kda_lower_bound", None) is None


def test_the_reference_stands_alone():
    """Plain ``jax.numpy``: nothing of ``paddle_tpu`` and nothing of another
    cell's reference, so that no one bug is common to both sides of two
    cells; no ``top_k`` (the router sorts) and no matmul inside the
    recurrence's step."""
    import inspect
    import re
    src = inspect.getsource(ref)
    imports = re.findall(r"^\s*(?:from|import)\s+(\S+)", src, re.M)
    assert sorted(set(imports)) == ["jax", "jax.numpy", "numpy"]
    assert "top_k(" not in src and "paddle_tpu" not in src.split('"""')[2]
    step = inspect.getsource(ref.recurrence)
    assert "@" not in step.split("def one")[1].split("@jax.checkpoint")[0]
    assert "expm1" in step


# -- the bounded gate -----------------------------------------------------------

def _gate_program(x, b, a_log, dt_bias, lower_bound, amp=False):
    """``sum(G * W)`` through the op and its gradients."""
    scope, main, startup = Scope(), Program(), Program()
    h = a_log.shape[0]
    with scope_guard(scope), program_guard(main, startup):
        xv = layers.data("x", shape=list(x.shape), dtype="float32",
                         append_batch_size=False)
        bv = layers.data("b", shape=list(b.shape), dtype="float32",
                         append_batch_size=False)
        wv = layers.data("w", shape=list(x.shape[:-1]) + [h, x.shape[-1]
                                                          // h],
                         dtype="float32", append_batch_size=False)
        xv.stop_gradient = bv.stop_gradient = False
        g, beta = layers.kda_gate(
            xv, bv, h, "toy", lower_bound=lower_bound,
            rank=None if lower_bound is None else "full")
        loss = layers.reduce_sum(g * wv) + layers.reduce_sum(beta)
        append_backward(loss)
        if amp:
            pt.amp.enable(main)
        exe = Executor()
        exe.run(startup, scope=scope)
    scope.set_var("toy.A_log", jnp.asarray(a_log))
    scope.set_var("toy.dt_bias", jnp.asarray(dt_bias))
    return main, g, beta, exe, scope


@pytest.mark.parametrize("amp", [False, True])
def test_the_bounded_gate_and_its_gradient_match_the_reference(amp):
    r = np.random.RandomState(0)
    h, d = 3, 8
    x = r.randn(2, 5, h * d).astype(np.float32) * 2
    b = r.randn(2, 5, h).astype(np.float32)
    a_log = np.log(r.uniform(1, 16, h)).astype(np.float32)
    dt_bias = r.randn(h * d).astype(np.float32)
    w = r.randn(2, 5, h, d).astype(np.float32)
    main, g, beta, exe, scope = _gate_program(x, b, a_log, dt_bias, -5.0,
                                              amp)
    names = [g.name, beta.name] + [grad_var_name(n) for n in (
        "x", "toy.A_log", "toy.dt_bias")]
    got = exe.run(main, feed={"x": x, "b": b, "w": w}, scope=scope,
                  fetch_list=names)

    def want(x, a_log, dt_bias):
        pre = (x + dt_bias).reshape(10, h, d)
        return ref.bounded_gate(pre, a_log, -5.0).reshape(2, 5, h, d)

    g_ref = want(x, a_log, dt_bias)
    grads = jax.grad(lambda *a: jnp.sum(want(*a) * w), argnums=(0, 1, 2))(
        jnp.asarray(x), jnp.asarray(a_log), jnp.asarray(dt_bias))
    assert np.asarray(got[0]).dtype == np.float32      # whatever AMP says
    _close(got[0], g_ref, 1e-6, "G")
    _close(got[1], 1 / (1 + np.exp(-b)), 1e-6, "Beta: not doubled")
    # under AMP the test's own ``g * w`` rounds to bf16, the op does not
    for have, ref_g, what in zip(got[2:], grads, ("dX", "dALog", "dDtBias")):
        _close(have, ref_g, 2e-2 if amp else 2e-5, what)
    # float32's sigmoid reaches its ends: the bounds are closed
    assert np.all(np.asarray(got[0]) <= 0) and np.all(np.asarray(got[0]) >= -5)


def test_the_bounded_gate_stays_in_its_bounds_at_extreme_inputs():
    h, d = 2, 4
    x = np.array([-1e4, -80.0, -1.0, 0.0, 1.0, 80.0, 1e4, 3e38],
                 np.float32).reshape(1, 1, h * d)
    a_log = np.log(np.array([1.0, 16.0], np.float32))
    main, g, _, exe, scope = _gate_program(
        x, np.zeros((1, 1, h), np.float32), a_log,
        np.zeros(h * d, np.float32), -5.0)
    got = np.asarray(exe.run(
        main, feed={"x": x, "b": np.zeros((1, 1, h), np.float32),
                    "w": np.ones((1, 1, h, d), np.float32)},
        scope=scope, fetch_list=[g.name, grad_var_name("x")])[0])
    assert np.all(np.isfinite(got)) and got.min() >= -5.0 and got.max() <= 0
    assert -2.5 == got[0, 0, 0, 3] < got[0, 0, 0, 2] < 0    # -5 sigmoid(0)
    # the softplus form has no floor: the same inputs pass -5 by far
    main, g, _, exe, scope = _gate_program(
        x, np.zeros((1, 1, h), np.float32), a_log,
        np.zeros(h * d, np.float32), None)
    free = np.asarray(exe.run(
        main, feed={"x": x, "b": np.zeros((1, 1, h), np.float32),
                    "w": np.ones((1, 1, h, d), np.float32)},
        scope=scope, fetch_list=[g.name])[0])
    assert free.min() < -1e4
    with pytest.raises(ValueError, match="lower_bound"):
        _gate_program(x, np.zeros((1, 1, h), np.float32), a_log,
                      np.zeros(h * d, np.float32), 5.0)


def test_solars_gate_holds_no_new_attribute_but_its_rank():
    """``kda_attention`` as Solar-Open2 builds it: the softplus form (no
    ``lower_bound`` in the op's attributes; the lowered step is the parent's
    to the text), the low-rank gates with their up-projections."""
    scope, main, startup = Scope(), Program(), Program()
    cfg = T.SolarOpen2Config(vocab_size=32, d_model=16, n_layer=1, n_head=2,
                             n_kv_head=1, n_kda_head=2, d_head=8,
                             d_expert=8, n_experts=4, top_k=2, gqa_layers=[],
                             kda_gate_rank=4, kda_chunk=16)
    with scope_guard(scope), program_guard(main, startup):
        T.build_solar_open2_pretrain(cfg, 16)
    gate, = [op for op in main.global_block().ops if op.type == "kda_gate"]
    assert "lower_bound" not in gate.attrs and gate.attrs["rank"] == "4"
    names = {p.name for p in main.all_parameters()}
    assert {"dec_0.kda.f_up.w", "dec_0.kda.g_up.w"} <= names
    moe, = [op for op in main.global_block().ops if op.type == "moe_ffn"]
    assert "n_group" not in moe.attrs and "topk_group" not in moe.attrs


# -- group-limited selection ----------------------------------------------------

def _router_inputs(seed, s=64, d=16, e=16):
    r = np.random.RandomState(seed)
    return (jnp.asarray(r.randn(s, d), jnp.float32),
            jnp.asarray(r.randn(d, e) * 0.5, jnp.float32),
            jnp.asarray(r.randn(e) * 0.2, jnp.float32),
            jnp.asarray(r.randn(s, 4), jnp.float32))


@pytest.mark.parametrize("groups", [(4, 2), (8, 4), (1, 1)])
def test_group_limited_selection_matches_the_argsort_form(groups):
    """Forward (the chosen experts, in order, and their weights) and the
    router's gradient (into its input and its weight, through the chosen
    scores alone) against the reference's sort."""
    n_group, topk_group = groups
    xt, wr, bias, cot = _router_inputs(1)
    kw = dict(renorm=True, score_func="sigmoid", bias=bias, norm_eps=1e-20,
              scale=2.5, n_group=n_group, topk_group=topk_group)

    def program(xt, wr):
        (top_p, _, _), (top_e, load, *_) = moe_ops._router(xt, wr, 4, **kw)
        return jnp.sum(top_p * cot), (top_p, top_e, load)

    def reference(xt, wr):
        weight, top_e = ref.route(
            xt, {"router_w": wr, "select_bias": bias}, 4, n_group,
            topk_group, 2.5)
        top_p = jnp.take_along_axis(weight, top_e, axis=-1)
        return jnp.sum(top_p * cot), (top_p, top_e)

    with jax.default_matmul_precision("highest"):
        (_, (top_p, top_e, load)), got = jax.value_and_grad(
            program, argnums=(0, 1), has_aux=True)(xt, wr)
        (_, (ref_p, ref_e)), want = jax.value_and_grad(
            reference, argnums=(0, 1), has_aux=True)(xt, wr)
    np.testing.assert_array_equal(np.asarray(top_e), np.asarray(ref_e))
    _close(top_p, ref_p, 1e-6, "weights")
    assert int(load.sum()) == 64 * 4
    for have, ref_g, what in zip(got, want, ("dX", "dRouterW")):
        _close(have, ref_g, 1e-5, what)
    if n_group > 1:
        # every token's experts lie in topk_group groups at most
        per = 16 // n_group
        assert max(len(set(row // per)) for row in np.asarray(top_e)) \
            <= topk_group
        free = moe_ops._router(xt, wr, 4, **dict(kw, n_group=1,
                                                 topk_group=1))[1][0]
        assert not np.array_equal(np.asarray(free), np.asarray(top_e))


def test_one_group_of_one_lowers_to_todays_text():
    """``n_group`` 1 is no attribute on the op and the router's lowering as
    it was: the same jaxpr with the arguments and without."""
    xt, wr, bias, _ = _router_inputs(2)
    scope, main = Scope(), Program()
    with scope_guard(scope), program_guard(main, Program()):
        x = layers.data("x", shape=[2, 8, 16], dtype="float32",
                        append_batch_size=False)
        layers.moe_ffn(x, 16, 4, 8, score_func="sigmoid", select_bias=True,
                       n_group=1, topk_group=1)
        layers.moe_ffn(x, 16, 4, 8, score_func="sigmoid", select_bias=True,
                       param_prefix="grouped", n_group=4, topk_group=2)
    plain, grouped = [op for op in main.global_block().ops
                      if op.type == "moe_ffn"]
    assert "n_group" not in plain.attrs and "topk_group" not in plain.attrs
    assert (grouped.attrs["n_group"], grouped.attrs["topk_group"]) == (4, 2)
    texts = [str(jax.make_jaxpr(moe_ops._router_of(attrs, 4, bias))(xt, wr))
             for attrs in ({"score_func": "sigmoid"},
                           {"score_func": "sigmoid", "n_group": 1,
                            "topk_group": 1})]
    assert texts[0] == texts[1] and " sort[" not in texts[0]
    grouped_text = str(jax.make_jaxpr(moe_ops._router_of(
        grouped.attrs | {"score_func": "sigmoid"}, 4, bias))(xt, wr))
    assert grouped_text != texts[0] and " sort[" not in grouped_text
    assert grouped_text.count("top_k[") == texts[0].count("top_k[") + 2
    for bad in (dict(n_group=3, topk_group=1), dict(n_group=4, topk_group=5),
                dict(n_group=1, topk_group=2),
                dict(n_group=8, topk_group=1)):      # 4 a token of 2
        with pytest.raises(ValueError), program_guard(Program(), Program()):
            x = layers.data("x", shape=[2, 8, 16], dtype="float32",
                            append_batch_size=False)
            layers.moe_ffn(x, 16, 4, 8, **bad)


# -- the toy model against the reference ----------------------------------------

def _model(cfg, seq=SEQ, seed=3, recompute=False):
    scope, main, startup = Scope(), Program(), Program()
    with scope_guard(scope), program_guard(main, startup):
        checkpoints = [] if recompute else None
        _, parts, loss = T.build_ling_pretrain(
            cfg, seq, checkpoints=checkpoints, fused_head=False)
        if recompute:
            stepper = opt.RecomputeOptimizer(opt.SGD(learning_rate=0.0))
            stepper._set_checkpoints(checkpoints, after_gradient=True)
            stepper.minimize(loss)
        else:
            append_backward(loss)
        exe = Executor()
        exe.run(startup, scope=scope, seed=seed)
    _randomise(scope, main, seed)
    return scope, main, exe, parts, loss


def _ref_params(scope, cfg):
    return adapter.reference_params(
        lambda n: jnp.asarray(scope.find_var(n), jnp.float32), cfg)


def _run(cfg, recompute=False):
    scope, main, exe, parts, loss = _model(cfg, recompute=recompute)
    feed = adapter.make_batch(np.random.RandomState(0), cfg, 2, SEQ)
    names = [p.name for p in main.all_parameters() if p.trainable]
    tops = [op.outputs["TopExperts"][0] for op in main.global_block().ops
            if op.type == "moe_ffn" and not op.attrs.get("recomputed")]
    got = exe.run(main, feed=feed, scope=scope, fetch_list=[
        loss.name, parts["hidden"].name] + tops
        + [grad_var_name(n) for n in names])
    grads = dict(zip(names, map(np.asarray, got[2 + len(tops):])))
    top = np.stack([np.asarray(t).reshape(-1, cfg.top_k)
                    for t in got[2:2 + len(tops)]])
    return scope, main, feed, float(np.asarray(got[0])), got[1], grads, top


def _reference_sums(scope, cfg, feed, module=ref, **changed):
    kw = dict(adapter.reference_kw(cfg, 8, 8), **changed)
    return jax.jit(lambda p, *a: module.batch_sums(p, *a, **kw))(
        _ref_params(scope, cfg),
        *(jnp.asarray(feed[k]) for k in ("src_ids", "lm_label")))


@pytest.fixture(scope="module")
def toy_run():
    """Seven layers (dense KDA, three KDA, MLA, two KDA over experts), every
    head and expert held, dense head: the program's loss, final-norm output,
    experts a token and gradients on 2 x 40 tokens, and the reference's,
    once."""
    cfg = toy_cfg()
    scope, main, feed, loss, hidden, grads, top = _run(cfg)
    kw = adapter.reference_kw(cfg, 8, 8)
    args = [jnp.asarray(feed[k]) for k in ("src_ids", "lm_label")]
    want, gref = jax.jit(jax.value_and_grad(
        lambda p, *a: ref.loss(p, *a, **kw)))(_ref_params(scope, cfg), *args)
    for blk in gref["blocks"]:
        blk.pop("select_bias", None)
    sums = _reference_sums(scope, cfg, feed)
    got_tree = adapter.reference_params(grads.__getitem__, cfg,
                                        select_bias=False)
    return dict(cfg=cfg, scope=scope, feed=feed, loss=loss, hidden=hidden,
                grads=grads, top=top, want=float(want),
                ref_hidden=sums["hidden"], ref_top=np.asarray(sums["top_e"]),
                off=adapter.gradient_difference(gref, got_tree), main=main)


def test_loss_final_norm_output_and_experts_match_the_reference(toy_run):
    r = toy_run
    assert abs(r["loss"] - r["want"]) / r["want"] <= LOSS_TOL
    assert _rel(r["hidden"], r["ref_hidden"]) <= HIDDEN_TOL
    # six expert layers; the program's top_k and the reference's argsort
    # choose the same experts in the same order
    assert r["top"].shape == r["ref_top"].shape == (6, 2 * SEQ, 4)
    np.testing.assert_array_equal(r["top"], r["ref_top"])


@pytest.mark.parametrize("kind", adapter.KINDS)
def test_every_gradient_leaf_matches_the_reference(kind, toy_run):
    """Leaf by leaf against ``jax.grad`` of the reference, by the kinds the
    cell judges by."""
    together, worst, leaf = toy_run["off"][kind]
    assert worst <= GRAD_TOL, (kind, leaf, worst)
    assert leaf, kind                            # the kind has leaves
    g = toy_run["grads"]
    moved = {"kda": ("dec_0.kda.A_log", "dec_6.kda.dt_bias",
                     "dec_1.kda.conv.filter", "dec_2.kda.o_norm.w"),
             "mla": ("dec_4.attn.q_nope_norm.w", "dec_4.attn.k_nope_norm.w",
                     "dec_4.attn.kv_norm.w", "dec_4.attn.gate.w"),
             "rest": ("dec_0.ffn.gate_up.w", "dec_4.attn.q.w",
                      "dec_4.attn.a.w", "dec_3.kda.in_proj.w")}
    for name in moved.get(kind, ()):
        assert np.abs(g[name]).max() > 0, name


def test_every_parameter_is_a_leaf_of_its_kind(toy_run):
    kda = [f"['blocks'][1]['{k}']" for k in adapter.KDA_LEAVES]
    mla = [f"['blocks'][4]['{k}']" for k in adapter.MLA_LEAVES]
    assert {adapter.kind_of(n) for n in kda} == {"kda"}
    assert {adapter.kind_of(n) for n in mla} == {"mla"}
    for name, kind in (("wq", "rest"), ("wo", "rest"), ("w_kvb", "rest"),
                       ("w_kr", "rest"), ("ffn_up", "rest"),
                       ("shared_up", "rest"), ("router_w", "router"),
                       ("up_w", "experts")):
        assert adapter.kind_of(f"['blocks'][4]['{name}']") == kind
    # no low-rank gate and no Q latent anywhere in the program
    names = {p.name for p in toy_run["main"].all_parameters()}
    assert not any(n.endswith(("f_up.w", "g_up.w", "q_norm.w", "q_b.w"))
                   for n in names)
    assert {"dec_4.attn.q.w", "dec_4.attn.gate.w", "dec_0.ffn.down.w",
            "dec_1.moe.select_bias"} <= names
    assert "dec_0.moe.router.w" not in names and "dec_4.kda.A_log" not in names


def test_the_recomputed_step_is_the_plain_step(toy_run):
    """``RecomputeOptimizer`` at the eight block boundaries over two kinds of
    block: the loss and every gradient of the plain step; the scan, its gate,
    the convolution, the flash op and the router's op among what is computed
    again."""
    _, main, _, loss, _, grads, _ = _run(toy_run["cfg"], recompute=True)
    assert loss == pytest.approx(toy_run["loss"], rel=1e-6)
    for name, g in toy_run["grads"].items():
        assert _rel(grads[name], g) <= 5e-5, name
    again = [op.type for op in main.global_block().ops
             if op.attrs.get("recomputed")]
    assert [again.count(op) for op in (
        "kda_scan", "kda_gate", "short_conv", "flash_attention",
        "moe_ffn")] == [6, 6, 6, 1, 6], again
    pairs = [op for op in main.global_block().ops
             if op.type == "optimization_barrier" and len(op.inputs["X"]) == 2]
    assert len(pairs) == 7
    assert all(op.inputs["X"][1].endswith("@GRAD") for op in pairs)


# -- planted faults ------------------------------------------------------------

def _faulty(**changed):
    """The reference module with some functions replaced."""
    mod = types.ModuleType("faulty_reference")
    mod.__dict__.update({k: v for k, v in vars(ref).items()
                         if not k.startswith("__")})
    for name, fn in list(vars(mod).items()):
        if isinstance(fn, types.FunctionType):
            setattr(mod, name, types.FunctionType(
                fn.__code__, mod.__dict__, name, fn.__defaults__,
                fn.__closure__))
    for name, make in changed.items():
        setattr(mod, name, make(mod))
    return mod


def _softplus_gate(mod):
    return lambda pre, a_log, lower_bound: \
        -jnp.exp(a_log)[None, :, None] * jax.nn.softplus(pre)


def _beta_doubled(mod):
    plain = mod.recurrence
    return lambda q, k, v, g, beta, block: plain(q, k, v, g, 2 * beta, block)


def _no_qk_norm(mod):
    plain = mod.rms

    def rms(v, w, eps):      # the two content norms: [.., H, d_nope] inputs
        return v if v.ndim == 3 and w.shape[0] == v.shape[-1] \
            and v.shape[-1] == 8 and v.shape[1] == 4 else plain(v, w, eps)
    return rms


def _gate_per_channel(mod):
    """The head gate read as a gate a CHANNEL: every head's gate spread over
    its channels by another rule (the gate of head ``i`` on channel ``i`` of
    every head)."""
    plain = mod.latent_attention

    def latent_attention(z, blk, d_nope, d_rope, d_v, eps, theta, q_block):
        h = blk["w_hgate"].shape[1]
        gate = jax.nn.sigmoid(z @ blk["w_hgate"])              # [T, H]
        wide = jnp.tile(jnp.pad(gate, ((0, 0), (0, d_v - h)),
                                constant_values=1.0), (1, h))
        ones = dict(blk, w_hgate=jnp.zeros_like(blk["w_hgate"]))
        inside = blk["wo"]
        ctx_wo = plain(z, dict(ones, wo=jnp.eye(inside.shape[0])), d_nope,
                       d_rope, d_v, eps, theta, q_block) * 2.0  # sigmoid(0)
        return (ctx_wo * wide) @ inside
    return latent_attention


def _no_group_mask(mod):
    plain = mod.choose
    return lambda scores, bias, top_k, n_group, topk_group: plain(
        scores, bias, top_k, 1, 1)


def _group_score_by_the_maximum(mod):
    def choose(scores, bias, top_k, n_group, topk_group):
        s, e = scores.shape
        sel = scores + bias
        best = jnp.max(sel.reshape(s, n_group, e // n_group), axis=-1)
        place = jnp.argsort(jnp.argsort(-best, axis=-1, stable=True),
                            axis=-1, stable=True)
        kept = jnp.repeat(place < topk_group, e // n_group, axis=1)
        return jnp.argsort(-jnp.where(kept, sel, -jnp.inf), axis=-1,
                           stable=True)[:, :top_k]
    return choose


def _bias_in_the_weights(mod):
    plain = mod.route

    def route(m, blk, top_k, n_group, topk_group, route_scale):
        _, top_e = plain(m, blk, top_k, n_group, topk_group, route_scale)
        s = jax.nn.sigmoid(m @ blk["router_w"]) + blk["select_bias"]
        chosen = jnp.zeros_like(s).at[
            jnp.arange(s.shape[0])[:, None], top_e].set(1.0)
        kept = s * chosen
        return kept / (jnp.sum(kept, -1, keepdims=True) + 1e-20) \
            * route_scale, top_e
    return route


FAULTS = {
    "the softplus gate for the bounded one": dict(bounded_gate=_softplus_gate),
    "beta doubled": dict(recurrence=_beta_doubled),
    "QK-norm left out": dict(rms=_no_qk_norm),
    "the head gate applied per channel":
        dict(latent_attention=_gate_per_channel),
    "no group mask": dict(choose=_no_group_mask),
    "the group score by the maximum, not the two largest":
        dict(choose=_group_score_by_the_maximum),
    "the bias added to the weights": dict(route=_bias_in_the_weights),
}
#: the faults that move the choice of experts and are held by
#: ``top_k_differ_share`` (the others by ``hidden_relative``)
BY_CHOICE = ("no group mask",
             "the group score by the maximum, not the two largest")


@pytest.mark.parametrize("fault", [None] + sorted(FAULTS))
def test_the_forward_check_catches(fault, toy_run):
    """The program against the reference with one fault planted in the
    reference: the final-norm output, which the cell holds to
    ``hidden_relative``, leaves its limit by over ten times; a fault in the
    choice of experts moves the share of tokens whose experts differ, which
    the cell holds to ``top_k_differ_share``, from 0 to over a tenth.  As
    built both are inside."""
    cfg, scope, feed = toy_run["cfg"], toy_run["scope"], toy_run["feed"]
    sums = _reference_sums(scope, cfg, feed,
                           module=_faulty(**FAULTS.get(fault, {})))
    off = _rel(toy_run["hidden"], sums["hidden"])
    differ = float(np.any(np.sort(toy_run["top"], -1)
                          != np.sort(np.asarray(sums["top_e"]), -1),
                          axis=(0, 2)).mean())
    if fault is None:
        assert off <= HIDDEN_TOL and differ == 0.0
    elif fault in BY_CHOICE:
        assert differ > 0.1, (fault, differ)
        assert off > 10 * HIDDEN_TOL, (fault, off)
    else:
        assert off > 10 * HIDDEN_TOL, (fault, off)


def test_latent_attention_at_the_periods_first_layer_is_caught_by_name():
    """The placement: latent attention is the LAST layer of each group of
    six, ``(i + 1) % 6 == 0``.  A program that puts it first (``i % 6 ==
    0``: published layer 6 of layers 1-7) holds ``dec_5.attn.*`` where the
    file's layers have ``dec_4.attn.*``; the cell's reading of the
    parameters fails on the first name, before any number."""
    right = toy_cfg()
    wrong = toy_cfg()
    wrong.mla_layers = [j for j in range(7) if (j + 1) % 6 == 0]
    assert (right.mla_layers, wrong.mla_layers) == ([4], [5])
    scope, main, *_ = _model(wrong)
    names = {p.name for p in main.all_parameters()}
    assert "dec_5.attn.q.w" in names and "dec_4.attn.q.w" not in names
    held = {n: scope.find_var(n) for n in names}
    with pytest.raises(KeyError, match="dec_4.attn"):
        adapter.reference_params(held.__getitem__, right)


# -- the share test ------------------------------------------------------------

def _layer_out(cfg, idx, x, values, seed=6):
    """One block's output over ``x`` from a program holding ``cfg``'s share;
    ``values(name)`` gives a parameter (None: the startup program's,
    randomised)."""
    scope, main, startup = Scope(), Program(), Program()
    with scope_guard(scope), program_guard(main, startup):
        xv = layers.data("x", shape=list(x.shape), dtype="float32",
                         append_batch_size=False)
        out, _ = T.decoder_block(xv, cfg, idx)
        exe = Executor()
        exe.run(startup, scope=scope, seed=seed)
    if values is None:
        _randomise(scope, main, seed)
    else:
        for p in main.all_parameters():
            scope.set_var(p.name, jnp.asarray(values(p.name)))
    got = exe.run(main, feed={"x": x}, scope=scope, fetch_list=[out.name])[0]
    return got, {p.name: np.asarray(scope.find_var(p.name))
                 for p in main.all_parameters()}


def _share(name, v, cfg, head_part, head_parts, expert_part, expert_parts):
    """The slice of the uncut layer's parameter ``v`` that a chip holds: its
    heads' columns of every projection split by head, its heads' rows of the
    output projections, its experts."""

    def cols(w, heads, groups, width):
        per = heads // head_parts
        g = w.reshape(*w.shape[:-1], groups, heads, width)
        return g[..., head_part * per:(head_part + 1) * per, :].reshape(
            *w.shape[:-1], groups * per * width)

    if ".moe." in name and v.ndim == 3:
        per = v.shape[0] // expert_parts
        return v[expert_part * per:(expert_part + 1) * per]
    h, d = cfg.n_kda_head, cfg.d_head
    if name.endswith("kda.in_proj.w"):
        wide, beta = np.split(v, [5 * h * d], axis=1)
        per = h // head_parts
        return np.concatenate(
            [cols(wide, h, 5, d),
             beta[:, head_part * per:(head_part + 1) * per]], axis=1)
    if name.endswith("kda.conv.filter"):
        return cols(v.T, h, 3, d).T
    if name.endswith("kda.dt_bias"):
        return cols(v, h, 1, d)
    if name.endswith("kda.A_log"):
        per = h // head_parts
        return v[head_part * per:(head_part + 1) * per]
    if name.endswith("kda.out.w"):
        return cols(v.T, h, 1, d).T
    h = cfg.n_head
    if name.endswith("attn.q.w"):
        return cols(v, h, 1, cfg.d_nope + cfg.d_rope)
    if name.endswith("attn.kv_b.w"):
        return cols(v, h, 1, cfg.d_nope + cfg.d_v)
    if name.endswith("attn.gate.w"):
        return cols(v, h, 1, 1)
    if name.endswith("attn.out.w"):
        return cols(v.T, h, 1, cfg.d_v).T
    return v           # the K/V latent, the rotary key, norms, router: whole


@pytest.mark.parametrize("kind", ["KDA", "MLA"])
def test_the_head_and_expert_shares_add_up_to_the_uncut_layer(kind):
    """The share test of the model-configs guide for the deployment's two
    ways of sharing a layer: two chips each hold half of the layer's heads
    (KDA's or the latent attention's; the K/V latent and the rotary key
    whole on both), four hold a quarter of its experts each.  The mixers'
    partial sums over the head-shares give the uncut ``u = x + Mixer(x)``
    (the all-reduce behind the mixer is the deployment's); from that ``u``
    the expert-shares' partial sums, with the shared expert (what every chip
    computes alike) counted once, give the uncut reference's layer."""
    # one layer of the seven, by its published number: 5 is latent
    # attention, 3 KDA, both over experts
    sizes = dict(n_layer=1, first_layer=5 if kind == "MLA" else 3)
    idx, whole = 0, toy_cfg(**sizes)
    assert whole.mla_layers == ([0] if kind == "MLA" else [])
    x = np.random.RandomState(11).randn(1, SEQ, whole.d_model).astype(
        np.float32)
    _, values = _layer_out(whole, idx, x, None)

    def run(head_part, expert_part, muted, x_in):
        cfg = toy_cfg(n_head=2, n_kda_head=2, n_held=4,
                      expert_offset=4 * expert_part, **sizes)
        return _layer_out(cfg, idx, x_in, lambda n: _share(
            n, values[n] * (0.0 if n.endswith(muted) else 1.0), whole,
            head_part, 2, expert_part, 4))[0]

    ffn, mixer_out = ("shared.down.w", "moe.down.w"), ("attn.out.w",
                                                       "kda.out.w")
    mix = [run(c, 0, ffn, x) - x for c in range(2)]
    u = x + sum(mix)
    alike = run(0, 0, mixer_out + ("moe.down.w",), u)
    routed = [run(0, c, mixer_out, u) - alike for c in range(4)]
    got = alike + sum(routed)
    params = adapter.reference_params(
        lambda n: jnp.asarray(values.get(n, 0.0)), whole)["blocks"][idx]
    with jax.default_matmul_precision("highest"):
        want, _ = ref.block(jnp.asarray(x[0]), params,
                            adapter.reference_kw(whole, 8, 8))
    _close(got[0], want, 2e-5, f"{kind}: shares + alike once")
    # no share is the layer, and the routed parts are not nothing
    assert _rel(x + mix[0], u) > 1e-2 and _rel(alike, got) > 1e-3
    assert min(_rel(r, 0 * r + 1e-30) for r in routed) > 0


# -- scopes and counters -------------------------------------------------------

def test_the_new_ops_ride_their_scopes_and_are_counted(toy_run):
    from paddle_tpu.framework import executor as E
    scoped = {E.op_scope(op) for op in toy_run["main"].global_block().ops}
    for s in ("pt.fwd/kda_scan/kda", "pt.bwd/kda_scan_grad/kda",
              "pt.fwd/kda_gate/kda", "pt.bwd/kda_gate_grad/kda",
              "pt.fwd/short_conv/kda", "pt.fwd/mul/kda",
              "pt.fwd/rms_norm/kda", "pt.fwd/sigmoid/kda",
              "pt.fwd/flash_attention", "pt.fwd/mul/mla_proj",
              "pt.fwd/rms_norm/mla_proj", "pt.fwd/rope/mla_proj",
              "pt.fwd/sigmoid/mla_proj", "pt.fwd/elementwise_mul/mla_proj",
              "pt.fwd/mul/shared_expert", "pt.fwd/mul/dense_ffn",
              "pt.fwd/moe_ffn"):
        assert s in scoped, (s, sorted(scoped))
    # the counters as DIFFERENCES over this test's own run: a total also
    # holds what an earlier test of the file (or of the worker) traced, the
    # plain top-k over 16 experts and the softplus gate among it
    def counted():
        return (kda_ops.KDA_LOWERINGS_CTR.value(
                    heads="4", head_dim="8", chunk="16", impl="xla",
                    neg_eigval="false"),
                kda_ops.KDA_GATE_LOWERINGS_CTR.value(form="bounded",
                                                     rank="full"),
                kda_ops.KDA_GATE_LOWERINGS_CTR.value(form="softplus",
                                                     rank="full"),
                moe_ops.MOE_LOWERINGS_CTR.value(
                    experts="16", top_k="4", score_func="sigmoid",
                    groups="4/2"),
                moe_ops.MOE_LOWERINGS_CTR.value(experts="16", groups="1/1"),
                attention_ops.FLASH_LOWERINGS_CTR.value(widths="8+4/8"))
    before = counted()
    _, main, *_ = _run(toy_run["cfg"], recompute=True)
    scan, bounded, softplus, grouped, plain, flash = (
        b - a for a, b in zip(before, counted()))
    scoped = {E.op_scope(op) for op in main.global_block().ops}
    assert {"pt.rc/kda_scan/kda", "pt.rc/kda_gate/kda",
            "pt.rc/flash_attention", "pt.rc/mul/mla_proj"} <= scoped
    # six KDA layers, forward, forward again and backward, beta not doubled
    assert scan >= 12
    assert bounded >= 6 and softplus == 0
    assert grouped >= 6 and plain == 0
    # the one latent-attention layer at its two widths, and nothing else
    assert flash >= 1
