"""SmallThinker-21BA3B on the training path against the plain float32
reference of ``benchmark/reference/smallthinker_21b_a3b.py``, at a toy size on
the CPU: ``moe_ffn`` with the router's own input (``RouterX``) and the ReLU
gate, every expert held and a share held (on every rung of a toy ladder),
against the reference's masked dense experts; then the whole model, four
layers (full, window, window, window) over groups of 7 query heads: loss,
final-norm output, every parameter's gradient and each token's experts
against ``jax.grad`` of the reference, float32 and under AMP, over several
weight seeds; the 8 shares of one layer add up to the uncut layer; every
structural fault ISSUE 38 names fails a tolerance.

Tolerances as ``tests/test_olmoe.py`` sets them and for its reasons (program
and reference are float32 on the CPU and differ by summation order: loss
1e-5, each gradient 1e-4 of its largest entry).  Sizes are tiny on purpose.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import paddle_tpu as pt  # noqa: E402
import test_olmoe as olmoe_test  # noqa: E402
from benchmark.models import olmoe_1b_7b as olmoe_adapter  # noqa: E402
from benchmark.models import smallthinker_21b_a3b as adapter  # noqa: E402
from benchmark.reference import smallthinker_21b_a3b as ref  # noqa: E402
from paddle_tpu import layers  # noqa: E402
from paddle_tpu.framework import (Executor, Program, Scope,  # noqa: E402
                                  program_guard, scope_guard)
from paddle_tpu.framework.backward import append_backward  # noqa: E402
from paddle_tpu.framework.core import grad_var_name  # noqa: E402
from paddle_tpu.models import transformer as T  # noqa: E402
_close, _run_op = olmoe_test._close, olmoe_test._run_op
LOSS_TOL, GRAD_TOL = olmoe_test.LOSS_TOL, olmoe_test.GRAD_TOL
LAYOUT = (0, 1, 1, 1)
SEQ = 24


def toy_cfg(**kw):
    """Groups of 7 query heads to a K/V head, as published (28 over 4)."""
    kw = dict(dict(vocab_size=96, d_model=32, n_layer=4, n_head=14,
                   n_kv_head=2, d_head=8, d_expert=24, n_experts=8, top_k=3,
                   window=8, sliding_window_layout=LAYOUT, n_held=4,
                   expert_offset=2), **kw)
    return T.SmallThinkerConfig(**kw)


def _model(cfg, seq, amp=False, seed=3, fused_head=False, backward=True):
    scope, main, startup = Scope(), Program(), Program()
    with scope_guard(scope), program_guard(main, startup):
        _, parts, loss = T.build_smallthinker_pretrain(
            cfg, seq, fused_head=fused_head)
        if backward:
            append_backward(loss)
        if amp:
            pt.amp.enable(main)
        exe = Executor()
        exe.run(startup, scope=scope, seed=seed)
    # norm scales start at 1: they would hide a norm that read the wrong
    # tensor; the router's N(0, 0.02) leaves every score near 1 / 64
    rng = np.random.RandomState(seed)
    for p in main.all_parameters():
        if p.name.endswith((".ln1.w", ".ln2.w", "_norm.w")):
            scope.set_var(p.name, jnp.asarray(
                rng.uniform(0.5, 1.5, p.shape).astype(np.float32)))
        elif p.name.endswith(".router.w"):
            scope.set_var(p.name, jnp.asarray(
                rng.randn(*p.shape).astype(np.float32) * 0.5))
    return scope, main, exe, parts, loss


def _batch(cfg, b, seq, seed=0):
    return adapter.make_batch(np.random.RandomState(seed), cfg, b, seq)


# -- moe_ffn: the router's own input and the ReLU gate ------------------------

def _moe_weights(rng, d, e_total, held, f):
    return {"moe.router.w": rng.randn(d, e_total).astype(np.float32) * 0.5,
            "moe.gate.w": rng.randn(held, d, f).astype(np.float32) * 0.3,
            "moe.up.w": rng.randn(held, d, f).astype(np.float32) * 0.3,
            "moe.down.w": rng.randn(held, f, d).astype(np.float32) * 0.3}


def _blk(w):
    return {"router_w": w["moe.router.w"], "gate_w": w["moe.gate.w"],
            "up_w": w["moe.up.w"], "down_w": w["moe.down.w"]}


WRT = ["x", "r", "moe.router.w", "moe.gate.w", "moe.up.w", "moe.down.w"]


def _run_layer(x, r, w, e_total, k, f, offset, act="relu", own=True,
               renorm=True):
    """``moe_ffn`` over rows ``x`` with the router reading ``r``: output,
    load and d(sum of squares of the output) / d ``WRT``."""
    held = w["moe.gate.w"].shape[0]

    def build():
        xv = layers.data("x", shape=list(x.shape[1:]), dtype="float32",
                         stop_gradient=False)
        rv = layers.data("r", shape=list(r.shape[1:]), dtype="float32",
                         stop_gradient=False)
        out, _, _, load = layers.moe_ffn(
            xv, e_total, k, f, norm_topk_prob=renorm, num_held=held,
            expert_offset=offset, act=act, router_x=rv if own else None)
        return [out, load], w

    wrt = WRT if own else [n for n in WRT if n != "r"]
    out, load, *grads = _run_op(build, {"x": x, "r": r}, wrt)
    return out, load, dict(zip(wrt, grads))


def _ref_layer(xs, rs, blk, k, offset):
    weight, top_e = ref.route(rs, blk, k)
    return ref.routed_experts(xs, weight, blk, offset), top_e


@pytest.fixture
def toy_tiles(monkeypatch):
    """Row tiles of 4 on the held path, so that toy shapes have a ladder."""
    from paddle_tpu.ops import moe_ops
    monkeypatch.setattr(moe_ops, "_GMM_TILING_HELD", (4, 1024, 1024))
    return moe_ops


@pytest.mark.parametrize("offset,held,e", [
    (0, 8, 8), (2, 2, 32), (0, 4, 32), (30, 2, 32)])
def test_relu_experts_under_a_router_with_its_own_input_match_the_reference(
        toy_tiles, offset, held, e):
    """Output, load and the gradients of the rows, of the router's input
    (apart from the rows'), of the router and of the held experts' weights:
    every expert held, and shares on a ladder of more than one rung."""
    rng = np.random.RandomState(offset * 10 + held)
    b, t, d, k, f = 2, 16, 16, 3, 12
    if held < e:
        assert len(toy_tiles.held_ladder(b * t, k, held, e)) > 1
    x = rng.randn(b, t, d).astype(np.float32)
    r = rng.randn(b, t, d).astype(np.float32)
    w = _moe_weights(rng, d, e, held, f)
    out, load, grads = _run_layer(x, r, w, e, k, f, offset)
    xs, rs = (jnp.asarray(a).reshape(b * t, d) for a in (x, r))
    want, top_e = _ref_layer(xs, rs, _blk(w), k, offset)
    _close(out.reshape(b * t, d), want, 1e-5, "moe_ffn relu / own input")
    np.testing.assert_array_equal(
        load, np.bincount(np.asarray(top_e).ravel(), minlength=e))
    gx, gr, gw = jax.grad(lambda xs, rs, blk: jnp.sum(_ref_layer(
        xs, rs, blk, k, offset)[0] ** 2), (0, 1, 2))(xs, rs, _blk(w))
    _close(grads["x"].reshape(b * t, d), gx, 1e-4, "d / d x")
    _close(grads["r"].reshape(b * t, d), gr, 1e-4, "d / d router input")
    for name, key in (("moe.router.w", "router_w"), ("moe.gate.w", "gate_w"),
                      ("moe.up.w", "up_w"), ("moe.down.w", "down_w")):
        _close(grads[name], gw[key], 1e-4, f"d / d {name}")
    # the planted fault: the router's cotangent added to the rows'
    wrong = grads["x"] + grads["r"]
    assert np.abs(wrong.reshape(b * t, d) - np.asarray(gx)).max() \
        > 10 * 1e-4 * np.abs(np.asarray(gx)).max()


def test_without_its_own_input_the_router_reads_the_rows():
    """``router_x=None`` is the op as it was: the router's cotangent and the
    experts' both reach ``X``, which is what one tensor fed to both inputs
    gives when its two cotangents are added."""
    rng = np.random.RandomState(2)
    x = rng.randn(1, 12, 16).astype(np.float32)
    w = _moe_weights(rng, 16, 8, 8, 12)
    out_a, load_a, g_a = _run_layer(x, x, w, 8, 3, 12, 0, own=False)
    out_b, load_b, g_b = _run_layer(x, x, w, 8, 3, 12, 0, own=True)
    np.testing.assert_array_equal(load_a, load_b)
    _close(out_a, out_b, 1e-6, "same input, one slot or two")
    _close(g_a["x"], g_b["x"] + g_b["r"], 1e-5, "d / d x, summed")


def test_softmax_renormalised_over_the_kept_is_softmax_of_the_kept_logits():
    """``score_func="softmax"`` with ``norm_topk_prob``: the program's
    softmax over all 64 with the kept renormalised is the published top-k of
    the logits then softmax over the k; without the renormalisation (the
    planted fault) the weights no longer sum to 1 and the output moves."""
    rng = np.random.RandomState(5)
    x = rng.randn(1, 16, 16).astype(np.float32)
    w = _moe_weights(rng, 16, 8, 8, 12)
    xs = jnp.asarray(x).reshape(16, 16)
    want, _ = _ref_layer(xs, xs, _blk(w), 3, 0)
    out, _, _ = _run_layer(x, x, w, 8, 3, 12, 0, renorm=True)
    _close(out.reshape(16, 16), want, 1e-5, "renormalised")
    out, _, _ = _run_layer(x, x, w, 8, 3, 12, 0, renorm=False)
    assert np.abs(out.reshape(16, 16) - np.asarray(want)).max() \
        > 1e-2 * np.abs(np.asarray(want)).max()


def test_silu_in_relus_place_is_another_layer():
    rng = np.random.RandomState(6)
    x = rng.randn(1, 16, 16).astype(np.float32)
    w = _moe_weights(rng, 16, 8, 4, 12)
    xs = jnp.asarray(x).reshape(16, 16)
    want, _ = _ref_layer(xs, xs, _blk(w), 3, 2)
    out, _, _ = _run_layer(x, x, w, 8, 3, 12, 2, act="silu")
    assert np.abs(out.reshape(16, 16) - np.asarray(want)).max() \
        > 1e-2 * np.abs(np.asarray(want)).max()
    with pytest.raises(ValueError, match="act"):
        _run_layer(x, x, w, 8, 3, 12, 2, act="gelu")


@pytest.mark.parametrize("what", ["Out", "x", "r", "moe.gate.w"])
def test_every_rung_gives_the_full_buffer_to_the_bit_under_relu(
        monkeypatch, toy_tiles, what):
    """The ReLU pair on the held path's rungs: what the ladder's own rung
    gives and what the full buffer alone gives for the same routing are
    equal exactly."""
    rng = np.random.RandomState(8)
    x = rng.randn(1, 32, 16).astype(np.float32)
    r = rng.randn(1, 32, 16).astype(np.float32)
    w = _moe_weights(rng, 16, 32, 2, 12)
    ladder = toy_tiles.held_ladder(32, 3, 2, 32)
    assert len(ladder) > 1
    results = []
    for lad in (ladder, ladder[-1:]):
        monkeypatch.setattr(toy_tiles, "held_ladder",
                            lambda *a, lad=lad: lad)
        out, load, grads = _run_layer(x, r, w, 32, 3, 12, 5)
        results.append(dict(grads, Out=out))
    assert toy_tiles.held_rung(int(load[5:7].sum()), ladder) \
        < len(ladder) - 1, "the routing should fit a shorter rung"
    np.testing.assert_array_equal(results[0][what], results[1][what])


def test_eight_shares_are_the_uncut_layer():
    """The share test: the parts that the 8 chips' ``moe_ffn`` ops give (2
    of 16 experts each, the router over all 16 reading its own input),
    added up, are the uncut reference's expert output for the whole layer;
    one chip alone is a part, not the layer."""
    rng = np.random.RandomState(21)
    b, t, d, e, k, f = 1, 12, 16, 16, 6, 12
    x = rng.randn(b, t, d).astype(np.float32)
    r = rng.randn(b, t, d).astype(np.float32)
    whole = _moe_weights(rng, d, e, e, f)
    total = 0.0
    for chip in range(8):
        w = dict(whole, **{n: whole[n][2 * chip:2 * chip + 2]
                           for n in ("moe.gate.w", "moe.up.w", "moe.down.w")})
        out, _, _ = _run_layer(x, r, w, e, k, f, 2 * chip)
        total = total + out.reshape(b * t, d)
    xs, rs = (jnp.asarray(a).reshape(b * t, d) for a in (x, r))
    want, _ = _ref_layer(xs, rs, _blk(whole), k, 0)
    _close(total, want, 1e-5, "8 shares")
    assert np.abs(out.reshape(b * t, d) - np.asarray(want)).max() > 1e-2


def test_moe_lowerings_carry_act_and_router_input():
    from paddle_tpu.ops.moe_ops import MOE_LOWERINGS_CTR as ctr
    rng = np.random.RandomState(4)
    x = rng.randn(1, 5, 16).astype(np.float32)
    w = _moe_weights(rng, 16, 8, 2, 12)
    for own, act in ((True, "relu"), (False, "silu")):
        labels = dict(impl="ragged_dot", experts="8", top_k="3", held="2",
                      score_func="softmax", ladder="10", act=act,
                      router_input="own" if own else "x")
        before = ctr.value(**labels)
        short = {k: v for k, v in labels.items()
                 if k not in ("act", "router_input")}
        before_short = ctr.value(**short)
        _run_layer(x, x, w, 8, 3, 12, 4, act=act, own=own)
        assert ctr.value(**labels) == before + 1
        # a reader that names the older labels only still reads its total
        assert ctr.value(**short) == before_short + 1


# -- the whole model ----------------------------------------------------------

def _ref_params(scope, cfg):
    return adapter.reference_params(
        lambda n: jnp.asarray(scope.find_var(n), jnp.float32), cfg)


def _ref_value_and_grad(cfg, params, feed, module=ref):
    """Jitted: eager, the reference's thousand small ops cost ten seconds."""
    kw = adapter.reference_kw(cfg, q_block=8)
    return jax.jit(jax.value_and_grad(
        lambda p, ids, lab: module.loss(p, ids, lab, **kw)))(
            params, jnp.asarray(feed["src_ids"]),
            jnp.asarray(feed["lm_label"]))


def _as_program_grads(gref):
    """Reference-layout gradients under the program's parameter names."""
    out = {"word_embedding": gref["wte"], "final_norm.w":
           gref["final_norm_w"], "lm_out.w": gref["head_w"]}
    for i, blk in enumerate(gref["blocks"]):
        p = f"dec_{i}"
        out[f"{p}.attn.qkv.w"] = jnp.concatenate(
            [blk["wq"], blk["wk"], blk["wv"]], axis=1)
        for name, key in (("attn.out.w", "wo"), ("ln1.w", "ln1_w"),
                          ("ln2.w", "ln2_w"), ("moe.router.w", "router_w"),
                          ("moe.gate.w", "gate_w"), ("moe.up.w", "up_w"),
                          ("moe.down.w", "down_w")):
            out[f"{p}.{name}"] = blk[key]
    return out


def _program_run(cfg, seq, feed, seed=3, amp=False, fused_head=False):
    """Loss, final-norm output, every parameter's gradient, each layer's
    ExpertLoad and TopExperts; and the reference's parameters."""
    scope, main, exe, parts, loss = _model(cfg, seq, amp=amp, seed=seed,
                                           fused_head=fused_head)
    names = [p.name for p in main.all_parameters()]
    loads = [v.name for v in parts["expert_load"]]
    tops = [op.outputs["TopExperts"][0] for op in main.global_block().ops
            if op.type == "moe_ffn"]
    got = exe.run(main, feed=feed, scope=scope, fetch_list=[
        loss.name, parts["hidden"].name] + [grad_var_name(n) for n in names]
        + loads + tops)
    n = len(names)
    return {"loss": float(np.asarray(got[0])), "hidden": np.asarray(got[1]),
            "grads": dict(zip(names, map(np.asarray, got[2:2 + n]))),
            "loads": [np.asarray(v) for v in got[2 + n:2 + n + len(loads)]],
            "tops": np.stack([np.asarray(v).reshape(-1, cfg.top_k)
                              for v in got[2 + n + len(loads):]]),
            "params": _ref_params(scope, cfg), "main": main}


@pytest.fixture(scope="module")
def toy_run():
    """Four layers (full, window, window, window), a share of the experts
    held, dense head: the program's readings on 2 x 24 tokens, once."""
    cfg = toy_cfg()
    feed = _batch(cfg, 2, SEQ)
    return cfg, feed, _program_run(cfg, SEQ, feed)


def _against_the_reference(cfg, feed, run, loss_tol, grad_tol):
    want, gref = _ref_value_and_grad(cfg, run["params"], feed)
    assert abs(run["loss"] - float(want)) / float(want) <= loss_tol, \
        (run["loss"], want)
    gref = _as_program_grads(gref)
    assert set(gref) == set(run["grads"])
    for name in sorted(gref):
        _close(run["grads"][name], gref[name], grad_tol,
               f"d loss / d {name}")
    _, ref_top, per_token = adapter.reference_loss(
        ref, run["params"], feed, cfg, hidden=run["hidden"], q_block=8)
    return ref_top, per_token


@pytest.mark.parametrize("seed", [3, 11, 29])
@pytest.mark.parametrize("held,offset", [(8, 0), (4, 2)],
                         ids=["all-held", "a-share"])
def test_loss_hidden_gradients_and_experts_match_the_reference(
        seed, held, offset):
    cfg = toy_cfg(n_held=held, expert_offset=offset)
    feed = _batch(cfg, 2, SEQ, seed)
    run = _program_run(cfg, SEQ, feed, seed=seed)
    ref_top, per_token = _against_the_reference(cfg, feed, run, LOSS_TOL,
                                                GRAD_TOL)
    assert olmoe_adapter.hidden_difference(per_token) <= 1e-5
    assert not olmoe_adapter.tokens_that_differ(run["tops"], ref_top).any()
    assert len(run["loads"]) == 4 and all(
        v.shape == (8,) and int(v.sum()) == 2 * SEQ * cfg.top_k
        for v in run["loads"])
    for load, top in zip(run["loads"], ref_top):
        np.testing.assert_array_equal(load, np.bincount(top.ravel(),
                                                        minlength=8))


@pytest.mark.parametrize("seed", [3, 11])
@pytest.mark.parametrize("held,offset", [(8, 0), (4, 2)],
                         ids=["all-held", "a-share"])
def test_under_amp_the_step_stays_within_bf16s_reach(seed, held, offset):
    """bf16 activations and expert rows, float32 router and norms, the fused
    head: loss, final-norm output and all leaves together near the float32
    reference (the limits are what bf16 reads at these widths with room, a
    tenth of what any fault of ``test_the_tolerance_catches`` moves)."""
    cfg = toy_cfg(n_held=held, expert_offset=offset)
    feed = _batch(cfg, 2, SEQ, seed)
    run = _program_run(cfg, SEQ, feed, seed=seed, amp=True, fused_head=True)
    want, gref = _ref_value_and_grad(cfg, run["params"], feed)
    assert abs(run["loss"] - float(want)) / float(want) <= 5e-3
    off = adapter.gradient_difference(
        jax.tree_util.tree_map(np.asarray, gref),
        adapter.reference_params(run["grads"].__getitem__, cfg))
    assert off["all"] <= 0.05, off
    _, _, per_token = adapter.reference_loss(
        ref, run["params"], feed, cfg, hidden=run["hidden"], q_block=8)
    assert olmoe_adapter.hidden_difference(per_token) <= 0.05


# -- planted faults -----------------------------------------------------------

def _router_reads(monkeypatch, which):
    """``ref.block`` with the router fed ``x`` (the raw residual) or ``m``
    (the post-attention norm) in the input norm's place."""
    def block(x, blk, window, rotary, n_head, n_kv_head, d_head, top_k, eps,
              theta, expert_offset, q_block):
        n = ref.rms_norm(x, blk["ln1_w"], eps)
        h = x + ref.attention(n, blk, window, rotary, n_head, n_kv_head,
                              d_head, theta, q_block)
        m = ref.rms_norm(h, blk["ln2_w"], eps)
        weight, top_e = ref.route({"x": x, "m": m}[which], blk, top_k)
        return h + ref.routed_experts(m, weight, blk, expert_offset), top_e
    monkeypatch.setattr(ref, "block", block)


def _router_reads_the_post_attention_norm(monkeypatch):
    _router_reads(monkeypatch, "m")


def _router_reads_the_raw_residual(monkeypatch):
    _router_reads(monkeypatch, "x")


def _softmax_before_the_top_k_unrenormalised(monkeypatch):
    def route(n, blk, top_k):
        p = jax.nn.softmax(n @ blk["router_w"], axis=-1)
        top_p, top_e = jax.lax.top_k(p, top_k)
        weight = jnp.sum(jax.nn.one_hot(top_e, p.shape[-1], dtype=p.dtype)
                         * top_p[:, :, None], axis=1)
        return weight, top_e
    monkeypatch.setattr(ref, "route", route)


def _silu_for_relu(monkeypatch):
    monkeypatch.setattr(ref, "relu_gated", lambda m, wg, wu, wd: (
        jax.nn.silu(m @ wg) * (m @ wu)) @ wd)


def _attention_with(monkeypatch, change):
    plain = ref.attention

    def attention(n, blk, window, rotary, *rest):
        return plain(n, blk, *change(window, rotary), *rest)
    monkeypatch.setattr(ref, "attention", attention)


def _rope_on_the_full_layer(monkeypatch):
    _attention_with(monkeypatch, lambda w, r: (w, True))


def _rope_missing_on_a_window_layer(monkeypatch):
    _attention_with(monkeypatch, lambda w, r: (w, False))


def _window_off_by_one(monkeypatch):
    _attention_with(monkeypatch, lambda w, r: (w + 1 if w else 0, r))


def _window_on_the_full_layer(monkeypatch):
    _attention_with(monkeypatch, lambda w, r: (w or 8, r))


FAULTS = [_router_reads_the_post_attention_norm,
          _router_reads_the_raw_residual,
          _softmax_before_the_top_k_unrenormalised, _silu_for_relu,
          _rope_on_the_full_layer, _rope_missing_on_a_window_layer,
          _window_off_by_one, _window_on_the_full_layer]


@pytest.mark.parametrize("fault", FAULTS)
def test_the_tolerance_catches(fault, monkeypatch, toy_run):
    """Each structural fault, planted in the reference, moves the loss or
    some gradient by more than ten times its tolerance."""
    cfg, feed, run = toy_run
    fault(monkeypatch)
    want, gref = _ref_value_and_grad(cfg, run["params"], feed)
    gref = _as_program_grads(gref)
    worst = max(
        np.abs(np.asarray(run["grads"][n], np.float64)
               - np.asarray(gref[n], np.float64)).max()
        / max(np.abs(np.asarray(gref[n])).max(), 1e-12) for n in gref)
    loss_off = abs(run["loss"] - float(want)) / float(want)
    assert worst > 10 * GRAD_TOL or loss_off > 10 * LOSS_TOL, \
        (fault.__name__, worst, loss_off)


def test_the_toy_run_itself_is_within_the_tolerances(toy_run):
    cfg, feed, run = toy_run
    _against_the_reference(cfg, feed, run, LOSS_TOL, GRAD_TOL)


def test_the_steps_gradient_check_catches_a_group_of_seven_summed_wrongly(
        toy_run):
    """What ``check_first_loss`` decides the timed step's backward by, on
    the toy program's own gradients: every leaf within float32's reach of
    ``jax.grad`` of the reference; with one layer's dK and dV at six
    sevenths (a sum over a group's query heads that lost one of seven) that
    leaf is a seventh off and named; with the router's cotangent in the
    rows' place the norms that feed them move."""
    cfg, feed, run = toy_run
    grads = run["grads"]
    _, g_ref = adapter.reference_gradient(ref, run["params"], feed, cfg, 8)

    def off_of(g):
        return adapter.gradient_difference(
            g_ref, adapter.reference_params(g.__getitem__, cfg))

    off = off_of(grads)
    assert off["all"] <= max(off[k][1] for k in ("rest", "experts",
                                                  "router")) < 1e-4, off
    assert all(off[k][0] <= off[k][1] for k in adapter.DECIDES)
    assert "router_w" in off["router"][2] and off["experts"][2].endswith(
        ("['gate_w']", "['up_w']", "['down_w']", "['ln2_w']"))
    assert adapter.DECIDES == {"rest": 1, "experts": 0, "router": 0}
    dq = cfg.n_head * cfg.d_head
    qkv = np.array(grads["dec_2.attn.qkv.w"])
    qkv[:, dq:] *= 6.0 / 7.0
    off = off_of(dict(grads, **{"dec_2.attn.qkv.w": qkv}))
    assert off["rest"][1] == pytest.approx(1.0 / 7.0, rel=1e-3), off
    assert off["rest"][2] in ("['blocks'][2]['wk']", "['blocks'][2]['wv']")
    assert off["rest"][0] < off["rest"][1]      # together it would hide
    assert off["router"][1] < 1e-4 and off["experts"][1] < 1e-4


def test_the_reference_in_bf16_is_told_from_float32(toy_run):
    cfg, feed, run = toy_run
    low = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16),
                                 run["params"])
    kw = adapter.reference_kw(cfg, q_block=8)
    ids = jnp.asarray(feed["src_ids"][:1])
    lab = jnp.asarray(feed["lm_label"][:1])
    a = ref.sequence_sums(run["params"], ids, lab, **kw)["hidden"]
    b = ref.sequence_sums(low, ids, lab, **kw)["hidden"].astype(jnp.float32)
    assert float(jnp.linalg.norm(a - b) / jnp.linalg.norm(a)) > 1e-3


# -- what the step is made of -------------------------------------------------

def test_the_block_names_its_window_its_groups_and_its_attention_tag(toy_run):
    """The built program: ``moe_ffn`` reads the input norm as ``RouterX`` and
    the post-attention norm as ``X`` with ``act=relu``; three of four flash
    ops carry the window and all of them 14 heads over 2; the attention's
    dense ops ride under the ``attn`` tag, their grads too."""
    from paddle_tpu.framework import executor as E
    cfg, _, run = toy_run
    ops = run["main"].global_block().ops
    moe = [op for op in ops if op.type == "moe_ffn"]
    assert len(moe) == 4
    for i, op in enumerate(moe):
        assert op.attrs["act"] == "relu" and op.attrs["norm_topk_prob"]
        assert op.attrs["expert_offset"] == 2
        ln1 = next(o for o in ops if o.type == "rms_norm"
                   and o.input("Scale") == [f"dec_{i}.ln1.w"])
        ln2 = next(o for o in ops if o.type == "rms_norm"
                   and o.input("Scale") == [f"dec_{i}.ln2.w"])
        assert op.input("RouterX") == ln1.output("Y")
        assert op.input("X") == ln2.output("Y")
    grad = [op for op in ops if op.type == "moe_ffn_grad"]
    assert len(grad) == 4 and all(
        op.output("IG$RouterX") and op.output("IG$RouterX")
        != op.output("IG$X") for op in grad)
    flash = [op for op in ops if op.type == "flash_attention"]
    assert [int(op.attrs.get("window") or 0) for op in flash] == [0, 8, 8, 8]
    assert sum(op.type == "rope" for op in ops) == 6     # Q and K, 3 layers
    scoped = {E.op_scope(op) for op in ops}
    assert "pt.fwd/flash_attention/attn" in scoped
    assert "pt.bwd/mul_grad/attn" in scoped and "pt.fwd/moe_ffn" in scoped


def test_flash_lowerings_count_groups_of_seven(toy_run):
    from paddle_tpu.ops.attention_ops import FLASH_LOWERINGS_CTR as ctr
    assert ctr.value(window="8", kv_groups="7", impl="jax",
                     widths="8/8") >= 3
    assert ctr.value(window="none", kv_groups="7", impl="jax",
                     widths="8/8") >= 1


def test_the_defaults_are_the_published_config():
    cfg = T.SmallThinkerConfig()
    assert (cfg.vocab_size, cfg.d_model, cfg.n_layer, cfg.n_head,
            cfg.n_kv_head, cfg.d_head, cfg.d_expert, cfg.n_experts,
            cfg.top_k, cfg.window, cfg.rms_eps, cfg.rope_theta) == (
        151936, 2560, 52, 28, 4, 128, 768, 64, 6, 4096, 1e-6, 1.5e6)
    assert cfg.sliding_window_layout == [0, 1, 1, 1] * 13
    assert cfg.rope_layout == cfg.sliding_window_layout
    assert cfg.n_held == 64 and cfg.expert_offset == 0
