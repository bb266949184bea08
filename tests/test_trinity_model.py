"""Trinity-Mini's whole model at a toy size on the CPU against ``jax.grad`` of
the plain float32 reference (``benchmark/reference/trinity_mini.py``): loss
and every parameter's gradient, the structural faults the tolerances catch,
the check of the timed step's gradient, the bf16 control.  The ops' own
tests, the tolerances' reasons and the toy configuration are in
``tests/test_trinity.py``; a file of its own so that the two run on two
workers.
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

import test_olmoe as olmoe_test  # noqa: E402
from benchmark.models import trinity_mini as adapter  # noqa: E402
from benchmark.reference import trinity_mini as ref  # noqa: E402
from paddle_tpu.framework.core import grad_var_name  # noqa: E402
_close = olmoe_test._close
LOSS_TOL, GRAD_TOL = olmoe_test.LOSS_TOL, olmoe_test.GRAD_TOL
from test_trinity import _batch, _model, toy_cfg  # noqa: E402


# -- the whole model ----------------------------------------------------------------

def _ref_params(scope, cfg):
    return adapter.reference_params(
        lambda n: jnp.asarray(scope.find_var(n), jnp.float32), cfg)


def _ref_loss_fn(cfg, module=ref):
    kw = adapter.reference_kw(cfg, q_block=16)
    return lambda p, ids, lab: module.loss(p, ids, lab, **kw)


def _ref_value_and_grad(cfg, params, feed):
    """Jitted: eager, the reference's thousand small ops cost ten seconds."""
    return jax.jit(jax.value_and_grad(_ref_loss_fn(cfg)))(
        params, jnp.asarray(feed["src_ids"]), jnp.asarray(feed["lm_label"]))


def _as_program_grads(gref, cfg):
    """Reference-layout gradients under the program's parameter names."""
    out = {"word_embedding": gref["wte"], "final_norm.w":
           gref["final_norm_w"], "lm_out.w": gref["head_w"]}
    for i, blk in enumerate(gref["blocks"]):
        p = f"dec_{i}"
        out[f"{p}.attn.qkv.w"] = jnp.concatenate(
            [blk["wq"], blk["wk"], blk["wv"], blk["wg"]], axis=1)
        pairs = [("attn.q_norm.w", "q_norm_w"), ("attn.k_norm.w", "k_norm_w"),
                 ("attn.out.w", "wo")] + [(f"ln{n}.w", f"ln{n}_w")
                                         for n in (1, 2, 3, 4)]
        if "ffn_gate" in blk:
            out[f"{p}.ffn.gate_up.w"] = jnp.concatenate(
                [blk["ffn_gate"], blk["ffn_up"]], axis=1)
            pairs.append(("ffn.down.w", "ffn_down"))
        else:
            out[f"{p}.shared.gate_up.w"] = jnp.concatenate(
                [blk["shared_gate"], blk["shared_up"]], axis=1)
            pairs += [("shared.down.w", "shared_down"),
                      ("moe.router.w", "router_w"), ("moe.gate.w", "gate_w"),
                      ("moe.up.w", "up_w"), ("moe.down.w", "down_w")]
        for name, key in pairs:
            out[f"{p}.{name}"] = blk[key]
    return out


def _program_grads(scope, main, exe, loss, feed):
    """Loss and the gradient of every parameter a gradient trains (the
    selection bias is a parameter that none does)."""
    names = [p.name for p in main.all_parameters()
             if not p.name.endswith(".select_bias")]
    got = exe.run(main, feed=feed, scope=scope,
                  fetch_list=[loss.name] + [grad_var_name(n) for n in names])
    return float(np.asarray(got[0])), dict(zip(names, map(np.asarray,
                                                          got[1:])))


@pytest.fixture(scope="module")
def toy_run():
    """One dense + two expert layers (sliding, sliding, full), dense head:
    the program's loss and gradients on 2 x 24 tokens, once."""
    cfg = toy_cfg()
    scope, main, exe, parts, loss = _model(cfg, 24)
    feed = _batch(cfg, 2, 24)
    got, grads = _program_grads(scope, main, exe, loss, feed)
    loads = exe.run(main, feed=feed, scope=scope,
                    fetch_list=[v.name for v in parts["expert_load"]])
    return cfg, _ref_params(scope, cfg), feed, got, grads, loads


def test_loss_and_every_parameters_gradient_match_the_reference(toy_run):
    cfg, params, feed, got, grads, loads = toy_run
    want, gref = _ref_value_and_grad(cfg, params, feed)
    assert abs(got - float(want)) / float(want) <= LOSS_TOL, (got, want)
    gref = _as_program_grads(gref, cfg)
    assert set(gref) == set(grads)
    for name in sorted(grads):
        _close(grads[name], gref[name], GRAD_TOL, f"d loss / d {name}")
    assert len(loads) == 2 and all(
        np.asarray(v).shape == (8,) and int(np.asarray(v).sum()) ==
        2 * 24 * cfg.top_k for v in loads)


def test_the_fused_head_and_amp_step_stay_near_the_reference():
    """The timed path's own pieces at toy widths: the fused head (bf16
    products) within its limits, and the selection bias is no gradient's
    and no optimizer's."""
    cfg = toy_cfg()
    scope, main, exe, _, loss = _model(cfg, 24, fused_head=True)
    feed = _batch(cfg, 2, 24)
    got, grads = _program_grads(scope, main, exe, loss, feed)
    want, gref = _ref_value_and_grad(cfg, _ref_params(scope, cfg), feed)
    assert abs(got - float(want)) / float(want) <= olmoe_test.FUSED_LOSS_TOL
    gref = _as_program_grads(gref, cfg)
    for name in sorted(grads):
        _close(grads[name], gref[name], olmoe_test.FUSED_GRAD_TOL, name)
    bias = [p for p in main.all_parameters()
            if p.name.endswith(".select_bias")]
    assert len(bias) == 2 and not any(p.trainable for p in bias)


def _window_off_by_one(monkeypatch):
    plain = ref.attention

    def attention(a, blk, sliding, n_head, n_kv_head, d_head, eps, theta,
                  window, q_block):
        return plain(a, blk, sliding, n_head, n_kv_head, d_head, eps, theta,
                     window + 1, q_block)
    monkeypatch.setattr(ref, "attention", attention)


def _rope_on_the_full_layer(monkeypatch):
    plain = ref.attention

    def attention(a, blk, sliding, n_head, n_kv_head, d_head, eps, theta,
                  window, q_block):
        if sliding:
            return plain(a, blk, True, n_head, n_kv_head, d_head, eps, theta,
                         window, q_block)
        rot = dict(blk)              # rotate, and keep the full causal mask
        return plain(a, rot, True, n_head, n_kv_head, d_head, eps, theta,
                     1 << 30, q_block)
    monkeypatch.setattr(ref, "attention", attention)


def _gate_dropped(monkeypatch):
    plain = ref.attention

    def attention(a, blk, *rest):
        open_gate = dict(blk, wg=jnp.zeros_like(blk["wg"]))
        return 2.0 * plain(a, open_gate, *rest)       # sigmoid(0) = 1/2
    monkeypatch.setattr(ref, "attention", attention)


def _renormalisation_dropped(monkeypatch):
    def route(m, blk, top_k, route_scale):
        s = jax.nn.sigmoid(m @ blk["router_w"])
        _, top_e = jax.lax.top_k(s + blk["select_bias"], top_k)
        chosen = jnp.sum(jax.nn.one_hot(top_e, s.shape[-1], dtype=s.dtype),
                         axis=1)
        return s * chosen * route_scale, top_e
    monkeypatch.setattr(ref, "route", route)


def _scale_dropped(monkeypatch):
    plain = ref.route
    monkeypatch.setattr(ref, "route",
                        lambda m, blk, k, scale: plain(m, blk, k, 1.0))


def _bias_reaches_the_weights(monkeypatch):
    def route(m, blk, top_k, route_scale):
        s = jax.nn.sigmoid(m @ blk["router_w"]) + blk["select_bias"]
        _, top_e = jax.lax.top_k(s, top_k)
        chosen = jnp.sum(jax.nn.one_hot(top_e, s.shape[-1], dtype=s.dtype),
                         axis=1)
        kept = s * chosen
        return kept / (kept.sum(-1, keepdims=True) + 1e-20) * route_scale, \
            top_e
    monkeypatch.setattr(ref, "route", route)


def _qk_norm_over_the_whole_projection(monkeypatch):
    plain = ref.rms_norm

    def rms_norm(z, w, eps):
        if z.ndim == 3 and w.ndim == 1 and w.shape[0] == z.shape[-1] \
                and z.shape[1] in (4, 2):            # [T, heads, dh]
            flat = z.reshape(z.shape[0], -1)
            return plain(flat, jnp.tile(w, z.shape[1]), eps).reshape(z.shape)
        return plain(z, w, eps)
    monkeypatch.setattr(ref, "rms_norm", rms_norm)


@pytest.mark.parametrize("fault", [
    _window_off_by_one, _rope_on_the_full_layer, _gate_dropped,
    _renormalisation_dropped, _qk_norm_over_the_whole_projection,
    # each fault compiles the reference's gradient again (5 s): the two
    # that ISSUE 32 does not name run with the slow tests
    pytest.param(_scale_dropped, marks=pytest.mark.slow),
    pytest.param(_bias_reaches_the_weights, marks=pytest.mark.slow)])
def test_the_tolerance_catches(fault, monkeypatch, toy_run):
    """Each structural fault, planted in the reference, moves the loss or
    some gradient by more than ten times its tolerance."""
    cfg, params, feed, got, grads, _ = toy_run
    fault(monkeypatch)
    want, gref = _ref_value_and_grad(cfg, params, feed)
    gref = _as_program_grads(gref, cfg)
    worst = max(
        np.abs(np.asarray(grads[n], np.float64)
               - np.asarray(gref[n], np.float64)).max()
        / max(np.abs(np.asarray(gref[n])).max(), 1e-12) for n in grads)
    loss_off = abs(got - float(want)) / float(want)
    assert worst > 10 * GRAD_TOL or loss_off > 10 * LOSS_TOL, \
        (fault.__name__, worst, loss_off)


def test_the_steps_gradient_check_catches_a_group_sum_that_lost_half(toy_run):
    """What ``check_first_loss`` decides the timed step's backward by, on
    the toy program's own gradients: every leaf within float32's reach of
    ``jax.grad`` of the reference; with one layer's dK at half its size (a
    sum over the query heads of a group that lost a head of two) that leaf
    is 0.5 off and named."""
    cfg, params, feed, _, grads, _ = toy_run
    _, g_ref = adapter.reference_gradient(ref, params, feed, cfg, 16)

    def in_reference_layout(g):
        return adapter.reference_params(
            lambda n: np.asarray(g[n]) if n in g
            else np.zeros(cfg.n_experts, np.float32), cfg)

    off = adapter.gradient_difference(g_ref, in_reference_layout(grads))
    assert off["all"] <= max(off[k][0] for k in ("rest", "experts",
                                                  "router")) < 1e-4, off
    assert "router_w" in off["router"][1] and off["experts"][1].endswith(
        ("['gate_w']", "['up_w']", "['down_w']"))
    dq, dkv = cfg.n_head * cfg.d_head, cfg.n_kv_head * cfg.d_head
    qkv = np.array(grads["dec_1.attn.qkv.w"])
    qkv[:, dq:dq + dkv] *= 0.5
    off = adapter.gradient_difference(
        g_ref, in_reference_layout(dict(grads, **{"dec_1.attn.qkv.w": qkv})))
    assert off["rest"][0] == pytest.approx(0.5, rel=1e-3), off
    assert off["rest"][1] == "['blocks'][1]['wk']", off
    assert off["router"][0] < 1e-4 and off["experts"][0] < 1e-4


def test_the_reference_in_bf16_is_told_from_float32(toy_run):
    """The precision control at toy widths: the same reference with every
    parameter, and so every activation, in bf16 is further from the float32
    one than any gradient tolerance above allows."""
    cfg, params, feed, *_ = toy_run
    low = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), params)
    kw = adapter.reference_kw(cfg, q_block=16)
    ids = jnp.asarray(feed["src_ids"][:1])
    lab = jnp.asarray(feed["lm_label"][:1])
    a = ref.sequence_sums(params, ids, lab, **kw)["hidden"]
    b = ref.sequence_sums(low, ids, lab, **kw)["hidden"].astype(jnp.float32)
    off = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(a))
    assert off > 1e-3, off


