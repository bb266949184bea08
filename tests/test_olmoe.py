"""OLMoE on the training path (``rms_norm``, ``rope``, ``moe_ffn`` and
``models.transformer.build_olmoe_pretrain``) against the plain float32
reference of ``benchmark/reference/olmoe_1b_7b.py``, at a toy size on the
CPU: each new op and its gradient against the reference's piece, then the
whole model's loss and every parameter's gradient against ``jax.grad`` of the
reference's loss.

Tolerances.  Program and reference are both float32 on the CPU and compute
the same function by different routes (sorted rows and grouped matmuls
against every expert over every token under a mask; a blockwise attention
scan against a full score matrix), so with the dense float32 head they
differ by summation order alone: measured 0 on the loss and under 1e-6 of
each gradient's largest entry, held to 1e-5 and 1e-4.  The fused head
(``fused_lm_head_ce``) multiplies in bf16 whatever the program's dtype:
measured 1.2e-4 on the loss and up to 4.4e-3 on a gradient, held to 5e-4 and
2e-2.  Every structural error this file plants (a renormalised top-k, a
per-head QK-norm, a RoPE that pairs ``i`` with ``i + 1``, a dropped token)
moves some gradient of the dense-head model by more than ten times its
tolerance (``test_the_tolerance_catches``).
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

import paddle_tpu as pt  # noqa: E402
from benchmark.models import olmoe_1b_7b as adapter  # noqa: E402
from benchmark.reference import olmoe_1b_7b as ref  # noqa: E402
from paddle_tpu import layers, monitor  # noqa: E402
from paddle_tpu.framework import (Executor, Program, Scope,  # noqa: E402
                                  program_guard, scope_guard)
from paddle_tpu.framework.backward import append_backward  # noqa: E402
from paddle_tpu.framework.core import grad_var_name  # noqa: E402
from paddle_tpu.models import transformer as T  # noqa: E402

LOSS_TOL, GRAD_TOL = 1e-5, 1e-4
FUSED_LOSS_TOL, FUSED_GRAD_TOL = 5e-4, 2e-2


def toy_cfg(**kw):
    kw = dict(dict(vocab_size=128, d_model=64, n_layer=2, n_head=4,
                   d_expert=32, n_experts=8, top_k=2), **kw)
    return T.OlmoeConfig(**kw)


def _run_op(build, feed, wrt):
    """Build one op's program, fetch its outputs and d(sum of squares of the
    first output)/d(wrt)."""
    scope = Scope()
    with scope_guard(scope), program_guard(Program(), Program()):
        outs, params = build()
        loss = layers.reduce_sum(layers.square(outs[0]))
        append_backward(loss)
        exe = Executor()
        exe.run(pt.default_startup_program(), scope=scope, seed=5)
        for name, value in params.items():
            scope.set_var(name, jnp.asarray(value))
        fetched = exe.run(
            feed=feed, scope=scope,
            fetch_list=[o.name for o in outs] +
            [grad_var_name(n) for n in wrt])
    return [np.asarray(v) for v in fetched]


def _close(got, want, tol, what=""):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = max(np.abs(want).max(), 1e-12)
    err = np.abs(got - want).max() / scale
    assert err <= tol, f"{what}: {err:.3e} of the largest entry > {tol}"


# -- the ops ----------------------------------------------------------------------

def test_rms_norm_and_its_gradient_match_the_reference():
    rng = np.random.RandomState(0)
    x = rng.randn(2, 5, 16).astype(np.float32)
    w = rng.uniform(0.5, 1.5, 16).astype(np.float32)

    def build():
        xv = layers.data("x", shape=[5, 16], dtype="float32",
                         stop_gradient=False)
        y = layers.rms_norm(xv, begin_norm_axis=2, epsilon=1e-5,
                            param_attr=pt.ParamAttr(name="w"))
        return [y], {"w": w}

    y, gx, gw = _run_op(build, {"x": x}, ["x", "w"])
    f = lambda x, w: jnp.sum(ref.rms_norm(x, w, 1e-5) ** 2)  # noqa: E731
    _close(y, ref.rms_norm(x, w, 1e-5), 1e-6, "rms_norm")
    rx, rw = jax.grad(f, argnums=(0, 1))(x, w)
    _close(gx, rx, 1e-5, "d rms_norm / d x")
    _close(gw, rw, 1e-5, "d rms_norm / d w")


def test_rope_and_its_gradient_match_the_reference():
    rng = np.random.RandomState(1)
    x = rng.randn(2, 7, 3 * 8).astype(np.float32)

    def build():
        xv = layers.data("x", shape=[7, 24], dtype="float32",
                         stop_gradient=False)
        return [layers.rope(xv, head_dim=8, theta=10000.0)], {}

    y, gx = _run_op(build, {"x": x}, ["x"])
    want = lambda x: ref.rope(  # noqa: E731
        jnp.asarray(x).reshape(2, 7, 3, 8), 10000.0).reshape(2, 7, 24)
    _close(y, want(x), 1e-6, "rope")
    _close(gx, jax.grad(lambda x: jnp.sum(want(x) ** 2))(x), 1e-5,
           "d rope / d x")
    # position 0 is the identity, and the pairing is i with i + 4, not i + 1
    np.testing.assert_allclose(y[:, 0], x[:, 0], atol=1e-6)
    ang = 3.0 * 10000.0 ** (-2.0 * 1 / 8)
    np.testing.assert_allclose(
        y[0, 3, 1], x[0, 3, 1] * np.cos(ang) - x[0, 3, 5] * np.sin(ang),
        rtol=1e-5)


def _moe_weights(rng, d, e, f, std=0.3):
    return {"moe.router.w": rng.randn(d, e).astype(np.float32) * std,
            "moe.gate.w": rng.randn(e, d, f).astype(np.float32) * std,
            "moe.up.w": rng.randn(e, d, f).astype(np.float32) * std,
            "moe.down.w": rng.randn(e, f, d).astype(np.float32) * std}


def _moe_blk(w):
    return {"router_w": w["moe.router.w"], "gate_w": w["moe.gate.w"],
            "up_w": w["moe.up.w"], "down_w": w["moe.down.w"]}


def _run_moe(x, w, e, k, f, renorm=False):
    t, d = x.shape[1:]

    def build():
        xv = layers.data("x", shape=[t, d], dtype="float32",
                         stop_gradient=False)
        out = layers.moe_ffn(xv, e, k, f, norm_topk_prob=renorm)
        return list(out), w

    names = ["x"] + sorted(w)
    got = _run_op(build, {"x": x}, names)
    return got[:4], dict(zip(names, got[4:]))


def test_moe_ffn_and_its_gradients_match_the_dense_masked_reference():
    rng = np.random.RandomState(2)
    b, t, d, e, k, f = 2, 12, 16, 8, 2, 24
    x = rng.randn(b, t, d).astype(np.float32)
    w = _moe_weights(rng, d, e, f)
    (out, lb, z, load), grads = _run_moe(x, w, e, k, f)

    def dense(x, blk):
        o, logits, p, top = ref.moe(x.reshape(b * t, d), blk, k)
        return o, logits, p, top

    o, logits, p, top = dense(jnp.asarray(x), _moe_blk(w))
    _close(out.reshape(b * t, d), o, 1e-5, "moe_ffn Out")
    counts = np.bincount(np.asarray(top).reshape(-1), minlength=e)
    np.testing.assert_array_equal(load, counts)
    assert int(load.sum()) == b * t * k          # nobody dropped
    _close(lb, e * np.sum(counts / (b * t) * np.asarray(p).mean(0)), 1e-5,
           "LbLoss")
    _close(z, np.mean(np.asarray(jax.nn.logsumexp(logits, -1)) ** 2), 1e-5,
           "ZLoss")

    def f_ref(x, blk):
        return jnp.sum(dense(x, blk)[0] ** 2)
    gx, gblk = jax.grad(f_ref, argnums=(0, 1))(jnp.asarray(x), _moe_blk(w))
    _close(grads["x"], gx, 1e-4, "d Out / d x")
    for name, key in (("moe.router.w", "router_w"), ("moe.gate.w", "gate_w"),
                      ("moe.up.w", "up_w"), ("moe.down.w", "down_w")):
        _close(grads[name], gblk[key], 1e-4, f"d Out / d {name}")


def test_a_skewed_router_still_drops_nothing():
    """Every token's first choice is expert 0 (positive rows, a router whose
    column 0 is large): 12.5 % of the experts get over half the rows, and the
    result still equals the reference's, which has no notion of capacity."""
    rng = np.random.RandomState(3)
    b, t, d, e, k, f = 2, 16, 16, 8, 2, 24
    x = rng.uniform(0.5, 1.5, (b, t, d)).astype(np.float32)
    w = _moe_weights(rng, d, e, f, std=0.1)
    w["moe.router.w"][:, 0] = 1.0
    (out, _, _, load), grads = _run_moe(x, w, e, k, f)
    assert load[0] == b * t and int(load.sum()) == b * t * k
    o = ref.moe(jnp.asarray(x).reshape(b * t, d), _moe_blk(w), k)[0]
    _close(out.reshape(b * t, d), o, 1e-5, "skewed moe_ffn")
    gw = jax.grad(lambda blk: jnp.sum(ref.moe(
        jnp.asarray(x).reshape(b * t, d), blk, k)[0] ** 2))(_moe_blk(w))
    _close(grads["moe.down.w"], gw["down_w"], 1e-4, "skewed d / d down")


def test_moe_lowerings_are_counted_once_per_compile():
    from paddle_tpu.ops.moe_ops import MOE_LOWERINGS_CTR
    rng = np.random.RandomState(4)
    labels = dict(impl="ragged_dot", experts="4", top_k="2", held="4",
                  score_func="softmax", ladder="")
    before = MOE_LOWERINGS_CTR.value(**labels)
    x = rng.randn(1, 6, 8).astype(np.float32)
    _run_moe(x, _moe_weights(rng, 8, 4, 8), 4, 2, 8)   # forward + its grad op
    assert MOE_LOWERINGS_CTR.value(**labels) == before + 1
    assert any(m["name"] == "paddle_tpu_moe_lowerings_total"
               for m in monitor.REGISTRY.collect())


# -- the whole model ------------------------------------------------------------

def _model(cfg, seq, amp=False, seed=3, fused_head=False):
    scope, main, startup = Scope(), Program(), Program()
    with scope_guard(scope), program_guard(main, startup):
        _, parts, loss = T.build_olmoe_pretrain(cfg, seq,
                                                fused_head=fused_head)
        append_backward(loss)
        if amp:
            pt.amp.enable(main)
        exe = Executor()
        exe.run(startup, scope=scope, seed=seed)
    # norm scales start at 1 and would hide a norm over the wrong axis
    rng = np.random.RandomState(seed)
    for p in main.all_parameters():
        if p.name.endswith(("ln1.w", "ln2.w", "_norm.w", "final_norm.w")):
            scope.set_var(p.name, jnp.asarray(
                rng.uniform(0.5, 1.5, p.shape).astype(np.float32)))
    return scope, main, exe, parts, loss


def _batch(cfg, b, seq, seed=0):
    return adapter.make_batch(np.random.RandomState(seed), cfg, b, seq)


def _ref_params(scope, cfg):
    return adapter.reference_params(
        lambda n: jnp.asarray(scope.find_var(n), jnp.float32), cfg)


def _ref_loss_fn(cfg, module=ref):
    kw = dict(n_head=cfg.n_head, top_k=cfg.top_k, eps=cfg.rms_eps,
              theta=cfg.rope_theta, lb_coef=cfg.lb_coef, z_coef=cfg.z_coef)
    return lambda p, ids, lab: module.loss(p, ids, lab, **kw)


def _program_grads(scope, main, exe, loss, feed):
    names = [p.name for p in main.all_parameters()]
    got = exe.run(main, feed=feed, scope=scope,
                  fetch_list=[loss.name] + [grad_var_name(n) for n in names])
    return float(np.asarray(got[0])), dict(zip(names, map(np.asarray,
                                                          got[1:])))


def _as_program_grads(gref, cfg):
    """Reference-layout gradients under the program's parameter names."""
    out = {"word_embedding": gref["wte"], "final_norm.w":
           gref["final_norm_w"], "lm_out.w": gref["head_w"]}
    for i, blk in enumerate(gref["blocks"]):
        p = f"dec_{i}"
        out[f"{p}.attn.qkv.w"] = jnp.concatenate(
            [blk["wq"], blk["wk"], blk["wv"]], axis=1)
        for name, key in (("ln1.w", "ln1_w"), ("attn.q_norm.w", "q_norm_w"),
                          ("attn.k_norm.w", "k_norm_w"),
                          ("attn.out.w", "wo"), ("ln2.w", "ln2_w"),
                          ("moe.router.w", "router_w"),
                          ("moe.gate.w", "gate_w"), ("moe.up.w", "up_w"),
                          ("moe.down.w", "down_w")):
            out[f"{p}.{name}"] = blk[key]
    return out


@pytest.mark.parametrize("fused_head", [False, True])
def test_loss_and_every_parameters_gradient_match_the_reference(fused_head):
    cfg = toy_cfg()
    scope, main, exe, parts, loss = _model(cfg, 32, fused_head=fused_head)
    feed = _batch(cfg, 2, 32)
    params = _ref_params(scope, cfg)
    got, grads = _program_grads(scope, main, exe, loss, feed)
    f = _ref_loss_fn(cfg)
    want, gref = jax.value_and_grad(f)(params, feed["src_ids"],
                                       feed["lm_label"])
    loss_tol, grad_tol = (FUSED_LOSS_TOL, FUSED_GRAD_TOL) if fused_head \
        else (LOSS_TOL, GRAD_TOL)
    assert abs(got - float(want)) / float(want) <= loss_tol, (got, want)
    gref = _as_program_grads(gref, cfg)
    assert set(gref) == set(grads)
    for name in sorted(grads):
        _close(grads[name], gref[name], grad_tol, f"d loss / d {name}")
    loads = exe.run(main, feed=feed, scope=scope,
                    fetch_list=[v.name for v in parts["expert_load"]])
    assert all(int(np.asarray(v).sum()) == 2 * 32 * cfg.top_k for v in loads)


def _per_head_qk_norm(monkeypatch):
    def attention(n, blk, n_head, eps, theta):
        b, t, d = n.shape
        dh = d // n_head
        split = lambda z: z.reshape(b, t, n_head, dh)  # noqa: E731
        q = ref.rms_norm(split(n @ blk["wq"]),
                         blk["q_norm_w"].reshape(n_head, dh), eps)
        k = ref.rms_norm(split(n @ blk["wk"]),
                         blk["k_norm_w"].reshape(n_head, dh), eps)
        q, k = ref.rope(q, theta), ref.rope(k, theta)
        v = split(n @ blk["wv"])
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(dh))
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None, None], s,
                      -jnp.inf)
        ctx = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
        return ctx.reshape(b, t, d) @ blk["wo"]
    monkeypatch.setattr(ref, "attention", attention)


def _rope_pairs_neighbours(monkeypatch):
    def rope(x, theta):
        t, dh = x.shape[1], x.shape[3]
        freq = theta ** (-2.0 * jnp.arange(dh // 2, dtype=jnp.float32) / dh)
        ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]
        cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                         axis=-1).reshape(x.shape)
    monkeypatch.setattr(ref, "rope", rope)


def _drops_a_token(monkeypatch):
    plain = ref.moe

    def moe(m, blk, top_k):
        out, logits, p, top_e = plain(m, blk, top_k)
        return out.at[5].set(0.0), logits, p, top_e   # one token over capacity
    monkeypatch.setattr(ref, "moe", moe)


@pytest.mark.parametrize("fault", ["renormalised_topk", "per_head_qk_norm",
                                   "rope_pairs_i_with_i_plus_1",
                                   "a_dropped_token"])
def test_the_tolerance_catches(fault, monkeypatch):
    """Each planted error moves the loss or a gradient past the tolerances
    of the test above (the program is the right one except in the first
    case, where it renormalises and the reference does not)."""
    cfg = toy_cfg(norm_topk_prob=(fault == "renormalised_topk"))
    scope, main, exe, _, loss = _model(cfg, 32)
    feed = _batch(cfg, 2, 32)
    got, grads = _program_grads(scope, main, exe, loss, feed)
    {"renormalised_topk": lambda m: None,
     "per_head_qk_norm": _per_head_qk_norm,
     "rope_pairs_i_with_i_plus_1": _rope_pairs_neighbours,
     "a_dropped_token": _drops_a_token}[fault](monkeypatch)
    want, gref = jax.value_and_grad(_ref_loss_fn(cfg))(
        _ref_params(scope, cfg), feed["src_ids"], feed["lm_label"])
    gref = _as_program_grads(gref, cfg)
    loss_off = abs(got - float(want)) / float(want)
    grad_off = max(
        np.abs(grads[n] - np.asarray(gref[n])).max() /
        max(np.abs(np.asarray(gref[n])).max(), 1e-12) for n in grads)
    assert loss_off > LOSS_TOL or grad_off > GRAD_TOL, (loss_off, grad_off)
    assert grad_off > 10 * GRAD_TOL, (loss_off, grad_off)


def test_amp_router_chooses_as_the_float32_reference_except_at_near_ties():
    """Under AMP the residual stream is bf16 but the router (logits, softmax,
    top-k) is float32 at full precision: a token's experts differ from the
    float32 reference's only where the reference's k-th and (k+1)-th
    probabilities are within bf16's reach of each other."""
    cfg = toy_cfg(n_layer=1)
    scope, main, exe, parts, loss = _model(cfg, 32, amp=True)
    feed = _batch(cfg, 4, 32, seed=9)
    top_var = next(op.outputs["TopExperts"][0]
                   for op in main.global_block().ops if op.type == "moe_ffn")
    got, top, load = exe.run(main, feed=feed, scope=scope, fetch_list=[
        loss.name, top_var, parts["expert_load"][0].name])
    kw = dict(n_head=cfg.n_head, top_k=cfg.top_k, eps=cfg.rms_eps,
              theta=cfg.rope_theta)
    params = _ref_params(scope, cfg)
    sums = ref.batch_sums(params, feed["src_ids"], feed["lm_label"], **kw)
    want = ref.loss_of_sums(sums, cfg.lb_coef, cfg.z_coef)["loss"]
    assert abs(float(np.asarray(got)) - float(want)) / float(want) < 2e-2
    mine = np.sort(np.asarray(top).reshape(-1, cfg.top_k), -1)
    theirs = np.sort(np.asarray(sums["top_e"][0]), -1)
    differ = np.any(mine != theirs, axis=-1)
    assert int(np.asarray(load).sum()) == 4 * 32 * cfg.top_k
    # the reference's margin between the last kept and the first dropped p
    x = params["wte"][feed["src_ids"]]
    blk = params["blocks"][0]
    with jax.default_matmul_precision("highest"):
        h = x + ref.attention(ref.rms_norm(x, blk["ln1_w"], cfg.rms_eps),
                              blk, cfg.n_head, cfg.rms_eps, cfg.rope_theta)
        m = ref.rms_norm(h, blk["ln2_w"], cfg.rms_eps).reshape(-1, 64)
        p = np.sort(np.asarray(jax.nn.softmax(m @ blk["router_w"], -1)), -1)
    margin = (p[:, -cfg.top_k] - p[:, -cfg.top_k - 1]) / p[:, -cfg.top_k]
    assert differ.sum() <= 0.1 * len(differ), differ.sum()
    assert np.all(margin[differ] < 0.05), margin[differ]


def test_shared_kv_heads_equal_repeated_kv_weights():
    """``n_kv_head`` < ``n_head``: each K/V head serves ``n_head //
    n_kv_head`` query heads, which is the full-head model whose K and V
    weight columns are those heads repeated."""
    rng = np.random.RandomState(6)
    d, h, kv, t = 32, 4, 2, 8
    dh = d // h
    x = rng.randn(2, t, d).astype(np.float32)

    def run(n_kv, weights=None):
        scope = Scope()
        with scope_guard(scope), program_guard(Program(), Program()):
            xv = layers.data("x", shape=[t, d], dtype="float32")
            out = T.multi_head_attention(xv, xv, xv, d, h, causal=True,
                                         bias=False, n_kv_head=n_kv,
                                         param_prefix="a")
            exe = Executor()
            exe.run(pt.default_startup_program(), scope=scope, seed=2)
            for name, value in (weights or {}).items():
                scope.set_var(name, jnp.asarray(value))
            got, = exe.run(feed={"x": x}, fetch_list=[out.name], scope=scope)
            return np.asarray(got), {
                n: np.asarray(scope.find_var(n))
                for n in ("a.qkv.w", "a.out.w")}

    shared, w = run(kv)
    assert w["a.qkv.w"].shape == (d, d + 2 * kv * dh)
    q, k, v = np.split(w["a.qkv.w"], [d, d + kv * dh], axis=1)
    rep = lambda m: np.repeat(  # noqa: E731
        m.reshape(d, kv, dh), h // kv, axis=1).reshape(d, d)
    full, _ = run(h, {"a.qkv.w": np.concatenate([q, rep(k), rep(v)], 1),
                      "a.out.w": w["a.out.w"]})
    np.testing.assert_allclose(shared, full, rtol=1e-5, atol=1e-6)
