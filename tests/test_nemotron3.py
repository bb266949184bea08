"""What the Nemotron-3-Nano cell forced into the program, on the CPU at toy
widths (the model's own head and state sizes where the arithmetic is
sensitive): ``ssd_scan`` and its grad op against the token-by-token
recurrence, the biased ``short_conv``, un-gated ``moe_ffn`` on the sorted and
the held path, the grouped gated norm, the toy model against the plain
reference leaf by leaf, the recomputed step against the plain step, the
expert-shares' sum, planted faults against named limits, and the counters'
labels read as differences."""

import hashlib
import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import paddle_tpu as pt  # noqa: E402
from benchmark import harness  # noqa: E402
from paddle_tpu import layers, optimizer as opt  # noqa: E402
from paddle_tpu.framework import (Program, Scope, program_guard,  # noqa: E402
                                  scope_guard)
from paddle_tpu.framework.backward import append_backward  # noqa: E402
from paddle_tpu.framework.core import grad_var_name  # noqa: E402
from paddle_tpu.initializer import NormalInitializer  # noqa: E402
from paddle_tpu.models import transformer as T  # noqa: E402
from paddle_tpu.ops import moe_ops, sequence_ops, ssd_ops  # noqa: E402

CONFIG = "nemotron3_nano_30b_a3b"
REF = harness.load_module("reference", CONFIG)
MODEL = harness.load_module("models", CONFIG)


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want)
                 / max(np.linalg.norm(want), 1e-30))


def _feed_program(values, build, seed=0):
    """A program over ``values`` ({name: array}, all differentiable feeds)
    whose output is ``build(vars)``, its startup program run under ``seed``:
    ``(main, scope, out, exe)``."""
    main, startup, scope = Program(), Program(), Scope()
    with scope_guard(scope), program_guard(main, startup):
        vs = {}
        for k, a in values.items():
            vs[k] = layers.data(k, shape=list(a.shape), dtype=str(a.dtype),
                                append_batch_size=False)
            vs[k].stop_gradient = False
        out = build(vs)
    exe = pt.Executor()
    exe.run(startup, scope=scope, seed=seed)
    return main, scope, out, exe


# -- ssd_scan -----------------------------------------------------------------

def _recurrence(p):
    """The token-by-token recurrence of one sequence, the reference's own
    step (``reference.recurrence``) over every head, plus the skip."""
    x, b, c = p["x"][0], p["b"][0], p["c"][0]
    h = x.shape[1]
    delta = p["dt"][0] if "dt_bias" not in p else \
        jax.nn.softplus(p["dt"][0] + p["dt_bias"])
    y = jax.vmap(lambda x, dl, a, b, c: REF.recurrence(x, dl, a, b, c, 8),
                 in_axes=(1, 1, 0, 1, 1), out_axes=1)(
        x, delta, -jnp.exp(p["a_log"]), REF.heads_from_groups(b, h),
        REF.heads_from_groups(c, h))
    return y + p["d"][None, :, None] * x


def _scan_case(t, chunk, bias, h=4, g=2, p=8, n=16, step=(1e-3, 0.1), seed=0):
    r = np.random.RandomState(seed)
    v = dict(x=r.randn(1, t, h, p), b=r.randn(1, t, g, n),
             c=r.randn(1, t, g, n), a_log=np.log(np.arange(1.0, h + 1)),
             d=r.randn(h), dy=r.randn(1, t, h, p))
    if bias:
        v["dt"], v["dt_bias"] = r.randn(1, t, h), r.randn(h)
    else:
        v["dt"] = np.exp(r.uniform(np.log(step[0]), np.log(step[1]),
                                   (1, t, h)))
    v = {k: a.astype(np.float32) for k, a in v.items()}
    keys = [k for k in v if k != "dy"]

    def build(vs):
        y = layers.ssd_scan(vs["x"], vs["dt"], vs["a_log"], vs["b"], vs["c"],
                            vs["d"], vs.get("dt_bias"), chunk=chunk)
        append_backward(layers.reduce_sum(y * vs["dy"]))
        return y

    main, scope, y, exe = _feed_program(v, build)
    got = exe.run(
        main, feed=v, scope=scope,
        fetch_list=[y.name] + [grad_var_name(k) for k in keys])
    with jax.default_matmul_precision("highest"):
        prim = {k: jnp.asarray(v[k]) for k in keys}
        want = _recurrence(prim)
        grads = jax.grad(lambda q: jnp.sum(_recurrence(q) * v["dy"][0]))(prim)
    return got[0][0], want, dict(zip(keys, got[1:])), grads


@pytest.mark.parametrize("t, chunk, bias, kw", [
    (64, 16, True, {}),                         # whole chunks
    (50, 16, True, {}),                         # a ragged last chunk
    (16, 16, False, {}),                        # one chunk
    (40, 16, False, {"step": (1e-4, 1e-4)}),    # the step at its floor
    (40, 16, False, {"step": (20.0, 20.0)}),    # and far over its range
    # the model's own head, state and chunk sizes, heads of two groups
    (300, 128, True, {"h": 16, "g": 2, "p": 64, "n": 128}),
], ids=["chunks", "ragged", "one_chunk", "step_floor", "step_huge",
        "published_sizes"])
def test_ssd_scan_and_its_grad_against_the_recurrence(t, chunk, bias, kw):
    """``ssd_scan``'s chunked form gives the token-by-token recurrence, and
    ``ssd_scan_grad`` (from the saved chunk states) ``jax.grad`` of it, every
    input's; heads of two groups read different ``B`` and ``C``; no NaN where
    a strong decay underflows."""
    y, want, grads, g_ref = _scan_case(t, chunk, bias, **kw)
    assert rel(y, want) < 5e-6
    for k, g in grads.items():
        assert np.all(np.isfinite(g)), k
        # at a step of 20 the state is forgotten in one position: A_log's
        # gradient is 0 to float32 and what the chunked form leaves of its
        # cancelling terms is compared on the other leaves' scale
        scale = max(np.linalg.norm(np.asarray(g_ref[k])),
                    1e-3 * np.linalg.norm(np.asarray(g_ref["x"])))
        assert np.linalg.norm(np.asarray(g) - np.asarray(g_ref[k])) \
            < 2e-4 * scale, k


def test_the_groups_are_read_by_consecutive_heads():
    """Head ``h`` reads group ``h // (H / G)``: with ``B`` zero in group 1
    the heads of group 1 give the skip alone, and no other."""
    r = np.random.RandomState(1)
    x = r.randn(1, 24, 4, 8).astype(np.float32)
    b = r.randn(1, 24, 2, 16).astype(np.float32)
    b[:, :, 1] = 0
    args = (jnp.asarray(x), jnp.full((1, 24, 4), 0.05), jnp.zeros(4),
            jnp.asarray(b), jnp.asarray(r.randn(1, 24, 2, 16), jnp.float32),
            jnp.ones(4))
    y = np.asarray(ssd_ops.ssd_chunked(*args, chunk=8))
    np.testing.assert_allclose(y[:, :, 2:], x[:, :, 2:], rtol=1e-6)
    assert np.abs(y[:, :, :2] - x[:, :, :2]).max() > 0.1


def test_the_states_are_the_state_before_every_chunk():
    r = np.random.RandomState(2)
    args = [jnp.asarray(a, jnp.float32) for a in (
        r.randn(1, 40, 2, 4), r.uniform(0.01, 0.1, (1, 40, 2)),
        np.zeros(2), r.randn(1, 40, 1, 8), r.randn(1, 40, 1, 8),
        np.zeros(2))]
    _, states = ssd_ops.ssd_chunked(*args, chunk=16, with_states=True)
    assert states.shape == (1, 2, 3, 4, 8) and states.dtype == jnp.float32
    assert np.all(np.asarray(states[:, :, 0]) == 0)
    # the state after 16 positions, by hand
    x, dt, _, b, _, _ = (np.asarray(a, np.float64) for a in args)
    s = np.zeros((2, 4, 8))
    for i in range(16):
        s = s * np.exp(-dt[0, i])[:, None, None] \
            + (dt[0, i][:, None] * x[0, i])[:, :, None] * b[0, i, 0]
    np.testing.assert_allclose(np.asarray(states[0, :, 1]), s, rtol=2e-5,
                               atol=1e-6)


# -- short_conv(bias=) --------------------------------------------------------

class _Ctx:
    amp = False
    is_abstract = True


def _jaxpr_text(f, *args):
    return re.sub(r"0x[0-9a-f]+", "0x", str(jax.make_jaxpr(f)(*args)))


def test_short_conv_with_a_bias_and_its_grad():
    r = np.random.RandomState(3)
    v = {"x": r.randn(2, 12, 6).astype(np.float32),
         "dy": r.randn(2, 12, 6).astype(np.float32)}

    def build(vs):
        y = layers.short_conv(vs["x"], 4, gated=False,
                              param_attr=pt.ParamAttr(
                                  name="f", initializer=NormalInitializer(
                                      0.0, 0.5)),
                              bias_attr=pt.ParamAttr(
                                  name="b", initializer=NormalInitializer(
                                      0.0, 0.5)))
        append_backward(layers.reduce_sum(y * vs["dy"]))
        return y

    main, scope, y, exe = _feed_program(v, build, seed=5)
    got = exe.run(main, feed=v, scope=scope, fetch_list=[y.name] + [
        grad_var_name(n) for n in ("x", "f", "b")])
    f, b = (jnp.asarray(scope.find_var(n)) for n in ("f", "b"))
    assert f.shape == (6, 4) and b.shape == (6,) and float(jnp.abs(b).max())

    def ref(x, f, b):
        return jnp.stack([REF.causal_conv_silu(x[i], f, b) for i in (0, 1)])
    want = ref(jnp.asarray(v["x"]), f, b)
    grads = jax.grad(lambda *a: jnp.sum(ref(*a) * v["dy"]), (0, 1, 2))(
        jnp.asarray(v["x"]), f, b)
    assert rel(got[0], want) < 1e-6
    for g, w in zip(got[1:], grads):
        assert rel(g, w) < 1e-5
    with pytest.raises(ValueError):
        with program_guard(Program(), Program()):
            layers.short_conv(layers.data("u", shape=[4, 6]), 3,
                              bias_attr=pt.ParamAttr(name="bb"))


#: sha256[:16] of the PARENT commit's lowerings (2063d31, jax 0.9.0), each
#: the op's and its grad op's jaxprs over the inputs of ``_lowered``: what
#: "the default leaves the lowering the parent's to the text" is held to
PARENT = {"moe_all_silu": "7d69c866ed046dcc",
          "moe_all_relu": "131f2f10ba257754",
          # the held path on a three-rung ladder, 4 of 64 experts
          "moe_held_silu": "7f4503f00fc41927",
          "moe_held_relu": "0e3e1bbd85be0991",
          "conv_gated": "39a8e25d9c56951e",
          "conv_ungated": "2428037f7454e711"}


def _moe_io(held, E, act, gated=True, S=2048, d=16, f=24, k=2):
    r = np.random.RandomState(0)
    x = jnp.asarray(r.randn(1, S, d), jnp.float32)
    ws = [jnp.asarray(r.randn(*s), jnp.float32)
          for s in ((d, E), (held, d, f), (held, d, f), (held, f, d))]
    attrs = {"top_k": k, "norm_topk_prob": True, "score_func": "sigmoid",
             "norm_eps": 1e-20, "route_scale": 2.5}
    if act != "silu":
        attrs["act"] = act
    if held != E:
        attrs["expert_offset"] = 2
    names = ("RouterW", "GateW", "UpW", "DownW")

    def ins(prefix, x, ws):
        d = {prefix + "X": [x], prefix + "SelectBias": [jnp.zeros(E)]}
        d.update({prefix + n: [w] for n, w in zip(names, ws)
                  if gated or n != "GateW"})
        return d

    def fwd(x, *ws):
        return moe_ops._moe_ffn(_Ctx(), ins("", x, ws), attrs)
    out = fwd(x, *ws)

    def bwd(x, *ws):
        return moe_ops._moe_ffn_grad(_Ctx(), dict(
            ins("X$", x, ws), Saved=out["Saved"], **{
                "OG$Out": [out["Out"][0]], "OG$LbLoss": [None],
                "OG$ZLoss": [None]}), attrs)
    return fwd, bwd, (x, *ws)


def _lowered(name):
    if name.startswith("moe"):
        held, E = (4, 64) if "held" in name else (8, 8)
        fwd, bwd, args = _moe_io(held, E, name.rsplit("_", 1)[1])
        return _jaxpr_text(fwd, *args) + _jaxpr_text(bwd, *args)
    gated = name == "conv_gated"
    r = np.random.RandomState(0)
    x = jnp.asarray(r.randn(2, 12, 18 if gated else 6), jnp.float32)
    w = jnp.asarray(r.randn(6, 4), jnp.float32)
    attrs = {} if gated else {"gated": False}

    def f(x, w):
        return sequence_ops._short_conv(_Ctx(), {"X": [x], "Filter": [w]},
                                        attrs)

    def g(x, w):
        return sequence_ops._short_conv_grad(_Ctx(), {
            "X$X": [x], "X$Filter": [w], "OG$Out": [f(x, w)["Out"][0]]},
            attrs)
    return _jaxpr_text(f, x, w) + _jaxpr_text(g, x, w)


#: the same of the four ``moe_ffn`` toys since PR 63: two experts a token is
#: no multiple of 8, so XLA's gather brings the slots home slot-major
#: (``moe_ops._sum_over_slots``); with the order forced slot-minor the texts
#: are ``PARENT``'s still
SLOT_MAJOR = {"moe_all_silu": "81247a05993d0673",
              "moe_all_relu": "115bc015a0e8ff78",
              "moe_held_silu": "2e5d3e6758c1cfba",
              "moe_held_relu": "28d43fcda25370ad"}


@pytest.mark.skipif(jax.__version__ != "0.9.0",
                    reason="the pinned texts are jax 0.9.0's")
@pytest.mark.parametrize("name", sorted(PARENT))
def test_the_defaults_lower_to_the_parents_text(name, monkeypatch):
    """``short_conv`` without a bias and ``moe_ffn`` with gated experts are
    the lowerings they were before the arguments existed, forward and grad
    op, to the text of their jaxprs; ``moe_ffn``'s apart from the order its
    un-sorts sum a token's two slots in (PR 63)."""
    def sha():
        return hashlib.sha256(_lowered(name).encode()).hexdigest()[:16]
    if name in SLOT_MAJOR:
        assert sha() == SLOT_MAJOR[name]
        monkeypatch.setattr(moe_ops, "_slot_major", lambda *a: False)
    assert sha() == PARENT[name]


def test_ungated_experts_run_two_grouped_matmuls_where_gated_run_three():
    """On the held path's three-rung ladder: the un-gated op's text holds two
    grouped matmuls forward where the gated has three, and four transposes
    backward (two products, rows and weights) where the gated has six."""
    assert moe_ops.held_ladder(2048, 2, 4, 64) == (512, 1024, 4096)

    def products(gated):
        fwd, bwd, args = _moe_io(4, 64, "silu" if gated else "relu2",
                                 gated=gated)
        return tuple(len(re.findall(r"ragged_dot(?:_general)?\[", t))
                     for t in (_jaxpr_text(fwd, *args),
                               _jaxpr_text(bwd, *args)))
    gated, plain = products(True), products(False)
    assert 2 * gated[0] == 3 * plain[0] and plain[0] > 0
    assert 2 * gated[1] == 3 * plain[1] and plain[1] > 0


# -- moe_ffn(gated=False) -----------------------------------------------------

def _reference_experts(x, P, held, offset, top_k, scale):
    m = x.reshape(-1, x.shape[-1])
    blk = {"router_w": P["m.router.w"], "select_bias": P["m.select_bias"],
           "up_w": P["m.up.w"], "down_w": P["m.down.w"]}
    weight, _ = REF.route(m, blk, top_k, scale)
    return REF.held_experts(m, blk, weight, offset).reshape(x.shape)


@pytest.mark.parametrize("held, offset, E, S", [
    (8, 0, 8, 32), (4, 2, 8, 32), (4, 8, 64, 2048)],
    ids=["sorted", "held_one_rung", "held_three_rungs"])
def test_ungated_experts_forward_and_every_leafs_gradient(held, offset, E, S):
    d, f, k = 16, 24, 2
    main, startup, scope = Program(), Program(), Scope()
    with scope_guard(scope), program_guard(main, startup):
        x = layers.data("x", shape=[1, S, d], dtype="float32",
                        append_batch_size=False)
        x.stop_gradient = False
        out, _, _, load = layers.moe_ffn(
            x, E, k, f, norm_topk_prob=True, param_prefix="m",
            initializer=NormalInitializer(0.0, 0.5), score_func="sigmoid",
            select_bias=True, norm_eps=1e-20, route_scale=2.5, num_held=held,
            expert_offset=offset, act="relu2", gated=False)
        pgs = append_backward(layers.reduce_sum(out * out))
        exe = pt.Executor()
        exe.run(startup, scope=scope, seed=3)
    names = [p.name for p, _ in pgs]
    assert sorted(names) == ["m.down.w", "m.router.w", "m.up.w"]
    assert "m.gate.w" not in {p.name for p in main.all_parameters()}
    op, = [o for o in main.global_block().ops if o.type == "moe_ffn"]
    assert "GateW" not in op.inputs and len(op.outputs["Saved"]) == 4
    xv = np.random.RandomState(0).randn(1, S, d).astype(np.float32)
    got = exe.run(main, feed={"x": xv}, scope=scope, fetch_list=[
        out.name, load.name, grad_var_name("x")] + [g.name for _, g in pgs])
    P = {n: jnp.asarray(scope.find_var(n))
         for n in names + ["m.select_bias"]}
    with jax.default_matmul_precision("highest"):
        want = _reference_experts(jnp.asarray(xv), P, held, offset, k, 2.5)
        gx, gp = jax.grad(lambda x, P: jnp.sum(jnp.square(
            _reference_experts(x, P, held, offset, k, 2.5))), (0, 1))(
                jnp.asarray(xv), P)
    assert int(np.asarray(got[1]).sum()) == S * k
    assert rel(got[0], want) < 1e-5
    assert rel(got[2], gx) < 1e-5
    for n, g in zip(names, got[3:]):
        assert rel(g, gp[n]) < 1e-5, n


def test_an_ungated_op_needs_its_own_activation():
    with program_guard(Program(), Program()):
        x = layers.data("x", shape=[4, 8])
        with pytest.raises(ValueError):
            layers.moe_ffn(x, 4, 2, 8, gated=False)            # act silu
        with pytest.raises(ValueError):
            layers.moe_ffn(x, 4, 2, 8, act="relu2")            # gated


# -- the grouped gated norm ---------------------------------------------------

def test_the_gated_norm_gates_first_and_norms_by_group():
    r = np.random.RandomState(4)
    v = {k: r.randn(2, 5, 32).astype(np.float32) for k in ("y", "z", "dy")}

    def build(vs):
        out = layers.gated_rms_norm(vs["y"], vs["z"], groups=4, epsilon=1e-5,
                                    param_attr=pt.ParamAttr(
                                        name="w",
                                        initializer=NormalInitializer(1.0,
                                                                      0.3)))
        append_backward(layers.reduce_sum(out * vs["dy"]))
        return out

    main, scope, out, exe = _feed_program(v, build, seed=2)
    got = exe.run(main, feed=v, scope=scope, fetch_list=[out.name] + [
        grad_var_name(n) for n in ("y", "z", "w")])
    w = np.asarray(scope.find_var("w"), np.float64)
    y, z = (v[k].astype(np.float64) for k in ("y", "z"))
    gated = (y * z / (1 + np.exp(-z))).reshape(2, 5, 4, 8)
    want = (gated / np.sqrt((gated ** 2).mean(-1, keepdims=True) + 1e-5)
            ).reshape(2, 5, 32) * w
    assert rel(got[0], want) < 1e-6

    def ref(y, z, w):
        return jnp.stack([REF.gated_group_norm(y[i], z[i], w, 4, 1e-5)
                          for i in (0, 1)])
    grads = jax.grad(lambda *a: jnp.sum(ref(*a) * v["dy"]), (0, 1, 2))(
        jnp.asarray(v["y"]), jnp.asarray(v["z"]), jnp.asarray(w, jnp.float32))
    for g, want_g in zip(got[1:], grads):
        assert rel(g, want_g) < 1e-5
    # the norm before the gate, or over all channels at once, is another
    # function
    one = (y * z / (1 + np.exp(-z)))
    one = one / np.sqrt((one ** 2).mean(-1, keepdims=True) + 1e-5) * w
    assert rel(one, want) > 0.05


# -- the toy model against the reference --------------------------------------

def toy_cfg(**kw):
    base = dict(vocab_size=96, d_model=32, pattern="MEM*E", n_mamba_head=4,
                d_mamba_head=8, n_group=2, d_state=16, chunk=16, n_head=4,
                n_kv_head=2, d_head=8, d_expert=16, d_shared=24, n_experts=8,
                top_k=2, n_held=4, expert_offset=2)
    base.update(kw)
    return T.NemotronHConfig(**base)


def _batch(cfg, seq=40, batch=2, seed=0):
    ids = np.random.RandomState(seed).randint(1, cfg.vocab_size,
                                              (batch, seq + 1))
    return {"src_ids": ids[:, :-1].astype(np.int64),
            "lm_label": ids[:, 1:].astype(np.int64)}


def _toy_forward(cfg, feed, seed=1, bias_std=0.0):
    """The float32 program's loss, final-norm output and every parameter's
    gradient, and the scope's parameters."""
    main, startup, scope = Program(), Program(), Scope()
    with scope_guard(scope), program_guard(main, startup):
        # the plain head: the fused head's bf16 products move a toy loss by
        # 1e-4
        _, parts, loss = T.build_nemotron_h_pretrain(
            cfg, feed["src_ids"].shape[1], attn_impl="base",
            fused_head=False)
        pgs = append_backward(loss)
        exe = pt.Executor()
        exe.run(startup, scope=scope, seed=seed)
    if bias_std:
        r = np.random.RandomState(9)
        for i, kind in enumerate(cfg.pattern):
            if kind == "E":
                scope.set_var(f"dec_{i}.moe.select_bias", jnp.asarray(
                    r.randn(cfg.n_experts) * bias_std, jnp.float32))
    with jax.default_matmul_precision("highest"):
        got = exe.run(main, feed=feed, scope=scope, fetch_list=[
            loss.name, parts["hidden"].name] + [g.name for _, g in pgs])
    grads = {p.name: np.asarray(g) for (p, _), g in zip(pgs, got[2:])}
    params = {p.name: np.asarray(scope.find_var(p.name))
              for p in main.all_parameters()}
    return float(np.asarray(got[0])), np.asarray(got[1]), grads, params


def _reference(cfg, params, feed, **kw):
    tree = MODEL.reference_params(
        lambda n: jnp.asarray(params[n], jnp.float32), cfg)
    args = (jnp.asarray(feed["src_ids"]), jnp.asarray(feed["lm_label"]))
    kwargs = dict(MODEL.reference_kw(cfg, q_block=8, scan_block=8), **kw)
    sums = REF.batch_sums(tree, *args, **kwargs)
    return tree, float(REF.loss_of_sums(sums)["loss"]), \
        np.asarray(sums["hidden"]), kwargs, args


def test_the_toy_model_against_the_reference_leaf_by_leaf():
    """Loss, final-norm output and EVERY parameter's gradient of the float32
    program against ``jax.grad`` of the reference, by kind of leaf."""
    cfg, feed = toy_cfg(), None
    feed = _batch(cfg)
    loss, hidden, grads, params = _toy_forward(cfg, feed)
    tree, want, ref_hidden, kw, args = _reference(cfg, params, feed)
    assert abs(loss - want) < 2e-6 * abs(want)
    assert rel(hidden, ref_hidden) < 1e-5
    g_ref = jax.grad(lambda p: REF.loss(p, *args, **kw))(tree)
    for blk in g_ref["blocks"]:
        blk.pop("select_bias", None)
    g_ref = jax.tree_util.tree_map(np.asarray, g_ref)
    off = MODEL.gradient_difference(g_ref, MODEL.reference_params(
        grads.__getitem__, cfg, select_bias=False))
    for kind in MODEL.KINDS:
        together, worst, leaf = off[kind]
        assert worst < 2e-4, (kind, leaf, worst)
    assert off["all"] < 1e-5
    # every kind has leaves here, the Mamba blocks' six among them
    names = {MODEL.kind_of(jax.tree_util.keystr(p)) for p, _ in
             jax.tree_util.tree_flatten_with_path(g_ref)[0]}
    assert names == set(MODEL.KINDS)


def _train_steps(cfg, feed, recompute, steps=3):
    main, startup, scope = Program(), Program(), Scope()
    with scope_guard(scope), program_guard(main, startup):
        cps = [] if recompute else None
        _, _, loss = T.build_nemotron_h_pretrain(
            cfg, feed["src_ids"].shape[1], checkpoints=cps, attn_impl="base")
        stepper = adamw = opt.AdamWOptimizer(learning_rate=1e-2,
                                             weight_decay=0.1)
        if recompute:
            assert len(cps) == cfg.n_layer + 1
            stepper = opt.RecomputeOptimizer(adamw)
            stepper._set_checkpoints(cps, after_gradient=True)
        stepper.minimize(loss)
        exe = pt.Executor()
        exe.run(startup, scope=scope, seed=1)
        losses = [float(np.asarray(exe.run(
            main, feed=feed, fetch_list=[loss.name], scope=scope)[0]))
            for _ in range(steps)]
    weights = {p.name: np.asarray(scope.find_var(p.name))
               for p in main.all_parameters()}
    return losses, weights, [o.type for o in main.global_block().ops]


def test_the_recomputed_step_against_the_plain_step():
    """Six boundaries over three kinds of one-sublayer block: the recomputed
    step's losses and weights after three AdamW steps are the plain
    step's."""
    cfg = toy_cfg()
    feed = _batch(cfg)
    plain, w_plain, ops_plain = _train_steps(cfg, feed, False)
    again, w_again, ops_again = _train_steps(cfg, feed, True)
    assert plain[2] < plain[0]
    np.testing.assert_allclose(again, plain, rtol=2e-6)
    for n in w_plain:
        np.testing.assert_allclose(w_again[n], w_plain[n], rtol=2e-4,
                                   atol=2e-6, err_msg=n)
    assert (ops_plain.count("ssd_scan"), ops_again.count("ssd_scan")) == \
        (2, 4)
    assert (ops_plain.count("moe_ffn"), ops_again.count("moe_ffn")) == (2, 4)
    assert ops_again.count("ssd_scan_grad") == 2


# -- the expert-shares add up -------------------------------------------------

def test_the_expert_shares_add_up_to_the_uncut_expert_block():
    """Four chips' shares of 16 experts (toy: the cell's sixteen shares of
    128): each share's partial result from ``moe_ffn``'s held path, the
    shared expert and the residual counted ONCE, add up to the reference's
    expert block over all 16 experts."""
    d, f, fs, E, k, S = 16, 24, 40, 16, 3, 64
    r = np.random.RandomState(7)
    full = {"norm_w": 1 + 0.1 * r.randn(d), "router_w": r.randn(d, E),
            "select_bias": 0.1 * r.randn(E), "shared_up": 0.3 * r.randn(d, fs),
            "shared_down": 0.3 * r.randn(fs, d),
            "up_w": 0.3 * r.randn(E, d, f), "down_w": 0.3 * r.randn(E, f, d)}
    full = {n: jnp.asarray(a, jnp.float32) for n, a in full.items()}
    x = jnp.asarray(r.randn(S, d), jnp.float32)
    kw = dict(eps=1e-5, top_k=k, route_scale=2.5, expert_offset=0)
    with jax.default_matmul_precision("highest"):
        want, _ = REF.block(x, full, kw)
    total = np.zeros((S, d))
    for share in range(4):
        cfg = T.NemotronHConfig(
            vocab_size=8, d_model=d, pattern="E", d_expert=f, d_shared=fs,
            n_experts=E, top_k=k, n_held=4, expert_offset=4 * share)
        main, startup, scope = Program(), Program(), Scope()
        with scope_guard(scope), program_guard(main, startup):
            xv = layers.data("x", shape=[1, S, d], dtype="float32",
                             append_batch_size=False)
            out, (_, _, load) = T.decoder_block(xv, cfg, 0)
            exe = pt.Executor()
            exe.run(startup, scope=scope, seed=1)
        held = slice(4 * share, 4 * share + 4)
        for name, value in (("dec_0.norm.w", full["norm_w"]),
                            ("dec_0.moe.router.w", full["router_w"]),
                            ("dec_0.moe.select_bias", full["select_bias"]),
                            ("dec_0.shared.up.w", full["shared_up"]),
                            ("dec_0.shared.down.w", full["shared_down"]),
                            ("dec_0.moe.up.w", full["up_w"][held]),
                            ("dec_0.moe.down.w", full["down_w"][held])):
            assert np.shape(scope.find_var(name)) == value.shape, name
            scope.set_var(name, value)
        with jax.default_matmul_precision("highest"):
            got, rows = exe.run(main, feed={"x": np.asarray(x)[None]},
                                fetch_list=[out.name, load.name],
                                scope=scope)
        assert int(np.asarray(rows).sum()) == S * k
        total += np.asarray(got[0], np.float64)
        if share == 0:
            first = np.asarray(got[0], np.float64)
    # the residual and the shared expert were in every share: once is enough
    with jax.default_matmul_precision("highest"):
        m = REF.rms(x, full["norm_w"], 1e-5)
        once = np.asarray(x + REF.relu2_ffn(m, full["shared_up"],
                                            full["shared_down"]), np.float64)
    assert rel(total - 3 * once, want) < 1e-5
    assert rel(first, want) > 0.05           # one share alone is not the block


# -- planted faults -----------------------------------------------------------

def _rotary(q, k):
    def turn(x):
        t, _, dh = x.shape
        ang = jnp.arange(t, dtype=jnp.float32)[:, None] * 10000.0 ** (
            -jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)[None, :]
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        a, b = x[..., :dh // 2], x[..., dh // 2:]
        return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)
    return turn(q), turn(k)


def _biased_route(m, blk, top_k, route_scale):
    s = jax.nn.sigmoid(m @ blk["router_w"]) + blk["select_bias"]
    top_e = jnp.argsort(-s, axis=-1, stable=True)[:, :top_k]
    kept = s * jnp.zeros_like(s).at[
        jnp.arange(s.shape[0])[:, None], top_e].set(1.0)
    return kept / (jnp.sum(kept, -1, keepdims=True) + 1e-20) * route_scale, \
        top_e


_recur = REF.recurrence
_norm = REF.gated_group_norm
#: fault -> (the reference's function it replaces, the faulty one)
FAULTS = {
    "the norm before the gate": ("gated_group_norm", lambda y, z, w, g, eps:
                                 _norm(y, jnp.full_like(z, 1e4), w, g, eps)
                                 * jax.nn.silu(z)),
    "the RMS over all channels": ("gated_group_norm",
                                  lambda y, z, w, g, eps: _norm(y, z, w, 1,
                                                                eps)),
    "head h reads group h % G": ("heads_from_groups", lambda v, heads:
                                 jnp.tile(v, (1, heads // v.shape[1], 1))),
    "the step missing from the input term": (
        "recurrence", lambda x, dl, a, b, c, blk: _recur(
            x / dl[:, None], dl, a, b, c, blk)),
    "a gated expert": ("relu2_ffn", lambda m, wu, wd: (
        jax.nn.silu(m @ wu) * (m @ wu)) @ wd),
    "relu without the square": ("relu2_ffn", lambda m, wu, wd:
                                jax.nn.relu(m @ wu) @ wd),
    "the bias added to the weights": ("route", _biased_route),
    "rotary applied": ("positions", _rotary),
}


@pytest.mark.parametrize("fault", [None, "D left out",
                                   "the pattern shifted by one"]
                         + sorted(FAULTS))
def test_a_planted_fault_is_caught_by_a_named_limit(fault, monkeypatch):
    """Each fault planted in the reference's place is over the toy cell's
    limit on the float32 forward (``hidden_relative``, and for most
    ``relative``, the loss's); the sound reference is under both."""
    sys.path.insert(0, os.path.join(ROOT, "tests", "benchmark"))
    import test_nemotron3_cell as cell
    tol = cell.toy_nemotron()[0]["loss_tolerance"]
    # experts at N(0, 0.2), not 0.02: what a routed expert adds is then a
    # visible part of the toy block's output
    cfg = toy_cfg(init_std=0.2)
    feed = _batch(cfg)
    loss, hidden, _, params = _toy_forward(cfg, feed, bias_std=0.3)
    if fault in FAULTS:
        name, wrong = FAULTS[fault]
        monkeypatch.setattr(REF, name, wrong)
    if fault == "D left out":
        params = {n: (np.zeros_like(v) if n.endswith(".mamba.D") else v)
                  for n, v in params.items()}
    tree, want, ref_hidden, kw, args = _reference(cfg, params, feed)
    if fault == "the pattern shifted by one":
        tree["blocks"] = tree["blocks"][1:] + tree["blocks"][:1]
        sums = REF.batch_sums(tree, *args, **kw)
        want = float(REF.loss_of_sums(sums)["loss"])
        ref_hidden = np.asarray(sums["hidden"])
    off = {"hidden_relative": rel(hidden, ref_hidden),
           "relative": abs(loss - want) / abs(want)}
    over = sorted(k for k, v in off.items() if not v <= tol[k])
    if fault is None:
        assert not over, off
    else:
        assert "hidden_relative" in over, (fault, off)


# -- the counters -------------------------------------------------------------

def test_the_counters_labels_read_as_differences():
    """One toy training step moves ``paddle_tpu_ssd_lowerings_total{impl,
    chunk}`` by forward + backward a Mamba block, the convolution's counter
    under ``bias="true"`` and the experts' under ``gated="0"``; the labels
    every older cell reads (``bias="false"``, ``gated="1"``) do not move;
    read as differences, whatever an earlier test of the run traced."""
    def now():
        return (ssd_ops.SSD_LOWERINGS_CTR.value(impl="xla", chunk="16"),
                sequence_ops.SHORT_CONV_LOWERINGS_CTR.value(
                    taps="4", gated="false", act="silu", bias="true"),
                moe_ops.MOE_LOWERINGS_CTR.value(act="relu2", gated="0"),
                sequence_ops.SHORT_CONV_LOWERINGS_CTR.value(bias="false"),
                moe_ops.MOE_LOWERINGS_CTR.value(gated="1"))
    before = now()
    cfg = toy_cfg()
    _train_steps(cfg, _batch(cfg), False, steps=1)
    moved = tuple(b - a for a, b in zip(before, now()))
    assert moved == (4, 4, 2, 0, 0), moved
    assert set(ssd_ops.SSD_LOWERINGS_CTR.labelnames) == {"impl", "chunk"}
    assert "bias" in sequence_ops.SHORT_CONV_LOWERINGS_CTR.labelnames
    assert "gated" in moe_ops.MOE_LOWERINGS_CTR.labelnames
