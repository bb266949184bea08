"""Xing4.0-29B-A4B's parts (``ops/hc_ops.py``: ``hc_pre`` / ``hc_post`` and
their grad ops; ``rope``'s frequency-table form; ``models/transformer.py``:
``XingConfig``, ``hyper_connection``, ``build_joyai_pretrain`` over a widened
stream) at a toy size on the CPU against the plain float32 reference
(``benchmark/reference/xing4_29b_a4b.py``): the two ops forward and every
gradient, float32 and under AMP; ``H_res`` doubly stochastic and its clamp;
one stream with unit maps is JoyAI's block; YaRN's table and the rotation by
it, kernel interpreted; loss and every gradient leaf of a 1 dense + 2 expert
model; the share test; the recomputed step against the plain one; JoyAI's
lowering with the new attributes absent; scopes and counters."""

import functools
import hashlib
import os
import re
import sys

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
from benchmark.models import xing4_29b_a4b as adapter  # noqa: E402
from benchmark.reference import xing4_29b_a4b as ref  # noqa: E402
from paddle_tpu import device, layers, optimizer as opt  # noqa: E402
from paddle_tpu.framework import (Executor, Program, Scope,  # noqa: E402
                                  program_guard, scope_guard)
from paddle_tpu.framework.backward import append_backward  # noqa: E402
from paddle_tpu.framework.core import grad_var_name  # noqa: E402
from paddle_tpu.models import transformer as T  # noqa: E402
from paddle_tpu.ops import attention_ops, hc_ops  # noqa: E402
from paddle_tpu.pallas import rope as rope_kernel  # noqa: E402

_close = olmoe_test._close
_rel = joyai_test._rel
LOSS_TOL, GRAD_TOL = olmoe_test.LOSS_TOL, olmoe_test.GRAD_TOL
SEQ, N, C = 16, 4, 32
#: the published group at a length the toy sequences reach: lo 1, hi 3 of 4
TOY_YARN = dict(T.XingConfig.YARN, original_max_position_embeddings=8,
                factor=4)


def toy_cfg(**kw):
    kw = dict(dict(vocab_size=96, d_model=C, n_layer=3, n_head=4,
                   q_lora_rank=24, kv_lora_rank=16, d_nope=16, d_rope=8,
                   d_v=12, d_inner=48, d_expert=24, n_experts=8, top_k=2,
                   n_dense_layer=1, n_held=8, expert_offset=0,
                   hc_mult=N, hc_sinkhorn_iters=20, rope_scaling=TOY_YARN),
              **kw)
    return T.XingConfig(**kw)


def _randomise(scope, main, seed):
    """Norm scales off 1 and selection biases off 0, as ``test_joyai``; the
    hyper-connections' alpha and bias off their small start, so that every
    map depends on the token and no map sits at a symmetric point."""
    joyai_test._randomise_norms(scope, main, seed)
    rng = np.random.RandomState(seed + 1)
    for p in main.all_parameters():
        if p.name.endswith(".alpha"):
            scope.set_var(p.name, jnp.asarray(
                rng.uniform(0.3, 0.9, p.shape).astype(np.float32)))
        elif ".hc_" in p.name and p.name.endswith(".bias"):
            scope.set_var(p.name, jnp.asarray(
                rng.randn(*p.shape).astype(np.float32) * 0.3))
        elif p.name.endswith(".phi"):
            # logits a few tenths wide: Sinkhorn-Knopp's 20 iterations reach
            # 1e-6 there (at 1.5 wide they leave a column 1e-2 off)
            scope.set_var(p.name, jnp.asarray(
                rng.randn(*p.shape).astype(np.float32) * 0.05))


# -- the two ops ------------------------------------------------------------------

def _hc_values(seed):
    rng = np.random.RandomState(seed)
    b, t = 2, 6
    return {"x": rng.randn(b, t, N * C).astype(np.float32),
            "w": rng.randn(b, t, N * C).astype(np.float32),
            "f": rng.randn(C, C).astype(np.float32) * 0.3,
            "phi": rng.randn(N * C, 2 * N + N * N).astype(np.float32) * 0.05,
            "alpha": rng.uniform(0.3, 0.9, 3).astype(np.float32),
            "bias": rng.randn(2 * N + N * N).astype(np.float32) * 0.3}


def _hc_reference(v):
    """``sum(w * X')`` of one hyper-connection round ``y = u F`` and its
    gradients, by the reference, float32 at ``highest``."""
    hc = {k: jnp.asarray(v[k]) for k in ("phi", "alpha", "bias")}

    def out_of(x, f, hc):
        def one(xs):
            return ref.hyper_connection(
                xs.reshape(-1, N, C), hc, lambda u: (u @ f, None), 1e-6, 20,
                1e-6, (-30.0, 30.0))[0].reshape(-1, N * C)
        return jnp.stack([one(xs) for xs in x])

    with jax.default_matmul_precision("highest"):
        out, back = jax.vjp(out_of, jnp.asarray(v["x"]), jnp.asarray(v["f"]),
                            hc)
        dx, df, dhc = back(jnp.asarray(v["w"]))
    return dict(out=out, x=dx, f=df, **dhc)


def _hc_program(v, amp):
    scope, main, startup = Scope(), Program(), Program()
    with scope_guard(scope), program_guard(main, startup):
        x = layers.data("x", shape=list(v["x"].shape), dtype="float32",
                        append_batch_size=False, stop_gradient=False)
        w = layers.data("w", shape=list(v["x"].shape), dtype="float32",
                        append_batch_size=False)
        # a variable a stream; under AMP each comes out of a bf16 op, as in
        # the model
        xs = [layers.scale(v, 1.0) for v in layers.split(x, N, dim=2)]
        u, h_post, h_res = layers.hc_pre(xs, param_prefix="hc")
        y = layers.fc(u, size=C, num_flatten_dims=2, bias_attr=False,
                      param_attr=pt.ParamAttr(name="f"))
        out = layers.concat(layers.hc_post(xs, y, h_post, h_res), axis=2)
        append_backward(layers.reduce_sum(out * w))
        if amp:
            pt.amp.enable(main)
        exe = Executor()
        exe.run(startup, scope=scope, seed=1)
    for k in ("phi", "alpha", "bias"):
        scope.set_var(f"hc.{k}", jnp.asarray(v[k]))
    scope.set_var("f", jnp.asarray(v["f"]))
    names = {"x": grad_var_name("x"), "f": grad_var_name("f"),
             "phi": grad_var_name("hc.phi"),
             "alpha": grad_var_name("hc.alpha"),
             "bias": grad_var_name("hc.bias")}
    got = exe.run(main, feed={"x": v["x"], "w": v["w"]}, scope=scope,
                  fetch_list=[out.name, h_res.name, u.name]
                  + list(names.values()))
    return dict(zip(["out", "h_res", "u"] + list(names), got)), main


@functools.lru_cache(maxsize=None)
def _hc_run(amp):
    v = _hc_values(3)
    got, main = _hc_program(v, amp)
    return got, _hc_reference(v), main


@pytest.mark.parametrize("amp", [False, True], ids=["float32", "amp"])
@pytest.mark.parametrize("what", ["out", "x", "f", "phi", "alpha", "bias"])
def test_hc_ops_and_every_gradient_match_the_reference(what, amp):
    """``hc_pre`` -> a matmul -> ``hc_post`` over [2, 6, 4 x 32] with
    non-degenerate alpha and b: the next stream and the gradient of the
    stream, of the sublayer's weight (``y``'s gradient passes through it),
    of Phi, alpha and b.  Under AMP the streams are bf16 and the
    coefficients float32: within bf16's rounding of the float32
    reference."""
    got, want, _ = _hc_run(amp)
    tol = 2e-2 if amp else (1e-5 if what == "out" else GRAD_TOL)
    assert _rel(got[what], want[what]) <= tol, what
    if amp:       # x is the test's float32 feed: its gradient is widened
        dtype = jnp.bfloat16 if what == "out" else jnp.float32
        assert got[what].dtype == dtype, (what, got[what].dtype)


def test_under_amp_the_streams_are_bf16_and_the_maps_float32():
    got, _, main = _hc_run(True)
    assert got["u"].dtype == jnp.bfloat16
    assert got["h_res"].dtype == jnp.float32
    off = adapter.stochastic_off([got["h_res"]], N)
    assert off <= 1e-5, off


def test_h_res_is_doubly_stochastic_and_its_clamp_is_reached():
    """Rows and columns of every token's map sum to 1 within 1e-5 after 20
    iterations; one iteration leaves the columns off; with logits far
    outside the clamp the map is what the clamped logits give, finite, and
    the logits' gradient is zero where they are clamped."""
    v = _hc_values(5)
    core = functools.partial(hc_ops.hc_pre_core, n=N, rms_eps=1e-6,
                             eps=1e-6, lo=-30.0, hi=30.0)

    def fn(x, *rest, iters=20):
        return core(*jnp.split(x, N, axis=-1), *rest, iters=iters)
    args = [jnp.asarray(v[k]) for k in ("x", "phi", "alpha", "bias")]
    _, _, h_res = fn(*args)
    assert adapter.stochastic_off([h_res], N) <= 1e-5
    _, _, once = functools.partial(fn, iters=1)(*args)
    assert adapter.stochastic_off([once], N) > 1e-2
    # logits of +-200: exp overflows float32 without the clamp
    far = jnp.asarray(v["bias"]).at[2 * N:].set(
        jnp.asarray(np.where(np.eye(N).ravel() > 0, 200.0, -200.0),
                    jnp.float32))
    _, _, clamped = fn(args[0], args[1], args[2], far)
    at_clamp = jnp.asarray(v["bias"]).at[2 * N:].set(
        jnp.asarray(np.where(np.eye(N).ravel() > 0, 30.0, -30.0),
                    jnp.float32))
    zero_alpha = jnp.asarray(v["alpha"]).at[2].set(0.0)
    _, _, want = fn(args[0], args[1], zero_alpha, at_clamp)
    assert np.isfinite(np.asarray(clamped)).all()
    _close(clamped, want, 1e-6, "H_res at the clamp")
    g = jax.grad(lambda b: jnp.sum(fn(args[0], args[1], args[2], b)[2]
                                   * jnp.arange(N * N)))(far)
    assert float(jnp.abs(g[2 * N:]).max()) == 0.0


def test_one_stream_with_unit_maps_is_joyais_block():
    """``hc_mult`` 1, alpha 0 and the bias the layer starts with: ``H_pre``
    = ``H_post`` = 1 to float32's last bit and ``H_res`` = 1 - ``hc_eps``
    (a 1 x 1 map divided by itself + ``hc_eps``), and the block under
    :func:`hyper_connection` gives what the block under the plain add gives
    from the same weights, to a few ``hc_eps``."""
    cfg = toy_cfg(hc_mult=1, n_layer=1, n_dense_layer=0, rope_scaling=None)
    x = np.random.RandomState(2).randn(2, SEQ, C).astype(np.float32)
    outs, weights = [], {}
    for rule in (T.plain_residual, T.hyper_connection(cfg)):
        scope, main, startup = Scope(), Program(), Program()
        with scope_guard(scope), program_guard(main, startup):
            xv = layers.data("x", shape=list(x.shape), dtype="float32",
                             append_batch_size=False)
            if rule is T.plain_residual:
                out, _ = T.decoder_block(xv, cfg, 0)
            else:                            # a stream of one variable
                (out,), _ = T.decoder_block([xv], cfg, 0, residual=rule)
            exe = Executor()
            exe.run(startup, scope=scope, seed=4)
        for p in main.all_parameters():
            if p.name.endswith(".alpha"):
                scope.set_var(p.name, jnp.zeros(3, jnp.float32))
            elif ".hc_" not in p.name:       # the first block's weights
                weights.setdefault(p.name, scope.find_var(p.name))
                scope.set_var(p.name, weights[p.name])
        outs.append(exe.run(main, feed={"x": x}, scope=scope,
                            fetch_list=[out.name])[0])
        kinds = {op.type for op in main.global_block().ops}
        assert ("hc_pre" in kinds) == (rule is not T.plain_residual)
    _close(outs[1], outs[0], 1e-5, "one stream, unit maps")


# -- YaRN -------------------------------------------------------------------------

def test_yarns_table_is_the_formulas():
    """The published group: pairs 0 to 10 turn as they did, 23 to 31 at a
    64th, a straight ramp between (``lo`` 10, ``hi`` 23); the softmax factor
    is ``(0.1 ln 64 + 1)^2``; and the reference's table, from its own
    formulas, is the program's."""
    s = T.XingConfig.YARN
    f = rope_kernel.yarn_frequencies(64, 10000.0, s)
    plain = 10000.0 ** (-2.0 * np.arange(32) / 64)
    ratio = f / plain
    np.testing.assert_allclose(ratio[:11], 1.0, rtol=1e-6)
    np.testing.assert_allclose(ratio[23:], 1 / 64.0, rtol=1e-6)
    ramp = (np.arange(11, 23) - 10) / 13.0
    np.testing.assert_allclose(ratio[11:23], 1 - ramp + ramp / 64, rtol=1e-6)
    assert T.yarn_softmax_factor(s) == pytest.approx(
        (0.1 * np.log(64.0) + 1) ** 2)
    assert T.yarn_softmax_factor(None) == 1.0
    yarn = adapter.reference_kw(toy_cfg(rope_scaling=s))["yarn"]
    np.testing.assert_allclose(ref.yarn_frequencies(64, 10000.0, yarn), f,
                               rtol=1e-6)
    assert ref.softmax_scale(128, 64, yarn) == pytest.approx(
        192 ** -0.5 * T.yarn_softmax_factor(s))
    with pytest.raises(ValueError):
        rope_kernel.yarn_frequencies(64, 10000.0, dict(s, mscale=0.7))


@pytest.fixture
def as_on_a_tpu(monkeypatch):
    """The lowering as a TPU would choose it, the kernel interpreted."""
    monkeypatch.setattr(device, "on_tpu", lambda: True)
    monkeypatch.setattr(rope_kernel, "rope", functools.partial(
        rope_kernel.rope, interpret=True))


@pytest.mark.parametrize("form", ["kernel", "xla"])
def test_rope_by_a_frequency_table_forward_and_gradient(form, request):
    """``rope(interleaved=True, rope_scaling=...)`` over [2, 3, 16, 64]
    against the reference's published rotation by YaRN's table (the scores
    ``q k^T`` are compared: the reference permutes the pairs) and its
    gradient; the kernel form interpreted, the jnp form as a CPU lowers it;
    the counter tells the table from ``theta``."""
    if form == "kernel":
        request.getfixturevalue("as_on_a_tpu")
    s = dict(T.XingConfig.YARN, original_max_position_embeddings=8)
    rng = np.random.RandomState(1)
    q, k, w = (rng.randn(2, 3, 16, 64).astype(np.float32) for _ in range(3))
    labels = dict(form=form, pairing="interleaved", width="64",
                  frequencies="table")
    before = attention_ops.ROPE_LOWERINGS_CTR.value(**labels)
    scope, main = Scope(), Program()
    with scope_guard(scope), program_guard(main, Program()):
        qv, kv, wv = (layers.data(n, shape=list(q.shape), dtype="float32",
                                  append_batch_size=False,
                                  stop_gradient=False) for n in "qkw")
        rq, rk = (layers.rope(v, 64, 10000.0, interleaved=True,
                              rope_scaling=s) for v in (qv, kv))
        append_backward(layers.reduce_sum(rq * rk * wv))
    got = Executor().run(main, feed={"q": q, "k": k, "w": w}, scope=scope,
                         fetch_list=[rq.name, rk.name, grad_var_name("q")])
    assert attention_ops.ROPE_LOWERINGS_CTR.value(**labels) == before + 4
    yarn = adapter.reference_kw(toy_cfg(rope_scaling=s))["yarn"]
    freq = ref.yarn_frequencies(64, 10000.0, yarn)

    def turned(v):           # [b, h, t, d] -> the reference's [t, h, d] each
        return jnp.stack([ref.rope_published(
            jnp.transpose(one, (1, 0, 2)), freq) for one in v])

    def loss(qa):
        # the permutation is the same on both sides: products of pairs
        return jnp.sum(turned(qa) * turned(jnp.asarray(k))
                       * _permuted(jnp.asarray(w)))

    def _permuted(v):
        b, h, t, d = v.shape
        return jnp.transpose(
            v.reshape(b, h, t, d // 2, 2).transpose(0, 1, 2, 4, 3)
            .reshape(b, h, t, d), (0, 2, 1, 3))

    want_q = turned(jnp.asarray(q))
    _close(_permuted(jnp.asarray(got[0])), want_q, 1e-5, "rope by the table")
    _close(got[2], jax.grad(loss)(jnp.asarray(q)), 1e-4, "its gradient")
    # and the table is not theta's: the plain form differs
    plain = attention_ops._rope_xla(jnp.asarray(q), 64, 10000.0, True)
    assert _rel(plain, got[0]) > 0.1




def test_latent_attention_hands_the_flash_op_its_pieces(toy_run):
    """Since PR 48: no ``concat`` and no ``expand`` (or their grads) under
    ``mla_proj``, every flash op with its QRope and KRope slots, the grad op
    returning both gradients, and the lowerings counted at the two-product
    widths ``d_nope+d_rope/d_v``."""
    from paddle_tpu.framework import executor as E
    from paddle_tpu.ops import attention_ops as A
    cfg, ops = toy_run["cfg"], toy_run["main"].global_block().ops
    built = [E.op_scope(op) for op in ops if op.type.partition("_grad")[0]
             in ("concat", "expand") and "mla_proj" in E.op_scope(op)]
    assert not built, built
    flash = [op for op in ops if op.type == "flash_attention"]
    assert len(flash) == cfg.n_layer
    for op in flash:
        assert op.input("QRope") and op.input("KRope")
    grads = [op for op in ops if op.type == "flash_attention_grad"]
    assert len(grads) == cfg.n_layer
    for op in grads:
        assert all(op.output("IG$" + s)[0] for s in
                   ("Q", "K", "V", "QRope", "KRope")), op.outputs
    widths = f"{cfg.d_nope}+{cfg.d_rope}/{cfg.d_v}"
    assert A.FLASH_LOWERINGS_CTR.value(
        window="none", kv_groups="1", impl="jax", widths=widths,
        lse="row") + A.FLASH_LOWERINGS_CTR.value(
        window="none", kv_groups="1", impl="jax", widths=widths,
        lse="lanes") >= cfg.n_layer
    assert A.FLASH_BWD_KERNEL_CTR.value(
        kernel="jax", window="none", widths=widths) >= cfg.n_layer
    assert not A.FLASH_LOWERINGS_CTR.value(
        window="none", kv_groups="1", impl="jax", lse="row",
        widths=f"{cfg.d_nope + cfg.d_rope}/{cfg.d_v}")

# -- the whole model ---------------------------------------------------------------

def _model(cfg, seq=SEQ, seed=3, recompute=False, amp=False):
    scope, main, startup = Scope(), Program(), Program()
    with scope_guard(scope), program_guard(main, startup):
        checkpoints = [] if recompute else None
        _, parts, loss = T.build_joyai_pretrain(
            cfg, seq, checkpoints=checkpoints, fused_head=False)
        if recompute:
            stepper = opt.RecomputeOptimizer(opt.SGD(learning_rate=0.0))
            stepper._set_checkpoints(checkpoints)
            stepper.minimize(loss)
        else:
            append_backward(loss)
        if amp:
            pt.amp.enable(main)
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
    maps = [op.outputs["HRes"][0] for op in main.global_block().ops
            if op.type == "hc_pre" and not op.attrs.get("recomputed")]
    got = exe.run(main, feed=feed, scope=scope, fetch_list=[
        loss.name, parts["hidden"].name] + maps + [
        grad_var_name(n) for n in names])
    grads = dict(zip(names, map(np.asarray, got[2 + len(maps):])))
    return (scope, main, feed, float(np.asarray(got[0])), got[1],
            got[2:2 + len(maps)], grads)


@pytest.fixture(scope="module")
def toy_run():
    """One dense + two expert layers over four streams, every expert held,
    dense head: the program's loss, final-norm output, maps and gradients on
    2 x 16 tokens, and the reference's, once."""
    cfg = toy_cfg()
    scope, main, feed, loss, hidden, maps, grads = _run(cfg)
    params = _ref_params(scope, cfg)
    kw = adapter.reference_kw(cfg, 8)
    args = [jnp.asarray(feed[k]) for k in ("src_ids", "lm_label")]
    want, gref = jax.jit(jax.value_and_grad(
        lambda p, *a: ref.loss(p, *a, **kw)))(params, *args)
    sums = jax.jit(lambda p, *a: ref.batch_sums(p, *a, **kw))(params, *args)
    got_tree = adapter.reference_params(
        lambda n: grads.get(n, np.zeros(cfg.n_experts, np.float32)), cfg,
        select_bias=False)
    for blk in gref["blocks"]:
        blk.pop("select_bias", None)
    return dict(cfg=cfg, loss=loss, hidden=hidden, maps=maps, grads=grads,
                want=float(want), ref_hidden=sums["hidden"],
                off=adapter.gradient_difference(gref, got_tree), main=main)


def test_loss_and_final_norm_output_match_the_reference(toy_run):
    r = toy_run
    assert abs(r["loss"] - r["want"]) / r["want"] <= LOSS_TOL
    assert _rel(r["hidden"], r["ref_hidden"]) <= joyai_test.HIDDEN_TOL
    assert len(r["maps"]) == 6                   # two a block
    assert adapter.stochastic_off(r["maps"], N) <= 1e-5


@pytest.mark.parametrize("kind", adapter.KINDS)
def test_every_gradient_leaf_matches_the_reference(kind, toy_run):
    """Leaf by leaf against ``jax.grad`` of the reference, by the kinds the
    cell judges by; ``maps``: every hyper-connection's Phi, alpha and b."""
    together, worst, leaf = toy_run["off"][kind]
    assert worst <= GRAD_TOL, (kind, leaf, worst)
    assert leaf, kind                            # the kind has leaves
    if kind == "maps":
        g = toy_run["grads"]
        for name in ("dec_0.hc_attn.phi", "dec_2.hc_ffn.alpha",
                     "dec_1.hc_ffn.bias"):
            assert np.abs(g[name]).max() > 0, name


def test_the_recomputed_step_is_the_plain_step(toy_run):
    """``RecomputeOptimizer`` at the block outputs, all four streams of
    each: the loss and every gradient of the plain step, and both new ops
    among what is computed again."""
    from paddle_tpu.framework.recompute import RECOMPUTE_OPS_CTR as ctr
    before = {op: ctr.value(op=op) for op in ("hc_pre", "hc_post")}
    _, main, _, loss, _, _, grads = _run(toy_run["cfg"], recompute=True)
    assert loss == pytest.approx(toy_run["loss"], rel=1e-6)
    for name, g in toy_run["grads"].items():
        assert _rel(grads[name], g) <= 1e-5, name
    again = [op.type for op in main.global_block().ops
             if op.attrs.get("recomputed")]
    # two of each a block, in two of the three blocks: the last block's
    # backward comes first and reads what its forward left
    assert (again.count("hc_pre"), again.count("hc_post")) == (4, 4), again
    assert all(ctr.value(op=op) > before[op] for op in before)


# -- the share test ----------------------------------------------------------------

def test_the_shares_and_what_every_chip_computes_alike_once_are_the_layer():
    """An expert block over four streams, 8 experts: the four shares of 2
    experts (each a program holding its two, ``expert_offset`` 0, 2, 4, 6)
    give ``A + H_post^T routed_c``, where ``A`` (the streams' own mix,
    attention, the shared expert) is what every chip computes alike.  ``A``
    counted once plus the four routed parts is the uncut reference's
    layer."""
    rng = np.random.RandomState(11)
    x = rng.randn(1, SEQ, N * C).astype(np.float32)
    whole = toy_cfg(n_layer=1, n_dense_layer=0)
    values, outs = None, []
    for offset in (None, 0, 2, 4, 6):
        cfg = whole if offset is None else toy_cfg(
            n_layer=1, n_dense_layer=0, n_held=2, expert_offset=offset)
        scope, main, startup = Scope(), Program(), Program()
        with scope_guard(scope), program_guard(main, startup):
            xv = layers.data("x", shape=list(x.shape), dtype="float32",
                             append_batch_size=False)
            streams, _ = T.decoder_block(
                layers.split(xv, N, dim=2), cfg, 0,
                residual=T.hyper_connection(cfg))
            out = layers.concat(streams, axis=2)
            exe = Executor()
            exe.run(startup, scope=scope, seed=6)
        if values is None:                   # the uncut layer's weights
            _randomise(scope, main, 6)
            values = {p.name: np.asarray(scope.find_var(p.name))
                      for p in main.all_parameters()}
            params = _ref_params_of_block(values, whole)
            continue
        for name, v in values.items():
            if name.startswith("dec_0.moe.") and v.ndim == 3:
                v = v[offset:offset + 2]
            scope.set_var(name, jnp.asarray(v))
        outs.append(exe.run(main, feed={"x": x}, scope=scope,
                            fetch_list=[out.name])[0])
        if offset == 0:                      # A: this share's experts muted
            scope.set_var("dec_0.moe.down.w",
                          jnp.zeros_like(scope.find_var("dec_0.moe.down.w")))
            alike = exe.run(main, feed={"x": x}, scope=scope,
                            fetch_list=[out.name])[0]
    got = alike + sum(o - alike for o in outs)
    kw = adapter.reference_kw(whole, 8)
    kw.pop("hc_mult")                # the entry's, not a block's
    with jax.default_matmul_precision("highest"):
        want, _ = ref.block(jnp.asarray(x[0]).reshape(SEQ, N, C), params,
                            **kw)
    _close(got[0], want.reshape(SEQ, N * C), 1e-5, "shares + alike once")
    assert _rel(alike, got) > 1e-2          # the routed parts are not nothing


def _ref_params_of_block(values, cfg):
    names = {"word_embedding": np.zeros((1, 1), np.float32),
             "final_norm.w": np.zeros(1, np.float32),
             "lm_out.w": np.zeros((1, 1), np.float32)}
    return adapter.reference_params(
        lambda n: jnp.asarray(names[n] if n in names else values[n]),
        cfg)["blocks"][0]


# -- JoyAI's lowering --------------------------------------------------------------

#: sha256 of the StableHLO text of JoyAI's toy training step (one dense, one
#: expert layer and the MTP module; the loss and every parameter's gradient
#: fetched, no optimizer; CPU lowering) as PR 44 left it, taken at 40ebb42
#: with this function: ``hc_mult`` 1 and ``rope_scaling`` None leave
#: JoyAI's block (``decoder_block`` since PR 59), ``latent_attention``,
#: ``build_joyai_pretrain`` and
#: ``rope`` lowering as they did, to the byte.  The timed step's own text
#: (``tools/joyai_step_aot.py --lowered``) was compared at both commits too
#: and is the same outside the Mosaic kernels' serialized bodies, which carry
#: source lines (PERF.md section 6, PR 45).  A PR that means to change JoyAI's
#: lowering replaces the hash and says so.  PR 48 did (it read 0f228436…
#: until then): ``latent_attention`` hands the flash op its pieces (QRope,
#: KRope) and the two ``concat``s and the ``expand`` are gone from the step.
#: PR 63 did, for the toy alone (28f4c15b… until then, and still with the
#: order forced slot-minor, ``JOYAI_TOY_STEP_SLOT_MINOR``): the toy's experts
#: a token are no multiple of 8, so ``moe_ffn``'s un-sorts bring the slots
#: home slot-major (``moe_ops._sum_over_slots``); JoyAI's own eight lower as
#: they did.
JOYAI_TOY_STEP_SHA256 = (
    "ec85ee409703cb71494d8c2b56171c10b55f93c6b8330f55a683e31b12e2df9c")
JOYAI_TOY_STEP_SLOT_MINOR = (
    "28f4c15b81468b08df4f320a905ff11ce51aec3d88a21f7eef051b8e8c560876")


def _joyai_step_text():
    cfg = joyai_test.toy_cfg(n_layer=2)
    scope, main, exe, _, loss = joyai_test._model(cfg)
    feed = joyai_test.adapter.make_batch(np.random.RandomState(0), cfg, 1,
                                         joyai_test.SEQ)
    fetch = [loss.name] + [grad_var_name(p.name)
                           for p in main.all_parameters() if p.trainable]
    exe.run(main, feed=feed, scope=scope, fetch_list=fetch)
    cb = next(p for p in exe._plans.values()
              if p.cb.fetch_names == tuple(fetch)).cb
    args = ([jnp.asarray(feed[n]) for n in cb.feed_names],
            [scope.find_var(n) for n in cb.persist_ro],
            [scope.find_var(n) for n in cb.persist_rw], jnp.uint32(1))
    return re.sub(r"loc\(.*?\)", "", cb.jitted.lower(*args).as_text())


def test_joyais_step_lowers_as_it_did_before_the_new_attributes(monkeypatch):
    from paddle_tpu.ops import moe_ops
    text = _joyai_step_text()
    assert "hc_pre" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == JOYAI_TOY_STEP_SHA256
    monkeypatch.setattr(moe_ops, "_slot_major", lambda *a: False)
    assert hashlib.sha256(_joyai_step_text().encode()).hexdigest() == \
        JOYAI_TOY_STEP_SLOT_MINOR


# -- scopes and counters -------------------------------------------------------------

def test_the_new_ops_ride_their_scopes_and_are_counted(toy_run):
    from paddle_tpu.framework import executor as E
    scoped = {E.op_scope(op) for op in toy_run["main"].global_block().ops}
    for s in ("pt.fwd/hc_pre", "pt.fwd/hc_post", "pt.bwd/hc_pre_grad",
              "pt.bwd/hc_post_grad", "pt.fwd/rope/mla_proj",
              "pt.fwd/mul/mla_proj", "pt.fwd/mul/dense_ffn",
              "pt.fwd/mul/shared_expert", "pt.fwd/moe_ffn"):
        assert s in scoped, (s, sorted(scoped))
    _, main, *_ = _run(toy_run["cfg"], recompute=True)
    scoped = {E.op_scope(op) for op in main.global_block().ops}
    assert {"pt.rc/hc_pre", "pt.rc/hc_post"} <= scoped
    for op in ("hc_pre", "hc_post", "hc_pre_grad", "hc_post_grad"):
        assert hc_ops.HC_LOWERINGS_CTR.value(
            op=op, n="4", sinkhorn_iters="20", impl="xla") > 0, op
