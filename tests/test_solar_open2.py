"""Solar-Open2-250B's parts (``ops/kda_ops.py``: ``kda_scan`` with its grad op
and ``kda_gate``; ``short_conv``'s ungated, activated form;
``models/transformer.py``: ``SolarOpen2Config``, ``kda_attention``,
``decoder_block`` over it, ``build_solar_open2_pretrain``) at a toy size
on the CPU against the plain float32 reference
(``benchmark/reference/solar_open2_250b.py``, whose recurrence runs token by
token): the chunked scan forward and every input's gradient at two chunk
sizes, a length that is no multiple of the chunk, and under a decay strong
enough that a quotient of cumulated decays is ``inf``; the ungated
convolution; AMP keeps the scan float32; loss and every gradient leaf of a
1 GQA + 2 KDA model; the recomputed step against the plain one; the head- and
expert-shares add up to the uncut layer; planted faults; LFM2's lowering with
the new attributes absent; scopes and counters."""

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

import test_joyai as joyai_test  # noqa: E402
import test_olmoe as olmoe_test  # noqa: E402
import paddle_tpu as pt  # noqa: E402
from benchmark.models import solar_open2_250b as adapter  # noqa: E402
from benchmark.reference import solar_open2_250b as ref  # noqa: E402
from paddle_tpu import layers, optimizer as opt  # noqa: E402
from paddle_tpu.framework import (Executor, Program, Scope,  # noqa: E402
                                  program_guard, scope_guard)
from paddle_tpu.framework.backward import append_backward  # noqa: E402
from paddle_tpu.framework.core import grad_var_name  # noqa: E402
from paddle_tpu.models import transformer as T  # noqa: E402
from paddle_tpu.ops import kda_ops, sequence_ops  # noqa: E402

_close = olmoe_test._close
_rel = joyai_test._rel
LOSS_TOL, GRAD_TOL = olmoe_test.LOSS_TOL, olmoe_test.GRAD_TOL
SEQ = 40                      # no multiple of the toy chunk 16
SCAN_SLOTS = ("Q", "K", "V", "G", "Beta")


def toy_cfg(**kw):
    kw = dict(dict(vocab_size=96, d_model=32, n_layer=3, n_head=4,
                   n_kv_head=2, n_kda_head=4, d_head=8, d_expert=24,
                   n_experts=8, top_k=2, gqa_layers=[0], kda_gate_rank=8,
                   kda_chunk=16, n_held=8, expert_offset=0), **kw)
    return T.SolarOpen2Config(**kw)


# -- kda_scan against the recurrence, token by token --------------------------------

def _scan_values(t, decay=0.3, b=2, h=3, d=16, seed=0):
    r = np.random.RandomState(seed)
    v = {s: r.randn(b, t, h, d).astype(np.float32) for s in ("Q", "K", "V")}
    v["G"] = -(np.abs(r.randn(b, t, h, d)) * decay).astype(np.float32)
    v["Beta"] = (1 / (1 + np.exp(-r.randn(b, t, h)))).astype(np.float32)
    v["W"] = r.randn(b, t, h, d).astype(np.float32)      # Out's cotangent
    return v


def _scan_reference(v, neg_eigval=True):
    """``sum(Out * W)`` and Out by the reference's own recurrence, and its
    gradients."""
    def out(q, k, vv, g, beta):
        q = ref.l2norm(q) * q.shape[-1] ** -0.5
        k = ref.l2norm(k)
        beta = beta * (2.0 if neg_eigval else 1.0)
        one = jax.vmap(lambda *a: ref.delta_rule(*a, 8), in_axes=1,
                       out_axes=1)
        return jax.vmap(one)(q, k, vv, g, beta)

    args = [jnp.asarray(v[s]) for s in SCAN_SLOTS]
    grads = jax.grad(lambda *a: jnp.sum(out(*a) * v["W"]),
                     argnums=tuple(range(5)))(*args)
    return out(*args), dict(zip(SCAN_SLOTS, grads))


def _scan_program(v, chunk, neg_eigval=True, amp=False):
    """``layers.kda_scan`` in a program with its grad op: Out and the five
    inputs' gradients under ``sum(Out * W)``."""
    scope, main = Scope(), Program()
    with scope_guard(scope), program_guard(main, Program()):
        ins = {s: layers.data(s, shape=list(v[s].shape), dtype="float32",
                              append_batch_size=False)
               for s in SCAN_SLOTS + ("W",)}
        for s in SCAN_SLOTS:
            ins[s].stop_gradient = False
        out = layers.kda_scan(*(ins[s] for s in SCAN_SLOTS), chunk=chunk,
                              neg_eigval=neg_eigval)
        loss = layers.reduce_sum(layers.cast(out, "float32") * ins["W"])
        append_backward(loss)
        if amp:
            pt.amp.enable(main)
        got = Executor().run(
            main, feed=v, scope=scope, fetch_list=[out.name] + [
                grad_var_name(ins[s].name) for s in SCAN_SLOTS])
    return got[0], dict(zip(SCAN_SLOTS, got[1:])), main


@pytest.fixture(scope="module")
def scans():
    """Each case once: the program's Out and gradients, the reference's."""
    cases = {"chunk16": (64, 16, 0.3), "chunk64": (128, 64, 0.3),
             "ragged": (100, 64, 0.3), "strong": (64, 64, 25.0)}
    out = {}
    for name, (t, chunk, decay) in cases.items():
        v = _scan_values(t, decay)
        out[name] = (_scan_program(v, chunk)[:2], _scan_reference(v), v)
    return out


@pytest.mark.parametrize("what", ("Out",) + SCAN_SLOTS)
@pytest.mark.parametrize("case", ["chunk16", "chunk64", "ragged", "strong"])
def test_kda_scan_and_every_gradient_match_the_recurrence(case, what, scans):
    """Chunks of 16 and of 64, 100 positions in chunks of 64 (padded inside),
    batch 2; ``strong``: a log-decay near -20 a position."""
    (out, grads), (want, want_g), _ = scans[case]
    got, ref_ = (out, want) if what == "Out" else (grads[what], want_g[what])
    assert np.isfinite(np.asarray(got)).all()
    _close(got, ref_, 1e-4, f"{case}: {what}")


def test_a_quotient_of_cumulated_decays_fails_where_the_differences_hold(
        scans):
    """What the op must not do: ``exp(Gam_i) / exp(Gam_j)`` under the strong
    decay is ``0 / 0``; the op's differences give the recurrence's numbers
    (the case above) and these are finite."""
    _, _, v = scans["strong"]
    assert v["G"].mean() < -15
    cum = np.exp(np.cumsum(v["G"].astype(np.float32), axis=1))
    with np.errstate(divide="ignore", invalid="ignore"):
        quotient = cum[:, 20] / cum[:, 4]
    assert not np.isfinite(quotient).all()
    diff = np.exp(np.cumsum(v["G"], axis=1)[:, 20]
                  - np.cumsum(v["G"], axis=1)[:, 4])
    assert np.isfinite(diff).all()


def test_beta_doubled_is_the_attribute_and_is_not_free():
    v = _scan_values(48)
    plain, _, _ = _scan_program(v, 16, neg_eigval=False)
    doubled, _, _ = _scan_program(v, 16, neg_eigval=True)
    _close(plain, _scan_reference(v, neg_eigval=False)[0], 1e-4, "beta once")
    assert _rel(plain, doubled) > 0.05


def test_under_amp_the_scan_and_its_gates_stay_float32():
    """Q, K and V go in as bf16 (``amp.py``: BF16_IF_BIG, those three slots),
    G and Beta stay float32, and inside everything is float32: from
    bf16-rounded Q, K, V the op gives the float32 recurrence's numbers to
    bf16's rounding of Out alone."""
    v = _scan_values(64)
    rounded = dict(v, **{s: np.asarray(jnp.asarray(v[s], jnp.bfloat16),
                                       np.float32) for s in "QKV"})
    out, grads, main = _scan_program(v, 16, amp=True)
    want, want_g = _scan_reference(rounded)
    assert out.dtype == jnp.bfloat16
    assert _rel(np.asarray(out, np.float32), want) < 4e-3
    # the log-decay's gradient comes back float32 and at float32's distance
    # from the recurrence's on the rounded streams, bf16's on dOut aside
    assert grads["G"].dtype == np.float32
    assert _rel(grads["G"], want_g["G"]) < 1e-2
    from paddle_tpu import amp
    assert "kda_scan" in amp.BF16_IF_BIG and "kda_gate" not in (
        amp.BF16_IF_BIG | amp.WHITE_LIST | amp.BLACK_LIST)
    assert amp._SLOT_RESTRICT["kda_scan"] == {"Q", "K", "V"}
    assert main._attrs["amp"] is True


# -- the ungated, activated short convolution --------------------------------------

@pytest.mark.parametrize("taps", [3, 4])
def test_the_ungated_short_conv_and_its_gradients(taps):
    r = np.random.RandomState(3)
    x = r.randn(2, 11, 6).astype(np.float32)
    w = r.randn(6, taps).astype(np.float32)
    cot = r.randn(2, 11, 6).astype(np.float32)
    scope, main, startup = Scope(), Program(), Program()
    with scope_guard(scope), program_guard(main, startup):
        xv = layers.data("x", shape=list(x.shape), dtype="float32",
                         append_batch_size=False)
        cv = layers.data("c", shape=list(x.shape), dtype="float32",
                         append_batch_size=False)
        xv.stop_gradient = False
        y = layers.short_conv(xv, taps, gated=False,
                              param_attr=pt.ParamAttr(name="filt"))
        append_backward(layers.reduce_sum(y * cv))
        exe = Executor()
        exe.run(startup, scope=scope)
        scope.set_var("filt", jnp.asarray(w))
        got = exe.run(main, feed={"x": x, "c": cot}, scope=scope,
                      fetch_list=[y.name, grad_var_name("x"),
                                  grad_var_name("filt")])

    def plain(x, w):
        return jax.nn.silu(sum(
            w[:, j] * jax.vmap(lambda g: ref.shifted(g, taps - 1 - j))(x)
            for j in range(taps)))

    want = plain(jnp.asarray(x), jnp.asarray(w))
    gx, gw = jax.grad(lambda x, w: jnp.sum(plain(x, w) * cot),
                      argnums=(0, 1))(jnp.asarray(x), jnp.asarray(w))
    for g, r_, what in zip(got, (want, gx, gw), ("out", "dx", "dfilter")):
        _close(g, r_, 1e-5, what)


def test_the_ungated_short_conv_holds_one_attribute_and_counts_as_silu():
    before = sequence_ops.SHORT_CONV_LOWERINGS_CTR.value(
        taps="4", gated="false", act="silu")
    scope, main, startup = Scope(), Program(), Program()
    with scope_guard(scope), program_guard(main, startup):
        x = layers.data("x", shape=[2, 8, 6], dtype="float32",
                        append_batch_size=False)
        y = layers.short_conv(x, 4, gated=False)
        exe = Executor()
        exe.run(startup, scope=scope)
        exe.run(main, feed={"x": np.ones((2, 8, 6), np.float32)},
                scope=scope, fetch_list=[y.name])
    op, = [op for op in main.global_block().ops if op.type == "short_conv"]
    assert op.attrs == {"gated": False}
    assert sequence_ops.SHORT_CONV_LOWERINGS_CTR.value(
        taps="4", gated="false", act="silu") == before + 1
    with program_guard(Program(), Program()):
        x = layers.data("x", shape=[2, 8, 7], dtype="float32",
                        append_batch_size=False)
        with pytest.raises(ValueError, match="three equal parts"):
            layers.short_conv(x, 3)


# -- the whole model ---------------------------------------------------------------

#: norm scales off 1, selection biases off 0 and experts large enough to
#: matter, as ``test_joyai`` (the head norm's scale with them)
_randomise = joyai_test._randomise_norms


def _model(cfg, seq=SEQ, seed=3, recompute=False, amp=False):
    scope, main, startup = Scope(), Program(), Program()
    with scope_guard(scope), program_guard(main, startup):
        checkpoints = [] if recompute else None
        _, parts, loss = T.build_solar_open2_pretrain(
            cfg, seq, checkpoints=checkpoints, fused_head=False)
        if recompute:
            stepper = opt.RecomputeOptimizer(opt.SGD(learning_rate=0.0))
            stepper._set_checkpoints(checkpoints, after_gradient=True)
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
    got = exe.run(main, feed=feed, scope=scope, fetch_list=[
        loss.name, parts["hidden"].name] + [grad_var_name(n) for n in names])
    grads = dict(zip(names, map(np.asarray, got[2:])))
    return scope, main, feed, float(np.asarray(got[0])), got[1], grads


def _reference_run(scope, cfg, feed, module=ref):
    params = _ref_params(scope, cfg)
    kw = adapter.reference_kw(cfg, 8, 8)
    args = [jnp.asarray(feed[k]) for k in ("src_ids", "lm_label")]
    want, gref = jax.jit(jax.value_and_grad(
        lambda p, *a: module.loss(p, *a, **kw)))(params, *args)
    sums = jax.jit(lambda p, *a: module.batch_sums(p, *a, **kw))(
        params, *args)
    for blk in gref["blocks"]:
        blk.pop("select_bias", None)
    return float(want), sums["hidden"], gref


@pytest.fixture(scope="module")
def toy_run():
    """One GQA + two KDA layers, every expert held, dense head: the
    program's loss, final-norm output and gradients on 2 x 40 tokens (40 is
    no multiple of the chunk 16), and the reference's, once."""
    cfg = toy_cfg()
    scope, main, feed, loss, hidden, grads = _run(cfg)
    want, ref_hidden, gref = _reference_run(scope, cfg, feed)
    got_tree = adapter.reference_params(
        lambda n: grads.get(n, np.zeros(cfg.n_experts, np.float32)), cfg,
        select_bias=False)
    return dict(cfg=cfg, scope=scope, feed=feed, loss=loss, hidden=hidden,
                grads=grads, want=want, ref_hidden=ref_hidden,
                off=adapter.gradient_difference(gref, got_tree), main=main)


def test_loss_and_final_norm_output_match_the_reference(toy_run):
    r = toy_run
    assert abs(r["loss"] - r["want"]) / r["want"] <= LOSS_TOL
    assert _rel(r["hidden"], r["ref_hidden"]) <= joyai_test.HIDDEN_TOL


@pytest.mark.parametrize("kind", adapter.KINDS)
def test_every_gradient_leaf_matches_the_reference(kind, toy_run):
    """Leaf by leaf against ``jax.grad`` of the reference, by the kinds the
    cell judges by; ``kda``: A_log, dt_bias, the filters, the gates, beta,
    the head norm."""
    together, worst, leaf = toy_run["off"][kind]
    assert worst <= GRAD_TOL, (kind, leaf, worst)
    assert leaf, kind                            # the kind has leaves
    if kind == "kda":
        g = toy_run["grads"]
        for name in ("dec_1.kda.A_log", "dec_2.kda.dt_bias",
                     "dec_1.kda.conv.filter", "dec_2.kda.o_norm.w",
                     "dec_1.kda.f_up.w", "dec_2.kda.g_up.w"):
            assert np.abs(g[name]).max() > 0, name


def test_every_kda_parameter_is_a_leaf_of_its_kind():
    names = [f"['blocks'][1]['{k}']" for k in adapter.KDA_LEAVES]
    assert {adapter.kind_of(n) for n in names} == {"kda"}
    assert adapter.kind_of("['blocks'][1]['wq']") == "rest"
    assert adapter.kind_of("['blocks'][1]['wo']") == "rest"
    assert adapter.kind_of("['blocks'][0]['w_gate']") == "rest"
    assert adapter.kind_of("['blocks'][0]['router_w']") == "router"
    assert adapter.kind_of("['blocks'][0]['up_w']") == "experts"
    assert adapter.kind_of("['blocks'][0]['shared_up']") == "rest"


def test_the_recomputed_step_is_the_plain_step(toy_run):
    """``RecomputeOptimizer`` at the block outputs: the loss and every
    gradient of the plain step, and the scan, its gate and the convolution
    among what is computed again."""
    from paddle_tpu.framework.recompute import RECOMPUTE_OPS_CTR as ctr
    ops = ("kda_scan", "kda_gate", "short_conv")
    before = {op: ctr.value(op=op) for op in ops}
    _, main, _, loss, _, grads = _run(toy_run["cfg"], recompute=True)
    assert loss == pytest.approx(toy_run["loss"], rel=1e-6)
    for name, g in toy_run["grads"].items():
        assert _rel(grads[name], g) <= 1e-5, name
    again = [op.type for op in main.global_block().ops
             if op.attrs.get("recomputed")]
    # every block: the embedding's output is a checkpoint too
    assert [again.count(op) for op in ops] == [2, 2, 2], again
    assert again.count("flash_attention") == 1
    # each block behind the gradient of its own output, the last block first:
    # the barrier of a block's input holds that gradient too
    types = [op.type for op in main.global_block().ops]
    pairs = [op for op in main.global_block().ops
             if op.type == "optimization_barrier" and len(op.inputs["X"]) == 2]
    assert len(pairs) == 3
    assert all(op.inputs["X"][1].endswith("@GRAD") for op in pairs)
    first_grad = types.index("kda_scan_grad")
    assert types[:first_grad].count("kda_scan") == 2 + 1    # forward + one
    assert all(ctr.value(op=op) > before[op] for op in before)


# -- planted faults ------------------------------------------------------------------

def _faulty(**changed):
    """The reference module with some functions replaced."""
    import types
    mod = types.ModuleType("faulty_reference")
    mod.__dict__.update({k: v for k, v in vars(ref).items()
                         if not k.startswith("__")})
    # the module's functions look their helpers up in their own globals: a
    # copy of each, over the changed namespace
    for name, fn in list(vars(mod).items()):
        if isinstance(fn, types.FunctionType):
            setattr(mod, name, types.FunctionType(
                fn.__code__, mod.__dict__, name, fn.__defaults__,
                fn.__closure__))
    for name, make in changed.items():
        setattr(mod, name, make(mod))
    return mod


def _decay_after_the_delta_step(mod):
    def delta_rule(q, k, v, g, beta, block):
        def step(s, x):
            q_t, k_t, v_t, g_t, b_t = x
            read = jnp.sum(s * k_t[:, None], axis=0)
            s = s + b_t * k_t[:, None] * (v_t - read)[None, :]
            s = jnp.exp(g_t)[:, None] * s
            return s, jnp.sum(s * q_t[:, None], axis=0)
        _, o = jax.lax.scan(step, jnp.zeros((q.shape[1], v.shape[1])),
                            (q, k, v, g, beta))
        return o
    return delta_rule


def _scalar_decay(mod):
    plain = mod.delta_rule

    def delta_rule(q, k, v, g, beta, block):
        return plain(q, k, v, jnp.broadcast_to(
            jnp.mean(g, axis=-1, keepdims=True), g.shape), beta, block)
    return delta_rule


def _conv_one_off(mod):
    plain = mod.shifted
    return lambda g, back: plain(g, back + 1)


def _rotary_on_gqa(mod):
    def attention(z, blk, d_head, q_block):
        t, half = z.shape[0], d_head // 2
        freq = 10000.0 ** (-2.0 * jnp.arange(half) / d_head)
        ang = jnp.arange(t)[:, None] * freq[None, :]
        cos, sin = (jnp.concatenate([f(ang)] * 2, -1)[:, None]
                    for f in (jnp.cos, jnp.sin))

        def turned(w):
            y = (z @ w).reshape(t, -1, d_head)
            rot = jnp.concatenate([-y[..., half:], y[..., :half]], -1)
            return (y * cos + rot * sin).reshape(t, -1)
        return _attention_from(z, turned(blk["wq"]), turned(blk["wk"]), blk,
                               d_head)
    return attention


def _attention_from(z, q, k, blk, d_head):
    """The gated softmax layer from q and k as given, dense."""
    t = z.shape[0]
    n_head, n_kv = q.shape[1] // d_head, k.shape[1] // d_head
    q = q.reshape(t, n_head, d_head)
    k = jnp.repeat(k.reshape(t, n_kv, d_head), n_head // n_kv, axis=1)
    v = jnp.repeat((z @ blk["wv"]).reshape(t, n_kv, d_head),
                   n_head // n_kv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) * d_head ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)
    return (o.reshape(t, -1) * jax.nn.sigmoid(z @ blk["w_gate"])) @ blk["wo"]


FAULTS = {
    "beta not doubled": dict(kw=dict(neg_eigval=False)),
    "the decay applied after the delta step":
        dict(delta_rule=_decay_after_the_delta_step),
    "a scalar decay a head for the per-channel one":
        dict(delta_rule=_scalar_decay),
    "the convolution one position off": dict(shifted=_conv_one_off),
    "q and k not normalised": dict(l2norm=lambda mod: lambda y: y),
    "the rotary applied to the GQA layer": dict(attention=_rotary_on_gqa),
}


@pytest.mark.parametrize("fault", [None] + sorted(FAULTS))
def test_the_forward_check_catches(fault, toy_run):
    """The program against the reference with one fault planted in the
    reference: the final-norm output, which the cell holds to
    ``hidden_relative``, leaves its limit by over ten times; as built it is
    inside."""
    cfg, scope, feed = toy_run["cfg"], toy_run["scope"], toy_run["feed"]
    changed = dict(FAULTS.get(fault, {}))
    kw = adapter.reference_kw(cfg, 8, 8)
    kw.update(changed.pop("kw", {}))
    mod = _faulty(**changed)
    sums = jax.jit(lambda p, *a: mod.batch_sums(p, *a, **kw))(
        _ref_params(scope, cfg),
        *(jnp.asarray(feed[k]) for k in ("src_ids", "lm_label")))
    off = _rel(toy_run["hidden"], sums["hidden"])
    if fault is None:
        assert off <= joyai_test.HIDDEN_TOL
    else:
        assert off > 10 * joyai_test.HIDDEN_TOL, (fault, off)


# -- the share test ----------------------------------------------------------------

def _layer_out(cfg, idx, x, values, seed=6):
    """One block's output over ``x`` from a program holding ``cfg``'s share;
    ``values(name, shape)`` gives a parameter or None (the startup
    program's)."""
    scope, main, startup = Scope(), Program(), Program()
    with scope_guard(scope), program_guard(main, startup):
        xv = layers.data("x", shape=list(x.shape), dtype="float32",
                         append_batch_size=False)
        out, _ = T.decoder_block(xv, cfg, idx, attn_impl="flash")
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


def _head_share(name, v, cfg, part, parts):
    """The slice of the uncut layer's parameter ``v`` that share ``part`` of
    ``parts`` holds: its heads' columns of every projection that is split by
    head, its heads' rows of the output projections, its experts."""
    d = cfg.d_head

    def cols(w, heads, groups):
        # [.., groups x heads x d] -> this share's heads of every group
        per = heads // parts
        g = w.reshape(*w.shape[:-1], groups, heads, d)
        return g[..., part * per:(part + 1) * per, :].reshape(
            *w.shape[:-1], groups * per * d)

    if ".moe." in name and v.ndim == 3:
        per = v.shape[0] // parts
        return v[part * per:(part + 1) * per]
    if name.endswith("attn.qkv.w"):
        dq, dkv = cfg.n_head * d, cfg.n_kv_head * d
        q, k, vv, g = np.split(v, [dq, dq + dkv, dq + 2 * dkv], axis=1)
        return np.concatenate([cols(q, cfg.n_head, 1),
                               cols(k, cfg.n_kv_head, 1),
                               cols(vv, cfg.n_kv_head, 1),
                               cols(g, cfg.n_head, 1)], axis=1)
    if name.endswith(("attn.out.w", "kda.out.w")):
        heads = cfg.n_head if "attn" in name else cfg.n_kda_head
        return cols(v.T, heads, 1).T
    h = cfg.n_kda_head
    if name.endswith("kda.in_proj.w"):
        dq, r = h * d, cfg.kda_gate_rank
        qkv, low, beta = np.split(v, [3 * dq, 3 * dq + 2 * r], axis=1)
        per = h // parts
        return np.concatenate([cols(qkv, h, 3), low,
                               beta[:, part * per:(part + 1) * per]], axis=1)
    if name.endswith("kda.conv.filter"):
        return cols(v.T, h, 3).T
    if name.endswith(("kda.f_up.w", "kda.g_up.w", "kda.dt_bias")):
        return cols(v, h, 1)
    if name.endswith("kda.A_log"):
        per = h // parts
        return v[part * per:(part + 1) * per]
    return v


@pytest.mark.parametrize("kind", ["GQA", "KDA"])
def test_the_head_and_expert_shares_add_up_to_the_uncut_layer(kind):
    """The share test of the model-configs guide, for a layer whose HEADS
    are shared: four chips, each a program holding a quarter of the layer's
    8 query heads over one of its 4 K/V heads, one of its 4 KDA heads and 2
    of its 8 experts.  The mixers' partial sums over the shares give the
    uncut ``h = x + Mixer(x)`` (the all-reduce behind the mixer is the
    deployment's); from that ``h`` the expert shares' partial sums, with the
    shared expert (what every chip computes alike) counted once, give the
    uncut reference's layer output."""
    parts = 4
    sizes = dict(n_layer=1, gqa_layers=[0] if kind == "GQA" else [])
    whole = toy_cfg(n_head=8, n_kv_head=4, n_kda_head=4, **sizes)
    x = np.random.RandomState(11).randn(1, SEQ, whole.d_model).astype(
        np.float32)
    _, values = _layer_out(whole, 0, x, None)

    def run(part, muted, x_in):
        """Share ``part``'s block output, the parameters in ``muted``
        zeroed."""
        cfg = toy_cfg(n_head=2, n_kv_head=1, n_kda_head=1, n_held=2,
                      expert_offset=2 * part, **sizes)
        return _layer_out(cfg, 0, x_in, lambda n: _head_share(
            n, values[n] * (0.0 if n.endswith(muted) else 1.0), whole, part,
            parts))[0]

    ffn, mixer_out = ("shared.down.w", "moe.down.w"), ("attn.out.w",
                                                       "kda.out.w")
    # each share's mixer alone (its FFN muted) is x + mixer_c(x)
    mix = [run(c, ffn, x) - x for c in range(parts)]
    h = x + sum(mix)
    # on the whole h, mixers muted: h + shared(h), then + routed_c(h)
    alike = run(0, mixer_out + ("moe.down.w",), h)
    routed = [run(c, mixer_out, h) - alike for c in range(parts)]
    got = alike + sum(routed)
    params = adapter.reference_params(
        lambda n: jnp.asarray(values.get(n, 0.0)), whole)["blocks"][0]
    with jax.default_matmul_precision("highest"):
        want, _ = ref.block(jnp.asarray(x[0]), params,
                            **adapter.reference_kw(whole, 8, 8))
    _close(got[0], want, 2e-5, f"{kind}: shares + alike once")
    # no share is the layer, and the routed parts are not nothing
    assert _rel(x + mix[0], h) > 1e-2 and _rel(alike, got) > 1e-3


# -- LFM2's lowering with the new attributes absent ---------------------------------

def test_lfm2s_program_holds_no_new_attribute_and_counts_as_gated():
    """``short_conv`` as LFM2 calls it: no ``gated`` in the op's
    attributes (the lowered step is the parent's to the text:
    ``tools/joyai_step_aot.py --cell lfm2 --lowered``), counted as gated."""
    before = sequence_ops.SHORT_CONV_LOWERINGS_CTR.value(
        taps="3", gated="true", act="none")
    scope, main, startup = Scope(), Program(), Program()
    with scope_guard(scope), program_guard(main, startup):
        x = layers.data("x", shape=[2, 8, 12], dtype="float32",
                        append_batch_size=False)
        y = T.short_conv_operator(x, 12, 3)
        exe = Executor()
        exe.run(startup, scope=scope)
        exe.run(main, feed={"x": np.ones((2, 8, 12), np.float32)},
                scope=scope, fetch_list=[y.name])
    op, = [op for op in main.global_block().ops if op.type == "short_conv"]
    assert "gated" not in op.attrs
    assert sequence_ops.SHORT_CONV_LOWERINGS_CTR.value(
        taps="3", gated="true", act="none") == before + 1


# -- scopes and counters -------------------------------------------------------------

def test_the_new_ops_ride_their_scopes_and_are_counted(toy_run):
    from paddle_tpu.framework import executor as E
    scoped = {E.op_scope(op) for op in toy_run["main"].global_block().ops}
    for s in ("pt.fwd/kda_scan/kda", "pt.bwd/kda_scan_grad/kda",
              "pt.fwd/kda_gate/kda", "pt.bwd/kda_gate_grad/kda",
              "pt.fwd/short_conv/kda", "pt.bwd/short_conv_grad/kda",
              "pt.fwd/mul/kda", "pt.fwd/rms_norm/kda", "pt.fwd/sigmoid/kda",
              "pt.fwd/flash_attention/attn", "pt.fwd/mul/attn",
              "pt.fwd/mul/shared_expert", "pt.fwd/moe_ffn"):
        assert s in scoped, (s, sorted(scoped))
    _, main, *_ = _run(toy_run["cfg"], recompute=True)
    scoped = {E.op_scope(op) for op in main.global_block().ops}
    assert {"pt.rc/kda_scan/kda", "pt.rc/short_conv/kda"} <= scoped
    assert kda_ops.KDA_LOWERINGS_CTR.value(
        heads="4", head_dim="8", chunk="16", impl="xla",
        neg_eigval="true") >= 4          # two layers, forward and backward
    assert sequence_ops.SHORT_CONV_LOWERINGS_CTR.value(
        taps="4", gated="false", act="silu") >= 4
