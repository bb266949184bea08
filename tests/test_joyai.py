"""JoyAI-LLM-Flash's builders (``models/transformer.py``: ``latent_attention``,
``decoder_block`` over ``JoyaiConfig``, ``build_joyai_pretrain``) at a toy
size on the CPU against the plain float32 reference (``benchmark/reference/
joyai_llm_flash.py``): latent attention alone; the share test (the routed
parts of all shares plus the shared expert once are the uncut layer); loss,
both its terms and every parameter's gradient of a 1 dense + 2 expert + MTP
model, the embedding's and the head's being the sums of their two uses; the
structural faults the limits catch; the bf16 control; the tags.  The flash
kernels' two widths and ``rope(interleaved=True)`` have ``test_flash_dv.py``.

Faults ISSUE 34 names that cannot pass silently and so are not planted: V
taken at Q's width and a rotary key per head are shape errors in the program
(``test_flash_dv.py`` holds Out and dV to V's width; the program's ``a.w`` has
one ``d_rope`` slice, not ``H``)."""

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
import test_trinity as trinity_test  # noqa: E402
from benchmark.models import joyai_llm_flash as adapter  # noqa: E402
from benchmark.models import trinity_mini as trinity_adapter  # noqa: E402
from benchmark.reference import joyai_llm_flash as ref  # noqa: E402
from benchmark.reference import trinity_mini as trinity_ref  # noqa: E402
from paddle_tpu import layers  # noqa: E402
from paddle_tpu.framework import (Executor, Program, Scope,  # noqa: E402
                                  program_guard, scope_guard)
from paddle_tpu.framework.backward import append_backward  # noqa: E402
from paddle_tpu.framework.core import grad_var_name  # noqa: E402
from paddle_tpu.models import transformer as T  # noqa: E402

_close = olmoe_test._close
LOSS_TOL, GRAD_TOL = olmoe_test.LOSS_TOL, olmoe_test.GRAD_TOL
HIDDEN_TOL = 1e-4            # |got - want| / |want| of a normed output
LAMBDA, SEQ = 0.3, 16


def toy_cfg(**kw):
    kw = dict(dict(vocab_size=96, d_model=32, n_layer=3, n_head=4,
                   q_lora_rank=24, kv_lora_rank=16, d_nope=16, d_rope=8,
                   d_v=12, d_inner=48, d_expert=24, n_experts=8, top_k=2,
                   n_dense_layer=1, n_held=4, expert_offset=2,
                   rope_theta=10000.0), **kw)
    return T.JoyaiConfig(**kw)


def _randomise_norms(scope, main, seed):
    # norm scales start at 1 and the bias at 0: they would hide a norm
    # dropped or over the wrong axis and a bias that reached the weights
    rng = np.random.RandomState(seed)
    for p in main.all_parameters():
        if p.name.endswith(("ln1.w", "ln2.w", "norm.w")):
            scope.set_var(p.name, jnp.asarray(
                rng.uniform(0.5, 1.5, p.shape).astype(np.float32)))
        elif p.name.endswith(".select_bias"):
            scope.set_var(p.name, jnp.asarray(
                rng.randn(*p.shape).astype(np.float32) * 0.1))
        elif ".moe." in p.name and not p.name.endswith("router.w"):
            # N(0, 0.02) experts add too little for a routing fault to show
            scope.set_var(p.name, jnp.asarray(
                rng.randn(*p.shape).astype(np.float32) * 0.3))


def _model(cfg, seq=SEQ, seed=3, fused_head=False):
    scope, main, startup = Scope(), Program(), Program()
    with scope_guard(scope), program_guard(main, startup):
        _, parts, loss = T.build_joyai_pretrain(cfg, seq, LAMBDA,
                                                fused_head=fused_head)
        append_backward(loss)
        exe = Executor()
        exe.run(startup, scope=scope, seed=seed)
    _randomise_norms(scope, main, seed)
    return scope, main, exe, parts, loss


def _ref_params(scope, cfg):
    return adapter.reference_params(
        lambda n: jnp.asarray(scope.find_var(n), jnp.float32), cfg)


def _feed_args(feed, mtp_label="mtp_label"):
    return [jnp.asarray(feed[k]) for k in ("src_ids", "lm_label", mtp_label)]


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


@pytest.fixture(scope="module")
def toy_run():
    """One dense + two expert layers + the MTP module, dense head: the
    program's loss, terms, two normed outputs and gradients on 2 x 16
    tokens, once."""
    cfg = toy_cfg()
    scope, main, exe, parts, loss = _model(cfg)
    feed = adapter.make_batch(np.random.RandomState(0), cfg, 2, SEQ)
    names = [p.name for p in main.all_parameters() if p.trainable]
    heads = [loss.name] + [parts[k].name for k in (
        "main_loss", "mtp_loss", "hidden", "mtp_hidden")]
    got = exe.run(main, feed=feed, scope=scope, fetch_list=heads + [
        v.name for v in parts["expert_load"]] + [
        grad_var_name(n) for n in names])
    loads, grads = got[5:8], dict(zip(names, map(np.asarray, got[8:])))
    params = _ref_params(scope, cfg)
    kw = adapter.reference_kw(cfg, 8)
    want, gref = jax.jit(jax.value_and_grad(
        lambda p, *a: ref.loss(p, *a, LAMBDA, **kw)))(params,
                                                      *_feed_args(feed))
    return (cfg, params, feed, [float(np.asarray(v)) for v in got[:3]],
            got[3:5], loads, grads, (float(want), gref))


def _forward(cfg, params, feed, lam=LAMBDA, mtp_label="mtp_label"):
    """The reference's ``(loss, main, mtp)`` and its two normed outputs,
    jitted (eager, its thousand small ops cost ten seconds) and traced anew
    on every call (a planted fault must not meet an earlier trace)."""
    kw = adapter.reference_kw(cfg, 8)
    s = jax.jit(lambda p, *a: ref.batch_sums(p, *a, **kw))(
        params, *_feed_args(feed, mtp_label))
    out = ref.loss_of_sums(s, lam)
    return [float(out[k]) for k in ("loss", "main", "mtp")], \
        [s["hidden"], s["mtp_hidden"]]


# -- the make of a batch ------------------------------------------------------------

def test_a_batch_feeds_three_tokens_a_position():
    cfg = toy_cfg()
    b = adapter.make_batch(np.random.RandomState(5), cfg, 3, 10)
    assert {k: v.shape for k, v in b.items()} == {
        "src_ids": (3, 10), "lm_label": (3, 10), "mtp_label": (3, 10)}
    assert (b["src_ids"][:, 1:] == b["lm_label"][:, :-1]).all()
    assert (b["lm_label"][:, 1:] == b["mtp_label"][:, :-1]).all()
    assert b["src_ids"].min() >= 1 and b["mtp_label"].max() < cfg.vocab_size


# -- latent attention alone ---------------------------------------------------------

def test_latent_attention_matches_the_reference():
    """Output and the input's gradient of ``latent_attention`` over [2, 16,
    32] against the reference's ``attention`` (the published rotation,
    dense masks), its parameters random, the latent norms' scales too."""
    cfg = toy_cfg()
    rng = np.random.RandomState(2)
    x = rng.randn(2, SEQ, cfg.d_model).astype(np.float32)
    w = rng.randn(2, SEQ, cfg.d_model).astype(np.float32)
    scope, main, startup = Scope(), Program(), Program()
    with scope_guard(scope), program_guard(main, startup):
        xv = layers.data("x", shape=list(x.shape), dtype="float32",
                         append_batch_size=False, stop_gradient=False)
        wv = layers.data("w", shape=list(x.shape), dtype="float32",
                         append_batch_size=False)
        out = T.latent_attention(xv, cfg, "l.attn")
        loss = layers.reduce_sum(out * wv)
        append_backward(loss)
        exe = Executor()
        exe.run(startup, scope=scope, seed=4)
    _randomise_norms(scope, main, 4)
    assert tuple(out.shape) == x.shape
    got, dx = exe.run(main, feed={"x": x, "w": w}, scope=scope,
                      fetch_list=[out.name, grad_var_name("x")])
    a = np.asarray(scope.find_var("l.attn.a.w"))
    blk = {"w_qa": a[:, :cfg.q_lora_rank], "w_kva": a[:, cfg.q_lora_rank:]}
    blk.update({k: np.asarray(scope.find_var(f"l.attn.{n}.w"))
                for k, n in (("q_norm_w", "q_norm"), ("kv_norm_w", "kv_norm"),
                             ("w_qb", "q_b"), ("w_kvb", "kv_b"),
                             ("wo", "out"))})
    blk = {k: jnp.asarray(v) for k, v in blk.items()}

    @jax.jit
    def want_of(xs):
        with jax.default_matmul_precision("highest"):
            return jnp.stack([ref.attention(
                s, blk, cfg.n_head, cfg.d_nope, cfg.d_rope, cfg.d_v,
                cfg.rms_eps, cfg.rope_theta, 8) for s in xs])
    want, back = jax.vjp(want_of, jnp.asarray(x))
    _close(got, want, 1e-5, "latent attention")
    _close(dx, back(jnp.asarray(w))[0], 1e-4, "latent attention d / d x")
    tags = {op.attrs.get("name_scope") for op in main.global_block().ops
            if not op.type.startswith("flash_attention")}
    assert tags == {"mla_proj", None}         # None: the test's own loss ops
    assert all(op.attrs.get("name_scope") is None
               for op in main.global_block().ops
               if op.type.startswith("flash_attention"))


# -- the share test -----------------------------------------------------------------

def test_four_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """The routed parts that the 4 chips' ``moe_ffn`` ops give (8 of 32
    experts each, scale 2.5), added up, plus the shared expert counted once,
    are the uncut reference layer's output."""
    rng = np.random.RandomState(7)
    b, t, d, e, k, f = 1, 12, 16, 32, 4, 8
    x = rng.randn(b, t, d).astype(np.float32)
    w = trinity_test._moe_weights(rng, d, e, e, f)
    shared = {n: jnp.asarray(rng.randn(*s).astype(np.float32) * 0.3)
              for n, s in (("shared_gate", (d, f)), ("shared_up", (d, f)),
                           ("shared_down", (f, d)))}
    total = np.zeros((b * t, d), np.float32)
    for chip in range(4):
        part = dict(w, **{n: w[n][8 * chip:8 * chip + 8]
                          for n in ("moe.gate.w", "moe.up.w", "moe.down.w")})
        out, load, _ = trinity_test._run_share(x, part, e, k, f, 8 * chip,
                                               scale=2.5, backward=False)
        assert int(load.sum()) == b * t * k
        total = total + out.reshape(b * t, d)
    xs = jnp.asarray(x).reshape(b * t, d)
    blk = dict(trinity_test._blk(w), **shared)
    with jax.default_matmul_precision("highest"):
        routed, _ = ref.routed_experts(xs, blk, k, 2.5, 0)
        want = ref.gated(xs, *(shared[n] for n in (
            "shared_gate", "shared_up", "shared_down"))) + routed
        got = total + np.asarray(ref.gated(xs, *(shared[n] for n in (
            "shared_gate", "shared_up", "shared_down"))))
    _close(got, want, 1e-5, "four shares + the shared expert once")


# -- the whole model ----------------------------------------------------------------

def test_loss_both_terms_and_every_gradient_match_the_reference(toy_run):
    cfg, params, feed, got, hidden, loads, grads, (want, gref) = toy_run
    terms, ref_hidden = _forward(cfg, params, feed)
    assert abs(got[0] - float(want)) / float(want) <= LOSS_TOL
    for g, w in zip(got, terms):
        assert abs(g - w) / w <= LOSS_TOL, (got, terms)
    assert got[0] == pytest.approx(got[1] + LAMBDA * got[2], rel=1e-6)
    for g, w in zip(hidden, ref_hidden):
        assert _rel(g, w) <= HIDDEN_TOL
    got_tree = adapter.reference_params(
        lambda n: grads.get(n, np.zeros(cfg.n_experts, np.float32)), cfg)
    off = trinity_adapter.gradient_difference(gref, got_tree)
    assert max(off[k][0] for k in ("rest", "experts", "router")) <= GRAD_TOL, off
    for name in ("word_embedding", "lm_out.w", "mtp_0.eh_proj.w",
                 "dec_1.attn.a.w", "mtp_0.attn.kv_b.w"):
        assert np.abs(grads[name]).max() > 0, name
    rows = 2 * SEQ * cfg.top_k
    assert len(loads) == 3 and all(
        np.asarray(v).shape == (8,) and int(np.asarray(v).sum()) == rows
        for v in loads)


def test_the_embedding_and_the_head_gradients_are_the_sums_of_two_uses(
        toy_run):
    """``word_embedding`` and ``lm_out.w`` are each read by two ops: the
    program's gradient is that of both uses together (``backward.py``'s
    rename + ``sum``; ``jax.grad`` of the reference, which reads each array
    twice), and the main model's use alone (the reference without the
    module: what an untied or forgotten second use would leave) is far
    from it, as is what the module's use adds."""
    cfg, params, feed, _, _, _, grads, (_, gref) = toy_run
    kw = adapter.reference_kw(cfg, 8)
    alone = {k: v for k, v in params.items() if k != "mtp"}
    g_main = jax.jit(jax.grad(lambda p, *a: ref.loss(
        p, *a, LAMBDA, **kw)))(alone, *_feed_args(feed))
    for prog, key in (("word_embedding", "wte"), ("lm_out.w", "head_w")):
        _close(grads[prog], gref[key], GRAD_TOL, f"d loss / d {prog}")
        assert _rel(g_main[key], grads[prog]) > 0.1, prog
        added = grads[prog] - np.asarray(g_main[key])    # lambda x the MTP's
        assert _rel(added, grads[prog]) > 0.1, prog


# -- structural faults, planted in the reference ---------------------------------------

def _scale_from_the_value_width(monkeypatch, cfg, params):
    plain = ref.attention
    k = ((cfg.d_nope + cfg.d_rope) / cfg.d_v) ** 0.5

    def attention(a, blk, *rest):
        return plain(a, dict(blk, w_qb=blk["w_qb"] * k), *rest)
    monkeypatch.setattr(ref, "attention", attention)


def _rotate_half_pairing(monkeypatch, cfg, params):
    """The rotation without the permutation: ``i`` pairs with ``i + d/2``."""
    def rope(x, theta):
        return trinity_ref.rope(x, theta)
    monkeypatch.setattr(ref, "rope_published", rope)


def _rotation_dropped(monkeypatch, cfg, params):
    monkeypatch.setattr(ref, "rope_published", lambda x, theta: x)


def _latent_norms_dropped(monkeypatch, cfg, params):
    plain = ref.rms_norm

    def rms_norm(z, w, eps):
        if w.shape[0] in (cfg.q_lora_rank, cfg.kv_lora_rank):
            return z
        return plain(z, w, eps)
    monkeypatch.setattr(ref, "rms_norm", rms_norm)


def _renormalisation_dropped(monkeypatch, cfg, params):
    def route(m, blk, top_k, route_scale):
        s = jax.nn.sigmoid(m @ blk["router_w"])
        _, top_e = jax.lax.top_k(s + blk["select_bias"], top_k)
        chosen = jnp.sum(jax.nn.one_hot(top_e, s.shape[-1], dtype=s.dtype),
                         axis=1)
        return s * chosen * route_scale, top_e
    monkeypatch.setattr(trinity_ref, "route", route)


def _scale_dropped(monkeypatch, cfg, params):
    plain = trinity_ref.route
    monkeypatch.setattr(trinity_ref, "route",
                        lambda m, blk, k, scale: plain(m, blk, k, 1.0))


def _hidden_before_embedding(monkeypatch, cfg, params):
    """``[g | e]`` for ``[e | g]``: the two halves of ``W_eh``'s rows
    swapped."""
    eh = params["mtp"]["eh_w"]
    return dict(params, mtp=dict(params["mtp"], eh_w=jnp.concatenate(
        [eh[cfg.d_model:], eh[:cfg.d_model]])))


@pytest.mark.parametrize("fault,kw", [
    (_scale_from_the_value_width, {}), (_rotate_half_pairing, {}),
    (_latent_norms_dropped, {}), (_renormalisation_dropped, {}),
    (_hidden_before_embedding, {}),
    (None, dict(mtp_label="lm_label")),        # t_{i+1} for t_{i+2}
    (None, dict(lam=0.0)),                     # lambda dropped
    pytest.param(_scale_dropped, {}, marks=pytest.mark.slow),
    pytest.param(_rotation_dropped, {}, marks=pytest.mark.slow)])
def test_the_tolerance_catches(fault, kw, monkeypatch, toy_run):
    """Each structural fault, planted in the reference, moves the loss, one
    of its terms or a normed output by more than ten times its limit (the
    forward alone: every fault here shows there; the untied head, which
    shows in gradients only, has the test above)."""
    cfg, params, feed, got, hidden, *_ = toy_run
    if fault is not None:
        params = fault(monkeypatch, cfg, params) or params
    terms, ref_hidden = _forward(cfg, params, feed, **kw)
    loss_off = max(abs(g - w) / w for g, w in zip(got, terms) if w)
    hidden_off = max(_rel(g, w) for g, w in zip(hidden, ref_hidden))
    assert loss_off > 10 * LOSS_TOL or hidden_off > 10 * HIDDEN_TOL, \
        (fault, kw, loss_off, hidden_off)


def test_the_reference_in_bf16_is_told_from_float32(toy_run):
    """The control: the reference computed in bf16 is farther from the
    program than the limits allow; in float32 it is inside them (the test
    above the last)."""
    cfg, params, feed, got, hidden, *_ = toy_run
    p16 = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), params)
    terms, ref_hidden = _forward(cfg, p16, feed)
    loss_off = max(abs(g - w) / w for g, w in zip(got, terms))
    hidden_off = max(_rel(g, np.asarray(w, np.float32))
                     for g, w in zip(hidden, ref_hidden))
    assert loss_off > LOSS_TOL and hidden_off > 10 * HIDDEN_TOL, \
        (loss_off, hidden_off)


# -- tags and counters ---------------------------------------------------------------

def test_the_tags_ride_the_ops_and_their_grads_into_the_step():
    """``mla_proj`` over everything of latent attention but the flash op,
    ``mtp`` over the whole module (``mtp.mla_proj``, ``mtp.shared_expert``
    inside it), ``dense_ffn`` and ``shared_expert`` as Trinity tags them;
    grad ops inherit; the flash counters label the two-product score's
    widths, 16+8/12, and no concat or expand is left under ``mla_proj``."""
    from paddle_tpu.framework import executor as E
    from paddle_tpu.ops.attention_ops import FLASH_LOWERINGS_CTR as ctr
    labels = dict(window="none", kv_groups="1", impl="jax", widths="16+8/12")
    before = ctr.value(**labels)
    cfg = toy_cfg(n_layer=2)
    scope, main, exe, parts, loss = _model(cfg)
    ops = main.global_block().ops
    tags = {(op.attrs.get("name_scope"), op.type.endswith("_grad"))
            for op in ops if op.attrs.get("name_scope")}
    assert tags == {(t, g) for t in (
        "mla_proj", "dense_ffn", "shared_expert", "mtp", "mtp.mla_proj",
        "mtp.shared_expert") for g in (False, True)}
    scoped = {E.op_scope(op) for op in ops}
    for s in ("pt.fwd/mul/mla_proj", "pt.bwd/mul_grad/mtp.mla_proj",
              "pt.fwd/rope/mla_proj", "pt.bwd/rope_grad/mla_proj",
              "pt.fwd/flash_attention", "pt.fwd/flash_attention/mtp",
              "pt.bwd/flash_attention_grad/mtp", "pt.fwd/moe_ffn/mtp",
              "pt.fwd/lookup_table/mtp", "pt.fwd/mul/mtp",
              "pt.bwd/mul_grad/mtp.shared_expert", "pt.fwd/mul/dense_ffn"):
        assert s in scoped, (s, sorted(scoped))
    built = [s for s in scoped if "mla_proj" in s and s.split("/")[1] in (
        "concat", "expand", "concat_grad", "expand_grad")]
    assert not built, built
    feed = adapter.make_batch(np.random.RandomState(0), cfg, 1, SEQ)
    exe.run(main, feed=feed, scope=scope, fetch_list=[loss.name])
    assert ctr.value(**labels) == before + 3    # dense, expert, MTP blocks
