"""LFM2-8B-A1B on the training path against the plain float32 reference of
``benchmark/reference/lfm2_8b_a1b.py``, at a toy size on the CPU: the
``short_conv`` op and its grad op against the convolution written as three
shifted multiplies (the first two positions, batch > 1, AMP); the head that
reads the embedding table (loss, and the table's gradient as the sum of the
lookup's and the head's; the ``[d, V]`` lowering's text unchanged); then the
whole model, five layers (conv and dense, attention, conv, conv, conv, the
last four expert layers): loss, final-norm output, every parameter's gradient
and each token's experts against ``jax.grad`` of the reference, every expert
held and a share held; the four shares of one layer add up to the uncut
layer; flash at score width 64 over groups of 4 against the dense-mask
oracle; the recompute fallback's loss equal to the plain step's; and the
structural faults fail a tolerance.

Tolerances as ``tests/test_olmoe.py`` sets them and for its reasons (program
and reference are float32 on the CPU and differ by summation order: loss
1e-5, each gradient 1e-4 of its largest entry).  Sizes are tiny on purpose.
"""

import hashlib
import importlib
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

import paddle_tpu as pt  # noqa: E402
import test_olmoe as olmoe_test  # noqa: E402
from benchmark.models import lfm2_8b_a1b as adapter  # noqa: E402
from benchmark.models import olmoe_1b_7b as olmoe_adapter  # noqa: E402
from benchmark.reference import lfm2_8b_a1b as ref  # noqa: E402
from paddle_tpu import layers, optimizer as opt  # noqa: E402
from paddle_tpu.framework import (Executor, Program, Scope,  # noqa: E402
                                  program_guard, scope_guard)
from paddle_tpu.framework.backward import append_backward  # noqa: E402
from paddle_tpu.framework.core import grad_var_name  # noqa: E402
from paddle_tpu.models import transformer as T  # noqa: E402
from paddle_tpu.pallas import mha_reference  # noqa: E402

F = importlib.import_module("paddle_tpu.pallas.flash_attention")
_close = olmoe_test._close
LOSS_TOL, GRAD_TOL = olmoe_test.LOSS_TOL, olmoe_test.GRAD_TOL
KINDS = ("conv", "full_attention", "conv", "conv", "conv")
SEQ = 24


def toy_cfg(**kw):
    """Groups of 4 query heads to a K/V head, as published (32 over 8); the
    published slice's kinds of layer; one leading dense layer."""
    kw = dict(dict(vocab_size=96, d_model=32, n_layer=5, n_head=8,
                   n_kv_head=2, d_head=8, d_inner=48, d_expert=24,
                   n_experts=8, top_k=3, n_dense_layer=1, layer_types=KINDS,
                   n_held=4, expert_offset=2), **kw)
    return T.Lfm2Config(**kw)


def _batch(cfg, b, seq, seed=0):
    return adapter.make_batch(np.random.RandomState(seed), cfg, b, seq)


# -- short_conv -----------------------------------------------------------------

def _conv_core(x, w):
    """The op's equations on the reference's three shifted multiplies, a
    sequence at a time: x [b, t, 3 d] -> [b, t, d]."""
    def one(xs):
        b_, c_, u = jnp.split(xs, 3, axis=-1)
        g = b_ * u
        taps = w.shape[1]
        return c_ * sum(w[:, j] * ref.shifted(g, taps - 1 - j)
                        for j in range(taps))
    return jnp.stack([one(xs) for xs in x])


def _run_short_conv(x, w, amp=False):
    """``layers.short_conv`` over ``x``: Out, d(sum of Out * probe) / d x and
    / d filter (a probe of random weights, so that no position's cotangent
    is like another's)."""
    probe = np.random.RandomState(9).randn(
        *x.shape[:2], x.shape[2] // 3).astype(np.float32)
    scope, main, startup = Scope(), Program(), Program()
    with scope_guard(scope), program_guard(main, startup):
        xv = layers.data("x", shape=list(x.shape[1:]), dtype="float32",
                         stop_gradient=False)
        pv = layers.data("probe", shape=list(probe.shape[1:]),
                         dtype="float32")
        out = layers.short_conv(xv, w.shape[1],
                                param_attr=pt.ParamAttr(name="filter"))
        loss = layers.reduce_sum(out * pv)
        append_backward(loss)
        if amp:
            pt.amp.enable(main)
        exe = Executor()
        exe.run(startup, scope=scope, seed=5)
        scope.set_var("filter", jnp.asarray(w))
        got = exe.run(main, feed={"x": x, "probe": probe}, scope=scope,
                      fetch_list=[out.name, grad_var_name("x"),
                                  grad_var_name("filter")],
                      return_numpy=False)
    return got, probe, main


@pytest.mark.parametrize("batch,taps,amp", [
    (1, 3, False), (3, 3, False), (2, 2, False), (2, 4, False), (2, 3, True)],
    ids=["one-sequence", "batch-3", "two-taps", "four-taps", "amp"])
def test_short_conv_and_its_gradient_match_three_shifted_multiplies(
        batch, taps, amp):
    rng = np.random.RandomState(batch * 10 + taps)
    t, d = 9, 8
    x = rng.randn(batch, t, 3 * d).astype(np.float32)
    w = rng.uniform(-0.6, 0.6, (d, taps)).astype(np.float32)
    (out, gx, gw), probe, main = _run_short_conv(x, w, amp)
    assert [op.type for op in main.global_block().ops
            if "short_conv" in op.type] == ["short_conv", "short_conv_grad"]
    xr = np.asarray(jnp.asarray(x, jnp.bfloat16), np.float32) if amp else x
    with jax.default_matmul_precision("highest"):
        want, (rx, rw) = jax.value_and_grad(
            lambda x, w: jnp.sum(_conv_core(x, w) * probe), (0, 1))(
                jnp.asarray(xr), jnp.asarray(w))
        want_out = _conv_core(jnp.asarray(xr), jnp.asarray(w))
    if amp:
        # the stream in bf16, the arithmetic and the filter float32; a
        # gradient in its variable's own dtype (the feed is float32 here; in
        # a model X is a bf16 projection and so is its gradient)
        assert out.dtype == jnp.bfloat16 and gx.dtype == jnp.float32
        assert gw.dtype == jnp.float32
        _close(out, want_out, 1e-2, "short_conv under AMP")
        _close(gx, rx, 2e-2, "d short_conv / d x under AMP")
        _close(gw, rw, 2e-2, "d short_conv / d filter under AMP")
        return
    assert out.dtype == jnp.float32
    _close(out, want_out, 1e-6, "short_conv")
    _close(gx, rx, 1e-5, "d short_conv / d x")
    _close(gw, rw, 1e-5, "d short_conv / d filter")
    # the first positions see zeros before the sequence starts: position 0
    # the last tap alone, position 1 the last two
    b_, c_, u = np.split(x, 3, axis=-1)
    g = b_ * u
    np.testing.assert_allclose(np.asarray(out)[:, 0],
                               c_[:, 0] * w[:, -1] * g[:, 0], rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(out)[:, 1],
        c_[:, 1] * (w[:, -2] * g[:, 0] + w[:, -1] * g[:, 1]), rtol=1e-5,
        atol=1e-6)
    # causal: a later position's input moves no earlier output
    later = x.copy()
    later[:, 5:] += 1.0
    (moved, _, _), _, _ = _run_short_conv(later, w)
    np.testing.assert_array_equal(np.asarray(moved)[:, :5],
                                  np.asarray(out)[:, :5])


def test_short_conv_infers_its_shape_at_build():
    main = Program()
    with program_guard(main, Program()):
        xv = layers.data("x", shape=[7, 30], dtype="float32")
        out = layers.short_conv(xv, 3)
    assert tuple(out.shape) == (-1, 7, 10) and out.dtype == "float32"
    w, = main.all_parameters()
    assert tuple(w.shape) == (10, 3)
    with pytest.raises(ValueError):
        with program_guard(Program(), Program()):
            layers.short_conv(layers.data("x", shape=[7, 31],
                                          dtype="float32"))


# -- the head that reads the table ---------------------------------------------------

def _tied_program(vocab, d, seq, tied, amp=False):
    scope, main, startup = Scope(), Program(), Program()
    with scope_guard(scope), program_guard(main, startup):
        ids = layers.data("ids", shape=[seq], dtype="int64")
        label = layers.data("label", shape=[seq], dtype="int64")
        x = layers.embedding(ids, size=[vocab, d],
                             param_attr=pt.ParamAttr(name="table"))
        h = layers.tanh(x)
        table = main.global_block().var("table")
        loss = layers.reduce_sum(layers.fused_lm_head_ce(
            h, vocab, label, bias_attr=False, ignore_index=0, chunk_size=8,
            param_attr=pt.ParamAttr(name="head.w"),
            table=table if tied else None))
        append_backward(loss)
        if amp:
            pt.amp.enable(main)
        exe = Executor()
        exe.run(startup, scope=scope, seed=5)
    return scope, main, exe, loss


def test_the_tied_head_reads_the_table_and_sums_its_two_gradients():
    from paddle_tpu.ops.nn_ops import TIED_HEAD_LOWERINGS_CTR as ctr
    vocab, d, seq = 40, 16, 12
    rng = np.random.RandomState(2)
    ids = rng.randint(1, vocab, (2, seq + 1))
    feed = {"ids": ids[:, :-1].astype("int64"),
            "label": ids[:, 1:].astype("int64")}
    before = ctr.value(table_reads="2")
    scope, main, exe, loss = _tied_program(vocab, d, seq, tied=True)
    table = rng.randn(vocab, d).astype(np.float32) * 0.5
    scope.set_var("table", jnp.asarray(table))
    got, g = exe.run(main, feed=feed, scope=scope,
                     fetch_list=[loss.name, grad_var_name("table")])
    assert [p.name for p in main.all_parameters()] == ["table"]
    head, = [op for op in main.global_block().ops
             if op.type == "fused_lm_head_ce"]
    assert head.attrs["w_layout"] == "vd" and head.attrs["table_reads"] == 2
    assert head.input("W") == ["table"]
    assert ctr.value(table_reads="2") > before

    def want(table):
        # the op multiplies in bf16, as its [d, V] lowering always has
        h = jnp.tanh(table[feed["ids"]]).astype(jnp.bfloat16)
        logits = jnp.einsum("btd,vd->btv", h, table.astype(jnp.bfloat16),
                            preferred_element_type=jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.sum(jnp.take_along_axis(
            logp, feed["label"][..., None], axis=-1))
    ref_loss, ref_g = jax.value_and_grad(want)(jnp.asarray(table))
    assert abs(float(got) - float(ref_loss)) <= 1e-4 * float(ref_loss)
    _close(g, ref_g, 2e-2, "d loss / d table: lookup's + head's")
    # the head's part alone is not the sum: rows no id looked up get the
    # head's gradient only, rows looked up get both
    unseen = np.setdiff1d(np.arange(vocab), feed["ids"].ravel())
    assert len(unseen) and np.abs(np.asarray(g)[unseen]).max() > 0


def test_the_untied_head_is_another_model_with_a_weight_of_its_own():
    scope, main, exe, loss = _tied_program(40, 16, 12, tied=False)
    assert sorted(p.name for p in main.all_parameters()) == ["head.w",
                                                             "table"]
    head, = [op for op in main.global_block().ops
             if op.type == "fused_lm_head_ce"]
    assert "w_layout" not in head.attrs and "table_reads" not in head.attrs
    assert tuple(main.global_block().var("head.w").shape) == (16, 40)


#: sha256 of the StableHLO text of ``fused_lm_head_ce``'s [d, V] lowering with
#: its gradient (``_untied_head_text``) at the parent of PR 40, a4fd8fe: the
#: attribute ``w_layout`` absent, the lowering is that commit's to the byte
UNTIED_HEAD_SHA256 = (
    "9d62547505db37091369428ba1270f2d331f2053e3e09a656fd494702762c666")


def _untied_head_text():
    from paddle_tpu.framework import registry
    info = registry.get_op_info("fused_lm_head_ce")

    class Ctx:
        is_abstract, amp, mesh = False, False, None

    def head(x, w, label):
        return info.lower(Ctx(), {"X": [x], "W": [w], "Label": [label]},
                          {"ignore_index": 0, "chunk_size": 8})["Loss"][0]
    shapes = (jax.ShapeDtypeStruct((2, 12, 16), jnp.float32),
              jax.ShapeDtypeStruct((16, 40), jnp.float32),
              jax.ShapeDtypeStruct((2, 12), jnp.int32))
    step = jax.jit(lambda x, w, lab: jax.value_and_grad(
        lambda x, w: jnp.sum(head(x, w, lab)), (0, 1))(x, w))
    return re.sub(r"loc\(.*?\)", "", step.lower(*shapes).as_text())


def test_the_d_by_v_lowering_is_the_parents_to_the_byte():
    text = _untied_head_text()
    assert hashlib.sha256(text.encode()).hexdigest() == UNTIED_HEAD_SHA256


# -- flash at score width 64 over groups of 4 ---------------------------------------

@pytest.mark.parametrize("impl", ["fused", "split", None])
def test_flash_at_width_64_over_groups_of_4_matches_the_oracle(impl):
    """8 query heads over 2 K/V heads, heads 64 wide, T 64 in blocks of 16,
    the whole causal half: forward and dQ, dK, dV of the Pallas kernels in
    interpret mode (``None``: the blockwise jax fallback) against
    ``mha_reference``."""
    rng = np.random.RandomState(0)
    q, k, v, w = [jnp.asarray(rng.randn(1, n, 64, 64).astype(np.float32))
                  for n in (8, 2, 2, 8)]

    def value_and_grads(fn):
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(
                lambda q, k, v: jnp.sum(w * fn(q, k, v)), (0, 1, 2))(q, k, v)
    got, g_got = value_and_grads(lambda q, k, v: F.flash_attention(
        q, k, v, causal=True, block_q=16, block_k=16, bwd_impl=impl,
        interpret=impl is not None))
    want, g_want = value_and_grads(
        lambda q, k, v: mha_reference(q, k, v, causal=True))
    assert abs(float(got - want)) <= 1e-4 * abs(float(want)) + 1e-4
    for a, b, name in zip(g_got, g_want, "qkv"):
        _close(a, b, 1e-5, f"width 64, groups of 4, d / d {name}")


def test_the_fused_backward_runs_at_width_64_and_16384():
    """The d <= 64 table's 16384 row gives the blocks; the kernel that runs
    is reckoned from the shapes: a head's [16384, 64] dQ accumulator fits
    VMEM, so nothing asked is the fused kernel (until PR 40 it was the split
    kernels: the kernel the row's missing name asked for kept 4.3 GB of
    partials)."""
    q = jax.ShapeDtypeStruct((1, 32, 16384, 64), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 8, 16384, 64), jnp.bfloat16)
    statics = F._statics(q, kv, kv, True, None, None, None, None, None, None,
                         False, None)
    assert statics[2:6] == (1024, 1024, (1024, 1024), None)
    assert statics[8] == 4
    qc = jax.ShapeDtypeStruct((32, 16384, 64), jnp.bfloat16)
    kc = jax.ShapeDtypeStruct((8, 16384, 64), jnp.bfloat16)
    for asked in (None, "fused"):
        assert F._bwd_kernel_name(qc, kc, kc, 1024, 1024, asked) == "fused"
    assert F._bwd_kernel_name(qc, kc, kc, 1024, 1024, "split") == "split"
    assert F._BWD_DEFAULTS[8192] == (1024, 512)
    assert F._fused_vmem_bytes(16384, 64, 64, 1024, 1024, 2) \
        <= F._FUSED_VMEM_SHARE * F._VMEM_BYTES


# -- the whole model ---------------------------------------------------------------

def _model(cfg, seq, amp=False, seed=3, fused_head=False, recompute=False):
    scope, main, startup = Scope(), Program(), Program()
    with scope_guard(scope), program_guard(main, startup):
        checkpoints = [] if recompute else None
        _, parts, loss = T.build_lfm2_pretrain(
            cfg, seq, fused_head=fused_head, attn_impl="base",
            checkpoints=checkpoints)
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
    # norm scales start at 1: they would hide a norm that read the wrong
    # tensor; the router's N(0, 0.02) leaves every score near 1 / 2
    rng = np.random.RandomState(seed)
    for p in main.all_parameters():
        if p.name.endswith((".ln1.w", ".ln2.w", "_norm.w")):
            scope.set_var(p.name, jnp.asarray(
                rng.uniform(0.5, 1.5, p.shape).astype(np.float32)))
        elif p.name.endswith(".router.w"):
            scope.set_var(p.name, jnp.asarray(
                rng.randn(*p.shape).astype(np.float32) * 0.5))
    return scope, main, exe, parts, loss


def _ref_params(scope, cfg):
    return adapter.reference_params(
        lambda n: jnp.asarray(scope.find_var(n), jnp.float32), cfg)


def _ref_value_and_grad(cfg, params, feed, module=ref):
    """Jitted: eager, the reference's thousand small ops cost ten seconds."""
    kw = adapter.reference_kw(cfg, q_block=8)
    want, g = jax.jit(jax.value_and_grad(
        lambda p, ids, lab: module.loss(p, ids, lab, **kw)))(
            params, jnp.asarray(feed["src_ids"]),
            jnp.asarray(feed["lm_label"]))
    for blk in g["blocks"]:
        blk.pop("select_bias", None)
    return want, g


def _program_run(cfg, seq, feed, seed=3, amp=False, fused_head=False,
                 recompute=False):
    """Loss, final-norm output, every trained parameter's gradient, each
    expert layer's ExpertLoad and TopExperts; and the reference's
    parameters."""
    scope, main, exe, parts, loss = _model(cfg, seq, amp=amp, seed=seed,
                                           fused_head=fused_head,
                                           recompute=recompute)
    names = [p.name for p in main.all_parameters() if p.trainable]
    loads = [v.name for v in parts["expert_load"]]
    tops = [op.outputs["TopExperts"][0] for op in main.global_block().ops
            if op.type == "moe_ffn"
            and not op.output("Out")[0].endswith("@RECOMPUTE")]
    params = _ref_params(scope, cfg)
    got = exe.run(main, feed=feed, scope=scope, fetch_list=[
        loss.name, parts["hidden"].name] + [grad_var_name(n) for n in names]
        + loads + tops)
    n = len(names)
    return {"loss": float(np.asarray(got[0])), "hidden": np.asarray(got[1]),
            "grads": dict(zip(names, map(np.asarray, got[2:2 + n]))),
            "loads": [np.asarray(v) for v in got[2 + n:2 + n + len(loads)]],
            "tops": np.stack([np.asarray(v).reshape(-1, cfg.top_k)
                              for v in got[2 + n + len(loads):]]),
            "params": params, "main": main}


def _grads_in_reference_layout(run, cfg):
    return adapter.reference_params(run["grads"].__getitem__, cfg,
                                    select_bias=False)


def _against_the_reference(cfg, feed, run, loss_tol, grad_tol, module=ref):
    want, gref = _ref_value_and_grad(cfg, run["params"], feed, module)
    assert abs(run["loss"] - float(want)) / float(want) <= loss_tol, \
        (run["loss"], want)
    got = _grads_in_reference_layout(run, cfg)
    flat_ref = jax.tree_util.tree_flatten_with_path(gref)[0]
    flat_got = jax.tree_util.tree_leaves(got)
    assert len(flat_ref) == len(flat_got)
    for (path, r), g in zip(flat_ref, flat_got):
        _close(g, r, grad_tol, f"d loss / d {jax.tree_util.keystr(path)}")
    _, ref_top, per_token = adapter.reference_loss(
        ref, run["params"], feed, cfg, hidden=run["hidden"], q_block=8)
    return ref_top, per_token


@pytest.fixture(scope="module")
def toy_run():
    """Five layers, a share of the experts held, dense head over the table:
    the program's readings on 2 x 24 tokens, once."""
    cfg = toy_cfg()
    feed = _batch(cfg, 2, SEQ)
    return cfg, feed, _program_run(cfg, SEQ, feed)


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
    # one table: no head weight, and the table's leaf is both readers' sum
    assert "lm_out.w" not in run["grads"]
    assert adapter.table_reads(run["main"]) == ["lookup_table", "matmul"]


@pytest.mark.parametrize("seed", [3, 11])
@pytest.mark.parametrize("held,offset", [(8, 0), (4, 2)],
                         ids=["all-held", "a-share"])
def test_under_amp_the_step_stays_within_bf16s_reach(seed, held, offset):
    """bf16 activations and expert rows, float32 router, norms and
    convolution arithmetic, the fused head over the table: loss, final-norm
    output and all leaves together near the float32 reference."""
    cfg = toy_cfg(n_held=held, expert_offset=offset)
    feed = _batch(cfg, 2, SEQ, seed)
    run = _program_run(cfg, SEQ, feed, seed=seed, amp=True, fused_head=True)
    assert adapter.table_reads(run["main"]) == ["lookup_table",
                                                "fused_lm_head_ce"]
    want, gref = _ref_value_and_grad(cfg, run["params"], feed)
    assert abs(run["loss"] - float(want)) / float(want) <= 5e-3
    off = adapter.gradient_difference(
        jax.tree_util.tree_map(np.asarray, gref),
        _grads_in_reference_layout(run, cfg))
    assert off["all"] <= 0.05, off
    _, _, per_token = adapter.reference_loss(
        ref, run["params"], feed, cfg, hidden=run["hidden"], q_block=8)
    assert olmoe_adapter.hidden_difference(per_token) <= 0.05


def test_the_recompute_fallback_reads_the_plain_steps_loss_and_gradients():
    """ISSUE 40's one fallback: checkpoints at the five block outputs and
    nothing finer; the backward runs each block's forward again (its
    ``short_conv`` among it, under the ``rc`` role) and the loss and every
    gradient are the plain step's."""
    from paddle_tpu.framework.executor import op_scope
    cfg = toy_cfg()
    feed = _batch(cfg, 2, SEQ)
    plain = _program_run(cfg, SEQ, feed)
    again = _program_run(cfg, SEQ, feed, recompute=True)
    assert again["loss"] == plain["loss"]
    for name, g in plain["grads"].items():
        _close(again["grads"][name], g, 1e-6, f"recomputed d loss / d {name}")
    ops = again["main"].global_block().ops
    convs = [op for op in ops if op.type == "short_conv"]
    clones = [op for op in convs
              if op.output("Out")[0].endswith("@RECOMPUTE")]
    assert len(convs) - len(clones) == 4 and len(clones) >= 3
    assert {op_scope(op) for op in clones} == {"pt.rc/short_conv/"
                                               "conv_operator"}
    assert {op_scope(op) for op in convs if op not in clones} \
        == {"pt.fwd/short_conv/conv_operator"}
    assert {op_scope(op) for op in ops if op.type == "short_conv_grad"} \
        == {"pt.bwd/short_conv_grad/conv_operator"}


# -- the share test ------------------------------------------------------------------

def _moe_weights(rng, d, e_total, held, f):
    return {"moe.router.w": rng.randn(d, e_total).astype(np.float32) * 0.5,
            "moe.gate.w": rng.randn(held, d, f).astype(np.float32) * 0.3,
            "moe.up.w": rng.randn(held, d, f).astype(np.float32) * 0.3,
            "moe.down.w": rng.randn(held, f, d).astype(np.float32) * 0.3}


def _run_share(x, w, e_total, k, f, offset):
    held = w["moe.gate.w"].shape[0]

    def build():
        xv = layers.data("x", shape=list(x.shape[1:]), dtype="float32",
                         stop_gradient=False)
        out, _, _, load = layers.moe_ffn(
            xv, e_total, k, f, norm_topk_prob=True, score_func="sigmoid",
            select_bias=True, norm_eps=1e-6, route_scale=1.0, num_held=held,
            expert_offset=offset)
        return [out, load], w
    out, load, _ = olmoe_test._run_op(build, {"x": x}, ["x"])
    return out, load


def test_four_shares_of_eight_experts_are_the_uncut_layer():
    """The share test: the parts that the 4 chips' ``moe_ffn`` ops give (8
    of 32 experts each, the router over all 32, sigmoid scores renormalised
    over the 4 kept with 1e-6), added up, are the uncut reference's expert
    layer; there is no shared expert, so nothing is counted once; one chip
    alone is a part, not the layer."""
    rng = np.random.RandomState(21)
    b, t, d, e, k, f = 1, 12, 16, 32, 4, 12
    x = rng.randn(b, t, d).astype(np.float32)
    whole = _moe_weights(rng, d, e, e, f)
    total = 0.0
    for chip in range(4):
        w = dict(whole, **{n: whole[n][8 * chip:8 * chip + 8]
                           for n in ("moe.gate.w", "moe.up.w", "moe.down.w")})
        out, load = _run_share(x, w, e, k, f, 8 * chip)
        assert int(load.sum()) == b * t * k
        total = total + out.reshape(b * t, d)
    blk = {"router_w": whole["moe.router.w"],
           "select_bias": jnp.zeros(e, jnp.float32),
           "gate_w": whole["moe.gate.w"], "up_w": whole["moe.up.w"],
           "down_w": whole["moe.down.w"]}
    with jax.default_matmul_precision("highest"):
        want, _ = ref.routed_experts(jnp.asarray(x).reshape(b * t, d), blk,
                                     k, 1.0)
    _close(total, want, 1e-5, "4 shares")
    assert np.abs(out.reshape(b * t, d) - np.asarray(want)).max() > 1e-2


# -- planted faults --------------------------------------------------------------------

def _conv_with(monkeypatch, core):
    """``ref.short_conv`` with another core ``(B, C, u, w) -> [T, d]``."""
    def short_conv(z, blk):
        b_, c_, u = jnp.split(z @ blk["in_w"], 3, axis=-1)
        return core(b_, c_, u, blk["conv_w"]) @ blk["out_w"]
    monkeypatch.setattr(ref, "short_conv", short_conv)


def _taps(g, w, back):
    return sum(w[:, j] * ref.shifted(g, back(j)) for j in range(w.shape[1]))


def _conv_shifted_by_one(monkeypatch):
    _conv_with(monkeypatch, lambda b_, c_, u, w: c_ * _taps(
        b_ * u, w, lambda j: w.shape[1] - j))


def _filter_reversed(monkeypatch):
    _conv_with(monkeypatch, lambda b_, c_, u, w: c_ * _taps(
        b_ * u, w, lambda j: j))


def _gates_swapped(monkeypatch):          # C gates the input, B the output
    _conv_with(monkeypatch, lambda b_, c_, u, w: b_ * _taps(
        c_ * u, w, lambda j: w.shape[1] - 1 - j))


def _silu_on_the_gate(monkeypatch):
    _conv_with(monkeypatch, lambda b_, c_, u, w: jax.nn.silu(c_) * _taps(
        b_ * u, w, lambda j: w.shape[1] - 1 - j))


def _table_untied(monkeypatch):
    real = ref.batch_sums

    def batch_sums(params, *a, **kw):
        # a head of its own: the table's transpose at the start, but a leaf
        # of its own, so the table's gradient loses the head's part
        return real(dict(params, head_w=jax.lax.stop_gradient(
            params["wte"].T)), *a, **kw)
    monkeypatch.setattr(ref, "batch_sums", batch_sums)


def _bias_added_to_the_gates(monkeypatch):
    real = ref.route

    def route(m, blk, top_k, route_scale):
        s = jax.nn.sigmoid(m.astype(jnp.float32)
                           @ blk["router_w"].astype(jnp.float32))
        biased = s + 0.3 * jnp.cos(jnp.arange(s.shape[-1]))
        _, top_e = jax.lax.top_k(biased, top_k)
        chosen = jnp.sum(jax.nn.one_hot(top_e, s.shape[-1], dtype=s.dtype), 1)
        kept = biased * chosen               # the bias in the weights too
        w = kept / (jnp.sum(kept, axis=-1, keepdims=True) + ref.NORM_EPS)
        return w * route_scale, real(m, blk, top_k, route_scale)[1]
    monkeypatch.setattr(ref, "route", route)


def _no_renormalisation(monkeypatch):
    def route(m, blk, top_k, route_scale):
        s = jax.nn.sigmoid(m.astype(jnp.float32)
                           @ blk["router_w"].astype(jnp.float32))
        _, top_e = jax.lax.top_k(s + blk["select_bias"], top_k)
        chosen = jnp.sum(jax.nn.one_hot(top_e, s.shape[-1], dtype=s.dtype), 1)
        return s * chosen * route_scale, top_e
    monkeypatch.setattr(ref, "route", route)


def _attention_with(monkeypatch, change):
    real = ref.attention

    def attention(z, blk, *a):
        return real(z, change(blk), *a)
    monkeypatch.setattr(ref, "attention", attention)


def _qk_norm_missing(monkeypatch):
    _attention_with(monkeypatch, lambda blk: dict(
        blk, q_norm_w=jnp.ones_like(blk["q_norm_w"]) * 3.0))


def _rope_missing(monkeypatch):
    monkeypatch.setattr(ref, "rope", lambda x, theta: x)


FAULTS = {
    "the convolution shifted by one": _conv_shifted_by_one,
    "the filter's taps reversed": _filter_reversed,
    "the two gates swapped": _gates_swapped,
    "an activation on the output gate": _silu_on_the_gate,
    "the table untied": _table_untied,
    "the bias added to the gates": _bias_added_to_the_gates,
    "the kept scores not renormalised": _no_renormalisation,
    "the per-head QK-norm's scale lost": _qk_norm_missing,
    "no rotary embedding": _rope_missing,
}


@pytest.mark.parametrize("fault", FAULTS)
def test_the_tolerance_catches(fault, monkeypatch, toy_run):
    """A reference that differs from the program by one structural fault is
    over the tolerance on the loss or on some gradient, by ten times."""
    cfg, feed, run = toy_run
    FAULTS[fault](monkeypatch)
    if fault == "the bias added to the gates":
        # the selection bias is zero in the program: give the reference's
        # choice the program's, so the fault is the weights' alone
        pass
    with pytest.raises(AssertionError):
        _against_the_reference(cfg, feed, run, 10 * LOSS_TOL, 10 * GRAD_TOL)


def test_the_toy_run_itself_is_within_the_tolerances(toy_run):
    cfg, feed, run = toy_run
    _against_the_reference(cfg, feed, run, LOSS_TOL, GRAD_TOL)


def test_the_reference_in_bf16_is_told_from_float32(toy_run):
    """The precision below the stated one: the reference's own loss with
    every weight and activation rounded to bf16 differs from the float32
    program by more than the float32 tolerance allows."""
    cfg, feed, run = toy_run
    low = jax.tree_util.tree_map(
        lambda a: jnp.asarray(a, jnp.bfloat16), run["params"])
    kw = adapter.reference_kw(cfg, q_block=8)
    got = float(ref.loss(low, jnp.asarray(feed["src_ids"]),
                         jnp.asarray(feed["lm_label"]), **kw))
    assert abs(got - run["loss"]) / run["loss"] > 10 * LOSS_TOL


# -- names and defaults ----------------------------------------------------------------

def test_the_block_names_its_operators_and_its_ffn(toy_run):
    """``conv_operator`` and ``attention_operator`` tags round the two kinds
    of operator, so their projections are attributed; ``dense_ffn`` round
    the leading dense layer's; grad ops inherit them."""
    cfg, _, run = toy_run
    ops = run["main"].global_block().ops
    tags = {}
    for op in ops:
        tags.setdefault(op.attrs.get("name_scope"), set()).add(op.type)
    assert {"mul", "short_conv", "mul_grad", "short_conv_grad"} \
        <= tags["conv_operator"]
    assert {"mul", "rms_norm", "rope", "softmax", "mul_grad"} \
        <= tags["attention_operator"]
    assert "short_conv" not in tags["attention_operator"]
    assert {"mul", "swish"} <= tags["dense_ffn"]
    assert "moe_ffn" in tags[None] and "lookup_table" in tags[None]
    moes = [op for op in ops if op.type == "moe_ffn"]
    assert len(moes) == 4 and all(
        op.attrs["score_func"] == "sigmoid" and op.attrs["norm_eps"] == 1e-6
        and op.attrs["expert_offset"] == 2 and op.input("SelectBias")
        and "route_scale" not in op.attrs for op in moes)


def test_the_defaults_are_the_published_config():
    cfg = T.Lfm2Config()
    assert (cfg.vocab_size, cfg.d_model, cfg.n_layer, cfg.n_head,
            cfg.n_kv_head, cfg.d_head, cfg.d_inner, cfg.d_expert,
            cfg.n_experts, cfg.top_k, cfg.n_dense_layer, cfg.conv_taps) \
        == (65536, 2048, 24, 32, 8, 64, 7168, 1792, 32, 4, 2, 3)
    assert cfg.layer_types.count("full_attention") == 6
    assert [i for i, k in enumerate(cfg.layer_types)
            if k == "full_attention"] == [2, 6, 10, 14, 18, 21]
    assert cfg.layer_types[1:6] == list(KINDS)
    assert (cfg.rms_eps, cfg.rope_theta, cfg.route_scale, cfg.n_held) \
        == (1e-5, 1e6, 1.0, 32)
