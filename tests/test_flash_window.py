"""The flash attention kernels' ``window`` and grouped K/V heads
(``paddle_tpu/pallas/flash_attention.py``) against the dense-mask oracle
``mha_reference``: forward and dQ, dK, dV of the Pallas kernels in interpret
mode (both backward implementations) and of the blockwise jax fallback, at
T 64 in blocks of 16 (the suite runs at its time limit), and the K/V blocks
the index maps name at the Trinity-Mini cell's real sizes.  And the
``flash_attention`` op of a ``Program`` with its grad op over the forward's
saved ``Out`` and ``Lse`` (``ops/attention_ops.py``): its gradients against
``jax.grad`` of the oracle, the forward half's calls in a training step, an
op without the ``Lse`` slot, and a recomputed segment around a flash
layer."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, optimizer as opt
from paddle_tpu.framework import (Executor, Program, Scope, program_guard,
                                  scope_guard)
from paddle_tpu.framework.backward import append_backward
from paddle_tpu.framework.core import grad_var_name
from paddle_tpu.models import transformer as T
from paddle_tpu.ops.attention_ops import (FLASH_BWD_KERNEL_CTR,
                                          FLASH_GRAD_LOWERINGS_CTR,
                                          FLASH_LOWERINGS_CTR)
from paddle_tpu.pallas import mha_reference

F = importlib.import_module("paddle_tpu.pallas.flash_attention")


def _close(got, want, tol, what=""):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    err = np.abs(got - want).max() / max(np.abs(want).max(), 1e-12)
    assert err <= tol, f"{what}: {err:.3e} of the largest entry > {tol}"


def _qkv(t, h=4, hk=2, d=16, seed=0):
    rng = np.random.RandomState(seed)
    return [jnp.asarray(rng.randn(1, n, t, d).astype(np.float32))
            for n in (h, hk, hk, h)]


def _value_and_grads(fn, q, k, v, w):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(w * fn(q, k, v)), (0, 1, 2))(q, k, v)


@pytest.mark.parametrize("window,impl,t,hk", [
    (8, "fused", 56, 2),    # smaller than a block (16); a padded length
    (16, "split", 64, 2),   # a block exactly
    (40, "fused", 64, 1),   # more than two blocks; one K/V head for all four
    (40, "split", 64, 2),
    (2, "fused", 72, 4),    # the diagonal and its neighbour; padded, no group
    (23, None, 64, 2),      # the blockwise jax fallback (no TPU, no interpret)
    (8, "fused", 64, 2),    # one pass, dQ resident in VMEM (PR 37)
    (16, "fused", 64, 2),
    (40, "fused", 64, 2),
    (2, "fused", 64, 2),
])
def test_window_flash_matches_the_dense_mask_oracle(window, impl, t, hk):
    """Forward and dQ, dK, dV of the Pallas kernels (interpret mode) over 4
    query heads on ``hk`` KV heads, T in blocks of 16, against
    ``mha_reference`` under the dense ``0 <= i - j < window`` mask."""
    q, k, v, w = _qkv(t, hk=hk)
    got, g_got = _value_and_grads(
        lambda q, k, v: F.flash_attention(
            q, k, v, causal=True, window=window, block_q=16, block_k=16,
            bwd_impl=impl, interpret=impl is not None), q, k, v, w)
    want, g_want = _value_and_grads(
        lambda q, k, v: mha_reference(q, k, v, causal=True, window=window),
        q, k, v, w)
    assert abs(float(got - want)) <= 1e-4 * abs(float(want)) + 1e-4
    for a, b, name in zip(g_got, g_want, "qkv"):
        _close(a, b, 1e-5, f"window {window} d / d {name}")
    if impl == "fused":                # and the split kernels, same inputs
        _, g_split = _value_and_grads(
            lambda q, k, v: F.flash_attention(
                q, k, v, causal=True, window=window, block_q=16, block_k=16,
                bwd_impl="split", interpret=True), q, k, v, w)
        for a, b, name in zip(g_got, g_split, "qkv"):
            _close(a, b, 2e-6, f"fused against split, d / d {name}")


def test_a_window_off_by_one_is_another_function():
    """The oracle itself tells ``window`` from ``window + 1`` by far more
    than the kernels' distance from it."""
    q, k, v, w = _qkv(64)
    a = mha_reference(q, k, v, causal=True, window=8)
    b = mha_reference(q, k, v, causal=True, window=9)
    assert float(jnp.max(jnp.abs(a - b))) > 1e-2


def test_a_window_as_long_as_the_sequence_is_the_causal_lowering():
    q, k, v, _ = _qkv(32)
    kw = dict(causal=True, block_q=16, block_k=16)
    plain = jax.jit(lambda q, k, v: F.flash_attention(q, k, v, **kw))
    for window in (32, 100):
        windowed = jax.jit(lambda q, k, v: F.flash_attention(
            q, k, v, window=window, **kw))
        assert windowed.lower(q, k, v).as_text() == \
            plain.lower(q, k, v).as_text()
    with pytest.raises(ValueError):
        F.flash_attention(q, k, v, window=8)          # not causal


@pytest.mark.parametrize("impl", [None, "split", "fused"])
def test_grouped_kv_heads_equal_repeated_kv_heads(impl):
    """4 query heads over 2 KV heads through the kernels' index maps against
    the same K and V repeated to 4 heads outside; dK and dV summed over each
    group (``None``: the rule, which is "fused" where it fits)."""
    q, k, v, w = _qkv(32)
    kw = dict(causal=True, window=12, block_q=16, block_k=16, interpret=True,
              bwd_impl=impl)
    got, (gq, gk, gv) = _value_and_grads(
        lambda q, k, v: F.flash_attention(q, k, v, **kw), q, k, v, w)
    rep = lambda x: jnp.repeat(x, 2, axis=1)  # noqa: E731
    want, (rq, rk, rv) = _value_and_grads(
        lambda q, k, v: F.flash_attention(q, k, v, **kw), q, rep(k), rep(v),
        w)
    fold = lambda g: g.reshape(1, 2, 2, 32, 16).sum(axis=2)  # noqa: E731
    assert abs(float(got - want)) <= 1e-5 * abs(float(want))
    _close(gq, rq, 1e-6, "dQ")
    _close(gk, fold(rk), 1e-5, "dK")
    _close(gv, fold(rv), 1e-5, "dV")


@pytest.mark.parametrize("t,bq,bk,window", [
    (8192, 1024, 1024, 2048), (8192, 1024, 512, 2048), (64, 16, 16, 8),
    (64, 16, 32, 40)])
def test_the_index_maps_name_the_bands_blocks_and_no_other(t, bq, bk, window):
    """Which K/V blocks the grid's steps name for each query block (what the
    pipeline copies): exactly the blocks that hold a visible key, and dead
    steps name a block of the band (consecutive equal names copy nothing).
    At 8192 in blocks of 1024 a window of 2048 names 3 of 8 blocks a row."""
    nq, nk = t // bq, t // bk
    copied = 0
    for i in range(nq):
        named = {int(F._live_k(i, j, window, bq, bk, 0, nk))
                 for j in range(nk)}
        rows = np.arange(i * bq, (i + 1) * bq)[:, None]
        cols = np.arange(t)[None, :]
        visible = (cols <= rows) & (rows - cols < window)
        want = {j for j in range(nk)
                if visible[:, j * bk:(j + 1) * bk].any()}
        assert named == want, (i, named, want)
        copied += len(named)
        for j in want:                          # and the transposed walk
            assert int(F._live_q(i, j, window, bq, bk, 0, nq)) == i
    if (t, bq, bk) == (8192, 1024, 1024):
        assert copied == 1 + 2 + 6 * 3          # of 36 under the diagonal


# -- the op of a Program and its grad op from the saved Out and Lse -----------

def _data(name, a, grad=True):
    return layers.data(name, shape=list(a.shape), append_batch_size=False,
                       dtype="float32", stop_gradient=not grad)


def _flash_ops(program):
    ops = program.global_block().ops
    return ([op for op in ops if op.type == "flash_attention"],
            [op for op in ops if op.type == "flash_attention_grad"])


#: name -> (heads, KV heads, Tq, Tk, the op's keyword arguments, bias shape[,
#: (d_qk, d_v)]); a ``fused_`` case asks its grad op for the fused backward
#: and runs the op's kernels as a TPU would take them, in interpret mode
GRAD_CASES = {
    "causal": (4, 4, 48, 48, dict(causal=True), None),
    "window": (4, 4, 48, 48, dict(causal=True, window=10), None),
    "grouped_kv_4_to_1": (4, 1, 48, 48, dict(causal=True, window=20), None),
    "additive_bias": (2, 2, 24, 24, dict(), (24, 24)),
    "bias_per_batch": (2, 2, 24, 24, dict(causal=True), (2, 1, 24, 24)),
    "tq_not_tk": (4, 2, 16, 40, dict(causal=True), None),
    "fused_causal": (4, 4, 48, 48, dict(causal=True), None),
    "fused_window": (4, 4, 48, 48, dict(causal=True, window=10), None),
    "fused_grouped_kv_4_to_1": (4, 1, 48, 48,
                                dict(causal=True, window=20), None),
    "fused_192_over_128": (2, 2, 32, 32, dict(causal=True), None, (192, 128)),
    "fused_padded_length": (2, 2, 40, 40, dict(causal=True), None),
    "fused_tq_not_tk": (4, 2, 16, 40, dict(causal=True), None),
}


def _kernels_in_interpret_mode(monkeypatch):
    """The flash ops' Pallas path on the CPU: the module believes it is on a
    TPU and both kernel entry points are forced to interpret mode."""
    monkeypatch.setattr(F, "on_tpu", lambda: True)
    for name, at in (("_flash_fwd_pallas", 9), ("_flash_bwd_pallas", 11)):
        def forced(*a, _real=getattr(F, name), _at=at, **kw):
            return _real(*a[:_at], True, *a[_at + 1:], **kw)
        monkeypatch.setattr(F, name, forced)


@pytest.mark.parametrize("case", sorted(GRAD_CASES))
def test_the_registered_grad_op_against_jax_grad_of_the_oracle(
        case, monkeypatch):
    """``flash_attention_grad`` as ``append_backward`` writes it (inputs Q,
    K, V, Bias where present, the forward's Out and Lse, dOut), run by the
    executor: dQ, dK, dV and dBias against ``jax.grad`` of ``mha_reference``
    in float32, within what this file holds the kernels' own vjp to; the
    ``fused_`` cases through the fused backward kernel (blocks of 16), which
    ``paddle_tpu_flash_bwd_kernel_total`` says was taken."""
    h, hk, tq, tk, kw, bias_shape, *widths = GRAD_CASES[case]
    d_qk, d_v = widths[0] if widths else (8, 8)
    fused = case.startswith("fused_")
    rng = np.random.RandomState(len(case))
    feed = {"q": rng.randn(2, h, tq, d_qk), "k": rng.randn(2, hk, tk, d_qk),
            "v": rng.randn(2, hk, tk, d_v), "w": rng.randn(2, h, tq, d_v)}
    if bias_shape:
        feed["bias"] = rng.randn(*bias_shape)
    feed = {n: a.astype(np.float32) for n, a in feed.items()}
    wrt = [n for n in ("q", "k", "v", "bias") if n in feed]
    window = kw.get("window")
    labels = dict(kernel="fused" if fused else "jax", widths=f"{d_qk}/{d_v}",
                  window="none" if window is None else str(window))
    before = FLASH_BWD_KERNEL_CTR.value(**labels)
    with scope_guard(Scope()), program_guard(Program(), Program()):
        v = {n: _data(n, a, grad=n != "w") for n, a in feed.items()}
        out = layers.flash_attention(
            v["q"], v["k"], v["v"], bias=v.get("bias"),
            **dict(kw, block_q=16, block_k=16) if fused else kw)
        loss = layers.reduce_sum(out * v["w"])
        append_backward(loss)
        (fwd,), (grad,) = _flash_ops(pt.default_main_program())
        if fused:
            grad.attrs["bwd_impl"] = "fused"
            _kernels_in_interpret_mode(monkeypatch)
        got = Executor().run(feed=feed, fetch_list=[
            out.name] + [grad_var_name(n) for n in wrt])
    assert FLASH_BWD_KERNEL_CTR.value(**labels) == before + 1
    assert grad.input("Out") == fwd.output("Out")
    assert grad.input("Lse") == fwd.output("Lse") and fwd.output("Lse")
    assert sorted(grad.inputs) == sorted(
        ["X$" + s.capitalize() for s in wrt] + ["Out", "Lse", "OG$Out"])
    assert "__fwd_type__" not in grad.attrs       # not the generic vjp

    def oracle(*args):
        a = dict(zip(wrt, args))
        b = a.get("bias")
        return mha_reference(a["q"], a["k"], a["v"],
                             bias=b[None, None] if b is not None and
                             b.ndim == 2 else b, **kw)
    args = [jnp.asarray(feed[n]) for n in wrt]
    with jax.default_matmul_precision("highest"):
        want = oracle(*args)
        g_want = jax.grad(lambda *a: jnp.sum(oracle(*a) * feed["w"]),
                          tuple(range(len(wrt))))(*args)
    _close(got[0], want, 1e-5, f"{case}: Out")
    for name, a, b in zip(wrt, got[1:], g_want):
        assert a.shape == b.shape, (name, a.shape, b.shape)
        _close(a, b, 1e-5, f"{case}: d / d {name}")


def _two_flash_layers(seq=32, checkpoints=False):
    """x -> two self-attention blocks on the flash path (4 heads over 2 KV
    heads, the second windowed) -> a scalar loss, with SGD."""
    x = layers.data("x", shape=[2, seq, 32], append_batch_size=False,
                    dtype="float32")
    h, marks = x, []
    for i, window in enumerate((None, 12)):
        h = h + T.multi_head_attention(
            h, h, h, 32, 4, param_prefix=f"l{i}.attn", attn_impl="flash",
            causal=True, n_kv_head=2, window=window, bias=False)
        marks.append(h)
    loss = layers.reduce_mean(layers.square(h))
    sgd = opt.SGDOptimizer(learning_rate=0.1)
    if checkpoints:
        sgd = opt.RecomputeOptimizer(sgd)
        sgd._set_checkpoints(marks)
    sgd.minimize(loss)
    return loss


def _train_steps(loss, scope, n=2, seq=32):
    exe = Executor()
    exe.run(pt.default_startup_program(), scope=scope, seed=3)
    x = np.random.RandomState(0).randn(2, seq, 32).astype(np.float32)
    return [float(np.asarray(exe.run(feed={"x": x}, scope=scope,
                                     fetch_list=[loss.name])[0]))
            for _ in range(n)]


def test_a_training_step_runs_the_forward_half_once_a_layer(monkeypatch):
    """Two flash layers, one compiled training step: the forward half of the
    kernel pair is traced twice (under the generic vjp it was four times:
    ``jax.vjp`` of the forward lowering ran it again for its residuals), and
    the grad op counts itself once a layer a compile."""
    calls = []
    real = F._flash_fwd
    scope = Scope()
    with scope_guard(scope), program_guard(Program(), Program()):
        loss = _two_flash_layers()
        monkeypatch.setattr(
            F, "_flash_fwd", lambda *a, **k: calls.append(1) or real(*a, **k))
        before = (FLASH_LOWERINGS_CTR.value(window="12", kv_groups="2",
                                            impl="jax", widths="8/8"),
                  FLASH_GRAD_LOWERINGS_CTR.value(window="none",
                                                 kv_groups="2", impl="jax", widths="8/8"),
                  FLASH_GRAD_LOWERINGS_CTR.value(window="12", kv_groups="2",
                                                 impl="jax", widths="8/8"))
        losses = _train_steps(loss, scope)
    assert len(calls) == 2, len(calls)
    assert (FLASH_LOWERINGS_CTR.value(window="12", kv_groups="2", impl="jax", widths="8/8"),
            FLASH_GRAD_LOWERINGS_CTR.value(window="none", kv_groups="2",
                                           impl="jax", widths="8/8"),
            FLASH_GRAD_LOWERINGS_CTR.value(window="12", kv_groups="2",
                                           impl="jax", widths="8/8")) == \
        tuple(b + 1 for b in before)
    assert losses[1] < losses[0]


def test_a_forward_program_whose_op_has_no_lse_slot_still_runs():
    """A program saved before the op had its second output: the lowering
    returns ``Lse``, the executor binds the slots the op names, and the
    grad maker says what to do instead of writing a grad op that cannot
    run."""
    q, k, v, _ = _qkv(32)
    with scope_guard(Scope()), program_guard(Program(), Program()):
        vs = [_data(n, a) for n, a in zip("qkv", (q, k, v))]
        out = layers.flash_attention(*vs, causal=True, window=9)
        (op,), _ = _flash_ops(pt.default_main_program())
        del op.outputs["Lse"]
        got, = Executor().run(feed=dict(zip("qkv", map(np.asarray,
                                                       (q, k, v)))),
                              fetch_list=[out.name])
        _close(got, mha_reference(q, k, v, causal=True, window=9), 1e-5)
        with pytest.raises(ValueError, match="Lse"):
            append_backward(layers.reduce_sum(out))


def test_a_recomputed_segments_lse_feeds_the_grad_op():
    """``RecomputeOptimizer`` with checkpoints at both block outputs: the
    second flash layer is emitted again behind the loss's gradient, its grad
    op reads that copy's ``Out`` and ``Lse``, and the step trains as it does
    without recomputation."""
    losses = {}
    for ck in (False, True):
        scope = Scope()
        with scope_guard(scope), program_guard(Program(), Program()):
            loss = _two_flash_layers(checkpoints=ck)
            if ck:
                fwd, grads = _flash_ops(pt.default_main_program())
                assert len(fwd) == 3 and len(grads) == 2
                again = fwd[2]
                assert again.output("Lse")[0].endswith("@RECOMPUTE")
                reads = [g for g in grads
                         if g.input("Lse") == again.output("Lse")]
                assert len(reads) == 1
                assert reads[0].input("Out") == again.output("Out")
            losses[ck] = _train_steps(loss, scope, n=3)
    np.testing.assert_allclose(losses[True], losses[False], rtol=1e-5)
