"""The flash attention kernels' two widths (``paddle_tpu/pallas/
flash_attention.py``: Q and K ``d_qk`` wide, V and the output ``d_v``) against
``mha_reference``: forward and dQ, dK, dV of the Pallas kernels in interpret
mode (fused and split backward) and of the blockwise jax fallbacks, at
96/64 and latent attention's 192/128, T <= 256, full and grouped K/V heads and
a window; the ``flash_attention`` op of a ``Program`` and its grad op at two
widths (shape inference, gradients, the counters' ``widths`` label);
``rope(interleaved=True)`` and its gradient against the published
permute-and-rotate; and that ``d_qk == d_v`` with ``interleaved=False`` leaves
OLMoE's and Trinity's toy steps at their parents' StableHLO text."""

import hashlib
import importlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from paddle_tpu import layers  # noqa: E402
from paddle_tpu.framework import (Executor, Program, Scope,  # noqa: E402
                                  program_guard, scope_guard)
from paddle_tpu.framework.backward import append_backward  # noqa: E402
from paddle_tpu.framework.core import grad_var_name  # noqa: E402
from paddle_tpu.pallas import mha_reference  # noqa: E402

F = importlib.import_module("paddle_tpu.pallas.flash_attention")


def _close(got, want, tol, what=""):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    err = np.abs(got - want).max() / max(np.abs(want).max(), 1e-12)
    assert err <= tol, f"{what}: {err:.3e} of the largest entry > {tol}"


def _qkv(t, d_qk, d_v, h=4, hk=4, seed=0):
    rng = np.random.RandomState(seed)
    return [jnp.asarray(rng.randn(1, n, t, d).astype(np.float32))
            for n, d in ((h, d_qk), (hk, d_qk), (hk, d_v), (h, d_v))]


def _value_and_grads(fn, q, k, v, w):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(w * fn(q, k, v)), (0, 1, 2))(q, k, v)


@pytest.mark.parametrize("d_qk,d_v,t,hk,window,impl", [
    (96, 64, 56, 4, None, "fused"),        # a padded length, no groups
    (96, 64, 32, 4, None, "split"),
    (96, 64, 88, 2, None, "fused"),        # padded blocks of 64, grouped
    (96, 64, 48, 2, 24, "split"),          # a window over grouped heads
    (192, 128, 96, 2, None, "fused"),      # latent's pair, padded, grouped
    (192, 128, 128, 4, None, "split"),
    (96, 64, 64, 4, None, None),           # the blockwise jax fallbacks
    (192, 128, 100, 2, 40, None),
    (64, 96, 48, 2, 20, "fused"),          # wider values under a window
    (96, 64, 32, 4, None, "fused"),        # one pass, dQ resident (PR 37)
    (96, 64, 40, 2, None, "fused"),        # padded blocks, grouped K/V heads
    (96, 64, 48, 2, 24, "fused"),          # a window over grouped heads
    (192, 128, 128, 4, None, "fused"),     # latent attention's pair
    (64, 96, 32, 4, None, "fused"),        # values wider than the scores
])
def test_two_width_flash_matches_the_oracle(d_qk, d_v, t, hk, window, impl):
    """Forward and every gradient at ``d_qk != d_v``, the scale the caller's
    (``d_qk ** -0.5``), against ``mha_reference``; the output is ``d_v``
    wide and nothing is padded to the wider of the two."""
    q, k, v, w = _qkv(t, d_qk, d_v, hk=hk)
    sm = d_qk ** -0.5
    blk = 64 if t > 64 else 16
    q, k, v, w = (a[:, :a.shape[1] // 2] for a in (q, k, v, w))   # 2 heads
    got, g_got = _value_and_grads(
        lambda q, k, v: F.flash_attention(
            q, k, v, causal=True, sm_scale=sm, window=window, block_q=blk,
            block_k=blk, bwd_impl=impl, interpret=impl is not None),
        q, k, v, w)
    want, g_want = _value_and_grads(
        lambda q, k, v: mha_reference(q, k, v, causal=True, sm_scale=sm,
                                      window=window), q, k, v, w)
    assert F.flash_attention(q, k, v, causal=True, block_q=blk, block_k=blk
                             ).shape == (1, 2, t, d_v)
    assert abs(float(got - want)) <= 1e-4 * abs(float(want)) + 1e-4
    for a, b, name in zip(g_got, g_want, "qkv"):
        assert a.shape == b.shape
        _close(a, b, 2e-5, f"{d_qk}/{d_v} d / d {name}")
    if impl == "fused":
        # the split kernels' products, accumulated in their order: dQ to the
        # bit, dK and dV to the rounding of a first-axis contraction
        _, g_split = _value_and_grads(
            lambda q, k, v: F.flash_attention(
                q, k, v, causal=True, sm_scale=sm, window=window,
                block_q=blk, block_k=blk, bwd_impl="split", interpret=True),
            q, k, v, w)
        for a, b, name in zip(g_got, g_split, "qkv"):
            _close(a, b, 2e-6, f"fused against split, d / d {name}")


def test_the_two_halves_keep_their_widths_and_k_must_match_q():
    q, k, v, w = _qkv(32, 24, 16, hk=2)
    o, lse = F.flash_attention_fwd(q, k, v, causal=True, block_q=16,
                                   block_k=16)
    assert o.shape == (1, 4, 32, 16) and lse.shape == (1, 4, 32)
    dq, dk, dv, db = F.flash_attention_bwd(q, k, v, None, o, lse, w,
                                           causal=True, block_q=16,
                                           block_k=16)
    assert (dq.shape, dk.shape, dv.shape, db) == \
        (q.shape, k.shape, v.shape, None)
    with pytest.raises(ValueError, match="contract over one width"):
        F.flash_attention(q, v, v, causal=True)


def test_the_scale_from_the_value_width_is_another_function():
    """``sm_scale`` from 128 instead of 192 moves the output by far more
    than the kernels' distance from the oracle."""
    q, k, v, _ = _qkv(64, 192, 128)
    a = mha_reference(q, k, v, causal=True, sm_scale=192 ** -0.5)
    b = mha_reference(q, k, v, causal=True, sm_scale=128 ** -0.5)
    assert float(jnp.abs(a - b).max() / jnp.abs(a).max()) > 0.05


def test_block_tables_at_the_wide_score_width():
    """128 < d_qk <= 256 at T 8192 has a table of its own (``tools/
    joyai_kernel_probe.py``), keyed by the score width; V is collapsed at
    its own width; d_qk <= 128 keeps its tables."""
    def plan(d_qk, d_v, dtype=jnp.bfloat16):
        q = jnp.zeros((1, 2, 8192, d_qk), dtype)
        v = jnp.zeros((1, 2, 8192, d_v), dtype)
        return F._plan(q, q, v, None, True, None, None, None, None, None,
                       None, False, None)[1]
    st = plan(192, 128)
    assert st[1] == pytest.approx(192 ** -0.5)
    assert st[2:6] == (1024, 1024, (1024, 1024), None)   # None: the rule
    assert plan(128, 128)[2:6] == (1024, 1024, (1024, 1024), None)
    assert plan(320, 128)[2:4] == (512, 1024)           # the baseline
    # float32 blocks are twice the bytes: the wide table is bf16's alone
    assert plan(192, 128, jnp.float32)[2:5] == (512, 1024, None)
    assert plan(128, 128, jnp.float32)[2:4] == (1024, 1024)
    q, _, v, _ = _qkv(8, 24, 16)
    (qc, kc, vc, _), _ = F._plan(q, q, v, None, True, None, None, None, None,
                                 None, None, False, None)
    assert (qc.shape, kc.shape, vc.shape) == ((4, 8, 24), (4, 8, 24),
                                              (4, 8, 16))


# -- the op of a Program ----------------------------------------------------------

#: name -> (heads, KV heads, T, d_qk, d_v, window, bwd_impl asked of the grad
#: op, the backward kernel a TPU runs there[, the share of VMEM the fused
#: backward may ask for, where the case sets it])
CHOICE_CASES = {
    "joyai_32x8192x192_over_128": (32, 32, 8192, 192, 128, None, None,
                                   "fused"),
    "trinity_full_32_over_4x8192x128": (32, 4, 8192, 128, 128, None, None,
                                        "fused"),
    "trinity_window_2048": (32, 4, 8192, 128, 128, 2048, None, "fused"),
    "olmoe_64x4096x128": (64, 64, 4096, 128, 128, None, None, "fused"),
    # a head's dQ accumulator at 65536 x 192 is 100 MB: past the share of
    # VMEM the fused backward may ask for, whatever is asked of the op
    "too_long_for_the_accumulator": (2, 2, 65536, 192, 128, None, "fused",
                                     "split"),
    # the same rule with the share made smaller under JoyAI's shape, where
    # the fused call asks for 49.5 MiB: the fall-back that is left
    "fused_past_a_smaller_share": (32, 32, 8192, 192, 128, None, "fused",
                                   "split", 0.25),
    # d <= 64 at a length whose row names blocks only: what got the kernel
    # with partials in HBM until PR 44 gets the rule
    "gpt_16x4096x64_nothing_asked": (16, 16, 4096, 64, 64, None, None,
                                     "fused"),
}


@pytest.mark.parametrize("case", sorted(CHOICE_CASES))
def test_the_backward_kernel_follows_the_shapes(case, monkeypatch):
    """Which backward ``flash_attention_grad`` lowers to on a TPU, traced
    abstractly at the three flash cells' shapes in bf16 (the tables name the
    fused kernel at 8192), at a length whose dQ accumulator passes the share
    of VMEM (the split kernels, which keep nothing that grows with T), under
    a smaller share, and at 64-wide heads with nothing asked; ``paddle_tpu_
    flash_bwd_kernel_total`` says which was taken, and the fused call asks
    for the VMEM its shapes need and no more than the share."""
    from paddle_tpu.ops import attention_ops as A
    h, hk, t, d_qk, d_v, window, asked, kernel, *share = CHOICE_CASES[case]
    monkeypatch.setattr(F, "on_tpu", lambda: True)
    if share:
        monkeypatch.setattr(F, "_FUSED_VMEM_SHARE", share[0])

    def arg(heads, width):
        return jax.ShapeDtypeStruct((1, heads, t, width), jnp.bfloat16)
    q, k, v, o = arg(h, d_qk), arg(hk, d_qk), arg(hk, d_v), arg(h, d_v)
    lse = jax.ShapeDtypeStruct((1, h, t), jnp.float32)
    attrs = {"causal": True, "window": window or 0, "bwd_impl": asked or ""}
    assert F.flash_bwd_kernel(q, k, v, causal=True, window=window,
                              bwd_impl=asked) == kernel
    labels = dict(kernel=kernel, widths=f"{d_qk}/{d_v}",
                  window="none" if window is None else str(window))
    before = A.FLASH_BWD_KERNEL_CTR.value(**labels)
    limits = []
    from jax.experimental.pallas import tpu as pltpu
    real_params = pltpu.CompilerParams
    monkeypatch.setattr(pltpu, "CompilerParams", lambda **kw: limits.append(
        kw.get("vmem_limit_bytes")) or real_params(**kw))
    got = jax.eval_shape(
        lambda q, k, v, o, lse, do: A._flash_attention_grad(None, {
            "X$Q": [q], "X$K": [k], "X$V": [v], "Out": [o], "Lse": [lse],
            "OG$Out": [do]}, attrs), q, k, v, o, lse, o)
    assert [got[s][0].shape for s in ("IG$Q", "IG$K", "IG$V")] == \
        [q.shape, k.shape, v.shape]
    assert A.FLASH_BWD_KERNEL_CTR.value(**labels) == before + 1
    if kernel == "fused":
        assert len(limits) == 1 and 16 << 20 < limits[0] <= \
            F._FUSED_VMEM_SHARE * F._VMEM_BYTES
    else:
        assert limits == []


#: every row of the four backward block tables: (table, length, the widest
#: d_qk and d_v the table serves, a window for the window table)
TABLE_ROWS = [(name, t, d_qk, d_v, window)
              for name, d_qk, d_v, window in (
                  ("_BWD_DEFAULTS", 64, 64, None),
                  ("_BWD_DEFAULTS_D128", 128, 128, None),
                  ("_BWD_WINDOW_DEFAULTS_D128", 128, 128, 2048),
                  ("_BWD_DEFAULTS_D256", 256, 256, None))
              for t in sorted(getattr(F, name))]


@pytest.mark.parametrize("name,t,d_qk,d_v,window", TABLE_ROWS,
                         ids=[f"{r[0]}-{r[1]}" for r in TABLE_ROWS])
def test_every_table_row_is_a_block_pair_the_fused_backward_fits(
        name, t, d_qk, d_v, window, monkeypatch):
    """A row of a backward table is a pair of blocks and nothing else (the
    kernel is the rule's), and at the widest bf16 head the table serves the
    rule gives the fused kernel: its VMEM ask stays under the share."""
    monkeypatch.setattr(F, "on_tpu", lambda: True)
    bq, bk = getattr(F, name)[t]
    q = jax.ShapeDtypeStruct((1, 8, t, d_qk), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 2, t, d_qk), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, 2, t, d_v), jnp.bfloat16)
    statics = F._statics(q, k, v, True, None, None, None, None, None, None,
                         False, window)
    assert statics[4:6] == ((bq, bk), None)
    assert F.flash_bwd_kernel(q, k, v, causal=True, window=window) == "fused"
    assert F._fused_vmem_bytes(t, d_qk, d_v, bq, bk, 2) \
        <= F._FUSED_VMEM_SHARE * F._VMEM_BYTES


def _dense_lse(q, k, bias, causal, sm, window):
    """Each query's log-sum-exp over its visible keys, from the dense
    scores ``mha_reference`` builds (its masks, end-aligned), in float32 at
    ``highest``."""
    group = q.shape[1] // k.shape[1]
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                       jnp.repeat(k, group, axis=1).astype(jnp.float32)) * sm
    if bias is not None:
        s = s + bias
    tq, tk = s.shape[-2:]
    i = jnp.arange(tq)[:, None] + tk - tq
    j = jnp.arange(tk)[None, :]
    mask = jnp.ones((tq, tk), bool)
    if causal:
        mask = mask & (i >= j)
    if window is not None:
        mask = mask & (i - j < window)
    return jax.nn.logsumexp(jnp.where(mask, s, -jnp.inf), axis=-1)


#: name -> (d_qk, d_v, KV heads of 4, causal, window, bias, ragged, dtype):
#: what the cells and the long-sequence recipes send through the forward
LSE_CASES = {
    "causal": (32, 32, 4, True, None, False, False, jnp.bfloat16),
    "window": (32, 32, 4, True, 72, False, False, jnp.float32),
    "grouped_kv": (32, 32, 2, True, None, False, False, jnp.float32),
    "192_over_128": (192, 128, 4, True, None, False, False, jnp.float32),
    "float32_noncausal": (32, 32, 4, False, None, False, False, jnp.float32),
    "bias": (32, 32, 4, False, None, True, False, jnp.float32),
    "ragged_tq": (32, 32, 2, True, None, False, True, jnp.float32),
}


@pytest.mark.parametrize("case", sorted(LSE_CASES))
@pytest.mark.parametrize("form", ["row", "lanes"])
def test_the_forwards_lse_in_both_forms_and_the_gradients_from_it(form, case):
    """The forward kernel (interpret mode) writes ``lse`` as ``[bh, 1, Tq]``
    rows where a query block fills lanes and as the lane-broadcast columns
    at ragged toy blocks: either way its readers get ``[b, h, Tq]``, each
    query's log-sum-exp over its visible keys as the dense scores give it,
    and the backward kernels (the blockwise jax backward under a bias)
    rebuild from it the gradients ``jax.grad`` of the oracle gives."""
    d_qk, d_v, hk, causal, window, biased, ragged, dtype = LSE_CASES[case]
    blk, t = (128, 256) if form == "row" else (16, 48)
    if ragged:
        t -= blk // 2 + 3                # a padded last block: 189, 37
    q, k, v, w = (a.astype(dtype) for a in _qkv(t, d_qk, d_v, hk=hk, seed=3))
    bias = jnp.asarray(np.random.RandomState(5).randn(1, 1, t, t),
                       jnp.float32) if biased else None
    sm = d_qk ** -0.5
    kw = dict(causal=causal, sm_scale=sm, window=window, block_q=blk,
              block_k=blk, interpret=True)
    assert F.flash_lse_layout(q, k, v, **kw) == form
    bh, tqp = 4, -(-t // blk) * blk
    text = str(jax.make_jaxpr(lambda q, k, v: F.flash_attention_fwd(
        q, k, v, bias, **kw))(q, k, v))
    assert (f"f32[{bh},1,{tqp}]" in text) == (form == "row")
    out, lse = F.flash_attention_fwd(q, k, v, bias, **kw)
    assert lse.shape == (1, 4, t) and lse.dtype == jnp.float32
    half = dtype == jnp.bfloat16
    _close(lse, _dense_lse(q, k, bias, causal, sm, window),
           1e-5, "lse")                  # float32 from the same bf16 inputs
    f32 = [a.astype(jnp.float32) for a in (q, k, v, w)]
    want, g_want = _value_and_grads(
        lambda q, k, v: mha_reference(q, k, v, bias, causal=causal,
                                      sm_scale=sm, window=window), *f32)
    _close(out, mha_reference(*f32[:3], bias, causal=causal, sm_scale=sm,
                              window=window), 1e-2 if half else 2e-5, "out")
    _, g_got = _value_and_grads(
        lambda q, k, v: F.flash_attention(q, k, v, bias, **kw).astype(
            jnp.float32), q, k, v, f32[3])
    for a, b, name in zip(g_got, g_want, "qkv"):
        assert a.shape == b.shape
        _close(a, b, 2e-2 if half else 2e-5, f"{form} {case} d / d {name}")


@pytest.mark.parametrize("t,block,form", [(32, 16, "lanes"), (16, 16, "row"),
                                          (128, 128, "row")])
def test_the_forward_counter_says_which_form_lse_left_in(t, block, form):
    """``paddle_tpu_flash_lowerings_total{lse}``: ``row`` where the op's
    query block fills lanes (or is the whole length, in whole sublanes),
    ``lanes`` at the ragged blocks left; a reader that names no ``lse``
    still reads the total."""
    from paddle_tpu.ops.attention_ops import FLASH_LOWERINGS_CTR
    labels = dict(window="none", kv_groups="2", impl="jax", widths="24/16")
    other = "lanes" if form == "row" else "row"
    before = [FLASH_LOWERINGS_CTR.value(**labels, lse=x)
              for x in (form, other)] + [FLASH_LOWERINGS_CTR.value(**labels)]
    scope, main, out, loss, feed = _op_program(t, 24, 16, block=block)
    Executor().run(main, feed=feed, scope=scope, fetch_list=[loss.name])
    assert [FLASH_LOWERINGS_CTR.value(**labels, lse=x)
            for x in (form, other)] + [FLASH_LOWERINGS_CTR.value(**labels)] \
        == [before[0] + 1, before[1], before[2] + 1]


def _op_program(t, d_qk, d_v, h=4, hk=2, block=16):
    q, k, v, w = (np.asarray(a) for a in _qkv(t, d_qk, d_v, h=h, hk=hk))
    scope, main = Scope(), Program()
    with scope_guard(scope), program_guard(main, Program()):
        vs = [layers.data(n, shape=list(a.shape), dtype="float32",
                          append_batch_size=False, stop_gradient=False)
              for n, a in zip("qkv", (q, k, v))]
        wv = layers.data("w", shape=list(w.shape), dtype="float32",
                         append_batch_size=False)
        out = layers.flash_attention(*vs, causal=True,
                                     sm_scale=d_qk ** -0.5, block_q=block,
                                     block_k=block)
        loss = layers.reduce_sum(out * wv)
        append_backward(loss)
    return scope, main, out, loss, dict(q=q, k=k, v=v, w=w)


def test_the_op_and_its_grad_op_at_two_widths():
    """Shape inference gives Out V's width; the grad op over the forward's
    Out and Lse returns dQ, dK at ``d_qk`` and dV at ``d_v``, equal to
    ``jax.grad`` of the oracle; both counters label the two widths."""
    from paddle_tpu.ops.attention_ops import (FLASH_GRAD_LOWERINGS_CTR,
                                              FLASH_LOWERINGS_CTR)
    labels = dict(window="none", kv_groups="2", impl="jax", widths="24/16")
    before = (FLASH_LOWERINGS_CTR.value(**labels),
              FLASH_GRAD_LOWERINGS_CTR.value(**labels))
    scope, main, out, loss, feed = _op_program(32, 24, 16)
    assert tuple(out.shape) == (1, 4, 32, 16)
    block = main.global_block()
    assert tuple(block.var(grad_var_name("v")).shape) == (1, 2, 32, 16)
    assert tuple(block.var(grad_var_name("k")).shape) == (1, 2, 32, 24)
    got = Executor().run(main, feed=feed, scope=scope, fetch_list=[
        loss.name] + [grad_var_name(n) for n in "qkv"])
    a = [jnp.asarray(feed[n]) for n in "qkvw"]
    want, g_want = _value_and_grads(
        lambda q, k, v: mha_reference(q, k, v, causal=True,
                                      sm_scale=24 ** -0.5), *a)
    assert abs(float(got[0]) - float(want)) <= 1e-4 * abs(float(want)) + 1e-4
    for g, w, n in zip(got[1:], g_want, "qkv"):
        _close(g, w, 2e-5, f"op d / d {n}")
    assert (FLASH_LOWERINGS_CTR.value(**labels),
            FLASH_GRAD_LOWERINGS_CTR.value(**labels)) == \
        (before[0] + 1, before[1] + 1)


# -- rope(interleaved=True) ---------------------------------------------------------

def _published_rope(x, theta):
    """[b, h, t, d] as the DeepSeek family's ``apply_rotary_pos_emb`` under
    ``rope_interleave``: pairs to the two halves, rotate halves."""
    b, h, t, d = x.shape
    x = x.reshape(b, h, t, d // 2, 2).swapaxes(4, 3).reshape(b, h, t, d)
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


def _rope_op(x, head_dim, theta, interleaved, w):
    scope, main = Scope(), Program()
    with scope_guard(scope), program_guard(main, Program()):
        xv = layers.data("x", shape=list(x.shape), dtype="float32",
                         append_batch_size=False, stop_gradient=False)
        wv = layers.data("w", shape=list(x.shape), dtype="float32",
                         append_batch_size=False)
        out = layers.rope(xv, head_dim, theta, interleaved=interleaved)
        assert tuple(out.shape) == x.shape
        loss = layers.reduce_sum(out * wv)
        append_backward(loss)
    return Executor().run(main, feed={"x": x, "w": w}, scope=scope,
                          fetch_list=[out.name, grad_var_name("x")])


def test_interleaved_rope_is_the_published_rotation_up_to_its_permutation():
    """Pairs ``(2i, 2i + 1)`` at ``pos * theta^(-2i/d)``: the published
    code's output is this one's with each pair's members moved to the two
    halves, so scores of rotated Q and K agree; forward and gradient, 4-D
    (one shared key head among them) and 3-D inputs."""
    rng = np.random.RandomState(0)
    theta = 32e6
    for shape in ((2, 3, 10, 8), (2, 1, 10, 8)):
        x = rng.randn(*shape).astype(np.float32)
        w = rng.randn(*shape).astype(np.float32)
        out, dx = _rope_op(x, 8, theta, True, w)
        want, back = jax.vjp(lambda x: _published_rope(x, theta),
                             jnp.asarray(x))
        perm = np.concatenate([np.arange(0, 8, 2), np.arange(1, 8, 2)])
        _close(out[..., perm], want, 1e-6, "interleaved rope")
        dwant, = back(jnp.asarray(w[..., perm]))
        _close(dx, dwant, 1e-6, "interleaved rope gradient")
    # [b, t, n * d] before the head split
    x = rng.randn(2, 10, 24).astype(np.float32)
    out3, _ = _rope_op(x, 8, theta, True, x)
    out4, _ = _rope_op(x.reshape(2, 10, 3, 8).transpose(0, 2, 1, 3).copy(),
                       8, theta, True, x.reshape(2, 10, 3, 8).transpose(
                           0, 2, 1, 3).copy())
    _close(out3.reshape(2, 10, 3, 8).transpose(0, 2, 1, 3), out4, 1e-6)


def test_rotate_half_pairing_is_another_function():
    """The default pairing (``i`` with ``i + d/2``) on the same input gives
    other scores than the adjacent pairs: the test above cannot pass with
    the wrong pairing."""
    rng = np.random.RandomState(1)
    q = rng.randn(1, 2, 12, 8).astype(np.float32)
    k = rng.randn(1, 2, 12, 8).astype(np.float32)

    def scores(inter):
        a, _ = _rope_op(q, 8, 1e4, inter, q)
        b, _ = _rope_op(k, 8, 1e4, inter, k)
        return np.einsum("bhqd,bhkd->bhqk", a, b)
    s_i, s_h = scores(True), scores(False)
    assert np.abs(s_i - s_h).max() / np.abs(s_i).max() > 0.05
    want = np.einsum("bhqd,bhkd->bhqk", _published_rope(jnp.asarray(q), 1e4),
                     _published_rope(jnp.asarray(k), 1e4))
    _close(s_i, want, 1e-5, "scores under the published rotation")


# -- the old lowerings are the old lowerings -------------------------------------------

#: sha256 of the StableHLO text of Trinity's toy training step (forward and
#: backward, the loss and every trainable parameter's gradient fetched; CPU
#: lowering): window and grouped K/V heads, rotate-half rope, ``d_qk ==
#: d_v``.  Taken at PR 34's parent, 496be36 (80aad17d...), and again in PR 35,
#: which meant to change it: ``moe_ffn``'s held path gathers the rows'
#: gradient back to tokens as stored (bf16, widened after) and takes its
#: buffer's length from a ladder (one rung at the toy's sizes: no switch).
#: OLMoE's is ``test_trinity.OLMOE_TOY_STEP_SHA256``, unchanged by both.
#: Taken again in PR 63, for the toys alone (ad196f98... until then, and
#: still with the order forced slot-minor, ``TRINITY_TOY_STEP_SLOT_MINOR``):
#: the toys' experts a token are no multiple of 8, so ``moe_ffn``'s un-sorts
#: bring the slots home slot-major (``moe_ops._sum_over_slots``); Trinity's
#: own eight lower as they did.
TRINITY_TOY_STEP_SHA256 = (
    "33f506a28ae94f171648a645fae9efcf37fa56bb1121c4c2bebc901f8106a7ea")
TRINITY_TOY_STEP_SLOT_MINOR = (
    "ad196f981981175b28153f4f34ddf4cbef8f8325098fa248e3daee88402f213c")


def _step_text(mod, cfg, seq=16):
    import re
    scope, main, exe, _, loss = mod._model(cfg, seq)
    feed = mod._batch(cfg, 1, seq)
    fetch = [loss.name] + [grad_var_name(p.name)
                           for p in main.all_parameters() if p.trainable]
    exe.run(main, feed=feed, scope=scope, fetch_list=fetch)
    cb = next(p for p in exe._plans.values()
              if p.cb.fetch_names == tuple(fetch)).cb
    args = ([jnp.asarray(feed[n]) for n in cb.feed_names],
            [scope.find_var(n) for n in cb.persist_ro],
            [scope.find_var(n) for n in cb.persist_rw], jnp.uint32(1))
    return re.sub(r"loc\(.*?\)", "", cb.jitted.lower(*args).as_text())


def test_one_width_and_the_default_rope_lower_as_at_the_parent(monkeypatch):
    """``d_qk == d_v`` and ``interleaved=False``: OLMoE's and Trinity's toy
    steps lower to the recorded StableHLO text, byte for byte, and with
    their slots summed slot-minor to the text recorded before PR 63."""
    import test_olmoe
    import test_trinity
    from paddle_tpu.ops import moe_ops

    def shas():
        return tuple(hashlib.sha256(_step_text(mod, cfg).encode()).hexdigest()
                     for mod, cfg in ((test_trinity, test_trinity.toy_cfg()),
                                      (test_olmoe,
                                       test_olmoe.toy_cfg(n_layer=1))))
    assert shas() == (TRINITY_TOY_STEP_SHA256,
                      test_trinity.OLMOE_TOY_STEP_SHA256)
    monkeypatch.setattr(moe_ops, "_slot_major", lambda *a: False)
    assert shas() == (TRINITY_TOY_STEP_SLOT_MINOR,
                      test_trinity.OLMOE_TOY_STEP_SLOT_MINOR)
