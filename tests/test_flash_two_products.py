"""The flash kernels' score as two products (PR 48; ``paddle_tpu/pallas/
flash_attention.py``: ``q_rope`` ``[b, h, Tq, d_r]`` and ``k_rope`` ``[b, h_r,
Tk, d_r]`` beside Q and K, the score ``(q·k + q_rope·k_rope) · sm_scale``):
the forward, the fused and the split backward in Pallas interpret mode and
both blockwise jax fallbacks against ``mha_reference`` on the CONCATENATED,
BROADCAST inputs (Out, Lse, dQ, dQRope, dK, dKRope, dV) over one rotary key
head and one a query head, causal and not, a length the blocks do not divide,
a window, grouped K/V heads under a shared rotary key, float32 and bf16
inputs, and a bias; dKRope as the sum over heads of the one-product dK's
rotary columns; a call without the two traces to the jaxpr it had before they
existed; the VMEM reckoning, the block tables at the whole score width; the
``flash_attention`` op of a ``Program`` with its QRope and KRope slots, its
grad op and the counters' ``widths`` label."""

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

F = importlib.import_module("paddle_tpu.pallas.flash_attention")

NAMES = ("q", "q_rope", "k", "k_rope", "v")


def _close(got, want, tol, what=""):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    err = np.abs(got - want).max() / max(np.abs(want).max(), 1e-12)
    assert err <= tol, f"{what}: {err:.3e} of the largest entry > {tol}"


def _pieces(t, h=4, hk=4, hr=1, dn=32, dr=16, dv=24, dtype=jnp.float32,
            seed=0, tq=None):
    """(q, q_rope, k, k_rope, v) and a weight for Out, batch 2."""
    rng = np.random.RandomState(seed)
    tq = tq or t
    shapes = ((h, tq, dn), (h, tq, dr), (hk, t, dn), (hr, t, dr),
              (hk, t, dv), (h, tq, dv))
    return [jnp.asarray(rng.randn(2, *s).astype(np.float32)).astype(dtype)
            for s in shapes]


def _built(q, q_rope, k, k_rope, v):
    """The one-product inputs the program used to make: ``[q | q_rope]`` and
    ``[k | k_rope for every head]``, K's and V's groups repeated."""
    h = q.shape[1]
    k, v = (jnp.repeat(a, h // a.shape[1], axis=1) for a in (k, v))
    return (jnp.concatenate([q, q_rope], axis=-1), jnp.concatenate(
        [k, jnp.repeat(k_rope, h // k_rope.shape[1], axis=1)], axis=-1), v)


def _oracle(pieces, w, sm, causal, window, bias=None):
    """Out, Lse and the five gradients of ``mha_reference`` over the built
    inputs, differentiated back to the pieces, float32 at ``highest``."""
    f32 = [a.astype(jnp.float32) for a in pieces]

    def lse_of(*p):
        q, k, _ = _built(*p)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * sm
        if bias is not None:
            s = s + bias
        tq, tk = s.shape[-2:]
        if causal:
            i = jnp.arange(tq)[:, None] + tk - tq
            j = jnp.arange(tk)[None]
            mask = i >= j
            if window is not None:
                mask = mask & (i - j < window)
            s = jnp.where(mask, s, F.NEG_INF)
        return jax.nn.logsumexp(s, axis=-1)

    def out_of(*p):
        return F.mha_reference(*_built(*p), bias=bias, causal=causal,
                               sm_scale=sm, window=window)
    with jax.default_matmul_precision("highest"):
        o, back = jax.vjp(out_of, *f32)
        return o, lse_of(*f32), back(w.astype(jnp.float32))


CASES = [
    # t, h, hk, hr, causal, window, impl, dtype
    (56, 4, 4, 1, True, None, "fused", "float32"),   # padded, one rotary head
    (56, 4, 4, 4, True, None, "fused", "float32"),   # a rotary head a head
    (32, 4, 4, 1, True, None, "split", "float32"),
    (40, 4, 4, 4, True, None, "split", "float32"),   # padded, split
    (48, 4, 2, 1, True, 24, "fused", "float32"),     # window, grouped K/V
    (48, 4, 2, 1, True, 24, "split", "float32"),
    (88, 4, 2, 2, True, None, "fused", "float32"),   # rotary groups = K/V's
    (64, 4, 1, 1, True, None, "fused", "float32"),   # one K/V head too
    (32, 4, 4, 1, False, None, "fused", "float32"),  # not causal
    (40, 4, 2, 1, False, None, "split", "float32"),  # not causal, padded
    (56, 4, 4, 1, True, None, None, "float32"),      # the jax fallbacks
    (48, 4, 2, 1, True, 20, None, "float32"),
    (32, 4, 4, 4, False, None, None, "float32"),
    (100, 4, 2, 2, True, 40, None, "float32"),
    (64, 4, 4, 1, True, None, "fused", "bfloat16"),  # bf16 inputs
    (48, 4, 2, 1, True, None, "split", "bfloat16"),
    (64, 4, 4, 1, True, None, None, "bfloat16"),
    (128, 2, 2, 1, True, None, "fused", "float32"),  # latent's 128 + 64 | 128
]


@pytest.mark.parametrize("t,h,hk,hr,causal,window,impl,dtype", CASES)
def test_two_products_match_the_oracle_on_the_built_inputs(
        t, h, hk, hr, causal, window, impl, dtype):
    wide = t == 128
    dn, dr, dv = (128, 64, 128) if wide else (32, 16, 24)
    *pieces, w = _pieces(t, h, hk, hr, dn, dr, dv, jnp.dtype(dtype))
    sm = (dn + dr) ** -0.5 * 1.3          # the caller's: YaRN's factor in it
    blk = 64 if wide else 16
    kw = dict(causal=causal, sm_scale=sm, window=window, block_q=blk,
              block_k=blk, bwd_impl=impl, interpret=impl is not None)
    q, qr, k, kr, v = pieces
    o, lse = F.flash_attention_fwd(q, k, v, q_rope=qr, k_rope=kr, **kw)
    assert o.shape == q.shape[:3] + (dv,) and o.dtype == q.dtype
    assert lse.shape == q.shape[:3] and lse.dtype == jnp.float32
    got = F.flash_attention_bwd(q, k, v, None, o, lse, w, q_rope=qr,
                                k_rope=kr, **kw)
    assert got[3] is None and len(got) == 6
    want_o, want_lse, want = _oracle(pieces, w, sm, causal, window)
    tol = 2e-5 if dtype == "float32" else 2e-2
    _close(o, want_o, tol, "Out")
    _close(lse, want_lse, 2e-5 if dtype == "float32" else 2e-3, "Lse")
    order = (got[0], got[4], got[1], got[5], got[2])   # q, qr, k, kr, v
    for name, a, b, x in zip(NAMES, order, want, pieces):
        assert a.shape == x.shape and a.dtype == x.dtype, name
        _close(a, b, tol, f"d / d {name}")
    # and through the custom vjp: the same kernels, the same numbers
    g = jax.grad(lambda *p: jnp.sum(w.astype(jnp.float32) * F.flash_attention(
        p[0], p[2], p[4], q_rope=p[1], k_rope=p[3], **kw)),
        (0, 1, 2, 3, 4))(*pieces)
    for name, a, b in zip(NAMES, g, order):
        _close(a, b, 1e-6 if dtype == "float32" else 1e-2, f"vjp {name}")


@pytest.mark.parametrize("impl", ["fused", "split", None])
def test_dk_rope_is_the_sum_over_heads_of_the_one_product_dks_columns(impl):
    """One rotary key head for four query heads: its gradient is what the
    program used to get from ``concat_grad`` and ``expand_grad``, the built
    K's last ``d_r`` columns summed over the heads; dK's content columns,
    dQ's two parts and dV are the one-product kernels' own."""
    *pieces, w = _pieces(48, 4, 4, 1)
    q, qr, k, kr, v = pieces
    kw = dict(causal=True, sm_scale=48 ** -0.5, block_q=16, block_k=16,
              bwd_impl=impl, interpret=impl is not None)
    g2 = jax.grad(lambda *p: jnp.sum(w * F.flash_attention(
        p[0], p[2], p[4], q_rope=p[1], k_rope=p[3], **kw)),
        (0, 1, 2, 3, 4))(*pieces)
    qb, kb, vb = _built(*pieces)
    g1 = jax.grad(lambda q, k, v: jnp.sum(w * F.flash_attention(
        q, k, v, **kw)), (0, 1, 2))(qb, kb, vb)
    _close(g2[3], g1[1][..., 32:].sum(axis=1, keepdims=True), 2e-6, "dKRope")
    _close(g2[2], g1[1][..., :32], 2e-6, "dK")
    _close(g2[0], g1[0][..., :32], 2e-6, "dQ")
    _close(g2[1], g1[0][..., 32:], 2e-6, "dQRope")
    _close(g2[4], g1[2], 2e-6, "dV")


@pytest.mark.parametrize("bias_heads", [1, 4])
def test_a_bias_rides_beside_the_second_product(bias_heads):
    """The forward kernel takes both; with a bias the backward is the
    blockwise jax path on every backend, and it handles the extra product."""
    *pieces, w = _pieces(40, 4, 2, 1)
    q, qr, k, kr, v = pieces
    bias = jnp.asarray(np.random.RandomState(5).randn(
        2 if bias_heads > 1 else 1, bias_heads, 40, 40).astype(np.float32))
    sm = 48 ** -0.5
    want_o, want_lse, want = _oracle(pieces, w, sm, True, None, bias)
    for interpret in (True, False):
        kw = dict(causal=True, sm_scale=sm, block_q=16, block_k=16,
                  interpret=interpret)
        o, lse = F.flash_attention_fwd(q, k, v, bias, q_rope=qr, k_rope=kr,
                                       **kw)
        _close(o, want_o, 2e-5, "Out under a bias")
        _close(lse, want_lse, 2e-5, "Lse under a bias")
        dq, dk, dv, db, dqr, dkr = F.flash_attention_bwd(
            q, k, v, bias, o, lse, w, q_rope=qr, k_rope=kr, **kw)
        assert db.shape == bias.shape
        for name, a, b in zip(NAMES, (dq, dqr, dk, dkr, dv), want):
            _close(a, b, 2e-5, f"d / d {name} under a bias")
    assert F.flash_bwd_kernel(q, k, v, bias, interpret=True, q_rope=qr,
                              k_rope=kr) == "jax"


def test_decode_shaped_queries_read_the_end_of_the_keys():
    """Fewer queries than keys: the causal edge is end-aligned for both
    products."""
    *pieces, w = _pieces(48, 4, 4, 1, tq=16)
    q, qr, k, kr, v = pieces
    o = F.flash_attention(q, k, v, causal=True, q_rope=qr, k_rope=kr,
                          block_q=16, block_k=16, interpret=True)
    _close(o, F.mha_reference(*_built(*pieces), causal=True), 2e-5)
    _close(o, F.mha_reference(q, k, v, causal=True, q_rope=qr, k_rope=kr),
           1e-6, "mha_reference's own slots")


def test_the_slots_come_together_and_fit_q_and_k():
    q, qr, k, kr, v, _ = _pieces(32)
    with pytest.raises(ValueError, match="come together"):
        F.flash_attention(q, k, v, q_rope=qr)
    with pytest.raises(ValueError, match="h % h_r"):
        F.flash_attention(q, k, v, q_rope=qr, k_rope=jnp.repeat(kr, 3, 1))
    with pytest.raises(ValueError, match="h % h_r"):
        F.flash_attention(q, k, v, q_rope=qr, k_rope=kr[..., :8])
    with pytest.raises(ValueError, match="h % h_r"):
        F.flash_attention(q, k, v, q_rope=qr[:, :2], k_rope=kr)


# -- a call without the slots ---------------------------------------------------------

def _s(*shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


#: sha256 (16 hex) of the kernels' jaxprs at the parent commit aea0136, the
#: Pallas calls traced as a TPU would take them (``interpret`` false), taken
#: with ``_one_product_jaxprs`` there: a call without ``q_rope`` / ``k_rope``
#: must trace to these texts still, kernel bodies, grids, index maps, scratch
#: shapes and VMEM limits included.
PARENT_JAXPRS = {
    "fwd": "b480fae3aa01c999", "fused": "1437b083248d496a",
    "split": "268a01be58101827", "fwd.window.groups": "a95dbe3cff9a68e7",
    "fused.window.groups": "3d01d49a54f53142",
    "split.window.groups": "bf141190b9badc01"}


def _one_product_jaxprs(old_signature):
    """The forward, fused and split kernels at [4, 2048, 192 | 128] blocks
    (1024, 1024) and, windowed over groups of 4, [8, 1024, 128 | 128] blocks
    (256, 256), called through the parent's positional signature or with
    the new arguments spelt out as ``None``."""
    more = {} if old_signature else dict(q_rope=None, k_rope=None)
    out = {}
    for tag, bh, g, t, d, win, blk in (
            ("", 4, 1, 2048, 192, None, 1024),
            (".window.groups", 8, 4, 1024, 128, 256, 256)):
        q, k, v = _s(bh, t, d), _s(bh // g, t, d), _s(bh // g, t, 128)
        sm = d ** -0.5
        out["fwd" + tag] = jax.make_jaxpr(
            lambda q, k, v: F._flash_fwd_pallas(
                q, k, v, None, True, sm, blk, blk, 0, False, win, g,
                **more))(q, k, v)
        o, lse = _s(bh, t, 128), _s(bh, t, dtype=jnp.float32)
        for impl in ("fused", "split"):
            out[impl + tag] = jax.make_jaxpr(
                lambda q, k, v, o, lse, do: F._flash_bwd_pallas(
                    q, k, v, o, lse, do, True, sm, blk, blk, 0, False,
                    impl=impl, window=win, group=g, **more))(
                        q, k, v, o, lse, o)
    return {n: str(j) for n, j in out.items()}


@pytest.mark.parametrize("old_signature", [True, False])
def test_a_call_without_the_slots_traces_as_it_did(old_signature,
                                                   monkeypatch):
    """Since PR 62 a masked tile pair of static kind runs by sub-tiles: at
    (1024, 1024) the causal diagonal's bodies are new text, at (256, 256)
    the backward's alone (the forward's sub-tile is wider than the block);
    and with the sub-tiles off the
    whole-tile path is the parent's text still, in all six."""
    def hashes():
        return {n: hashlib.sha256(t.encode()).hexdigest()[:16]
                for n, t in _one_product_jaxprs(old_signature).items()}
    now = hashes()
    # forward sub-tiles of 512 and backward ones of 128: blocks of 256 hold
    # no two of the forward's
    assert {n for n, h in now.items() if h == PARENT_JAXPRS[n]} == {
        "fwd.window.groups"}
    monkeypatch.setattr(F, "_SUB_FWD", 0)
    monkeypatch.setattr(F, "_SUB_BWD", 0)
    assert hashes() == PARENT_JAXPRS


def test_the_public_entry_without_the_slots_is_the_old_signatures_call():
    """``flash_attention`` and its two halves with the arguments left out
    and with them ``None``: one jaxpr, through the custom vjp and the
    blockwise fallbacks alike."""
    q, k, v = (_s(1, 4, 256, 96, dtype=jnp.float32),
               _s(1, 2, 256, 96, dtype=jnp.float32),
               _s(1, 2, 256, 64, dtype=jnp.float32))
    for interpret in (True, False):
        def grad(**more):
            return str(jax.make_jaxpr(jax.grad(
                lambda q, k, v: jnp.sum(F.flash_attention(
                    q, k, v, causal=True, block_q=64, block_k=64,
                    interpret=interpret, **more)), (0, 1, 2)))(q, k, v))
        assert grad() == grad(q_rope=None, k_rope=None)
        assert ("pallas_call" in grad()) == interpret


def test_vmem_is_reckoned_at_whole_lanes_and_the_fused_backward_still_fits():
    assert F._lanes(0) == 0 and F._lanes(64) == 128 and F._lanes(128) == 128
    # without a rotary part: the parent's numbers
    assert F._fwd_vmem_bytes(192, 128, 1024, 1024, 2) == \
        F._fwd_vmem_bytes(192, 128, 1024, 1024, 2, 0, 0)
    assert F._fwd_vmem_bytes(192, 128, 1024, 1024, 2) / 2 ** 20 == 28.75
    assert F._fused_vmem_bytes(8192, 192, 128, 1024, 1024, 2) == \
        F._fused_vmem_bytes(8192, 192, 128, 1024, 1024, 2, 0)
    # 128 + 64: the 64 at 128 lanes, so as 128 + 128 would be, not as 192
    assert F._fwd_vmem_bytes(128, 128, 1024, 1024, 2, 0, 64) == \
        F._fwd_vmem_bytes(256, 128, 1024, 1024, 2)
    assert F._fused_vmem_bytes(8192, 128, 128, 1024, 1024, 2, 64) == \
        F._fused_vmem_bytes(8192, 256, 128, 1024, 1024, 2)
    q, k, v = _s(32, 8192, 128), _s(32, 8192, 128), _s(32, 8192, 128)
    assert F._bwd_kernel_name(q, k, v, 1024, 1024, None, 64) == "fused"
    assert F._bwd_kernel_name(q, k, v, 1024, 1024, "split", 64) == "split"
    long = _s(2, 65536, 128)
    assert F._bwd_kernel_name(long, long, long, 1024, 1024, None, 64) == \
        "split"


def test_the_tables_are_read_at_the_whole_score_width():
    """128 + 64 takes the rows a concatenated 192 got (so the blocks do not
    change under the comparison), the default scale is the whole width's,
    and the rotary parts are collapsed like Q and K."""
    def plan(dn, dr, t=8192, dtype=jnp.bfloat16):
        q, v = jnp.zeros((1, 2, t, dn), dtype), jnp.zeros((1, 2, t, 128),
                                                          dtype)
        rope = {} if not dr else dict(
            q_rope=jnp.zeros((1, 2, t, dr), dtype),
            k_rope=jnp.zeros((1, 1, t, dr), dtype))
        return F._plan(q, q, v, None, True, None, None, None, None, None,
                       None, False, None, **rope)
    (qc, kc, vc, bc), rest = plan(128, 64)
    assert rest[1] == pytest.approx(192 ** -0.5)
    assert rest[2:6] == plan(192, 0)[1][2:6] == (1024, 1024, (1024, 1024),
                                                 None)
    assert (qc.shape, rest[-2].shape, rest[-1].shape) == \
        ((2, 8192, 128), (2, 8192, 64), (1, 8192, 64))
    assert plan(128, 0)[1][-2:] == (None, None)
    assert plan(128, 64, 4096)[1][2:5] == plan(192, 0, 4096)[1][2:5]
    # and the two readers of the plan follow
    q, qr, k, kr, v, _ = _pieces(64, dn=128, dr=64, dv=128)
    assert F.flash_bwd_kernel(q, k, v, interpret=True, q_rope=qr,
                              k_rope=kr) == "fused"
    assert F.flash_lse_layout(q, k, v, q_rope=qr, k_rope=kr) in ("row",
                                                                 "lanes")


# -- the op of a Program ---------------------------------------------------------------

def _program(with_rope, hr=1):
    scope, main, startup = Scope(), Program(), Program()
    shapes = dict(q=(2, 4, 32, 16), qr=(2, 4, 32, 8), k=(2, 2, 32, 16),
                  kr=(2, hr, 32, 8), v=(2, 2, 32, 12), w=(2, 4, 32, 12))
    with scope_guard(scope), program_guard(main, startup):
        var = {n: layers.data(n, shape=list(s), dtype="float32",
                              append_batch_size=False,
                              stop_gradient=n == "w")
               for n, s in shapes.items()}
        more = dict(q_rope=var["qr"], k_rope=var["kr"]) if with_rope else {}
        out = layers.flash_attention(var["q"], var["k"], var["v"],
                                     causal=True, sm_scale=24 ** -0.5,
                                     **more)
        loss = layers.reduce_sum(out * var["w"])
        append_backward(loss)
    rng = np.random.RandomState(3)
    feed = {n: rng.randn(*s).astype(np.float32) for n, s in shapes.items()}
    return scope, main, out, feed


@pytest.mark.parametrize("hr", [1, 2, 4])
def test_the_op_takes_the_slots_and_its_grad_op_returns_both(hr):
    from paddle_tpu.ops import attention_ops as A
    labels = dict(window="none", kv_groups="2", impl="jax", widths="16+8/12")
    before = (A.FLASH_LOWERINGS_CTR.value(lse="row", **labels),
              A.FLASH_GRAD_LOWERINGS_CTR.value(**labels),
              A.FLASH_BWD_KERNEL_CTR.value(kernel="jax", window="none",
                                           widths="16+8/12"))
    scope, main, out, feed = _program(True, hr)
    assert tuple(out.shape) == (2, 4, 32, 12)       # V's width, inferred
    ops = {op.type: op for op in main.global_block().ops}
    assert ops["flash_attention"].input("QRope") == ["qr"]
    g = ops["flash_attention_grad"]
    assert g.input("X$QRope") == ["qr"] and g.input("X$KRope") == ["kr"]
    assert g.output("IG$QRope") == [grad_var_name("qr")]
    assert g.output("IG$KRope") == [grad_var_name("kr")]
    names = ("q", "qr", "k", "kr", "v")
    got = Executor().run(main, feed=feed, scope=scope, fetch_list=[out.name] + [
        grad_var_name(n) for n in names])
    pieces = [jnp.asarray(feed[n]) for n in names]
    want_o, _, want = _oracle(pieces, jnp.asarray(feed["w"]), 24 ** -0.5,
                              True, None)
    _close(got[0], want_o, 2e-5, "Out")
    for n, a, b in zip(names, got[1:], want):
        assert a.shape == feed[n].shape
        _close(a, b, 2e-5, f"d / d {n}")
    after = (A.FLASH_LOWERINGS_CTR.value(lse="row", **labels),
             A.FLASH_GRAD_LOWERINGS_CTR.value(**labels),
             A.FLASH_BWD_KERNEL_CTR.value(kernel="jax", window="none",
                                          widths="16+8/12"))
    assert after == tuple(b + 1 for b in before)


def test_an_op_without_the_slots_is_labelled_and_lowered_as_before():
    from paddle_tpu.ops import attention_ops as A
    labels = dict(window="none", kv_groups="2", impl="jax", widths="16/12")
    before = A.FLASH_GRAD_LOWERINGS_CTR.value(**labels)
    scope, main, out, feed = _program(False)
    g = next(op for op in main.global_block().ops
             if op.type == "flash_attention_grad")
    assert not g.input("X$QRope") and "IG$QRope" not in g.outputs
    dq, = Executor().run(main, feed=feed, scope=scope,
                         fetch_list=[grad_var_name("q")])
    assert dq.shape == (2, 4, 32, 16)
    assert A.FLASH_GRAD_LOWERINGS_CTR.value(**labels) == before + 1


def test_the_layer_refuses_half_a_pair():
    with program_guard(Program(), Program()):
        x = layers.data("x", shape=[1, 2, 8, 4], dtype="float32",
                        append_batch_size=False)
        with pytest.raises(ValueError, match="come together"):
            layers.flash_attention(x, x, x, q_rope=x)
