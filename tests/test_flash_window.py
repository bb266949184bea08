"""The flash attention kernels' ``window`` and grouped K/V heads
(``paddle_tpu/pallas/flash_attention.py``) against the dense-mask oracle
``mha_reference``: forward and dQ, dK, dV of the Pallas kernels in interpret
mode (both backward implementations) and of the blockwise jax fallback, at
T 64 in blocks of 16 (the suite runs at its time limit), and the K/V blocks
the index maps name at the Trinity-Mini cell's real sizes."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

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


@pytest.mark.parametrize("window,impl", [
    (8, "combined"),      # smaller than a block (16)
    (16, "split"),        # a block exactly
    (40, "combined"),     # more than two blocks
    (40, "split"),
    (2, "combined"),      # the diagonal and its neighbour
    (23, None),           # the blockwise jax fallback (no TPU, no interpret)
])
def test_window_flash_matches_the_dense_mask_oracle(window, impl):
    """Forward and dQ, dK, dV of the Pallas kernels (interpret mode) over 4
    query heads on 2 KV heads, T 64 in blocks of 16, against
    ``mha_reference`` under the dense ``0 <= i - j < window`` mask."""
    q, k, v, w = _qkv(64)
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


def test_grouped_kv_heads_equal_repeated_kv_heads():
    """4 query heads over 2 KV heads through the kernels' index maps against
    the same K and V repeated to 4 heads outside; dK and dV summed over each
    group."""
    q, k, v, w = _qkv(32)
    kw = dict(causal=True, window=12, block_q=16, block_k=16, interpret=True)
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
