"""Unit coverage for the pallas/conv_bn.py building blocks (the fused
conv+BN machinery RN50_ABLATION.md's round-4 addendum documents): kernel
parity, custom-vjp gradients, block sizing, and the flash backward's
partial-budget fallback logic."""

import functools

import numpy as np

import jax
import jax.numpy as jnp

from paddle_tpu.pallas.conv_bn import (conv1x1_stats, conv1x1_stats_nchw,
                                       matmul_bn_stats, mm_stats)


def _rand(shape, seed):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape)
                       .astype(np.float32) * 0.3)


def test_conv1x1_stats_forward_parity():
    x, w = _rand((2, 16, 49), 0), _rand((8, 16), 1)
    y, s, s2 = conv1x1_stats_nchw(x, w, interpret=True)
    y_ref = jnp.einsum("oc,ncp->nop",
                       w.astype(jnp.bfloat16), x.astype(jnp.bfloat16)
                       ).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(s), np.asarray(y_ref.sum((0, 2))),
                               rtol=2e-2, atol=3e-1)
    np.testing.assert_allclose(np.asarray(s2),
                               np.asarray((y_ref ** 2).sum((0, 2))),
                               rtol=3e-2, atol=5e-1)


def test_conv1x1_stats_custom_vjp_matches_reference():
    """Gradients through (y, sums, sumsqs) — all three cotangent routes."""
    x, w = _rand((2, 16, 49), 2), _rand((8, 16), 3)
    coef = jnp.arange(8, dtype=jnp.float32)

    def loss(fn):
        def go(x, w):
            y, s, s2 = fn(x, w)
            return ((y.astype(jnp.float32) ** 2).sum() * 0.5
                    + (s * coef).sum() + (s2 * 0.1).sum())
        return go

    def ref(x, w):
        y = jnp.einsum("oc,ncp->nop", w, x)
        return y, y.sum((0, 2)), (y * y).sum((0, 2))

    g = jax.grad(loss(functools.partial(conv1x1_stats, interpret=True)),
                 argnums=(0, 1))(x, w)
    g_ref = jax.grad(loss(ref), argnums=(0, 1))(x, w)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-2, atol=3e-2)


def test_conv1x1_block_sizing():
    """P with no 128-multiple divisor (56^2=3136) takes the whole row;
    divisible P gets a 128-multiple block."""
    x, w = _rand((1, 8, 3136), 4), _rand((8, 8), 5)
    y, s, _ = conv1x1_stats_nchw(x, w, interpret=True)   # must not raise
    assert y.shape == (1, 8, 3136)
    x2 = _rand((1, 8, 1024), 6)
    y2, _, _ = conv1x1_stats_nchw(x2, w, interpret=True)
    assert y2.shape == (1, 8, 1024)


def test_matmul_bn_stats_relu_without_producer_stats():
    """relu applies independently of the normalize prologue (review
    finding: it was silently dropped when producer_stats was None)."""
    x = _rand((64, 16), 7)
    w = _rand((16, 8), 8)
    y, _, _ = matmul_bn_stats(x, w, None, relu=True, block_m=32,
                              interpret=True)
    y_ref = (jnp.maximum(x, 0.0).astype(jnp.bfloat16)
             @ w.astype(jnp.bfloat16)).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=2e-2, atol=2e-2)


def test_mm_stats_grads():
    x, w = _rand((64, 16), 9), _rand((16, 8), 10)

    def loss(x, w):
        y, s, s2 = mm_stats(x, w, interpret=True)
        return (y.astype(jnp.float32) ** 2).sum() + s.sum() + s2.sum()

    def ref(x, w):
        y = x @ w
        return (y ** 2).sum() + y.sum() + (y * y).sum()

    g = jax.grad(loss, argnums=(0, 1))(x, w)
    g_ref = jax.grad(ref, argnums=(0, 1))(x, w)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-2, atol=3e-1)

