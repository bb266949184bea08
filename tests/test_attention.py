"""Flash/ring attention vs the O(T^2) reference — numeric parity of both
forward and gradients (the OpTest discipline of SURVEY §4.1 applied to the
Pallas layer), plus ring attention under shard_map on the 8-device mesh
(§4.4's multi-device-without-a-cluster pattern)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from paddle_tpu.pallas import flash_attention, mha_reference, ring_attention


def _rand(shape, seed):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape)
                       .astype(np.float32))


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(causal):
    q, k, v = (_rand((2, 2, 24, 8), i) for i in range(3))
    ref = mha_reference(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=8, block_k=8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_with_bias():
    q, k, v = (_rand((2, 3, 16, 8), i) for i in range(3))
    bias = _rand((16, 16), 7)
    ref = mha_reference(q, k, v, bias=bias[None, None])
    out = flash_attention(q, k, v, bias=bias, block_q=8, block_k=8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_grads_match_reference(causal):
    q, k, v = (_rand((2, 2, 20, 8), i) for i in range(3))
    w = _rand((2, 2, 20, 8), 9)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=causal) * w)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal,
                                       block_q=8, block_k=8) * w)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_out = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_out, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_flash_bias_grad():
    q, k, v = (_rand((2, 2, 12, 8), i) for i in range(3))
    bias = _rand((12, 12), 5)
    w = _rand((2, 2, 12, 8), 6)

    def loss_ref(b):
        return jnp.sum(mha_reference(q, k, v, bias=b[None, None]) * w)

    def loss_flash(b):
        return jnp.sum(flash_attention(q, k, v, bias=b,
                                       block_q=8, block_k=8) * w)

    np.testing.assert_allclose(np.asarray(jax.grad(loss_flash)(bias)),
                               np.asarray(jax.grad(loss_ref)(bias)),
                               rtol=2e-4, atol=2e-4)


def test_flash_pallas_interpret_kernel():
    """The actual Pallas kernel (interpret mode on CPU) matches too."""
    q, k, v = (_rand((1, 2, 16, 8), i) for i in range(3))
    for causal in (False, True):
        ref = mha_reference(q, k, v, causal=causal)
        out = flash_attention(q, k, v, causal=causal, block_q=8,
                              block_k=8, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


def _ring_run(q, k, v, causal):
    mesh = Mesh(np.array(jax.devices()), ("sp",))
    spec = P(None, None, "sp", None)
    fn = shard_map(
        lambda q, k, v: ring_attention(q, k, v, "sp", causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return jax.jit(fn)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_reference(causal):
    q, k, v = (_rand((1, 2, 32, 8), i) for i in range(3))
    ref = mha_reference(q, k, v, causal=causal)
    out = _ring_run(q, k, v, causal)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_grads(causal):
    q, k, v = (_rand((1, 2, 16, 8), i) for i in range(3))
    w = _rand((1, 2, 16, 8), 11)
    ring = _ring_run(q, k, v, causal)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=causal) * w)

    def loss_ring(q, k, v):
        return jnp.sum(ring(q, k, v) * w)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_out = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_out, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_flash_causal_end_aligned_kv_cache():
    """Tq != Tk causal must be end-aligned (decode step sees all keys)."""
    q = _rand((1, 1, 2, 8), 0)
    k, v = _rand((1, 1, 8, 8), 1), _rand((1, 1, 8, 8), 2)
    ref = mha_reference(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=8, block_k=8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_bias_per_batch_broadcast():
    """[b, 1, Tq, Tk] padding-mask-style bias broadcasts over heads."""
    q, k, v = (_rand((2, 2, 4, 8), i) for i in range(3))
    bias = _rand((2, 1, 4, 4), 7)
    ref = mha_reference(q, k, v, bias=bias)
    out = flash_attention(q, k, v, bias=bias, block_q=8, block_k=8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_pallas_backward_kernels_interpret(causal):
    """The Pallas dq + dk/dv kernels (interpret mode) match the reference
    gradients, including ragged block edges (T not divisible by block)."""
    rng = np.random.RandomState(11)
    q = jnp.asarray(rng.randn(2, 2, 13, 8).astype(np.float32))
    k = jnp.asarray(rng.randn(2, 2, 13, 8).astype(np.float32))
    v = jnp.asarray(rng.randn(2, 2, 13, 8).astype(np.float32))

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=causal) ** 2)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal,
                                       block_q=8, block_k=8,
                                       interpret=True) ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_out = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_out, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("impl", ["fused", "split"])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_pallas_bwd_kernels_interpret(impl, causal):
    """Both Pallas backward implementations (the single-recompute fused
    kernel with the head's dq in scratch, and the two-pass split kernels)
    match the dense reference gradients in interpret mode — including a
    non-multiple sequence length (padding path)."""
    q, k, v = (_rand((1, 2, 20, 8), i) for i in range(3))

    def loss_ref(q, k, v):
        return (mha_reference(q, k, v, causal=causal) ** 2).sum()

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=causal, block_q=8,
                                block_k=8, bwd_impl=impl,
                                interpret=True) ** 2).sum()

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_out = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_out, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_flash_bwd_falls_back_to_split_past_the_vmem_share(monkeypatch):
    """The one fall-back left: the fused backward where a head's dQ
    accumulator and the blocks fit ``_FUSED_VMEM_SHARE`` of a core's VMEM,
    the split kernels past it, asked for by name or not; ``impl="split"``
    is the split kernels outright."""
    import importlib
    FA = importlib.import_module("paddle_tpu.pallas.flash_attention")
    calls = []
    orig = FA._flash_bwd_pallas_split
    monkeypatch.setattr(
        FA, "_flash_bwd_pallas_split",
        lambda *a, fused=False, **k: calls.append(
            "fused" if fused else "split") or orig(*a, fused=fused, **k))
    r = np.random.RandomState(0)
    q = jnp.asarray(r.randn(2, 32, 8).astype(np.float32) * 0.3)
    o = jnp.asarray(r.randn(2, 32, 8).astype(np.float32) * 0.3)
    lse = jnp.asarray(r.randn(2, 32).astype(np.float32))
    do = jnp.asarray(r.randn(2, 32, 8).astype(np.float32) * 0.3)
    args = (q, q, q, o, lse, do, False, 1.0, 8, 8, 0, True)
    want = FA._flash_bwd_pallas(*args)
    assert calls == ["fused"]
    FA._flash_bwd_pallas(*args, impl="split")
    assert calls[-1] == "split"
    monkeypatch.setattr(FA, "_FUSED_VMEM_SHARE", 0.0)
    for impl in (None, "fused"):
        got = FA._flash_bwd_pallas(*args, impl=impl)
        assert calls[-1] == "split"
        for a, b in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=2e-5)


def test_flash_bwd_impl_names_the_two_kernels():
    """``bwd_impl="combined"`` (the kernel that went in PR 44) and any other
    name raise, naming the two that are left."""
    q = _rand((1, 2, 16, 8), 0)
    for name in ("combined", "fast"):
        with pytest.raises(ValueError, match="fused.*split"):
            flash_attention(q, q, q, causal=True, bwd_impl=name,
                            interpret=True)
