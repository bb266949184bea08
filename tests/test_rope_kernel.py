"""The rotary kernel (``paddle_tpu/pallas/rope.py``, interpreted on the CPU)
against the jnp form the op keeps for every other shape
(``ops/attention_ops.py:_rope_xla``) and against the plain reference
(``benchmark/reference/olmoe_1b_7b.py:rope``; for adjacent pairs the
published permute-and-rotate of ``benchmark/reference/joyai_llm_flash.py``),
forward and gradient; which shapes the lowering gives the kernel; what
``paddle_tpu_rope_lowerings_total`` counts.

Tolerances.  Kernel and jnp form compute the same float32 products and sums
and round once; on the CPU, where XLA contracts a product and a sum into one
fused multiply-add in one form and not the other, they differ by an ulp of
the float32 sum: held to 1e-6 of the largest entry in float32 and to one
bf16 ulp (2^-8 of the largest entry) in bf16.  The reference is float32
throughout, so a bf16 stream is held to its own rounding of it."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import paddle_tpu as pt  # noqa: E402,F401
from benchmark.reference import joyai_llm_flash as joyai_ref  # noqa: E402
from benchmark.reference import olmoe_1b_7b as ref  # noqa: E402
from paddle_tpu import device, layers  # noqa: E402
from paddle_tpu.framework import (Executor, Program, Scope,  # noqa: E402
                                  program_guard, scope_guard)
from paddle_tpu.framework.backward import append_backward  # noqa: E402
from paddle_tpu.framework.core import grad_var_name  # noqa: E402
from paddle_tpu.ops import attention_ops  # noqa: E402
from paddle_tpu.pallas import rope as kernel  # noqa: E402

THETA = 10000.0
B, H, T = 2, 3, 32


def _stream(width, rank, dtype, seed):
    """A ``[B, H, T, head_dim]`` (rank 4) or ``[B, T, H * head_dim]`` (rank
    3) stream and its head_dim; width 192: the 64 rotary lanes split off a
    192-wide head, as ``latent_attention`` hands them over."""
    dh = 64 if width == 192 else width
    x = jax.random.normal(jax.random.PRNGKey(seed), (B, H, T, width))
    x = x[..., width - dh:].astype(dtype)
    if rank == 3:
        x = x.transpose(0, 2, 1, 3).reshape(B, T, H * dh)
    return x, dh


def _reference(x, dh, interleaved):
    """The plain float32 reference on ``x`` of either rank, in the kernel's
    lane order."""
    xf = x.astype(jnp.float32)
    heads = xf if x.ndim == 4 else \
        xf.reshape(B, T, H, dh).transpose(0, 2, 1, 3)       # [B, H, T, dh]
    if interleaved:
        # the published code leaves each pair's members in the two halves
        perm = np.concatenate([np.arange(0, dh, 2), np.arange(1, dh, 2)])
        out = jnp.stack([joyai_ref.rope_published(
            h.transpose(1, 0, 2), THETA) for h in heads])   # [B, T, H, dh]
        out = out[..., np.argsort(perm)]
    else:
        out = ref.rope(heads.transpose(0, 2, 1, 3), THETA)
    return out.reshape(x.shape) if x.ndim == 3 else \
        out.transpose(0, 2, 1, 3)


def _close(got, want, tol, what):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    err = np.abs(got - want).max() / max(np.abs(want).max(), 1e-12)
    assert err <= tol, f"{what}: {err:.3e} of the largest entry > {tol}"


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("rank", [3, 4])
@pytest.mark.parametrize("width", [64, 128, 192])
@pytest.mark.parametrize("pairing", ["half", "interleaved"])
def test_kernel_matches_the_jnp_form_and_the_reference(pairing, width, rank,
                                                       dtype):
    interleaved = pairing == "interleaved"
    dtype = jnp.dtype(dtype)
    x, dh = _stream(width, rank, dtype, 0)
    g, _ = _stream(width, rank, dtype, 1)
    tol = 1e-6 if dtype == jnp.float32 else 2.0 ** -8

    def jnp_form(v):
        return attention_ops._rope_xla(v, dh, THETA, interleaved)

    out = kernel.rope(x, dh, THETA, interleaved, interpret=True)
    dx = kernel.rope(g, dh, THETA, interleaved, transpose=True,
                     interpret=True)
    assert out.dtype == dx.dtype == dtype and out.shape == x.shape
    _close(out, jnp_form(x), tol, "kernel against the jnp form")
    _close(dx, jax.vjp(jnp_form, x)[1](g)[0], tol,
           "kernel's gradient against the jnp form's")
    want, back = jax.vjp(lambda v: _reference(v, dh, interleaved),
                         x.astype(jnp.float32))
    _close(out, want, tol, "kernel against the reference")
    _close(dx, back(g.astype(jnp.float32))[0], tol,
           "kernel's gradient against the reference's")


def _rope_program(shape, head_dim, interleaved=False):
    """``(out, d sum(out * w) / dx)`` of the op on random float32 ``x``."""
    rng = np.random.RandomState(0)
    x = rng.randn(*shape).astype(np.float32)
    w = rng.randn(*shape).astype(np.float32)
    scope, main = Scope(), Program()
    with scope_guard(scope), program_guard(main, Program()):
        xv = layers.data("x", shape=list(shape), dtype="float32",
                         append_batch_size=False, stop_gradient=False)
        wv = layers.data("w", shape=list(shape), dtype="float32",
                         append_batch_size=False)
        out = layers.rope(xv, head_dim, THETA, interleaved=interleaved)
        append_backward(layers.reduce_sum(out * wv))
        grads = [op for op in main.global_block().ops
                 if op.type == "rope_grad"]
        # the op's own gradient: Out's gradient in, nothing of the forward
        assert [sorted(op.inputs) for op in grads] == [["OG$Out"]]
    got = Executor().run(main, feed={"x": x, "w": w}, scope=scope,
                         fetch_list=[out.name, grad_var_name("x")])
    form = functools.partial(attention_ops._rope_xla, dh=head_dim,
                             theta=THETA, interleaved=interleaved)
    want, back = jax.vjp(form, jnp.asarray(x))
    return got, (want, back(jnp.asarray(w))[0])


def _counts():
    return {(labels["form"], labels["pairing"], labels["width"]):
            int(cell.get()) for labels, cell in
            attention_ops.ROPE_LOWERINGS_CTR.series()}


def _moved(before):
    """The counter's series that moved since ``before``, and by how much."""
    return {k: v - before.get(k, 0) for k, v in _counts().items()
            if v != before.get(k, 0)}


@pytest.fixture
def as_on_a_tpu(monkeypatch):
    """The lowering as a TPU would choose it, the kernel interpreted."""
    monkeypatch.setattr(device, "on_tpu", lambda: True)
    monkeypatch.setattr(kernel, "rope", functools.partial(kernel.rope,
                                                          interpret=True))


@pytest.mark.parametrize("shape,head_dim,why", [
    ((2, 3, 20, 128), 128, "a length no block divides"),
    ((2, 16, 3 * 8), 8, "the tests' toy width"),
    ((2, 16, 2 * 64), 64, "half tiles side by side"),
    ((2, 3, 16, 32), 32, "a quarter of a tile")])
def test_shapes_the_kernel_does_not_take_keep_the_jnp_form(
        as_on_a_tpu, shape, head_dim, why):
    assert not kernel.fits(shape, head_dim, jnp.float32), why
    before = _counts()
    got, want = _rope_program(shape, head_dim)
    for a, b in zip(got, want):
        _close(a, b, 1e-6, f"rope {shape}")
    assert _moved(before) == {("xla", "half", str(head_dim)): 2}


def test_the_counter_counts_each_form_forward_and_gradient(as_on_a_tpu):
    before = _counts()
    for shape, head_dim, interleaved in (((2, 3, 32, 128), 128, False),
                                         ((2, 1, 32, 64), 64, True),
                                         ((2, 32, 2 * 128), 128, False)):
        assert kernel.fits(shape, head_dim, jnp.float32)
        got, want = _rope_program(shape, head_dim, interleaved)
        _close(got[0], want[0], 1e-6, f"rope {shape}")
        _close(got[1], want[1], 1e-6, f"rope_grad {shape}")
    _rope_program((2, 8, 24), 8)
    assert _moved(before) == {("kernel", "half", "128"): 4,
                              ("kernel", "interleaved", "64"): 2,
                              ("xla", "half", "8"): 2}


def test_off_the_chip_every_shape_keeps_the_jnp_form():
    before = _counts()
    _rope_program((2, 3, 32, 128), 128)
    assert _moved(before) == {("xla", "half", "128"): 2}
