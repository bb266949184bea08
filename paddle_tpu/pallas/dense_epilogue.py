"""Fused dense epilogue: matmul + bias + activation in one Pallas pass.

TPP (arxiv 2104.05755) frames exactly this shape — a GEMM whose
epilogue (bias, activation) rides the accumulator while the tile is
still in VMEM, so the activation tensor is written to HBM once instead
of once per epilogue op.  XLA usually fuses bias+act into its own GEMM
already, which is why this kernel is NOT wired as a default lowering:
``analysis.fusion``'s autotuner benches it against the XLA composition
per (pattern, shape) and only routes ``fused_dense_act`` through it
when it measurably wins (the same measured-verdict discipline
``pallas/layer_norm.py`` documents for its LN kernel).

Forward tiles rows into VMEM ([block_m, K] @ [K, N] on the MXU in bf16
with f32 accumulation), applies bias + act on the accumulator, and
writes the tile once.  Backward is plain XLA matmul math through the
activation's local derivative — on the MXU there is nothing left for a
hand backward to save (what RN50_ABLATION.md found for its matmul too).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_LANE = 128


def _erf_gelu(v):
    """Exact (erf) gelu for use INSIDE the kernel: the Pallas TPU lowering
    of JAX 0.9 implements neither ``erf`` nor the ``erfc`` that
    ``jax.nn.gelu(approximate=False)`` now emits, so erf is the
    Abramowitz-Stegun 7.1.26 rational form (|error| <= 1.5e-7, below the
    bf16 output's rounding) built from ``exp`` alone."""
    z = jnp.abs(v) * 0.7071067811865476
    t = 1.0 / (1.0 + 0.3275911 * z)
    poly = t * (0.254829592 + t * (-0.284496736 + t * (
        1.421413741 + t * (-1.453152027 + t * 1.061405429))))
    erf = jnp.sign(v) * (1.0 - poly * jnp.exp(-z * z))
    return 0.5 * v * (1.0 + erf)


def _act_fn(name, approximate=False, in_kernel=False):
    if name == "relu":
        return lambda v: jnp.maximum(v, 0.0)
    if name == "gelu":
        if in_kernel and not approximate:
            return _erf_gelu
        return functools.partial(jax.nn.gelu, approximate=approximate)
    return lambda v: v


def tileable(m: int, block_m: int = 512) -> bool:
    """Whether ``M`` rows split into dividing row blocks of >= 8 (or fit
    one block) — what ``matmul_bias_act`` needs; callers test it up front
    instead of catching the kernel's ValueError."""
    return m <= block_m or m % 8 == 0


def _kernel(x_ref, w_ref, b_ref, y_ref, *, act, approximate, out_dtype):
    import jax.lax as lax

    x = x_ref[...].astype(jnp.bfloat16)
    w = w_ref[...].astype(jnp.bfloat16)
    y = lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
    y = y + b_ref[...].astype(jnp.float32)
    y = _act_fn(act, approximate, in_kernel=True)(y)
    y_ref[...] = y.astype(out_dtype)


def matmul_bias_act(x, w, b, act="", approximate=False, block_m=512,
                    interpret=False):
    """``act(x @ w + b)`` with the epilogue fused into the GEMM tile.

    ``x``: [M, K]; ``w``: [K, N]; ``b``: [N].  Differentiable via
    custom_vjp (XLA matmul backward).  Compiled by Mosaic unless
    ``interpret`` is passed (the CPU test path) — numerics match the jnp
    composition to bf16 rounding.  ``M`` must satisfy :func:`tileable`.
    """
    return _mba(x, w, b, act, bool(approximate), int(block_m),
                bool(interpret))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _mba(x, w, b, act, approximate, block_m, interpret):
    return _mba_fwd_impl(x, w, b, act, approximate, block_m, interpret)


def _mba_fwd_impl(x, w, b, act, approximate, block_m, interpret):
    from jax.experimental import pallas as pl

    m, k = x.shape
    n = w.shape[1]
    bm = min(block_m, m)
    while m % bm:
        # conv-free dense shapes are usually powers of two; shrink until
        # the block divides instead of padding (a padded tile would need
        # a masked bias/act epilogue)
        bm //= 2
        if bm < 8:
            raise ValueError(f"no dividing block_m for M={m}")
    y = pl.pallas_call(
        functools.partial(_kernel, act=act, approximate=approximate,
                          out_dtype=x.dtype),
        grid=(m // bm,),
        in_specs=[pl.BlockSpec((bm, k), lambda i: (i, 0)),
                  pl.BlockSpec((k, n), lambda i: (0, 0)),
                  pl.BlockSpec((1, n), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((bm, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        interpret=interpret,
        name="matmul_bias_act",
    )(x, w, b.reshape(1, n))
    return y


def _mba_fwd(x, w, b, act, approximate, block_m, interpret):
    y = _mba_fwd_impl(x, w, b, act, approximate, block_m, interpret)
    return y, (x, w, b)


def _mba_bwd(act, approximate, block_m, interpret, res, dy):
    x, w, b = res
    # recompute the pre-activation (one extra GEMM beats saving the
    # [M, N] pre-act tensor to HBM; XLA CSEs it with the forward when
    # both live in one computation)
    pre = (x.astype(jnp.bfloat16) @ w.astype(jnp.bfloat16)).astype(
        jnp.float32) + b.astype(jnp.float32)
    if act:
        _, act_vjp = jax.vjp(_act_fn(act, approximate), pre)
        dpre, = act_vjp(dy.astype(jnp.float32))
    else:
        dpre = dy.astype(jnp.float32)
    dpre_b = dpre.astype(x.dtype)
    dx = dpre_b @ w.T.astype(dpre_b.dtype)
    dw = x.T @ dpre_b
    db = jnp.sum(dpre, axis=0)
    return (dx.astype(x.dtype), dw.astype(w.dtype), db.astype(b.dtype))


_mba.defvjp(_mba_fwd, _mba_bwd)
