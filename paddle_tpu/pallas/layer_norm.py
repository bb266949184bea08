"""Fused LayerNorm: one-pass forward, fused one-pass backward (Pallas).

Why a kernel: XLA lowers training LayerNorm to separate stat/normalize/
grad-reduction fusions — measured 15.1 ms of a 127.3 ms BERT-base step
across 25 LN sites (r3 ablation, BERT_ABLATION.md).  Tiling rows into
VMEM lets each pass touch HBM exactly once: fwd reads x and writes y in
one sweep (stats live in registers); bwd reads (x, dy) once, emits dx and
accumulates dscale/dbias in VMEM scratch across the sequential TPU grid.

Backward recomputes the row stats from the x tile instead of saving
mean/rstd — the tile is already in VMEM, so recomputation is free while
saved stats would be extra HBM traffic.

Available as a library kernel but NOT wired as the default ``layer_norm``
lowering: measured end-to-end (BERT_ABLATION.md) the kernel boundary
costs more in lost XLA fusion/overlap than the one-sweep HBM saving
recoups (132.7 ms vs 127.3 ms step), so ops/nn_ops.py deliberately keeps
the plain jnp math as the lowering; call ``fused_layer_norm`` directly
where a standalone LN dominates.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..device import on_tpu

_LANE = 128


def _ln_ref(x, scale, bias, eps):
    """Plain-jax reference (and CPU fallback): f32 stats, input dtype out."""
    xf = x.astype(jnp.float32)
    m = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True) - jnp.square(m)
    rstd = jax.lax.rsqrt(jnp.maximum(var, 0.0) + eps)
    y = (xf - m) * rstd * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    return y.astype(x.dtype)


def _fwd_kernel(x_ref, s_ref, b_ref, y_ref, *, eps):
    xf = x_ref[...].astype(jnp.float32)
    m = jnp.mean(xf, axis=1, keepdims=True)
    var = jnp.mean(jnp.square(xf), axis=1, keepdims=True) - jnp.square(m)
    rstd = jax.lax.rsqrt(jnp.maximum(var, 0.0) + eps)
    y = (xf - m) * rstd * s_ref[...].astype(jnp.float32) \
        + b_ref[...].astype(jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)


def _bwd_kernel(x_ref, s_ref, dy_ref, dx_ref, ds_ref, db_ref,
                ds_sc, db_sc, *, eps):
    from jax.experimental import pallas as pl

    i = pl.program_id(0)
    n = pl.num_programs(0)

    @pl.when(i == 0)
    def _init():
        ds_sc[...] = jnp.zeros_like(ds_sc)
        db_sc[...] = jnp.zeros_like(db_sc)

    xf = x_ref[...].astype(jnp.float32)
    dyf = dy_ref[...].astype(jnp.float32)
    m = jnp.mean(xf, axis=1, keepdims=True)
    var = jnp.mean(jnp.square(xf), axis=1, keepdims=True) - jnp.square(m)
    rstd = jax.lax.rsqrt(jnp.maximum(var, 0.0) + eps)
    xhat = (xf - m) * rstd
    g = dyf * s_ref[...].astype(jnp.float32)
    c1 = jnp.mean(g, axis=1, keepdims=True)
    c2 = jnp.mean(g * xhat, axis=1, keepdims=True)
    dx_ref[...] = (rstd * (g - c1 - xhat * c2)).astype(dx_ref.dtype)
    ds_sc[...] += jnp.sum(dyf * xhat, axis=0)
    db_sc[...] += jnp.sum(dyf, axis=0)

    @pl.when(i == n - 1)
    def _flush():
        ds_ref[...] = ds_sc[...]
        db_ref[...] = db_sc[...]


def _pick_block(rows):
    for b in (512, 256, 128, 64, 32, 16, 8):
        if rows % b == 0:
            return b
    return 1


def _fwd_pallas(x2, scale, bias, eps, interpret):
    from jax.experimental import pallas as pl

    rows, d = x2.shape
    br = _pick_block(rows)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        grid=(rows // br,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), x2.dtype),
        interpret=interpret,
        name="layer_norm_fwd",
    )(x2, scale, bias)


def _bwd_pallas(x2, scale, dy2, eps, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, d = x2.shape
    br = _pick_block(rows)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, eps=eps),
        grid=(rows // br,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
            pl.BlockSpec((br, d), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, d), x2.dtype),
            jax.ShapeDtypeStruct((d,), jnp.float32),
            jax.ShapeDtypeStruct((d,), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((d,), jnp.float32),
            pltpu.VMEM((d,), jnp.float32),
        ],
        interpret=interpret,
        name="layer_norm_bwd",
    )(x2, scale, dy2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _fused_ln(x2, scale, bias, eps, interpret):
    if on_tpu() or interpret:
        return _fwd_pallas(x2, scale, bias, eps, interpret)
    return _ln_ref(x2, scale, bias, eps)


def _fused_ln_fwd(x2, scale, bias, eps, interpret):
    return _fused_ln(x2, scale, bias, eps, interpret), (x2, scale)


def _fused_ln_bwd(eps, interpret, res, dy):
    x2, scale = res
    if on_tpu() or interpret:
        dx, ds, db = _bwd_pallas(x2, scale, dy, eps, interpret)
    else:
        xf = x2.astype(jnp.float32)
        dyf = dy.astype(jnp.float32)
        m = jnp.mean(xf, axis=1, keepdims=True)
        var = jnp.mean(jnp.square(xf), axis=1, keepdims=True) \
            - jnp.square(m)
        rstd = jax.lax.rsqrt(jnp.maximum(var, 0.0) + eps)
        xhat = (xf - m) * rstd
        g = dyf * scale.astype(jnp.float32)
        c1 = jnp.mean(g, axis=1, keepdims=True)
        c2 = jnp.mean(g * xhat, axis=1, keepdims=True)
        dx = (rstd * (g - c1 - xhat * c2)).astype(x2.dtype)
        ds = jnp.sum(dyf * xhat, axis=0)
        db = jnp.sum(dyf, axis=0)
    return dx, ds.astype(scale.dtype), db.astype(scale.dtype)


_fused_ln.defvjp(_fused_ln_fwd, _fused_ln_bwd)


def fused_layer_norm(x, scale, bias, eps=1e-5, interpret=False):
    """LayerNorm over the LAST dim of ``x`` with f32 stats.

    ``x``: [..., d]; ``scale``/``bias``: [d].  Differentiable (custom
    one-pass backward).  On CPU (no ``interpret``) runs the plain-jax
    reference math.
    """
    lead = x.shape[:-1]
    d = x.shape[-1]
    x2 = x.reshape(-1, d)
    y2 = _fused_ln(x2, scale, bias, float(eps), interpret)
    return y2.reshape(lead + (d,))
