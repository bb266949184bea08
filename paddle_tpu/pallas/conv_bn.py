"""Fused 1x1-conv (matmul) + BatchNorm building blocks (Pallas, TPU).

RN50_ABLATION.md prices ResNet-50's gap to roofline at XLA's fusion
policy around BatchNorm: with batch statistics, every conv output is
(1) written, (2) re-read for the stat reductions, and (3) re-read +
re-written by the normalize — HBM passes a fused executor would fold
into the conv itself.  A bottleneck block's 1x1 convs ARE matmuls
([N*H*W, Cin] @ [Cin, Cout]), so the fold needs no conv halos:

- ``matmul_bn_stats``: Y = prologue(X) @ W with the BN NORMALIZE (+ReLU)
  of the PRODUCER's batch-norm folded into the X read (consumer-side
  fold), and sum(Y)/sum(Y^2) accumulated per channel as the epilogue —
  Y is read exactly once and its stats cost no extra pass.

Kept behind the conv1x1+BN fusion pattern; the measured verdict
on whether this beats XLA's own fusion end-to-end lives in
RN50_ABLATION.md (round-4 addendum).  Ref workload:
/root/reference/python/paddle/fluid/tests/book/test_image_classification.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _kernel(x_ref, w_ref, mu_ref, inv_ref, g_ref, b_ref, y_ref, s_ref,
            s2_ref, *, relu, normalize, out_dtype):
    import jax.lax as lax
    from jax.experimental import pallas as pl

    i = pl.program_id(0)

    x = x_ref[...].astype(jnp.float32)
    if normalize:
        x = (x - mu_ref[...]) * inv_ref[...] * g_ref[...] + b_ref[...]
    if relu:   # independent of the normalize prologue
        x = jnp.maximum(x, 0.0)
    y = lax.dot_general(x.astype(jnp.bfloat16),
                        w_ref[...].astype(jnp.bfloat16),
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
    y_ref[...] = y.astype(out_dtype)

    @pl.when(i == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)
        s2_ref[...] = jnp.zeros_like(s2_ref)

    s_ref[...] = s_ref[...] + jnp.sum(y, axis=0, keepdims=True)
    s2_ref[...] = s2_ref[...] + jnp.sum(y * y, axis=0, keepdims=True)


def matmul_bn_stats(x, w, producer_stats=None, relu=True, block_m=1024,
                    interpret=False):
    """Y = act(norm(x)) @ w, plus per-channel (sum, sumsq) of Y.

    ``producer_stats``: optional (mu, inv_sigma, gamma, beta) each [Cin]
    — the BN of the op that PRODUCED x, folded into this kernel's read.
    Returns (y [M, Cout], sums [Cout], sumsqs [Cout]).
    """
    from jax.experimental import pallas as pl

    m, kdim = x.shape
    n = w.shape[1]
    normalize = producer_stats is not None
    if normalize:
        mu, inv, g, b = (a.reshape(1, kdim).astype(jnp.float32)
                         for a in producer_stats)
        stat_args = (mu, inv, g, b)
    else:
        stat_args = ()
    block_m = min(block_m, m)
    while m % block_m:
        # M = N*H*W is highly composite for conv shapes; shrink the block
        # until it divides instead of padding (padded rows would pollute
        # the stats through the normalize prologue)
        block_m //= 2
        if block_m < 8:
            raise ValueError(f"no dividing block_m for M={m}")
    mp = m
    nm = mp // block_m
    row_spec = pl.BlockSpec((1, kdim), lambda i: (0, 0))
    in_specs = [pl.BlockSpec((block_m, kdim), lambda i: (i, 0)),
                pl.BlockSpec((kdim, n), lambda i: (0, 0))]
    if normalize:
        in_specs += [row_spec] * 4
        kern = functools.partial(_kernel, relu=relu, normalize=True,
                                 out_dtype=x.dtype)
    else:
        # no dead stat operands DMA'd per grid step on the plain path
        def kern(x_ref, w_ref, y_ref, s_ref, s2_ref):
            _kernel(x_ref, w_ref, None, None, None, None,
                    y_ref, s_ref, s2_ref, relu=relu, normalize=False,
                    out_dtype=x.dtype)
    y, s, s2 = pl.pallas_call(
        kern,
        grid=(nm,),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((block_m, n), lambda i: (i, 0)),
                   pl.BlockSpec((1, n), lambda i: (0, 0)),
                   pl.BlockSpec((1, n), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((mp, n), x.dtype),
                   jax.ShapeDtypeStruct((1, n), jnp.float32),
                   jax.ShapeDtypeStruct((1, n), jnp.float32)],
        interpret=interpret,
        name="matmul_bn_stats",
    )(x, w, *stat_args)
    return y, s.reshape(n), s2.reshape(n)


# ---------------------------------------------------------------------------
# NCHW-native variant: contraction over C, HW stays the minor (lane) dim —
# NO layout transpose at the kernel boundary (the channel-minor variant
# above costs 4 full transpose passes per op inside a real NCHW model,
# measured 114.7 -> 214.5 ms on the RN50 step; this one is the keeper)
# ---------------------------------------------------------------------------

def _nchw_kernel(x_ref, w_ref, y_ref, s_ref, s2_ref, *, out_dtype):
    import jax.lax as lax
    from jax.experimental import pallas as pl

    i, j = pl.program_id(0), pl.program_id(1)

    x = x_ref[0].astype(jnp.bfloat16)            # [Cin, bhw]
    w = w_ref[...].astype(jnp.bfloat16)          # [Cout, Cin]
    y = lax.dot_general(w, x, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)  # [Cout, bhw]
    y_ref[0] = y.astype(out_dtype)

    @pl.when((i == 0) & (j == 0))
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)
        s2_ref[...] = jnp.zeros_like(s2_ref)

    s_ref[...] = s_ref[...] + jnp.sum(y, axis=1, keepdims=True)
    s2_ref[...] = s2_ref[...] + jnp.sum(y * y, axis=1, keepdims=True)


def conv1x1_stats_nchw(x, w, block_hw=512, interpret=False):
    """y[n,co,p] = Σ_ci w[co,ci]·x[n,ci,p] plus per-co (sum, sumsq) of y.

    ``x``: [N, Cin, P] (P = H*W, contiguous NCHW view), ``w``:
    [Cout, Cin].  Returns (y [N, Cout, P], sums [Cout], sumsqs [Cout]).
    """
    from jax.experimental import pallas as pl

    nb, cin, p = x.shape
    cout = w.shape[0]
    # mosaic: last block dim must be a 128-multiple divisor of P, or P
    # itself (conv spatial sizes like 56^2=3136 have none — whole row
    # then; even stage0's row is only Cin*P*2B ≈ 1.6 MB of VMEM)
    cands = [b for b in range(block_hw, 0, -128)
             if b % 128 == 0 and p % b == 0]
    block_hw = cands[0] if cands else p
    nhw = p // block_hw
    y, s, s2 = pl.pallas_call(
        functools.partial(_nchw_kernel, out_dtype=x.dtype),
        grid=(nb, nhw),
        in_specs=[pl.BlockSpec((1, cin, block_hw), lambda i, j: (i, 0, j)),
                  pl.BlockSpec((cout, cin), lambda i, j: (0, 0))],
        out_specs=[pl.BlockSpec((1, cout, block_hw),
                                lambda i, j: (i, 0, j)),
                   pl.BlockSpec((cout, 1), lambda i, j: (0, 0)),
                   pl.BlockSpec((cout, 1), lambda i, j: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((nb, cout, p), x.dtype),
                   jax.ShapeDtypeStruct((cout, 1), jnp.float32),
                   jax.ShapeDtypeStruct((cout, 1), jnp.float32)],
        interpret=interpret,
        name="conv1x1_stats_nchw",
    )(x, w)
    return y, s.reshape(cout), s2.reshape(cout)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def conv1x1_stats(x, w, interpret=False):
    """Differentiable (y, sums, sumsqs) over NCHW-flattened x [N,Cin,P].

    Backward is XLA dot_generals in the SAME layout (no transposes):
    dy_eff = dy + ds + 2·y·ds2; dx[n,ci,p] = Σ_co w[co,ci]·dy_eff;
    dw[co,ci] = Σ_{n,p} dy_eff[n,co,p]·x[n,ci,p]."""
    return conv1x1_stats_nchw(x, w, interpret=interpret)


def _conv1x1_stats_fwd(x, w, interpret):
    y, s, s2 = conv1x1_stats_nchw(x, w, interpret=interpret)
    return (y, s, s2), (x, w, y)


def _conv1x1_stats_bwd(interpret, res, cts):
    x, w, y = res
    dy, ds, ds2 = cts
    dy_eff = (dy.astype(jnp.float32) + ds[None, :, None]
              + 2.0 * y.astype(jnp.float32) * ds2[None, :, None])
    dy_b = dy_eff.astype(x.dtype)
    # logical einsums in the SAME nc p layout — XLA's layout assignment
    # handles the physical form (only PALLAS boundaries force transposes)
    dx = jnp.einsum("nop,oc->ncp", dy_b, w.astype(dy_b.dtype))
    dw = jnp.einsum("nop,ncp->oc", dy_b, x)
    return dx.astype(x.dtype), dw.astype(w.dtype)


conv1x1_stats.defvjp(_conv1x1_stats_fwd, _conv1x1_stats_bwd)


# ---------------------------------------------------------------------------
# channel-minor variant (kept for reference/microbench; the NCHW op above
# is what the model pass uses)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def mm_stats(x, w, interpret=False):
    """(y, sums, sumsqs) with y = x @ w — the Pallas fused forward.

    Backward is plain XLA matmul math (dy_eff = dy + ds + 2·y·ds2,
    dx = dy_eff·wᵀ, dw = xᵀ·dy_eff): measured on the RN50 step the
    matmuls already run at the MXU rate and XLA fuses the stat-cotangent
    elementwise into them, so a Pallas backward has nothing left to save
    (RN50_ABLATION.md round-4 addendum)."""
    return matmul_bn_stats(x, w, None, relu=False, interpret=interpret)


def _mm_stats_fwd(x, w, interpret):
    y, s, s2 = matmul_bn_stats(x, w, None, relu=False, interpret=interpret)
    return (y, s, s2), (x, w, y)


def _mm_stats_bwd(interpret, res, cts):
    x, w, y = res
    dy, ds, ds2 = cts
    dy_eff = (dy.astype(jnp.float32) + ds[None, :]
              + 2.0 * y.astype(jnp.float32) * ds2[None, :])
    dy_b = dy_eff.astype(x.dtype)
    dx = dy_b @ w.T
    dw = (x.T @ dy_b).astype(w.dtype)
    return dx.astype(x.dtype), dw


mm_stats.defvjp(_mm_stats_fwd, _mm_stats_bwd)
