"""Manifold-constrained hyper-connections (arXiv:2512.24880 §4, over the
hyper-connections of arXiv:2409.19606): the residual stream is ``n`` streams
wide, a sublayer reads a learned, token-dependent mix of them and writes its
output back through another, and the streams themselves are mixed by a doubly
stochastic ``n x n`` map.  Two ops round a sublayer ``F``, each with a grad op
of its own::

    u, H_post, H_res = hc_pre(X; Phi, alpha, b)
    X' = hc_post(X, F(norm(u)), H_post, H_res)

The stream is held as ``n`` tensors ``[b, t, C]``, one a stream, each like
every other activation of the model (one ``[b, t, n * C]`` tensor made XLA
build every block's output by padding each stream to the whole width and
taking the maximum, in float32, and a ``[.., n, C]`` array would pad ``n`` to
a sublane tile: PERF.md section 6, PR 45): the ops' ``X`` and ``Out`` slots
hold ``n`` variables.  With ``x = vec(X)`` (the streams side by side, so
``Phi``'s rows ``j C .. (j + 1) C`` meet stream ``j``) and ``r = (mean(x^2) +
rms_eps)^-1/2``::

    m = r * (x Phi)                          Phi [n C, 2 n + n^2]: pre|post|res
    H_pre  = sigmoid(alpha[0] m[:n] + b[:n])
    H_post = 2 sigmoid(alpha[1] m[n:2n] + b[n:2n])
    H_res  = SK(clip(alpha[2] mat(m[2n:]) + mat(b[2n:]), lo, hi))
    SK(A): M = exp(A); ``iters`` times: every column of M divided by its sum
           + eps, then every row by its sum + eps
    u  = sum_j H_pre[j] X[j]
    X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y

The coefficients (``m``, the three maps, Sinkhorn-Knopp) are float32 whatever
AMP says; the streams keep the dtype they come in.  Under AMP the stream is
exactly bf16, so ``x Phi`` at float32's precision is ONE bf16 pass of the
matmul unit over ``Phi`` split in three bf16 parts side by side (72 columns
where six passes of a ``highest`` float32 matmul would each fill 24 of 128).
Inside, the coefficients are ``[S, m]`` arrays, the tokens on the sublanes as
the stream has them and a token's ``m`` numbers in one row of lanes (an ``[S,
n, n]`` array would spend a register tile on 16 numbers, and with the tokens
on the lanes XLA laid the stream itself out transposed to match).

Both grad ops read their forward op's inputs and Out's gradients alone and
compute the maps again (``jax.vjp`` of the forward): nothing of the Sinkhorn
iterations is kept between forward and backward.
``paddle_tpu_hc_lowerings_total`` counts the lowerings."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import monitor as _monitor
from ..framework.core import grad_var_name
from ..framework.registry import register_op
from .common import X

HC_LOWERINGS_CTR = _monitor.REGISTRY.counter(
    "paddle_tpu_hc_lowerings_total",
    "hc_pre, hc_post and their grad ops' lowerings by op, the number of "
    "streams, Sinkhorn-Knopp's iterations and what implements the op (xla: "
    "jnp that XLA fuses) — counted while tracing, once per compile of a "
    "block that holds the op, nothing per step",
    ("op", "n", "sinkhorn_iters", "impl"))

_F32, _BF16 = jnp.float32, jnp.bfloat16


def _split3(w):
    """A float32 array as three bf16 parts, side by side on the last axis,
    that add up to it to 2^-24 of its size."""
    hi = w.astype(_BF16)
    rest = w - hi.astype(_F32)
    mid = rest.astype(_BF16)
    return jnp.concatenate(
        [hi, mid, (rest - mid.astype(_F32)).astype(_BF16)], axis=-1)


def _join3(p):
    """The three parts' products added up, the small ones first."""
    hi, mid, lo = jnp.split(p, 3, axis=-1)
    return (lo + mid) + hi


@jax.custom_vjp
def _project_bf16(x, phi):
    """``x Phi`` [S, m] float32 of a bf16 stream ``x`` [S, K] and a float32
    ``Phi`` [K, m], to float32's precision in one bf16 pass (the module's
    docstring)."""
    return _join3(jnp.dot(x, _split3(phi), preferred_element_type=_F32))


def _project_bf16_fwd(x, phi):
    return _project_bf16(x, phi), (x, phi)


def _project_bf16_bwd(saved, dp):
    """The stream's gradient is a bf16 tensor: one bf16 pass, as every other
    activation gradient of an AMP step; ``Phi`` is a master weight: its
    gradient ``x^T dp`` to float32's precision, ``dp`` split as ``Phi`` is in
    the forward."""
    x, phi = saved
    dx = jnp.dot(dp.astype(_BF16), phi.astype(_BF16).T,
                 preferred_element_type=_F32).astype(x.dtype)
    dphi = _join3(jax.lax.dot_general(
        x, _split3(dp), (((0,), (0,)), ((), ())),
        preferred_element_type=_F32))
    return dx, dphi


_project_bf16.defvjp(_project_bf16_fwd, _project_bf16_bwd)


def _project(x, phi):
    if x.dtype == _BF16:
        return _project_bf16(x, phi)
    return jnp.dot(x.astype(_F32), phi, precision=jax.lax.Precision.HIGHEST)


def sinkhorn(logits, n, iters, eps):
    """``[S, n * n]`` (entry ``i * n + j`` is row ``i``, column ``j`` of a
    token's matrix) -> the same shape: ``exp``, then ``iters`` times every
    column divided by its sum + ``eps`` and every row by its sum + ``eps``.
    The tokens stay on the sublanes, as everything else of the op has them
    (with the tokens on the lanes XLA laid the whole stream out transposed
    to match: PERF.md section 6, PR 45), and a token's 16 numbers lie in one
    row of lanes: the sums are adds of lane slices.  A loop of ``iters``
    steps, not 2 x ``iters`` unrolled normalisations: unrolled, XLA carried
    the scale factors from step to step through forty small kernels; the
    backward is the loop run back over the kept steps."""
    def step(m, _):
        cols = sum(m[:, i * n:(i + 1) * n] for i in range(n))       # [S, n]
        m = m / (jnp.tile(cols, (1, n)) + eps)
        rows = jnp.stack([sum(m[:, i * n + j] for j in range(n))
                          for i in range(n)], axis=-1)              # [S, n]
        return m / (jnp.repeat(rows, n, axis=1) + eps), None
    return jax.lax.scan(step, jnp.exp(logits), None, length=iters)[0]


def hc_pre_core(*args, n, iters, rms_eps, eps, lo, hi):
    """``(u [.., C], H_post [.., n], H_res [.., n * n])`` of the ``n``
    streams [.., C], Phi, alpha and bias (``args``, in that order): ``u`` in
    the streams' dtype, the maps float32 (``H_res[.., i * n + j]`` is row
    ``i``, column ``j``)."""
    xs, (phi, alpha, bias) = args[:n], args[n:]
    lead, c = xs[0].shape[:-1], xs[0].shape[-1]
    xs = [x.reshape(-1, c) for x in xs]
    xf = [x.astype(_F32) for x in xs]
    squares = sum(jnp.sum(x * x, axis=-1, keepdims=True) for x in xf)
    r = jax.lax.rsqrt(squares / (n * c) + rms_eps)
    phi = phi.astype(_F32)
    m = r * sum(_project(x, phi[j * c:(j + 1) * c])
                for j, x in enumerate(xs))                # [S, 2n + n^2]
    alpha, b = alpha.astype(_F32), bias.astype(_F32)
    pre = jax.nn.sigmoid(alpha[0] * m[:, :n] + b[:n])             # [S, n]
    post = 2.0 * jax.nn.sigmoid(alpha[1] * m[:, n:2 * n] + b[n:2 * n])
    res = sinkhorn(jnp.clip(alpha[2] * m[:, 2 * n:] + b[2 * n:], lo, hi),
                   n, iters, eps)                             # [S, n * n]
    u = sum(pre[:, j:j + 1] * x for j, x in enumerate(xf))
    return (u.astype(xs[0].dtype).reshape(lead + (c,)),
            post.reshape(lead + (n,)), res.reshape(lead + (n * n,)))


def hc_post_core(*args, n):
    """The ``n`` next streams, each [.., C] in the streams' dtype, of the
    ``n`` streams, ``y``, ``H_post`` and ``H_res`` (``args``, in that
    order): stream ``i`` is ``sum_j H_res[i, j] X[j] + H_post[i] y``,
    float32 inside."""
    xs, (y, h_post, h_res) = args[:n], args[n:]
    shape, c = xs[0].shape, xs[0].shape[-1]
    xf = [x.reshape(-1, c).astype(_F32) for x in xs]
    ys = y.reshape(-1, c).astype(_F32)
    hp = h_post.reshape(-1, n).astype(_F32)
    hr = h_res.reshape(-1, n * n).astype(_F32)
    return tuple(
        (sum(hr[:, i * n + j][:, None] * xf[j] for j in range(n))
         + hp[:, i][:, None] * ys).astype(xs[0].dtype).reshape(shape)
        for i in range(n))


def _pre_fn(attrs):
    lo, hi = attrs["res_clamp"]
    return functools.partial(
        hc_pre_core, n=int(attrs["n"]), iters=int(attrs["sinkhorn_iters"]),
        rms_eps=float(attrs["rms_eps"]), eps=float(attrs["eps"]),
        lo=float(lo), hi=float(hi))


def _count(ctx, op, attrs):
    # shape inference runs the lowering abstractly: uncounted
    if not getattr(ctx, "is_abstract", False):
        # ``n`` is also inc's own first argument: through labels()
        HC_LOWERINGS_CTR.labels(
            op=op, n=str(int(attrs["n"])), impl="xla",
            sinkhorn_iters=str(int(attrs["sinkhorn_iters"]))).inc()


_PRE_IN, _POST_IN = ("X", "Phi", "Alpha", "Bias"), ("X", "Y", "HPost", "HRes")


def _args(ins, slots, prefix, attrs):
    """The core's arguments from an op's inputs: the ``n`` streams of the
    first slot, then one array a slot."""
    xs = ins[prefix + slots[0]]
    assert len(xs) == int(attrs["n"]), "one variable a stream"
    return list(xs) + [X(ins, prefix + s) for s in slots[1:]]


def _hc_pre(ctx, ins, attrs):
    """The read side of a hyper-connection (the module's docstring): X, the
    ``n`` streams [b, t, C]; Phi [n C, 2 n + n^2], Alpha [3], Bias [2 n +
    n^2] -> U [b, t, C] in X's dtype (the sublayer's input before its norm),
    HPost [b, t, n] and HRes [b, t, n n] float32.  Attributes: ``n``,
    ``sinkhorn_iters``, ``eps`` (Sinkhorn's two denominators), ``rms_eps``,
    ``res_clamp`` (lo, hi: on the logits, before the exponential)."""
    _count(ctx, "hc_pre", attrs)
    u, h_post, h_res = _pre_fn(attrs)(*_args(ins, _PRE_IN, "", attrs))
    return {"U": [u], "HPost": [h_post], "HRes": [h_res]}


def _grad_maker(grad_type, in_slots, out_slots):
    def maker(op, block, no_grad_set):
        def wanted(n):
            v = block.var(n) if block.has_var(n) else None
            return n not in no_grad_set and not (v is not None
                                                 and v.stop_gradient)
        inputs = {"X$" + s: op.input(s) for s in in_slots}
        inputs.update({"OG$" + s: [grad_var_name(n) for n in op.output(s)]
                       for s in out_slots})
        outputs = {"IG$" + s: [grad_var_name(n) if wanted(n) else ""
                               for n in op.input(s)] for s in in_slots}
        return [{"type": grad_type, "inputs": inputs, "outputs": outputs,
                 "attrs": dict(op.attrs)}]
    return maker


register_op("hc_pre", _hc_pre, grad_maker=_grad_maker(
    "hc_pre_grad", _PRE_IN, ("U", "HPost", "HRes")))


def _pullback(fn, slots, ins, attrs, out_grads):
    """``fn``'s vjp at the forward inputs, applied to Out's gradients (a
    gradient no later op made is zero), each result in its input's dtype."""
    n = int(attrs["n"])
    prim = _args(ins, slots, "X$", attrs)
    got, back = jax.vjp(fn, *prim)
    cot = tuple(jnp.zeros_like(g) if d is None else d.astype(g.dtype)
                for g, d in zip(got, out_grads))
    grads = [g.astype(p.dtype) for g, p in zip(back(cot), prim)]
    out = {"IG$" + slots[0]: grads[:n]}
    out.update({"IG$" + s: [g] for s, g in zip(slots[1:], grads[n:])})
    return out


@register_op("hc_pre_grad")
def _hc_pre_grad(ctx, ins, attrs):
    """``hc_pre``'s backward from the streams, the three parameters and the
    gradients of U, HPost and HRes: ``r``, ``m``, the maps and
    Sinkhorn-Knopp's iterations are computed again here and differentiated
    (``jax.vjp``); Phi's, Alpha's and Bias's gradients are float32."""
    _count(ctx, "hc_pre_grad", attrs)
    return _pullback(_pre_fn(attrs), _PRE_IN, ins, attrs,
                     [X(ins, "OG$" + s) for s in ("U", "HPost", "HRes")])


def _hc_post(ctx, ins, attrs):
    """The write side of a hyper-connection: X, the ``n`` streams [b, t, C];
    Y [b, t, C] (the sublayer's output), HPost [b, t, n], HRes [b, t, n n]
    -> Out, the ``n`` next streams in X's dtype, stream ``i`` = ``sum_j
    HRes[i, j] X[j] + HPost[i] Y``.  Attributes: ``n``; ``sinkhorn_iters``
    rides along for the counter."""
    _count(ctx, "hc_post", attrs)
    n = int(attrs["n"])
    return {"Out": list(hc_post_core(*_args(ins, _POST_IN, "", attrs), n=n))}


register_op("hc_post", _hc_post, grad_maker=_grad_maker(
    "hc_post_grad", _POST_IN, ("Out",)))


@register_op("hc_post_grad")
def _hc_post_grad(ctx, ins, attrs):
    """``hc_post``'s backward: ``dX[j] = sum_i HRes[i, j] dOut[i]``, ``dY =
    sum_i HPost[i] dOut[i]``, ``dHPost[i] = <dOut[i], Y>``, ``dHRes[i, j] =
    <dOut[i], X[j]>`` (float32 sums)."""
    _count(ctx, "hc_post_grad", attrs)
    n = int(attrs["n"])
    return _pullback(functools.partial(hc_post_core, n=n), _POST_IN, ins,
                     attrs, ins.get("OG$Out") or [None] * n)
