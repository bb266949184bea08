"""Sequence op lowerings over padded-plus-lengths tensors.

TPU-native stand-ins for ``operators/sequence_ops/`` (48 LoD kernels): data
is dense ``[batch, time, ...]``; an optional ``SeqLen`` input ``[batch]``
masks the padding.  Without SeqLen the full time axis is used.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .. import monitor as _monitor
from ..framework.core import grad_var_name
from ..framework.registry import register_op
from .common import X, XS, ids_dtype, canon_dtype


def _time_mask(x, seq_len, dtype=None):
    """[b, t, ...] mask from lengths, broadcastable to x."""
    if seq_len is None:
        return None
    t = x.shape[1]
    m = jnp.arange(t)[None, :] < seq_len.reshape(-1, 1)
    m = m.reshape(m.shape + (1,) * (x.ndim - 2))
    return m if dtype is None else m.astype(dtype)


@register_op("sequence_mask", no_grad=True)
def _sequence_mask(ctx, ins, attrs):
    lens = X(ins, "X")
    maxlen = attrs.get("maxlen", -1)
    if maxlen is None or maxlen < 0:
        maxlen = int(np.asarray(jnp.max(lens))) if not hasattr(lens, "aval") \
            else lens.shape[-1]
    m = jnp.arange(maxlen)[None, :] < lens.reshape(-1, 1)
    return {"Y": [m.astype(canon_dtype(attrs.get("out_dtype", "int64")))]}


@register_op("sequence_pool")
def _sequence_pool(ctx, ins, attrs):
    x = X(ins, "X")          # [b, t, ...]
    seq_len = X(ins, "SeqLen")
    ptype = attrs.get("pooltype", "AVERAGE").upper()
    mask = _time_mask(x, seq_len, x.dtype)
    n = seq_len.reshape(-1, *([1] * (x.ndim - 2))).astype(x.dtype) \
        if seq_len is not None else x.shape[1]
    if ptype in ("AVERAGE", "SUM", "SQRT"):
        xs = x * mask if mask is not None else x
        s = jnp.sum(xs, axis=1)
        if ptype == "AVERAGE":
            out = s / n
        elif ptype == "SQRT":
            out = s / jnp.sqrt(n.astype(x.dtype)) if seq_len is not None \
                else s / np.sqrt(x.shape[1])
        else:
            out = s
    elif ptype == "MAX":
        neg = jnp.finfo(x.dtype).min if jnp.issubdtype(x.dtype, jnp.floating) \
            else jnp.iinfo(x.dtype).min
        xm = jnp.where(mask, x, neg) if mask is not None else x
        out = jnp.max(xm, axis=1)
    elif ptype == "FIRST":
        out = x[:, 0]
    elif ptype == "LAST":
        if seq_len is not None:
            idx = jnp.maximum(seq_len.astype(jnp.int32) - 1, 0)
            out = jnp.take_along_axis(
                x, idx.reshape(-1, 1, *([1] * (x.ndim - 2))), axis=1)[:, 0]
        else:
            out = x[:, -1]
    else:
        raise NotImplementedError(f"sequence_pool type {ptype}")
    return {"Out": [out], "MaxIndex": [jnp.zeros((x.shape[0],), jnp.int32)]}


@register_op("sequence_softmax")
def _sequence_softmax(ctx, ins, attrs):
    x = X(ins, "X")
    seq_len = X(ins, "SeqLen")
    if seq_len is not None:
        mask = _time_mask(x, seq_len)
        neg = jnp.finfo(x.dtype).min
        xm = jnp.where(mask, x, neg)
        out = jax.nn.softmax(xm, axis=1)
        out = jnp.where(mask, out, 0.0)
    else:
        out = jax.nn.softmax(x, axis=1)
    return {"Out": [out]}


@register_op("sequence_reverse")
def _sequence_reverse(ctx, ins, attrs):
    x = X(ins, "X")
    seq_len = X(ins, "SeqLen")
    if seq_len is None:
        return {"Y": [jnp.flip(x, axis=1)]}
    t = x.shape[1]
    ar = jnp.arange(t)[None, :]
    lens = seq_len.reshape(-1, 1).astype(jnp.int32)
    idx = jnp.where(ar < lens, lens - 1 - ar, ar)
    out = jnp.take_along_axis(x, idx.reshape(idx.shape + (1,) * (x.ndim - 2)),
                              axis=1)
    return {"Y": [out]}


@register_op("sequence_expand")
def _sequence_expand(ctx, ins, attrs):
    x, y = X(ins, "X"), X(ins, "Y")
    # padded analog: x [b, ...] broadcast over y's time axis [b, t, ...]
    if x.ndim == y.ndim:
        return {"Out": [jnp.broadcast_to(x, y.shape[:2] + x.shape[2:])]}
    xe = jnp.expand_dims(x, 1)
    return {"Out": [jnp.broadcast_to(xe, (x.shape[0], y.shape[1]) + x.shape[1:])]}


@register_op("sequence_expand_as")
def _sequence_expand_as(ctx, ins, attrs):
    return _sequence_expand(ctx, ins, attrs)


@register_op("sequence_pad")
def _sequence_pad(ctx, ins, attrs):
    x = X(ins, "X")
    seq_len = X(ins, "SeqLen")
    lengths = seq_len if seq_len is not None else \
        jnp.full((x.shape[0],), x.shape[1], ids_dtype())
    return {"Out": [x], "Length": [lengths.astype(ids_dtype())]}


@register_op("sequence_unpad")
def _sequence_unpad(ctx, ins, attrs):
    x, length = X(ins, "X"), X(ins, "Length")
    mask = _time_mask(x, length, x.dtype)
    return {"Out": [x * mask if mask is not None else x]}


@register_op("sequence_concat")
def _sequence_concat(ctx, ins, attrs):
    return {"Out": [jnp.concatenate(XS(ins, "X"), axis=1)]}


@register_op("sequence_slice")
def _sequence_slice(ctx, ins, attrs):
    x, off, ln = X(ins, "X"), X(ins, "Offset"), X(ins, "Length")
    # static shapes: slice each row by dynamic offset, keep max length
    maxlen = int(np.asarray(ln).max()) if not hasattr(ln, "aval") else x.shape[1]
    def row(xi, oi):
        return jax.lax.dynamic_slice_in_dim(xi, oi, maxlen, axis=0)
    out = jax.vmap(row)(x, off.reshape(-1).astype(jnp.int32))
    return {"Out": [out]}


@register_op("sequence_reshape")
def _sequence_reshape(ctx, ins, attrs):
    x = X(ins, "X")
    nd = attrs["new_dim"]
    return {"Out": [x.reshape(x.shape[0], -1, nd)]}


@register_op("sequence_enumerate", no_grad=True)
def _sequence_enumerate(ctx, ins, attrs):
    x = X(ins, "X")  # [b, t]
    win = attrs["win_size"]
    pad = attrs.get("pad_value", 0)
    t = x.shape[1]
    cols = []
    for w in range(win):
        shifted = jnp.pad(x[:, w:], [(0, 0), (0, w)], constant_values=pad)
        cols.append(shifted)
    return {"Out": [jnp.stack(cols, axis=-1)]}


@register_op("sequence_erase", no_grad=True)
def _sequence_erase(ctx, ins, attrs):
    x = X(ins, "X")
    tokens = attrs.get("tokens", [])
    keep = jnp.ones_like(x, dtype=bool)
    for tk in tokens:
        keep &= (x != tk)
    # static shape: replace erased with 0 and compact is not possible; mask out
    return {"Out": [jnp.where(keep, x, 0)]}


# -- short_conv: a gated causal depthwise convolution over [b, t, d] -----------

def _causal_depthwise(g, filt):
    """``c[t] = sum_j filt[:, j] * g[t - (L - 1) + j]`` over [b, t, d], zeros
    before the sequence starts (``filt`` [d, L]): L shifted multiplies."""
    taps, t = filt.shape[1], g.shape[1]
    pads = jnp.pad(g, [(0, 0), (taps - 1, 0), (0, 0)])
    return sum(pads[:, j:j + t] * filt[:, j] for j in range(taps))


SHORT_CONV_LOWERINGS_CTR = _monitor.REGISTRY.counter(
    "paddle_tpu_short_conv_lowerings_total",
    "short_conv and short_conv_grad lowerings by the filter's taps, whether "
    "the convolution is gated (LFM2: C * conv(B * u), act none) or not "
    "(KDA: silu(conv(x)), act silu), whether the ungated form adds a bias "
    "(a state-space mixer's) and what implements it (pallas: the kernel pair "
    "of pallas/short_conv.py; xla: jnp that XLA fuses) — counted while "
    "tracing, once per compile of a program that holds the op",
    ("taps", "gated", "act", "bias", "impl"))


def _short_conv_lowering(ctx, x, filt, attrs, bias=None):
    """``pallas`` or ``xla`` for the op and its grad op alike, from what
    ``pallas/short_conv.py:fits`` and ``device.on_tpu()`` say; counts it."""
    from ..device import on_tpu
    from ..pallas import short_conv
    gated = bool(attrs.get("gated", True))
    impl = "pallas" if short_conv.fits(
        x.shape, filt.shape[1], x.dtype, gated) and on_tpu() else "xla"
    # shape inference runs the lowering abstractly: uncounted
    if not getattr(ctx, "is_abstract", False):
        SHORT_CONV_LOWERINGS_CTR.labels(
            taps=str(filt.shape[1]), gated=str(gated).lower(),
            act="none" if gated else "silu",
            bias=str(bias is not None).lower(), impl=impl).inc()
    return impl


def _short_conv(ctx, ins, attrs):
    """The core of a gated short-convolution operator (LFM2's ``conv``
    layers): X [b, t, 3 d] is the input projection, split in three ``B | C |
    u``; ``g = B * u``; ``c[t] = sum_j Filter[:, j] * g[t - (L - 1) + j]``, a
    causal depthwise convolution over the ``d`` channels with zeros before
    the sequence starts (Filter [d, L], no bias); ``Out = C * c`` [b, t, d].
    No activation, no positional term; the two projections round it are the
    program's own ``mul`` ops.  Bandwidth-bound: three [t, d] streams in, one
    out.  Float32 inside (a v5e has no bf16 vector unit), the output in X's
    dtype.  ``gated=False`` (KDA's, a state-space mixer's): :func:`_ungated`."""
    x, filt, bias = X(ins, "X"), X(ins, "Filter"), X(ins, "Bias")
    f32 = jnp.float32
    impl = _short_conv_lowering(ctx, x, filt, attrs, bias)
    if not attrs.get("gated", True):
        return {"Out": [_ungated(impl, x, filt, bias)]}
    b_, c_, u = jnp.split(x.astype(f32), 3, axis=-1)
    out = c_ * _causal_depthwise(b_ * u, filt.astype(f32))
    return {"Out": [out.astype(x.dtype)]}


def _depthwise_back(g, dc, w):
    """``(dg, dFilter)`` of ``c = _causal_depthwise(g, w)`` from ``dc``: the
    same taps run towards the past, and per tap the sum over batch and time
    of ``dc`` times the shifted input."""
    taps, t = w.shape[1], g.shape[1]
    ahead = jnp.pad(dc, [(0, 0), (0, taps - 1), (0, 0)])
    dg = sum(ahead[:, taps - 1 - j:taps - 1 - j + t] * w[:, j]
             for j in range(taps))
    behind = jnp.pad(g, [(0, 0), (taps - 1, 0), (0, 0)])
    d_filt = jnp.stack([jnp.sum(behind[:, j:j + t] * dc, axis=(0, 1))
                        for j in range(taps)], axis=1)
    return dg, d_filt


def _short_conv_grad_maker(op, block, no_grad_set):
    def wanted(n):
        v = block.var(n) if block.has_var(n) else None
        return n not in no_grad_set and not (v is not None
                                             and v.stop_gradient)
    slots = ("X", "Filter") + (("Bias",) if op.input("Bias") else ())
    g_inputs = {"X$" + s: op.input(s) for s in slots}
    g_inputs["OG$Out"] = [grad_var_name(n) for n in op.output("Out")]
    g_outputs = {"IG$" + s: [grad_var_name(n) if wanted(n) else ""
                             for n in op.input(s)] for s in slots}
    return [{"type": "short_conv_grad", "inputs": g_inputs,
             "outputs": g_outputs, "attrs": dict(op.attrs)}]


register_op("short_conv", _short_conv, grad_maker=_short_conv_grad_maker)


@register_op("short_conv_grad")
def _short_conv_grad(ctx, ins, attrs):
    """The backward of ``short_conv`` from its inputs alone (the gate product
    and the convolution are three multiplies a channel: computed again, not
    saved): ``dC = dOut * c``; ``dc = dOut * C``; ``dg[s] = sum_j Filter[:,
    j] * dc[s + (L - 1) - j]`` (the same taps, run towards the past);
    ``dB = dg * u``, ``du = dg * B``; ``dFilter[:, j] = sum_{b, t} dc[t] *
    g[t - (L - 1) + j]`` in float32 (the filter is a master weight).  Reads
    X and dOut, writes dX: seven [t, d] streams.  ``gated=False``:
    :func:`_ungated_grad`.

    The gated form below, ``_causal_depthwise`` and ``_depthwise_back``
    above, and the LINES all three stand on are the parent commit's (PR 58
    moved the ungated form to the end of this file and filled what it left
    with words): the compile cache keys on the source line of every frame
    under a lowering, and LFM2's step, which holds the gated form alone, is
    to stay the executable it was.  After an edit up here,
    ``tools/joyai_step_aot.py --cell lfm2 --fingerprint`` at both commits
    from one directory says whether it still is; where it is not, nothing
    is wrong but LFM2's first run compiles its step again."""
    x, filt, d_out = X(ins, "X$X"), X(ins, "X$Filter"), X(ins, "OG$Out")
    bias = X(ins, "X$Bias")
    f32 = jnp.float32
    impl = _short_conv_lowering(ctx, x, filt, attrs, bias)
    w = filt.astype(f32)
    if not attrs.get("gated", True):
        out = _ungated_grad(impl, x, filt, bias, d_out)
        return {"IG$" + s: [g] for s, g in zip(("X", "Filter", "Bias"), out)
                if g is not None}
    b_, c_, u = jnp.split(x.astype(f32), 3, axis=-1)
    g = b_ * u
    dy = jnp.zeros_like(g) if d_out is None else d_out.astype(f32)
    dc = dy * c_
    dg, d_filt = _depthwise_back(g, dc, w)
    dx = jnp.concatenate([dg * u, dy * _causal_depthwise(g, w), dg * b_],
                         axis=-1)
    return {"IG$X": [dx.astype(x.dtype)],
            "IG$Filter": [d_filt.astype(filt.dtype)]}


# -- the ungated form: silu(conv(X) [+ Bias]) ---------------------------------

def _ungated(impl, x, filt, bias):
    """``gated=False`` (KDA's convolution in front of Q, K and V): X [b, t,
    d] is convolved as it is and SiLU follows, ``Out = silu(conv(X))``;
    Filter [d, L] as in the gated form; with the optional input Bias [d],
    ``silu(conv(X) + Bias)`` (a state-space mixer's).  Float32 inside, Out
    in X's dtype.  ``pallas``, on a TPU where ``pallas/short_conv.py:fits``
    says so (channels in whole lane tiles, a length of whole tiles, float32
    or bf16): the kernel ``short_conv_fwd``, one pass over X and Out;
    ``xla`` everywhere else (the CPU, toy widths): the ``jax.numpy`` text,
    which is also what the tests hold the kernel to."""
    if impl == "pallas":
        from ..pallas import short_conv
        return short_conv.short_conv_fwd(x, filt, bias)
    f32 = jnp.float32
    conv = _causal_depthwise(x.astype(f32), filt.astype(f32))
    if bias is not None:
        conv = conv + bias.astype(f32)
    return jax.nn.silu(conv).astype(x.dtype)


def _ungated_grad(impl, x, filt, bias, d_out):
    """``(dX, dFilter, dBias or None)`` of :func:`_ungated` from its inputs
    and Out's gradient, each in its variable's dtype: the convolution again,
    SiLU's slope at it, ``dc = dOut * slope``, then the gated form's way back
    through the taps; Bias gets ``sum_{b, t} dc``.  ``pallas``: the kernel
    ``short_conv_bwd``, one pass over X, dOut and dX with the two sums in
    float32 beside it."""
    f32 = jnp.float32
    if impl == "pallas":
        from ..pallas import short_conv
        dx, d_filt, d_bias = short_conv.short_conv_bwd(
            x, filt, bias, jnp.zeros_like(x) if d_out is None else d_out)
    else:
        g, w = x.astype(f32), filt.astype(f32)
        conv = _causal_depthwise(g, w)
        if bias is not None:
            conv = conv + bias.astype(f32)
        _, slope = jax.vjp(jax.nn.silu, conv)
        dc, = slope(jnp.zeros_like(g) if d_out is None
                    else d_out.astype(f32))
        dg, d_filt = _depthwise_back(g, dc, w)
        dx = dg.astype(x.dtype)
        d_bias = None if bias is None else jnp.sum(dc, axis=(0, 1))
    return (dx, d_filt.astype(filt.dtype),
            None if bias is None else d_bias.astype(bias.dtype))
