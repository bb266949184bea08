"""NN op lowerings: conv, pool, norms, softmax, dropout, losses, interp.

Reference kernels: ``operators/conv_op.cc`` (+ ``conv_cudnn_op.cu``),
``operators/pool_op.cc``, ``operators/batch_norm_op.cc``,
``operators/layer_norm_op.cc``, ``operators/group_norm_op.cc``,
``operators/softmax_op.cc``, ``operators/softmax_with_cross_entropy_op.cc``,
``operators/dropout_op.cc``, ``operators/cross_entropy_op.cc``,
``operators/interpolate_op.cc`` …

TPU notes: convs lower to ``lax.conv_general_dilated`` which XLA tiles onto
the MXU; data stays in the framework-visible NCHW layout for API parity and
XLA picks the internal layout.  Dropout REGENERATES its keep mask in the
backward pass from a per-op RNG tag (recompute beats the reference's stored
Mask on an HBM-bound step); the Mask output remains for API parity and for
legacy untagged ops.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .. import monitor as _monitor
from ..framework.core import grad_var_name
from ..framework.registry import register_op
from ..framework.executor import per_dp_shard
from .common import X, XS, broadcast_to_x, static_int

# ---------------------------------------------------------------------------
# conv
# ---------------------------------------------------------------------------


def _pair(v, n=2):
    if isinstance(v, (list, tuple)):
        return list(v)
    return [v] * n


@register_op("conv2d")
def _conv2d(ctx, ins, attrs):
    x, w = X(ins, "Input"), X(ins, "Filter")
    strides = _pair(attrs.get("strides", [1, 1]))
    pads = _pair(attrs.get("paddings", [0, 0]))
    dils = _pair(attrs.get("dilations", [1, 1]))
    groups = attrs.get("groups", 1) or 1
    # no preferred_element_type=f32: this jax version's conv transpose
    # (vjp) rule emits a mixed-dtype conv for the f32-out/bf16-in form,
    # and on TPU the MXU accumulates bf16 convs in f32 internally anyway
    out = jax.lax.conv_general_dilated(
        x, w, window_strides=strides,
        padding=[(pads[0], pads[0]), (pads[1], pads[1])],
        rhs_dilation=dils, feature_group_count=groups,
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    return {"Output": [out.astype(x.dtype)]}


@register_op("depthwise_conv2d")
def _depthwise_conv2d(ctx, ins, attrs):
    x, w = X(ins, "Input"), X(ins, "Filter")
    a = dict(attrs)
    a["groups"] = x.shape[1]
    return _conv2d(ctx, ins, a)


@register_op("conv3d")
def _conv3d(ctx, ins, attrs):
    x, w = X(ins, "Input"), X(ins, "Filter")
    strides = _pair(attrs.get("strides", [1, 1, 1]), 3)
    pads = _pair(attrs.get("paddings", [0, 0, 0]), 3)
    dils = _pair(attrs.get("dilations", [1, 1, 1]), 3)
    out = jax.lax.conv_general_dilated(
        x, w, window_strides=strides,
        padding=[(p, p) for p in pads], rhs_dilation=dils,
        feature_group_count=attrs.get("groups", 1) or 1,
        dimension_numbers=("NCDHW", "OIDHW", "NCDHW"))
    return {"Output": [out]}


def _conv_transpose_nd(x, w, strides, pads, dils, groups, nd):
    """Exact transposed conv (== vjp of the forward conv wrt its input):
    input-dilate by stride, convolve with the spatially-flipped, IO-swapped
    kernel.  w: [in, out/groups, k...] (the fluid filter layout)."""
    ci = w.shape[0]
    og = w.shape[1]
    k = w.shape[2:]
    spatial = tuple(range(2, 2 + nd))
    wf = jnp.flip(w, axis=spatial)
    # [Ci, Co/g, ...] → grouped IO swap → [Co, Ci/g, ...]
    wf = wf.reshape((groups, ci // groups, og) + k)
    wf = jnp.swapaxes(wf, 1, 2).reshape((groups * og, ci // groups) + k)
    pad_cfg = [(dils[i] * (k[i] - 1) - pads[i],
                dils[i] * (k[i] - 1) - pads[i]) for i in range(nd)]
    dn = {1: ("NCH", "OIH", "NCH"), 2: ("NCHW", "OIHW", "NCHW"),
          3: ("NCDHW", "OIDHW", "NCDHW")}[nd]
    return jax.lax.conv_general_dilated(
        x, wf, window_strides=(1,) * nd, padding=pad_cfg,
        lhs_dilation=tuple(strides), rhs_dilation=tuple(dils),
        feature_group_count=groups, dimension_numbers=dn)


@register_op("conv2d_transpose")
def _conv2d_transpose(ctx, ins, attrs):
    x, w = X(ins, "Input"), X(ins, "Filter")  # w: [in, out/groups, kh, kw]
    strides = _pair(attrs.get("strides", [1, 1]))
    pads = _pair(attrs.get("paddings", [0, 0]))
    dils = _pair(attrs.get("dilations", [1, 1]))
    groups = attrs.get("groups", 1) or 1
    out = _conv_transpose_nd(x, w, strides, pads, dils, groups, 2)
    return {"Output": [out]}


# ---------------------------------------------------------------------------
# pooling (ref operators/pool_op.cc, math/pooling.cc)
# ---------------------------------------------------------------------------


def _pool2d_impl(x, ksize, strides, pads, pooling_type, global_pooling,
                 adaptive, exclusive, ceil_mode=False):
    n, c, h, w = x.shape
    if global_pooling or (adaptive and tuple(ksize) == (1, 1)):
        red = jnp.max if pooling_type == "max" else jnp.mean
        return red(x, axis=(2, 3), keepdims=True)
    if adaptive:
        oh, ow = ksize
        if h % oh == 0 and w % ow == 0:
            xr = x.reshape(n, c, oh, h // oh, ow, w // ow)
            red = jnp.max if pooling_type == "max" else jnp.mean
            return red(xr, axis=(3, 5))
        raise NotImplementedError("adaptive pool needs divisible sizes")
    kh, kw = ksize
    sh, sw = strides
    ph, pw = pads
    # ceil_mode: extend the right/bottom padding so the window count ceils
    # (ref math/pooling.cc output-size arithmetic)
    def _extra(dim, k, s, p):
        if not ceil_mode:
            return 0
        out_ceil = -(-(dim + 2 * p - k) // s) + 1
        return max(0, (out_ceil - 1) * s + k - dim - 2 * p)
    eh = _extra(h, kh, sh, ph)
    ew = _extra(w, kw, sw, pw)
    pad_cfg = [(0, 0), (0, 0), (ph, ph + eh), (pw, pw + ew)]
    if pooling_type == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
        out = jax.lax.reduce_window(
            x, init, jax.lax.max, (1, 1, kh, kw), (1, 1, sh, sw), pad_cfg)
    else:
        summed = jax.lax.reduce_window(
            x, 0.0, jax.lax.add, (1, 1, kh, kw), (1, 1, sh, sw), pad_cfg)
        if exclusive and (ph or pw or eh or ew):
            ones = jnp.ones((1, 1, h, w), x.dtype)
            cnt = jax.lax.reduce_window(
                ones, 0.0, jax.lax.add, (1, 1, kh, kw), (1, 1, sh, sw),
                pad_cfg)
            out = summed / cnt
        else:
            out = summed / (kh * kw)
    return out


@register_op("pool2d")
def _pool2d(ctx, ins, attrs):
    x = X(ins, "X")
    out = _pool2d_impl(
        x, _pair(attrs.get("ksize", [1, 1])),
        _pair(attrs.get("strides", [1, 1])),
        _pair(attrs.get("paddings", [0, 0])),
        attrs.get("pooling_type", "max"),
        attrs.get("global_pooling", False),
        attrs.get("adaptive", False),
        attrs.get("exclusive", True),
        attrs.get("ceil_mode", False))
    return {"Out": [out]}


@register_op("pool3d")
def _pool3d(ctx, ins, attrs):
    x = X(ins, "X")
    k = _pair(attrs.get("ksize", [1, 1, 1]), 3)
    s = _pair(attrs.get("strides", [1, 1, 1]), 3)
    p = _pair(attrs.get("paddings", [0, 0, 0]), 3)
    ptype = attrs.get("pooling_type", "max")
    if attrs.get("global_pooling", False):
        red = jnp.max if ptype == "max" else jnp.mean
        return {"Out": [red(x, axis=(2, 3, 4), keepdims=True)]}
    if ptype == "max":
        out = jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max, (1, 1) + tuple(k), (1, 1) + tuple(s),
            [(0, 0), (0, 0)] + [(pp, pp) for pp in p])
    else:
        out = jax.lax.reduce_window(
            x, 0.0, jax.lax.add, (1, 1) + tuple(k), (1, 1) + tuple(s),
            [(0, 0), (0, 0)] + [(pp, pp) for pp in p]) / float(np.prod(k))
    return {"Out": [out]}


# ---------------------------------------------------------------------------
# normalization
# ---------------------------------------------------------------------------


def _bn_axes(layout, ndim):
    if layout == "NHWC":
        return tuple(range(ndim - 1)), (1,) * (ndim - 1) + (-1,)
    return (0,) + tuple(range(2, ndim)), (1, -1) + (1,) * (ndim - 2)


def _batch_norm_lower(ctx, ins, attrs):
    x = X(ins, "X")
    scale, bias = X(ins, "Scale"), X(ins, "Bias")
    mean, var = X(ins, "Mean"), X(ins, "Variance")
    momentum = attrs.get("momentum", 0.9)
    eps = attrs.get("epsilon", 1e-5)
    layout = attrs.get("data_layout", "NCHW")
    is_test = attrs.get("is_test", False)
    use_global = attrs.get("use_global_stats", False) or is_test
    axes, bshape = _bn_axes(layout, x.ndim)

    if use_global:
        m, v = mean, var
        saved_m, saved_v = mean, var
        mean_out, var_out = mean, var
    else:
        # one-pass stats: E[x] and E[x²] reduce in the SAME read of the
        # (huge) conv output — jnp.var would re-center and cost a second
        # full HBM pass.  f32 accumulation; conv outputs are zero-ish
        # mean so the m²-cancellation is benign (r3 ablation: two-pass
        # BN stats were ~24% of the ResNet-50 train step)
        xf = x.astype(jnp.float32)
        m = jnp.mean(xf, axis=axes)
        m2 = jnp.mean(jnp.square(xf), axis=axes)
        v = jnp.maximum(m2 - jnp.square(m), 0.0)
        saved_m, saved_v = m, v
        mean_out = mean * momentum + m * (1 - momentum)
        var_out = var * momentum + v * (1 - momentum)
    # normalization as ONE fused multiply-add in the input dtype: the
    # per-channel affine (a, b) is computed in f32 (tiny), while the big
    # activation tensor is touched once in bf16 — keeps the whole conv→bn→
    # relu chain bf16 and halves HBM traffic vs f32 elementwise math
    # (ResNet-50 train step: 91 GB → measured on-chip, see bench notes)
    inv = jax.lax.rsqrt(v + eps)
    a = (inv * scale)
    b = (bias - m * a)
    y = x * a.astype(x.dtype).reshape(bshape) + b.astype(x.dtype).reshape(bshape)
    return {"Y": [y],
            "MeanOut": [mean_out], "VarianceOut": [var_out],
            "SavedMean": [saved_m],
            "SavedVariance": [jax.lax.rsqrt(saved_v + eps)]}


def _batch_norm_grad_maker(op, block, no_grad_set):
    """Grad only flows through Y → (X, Scale, Bias); running-stat outputs are
    state updates, excluded from differentiation (ref batch_norm_grad op)."""
    g_inputs = {"X$X": op.input("X"), "X$Scale": op.input("Scale"),
                "X$Bias": op.input("Bias"),
                "OG$Y": [grad_var_name(n) for n in op.output("Y")]}
    if op.attrs.get("use_global_stats", False) or \
            op.attrs.get("is_test", False):
        # frozen BN differentiates through the running-stat normalization,
        # not batch stats (ref batch_norm_grad use_global_stats path)
        g_inputs["X$Mean"] = op.input("Mean")
        g_inputs["X$Variance"] = op.input("Variance")
    g_outputs = {
        "IG$X": [grad_var_name(n) if n not in no_grad_set else ""
                 for n in op.input("X")],
        "IG$Scale": [grad_var_name(n) for n in op.input("Scale")],
        "IG$Bias": [grad_var_name(n) for n in op.input("Bias")]}
    attrs = dict(op.attrs)
    return [{"type": "batch_norm_explicit_grad", "inputs": g_inputs,
             "outputs": g_outputs, "attrs": attrs}]


register_op("batch_norm", _batch_norm_lower, grad_maker=_batch_norm_grad_maker)


@register_op("batch_norm_explicit_grad")
def _batch_norm_explicit_grad(ctx, ins, attrs):
    x, scale, bias = X(ins, "X$X"), X(ins, "X$Scale"), X(ins, "X$Bias")
    gy = X(ins, "OG$Y")
    use_global = attrs.get("use_global_stats", False) or \
        attrs.get("is_test", False)
    run_m = X(ins, "X$Mean") if use_global else None
    run_v = X(ins, "X$Variance") if use_global else None

    def fwd(x_, s_, b_):
        eps = attrs.get("epsilon", 1e-5)
        layout = attrs.get("data_layout", "NCHW")
        axes, bshape = _bn_axes(layout, x_.ndim)
        if use_global:
            # frozen BN: running stats are constants w.r.t. x (no dm/dx,
            # dv/dx terms), matching the forward's use_global branch
            m, v = run_m, run_v
        else:
            xf = x_.astype(jnp.float32)
            m = jnp.mean(xf, axis=axes)
            v = jnp.var(xf, axis=axes)
        # same bf16 multiply-add form as the forward lowering so XLA CSEs
        # the recomputation and the big tensors stay bf16 in the vjp
        inv = jax.lax.rsqrt(v + eps)
        a = inv * s_
        b = b_ - m * a
        return x_ * a.astype(x_.dtype).reshape(bshape) \
            + b.astype(x_.dtype).reshape(bshape)

    _, vjp = jax.vjp(fwd, x, scale, bias)
    gx, gs, gb = vjp(gy)
    return {"IG$X": [gx], "IG$Scale": [gs], "IG$Bias": [gb]}


@register_op("layer_norm")
def _layer_norm(ctx, ins, attrs):
    # NOTE: a fused one-pass Pallas LN was written, numerically verified,
    # and LOST end to end on this model class: the kernel boundary breaks
    # XLA's producer/consumer fusion and compute overlap, costing more
    # than the one pass saves (BERT-base: 132.7 ms against 127.3 ms XLA,
    # BERT_ABLATION.md).  It is gone; this XLA lowering is the only one:
    # measure before writing another.
    x = X(ins, "X")
    scale, bias = X(ins, "Scale"), X(ins, "Bias")
    eps = attrs.get("epsilon", 1e-5)
    begin = attrs.get("begin_norm_axis", 1)
    lead = x.shape[:begin]
    x2 = x.reshape(int(np.prod(lead)), -1)
    xf = x2.astype(jnp.float32)
    m = jnp.mean(xf, axis=1, keepdims=True)
    v = jnp.var(xf, axis=1, keepdims=True)
    # stats in f32 (fused reduce over the bf16 input); the per-row affine
    # is tiny, so the big tensor is only touched by bf16 elementwise ops —
    # same traffic-halving treatment as batch_norm's FMA form
    inv = jax.lax.rsqrt(v + eps)
    y = (x2 - m.astype(x2.dtype)) * inv.astype(x2.dtype)
    if scale is not None:
        y = y * scale.astype(y.dtype).reshape(1, -1)
    if bias is not None:
        y = y + bias.astype(y.dtype).reshape(1, -1)
    return {"Y": [y.reshape(x.shape).astype(x.dtype)],
            "Mean": [m.reshape(lead)], "Variance": [v.reshape(lead)]}


@register_op("rms_norm")
def _rms_norm(ctx, ins, attrs):
    """``Scale * x / sqrt(mean(x^2) + eps)`` over the trailing axes from
    ``begin_norm_axis`` (Zhang & Sennrich 2019; the norm of the OLMo/OLMoE
    block).  The whole expression is float32 whatever the input's dtype —
    it is one elementwise pass after the reduction either way, so the
    stream stays in the input's dtype at no extra traffic."""
    x, scale = X(ins, "X"), X(ins, "Scale")
    eps = attrs.get("epsilon", 1e-5)
    begin = attrs.get("begin_norm_axis", 1)
    axes = tuple(range(begin, x.ndim))
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=axes,
                                    keepdims=True) + eps)
    if scale is not None:
        y = y * scale.astype(jnp.float32).reshape(x.shape[begin:])
    return {"Y": [y.astype(x.dtype)]}


@register_op("group_norm")
def _group_norm(ctx, ins, attrs):
    x = X(ins, "X")  # NCHW
    scale, bias = X(ins, "Scale"), X(ins, "Bias")
    eps = attrs.get("epsilon", 1e-5)
    groups = attrs.get("groups", 1)
    n, c = x.shape[:2]
    spatial = x.shape[2:]
    xg = x.astype(jnp.float32).reshape(n, groups, -1)
    m = jnp.mean(xg, axis=2, keepdims=True)
    v = jnp.var(xg, axis=2, keepdims=True)
    y = ((xg - m) * jax.lax.rsqrt(v + eps)).reshape(n, c, *spatial)
    bshape = (1, c) + (1,) * len(spatial)
    if scale is not None:
        y = y * scale.reshape(bshape)
    if bias is not None:
        y = y + bias.reshape(bshape)
    return {"Y": [y.astype(x.dtype)], "Mean": [m.reshape(n, groups)],
            "Variance": [v.reshape(n, groups)]}


@register_op("data_norm")
def _data_norm(ctx, ins, attrs):
    x = X(ins, "X")
    bsize = X(ins, "BatchSize")
    bsum = X(ins, "BatchSum")
    bsqr = X(ins, "BatchSquareSum")
    means = bsum / bsize
    scales = jax.lax.rsqrt(bsqr / bsize - jnp.square(means) + 1e-4)
    y = (x - means) * scales
    return {"Y": [y], "Means": [means], "Scales": [scales]}


@register_op("l2_normalize")
def _l2_normalize(ctx, ins, attrs):
    x = X(ins, "X")
    axis = attrs.get("axis", -1)
    eps = attrs.get("epsilon", 1e-12)
    norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=axis, keepdims=True) + eps)
    return {"Out": [x / norm], "Norm": [norm]}


register_op("norm", _l2_normalize)


@register_op("lrn")
def _lrn(ctx, ins, attrs):
    x = X(ins, "X")  # NCHW
    n_ = attrs.get("n", 5)
    k = attrs.get("k", 1.0)
    alpha = attrs.get("alpha", 1e-4)
    beta = attrs.get("beta", 0.75)
    sq = jnp.square(x)
    half = n_ // 2
    pad = jnp.pad(sq, [(0, 0), (half, half), (0, 0), (0, 0)])
    acc = sum(pad[:, i:i + x.shape[1]] for i in range(n_))
    mid = k + alpha * acc
    return {"Out": [x / jnp.power(mid, beta)], "MidOut": [mid]}


# ---------------------------------------------------------------------------
# softmax & losses
# ---------------------------------------------------------------------------


@register_op("softmax")
def _softmax(ctx, ins, attrs):
    x = X(ins, "X")
    axis = attrs.get("axis", -1)
    # f32-stable internally, preserve input dtype (bf16 attention weights)
    out = jax.nn.softmax(x.astype(jnp.float32), axis=axis)
    return {"Out": [out.astype(x.dtype)]}


@register_op("log_softmax")
def _log_softmax(ctx, ins, attrs):
    return {"Out": [jax.nn.log_softmax(X(ins, "X"), axis=attrs.get("axis", -1))]}


def _swce_lower(ctx, ins, attrs):
    logits, label = X(ins, "Logits"), X(ins, "Label")
    axis = attrs.get("axis", -1)
    soft_label = attrs.get("soft_label", False)
    ignore_index = attrs.get("ignore_index", -100)
    lse = jax.scipy.special.logsumexp(logits, axis=axis, keepdims=True)
    log_sm = logits - lse
    sm = jnp.exp(log_sm)
    if soft_label:
        loss = -jnp.sum(label * log_sm, axis=axis, keepdims=True)
    else:
        li = label
        if li.ndim == logits.ndim and li.shape[axis] == 1:
            li = jnp.squeeze(li, axis=axis)
        picked = jnp.take_along_axis(
            log_sm, jnp.expand_dims(li, axis).astype(jnp.int32), axis=axis)
        loss = -picked
        if ignore_index >= 0:
            mask = (jnp.expand_dims(li, axis) != ignore_index)
            loss = jnp.where(mask, loss, 0.0)
    return {"Softmax": [sm], "Loss": [loss]}


def _swce_grad_maker(op, block, no_grad_set):
    """grad = softmax - onehot(label) — avoids re-running the fwd under vjp
    (ref operators/softmax_with_cross_entropy_op.cc grad kernel)."""
    g_inputs = {"Softmax": op.output("Softmax"), "Label": op.input("Label"),
                "LossGrad": [grad_var_name(n) for n in op.output("Loss")]}
    g_outputs = {"LogitsGrad": [grad_var_name(n) for n in op.input("Logits")]}
    return [{"type": "softmax_with_cross_entropy_grad", "inputs": g_inputs,
             "outputs": g_outputs, "attrs": dict(op.attrs)}]


register_op("softmax_with_cross_entropy", _swce_lower,
            grad_maker=_swce_grad_maker)


@register_op("softmax_with_cross_entropy_grad")
def _swce_grad(ctx, ins, attrs):
    sm, label, gloss = X(ins, "Softmax"), X(ins, "Label"), X(ins, "LossGrad")
    axis = attrs.get("axis", -1)
    if attrs.get("soft_label", False):
        glogits = (sm - label) * gloss
    else:
        li = label
        if li.ndim == sm.ndim and li.shape[axis] == 1:
            li = jnp.squeeze(li, axis=axis)
        onehot = jax.nn.one_hot(li, sm.shape[axis], axis=axis, dtype=sm.dtype)
        glogits = (sm - onehot) * gloss
        ignore_index = attrs.get("ignore_index", -100)
        if ignore_index >= 0:
            mask = (jnp.expand_dims(li, axis) != ignore_index)
            glogits = jnp.where(mask, glogits, 0.0)
    return {"LogitsGrad": [glogits]}


@register_op("cross_entropy")
def _cross_entropy(ctx, ins, attrs):
    x, label = X(ins, "X"), X(ins, "Label")  # x: probabilities
    soft_label = attrs.get("soft_label", False)
    ignore_index = attrs.get("ignore_index", -100)
    eps = 1e-12
    if soft_label:
        loss = -jnp.sum(label * jnp.log(x + eps), axis=-1, keepdims=True)
    else:
        li = label
        if li.ndim == x.ndim and li.shape[-1] == 1:
            li = li[..., 0]
        picked = jnp.take_along_axis(x, li[..., None].astype(jnp.int32), axis=-1)
        loss = -jnp.log(picked + eps)
        if ignore_index >= 0:
            loss = jnp.where(li[..., None] != ignore_index, loss, 0.0)
    return {"Y": [loss]}


register_op("cross_entropy2", _cross_entropy)


@register_op("sigmoid_cross_entropy_with_logits")
def _sce_logits(ctx, ins, attrs):
    x, label = X(ins, "X"), X(ins, "Label")
    ignore_index = attrs.get("ignore_index", -100)
    loss = jnp.maximum(x, 0) - x * label + jnp.log1p(jnp.exp(-jnp.abs(x)))
    mask = (label != ignore_index)
    loss = jnp.where(mask, loss, 0.0)
    if attrs.get("normalize", False):
        loss = loss / jnp.maximum(jnp.sum(mask.astype(x.dtype)), 1.0)
    return {"Out": [loss]}


@register_op("square_error_cost")
def _square_error_cost(ctx, ins, attrs):
    x, y = X(ins, "X"), X(ins, "Y")
    return {"Out": [jnp.square(x - y)]}


@register_op("huber_loss")
def _huber_loss(ctx, ins, attrs):
    x, y = X(ins, "X"), X(ins, "Y")
    d = attrs.get("delta", 1.0)
    r = y - x
    ar = jnp.abs(r)
    loss = jnp.where(ar <= d, 0.5 * r * r, d * (ar - 0.5 * d))
    return {"Out": [loss], "Residual": [r]}


@register_op("smooth_l1_loss")
def _smooth_l1(ctx, ins, attrs):
    x, y = X(ins, "X"), X(ins, "Y")
    iw, ow = X(ins, "InsideWeight"), X(ins, "OutsideWeight")
    sigma = attrs.get("sigma", 1.0)
    s2 = sigma * sigma
    d = x - y
    if iw is not None:
        d = d * iw
    ad = jnp.abs(d)
    loss = jnp.where(ad < 1.0 / s2, 0.5 * d * d * s2, ad - 0.5 / s2)
    if ow is not None:
        loss = loss * ow
    out = jnp.sum(loss.reshape(x.shape[0], -1), axis=1, keepdims=True)
    return {"Out": [out], "Diff": [d]}


@register_op("log_loss")
def _log_loss(ctx, ins, attrs):
    p, label = X(ins, "Predicted"), X(ins, "Labels")
    eps = attrs.get("epsilon", 1e-4)
    loss = -label * jnp.log(p + eps) - (1 - label) * jnp.log(1 - p + eps)
    return {"Loss": [loss]}


@register_op("rank_loss")
def _rank_loss(ctx, ins, attrs):
    label, left, right = X(ins, "Label"), X(ins, "Left"), X(ins, "Right")
    d = left - right
    loss = jnp.log1p(jnp.exp(d)) - label * d
    return {"Out": [loss]}


@register_op("margin_rank_loss")
def _margin_rank_loss(ctx, ins, attrs):
    label, x1, x2 = X(ins, "Label"), X(ins, "X1"), X(ins, "X2")
    margin = attrs.get("margin", 0.0)
    out = jnp.maximum(0.0, -label * (x1 - x2) + margin)
    return {"Out": [out], "Activated": [(out > 0).astype(x1.dtype)]}


@register_op("hinge_loss")
def _hinge_loss(ctx, ins, attrs):
    logits, label = X(ins, "Logits"), X(ins, "Labels")
    return {"Loss": [jnp.maximum(0.0, 1.0 - (2.0 * label - 1.0) * logits)]}


@register_op("kldiv_loss")
def _kldiv_loss(ctx, ins, attrs):
    x, target = X(ins, "X"), X(ins, "Target")
    red = attrs.get("reduction", "mean")
    loss = target * (jnp.log(jnp.maximum(target, 1e-12)) - x)
    loss = jnp.where(target > 0, loss, 0.0)
    if red == "mean":
        loss = jnp.mean(loss)
    elif red == "sum":
        loss = jnp.sum(loss)
    elif red == "batchmean":
        loss = jnp.sum(loss) / x.shape[0]
    return {"Loss": [loss]}


@register_op("bpr_loss")
def _bpr_loss(ctx, ins, attrs):
    x, label = X(ins, "X"), X(ins, "Label")
    li = label[..., 0] if label.ndim == x.ndim and label.shape[-1] == 1 else label
    pos = jnp.take_along_axis(x, li[..., None].astype(jnp.int32), axis=-1)
    diff = x - pos
    loss = jnp.mean(jnp.log1p(jnp.exp(diff)), axis=-1, keepdims=True)
    return {"Y": [loss]}


@register_op("label_smooth")
def _label_smooth(ctx, ins, attrs):
    x = X(ins, "X")
    dist = X(ins, "PriorDist")
    eps = attrs.get("epsilon", 0.0)
    if dist is not None:
        out = (1 - eps) * x + eps * dist
    else:
        out = (1 - eps) * x + eps / x.shape[-1]
    return {"Out": [out]}


@register_op("npair_loss")
def _npair_loss(ctx, ins, attrs):
    anchor, positive, labels = X(ins, "Anchor"), X(ins, "Positive"), X(ins, "Labels")
    l2 = attrs.get("l2_reg", 0.002)
    sim = anchor @ positive.T
    lab = labels.reshape(-1)
    same = (lab[:, None] == lab[None, :]).astype(anchor.dtype)
    tgt = same / jnp.sum(same, axis=1, keepdims=True)
    lse = jax.scipy.special.logsumexp(sim, axis=1, keepdims=True)
    ce = jnp.mean(jnp.sum(-tgt * (sim - lse), axis=1))
    reg = l2 * (jnp.mean(jnp.sum(jnp.square(anchor), 1)) +
                jnp.mean(jnp.sum(jnp.square(positive), 1))) / 2
    return {"Out": [ce + reg]}


@register_op("center_loss")
def _center_loss(ctx, ins, attrs):
    x, label, centers = X(ins, "X"), X(ins, "Label"), X(ins, "Centers")
    lr = X(ins, "CenterUpdateRate")
    li = label.reshape(-1).astype(jnp.int32)
    csel = jnp.take(centers, li, axis=0)
    diff = x - csel
    loss = 0.5 * jnp.sum(jnp.square(diff), axis=1, keepdims=True)
    if attrs.get("need_update", True) and lr is not None:
        cnt = jnp.zeros((centers.shape[0],), x.dtype).at[li].add(1.0)
        upd = jnp.zeros_like(centers).at[li].add(diff)
        centers_out = centers + lr.reshape(()) * upd / (cnt[:, None] + 1.0)
    else:
        centers_out = centers
    return {"Loss": [loss], "SampleCenterDiff": [diff],
            "CentersOut": [centers_out]}


# ---------------------------------------------------------------------------
# dropout — mask is an op output so backward reuses it (ref dropout_op.cc)
# ---------------------------------------------------------------------------


def _dropout_keep(ctx, attrs, shape):
    """The 0/1 keep mask, regenerated identically wherever it's evaluated:
    the RNG key is a pure function of (per-step seed, op tag), so forward
    and backward recompute the same bits instead of storing the mask.

    uint8 threshold test: random-bit GENERATION is the dominant dropout
    cost on TPU (~105 GB/s rbg rate measured on v5e), so one byte per
    element; resolution 1/256 rounds the keep rate by <0.2% absolute.
    Compare in int32: the threshold for p→1.0 is 256, which would wrap to
    0 as uint8 and keep everything.

    Under data parallel the bits are drawn per batch shard (XLA's
    partitioner does not split ``rng-bit-generator``: every chip would draw
    the global shape and keep its slice), each shard from the key folded
    with its own index, or all shards would drop the same positions.
    """
    p = attrs.get("dropout_prob", 0.5)
    tag = attrs.get("seed", 0)
    key = ctx.rng_tagged(tag) if tag else ctx.rng()
    # floor of 1 so tiny-but-nonzero probs still drop ~1/256 instead of
    # silently becoming a no-op
    threshold = max(1, int(round(float(p) * 256.0))) if p > 0 else 0

    def keep(shard, key):
        local = shape
        if shard.index is not None:
            key = jax.random.fold_in(key, shard.index)
            local = (shape[0] // shard.count,) + tuple(shape[1:])
        bits = jax.random.bits(key, local, jnp.uint8)
        return bits.astype(jnp.int32) >= threshold

    return per_dp_shard(ctx, keep, replicated=(key,),
                        batch=shape[0] if shape else None)


def _dropout_lower(ctx, ins, attrs):
    x = X(ins, "X")
    p = attrs.get("dropout_prob", 0.5)
    is_test = attrs.get("is_test", False)
    impl = attrs.get("dropout_implementation", "downgrade_in_infer")
    if is_test:
        out = x * (1.0 - p) if impl == "downgrade_in_infer" else x
        return {"Out": [out], "Mask": [jnp.ones_like(x, dtype=jnp.uint8)]}
    keep = _dropout_keep(ctx, attrs, x.shape)
    if impl == "upscale_in_train":
        scale = 1.0 / (1.0 - p) if p < 1.0 else 0.0
        out = jnp.where(keep, x * scale, 0.0)
    else:
        out = jnp.where(keep, x, 0.0)
    return {"Out": [out.astype(x.dtype)], "Mask": [keep.astype(jnp.uint8)]}


def _dropout_grad_maker(op, block, no_grad_set):
    g_inputs = {"OutGrad": [grad_var_name(n) for n in op.output("Out")]}
    if not op.attrs.get("seed", 0):
        # legacy untagged op: the stored mask is the only way to replay it
        g_inputs["Mask"] = op.output("Mask")
    g_outputs = {"XGrad": [grad_var_name(n) for n in op.input("X")]}
    return [{"type": "dropout_grad", "inputs": g_inputs,
             "outputs": g_outputs, "attrs": dict(op.attrs)}]


register_op("dropout", _dropout_lower, grad_maker=_dropout_grad_maker,
            stateful_rng=True)


@register_op("dropout_grad", stateful_rng=True)
def _dropout_grad(ctx, ins, attrs):
    gout = X(ins, "OutGrad")
    p = attrs.get("dropout_prob", 0.5)
    impl = attrs.get("dropout_implementation", "downgrade_in_infer")
    scale = (1.0 / (1.0 - p)) if (impl == "upscale_in_train" and p < 1.0) else 1.0
    if attrs.get("seed", 0):
        keep = _dropout_keep(ctx, attrs, gout.shape)
    else:
        keep = X(ins, "Mask").astype(bool)
    return {"XGrad": [jnp.where(keep, gout * scale, 0.0).astype(gout.dtype)]}


@register_op("random_crop", no_grad=True, stateful_rng=True)
def _random_crop(ctx, ins, attrs):
    x = X(ins, "X")
    shape = attrs["shape"]
    # crop trailing dims to `shape`
    lead = x.ndim - len(shape)
    key = ctx.rng()
    starts = []
    for i, s in enumerate(shape):
        limit = x.shape[lead + i] - s
        key, sub = jax.random.split(key)
        starts.append(jax.random.randint(sub, (), 0, limit + 1))
    out = x
    for i, (st, sz) in enumerate(zip(starts, shape)):
        out = jax.lax.dynamic_slice_in_dim(out, st, sz, axis=lead + i)
    return {"Out": [out]}


# ---------------------------------------------------------------------------
# interpolation / vision-ish (subset)
# ---------------------------------------------------------------------------


@register_op("nearest_interp")
def _nearest_interp(ctx, ins, attrs):
    x = X(ins, "X")  # NCHW
    oh = attrs.get("out_h", -1)
    ow = attrs.get("out_w", -1)
    os_ = X(ins, "OutSize")
    if os_ is not None:
        static_int(os_, "interp OutSize")
        oh, ow = int(np.asarray(os_)[0]), int(np.asarray(os_)[1])
    n, c = x.shape[:2]
    out = jax.image.resize(x, (n, c, oh, ow), method="nearest")
    return {"Out": [out]}


@register_op("bilinear_interp")
def _bilinear_interp(ctx, ins, attrs):
    x = X(ins, "X")
    oh = attrs.get("out_h", -1)
    ow = attrs.get("out_w", -1)
    os_ = X(ins, "OutSize")
    if os_ is not None:
        static_int(os_, "interp OutSize")
        oh, ow = int(np.asarray(os_)[0]), int(np.asarray(os_)[1])
    n, c = x.shape[:2]
    out = jax.image.resize(x, (n, c, oh, ow), method="bilinear")
    return {"Out": [out]}


@register_op("trilinear_interp")
def _trilinear_interp(ctx, ins, attrs):
    x = X(ins, "X")
    od, oh, ow = attrs.get("out_d", -1), attrs.get("out_h", -1), attrs.get("out_w", -1)
    n, c = x.shape[:2]
    return {"Out": [jax.image.resize(x, (n, c, od, oh, ow), method="trilinear")]}


@register_op("pixel_shuffle")
def _pixel_shuffle(ctx, ins, attrs):
    x = X(ins, "X")
    r = attrs.get("upscale_factor", 1)
    n, c, h, w = x.shape
    out = x.reshape(n, c // (r * r), r, r, h, w)
    out = out.transpose(0, 1, 4, 2, 5, 3).reshape(n, c // (r * r), h * r, w * r)
    return {"Out": [out]}


@register_op("space_to_depth")
def _space_to_depth(ctx, ins, attrs):
    x = X(ins, "X")
    b = attrs["blocksize"]
    n, c, h, w = x.shape
    out = x.reshape(n, c, h // b, b, w // b, b)
    out = out.transpose(0, 3, 5, 1, 2, 4).reshape(n, c * b * b, h // b, w // b)
    return {"Out": [out]}


@register_op("shuffle_channel")
def _shuffle_channel(ctx, ins, attrs):
    x = X(ins, "X")
    g = attrs.get("group", 1)
    n, c, h, w = x.shape
    out = x.reshape(n, g, c // g, h, w).transpose(0, 2, 1, 3, 4).reshape(x.shape)
    return {"Out": [out]}


@register_op("temporal_shift")
def _temporal_shift(ctx, ins, attrs):
    x = X(ins, "X")
    seg = attrs["seg_num"]
    ratio = attrs.get("shift_ratio", 0.25)
    nt, c, h, w = x.shape
    n = nt // seg
    xr = x.reshape(n, seg, c, h, w)
    c1 = int(c * ratio)
    c2 = int(c * 2 * ratio)
    pre = jnp.pad(xr[:, 1:, :c1], [(0, 0), (0, 1), (0, 0), (0, 0), (0, 0)])
    post = jnp.pad(xr[:, :-1, c1:c2], [(0, 0), (1, 0), (0, 0), (0, 0), (0, 0)])
    rest = xr[:, :, c2:]
    out = jnp.concatenate([pre, post, rest], axis=2).reshape(nt, c, h, w)
    return {"Out": [out]}


@register_op("grid_sampler")
def _grid_sampler(ctx, ins, attrs):
    x, grid = X(ins, "X"), X(ins, "Grid")
    n, c, h, w = x.shape
    gx = (grid[..., 0] + 1) * (w - 1) / 2
    gy = (grid[..., 1] + 1) * (h - 1) / 2
    x0 = jnp.floor(gx).astype(jnp.int32)
    y0 = jnp.floor(gy).astype(jnp.int32)
    x1, y1 = x0 + 1, y0 + 1
    wx = gx - x0
    wy = gy - y0

    def sample(yi, xi):
        yi = jnp.clip(yi, 0, h - 1)
        xi = jnp.clip(xi, 0, w - 1)
        bidx = jnp.arange(n)[:, None, None]
        return x[bidx, :, yi, xi]  # n, oh, ow, c

    v00 = sample(y0, x0)
    v01 = sample(y0, x1)
    v10 = sample(y1, x0)
    v11 = sample(y1, x1)
    wx_ = wx[..., None]
    wy_ = wy[..., None]
    out = (v00 * (1 - wx_) * (1 - wy_) + v01 * wx_ * (1 - wy_) +
           v10 * (1 - wx_) * wy_ + v11 * wx_ * wy_)
    return {"Output": [out.transpose(0, 3, 1, 2)]}


@register_op("affine_channel")
def _affine_channel(ctx, ins, attrs):
    x, scale, bias = X(ins, "X"), X(ins, "Scale"), X(ins, "Bias")
    layout = attrs.get("data_layout", "NCHW")
    shape = (1, -1, 1, 1) if layout == "NCHW" else (1, 1, 1, -1)
    return {"Out": [x * scale.reshape(shape) + bias.reshape(shape)]}


@register_op("unfold")
def _unfold(ctx, ins, attrs):
    x = X(ins, "X")
    k = attrs["kernel_sizes"]
    s = attrs.get("strides", [1, 1])
    p = attrs.get("paddings", [0, 0, 0, 0])
    d = attrs.get("dilations", [1, 1])
    n, c, h, w = x.shape
    patches = jax.lax.conv_general_dilated_patches(
        x, filter_shape=tuple(k), window_strides=tuple(s),
        padding=[(p[0], p[2] if len(p) > 2 else p[0]),
                 (p[1], p[3] if len(p) > 3 else p[1])],
        rhs_dilation=tuple(d),
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    return {"Y": [patches.reshape(n, patches.shape[1], -1)]}


@register_op("im2sequence")
def _im2sequence(ctx, ins, attrs):
    x = X(ins, "X")
    k = attrs["kernels"]
    s = attrs.get("strides", [1, 1])
    p = attrs.get("paddings", [0, 0, 0, 0])
    n, c, h, w = x.shape
    patches = jax.lax.conv_general_dilated_patches(
        x, filter_shape=tuple(k), window_strides=tuple(s),
        padding=[(p[0], p[2]), (p[1], p[3])],
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    nc, oh, ow = patches.shape[1], patches.shape[2], patches.shape[3]
    out = patches.transpose(0, 2, 3, 1).reshape(n * oh * ow, nc)
    return {"Out": [out]}


@register_op("fc")
def _fc(ctx, ins, attrs):
    """Fused fc produced by fc_fuse_pass (ref operators/fc_op.cc): flatten
    Input at in_num_col_dims, matmul W, add Bias, optional activation."""
    from .math_ops import _ACTIVATIONS
    x, w, b = X(ins, "Input"), X(ins, "W"), X(ins, "Bias")
    ncd = attrs.get("in_num_col_dims", 1)
    x2 = x.reshape(int(np.prod(x.shape[:ncd])), -1)
    out = x2 @ w
    if b is not None:
        out = out + b.reshape(1, -1)
    act = attrs.get("activation_type", "")
    if act:
        out = (jax.nn.gelu if act == "gelu" else _ACTIVATIONS[act])(out)
    return {"Out": [out.reshape(x.shape[:ncd] + (w.shape[1],))]}


@register_op("fused_elemwise_activation")
def _fused_elemwise_activation(ctx, ins, attrs):
    """ref operators/fused/fused_elemwise_activation_op.cc: functor_list is
    [binary, unary] applied as unary(binary(x, y))."""
    from .math_ops import _ACTIVATIONS
    x, y = X(ins, "X"), X(ins, "Y")
    binary, unary = attrs["functor_list"]
    if binary != "elementwise_add":
        raise NotImplementedError(f"fused functor {binary}")
    out = x + broadcast_to_x(x, y, attrs.get("axis", -1))
    if unary == "scale":
        s, b = attrs.get("scale", 1.0), attrs.get("bias", 0.0)
        out = out * s + b if attrs.get("bias_after_scale", True) \
            else (out + b) * s
    elif unary == "gelu":
        out = jax.nn.gelu(out, approximate=False)
    else:
        out = _ACTIVATIONS[unary](out)
    return {"Out": [out]}


TIED_HEAD_LOWERINGS_CTR = _monitor.REGISTRY.counter(
    "paddle_tpu_tied_head_lowerings_total",
    "fused_lm_head_ce lowerings (forward, and the generic vjp's forward "
    "inside the grad op) that read an embedding's [vocab, d] table as the "
    "head's weight, by table_reads: the forward ops of the program that "
    "read that table, the head among them (2: one lookup and the head) — "
    "counted while tracing, once per compile, nothing per step",
    ("table_reads",))


@register_op("fused_lm_head_ce")
def _fused_lm_head_ce(ctx, ins, attrs):
    """LM head projection + softmax cross-entropy, scanned over token
    chunks so the [tokens, vocab] logits are NEVER materialized in HBM
    (with vocab 30k+, full f32 logits are gigabytes — the dominant memory
    AND bandwidth cost of an MLM/LM step; the reference computes them
    dense, operators/softmax_with_cross_entropy_op.cc).  jax.checkpoint on
    the chunk body makes the backward recompute each chunk's logits, so
    training memory stays O(chunk * vocab).  No reference counterpart —
    TPU-native capability.

    ``w_layout`` (absent: W is [d, vocab], like ``fc``'s, and the lowering is
    what it was before the attribute existed): ``"vd"``, W is an embedding's
    [vocab, d] table and the logits contract over its second axis (tied
    input and output embeddings: one parameter read both ways; its gradient
    leaves here [vocab, d] and ``backward.py`` adds the lookup's to it)."""
    x, w = X(ins, "X"), X(ins, "W")
    b = X(ins, "Bias")
    label = X(ins, "Label")
    ignore = attrs.get("ignore_index", -100)
    chunk = int(attrs.get("chunk_size", 1024))
    tied = attrs.get("w_layout") == "vd"
    if tied and not getattr(ctx, "is_abstract", False):
        TIED_HEAD_LOWERINGS_CTR.inc(
            table_reads=str(int(attrs.get("table_reads", 0))))

    def head(shard, x, label, w, *bias):
        b = bias[0] if bias else None
        lead = x.shape[:-1]
        d = x.shape[-1]
        n = int(np.prod(lead))
        x2 = x.reshape(n, d)
        l1 = label.reshape(n)
        pad = (-n) % chunk
        if pad:
            x2 = jnp.concatenate([x2, jnp.zeros((pad, d), x2.dtype)])
            l1 = jnp.concatenate(
                [l1, jnp.full((pad,), ignore, l1.dtype)])
        n_chunks = (n + pad) // chunk
        xc = x2.reshape(n_chunks, chunk, d)
        lc = l1.reshape(n_chunks, chunk)

        def body(carry, inp):
            xi, li = inp
            if tied:                    # [chunk, d] x [vocab, d]^T
                logits = jax.lax.dot_general(
                    xi.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                    (((1,), (1,)), ((), ()))).astype(jnp.float32)
            else:
                logits = (xi.astype(jnp.bfloat16) @ w.astype(jnp.bfloat16)
                          ).astype(jnp.float32)
            if b is not None:
                logits = logits + b.astype(jnp.float32)
            m = jax.lax.stop_gradient(
                jnp.max(logits, axis=-1, keepdims=True))
            lse = jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1)) + m[:, 0]
            safe = jnp.where(li == ignore, 0, li)
            picked = jnp.take_along_axis(
                logits, safe[:, None], axis=-1)[:, 0]
            loss = jnp.where(li == ignore, 0.0, lse - picked)
            return carry, loss

        _, losses = jax.lax.scan(jax.checkpoint(body), 0.0, (xc, lc))
        return losses.reshape(-1)[:n].reshape(lead + (1,))

    # per batch shard under data parallel: the scan runs over the token
    # chunks, which is the sharded batch axis, and a `while` cannot be
    # partitioned along its own iteration axis — left to the partitioner,
    # x is all-gathered and every chip runs the global batch's chunks.
    # The transpose of the replicated W is one psum of dW after the scan.
    out = per_dp_shard(ctx, head, sharded=(x, label),
                       replicated=(w,) if b is None else (w, b))
    return {"Loss": [out]}
