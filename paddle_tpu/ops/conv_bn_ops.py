"""Fused train-time conv(1x1)+BatchNorm op (TPU-native; no reference
counterpart — the reference's conv_bn_fuse_pass.cc folds BN into conv
weights for INFERENCE only, which is impossible with batch statistics).

``fused_conv1x1_bn`` computes the 1x1 conv as a channel-minor Pallas
matmul whose epilogue accumulates the BN sum/sumsq in the same read
(pallas/conv_bn.py), then normalizes with the bf16 FMA form.  Semantics
match conv2d(bias-free, 1x1) -> batch_norm(train) [-> act] exactly:
same outputs (Y, MeanOut, VarianceOut, SavedMean, SavedVariance as
rsqrt), same running-stat updates.  Gradients flow through the generic
vjp of this lowering (the Pallas kernel carries a custom_vjp).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..framework.registry import register_op
from .common import X


@register_op("fused_conv1x1_bn")
def _fused_conv1x1_bn(ctx, ins, attrs):
    x = X(ins, "X")                       # [N, C, H, W]
    filt = X(ins, "Filter")               # [Cout, Cin, 1, 1]
    scale, bias = X(ins, "Scale"), X(ins, "Bias")
    mean, var = X(ins, "Mean"), X(ins, "Variance")
    momentum = attrs.get("momentum", 0.9)
    eps = attrs.get("epsilon", 1e-5)
    act = attrs.get("act", "") or ""
    stride = attrs.get("stride", 1)
    is_test = attrs.get("is_test", False)
    use_global = attrs.get("use_global_stats", False) or is_test

    cout, cin = filt.shape[0], filt.shape[1]
    if stride > 1:
        x = x[:, :, ::stride, ::stride]
    nb, _, h, w = x.shape
    m = nb * h * w
    w2 = filt.reshape(cout, cin)          # [Cout, Cin]
    xf = x.reshape(nb, cin, h * w)        # NCHW view — no transpose

    if use_global:
        # frozen path: fold BN into the matmul weights (exactly the
        # inference conv_bn fold) — no stats pass at all
        inv = jax.lax.rsqrt(var + eps)
        a = (inv * scale)
        wf = (w2 * a[:, None]).astype(w2.dtype)
        y = jnp.einsum("oc,ncp->nop", wf, xf)
        y = y + (bias - mean * inv * scale).astype(y.dtype)[None, :, None]
        saved_m, saved_v = mean, jax.lax.rsqrt(var + eps)
        mean_out, var_out = mean, var
    else:
        from ..device import on_tpu
        if on_tpu():
            # compiled by Mosaic, or the compiler's error raises
            from ..pallas.conv_bn import conv1x1_stats
            y_raw, s, s2 = conv1x1_stats(xf, w2)
        else:
            # CPU test path, chosen by platform: the same (y, sum, sumsq)
            # in plain jnp — the interpreted Pallas kernel would run the
            # tile loop as traced ops (1.66x the whole RN50 CPU step).
            # Mirrors the unfused chain's dtypes: the matmul in bf16
            # under AMP (conv2d is amp white-listed), stats accumulated
            # in f32 (batch_norm's one-pass rule)
            mm_w, mm_x = w2, xf
            if getattr(ctx, "amp", False):
                mm_w = mm_w.astype(jnp.bfloat16)
                mm_x = mm_x.astype(jnp.bfloat16)
            y_raw = jnp.einsum("oc,ncp->nop", mm_w, mm_x)
            yf = y_raw.astype(jnp.float32)
            s = jnp.sum(yf, axis=(0, 2))
            s2 = jnp.sum(jnp.square(yf), axis=(0, 2))
        mu = s / m
        v = jnp.maximum(s2 / m - jnp.square(mu), 0.0)
        inv = jax.lax.rsqrt(v + eps)
        a = inv * scale
        b = bias - mu * a
        y = y_raw * a.astype(y_raw.dtype)[None, :, None] \
            + b.astype(y_raw.dtype)[None, :, None]
        saved_m, saved_v = mu, jax.lax.rsqrt(v + eps)
        mean_out = mean * momentum + mu * (1 - momentum)
        var_out = var * momentum + v * (1 - momentum)
    if act == "relu":
        y = jnp.maximum(y, 0)
    y4 = y.reshape(nb, cout, h, w)
    return {"Y": [y4], "MeanOut": [mean_out], "VarianceOut": [var_out],
            "SavedMean": [saved_m], "SavedVariance": [saved_v]}
