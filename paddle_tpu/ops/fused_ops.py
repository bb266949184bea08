"""Fused-op lowerings targeted by ``analysis.fusion``'s rewrites.

Both ops are EXACT compositions of the unfused lowerings they replace
(same jnp calls, same broadcast/cast order, same tagged-dropout RNG
stream), so a fused program's loss trajectory matches the unfused one
bit-for-bit and its lowered text is the chain's: the rewrite is a
canonicalization (fewer program ops to walk, one scope to account a
dense layer under), not a kernel.

AMP note: the unfused chain casts per op (``amp.cast_ins``: matmul
white-list → bf16 always; add/act/dropout/LN → bf16 only for ndim≥3
activations).  A single fused op would get ONE blanket cast, changing
numerics for 2-D activations — so these lowerings are registered in no
AMP list and replicate the per-stage policy internally.

Gradients flow through the generic vjp of these lowerings
(``registry.make_grad_ops`` convention — the fusion pass synthesizes
the ``<type>_grad`` descs wired to the original external grad names).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.registry import register_op
from .common import X, XS, broadcast_to_x
from .nn_ops import _dropout_keep


def _amp_pair(ctx, *arrs):
    """bf16-cast a value group (the fused analog of one cast_ins call)."""
    if not getattr(ctx, "amp", False):
        return arrs
    out = []
    for a in arrs:
        if a is not None and hasattr(a, "dtype") and \
                a.dtype in (jnp.float32, jnp.bfloat16, jnp.float16) and \
                a.dtype != jnp.bfloat16:
            a = a.astype(jnp.bfloat16)
        out.append(a)
    return out


@register_op("fused_dense_act", stateful_rng=True)
def _fused_dense_act(ctx, ins, attrs):
    """mul/matmul + elementwise_add(bias) + gelu/relu [+ tagged dropout]
    in one op (ops fused by ``analysis.fusion`` pattern
    ``dense_epilogue``)."""
    x, w, b = X(ins, "X"), X(ins, "W"), X(ins, "Bias")
    xnc = int(attrs.get("x_num_col_dims", 1))
    act = attrs.get("act", "") or ""
    approximate = bool(attrs.get("approximate", False))

    # stage 1 — the matmul (AMP white-list: always bf16)
    x_c, w_c = _amp_pair(ctx, x, w)
    if xnc >= 0:                         # mul semantics
        xs, ws = x_c.shape, w_c.shape
        x2 = x_c.reshape(int(np.prod(xs[:xnc])), -1)
        w2 = w_c.reshape(int(ws[0]), -1)
        out_shape = xs[:xnc] + ws[1:]
    else:                                # matmul semantics (no transpose)
        xs = x_c.shape
        x2 = x_c.reshape(int(np.prod(xs[:-1])), xs[-1])
        w2 = w_c
        out_shape = xs[:-1] + w_c.shape[1:]
    out = x2 @ w2
    # stage 2 — bias add (+act): AMP casts only 'big' activations
    big = len(out_shape) >= 3
    if big:
        out, b = _amp_pair(ctx, out, b)
    out = out + broadcast_to_x(out, b,
                               int(attrs.get("bias_axis", -1))
                               if len(out_shape) == out.ndim else -1)
    if act == "gelu":
        out = jax.nn.gelu(out, approximate=approximate)
    elif act == "relu":
        out = jax.nn.relu(out)
    out = out.reshape(out_shape)

    # stage 3 — tagged dropout (_dropout_lower's arithmetic on
    # _dropout_keep's mask; the tag makes fwd/bwd/unfused draws identical)
    tag = int(attrs.get("seed", 0))
    if tag:
        p = attrs.get("dropout_prob", 0.5)
        impl = attrs.get("dropout_implementation", "downgrade_in_infer")
        if attrs.get("is_test", False):
            out = out * (1.0 - p) if impl == "downgrade_in_infer" else out
        else:
            keep = _dropout_keep(ctx, attrs, out.shape)
            if impl == "upscale_in_train":
                scale = 1.0 / (1.0 - p) if p < 1.0 else 0.0
                out = jnp.where(keep, out * scale, 0.0)
            else:
                out = jnp.where(keep, out, 0.0)
    return {"Out": [out]}


@register_op("fused_embedding_layer_norm")
def _fused_embedding_layer_norm(ctx, ins, attrs):
    """lookup_table [+ elementwise_adds] + layer_norm in one op (pattern
    ``embedding_layer_norm``): the row gather, the embedding-sum adds
    and the normalization in one lowering."""
    w, ids = X(ins, "W"), X(ins, "Ids")
    addends = XS(ins, "Addends")
    scale, bias = X(ins, "Scale"), X(ins, "Bias")

    # lookup_table, exactly (squeeze trailing 1, padding row zeroed)
    sq_ids = ids[..., 0] if ids.ndim >= 2 and ids.shape[-1] == 1 else ids
    x = jnp.take(w, sq_ids, axis=0)
    pad = attrs.get("padding_idx", -1)
    if pad is not None and pad != -1:
        mask = (sq_ids != pad)[..., None]
        x = jnp.where(mask, x, jnp.zeros_like(x))

    for a in addends:
        if x.ndim >= 3 or getattr(a, "ndim", 0) >= 3:
            x, a = _amp_pair(ctx, x, a)
        x = x + broadcast_to_x(x, a, -1)

    eps = attrs.get("epsilon", 1e-5)
    begin = attrs.get("begin_norm_axis", 1)
    if getattr(ctx, "amp", False) and x.ndim >= 3:
        x, = _amp_pair(ctx, x)           # LN casts only its X slot
    lead = x.shape[:begin]
    x2 = x.reshape(int(np.prod(lead)), -1)
    xf = x2.astype(jnp.float32)
    m = jnp.mean(xf, axis=1, keepdims=True)
    v = jnp.var(xf, axis=1, keepdims=True)
    inv = jax.lax.rsqrt(v + eps)         # exact _layer_norm replica
    y = (x2 - m.astype(x2.dtype)) * inv.astype(x2.dtype)
    if scale is not None:
        y = y * scale.astype(y.dtype).reshape(1, -1)
    if bias is not None:
        y = y + bias.astype(y.dtype).reshape(1, -1)
    return {"Out": [y.reshape(x.shape).astype(x.dtype)],
            "Mean": [m.reshape(lead)], "Variance": [v.reshape(lead)]}
