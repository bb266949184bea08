"""Miscellaneous op lowerings: hashing, positional encoding, distillation
losses, tree convolution, SelectedRows shims.

Reference kernels: ``operators/hash_op.cc``, ``add_position_encoding_op.cc``,
``fsp_op.cc``, ``teacher_student_sigmoid_loss_op.cc``,
``similarity_focus_op.cc``, ``scatter_nd_add_op.cc`` (scatter_nd variant),
``crop_tensor_op.cc``, ``tree_conv_op.cc`` (+ ``math/tree2col.cc``),
``merge_selected_rows_op.cc``, ``get_tensor_from_selected_rows_op.cc``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.registry import register_op
from .common import X, XS, static_int, ids_dtype


@register_op("hash", no_grad=True)
def _hash(ctx, ins, attrs):
    """Multi-hash of int ids (ref hash_op.cc: xxHash % mod_by per hash seed).

    TPU-native: a Knuth multiplicative hash per seed — stateless, vectorized,
    same contract (num_hash hashed id columns bounded by mod_by).
    """
    x = X(ins, "X")
    num_hash = attrs.get("num_hash", 1)
    mod_by = attrs.get("mod_by", 1)
    ids = x.astype(jnp.uint32)
    # combine trailing feature dim first (ref hashes the whole row)
    row = ids.reshape(ids.shape[0], -1)
    outs = []
    for i in range(num_hash):
        seed = jnp.uint32((0x9E3779B1 + 0x85EBCA6B * i) % (2 ** 32))
        h = jnp.zeros((row.shape[0],), jnp.uint32)
        for j in range(row.shape[1]):
            h = (h ^ (row[:, j] * seed)) * jnp.uint32(0x9E3779B1)
            h = h ^ (h >> 15)
        outs.append((h % jnp.uint32(mod_by)).astype(ids_dtype()))
    out = jnp.stack(outs, axis=1)[:, :, None]
    return {"Out": [out]}


@register_op("add_position_encoding")
def _add_position_encoding(ctx, ins, attrs):
    """out = alpha*x + beta*sinusoid(pos) (ref add_position_encoding_op.cc)."""
    x = X(ins, "X")
    alpha = attrs.get("alpha", 1.0)
    beta = attrs.get("beta", 1.0)
    b, t, d = x.shape
    half = d // 2
    pos = jnp.arange(t, dtype=jnp.float32)[:, None]
    div = jnp.power(10000.0, jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos / div
    pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=1)
    if pe.shape[1] < d:
        pe = jnp.pad(pe, [(0, 0), (0, d - pe.shape[1])])
    return {"Out": [alpha * x + beta * pe[None].astype(x.dtype)]}


@register_op("fsp")
def _fsp(ctx, ins, attrs):
    """Flow-of-solution-procedure matrix for distillation (ref fsp_op.cc):
    out[b] = X[b].reshape(cx, h*w) @ Y[b].reshape(cy, h*w)^T / (h*w)."""
    x, y = X(ins, "X"), X(ins, "Y")
    b, cx, h, w = x.shape
    cy = y.shape[1]
    xf = x.reshape(b, cx, h * w)
    yf = y.reshape(b, cy, h * w)
    out = jnp.einsum("bik,bjk->bij", xf, yf) / float(h * w)
    return {"Out": [out]}


@register_op("teacher_student_sigmoid_loss")
def _ts_sigmoid_loss(ctx, ins, attrs):
    """Distillation CTR loss (ref teacher_student_sigmoid_loss_op.cc).

    label <= -1: teacher signal absent → plain sigmoid CE on sign;
    otherwise combine hard CE with soft teacher score.
    """
    x, label = X(ins, "X"), X(ins, "Label")
    soft_max_up = attrs.get("soft_max_up_bound", 15.0)
    soft_max_lo = attrs.get("soft_max_lower_bound", -15.0)
    lbl = label.astype(x.dtype)
    z = jnp.clip(x, soft_max_lo, soft_max_up)
    # hard part: -(y*log(sig) + (1-y)*log(1-sig)) with y = (label > 0)
    yhard = (lbl > 0).astype(x.dtype)
    hard = jnp.maximum(z, 0) - z * yhard + jnp.log1p(jnp.exp(-jnp.abs(z)))
    # soft part when 0 < label < 1 (teacher score)
    is_soft = jnp.logical_and(lbl > 0, lbl < 1).astype(x.dtype)
    soft = jnp.maximum(z, 0) - z * lbl + jnp.log1p(jnp.exp(-jnp.abs(z)))
    out = jnp.where(is_soft > 0, soft, hard)
    return {"Y": [out]}


@register_op("similarity_focus", no_grad=True)
def _similarity_focus(ctx, ins, attrs):
    """ref similarity_focus_op.cc: for each selected channel, emit a 0/1 mask
    marking, per (h, w) position, whether that position holds the channel's
    row/column maximum (greedy non-repeating in the reference; we use the
    vectorizable row-max ∪ col-max form)."""
    x = X(ins, "X")
    axis = attrs.get("axis", 1)
    indexes = attrs.get("indexes", [0])
    if axis != 1:
        x_ = jnp.moveaxis(x, axis, 1)
    else:
        x_ = x
    mask = jnp.zeros(x_.shape, x.dtype)
    for idx in indexes:
        ch = x_[:, idx]                       # [b, h, w]
        rowmax = (ch == ch.max(axis=2, keepdims=True))
        colmax = (ch == ch.max(axis=1, keepdims=True))
        m = jnp.logical_or(rowmax, colmax).astype(x.dtype)  # [b,h,w]
        mask = jnp.maximum(mask, m[:, None])
    out = mask if axis == 1 else jnp.moveaxis(mask, 1, axis)
    return {"Out": [out]}


@register_op("scatter_nd")
def _scatter_nd(ctx, ins, attrs):
    """scatter_nd(index, updates, shape): zeros of `shape` with updates
    scatter-added at index (ref scatter_nd_add over fill_zeros)."""
    index, updates = X(ins, "Index"), X(ins, "Updates")
    shape = attrs["shape"]
    zeros = jnp.zeros(shape, updates.dtype)
    return {"Out": [zeros.at[tuple(jnp.moveaxis(index, -1, 0))].add(updates)]}


@register_op("crop_tensor")
def _crop_tensor(ctx, ins, attrs):
    """crop with offsets/shape as attrs or compile-time tensor inputs
    (ref crop_tensor_op.cc — Shape/Offsets tensors must be static under XLA)."""
    x = X(ins, "X")
    offsets = attrs.get("offsets") or [0] * x.ndim
    shape = attrs.get("shape") or list(x.shape)
    shape = [xs if s in (-1, 0) else s for s, xs in zip(shape, x.shape)]
    idx = tuple(slice(o, o + s) for o, s in zip(offsets, shape))
    return {"Out": [x[idx]]}


@register_op("tree_conv")
def _tree_conv(ctx, ins, attrs):
    """Tree-based convolution (ref tree_conv_op.cc, math/tree2col.cc).

    NodesVector [b, n, f]: node features; EdgeSet [b, e, 2]: parent->child
    edges (1-based, 0-padded); Filter [f, 3, out, m].  Each node's patch is
    itself + its direct children; the three filter slices weight (top, left,
    right) positions per the continuous binary-tree formulation.
    """
    nodes = X(ins, "NodesVector")
    edges = X(ins, "EdgeSet")
    filt = X(ins, "Filter")
    f_in, three, out_c, m = filt.shape
    b, n, f = nodes.shape
    e = edges.shape[1]
    parent = edges[..., 0].astype(jnp.int32)   # [b, e], 1-based; 0 = pad
    child = edges[..., 1].astype(jnp.int32)
    valid = (parent > 0).astype(nodes.dtype)   # [b, e]
    p0 = jnp.maximum(parent - 1, 0)
    c0 = jnp.maximum(child - 1, 0)

    # children features aggregated to parents, with left/right position
    # weights eta_l/eta_r from child ordinal within its sibling list
    nchild = jnp.zeros((b, n), nodes.dtype)
    nchild = jax.vmap(lambda nc, p, v: nc.at[p].add(v))(nchild, p0, valid)
    nc_per_edge = jnp.take_along_axis(nchild, p0, axis=1)  # [b, e]
    # sibling ordinal: cumulative count of edges already seen for that parent
    def per_batch(p, v):
        counts = jnp.zeros((n,), nodes.dtype)
        def body(i, cs_and_out):
            counts, out = cs_and_out
            pi = p[i]
            out = out.at[i].set(counts[pi])
            counts = counts.at[pi].add(v[i])
            return (counts, out)
        counts, out = jax.lax.fori_loop(0, e, body,
                                        (counts, jnp.zeros((e,), nodes.dtype)))
        return out
    sib_idx = jax.vmap(per_batch)(p0, valid)               # [b, e]
    denom = jnp.maximum(nc_per_edge - 1.0, 1.0)
    eta_r = jnp.where(nc_per_edge > 1, sib_idx / denom, 0.5) * valid
    eta_l = (1.0 - eta_r) * valid
    child_feat = jnp.take_along_axis(
        nodes, c0[..., None].astype(jnp.int32), axis=1)    # [b, e, f]

    wt, wl, wr = filt[:, 0], filt[:, 1], filt[:, 2]        # [f, out, m]
    top = jnp.einsum("bnf,fom->bnom", nodes, wt)
    cl = jnp.einsum("bef,fom->beom", child_feat * eta_l[..., None], wl)
    cr = jnp.einsum("bef,fom->beom", child_feat * eta_r[..., None], wr)
    agg = jnp.zeros((b, n, out_c, m), nodes.dtype)
    agg = jax.vmap(lambda a, p, v: a.at[p].add(v))(agg, p0, cl + cr)
    # no activation here: the layer appends act (ref applies act(conv+bias))
    return {"Out": [(top + agg).reshape(b, n, out_c, m)]}


@register_op("merge_selected_rows")
def _merge_selected_rows(ctx, ins, attrs):
    """ref merge_selected_rows_op.cc: dedup rows of a SelectedRows, summing
    duplicate rows.  On TPU sparse grads are carried dense (XLA scatter-add
    already merged duplicates), so this is the identity on the carrier."""
    return {"Out": [X(ins, "X")]}


@register_op("get_tensor_from_selected_rows")
def _get_tensor_from_selected_rows(ctx, ins, attrs):
    """ref get_tensor_from_selected_rows_op.cc — dense carrier passthrough."""
    return {"Out": [X(ins, "X")]}


@register_op("optimization_barrier", no_grad=True)
def _optimization_barrier(ctx, ins, attrs):
    """XLA CSE fence: recomputed-segment inputs pass through this so the
    compiler cannot merge the recomputation with the original forward
    values (jax.checkpoint uses the same primitive for the same reason).
    No reference counterpart — remat support is TPU-native."""
    if len(ins["X"]) > 1:      # values that may only go on together
        return {"Out": list(jax.lax.optimization_barrier(tuple(ins["X"])))}
    return {"Out": [jax.lax.optimization_barrier(X(ins, "X"))]}
