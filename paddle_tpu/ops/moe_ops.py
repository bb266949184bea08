"""Mixture-of-experts ops — the capability behind the mesh's ``ep`` axis.

No reference counterpart (the 2019 snapshot has no MoE).  Two ops over one
routing scheme: the slot -> expert ids are sorted (stable), the rows gathered
into that order, each expert's rows multiplied as one group of a grouped
matmul whose group sizes are data, and the results un-sorted.  Every shape is
static, whatever the routing; no one-hot ``[S, E, C]`` dispatch tensor.

- ``moe_ffn``: dropless top-k over gated-SiLU experts (OLMoE, Mixtral).
- ``switch_ffn``: Switch-Transformer top-1 with biases and a capacity, which
  here is a cap on the rows of a group that count, not a tensor dimension.

The layers annotate the expert weights with dist_spec ``("ep", ...)``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .. import monitor as _monitor
from ..framework.core import grad_var_name
from ..framework.registry import register_op
from .common import X


@jax.custom_vjp
def _permute_rows(x, perm, inverse):
    """``x[perm]`` for a permutation whose inverse is at hand: the transpose
    is the gather ``g[inverse]``, never a scatter."""
    return jnp.take(x, perm, axis=0)


_permute_rows.defvjp(
    lambda x, perm, inverse: (jnp.take(x, perm, axis=0), inverse),
    lambda inverse, g: (jnp.take(g, inverse, axis=0), None, None))


def _inverse_permutation(perm):
    r = perm.shape[0]
    return jnp.zeros((r,), jnp.int32).at[perm].set(
        jnp.arange(r, dtype=jnp.int32), unique_indices=True)


def _expert_load(slot_e, n_experts):
    """[E] int32: how many of the slots ``slot_e`` [R] chose each expert."""
    return jnp.sum(slot_e[:, None] == jnp.arange(n_experts)[None, :], axis=0,
                   dtype=jnp.int32)


def _sorted_slots(slot_e):
    """``order`` [R]: the slots ``slot_e`` [R] (each slot's expert) sorted by
    expert, stable, so arrival order holds within an expert; ``place`` [R]:
    each slot's row in that order."""
    order = jnp.argsort(slot_e, stable=True).astype(jnp.int32)
    return order, _inverse_permutation(order)


@register_op("switch_ffn")
def _switch_ffn(ctx, ins, attrs):
    """Switch-Transformer FFN: y = combine(expert_ffn(dispatch(x))).

    Inputs: X [B,T,d], GateW [d,E], W1 [E,d,f], B1 [E,f], W2 [E,f,d],
    B2 [E,d].  Outputs: Out [B,T,d], AuxLoss [] (load-balancing loss,
    E·Σ_e fraction_e·prob_e — add a small multiple to the training loss).
    Tokens beyond an expert's capacity are dropped (contribute zero),
    per the Switch recipe.

    Routed like ``moe_ffn``: rows sorted by expert (stable, so a row's rank
    within its group is its arrival order) and two grouped matmuls; capacity
    is a cap on the rows of a group that count, not a tensor dimension.
    """
    x, gw = X(ins, "X"), X(ins, "GateW")
    w1, b1 = X(ins, "W1"), X(ins, "B1")
    w2, b2 = X(ins, "W2"), X(ins, "B2")
    act = attrs.get("act", "relu")
    cf = float(attrs.get("capacity_factor", 1.25))
    B, T, d = x.shape
    E = gw.shape[-1]
    S = B * T
    cap = int(max(1, np.ceil(cf * S / E)))
    xt = x.reshape(S, d)

    # gating in f32 (tiny [S, E] tensors; router numerics matter)
    logits = xt.astype(jnp.float32) @ gw.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate = probs.max(axis=-1)
    idx = probs.argmax(axis=-1)

    load = _expert_load(idx, E)
    order, place = _sorted_slots(idx)
    e_of_row = jnp.take(idx, order)
    rank = jnp.arange(S, dtype=jnp.int32) - jnp.take(
        jnp.cumsum(load) - load, e_of_row)
    xs = _permute_rows(xt, order, place)
    h = jax.lax.ragged_dot(xs, w1.astype(x.dtype), load) \
        + jnp.take(b1.astype(x.dtype), e_of_row, axis=0)
    h = jax.nn.gelu(h) if act == "gelu" else jax.nn.relu(h)
    ye = jax.lax.ragged_dot(h, w2.astype(x.dtype), load) \
        + jnp.take(b2.astype(x.dtype), e_of_row, axis=0)
    ye = jnp.where((rank < cap)[:, None], ye, 0)
    y = _permute_rows(ye, place, order) * gate.astype(x.dtype)[:, None]

    frac = load.astype(jnp.float32) / S                         # tokens/e
    aux = (frac * probs.mean(axis=0)).sum() * E
    return {"Out": [y.reshape(B, T, d)], "AuxLoss": [aux]}


# -- dropless top-k routing over sorted rows ---------------------------------

MOE_LOWERINGS_CTR = _monitor.REGISTRY.counter(
    "paddle_tpu_moe_lowerings_total",
    "moe_ffn forward lowerings by the implementation of the expert matmuls, "
    "the number of experts and the experts per token — counted while "
    "tracing, once per compile of a block that holds the op, nothing per "
    "step", ("impl", "experts", "top_k"))


#: megablox tile sizes (rows, contraction, columns) for the bf16 expert
#: matmuls on a TPU; swept on a v5e at the OLMoE shapes
#: (tools/olmoe_kernel_sweep.py, PERF.md)
_GMM_TILING = (512, 1024, 1024)


def _experts_impl(dt):
    from ..device import on_tpu
    return "megablox" if dt == jnp.bfloat16 and on_tpu() else "ragged_dot"


def _grouped_matmul(dt, impl=None, tiling=None):
    """``mm(rows [R, a], w [E, a, b], load [E]) -> [R, b]`` in ``dt``: row
    ``r`` times the matrix of the group it lies in, groups being consecutive
    runs of ``load[e]`` rows.  The weight is cast inside, so that a
    ``jax.vjp`` of ``mm`` returns its gradient in the weight's own dtype.

    ``impl``: "megablox", the Pallas grouped matmul that ships with JAX (TPU
    only; the default for bf16 on a TPU), or "ragged_dot",
    ``jax.lax.ragged_dot`` (the default elsewhere, and for float32 rows — the
    test-mode program's — at ``highest`` precision).  On a v5e megablox is
    the faster (PERF.md section 6) and, unlike XLA's ragged-dot kernels,
    keeps the program's scope in the device trace."""
    if (impl or _experts_impl(dt)) == "megablox":
        from jax.experimental.pallas.ops.tpu.megablox import ops as _mb

        def mm(rows, w, load):
            return _mb.gmm(rows.astype(dt), w.astype(dt), load, dt,
                           tiling or _GMM_TILING)
        return mm
    prec = jax.lax.Precision.HIGHEST if dt == jnp.float32 else None

    def mm(rows, w, load):
        return jax.lax.ragged_dot(rows.astype(dt), w.astype(dt), load,
                                  precision=prec)
    return mm


def _gate(g, u, dt):
    """``silu(g) * u`` in float32, stored in ``dt``."""
    return (jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)
            ).astype(dt)


def gated_experts(xs, wg, wu, wd, load, dt, impl=None, tiling=None):
    """``Wd_e (silu(Wg_e x) * Wu_e x)`` for sorted rows ``xs`` [R, d] whose
    expert is given by the run lengths ``load`` [E]; operands in ``dt``,
    accumulation and the gate's arithmetic in float32.  Returns ``(y, g,
    u)``: the result and the two projections a backward needs."""
    mm = _grouped_matmul(dt, impl, tiling)
    g = mm(xs, wg, load)
    u = mm(xs, wu, load)
    return mm(_gate(g, u, dt), wd, load), g, u


def _router(xt, wr, k, renorm):
    """Float32 at full precision whatever AMP says: ``(top_p [S, k], lb [],
    z [])`` and, not differentiated, ``(top_e [S, k], load [E])``."""
    f32 = jnp.float32
    S, E = xt.shape[0], wr.shape[-1]
    logits = jnp.dot(xt.astype(f32), wr.astype(f32),
                     precision=jax.lax.Precision.HIGHEST)           # [S, E]
    p = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(p, k)
    if renorm:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    load = _expert_load(top_e.reshape(S * k), E)
    lb = E * jnp.sum(load.astype(f32) / S * jnp.mean(p, axis=0))
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return (top_p, lb, z), (top_e, load)


def _moe_dtype(ctx, x):
    amp = getattr(ctx, "amp", False) and x.dtype in (jnp.float32,
                                                     jnp.bfloat16)
    return jnp.bfloat16 if amp else x.dtype


def _moe_ffn(ctx, ins, attrs):
    """Dropless top-k mixture of gated-SiLU experts (the OLMoE / Mixtral
    layer): ``Out = sum_{e in topk} p_e * Wd_e (silu(Wg_e x) * Wu_e x)``,
    ``p = softmax(x Wr)`` over all experts, the ``k`` largest kept as they
    are or renormalised to sum 1 (``norm_topk_prob``).  No capacity: every
    token reaches its ``k`` experts whatever the load.

    Inputs: X [B,T,d], RouterW [d,E], GateW [E,d,f], UpW [E,d,f],
    DownW [E,f,d].  Outputs: Out [B,T,d]; LbLoss [] = ``E * sum_e f_e P_e``
    (``f_e``: slots that chose ``e`` over tokens, so it sums to ``k``;
    ``P_e``: mean of ``p_e`` over tokens); ZLoss [] = mean over tokens of
    ``logsumexp(x Wr)^2``; ExpertLoad [E] int32 rows per expert;
    TopExperts [B,T,k] int32, each token's experts by falling ``p``;
    Saved: what ``moe_ffn_grad`` reuses (the sort order, the sorted rows, the
    two projections and the experts' output).

    Four parts, each under its own scope for the device trace: ``router``
    (float32 at full precision, whatever AMP says), ``dispatch`` (stable sort
    of the ``S*k`` slot -> expert ids, one row gather), ``experts`` (three
    grouped matmuls whose group sizes are data), ``combine`` (un-sort, weight
    by ``p_e``, sum the ``k``).  Every shape is static; no ``[S, E, C]``
    tensor exists.  Under AMP the rows and the expert weights are bf16 with
    float32 accumulation."""
    x, wr = X(ins, "X"), X(ins, "RouterW")
    wg, wu, wd = X(ins, "GateW"), X(ins, "UpW"), X(ins, "DownW")
    k = int(attrs["top_k"])
    B, T, d = x.shape
    E = wr.shape[-1]
    S = B * T
    dt = _moe_dtype(ctx, x)
    if not getattr(ctx, "is_abstract", False):
        MOE_LOWERINGS_CTR.inc(impl=_experts_impl(dt), experts=str(E),
                              top_k=str(k))
    xt = x.reshape(S, d)

    with jax.named_scope("router"):
        (top_p, lb, z), (top_e, load) = _router(
            xt, wr, k, bool(attrs.get("norm_topk_prob", False)))

    with jax.named_scope("dispatch"):
        order, place = _sorted_slots(top_e.reshape(S * k))
        xs = jnp.take(xt.astype(dt), order // k, axis=0)

    with jax.named_scope("experts"):
        y, g, u = gated_experts(xs, wg, wu, wd, load, dt)

    with jax.named_scope("combine"):
        ys = jnp.take(y, place, axis=0).reshape(S, k, d)
        out = jnp.sum(ys.astype(jnp.float32) * top_p[:, :, None], axis=1)
    return {"Out": [out.astype(x.dtype).reshape(B, T, d)], "LbLoss": [lb],
            "ZLoss": [z], "ExpertLoad": [load],
            "TopExperts": [top_e.astype(jnp.int32).reshape(B, T, k)],
            "Saved": [order, xs, g, u, y]}


def _moe_ffn_grad_maker(op, block, no_grad_set):
    def grads(names):
        return [grad_var_name(n) for n in names]
    slots = ("X", "RouterW", "GateW", "UpW", "DownW")
    g_inputs = {"X$" + s: op.input(s) for s in slots}
    g_inputs["Saved"] = op.output("Saved")
    for s in ("Out", "LbLoss", "ZLoss"):
        g_inputs["OG$" + s] = grads(op.output(s))
    g_outputs = {"IG$" + s: [g if n not in no_grad_set else ""
                             for n, g in zip(op.input(s),
                                             grads(op.input(s)))]
                 for s in slots}
    return [{"type": "moe_ffn_grad", "inputs": g_inputs,
             "outputs": g_outputs, "attrs": dict(op.attrs)}]


register_op("moe_ffn", _moe_ffn, grad_maker=_moe_ffn_grad_maker)


@register_op("moe_ffn_grad")
def _moe_ffn_grad(ctx, ins, attrs):
    """The backward of ``moe_ffn`` from what the forward saved: no second
    sort, no second gather of the rows, no second forward matmul.  The router
    (a [S, d] x [d, E] product) is computed again for its vjp; each grouped
    matmul is transposed by ``jax.vjp`` at its saved operands, whose unused
    primal XLA removes; the transposes of the two row gathers are gathers
    (every row of ``x`` is read exactly ``k`` times).  An output whose
    gradient nobody produced counts as zero."""
    x, wr = X(ins, "X$X"), X(ins, "X$RouterW")
    weights = [X(ins, "X$" + s) for s in ("GateW", "UpW", "DownW")]
    order, xs, g, u, y = ins["Saved"]
    d_out, d_lb, d_z = (X(ins, "OG$" + s) for s in ("Out", "LbLoss", "ZLoss"))
    k = int(attrs["top_k"])
    B, T, d = x.shape
    S, R = B * T, B * T * k
    f32, dt = jnp.float32, xs.dtype
    mm = _grouped_matmul(dt)
    xt = x.reshape(S, d)

    with jax.named_scope("router"):
        (top_p, _, _), router_vjp, (_, load) = jax.vjp(
            lambda xt, wr: _router(xt, wr, k,
                                   bool(attrs.get("norm_topk_prob", False))),
            xt, wr, has_aux=True)

    with jax.named_scope("combine"):
        place = _inverse_permutation(order)
        d_rows = jnp.zeros((R, d), f32) if d_out is None else jnp.take(
            d_out.reshape(S, d), order // k, axis=0).astype(f32)
        d_top_p = jnp.take(jnp.sum(d_rows * y.astype(f32), axis=-1),
                           place).reshape(S, k)
        dy = (d_rows * jnp.take(top_p.reshape(R), order)[:, None]).astype(dt)

    def transposed(rows, w, cot):
        return jax.vjp(lambda a, b: mm(a, b, load), rows, w)[1](cot)

    with jax.named_scope("experts"):
        wg, wu, wd = weights
        dh, d_wd = transposed(_gate(g, u, dt), wd, dy)
        gf, uf, dhf = g.astype(f32), u.astype(f32), dh.astype(f32)
        sig = jax.nn.sigmoid(gf)
        dxs_g, d_wg = transposed(
            xs, wg, (dhf * uf * sig * (1.0 + gf * (1.0 - sig))).astype(dt))
        dxs_u, d_wu = transposed(xs, wu, (dhf * gf * sig).astype(dt))

    with jax.named_scope("dispatch"):
        dx = jnp.take(dxs_g + dxs_u, place, axis=0).reshape(S, k, d) \
            .astype(f32).sum(axis=1)

    with jax.named_scope("router"):
        zero = jnp.zeros((), f32)
        dx_r, d_wr = router_vjp((d_top_p, zero if d_lb is None else d_lb,
                                 zero if d_z is None else d_z))
    return {"IG$X": [(dx + dx_r).astype(x.dtype).reshape(B, T, d)],
            "IG$RouterW": [d_wr.astype(wr.dtype)], "IG$GateW": [d_wg],
            "IG$UpW": [d_wu], "IG$DownW": [d_wd]}
