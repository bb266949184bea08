"""Mixture-of-experts ops — the capability behind the mesh's ``ep`` axis.

No reference counterpart (the 2019 snapshot has no MoE).  Two ops over one
routing scheme: the slot -> expert ids are sorted (stable), the rows gathered
into that order, each expert's rows multiplied as one group of a grouped
matmul whose group sizes are data, and the results un-sorted.  Every shape is
static, whatever the routing (a chip's share of the experts picks its row
buffer's length from a short ladder of static lengths, below); no one-hot
``[S, E, C]`` dispatch tensor.

- ``moe_ffn``: dropless top-k over gated experts, SiLU on the gate branch
  (OLMoE, Mixtral; with ``score_func="sigmoid"``, a selection bias,
  renormalised and scaled weights and a share of the experts, the
  DeepSeek-V3 / Trinity layer) or ReLU (``act="relu"``; with a router that
  reads an input of its own, ``RouterX``, the SmallThinker layer).
- ``switch_ffn``: Switch-Transformer top-1 with biases and a capacity, which
  here is a cap on the rows of a group that count, not a tensor dimension.

``moe_ffn``'s routing attributes (all optional; the defaults are the op as it
was before they existed, bit for bit):

- ``score_func``: ``softmax`` (over all experts) or ``sigmoid`` (each expert
  by itself).  Either way ``_router`` computes logits, scores, top-k and
  weights in float32 with the matmul at ``highest``, whatever AMP says.
  The ``k`` are chosen without a sort (``_passes``: ``k`` passes of arg-max,
  the first index winning a tie, which is ``lax.top_k``'s order; a TPU
  lowers ``lax.top_k`` to a sort of the whole row), and the backward runs
  no router again: ``moe_ffn`` saves the logits, the choice (as indices and
  as each chosen column's slot), the weights and the count, and
  ``moe_ffn_grad`` computes the router's two gradients
  from them in closed form (``_router_backward``), two products where the
  vjp of the forward had three (PR 64).  Not where ``top_k`` is no multiple
  of 8 (``_narrow``): there the choice stays ``lax.top_k``'s and the
  backward ``jax.vjp``'s of the router computed again, as they were.
- input ``SelectBias`` [E_total]: added to the scores for the choice of the
  ``k`` experts only; the weights are the unbiased scores.  Not
  differentiated (the choice is not, and nothing else reads it).
- ``norm_topk_prob`` with ``norm_eps``: the kept scores divided by their sum
  plus ``norm_eps`` (published 1e-20 for sigmoid routers); ``route_scale``
  multiplies the weights after that.
- ``n_group``, ``topk_group`` (group-limited selection, DeepSeek-V3's
  ``noaux_tc`` / Ling 2.0's gate): the ``E_total`` experts in ``n_group``
  groups of consecutive experts, a group's score the sum of its two largest
  biased scores, the ``topk_group`` best groups kept, the others' entries
  masked out of the choice of the ``k`` (``_group_mask``: the choice's own
  top-k over ``[S, n_group, E / n_group]`` and over the group scores, no
  sort of ``E_total``).  The weights stay the unbiased scores of the chosen; the
  backward passes nothing through the mask.  1 / 1 is no attribute and the
  lowering as it was.
- ``expert_offset``: the router stays ``[d, E_total]``, the expert weights
  are ``[E_here, d, f]`` and hold experts ``offset .. offset + E_here - 1``.
  The op computes ``sum_{e in top-k, offset <= e < offset + E_here} w_e *
  expert_e(x)`` with ``w`` normalised over all ``k`` chosen: this chip's
  part of the layer.  Only the slots routed to held experts are multiplied;
  the sort puts them first.  The row buffer's length follows ``ExpertLoad``:
  ``held_ladder`` gives a few static lengths from the shapes alone (from
  about twice even routing's share, doubling, up to ``S * min(k, E_here)``,
  the most a routing can send here), and the router's own count of the held
  rows picks the shortest that holds them all (``jax.lax.switch`` over
  copies of each row gather and of the gate's pass, one a length; the
  grouped matmuls stay outside at the longest length, since they visit the
  held rows' tiles and no other whatever lies behind).  The two un-sorts
  (the forward's weighted sum over a token's slots, the backward's gather
  back to tokens) read the held rows alone on a TPU, whatever the rung:
  ``pallas/held_rows.py`` copies a row for each held slot and none for a
  slot held elsewhere, so they cost by the rows routed here and not by the
  ``S * k`` slots (PR 42).  A ladder with a rung short enough for XLA's own
  gather to read fast (``_SHORT_SOURCE_BYTES``: Trinity's, JoyAI's) keeps
  that gather, a copy a rung in a switch, as every ladder does off the TPU.
  Every rung holds every held row, so the held experts drop nothing
  whatever the load; ``moe_ffn_grad`` picks its rung from the same count.
  ``Saved`` keeps the longest rung's shapes: a shorter rung writes the
  front.  ``ExpertLoad`` stays ``[E_total]``.  Nothing stands in for the
  absent experts or their exchange.
- ``act``: the gate branch's activation, ``silu`` (default) or ``relu``
  (``relu(Wg x) * Wu x``, "ReGLU"), forward and backward, with every expert
  held and on every rung of a share's ladder.
- no ``GateW`` input, with ``act="relu2"`` (``layers.moe_ffn(gated=False)``):
  un-gated experts, ``Wd_e relu(Wu_e x)^2`` (Nemotron-H's): TWO grouped
  matmuls a pass where a gated expert has three, ``Saved`` holds one
  projection, and the backward passes ``2 relu(u)`` through the square;
  sorted and held paths, every rung.  With GateW the op is as it was.
- input ``RouterX`` [B, T, d]: what the router reads where that is not what
  the experts read (a router placed before attention scores the layer's
  input; the experts get the post-attention rows).  Only the ``router``
  scope reads it, and ``moe_ffn_grad`` returns its cotangent apart from
  ``X``'s.  Without it the router reads ``X``, the lowering as it was.

The layers annotate the expert weights with dist_spec ``("ep", ...)``.
"""

from __future__ import annotations

import functools

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
    "step; held = the experts whose weights the op holds, score_func = the "
    "router's score, ladder = the static lengths the row buffer of a chip's "
    "share chooses from, shortest first ('' where every expert is held), "
    "act = the gate branch's activation, router_input = x where the router "
    "reads the experts' rows and own where it has an input of its own, "
    "unsort = what the two un-sorts (the forward's weighted sum, the "
    "backward's gather back to tokens) cost by: rows, a share's held rows "
    "alone (pallas/held_rows.py), or slots, XLA's gather over every slot; "
    "groups = n_group/topk_group of the router's group-limited selection, "
    "1/1 where every expert competes with every other; gated = 1 where an "
    "expert is Wd (act(Wg x) * Wu x), three grouped matmuls, 0 where it is "
    "Wd act(Wu x), two; slot_sum = the order XLA's gather brings a token's "
    "k slots home in for the two un-sorts' sums: major, slot j of every "
    "token in rows j*S .. (j+1)*S and the sum over the leading axis (where "
    "k is no multiple of the float32 sublane tile of 8), or minor, a "
    "token's k rows adjacent (k a multiple of 8, and the held-rows kernel, "
    "which walks the slots in that order)",
    ("impl", "experts", "top_k", "held", "score_func", "ladder", "act",
     "router_input", "unsort", "groups", "gated", "slot_sum"))


MOE_ROUTED_ROWS_CTR = _monitor.REGISTRY.counter(
    "paddle_tpu_moe_routed_rows_total",
    "routed slots (tokens x experts per token) of the ExpertLoad outputs "
    "handed to record_expert_load: where=all, every slot; where=held, those "
    "that chose an expert whose weights the op holds.  Counted on the host "
    "by whoever fetched the loads, nothing per step", ("where",))


MOE_HELD_BUFFER_CTR = _monitor.REGISTRY.counter(
    "paddle_tpu_moe_held_buffer_total",
    "of the ExpertLoad outputs handed to record_expert_load for a chip's "
    "share of the experts, how many selected a row buffer of `rows` rows: "
    "the rung of held_ladder that the lowering's own rule (held_rung) picks "
    "for that load.  Counted on the host by whoever fetched the loads, "
    "nothing per step", ("rows",))


#: {(S * k, E, E_here): ladder} of the held-path lowerings traced in this
#: process: what record_expert_load cannot read off a load (``load.sum()`` is
#: ``S * k`` and ``load.size`` is ``E``; the ladder also needs ``S``)
_TRACED_LADDERS = {}


def record_expert_load(load, expert_offset=0, n_held=None):
    """Add one fetched ``ExpertLoad`` [E_total] (a host array) to
    ``paddle_tpu_moe_routed_rows_total``; ``n_held`` defaults to all.  For a
    share of the experts whose lowering this process traced, also count the
    buffer length that load selects (``paddle_tpu_moe_held_buffer_total``)."""
    load = np.asarray(load).reshape(-1)
    n_held = load.size if n_held is None else int(n_held)
    held_rows = int(load[expert_offset:expert_offset + n_held].sum())
    MOE_ROUTED_ROWS_CTR.inc(int(load.sum()), where="all")
    MOE_ROUTED_ROWS_CTR.inc(held_rows, where="held")
    ladder = _TRACED_LADDERS.get((int(load.sum()), load.size, n_held))
    if ladder is not None:
        MOE_HELD_BUFFER_CTR.inc(rows=str(ladder[held_rung(held_rows,
                                                          ladder)]))


#: megablox tile sizes (rows, contraction, columns) for the bf16 expert
#: matmuls on a TPU; swept on a v5e at the OLMoE shapes
#: (tools/olmoe_kernel_sweep.py, PERF.md)
_GMM_TILING = (512, 1024, 1024)


#: the same for a chip's share of the experts (``expert_offset``), whose
#: groups are the few hundred rows of one sequence's slots and not
#: thousands: megablox visits a row tile once for each group that touches it,
#: so at 512 rows half the visits are of tiles that straddle two groups;
#: measured in the Trinity-Mini step and kernel by kernel on a v5e
#: (tools/trinity_experts_sweep.py, PERF.md)
_GMM_TILING_HELD = (256, 1024, 1024)


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


_ACTS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def _act_of(attrs, gated=True):
    """The op's activation: gated experts (the op has a GateW) have the two
    forms of ``_ACTS``, un-gated experts the one form ``relu2``."""
    act = attrs.get("act", "silu") or "silu"
    if act not in (_ACTS if gated else ("relu2",)):
        raise ValueError(f"moe_ffn act {act!r} of "
                         f"{'gated' if gated else 'un-gated'} experts")
    return act


def _gate(g, u, dt, act="silu"):
    """``act(g) * u`` in float32, stored in ``dt``; un-gated experts (``g``
    None): ``relu(u)^2``."""
    if g is None:
        return jnp.square(jax.nn.relu(u.astype(jnp.float32))).astype(dt)
    return (_ACTS[act](g.astype(jnp.float32)) * u.astype(jnp.float32)
            ).astype(dt)


def _head(a, rows):
    """The first ``rows`` rows of ``a``; None stays None (un-gated experts
    have no gate branch)."""
    return None if a is None else a[:rows]


def gated_experts(xs, wg, wu, wd, load, dt, impl=None, tiling=None,
                  gate=_gate):
    """``Wd_e (act(Wg_e x) * Wu_e x)`` for sorted rows ``xs`` [R, d] whose
    expert is given by the run lengths ``load`` [E]; operands in ``dt``,
    accumulation and the gate's arithmetic in float32.  Returns ``(y, g,
    u)``: the result and the two projections a backward needs.  ``gate(g,
    u, dt)``: :func:`_gate` (SiLU) unless the caller binds another ``act``, or
    the held path's, which passes over a rung's rows only.  ``wg`` None:
    un-gated experts, ``Wd_e gate(None, Wu_e x)``, two products and ``g``
    None."""
    mm = _grouped_matmul(dt, impl, tiling)
    g = None if wg is None else mm(xs, wg, load)
    u = mm(xs, wu, load)
    return mm(gate(g, u, dt), wd, load), g, u


def _narrow(k):
    """Whether ``[S, k]`` arrays are narrow in the way a v5e's compiled step
    has not survived: ``k`` no multiple of 8, so that XLA:TPU tiles them (4,
    128) or pads them.  With 4 experts a token at 4096 tokens (Xing4.0's
    cell) the step never finishes its first run with the choice by passes
    of arg-max, and neither with the router's backward in closed form,
    whichever way the ``k`` columns are joined or read (as PR 63's
    transposed ``[4096, 4]`` index array: PERF.md section 6, PRs 63 and 64;
    section 7, row 64); with ``jax.lax.top_k`` and ``jax.vjp`` it runs.  So
    a router of such a ``k`` keeps both (:func:`_router`, ``moe_ffn_grad``);
    LFM2's 4 of 32 at 16384 tokens ran either way, and 6 a token was not
    tried.  Something the lowering reads off its attribute ``top_k``."""
    return k % 8 != 0


def _passes(a, k):
    """The ``k`` largest of each row of ``a`` without a sort, as two lists of
    ``k`` columns [..., 1]: ``k`` passes, each the row's maximum and the
    first column that holds it (``argmax``: the first index wins a tie, as
    ``lax.top_k`` orders them), that entry masked to -inf for the next pass.
    Rows must hold at least ``k`` entries above -inf (the router's do:
    scores are finite and the group mask leaves ``topk_group`` whole
    groups), and no -0.0 beside a 0.0, which tie here.  Written as the
    least column that equals the maximum, XLA:TPU's plan for JoyAI's
    brim-full step no longer fitted the chip (16.04 of 15.75 GiB for the
    parent's 14.55 GB).  ``jax.lax.top_k`` is a sort of the row on a TPU: on
    a v5e the passes are 0.8 ms an evaluation faster over Ling's [8192, 512]
    with its group mask, 0.6 over [8192, 320], 0.2 over 256, and level
    within 0.08 ms from 128 entries down to 32 and from 8 passes down to 4,
    so no rule on the shapes keeps the sort (tools/router_probe.py, PERF.md
    section 6, PR 64)."""
    cols = jnp.arange(a.shape[-1], dtype=jnp.int32)
    tops, ats = [], []
    for _ in range(k):
        tops.append(jnp.max(a, axis=-1, keepdims=True))
        ats.append(jnp.argmax(a, axis=-1, keepdims=True).astype(jnp.int32))
        a = jnp.where(cols == ats[-1], -jnp.inf, a)
    return tops, ats


def _joined(ats):
    """[..., k] int32 of ``k`` index columns: joined as float32 (exact, a
    row is far under 2**24 entries) and made int32 behind a barrier.  Joined
    as int32, XLA:TPU writes the [4096, 4] of Xing4.0's router by a
    pad-and-add fusion into a (4, 128)-tiled layout, PR 63's shape (PERF.md
    section 7, row 64); float32 arrays of that shape and tiling run."""
    joined = jnp.concatenate([at.astype(jnp.float32) for at in ats], axis=-1)
    return jax.lax.optimization_barrier(joined).astype(jnp.int32)


def _top_k(a, k):
    """``jax.lax.top_k(a, k)`` over the last axis by :func:`_passes`: the
    same values and the same indices in the same order."""
    tops, ats = _passes(a, k)
    return jnp.concatenate(tops, axis=-1), _joined(ats)


def _group_mask(sel, n_group, topk_group, top_k):
    """[S, E] bool: the experts of each token's ``topk_group`` best of
    ``n_group`` groups of ``E / n_group`` consecutive experts, a group's
    score the sum of its two largest entries of ``sel`` (DeepSeek-V3's
    ``noaux_tc``): the two largest over [S, n_group, E / n_group] and the
    ``topk_group`` largest of the group scores, by ``top_k`` (:func:`_top_k`,
    no sort, or ``jax.lax.top_k``: the router's own choice says)."""
    S, E = sel.shape
    top2, _ = top_k(sel.reshape(S, n_group, E // n_group), 2)
    _, best = top_k(jnp.sum(top2, axis=-1), topk_group)
    kept = jnp.any(best[:, :, None] == jnp.arange(n_group)[None, None, :],
                   axis=1)                                  # [S, n_group]
    return jnp.repeat(kept, E // n_group, axis=1)


def _scores(logits, score_func):
    if score_func == "sigmoid":
        return jax.nn.sigmoid(logits)
    if score_func == "softmax":
        return jax.nn.softmax(logits, axis=-1)
    raise ValueError(f"moe_ffn score_func {score_func!r}")


def _router(xt, wr, k, renorm, score_func="softmax", bias=None,
            norm_eps=0.0, scale=1.0, n_group=1, topk_group=1):
    """Float32 at full precision whatever AMP says, softmax or sigmoid:
    ``(top_p [S, k], lb [], z [])`` and ``(top_e [S, k], load [E], logits
    [S, E], rank [S, E] int32)``: ``rank`` holds a chosen column's slot, 1
    to ``k``, and 0 elsewhere, and with ``load`` and ``logits`` is what
    :func:`_router_backward` reads (None for a narrow ``k``,
    :func:`_narrow`, whose choice is ``jax.lax.top_k``'s and whose backward
    ``jax.vjp``'s).  ``bias`` [E] moves the
    choice of the ``k`` only; ``top_p`` are the unbiased scores of the
    chosen, renormalised (``/ (sum + norm_eps)``) and scaled if asked.
    Under ``sigmoid`` the load-balancing loss reads the scores normalised
    over the experts.  ``n_group`` > 1: the ``k`` are chosen among the
    experts of the ``topk_group`` best groups (:func:`_group_mask` of the
    biased scores).  The choice is :func:`_top_k`'s, no sort.  Three scopes
    for the device trace: ``score`` (the product and the activation),
    ``select`` (the mask, the choice, the weights), ``losses``."""
    f32 = jnp.float32
    S, E = xt.shape[0], wr.shape[-1]
    with jax.named_scope("score"):
        logits = jnp.dot(xt.astype(f32), wr.astype(f32),
                         precision=jax.lax.Precision.HIGHEST)       # [S, E]
        p = _scores(logits, score_func)
    with jax.named_scope("select"):
        if _narrow(k):          # the choice and its scores as they were
            if n_group > 1:
                sel = jax.lax.stop_gradient(
                    p if bias is None else p + bias.astype(f32)[None, :])
                sel = jnp.where(_group_mask(sel, n_group, topk_group,
                                            jax.lax.top_k), sel, -jnp.inf)
                _, top_e = jax.lax.top_k(sel, k)
                q = jnp.take_along_axis(p, top_e, axis=-1)
            elif bias is None:
                q, top_e = jax.lax.top_k(p, k)
            else:
                _, top_e = jax.lax.top_k(
                    p + jax.lax.stop_gradient(bias.astype(f32))[None, :], k)
                q = jnp.take_along_axis(p, top_e, axis=-1)
            rank = None
        else:
            sel = p if bias is None else p + bias.astype(f32)[None, :]
            if n_group > 1:
                sel = jnp.where(_group_mask(sel, n_group, topk_group, _top_k),
                                sel, -jnp.inf)
            tops, ats = _passes(sel, k)
            top_e = _joined(ats)
            # the chosen scores and each chosen column's slot (1 .. k, 0 for
            # the others) straight from the passes' columns, a
            # compare-and-select a slot over the row: ``take_along_axis(p,
            # top_e)`` is a gather of S * k single entries on a TPU, 0.55 to
            # 1.4 ms at the cells' sizes and more than the choice itself,
            # where the row sums cost 0.05 (tools/router_probe.py --pieces)
            cols = jnp.arange(E, dtype=jnp.int32)
            hits = [cols == at for at in ats]
            q = jnp.concatenate(tops if sel is p else [
                jnp.sum(jnp.where(hit, p, 0.0), axis=-1, keepdims=True)
                for hit in hits], axis=-1)
            rank = sum(jnp.where(hit, j + 1, 0) for j, hit in enumerate(hits))
        if renorm:
            denom = jnp.sum(q, axis=-1, keepdims=True)
            q = q / (denom + norm_eps if norm_eps else denom)
        top_p = q * scale if scale != 1.0 else q
    with jax.named_scope("losses"):
        load = _expert_load(top_e.reshape(S * k), E)
        if score_func == "sigmoid":
            p = p / jnp.sum(p, axis=-1, keepdims=True)
        lb = E * jnp.sum(load.astype(f32) / S * jnp.mean(p, axis=0))
        z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return (top_p, lb, z), (top_e, load, logits, rank)


def _router_backward(xt, wr, logits, rank, load, cotangents, renorm,
                     score_func="softmax", norm_eps=0.0, scale=1.0):
    """``(dx [S, d], d_wr [d, E])``: the router's backward in closed form
    from what the forward saved, the ``logits`` [S, E], the chosen columns'
    slots ``rank`` [S, E] and the ``load`` [E]; no product of the forward's,
    no choice, no count, no gather and no [S, k] integer array (a [4096, 4]
    one read by its columns is among what hung Xing4.0's step: PERF.md
    section 6, PR 64).  ``cotangents``: ``(d_top_p [S, k], d_lb, d_z)``, None
    for a loss nobody differentiated.  Nothing flows through the bias, the
    mask or the choice.

    ``d_logits`` [S, E] is elementwise over the logits' rows beside a few
    row sums: ``d_top_p`` set into the chosen columns by ``k``
    compare-and-selects of ``rank`` (no scatter), then through
    ``route_scale`` and the renormalisation (``q_j / (sum q + norm_eps)``,
    the sum over the chosen columns of the row); the load-balancing loss ``E
    sum_e load_e / S mean_s p_se`` adds ``d_lb E load_e / S^2`` to every
    row, under ``sigmoid`` through the scores' normalisation over the
    experts; the score function's own derivative; the z-loss's ``d_z 2 lse /
    S softmax(logits)``.  Then the two products the vjp of the forward's
    has, at ``highest``."""
    f32 = jnp.float32
    d_top_p, d_lb, d_z = cotangents
    S, E = logits.shape
    p = _scores(logits, score_func)
    dp = sum(jnp.where(rank == j + 1, d_top_p[:, j:j + 1], 0.0)
             for j in range(d_top_p.shape[-1]))
    if scale != 1.0:
        dp = dp * scale
    if renorm:      # top_p_j = q_j / denom: the row's sum moves every weight
        chosen = rank > 0
        denom = jnp.sum(jnp.where(chosen, p, 0.0), axis=-1, keepdims=True)
        if norm_eps:
            denom = denom + norm_eps
        dp = (dp - jnp.where(chosen, jnp.sum(dp * p, axis=-1, keepdims=True)
                             / denom, 0.0)) / denom
    if d_lb is not None:
        d_mean = (d_lb * E / S / S) * load.astype(f32)[None, :]
        if score_func == "sigmoid":
            total = jnp.sum(p, axis=-1, keepdims=True)
            d_mean = (d_mean - jnp.sum(d_mean * p, axis=-1, keepdims=True)
                      / total) / total
        dp = dp + d_mean
    if score_func == "sigmoid":
        d_logits = dp * p * (1.0 - p)
    else:
        d_logits = p * dp - p * jnp.sum(p * dp, axis=-1, keepdims=True)
    if d_z is not None:
        lse = jax.nn.logsumexp(logits, axis=-1, keepdims=True)
        d_logits = d_logits + (d_z * 2.0 / S) * lse * jnp.exp(logits - lse)
    hi = jax.lax.Precision.HIGHEST
    dx = jnp.dot(d_logits, wr.astype(f32).T, precision=hi)
    d_wr = jnp.dot(xt.astype(f32).T, d_logits, precision=hi)
    return dx.astype(xt.dtype), d_wr


def _weights_of(attrs):
    """The op's routing attributes that the weights read: ``_router``'s and
    ``_router_backward``'s keywords alike."""
    return dict(renorm=bool(attrs.get("norm_topk_prob", False)),
                score_func=attrs.get("score_func", "softmax") or "softmax",
                norm_eps=float(attrs.get("norm_eps", 0.0) or 0.0),
                scale=float(attrs.get("route_scale", 1.0) or 1.0))


def _router_of(attrs, k, bias):
    """``(xt, wr) -> _router(...)`` with the op's routing attributes."""
    kw = dict(_weights_of(attrs), bias=bias,
              n_group=int(attrs.get("n_group", 1) or 1),
              topk_group=int(attrs.get("topk_group", 1) or 1))
    return lambda xt, wr: _router(xt, wr, k, **kw)


def _held_slots(top_e, offset, n_held, k):
    """For a chip that holds experts ``offset .. offset + n_held - 1`` of a
    wider router: ``held`` [R] bool (the slot's expert lives here), ``order``
    [R] (the slots sorted by local expert, stable, the slots of absent
    experts last) and ``place`` [R] its inverse.  All three are over slot
    ids, whatever the row buffer's length turns out to be."""
    S = top_e.shape[0]
    local = top_e.reshape(S * k) - offset
    held = (local >= 0) & (local < n_held)
    order, place = _sorted_slots(jnp.where(held, local, n_held))
    return held, order, place


def held_ladder(S, k, n_held, E):
    """The static lengths, shortest first, that the row buffer of a chip's
    share of the experts (``n_held`` of ``E``, ``S`` tokens, ``k`` experts a
    token) chooses from; a function of these four alone.  The last is ``S *
    min(k, n_held)``, the most slots a routing can send here (a token's ``k``
    experts are distinct); before it, from twice even routing's share ``S *
    k * n_held / E`` (fresh weights send 0.7 to 1.5 of it, PERF.md) doubling
    up to a quarter of the last, each a multiple of the held path's row tile
    so that a rung multiplies the same rows in the same tiles as the full
    buffer does.  A quarter: on a v5e at Trinity's and JoyAI's sizes a rung
    of half the buffer was no faster than the whole when the rule was set
    (PR 35: XLA's un-sorts cost per slot unless their source is short, and
    every rung has its copies to the front to pay:
    tools/trinity_experts_sweep.py --lengths, PERF.md section 6).  Since PR
    42 the un-sorts of a long rung read the held rows alone and no longer
    cost by the slot; what a rung still buys is the row gathers, the gate's
    passes and the cotangents at its length, and the rule was not measured
    again (PERF.md section 7, row 28); so at most three lengths under the
    last, here two."""
    full, tile = S * min(k, n_held), _GMM_TILING_HELD[0]
    rung = -(-2 * S * k * n_held // (E * tile)) * tile
    ladder = []
    while 4 * rung <= full:
        ladder.append(rung)
        rung *= 2
    return tuple(ladder[:3]) + (full,)


def held_rung(held_rows, ladder):
    """The index of the shortest rung of ``ladder`` that holds ``held_rows``
    rows (a traced scalar in the lowerings, a number on the host): the one
    rule ``moe_ffn``, ``moe_ffn_grad`` and ``record_expert_load`` pick by."""
    return sum((held_rows > rows) * 1 for rows in ladder[:-1])


def _over_rungs(ladder, held_rows, part, *operands):
    """``part(rows, *operands)`` at the rung of ``ladder`` that holds
    ``held_rows``; no switch where the ladder has one rung.  A branch's
    results are pinned inside it: XLA's conditional code motion otherwise
    lifts the sum that ends every branch out of the switch and makes the
    ``[S * k, d]`` float32 rows before it a result of the switch (537 MB a
    layer at Trinity-Mini's sizes, in the TPU compiler's output for the
    step, PR 35)."""
    if len(ladder) == 1:
        return part(ladder[0], *operands)

    def pinned(rows):
        return lambda *a: jax.lax.optimization_barrier(part(rows, *a))
    return jax.lax.switch(held_rung(held_rows, ladder),
                          [pinned(rows) for rows in ladder], *operands)


def _front(a, rows):
    """``a`` [L, n] as the front of a ``rows``-long buffer whose other rows
    nobody writes (they are never read unmasked).  On a TPU a Pallas copy
    over ``a``'s row tiles into an output of the full length: the blocks it
    does not visit stay as they were allocated.  (``jax.lax.empty`` + an
    in-place update reads half a millisecond a layer faster in the op alone
    and is left for later: PERF.md section 7, row 28.)  Elsewhere zeros."""
    if a.shape[0] == rows:
        return a
    from ..device import on_tpu
    if not on_tpu():
        return jnp.pad(a, ((0, rows - a.shape[0]), (0, 0)))
    return _front_copy(*a.shape, rows, a.dtype, _GMM_TILING_HELD[0])(a)


@functools.lru_cache(maxsize=None)
def _front_copy(length, width, rows, dtype, tile):
    """``_front``'s Pallas copy in row tiles of ``tile`` (every shorter
    rung's divisor), jitted and kept by shape: a step holds some dozens of
    fronts of two to four shapes (six a layer and rung, the forward's again
    under recomputation), and each one traced and lowered for itself was
    1.5 s of set-up in JoyAI-LLM-Flash's cell (PERF.md section 6, PR 35)."""
    from jax.experimental import pallas as pl

    def copy(src, dst):
        dst[...] = src[...]
    block = pl.BlockSpec((tile, width), lambda i: (i, 0))
    return jax.jit(pl.pallas_call(
        copy, grid=(length // tile,), in_specs=[block], out_specs=block,
        out_shape=jax.ShapeDtypeStruct((rows, width), dtype),
        name="moe_front"))


def _gate_front(ladder, held_rows, g, u, dt, act="silu"):
    """``_gate`` over the rows of the rung that holds ``held_rows``, at the
    front of a buffer of the longest rung's length."""
    return _over_rungs(ladder, held_rows, lambda rows, g, u: _front(
        _gate(_head(g, rows), u[:rows], dt, act), ladder[-1]), g, u)


def _gate_backward(g, u, dh, dt, act="silu"):
    """``(dg, du)`` of ``_gate(g, u)`` given ``dh``: float32 arithmetic,
    stored in ``dt``.  ReLU: ``dg = dh u [g > 0]``, ``du = dh relu(g)``.
    Un-gated experts (``g`` None): ``(None, dh 2 relu(u))``, the square's
    slope."""
    f32 = jnp.float32
    if g is None:
        return None, (dh.astype(f32) * 2.0 * jax.nn.relu(u.astype(f32))
                      ).astype(dt)
    gf, uf, dhf = g.astype(f32), u.astype(f32), dh.astype(f32)
    if act == "relu":
        return (jnp.where(gf > 0, dhf * uf, 0.0).astype(dt),
                (dhf * jax.nn.relu(gf)).astype(dt))
    sig = jax.nn.sigmoid(gf)
    return ((dhf * uf * sig * (1.0 + gf * (1.0 - sig))).astype(dt),
            (dhf * gf * sig).astype(dt))


#: the longest source XLA's row gather reads fast, 16384 rows of 2048 bf16:
#: on a v5e it moves a slot in 14.5 ns from a source up to that long and in 41
#: ns from a longer one (PR 35), and the kernel that reads the held rows alone
#: beats the former only where next to no slot is held (PR 42:
#: tools/trinity_experts_sweep.py --unsorts, PERF.md section 6)
_SHORT_SOURCE_BYTES = 16384 * 2048 * 2


def _rows_unsort(S, k, d, ladder, dt):
    """What brings a share's held rows back to their tokens at the cost of
    those rows (``pallas/held_rows.py``'s ``held_rows_to_tokens``), where the
    two un-sorts go through it: on a TPU, at shapes the kernel takes, and
    where even the ladder's first rung is a longer source than XLA's gather
    reads fast (LFM2's one rung, SmallThinker's two).  None elsewhere: the
    un-sorts stay XLA's gather over every slot from the rung's rows, the
    lowering as it was (Trinity's and JoyAI's first rungs are its fast
    side).  A function of the shapes alone."""
    from ..device import on_tpu
    from ..pallas import held_rows
    if on_tpu() and held_rows.fits(S, k, d, ladder[-1], dt) and \
            ladder[0] * d * jnp.dtype(dt).itemsize > _SHORT_SOURCE_BYTES:
        return held_rows.held_rows_to_tokens
    return None


def _slot_major(k, unsort=None):
    """Whether XLA's gather brings the slots home slot-major
    (:func:`_sum_over_slots`): where the ``[S, k, d]`` float32 view would put
    a ``k`` that is no multiple of 8, the sublane tile, on the sublanes.
    Something the lowering reads off its input's shape, nothing else; the
    held-rows kernel (``unsort``) builds no view."""
    return unsort is None and k % 8 != 0


def _sum_over_slots(rows, place, S, k, weights=None, held=None, major=False):
    """``sum_j [held] w[t, j] * rows[place[t * k + j]]`` [S, d] in float32,
    the terms added in slot order ``j = 0 .. k - 1``: the two un-sorts' sum
    over a token's ``k`` slots, ``rows`` [L, d] gathered as stored and
    widened after.  ``place`` [S * k] is in slot-id order; ``weights``
    [S, k] float32 (the forward's ``top_p``) or none (the backward's gather
    back to tokens); ``held`` [S * k] bool masks the slots whose row nobody
    wrote (a column ``[S * k, 1]`` where the caller made it before it read
    the rows).

    One algorithm in one of two index orders.  ``major`` false: the gather
    reads ``place`` as it is, a token's ``k`` rows come adjacent, the view is
    ``[S, k, d]`` and the sum runs over axis 1.  On a TPU that view has ``k``
    on the sublanes of float32's (8, 128) tile: a bitcast where ``k`` is a
    multiple of 8, else a ``reshape`` that moves every row into an array
    padded to 8, and a sum that reads the padding (Nemotron-3-Nano's k 6 and
    Xing4.0's k 4: 18 and 7 ms a step, PERF.md section 6, PR 63).
    ``major`` true: the gather reads ``place`` slot by slot (``S * k``
    int32), rows ``j * S .. (j + 1) * S`` are slot ``j`` of every token, the
    view ``[k, S, d]`` is a bitcast whenever ``S`` is a multiple of 8, and
    the sum over the leading axis is ``k - 1`` adds of ``[S, d]`` slabs.  The
    same products and the same terms in the same order either way.

    The slot-major order of ``place`` and ``held`` is ``k`` strided slices
    of the flat array, never ``reshape(S, k).T``: the TPU compiler makes
    that transpose of a ``[4096, 4]`` int32 or bool array a copy into a
    (4, 128)-tiled ``[4, 4096]`` which a v5e never finishes (Xing4.0's step
    hung at its first run with either un-sort written so; ``[8192, 6]``
    tiles by (8, 128) and ran; the float32 weights' transpose runs at both:
    PERF.md section 6, PR 63)."""
    f32 = jnp.float32
    if major:
        def ordered(a):             # [S * k, ...] by slot id -> by (j, t)
            return jnp.concatenate([a[j::k] for j in range(k)])
        view, axis = (k, S, -1), 0
    else:
        def ordered(a):
            return a
        view, axis = (S, k, -1), 1
    ys = jnp.take(rows, ordered(place), axis=0)
    if held is not None:
        held = ordered(held)
        ys = jnp.where(held[:, None] if held.ndim == 1 else held,
                       ys.astype(f32), 0.0)
    ys = ys.reshape(view).astype(f32)
    if weights is not None:
        ys = ys * (weights.T if major else weights)[:, :, None]
    return jnp.sum(ys, axis=axis)


def _moe_dtype(ctx, x):
    amp = getattr(ctx, "amp", False) and x.dtype in (jnp.float32,
                                                     jnp.bfloat16)
    return jnp.bfloat16 if amp else x.dtype


def _moe_ffn(ctx, ins, attrs):
    """Dropless top-k mixture of gated experts (the OLMoE / Mixtral layer):
    ``Out = sum_{e in topk} p_e * Wd_e (act(Wg_e x) * Wu_e x)``, ``act`` SiLU
    or (``act="relu"``) ReLU; ``p = softmax(r Wr)`` over all experts (or
    ``sigmoid``, ``score_func``), ``r`` being ``x`` or the router's own
    input ``RouterX``, the ``k`` largest kept as they are or renormalised to
    sum 1 (``norm_topk_prob``; under softmax that is the softmax over the
    ``k`` kept logits).  No capacity: every token reaches its ``k`` experts
    whatever the load.

    Inputs: X [B,T,d], RouterW [d,E], GateW [E,d,f], UpW [E,d,f],
    DownW [E,f,d]; optional RouterX [B,T,d].  Outputs: Out [B,T,d];
    LbLoss [] = ``E * sum_e f_e P_e``
    (``f_e``: slots that chose ``e`` over tokens, so it sums to ``k``;
    ``P_e``: mean of ``p_e`` over tokens); ZLoss [] = mean over tokens of
    ``logsumexp(r Wr)^2``; ExpertLoad [E] int32 rows per expert;
    TopExperts [B,T,k] int32, each token's experts by falling ``p``;
    Saved: what ``moe_ffn_grad`` reuses, in this order: the sort order
    [S * k] int32, the sorted rows, the gate's projection (gated experts
    only), the up projection and the experts' output (each over the row
    buffer, in the rows' dtype), and the router's float32 logits [S, E], the
    chosen columns' slots [S, E] int32 (1 to k, 0 elsewhere), its choice
    [S, k] int32 (``TopExperts`` before its reshape), its weights [S, k]
    float32 and its count [E] int32 (``ExpertLoad``); the router's five not
    for a narrow ``k`` (:func:`_narrow`).

    Optional input SelectBias [E]; attributes ``score_func``, ``norm_eps``,
    ``route_scale`` and ``expert_offset`` as the module docstring says.  With
    fewer expert weights than router outputs (GateW [E_here, d, f]) the op
    computes the part of ``Out`` that experts ``expert_offset .. + E_here -
    1`` give; ExpertLoad and TopExperts stay over all ``E``.

    Four parts, each under its own scope for the device trace: ``router``
    (float32 at full precision, whatever AMP says), ``dispatch`` (stable sort
    of the ``S*k`` slot -> expert ids, one row gather), ``experts`` (three
    grouped matmuls whose group sizes are data), ``combine`` (un-sort, weight
    by ``p_e``, sum the ``k``: :func:`_sum_over_slots`, in slot order 0 ..
    ``k - 1`` in float32 whichever way the gather brings the slots home:
    a token's ``k`` rows adjacent under an ``[S, k, d]`` view where ``k`` is
    a multiple of 8, float32's sublane tile on a TPU, and else slot-major,
    slot ``j`` of every token in rows ``j * S .. (j + 1) * S`` under a
    ``[k, S, d]`` view, so that no view pads ``k`` to 8 and no ``reshape``
    moves every row; ``moe_ffn_grad``'s gather back to tokens, its
    ``dispatch``, likewise: ``_slot_major``, PR 63).  Every shape is static;
    no ``[S, E, C]`` tensor exists.  With a share of the experts the row
    buffer's length is
    one of ``held_ladder``'s static lengths, the shortest that holds the
    rows the router counted for the held experts (the held slots are sorted
    to the front, so every rung is dropless and gives what the longest
    gives); the row gather and the gate's pass are each lowered once a rung
    under a ``jax.lax.switch``; the sort, the router and the grouped matmuls
    once (these visit the held rows' tiles and no other, whatever the
    buffer's length), and so is the weighted sum where every rung is a long
    source on a TPU: ``pallas/held_rows.py`` reads the held rows alone
    (``_rows_unsort``; elsewhere it is a gather over every slot, a copy a
    rung in a switch of its own).  Under AMP the rows and the expert weights
    are bf16 with float32 accumulation.

    Without GateW (``act`` ``relu2``): un-gated experts, ``Out = sum p_e
    Wd_e relu(Wu_e x)^2``; two grouped matmuls under ``experts``, and Saved
    holds the one projection."""
    x, wr = X(ins, "X"), X(ins, "RouterW")
    wg, wu, wd = X(ins, "GateW"), X(ins, "UpW"), X(ins, "DownW")
    k = int(attrs["top_k"])
    B, T, d = x.shape
    E, n_held = wr.shape[-1], wu.shape[0]
    offset = int(attrs.get("expert_offset", 0) or 0)
    if offset < 0 or offset + n_held > E:
        raise ValueError(f"moe_ffn holds experts {offset}..{offset + n_held}"
                         f" of a router over {E}")
    S = B * T
    dt = _moe_dtype(ctx, x)
    act = _act_of(attrs, wg is not None)
    router_x = X(ins, "RouterX")
    ladder = () if n_held == E else held_ladder(S, k, n_held, E)
    unsort = _rows_unsort(S, k, d, ladder, dt) if ladder else None
    major = _slot_major(k, unsort)
    if not getattr(ctx, "is_abstract", False):
        MOE_LOWERINGS_CTR.inc(
            impl=_experts_impl(dt), experts=str(E), top_k=str(k),
            held=str(n_held),
            score_func=attrs.get("score_func", "softmax") or "softmax",
            ladder=".".join(map(str, ladder)), act=act,
            router_input="x" if router_x is None else "own",
            unsort="slots" if unsort is None else "rows",
            groups=f"{int(attrs.get('n_group', 1) or 1)}/"
                   f"{int(attrs.get('topk_group', 1) or 1)}",
            gated="0" if wg is None else "1",
            slot_sum="major" if major else "minor")
        if ladder:
            _TRACED_LADDERS[S * k, E, n_held] = ladder
    xt = x.reshape(S, d)

    with jax.named_scope("router"):
        (top_p, lb, z), (top_e, load, logits, rank) = _router_of(
            attrs, k, X(ins, "SelectBias"))(
                xt if router_x is None else router_x.reshape(S, d), wr)

    if n_held == E:
        with jax.named_scope("dispatch"):
            order, place = _sorted_slots(top_e.reshape(S * k))
            xs = jnp.take(xt.astype(dt), order // k, axis=0)

        with jax.named_scope("experts"):
            y, g, u = gated_experts(
                xs, wg, wu, wd, load, dt,
                gate=functools.partial(_gate, act=act))

        with jax.named_scope("combine"):
            out = _sum_over_slots(y, place, S, k, top_p, major=major)
    else:
        # the buffer's length at each stage is the rung's: the front of the
        # longest (Saved's shape), what lies behind not written.  The
        # grouped matmuls visit the held run lengths' rows and no other:
        # what lies behind them in the buffer is never multiplied (and
        # never written, so never read unmasked below)
        full = ladder[-1]
        with jax.named_scope("dispatch"):
            held, order, place = _held_slots(top_e, offset, n_held, k)
            load_here = jax.lax.dynamic_slice_in_dim(load, offset, n_held)
            held_rows = jnp.sum(load_here)
            xs = _over_rungs(
                ladder, held_rows, lambda rows, xt, order: _front(jnp.take(
                    xt.astype(dt), order[:rows] // k, axis=0), full),
                xt, order)

        with jax.named_scope("experts"):
            y, g, u = gated_experts(
                xs, wg, wu, wd, load_here, dt, tiling=_GMM_TILING_HELD,
                gate=lambda g, u, dt: _gate_front(ladder, held_rows, g, u,
                                                  dt, act))

        def weighted_sum(rows, y, place, held, top_p):
            return _sum_over_slots(y[:rows], jnp.minimum(place, rows - 1),
                                   S, k, top_p, held, major)

        with jax.named_scope("combine"):
            if unsort is None:
                out = _over_rungs(ladder, held_rows, weighted_sum,
                                  y, place, held, top_p)
            else:                  # whatever the rung: it reads the held rows
                out = unsort((y,), place, held, k, top_p)
    return {"Out": [out.astype(x.dtype).reshape(B, T, d)], "LbLoss": [lb],
            "ZLoss": [z], "ExpertLoad": [load],
            "TopExperts": [top_e.astype(jnp.int32).reshape(B, T, k)],
            "Saved": [a for a in (order, xs, g, u, y) if a is not None]
            + ([] if _narrow(k) else [logits, rank, top_e, top_p, load])}


def _moe_ffn_grad_maker(op, block, no_grad_set):
    def grads(names):
        return [grad_var_name(n) for n in names]
    slots = tuple(s for s in ("X", "RouterW", "GateW", "UpW", "DownW")
                  if op.input(s))
    g_inputs = {"X$" + s: op.input(s) for s in slots}
    if op.input("SelectBias"):      # a narrow k's backward routes again
        g_inputs["X$SelectBias"] = op.input("SelectBias")
    if op.input("RouterX"):
        slots += ("RouterX",)
        g_inputs["X$RouterX"] = op.input("RouterX")
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
    sort, no second gather of the rows, no second forward matmul, no second
    routing.  The router's weights are the saved ones and its backward is in
    closed form from the saved logits, slots and count (``_router_backward``
    under the scope ``router/backward``: ``d_logits`` and the two products
    with the router's weight and its input, ``RouterX`` where the forward had
    one: its cotangent then is ``IG$RouterX``'s and ``X`` gets the experts'
    alone) -- but for a narrow ``k`` (:func:`_narrow`), whose router is
    computed again for its vjp as until PR 64 and whose ``Saved`` holds
    nothing of the router; each grouped matmul is transposed by ``jax.vjp`` at its saved
    operands, whose unused primal XLA removes; the transposes of the two row
    gathers are gathers (every row of ``x`` is read exactly ``k`` times;
    with a share of the experts the gather back to tokens reads the held
    rows alone where the forward's weighted sum does, and then adds each
    row's two parts as it reads them).  An output whose gradient nobody
    produced counts as zero (a loss's: its term of the router's backward is
    left out)."""
    x, wr = X(ins, "X$X"), X(ins, "X$RouterW")
    weights = [X(ins, "X$" + s) for s in ("GateW", "UpW", "DownW")]
    d_out, d_lb, d_z = (X(ins, "OG$" + s) for s in ("Out", "LbLoss", "ZLoss"))
    k = int(attrs["top_k"])
    saved = list(ins["Saved"])
    if not _narrow(k):
        logits, rank, top_e, top_p, routed = saved[-5:]
        load = routed       # the held path's is its experts' part of it
        del saved[-5:]
    if weights[0] is None:      # un-gated experts: no gate branch was saved
        (order, xs, u, y), g = saved, None
    else:
        order, xs, g, u, y = saved
    B, T, d = x.shape
    S, R = B * T, B * T * k
    f32, dt = jnp.float32, xs.dtype
    xt = x.reshape(S, d)
    router_x = X(ins, "X$RouterX")
    act = _act_of(attrs, weights[0] is not None)
    E, n_held = wr.shape[-1], weights[1].shape[0]
    mm = _grouped_matmul(dt, tiling=None if n_held == E else _GMM_TILING_HELD)
    offset = int(attrs.get("expert_offset", 0) or 0)
    router_in = xt if router_x is None else router_x.reshape(S, d)

    if _narrow(k):          # the router again, for its vjp, as until PR 64
        with jax.named_scope("router"):
            (top_p, _, _), router_vjp, (top_e, load, *_) = jax.vjp(
                _router_of(attrs, k, X(ins, "X$SelectBias")), router_in, wr,
                has_aux=True)

    def transposed(rows, w, cot):
        return jax.vjp(lambda a, b: mm(a, b, load), rows, w)[1](cot)

    wg, wu, wd = weights
    if n_held == E:
        with jax.named_scope("combine"):
            place = _inverse_permutation(order)
            d_rows = jnp.zeros((R, d), f32) if d_out is None else jnp.take(
                d_out.reshape(S, d), order // k, axis=0).astype(f32)
            d_top_p = jnp.take(jnp.sum(d_rows * y.astype(f32), axis=-1),
                               place).reshape(S, k)
            dy = (d_rows * jnp.take(top_p.reshape(R), order)[:, None]
                  ).astype(dt)

        with jax.named_scope("experts"):
            dh, d_wd = transposed(_gate(g, u, dt, act), wd, dy)
            if act == "silu":
                # _gate_backward's arithmetic in the order it has been
                # lowered in since PR 27 (dg, its product, then du), so that
                # the SiLU lowering stays what it was, to the byte
                gf, uf, dhf = g.astype(f32), u.astype(f32), dh.astype(f32)
                sig = jax.nn.sigmoid(gf)
                dxs_g, d_wg = transposed(
                    xs, wg,
                    (dhf * uf * sig * (1.0 + gf * (1.0 - sig))).astype(dt))
                dxs_u, d_wu = transposed(xs, wu, (dhf * gf * sig).astype(dt))
            else:
                dg, du = _gate_backward(g, u, dh, dt, act)
                dxs_g, d_wg = (0, None) if g is None else \
                    transposed(xs, wg, dg)
                dxs_u, d_wu = transposed(xs, wu, du)

        with jax.named_scope("dispatch"):
            dx = _sum_over_slots(dxs_g + dxs_u, place, S, k,
                                 major=_slot_major(k))
    else:
        # the buffer's first rows are the held slots; what lies behind them
        # was never written and is masked wherever it is read.  The rung is
        # the forward's: the same rule over the same count
        ladder = held_ladder(S, k, n_held, E)
        full = xs.shape[0]
        unsort = _rows_unsort(S, k, d, ladder, dt)

        def cot_rows(rows, order, slot_held, d_out):
            row_held = jnp.take(slot_held, order)[:, None]
            d_rows = jnp.zeros((rows, d), f32) if d_out is None else \
                jnp.where(row_held, jnp.take(d_out.reshape(S, d), order // k,
                                             axis=0).astype(f32), 0.0)
            return row_held, d_rows

        def weights_cot(rows, row_held, d_rows, place, slot_held, y):
            return jnp.where(slot_held, jnp.take(jnp.sum(
                d_rows * jnp.where(row_held, y.astype(f32), 0.0), axis=-1),
                jnp.minimum(place, rows - 1)), 0.0).reshape(S, k)

        def rows_cot(d_rows, order, top_p):
            dy = (d_rows * jnp.take(top_p.reshape(R), order)[:, None]
                  ).astype(dt)
            return _front(dy, full)

        def cotangents(rows, order, place, slot_held, top_p, d_out, y):
            order, y = order[:rows], y[:rows]
            row_held, d_rows = cot_rows(rows, order, slot_held, d_out)
            return weights_cot(rows, row_held, d_rows, place, slot_held, y), \
                rows_cot(d_rows, order, top_p)

        def weights_cot_alone(rows, order, place, slot_held, d_out, y):
            row_held, d_rows = cot_rows(rows, order[:rows], slot_held, d_out)
            return weights_cot(rows, row_held, d_rows, place, slot_held,
                               y[:rows])

        def rows_cot_alone(rows, order, slot_held, top_p, d_out):
            _, d_rows = cot_rows(rows, order[:rows], slot_held, d_out)
            return rows_cot(d_rows, order[:rows], top_p)

        with jax.named_scope("combine"):
            slot_held = (top_e.reshape(R) >= offset) & \
                (top_e.reshape(R) < offset + n_held)
            place = _inverse_permutation(order)
            load = jax.lax.dynamic_slice_in_dim(load, offset, n_held)
            held_rows = jnp.sum(load)
            if unsort is None:
                d_top_p, dy = _over_rungs(ladder, held_rows, cotangents,
                                          order, place, slot_held, top_p,
                                          d_out, y)
            else:
                # the weights' cotangent in a switch of its own and dy's
                # behind it, so that y's last reader is done before dy's
                # full-length buffer exists: in one switch the two buffers
                # stood beside every layer's Saved at the step's peak, which
                # SmallThinker's step showed once its un-sorts' temporaries
                # no longer made XLA rematerialise (15.04 GB for the parent's
                # 14.59: PERF.md section 6, PR 42).  One rung: one
                # computation, as it was
                d_top_p = _over_rungs(ladder, held_rows, weights_cot_alone,
                                      order, place, slot_held, d_out, y)
                if len(ladder) > 1:
                    d_top_p, order, d_out = jax.lax.optimization_barrier(
                        (d_top_p, order, d_out))
                dy = _over_rungs(ladder, held_rows, rows_cot_alone, order,
                                 slot_held, top_p, d_out)

        with jax.named_scope("experts"):
            # the gate's output again, and not the forward's kept: XLA would
            # merge this switch with the forward's and hold 134 MB a layer
            # from forward to backward that it cannot rematerialise (a
            # switch's result), at the price of rematerialising elsewhere
            h = _gate_front(ladder, held_rows,
                            *jax.lax.optimization_barrier((g, u)), dt, act)
            dh, d_wd = transposed(h, wd, dy)
            if unsort is not None:
                # both readers of dy before the gate's backward, and below
                # the weights' gradients (the last readers of xs) before the
                # rows': with the split above, this op's full-length
                # buffers no longer stand beside every layer's Saved at the
                # step's peak (SmallThinker's step 14.41 GB for the parent's
                # 14.59, the compiler's count: PERF.md section 6, PR 42)
                dh, d_wd = jax.lax.optimization_barrier((dh, d_wd))
            dg, du = _over_rungs(
                ladder, held_rows, lambda rows, g, u, dh: tuple(
                    None if a is None else _front(a, full)
                    for a in _gate_backward(_head(g, rows), u[:rows],
                                            dh[:rows], dt, act)), g, u, dh)
            if g is None:           # un-gated: the one projection's transpose
                dxs_u, d_wu = transposed(xs, wu, du)
                dxs, d_wg = (dxs_u,), None
            elif unsort is None:
                dxs_g, d_wg = transposed(xs, wg, dg)
                dxs_u, d_wu = transposed(xs, wu, du)
                dxs = (dxs_g, dxs_u)
            else:
                d_wg, d_wu, dg, du = jax.lax.optimization_barrier(
                    (transposed(xs, wg, dg)[1], transposed(xs, wu, du)[1],
                     dg, du))
                dxs = tuple(transposed(xs, w, c)[0]
                            for w, c in ((wg, dg), (wu, du)))

        def back_to_tokens(rows, dxs, place, slot_held):
            # the mask's column before the rows are read: the order the
            # k = 8 shares' backward has been lowered in since PR 35
            return _sum_over_slots(held=slot_held[:, None], rows=dxs[:rows],
                                   place=jnp.minimum(place, rows - 1), S=S,
                                   k=k, major=_slot_major(k))

        with jax.named_scope("dispatch"):
            if unsort is None:
                # the two parts are added over the whole buffer, outside the
                # switch: with the sum inside it the TPU compiler scheduled
                # the last block's step so that a forward matmul it
                # rematerialised read its weight behind that weight's AdamW
                # update (one gradient leaf a third off;
                # tools/joyai_step_aot.py's reads_after_update, PERF.md
                # section 6, PR 35)
                dx = _over_rungs(ladder, held_rows, back_to_tokens,
                                 functools.reduce(jnp.add, dxs), place,
                                 slot_held)
            else:
                # each held row's two parts added as it is read: no pass
                # over the whole buffer, and no switch
                dx = unsort(dxs, place, slot_held, k)

    if _narrow(k):
        with jax.named_scope("router"):
            zero = jnp.zeros((), f32)
            dx_r, d_wr = router_vjp((d_top_p, zero if d_lb is None else d_lb,
                                     zero if d_z is None else d_z))
    else:
        with jax.named_scope("router"), jax.named_scope("backward"):
            dx_r, d_wr = _router_backward(
                router_in, wr, logits, rank, routed, (d_top_p, d_lb, d_z),
                **_weights_of(attrs))
    out = {"IG$RouterW": [d_wr.astype(wr.dtype)], "IG$UpW": [d_wu],
           "IG$DownW": [d_wd]}
    if d_wg is not None:
        out["IG$GateW"] = [d_wg]
    if router_x is None:
        out["IG$X"] = [(dx + dx_r).astype(x.dtype).reshape(B, T, d)]
    else:
        out["IG$X"] = [dx.astype(x.dtype).reshape(B, T, d)]
        out["IG$RouterX"] = [dx_r.astype(router_x.dtype).reshape(B, T, d)]
    return out
