"""Times ``moe_ffn``'s router alone at the listed cells' shapes, on the chip:
the forward (``moe_ops._router``) with the choice by ``k`` passes of arg-max
and by ``jax.lax.top_k`` (a sort of the row on a TPU), the choice alone either
way, the float32 product at ``highest`` alone, and the backward from the saved
logits, slots and count (``_router_backward``) against ``jax.vjp`` of the
whole forward, which is what ``moe_ffn_grad`` ran until PR 64.  Says whether
both choices gave the same experts in the same order and how far the two
backwards lie apart.  What "no shape keeps the sort for speed" was read from;
``narrow`` says which path the op itself takes at that ``top_k``
(``moe_ops._narrow``), and both are timed whatever it says.  A call under 0.4
ms reads 0.4: the host's dispatch is the floor.

    chiprun -- python3 tools/router_probe.py [--cells ling,olmoe] [--calls 30]

One JSON line a cell, ms a call; ``--cpu`` runs tiny shapes here, for the
tool's own test.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: cell -> (S, d, E, k, score_func, select bias, (n_group, topk_group),
#: norm_topk_prob, norm_eps, route_scale): the routers of BENCHMARK.json's
#: moe_ffn cells (benchmark/configs/*.json, router_outputs)
CELLS = {
    "ling": (8192, 2560, 512, 8, "sigmoid", True, (8, 4), True, 1e-20, 2.5),
    "solar": (8192, 4096, 320, 8, "sigmoid", True, (1, 1), True, 1e-20, 1.0),
    "joyai": (8192, 2048, 256, 8, "sigmoid", True, (1, 1), True, 1e-20, 2.5),
    "trinity": (8192, 2048, 128, 8, "sigmoid", True, (1, 1), True, 1e-20,
                2.826),
    "nemotron3": (8192, 2688, 128, 6, "sigmoid", True, (1, 1), True, 1e-20,
                  2.5),
    "sdar": (16384, 2048, 128, 8, "softmax", False, (1, 1), True, 0.0, 1.0),
    "olmoe": (16384, 2048, 64, 8, "softmax", False, (1, 1), False, 0.0, 1.0),
    "smallthinker": (16384, 2560, 64, 6, "softmax", False, (1, 1), True, 0.0,
                     1.0),
    "xing4": (4096, 3584, 64, 4, "sigmoid", True, (1, 1), True, 1e-20, 2.0),
    "lfm2": (16384, 2048, 32, 4, "sigmoid", True, (1, 1), True, 1e-20, 1.0),
}


def timed(fn, args, calls):
    import jax
    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / calls * 1e3


def pieces(name, calls, shrink, stack=16):
    """The choice's parts alone, ms each: ``stack`` different score arrays a
    call under ``lax.map``, so that the host's dispatch is a sixteenth of the
    reading's floor.  The group mask, the ``k`` passes, the sort, the gather
    of the chosen scores, the same by compare-and-select row sums
    (as ``moe_ops._router`` fetches them) and the count of the rows an
    expert."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import moe_ops
    S, d, E, k, score, biased, groups, renorm, eps, scale = CELLS[name]
    if shrink:
        S = 64
    f32 = jnp.float32
    ps = jax.random.uniform(jax.random.key(3), (stack, S, E), f32)
    top_e = jax.jit(jax.vmap(lambda p: moe_ops._top_k(p, k)[1]))(ps)
    rec = {"cell": name, "S": S, "E": E, "k": k, "groups": groups}

    def each(fn, *stacks):
        run = jax.jit(lambda *a: jax.lax.map(lambda xs: fn(*xs), a))
        return timed(run, stacks, calls) / stack

    if groups[0] > 1:
        rec["group_mask_ms"] = each(
            lambda p: moe_ops._group_mask(p, *groups, moe_ops._top_k), ps)
    rec["passes_ms"] = each(lambda p: moe_ops._top_k(p, k), ps)
    rec["sort_ms"] = each(lambda p: jax.lax.top_k(p, k), ps)
    rec["gather_ms"] = each(
        lambda p, e: jnp.take_along_axis(p, e, axis=-1), ps, top_e)
    cols = jnp.arange(E, dtype=jnp.int32)
    rec["select_sums_ms"] = each(lambda p, e: jnp.concatenate(
        [jnp.sum(jnp.where(cols == e[:, j:j + 1], p, 0.0), axis=-1,
                 keepdims=True) for j in range(k)], axis=-1), ps, top_e)
    rec["load_ms"] = each(
        lambda e: moe_ops._expert_load(e.reshape(S * k), E), top_e)
    return rec


def probe(name, calls, shrink):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops import moe_ops
    S, d, E, k, score, biased, groups, renorm, eps, scale = CELLS[name]
    if shrink:
        S, d = 64, 32
    f32 = jnp.float32
    ks = jax.random.split(jax.random.key(7), 5)
    xt = jax.random.normal(ks[0], (S, d), jnp.bfloat16)
    wr = jax.random.normal(ks[1], (d, E), f32) * 0.02
    bias = jax.random.normal(ks[2], (E,), f32) * 0.01 if biased else None
    d_top_p = jax.random.normal(ks[3], (S, k), f32)
    d_lb, d_z = jnp.asarray(0.01, f32), jnp.asarray(0.001, f32)
    weights = dict(renorm=renorm, score_func=score, norm_eps=eps, scale=scale)
    kw = dict(weights, bias=bias, n_group=groups[0], topk_group=groups[1])

    def under(passes, fn, *args, **kwargs):
        """``fn`` traced with the choice by passes or by the sort, whatever
        ``moe_ops._narrow`` says of this ``k``."""
        moe_ops._narrow = lambda k: not passes
        try:
            return fn(*args, **kwargs)
        finally:
            moe_ops._narrow = narrow

    def forward_by(passes):
        # a function of its own either way: jit keeps one trace a function
        return lambda xt, wr: under(passes, moe_ops._router, xt, wr, k, **kw)

    def by_vjp(xt, wr, d_top_p):
        _, pull, _ = jax.vjp(forward_by(False), xt, wr, has_aux=True)
        return pull((d_top_p, d_lb, d_z))

    def by_hand(xt, wr, logits, rank, load, d_top_p):
        return moe_ops._router_backward(
            xt, wr, logits, rank, load, (d_top_p, d_lb, d_z), **weights)

    narrow = moe_ops._narrow
    rec = {"cell": name, "S": S, "d": d, "E": E, "k": k, "groups": groups,
           "narrow": bool(narrow(k))}
    rec["product_ms"] = timed(jax.jit(lambda xt, wr: jnp.dot(
        xt.astype(f32), wr, precision=jax.lax.Precision.HIGHEST)), (xt, wr),
        calls)
    def choice_by(passes):
        top_k = moe_ops._top_k if passes else jax.lax.top_k

        def choice(sel):
            if groups[0] > 1:
                sel = jnp.where(moe_ops._group_mask(sel, *groups, top_k),
                                sel, -jnp.inf)
            return top_k(sel, k)
        return choice

    sel = jax.random.uniform(ks[4], (S, E), f32)
    outs = {}
    for how, passes in (("sort", False), ("passes", True)):
        rec[f"choice_{how}_ms"] = timed(jax.jit(choice_by(passes)), (sel,),
                                        calls)
        fwd = jax.jit(forward_by(passes))
        outs[how] = fwd(xt, wr)
        rec[f"forward_{how}_ms"] = timed(fwd, (xt, wr), calls)
    vjp = jax.jit(by_vjp)
    outs["vjp"] = vjp(xt, wr, d_top_p)
    rec["backward_vjp_ms"] = timed(vjp, (xt, wr, d_top_p), calls)
    (_, (top_e, load, logits, rank)) = outs["passes"]
    rec["same_choice"] = bool(np.array_equal(np.asarray(outs["sort"][1][0]),
                                             np.asarray(top_e)))
    hand = jax.jit(by_hand)
    args = (xt, wr, logits, rank, load, d_top_p)
    grads = hand(*args)
    rec["backward_by_hand_ms"] = timed(hand, args, calls)
    for what, got, want in zip(("dx", "d_wr"), grads, outs["vjp"]):
        got, want = (np.asarray(a, np.float64) for a in (got, want))
        rec[f"{what}_apart"] = float(np.abs(got - want).max()
                                     / max(np.abs(want).max(), 1e-30))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", default=",".join(CELLS))
    ap.add_argument("--calls", type=int, default=30)
    ap.add_argument("--pieces", action="store_true",
                    help="the choice's parts alone, dispatch amortised")
    ap.add_argument("--cpu", action="store_true",
                    help="64 rows of 32, here: the tool's own test")
    args = ap.parse_args()
    import jax
    if not args.cpu and jax.default_backend() != "tpu":
        sys.exit("router_probe: no TPU (the times are the chip's; --cpu "
                 "runs a toy size)")
    lines = []
    for name in args.cells.split(","):
        lines.append(json.dumps((pieces if args.pieces else probe)(
            name, args.calls, args.cpu)))
        print(lines[-1], flush=True)
    if not args.cpu:            # the chip's times alone are kept
        os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
        with open(os.path.join(ROOT, "chiprun_out", "router_probe.jsonl"),
                  "a") as log:
            log.write("\n".join(lines) + "\n")


if __name__ == "__main__":
    main()
