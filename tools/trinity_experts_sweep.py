"""Times the grouped matmuls of a chip's share of the experts alone, at the
Trinity-Mini cell's shapes on the chip: a static buffer of 65536 rows of which
the first few thousand are routed to the 16 held experts (``moe_ffn`` with
``expert_offset``), bf16, megablox ``gmm`` (forward), ``gmm`` with the weight
transposed (the rows' gradient) and ``tgmm`` (the weights' gradient), for the
gate/up shape (2048 -> 1024) and the down shape (1024 -> 2048), over tile
sizes and over loads: none, a remnant (40 rows an expert), even routing
(8192 rows in all) and a heavy layer (12520).

What the cell's run-to-run spread follows is the difference between the loads
(PERF.md section 6, PR 32): the rows the router sends here change during a
run, at a pace the seed sets.

    chiprun -- python3 tools/trinity_experts_sweep.py

One JSON line per kernel, shape and tiling (milliseconds by load; ``error``
where the compiler refuses the tiling), also in
``chiprun_out/trinity_experts_sweep.jsonl``.

With ``--lengths`` it sweeps the row buffer's length instead (PR 35: the
table ``ops/moe_ops.py:held_ladder`` was chosen from), at Trinity-Mini's
share (16 of 128 experts of width 1024) and JoyAI-LLM-Flash's (16 of 256 of
width 768), 8192 tokens of 2048, 8 experts a token.  Per length, in
milliseconds: the held path's four row movements alone as the lowering
writes them (``gather_rows``: the forward's row gather; ``unsort_sum``:
un-sort, mask, weighted sum; ``cot_gather``: the backward's cotangent gather
with the weights' gradient and ``dy``; ``gather_back``: the gather back to
tokens), ``empty_gmm`` (the nine grouped-matmul calls of a layer's forward
and backward with no row routed here, at that many buffer rows),
``fronts`` (the six copies that put a rung's rows at the front of a
full-length buffer: the rows, the gate's output twice, ``dy`` and the gate's
two cotangents) and ``whole``: ``moe_ffn`` +
``moe_ffn_grad`` through the lowerings themselves, the ladder forced to
``(length, full)``, under a routing that sends the held experts their even
share (``even``) and one that sends them nothing (``none``).

    chiprun -- python3 tools/trinity_experts_sweep.py \\
        --lengths 4096,8192,16384,32768,65536
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TILINGS = ((512, 1024, 1024), (256, 1024, 1024), (128, 1024, 1024),
           (256, 2048, 1024), (128, 2048, 1024), (256, 1024, 2048),
           (128, 1024, 2048), (256, 2048, 2048), (128, 2048, 2048),
           (256, 512, 1024), (384, 1024, 1024))


def loads(n_held, seed=0):
    import numpy as np
    rng = np.random.default_rng(seed)
    even = rng.multinomial(8192, [1.0 / n_held] * n_held)
    heavy = rng.multinomial(12520, rng.dirichlet([4.0] * n_held))
    return {"none": np.zeros(n_held, np.int32),
            "remnant": np.full(n_held, 40, np.int32),
            "even": even.astype(np.int32), "heavy": heavy.astype(np.int32)}


#: (name, router outputs, expert width) of the two shares that run the held
#: path; tokens, model width, experts a token and held experts are shared
SHARES = (("trinity_mini", 128, 1024), ("joyai_llm_flash", 256, 768))


def sweep_lengths(args, timed, emit):
    """The row buffer's length against time: the pieces alone and the two
    lowerings whole (module docstring)."""
    import types
    import importlib
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops import moe_ops
    mb = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")
    interpret = jax.default_backend() != "tpu"
    S, d, k, G = (64, 128, 4, 4) if interpret else (8192, 2048, 8, args.held)
    shares = (("toy", 32, 128),) if interpret else SHARES
    full = S * min(k, G)
    f32, bf = jnp.float32, jnp.bfloat16
    ctx = types.SimpleNamespace(amp=False, is_abstract=True)
    tiling = (128, 128, 128) if interpret else moe_ops._GMM_TILING_HELD
    rng = np.random.default_rng(0)
    for name, E, f in shares:
        ks = jax.random.split(jax.random.key(1), 8)
        xt = jax.random.normal(ks[0], (1, S, d), bf)
        d_out = jax.random.normal(ks[1], (1, S, d), bf)
        wr = jax.random.normal(ks[2], (d, E), f32) * 0.02
        wg, wu = (jax.random.normal(kk, (G, d, f), f32) * 0.02
                  for kk in ks[3:5])
        wd = jax.random.normal(ks[5], (G, f, d), f32) * 0.02
        attrs = {"top_k": k, "score_func": "sigmoid", "norm_topk_prob": True,
                 "norm_eps": 1e-20, "route_scale": 2.5, "expert_offset": 0}
        # the selection bias steers the load: nothing (the fresh router's
        # near-even share) or the held experts out of every token's choice
        biases = {"even": jnp.zeros((E,), f32),
                  "none": jnp.zeros((E,), f32).at[:G].set(-10.0)}

        def step(bias, xt, d_out, wr, wg, wu, wd):
            ins = {"X": [xt], "RouterW": [wr], "GateW": [wg], "UpW": [wu],
                   "DownW": [wd], "SelectBias": [bias]}
            fwd = moe_ops._moe_ffn(ctx, ins, attrs)
            g_ins = {"X$" + n: v for n, v in ins.items()}
            g_ins.update({"Saved": fwd["Saved"], "OG$Out": [d_out]})
            bwd = moe_ops._moe_ffn_grad(ctx, g_ins, attrs)
            return fwd["Out"][0], fwd["ExpertLoad"][0], \
                [v[0] for v in bwd.values()]

        # one routing's slot tables, for the pieces
        top_e = jnp.asarray(np.stack([rng.choice(E, k, replace=False)
                                      for _ in range(S)]).astype(np.int32))
        held, order, place = jax.jit(
            lambda t: moe_ops._held_slots(t, 0, G, k))(top_e)
        top_p = jax.random.uniform(ks[6], (S, k), f32)
        zero = jnp.zeros((G,), jnp.int32)
        for rows in args.lengths:
            if rows > full:
                continue
            rec = {"share": name, "rows": rows}
            y = jax.random.normal(ks[7], (rows, d), bf)
            h = jax.random.normal(ks[7], (rows, f), bf)

            def gather_rows(xt, order):
                return jnp.take(xt.reshape(S, d), order[:rows] // k, axis=0)

            def unsort_sum(y, place, held, top_p):
                ys = jnp.take(y, jnp.minimum(place, rows - 1), axis=0)
                ys = jnp.where(held[:, None], ys.astype(f32), 0.0)
                return jnp.sum(ys.reshape(S, k, d) * top_p[:, :, None],
                               axis=1)

            def cot_gather(d_out, y, order, place, held, top_p):
                o, clipped = order[:rows], jnp.minimum(place, rows - 1)
                row_held = jnp.take(held, o)[:, None]
                d_rows = jnp.where(row_held, jnp.take(
                    d_out.reshape(S, d), o // k, axis=0).astype(f32), 0.0)
                d_top_p = jnp.where(held, jnp.take(jnp.sum(
                    d_rows * jnp.where(row_held, y.astype(f32), 0.0),
                    axis=-1), clipped), 0.0)
                return d_top_p, (d_rows * jnp.take(
                    top_p.reshape(S * k), o)[:, None]).astype(bf)

            def gather_back(dxs, place, held):
                return jnp.where(held[:, None], jnp.take(
                    dxs[:rows], jnp.minimum(place, rows - 1),
                    axis=0).astype(f32), 0.0).reshape(S, k, d).sum(axis=1)

            def empty_gmm(y, h, wg, wu, wd, load):
                kw = dict(interpret=interpret)
                wgb, wub, wdb = wg.astype(bf), wu.astype(bf), wd.astype(bf)
                outs = [mb.gmm(y, wgb, load, bf, tiling, **kw),
                        mb.gmm(y, wub, load, bf, tiling, **kw),
                        mb.gmm(h, wdb, load, bf, tiling, **kw)]
                outs += [mb.gmm(c, w, load, bf, tiling, transpose_rhs=True,
                                **kw)
                         for c, w in ((h, wgb), (h, wub), (y, wdb))]
                outs += [mb.tgmm(a.swapaxes(0, 1), c, load, f32, tiling,
                                 num_actual_groups=G, **kw)
                         for a, c in ((y, h), (y, h), (h, y))]
                return outs

            def fronts(y, h):
                # a sum of each buffer's first rows: the copies run, and no
                # full-length result has to leave the jit
                return [moe_ops._front(a, full)[:8].astype(f32).sum()
                        for a in (y, h, h * 2, y * 2, h * 3, h * 4)]

            pieces = {
                "gather_rows": (gather_rows, xt, order),
                "unsort_sum": (unsort_sum, y, place, held, top_p),
                "cot_gather": (cot_gather, d_out, y, order, place, held,
                               top_p),
                "gather_back": (gather_back, y, place, held),
                "empty_gmm": (empty_gmm, y, h, wg, wu, wd, zero),
                "fronts": (fronts, y, h)}
            for piece, (fn, *a) in pieces.items():
                rec[piece] = timed(jax.jit(fn), *a)
            ladder = (rows, full) if rows < full else (full,)
            moe_ops.held_ladder, kept = (lambda *a: ladder), \
                moe_ops.held_ladder
            try:
                # a fresh function a ladder: jit's cache is by function
                whole = jax.jit(lambda *a: step(*a))
                for load, bias in biases.items():
                    here = int(whole(bias, xt, d_out, wr, wg, wu, wd)[1][:G]
                               .sum())
                    if here > rows:       # the switch would take the full
                        rec["whole_" + load] = None
                        continue
                    rec["held_rows_" + load] = here
                    rec["whole_" + load] = timed(whole, bias, xt, d_out, wr,
                                                 wg, wu, wd)
            finally:
                moe_ops.held_ladder = kept
            emit(rec)


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--rows", type=int, default=65536)
    ap.add_argument("--held", type=int, default=16)
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--lengths", type=lambda s: [int(v) for v in
                                                 s.split(",")],
                    help="sweep the row buffer's length (comma-separated "
                    "rows) instead of the tiles")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import importlib
    mb = importlib.import_module(      # the package exports a function `gmm`
        "jax.experimental.pallas.ops.tpu.megablox.gmm")
    interpret = jax.default_backend() != "tpu"
    shapes = (("gate_up", (2048, 1024)), ("down", (1024, 2048)))
    tilings = TILINGS
    if interpret:                 # a rehearsal of the path: no time is real
        args.rows, args.calls = 512, 1
        shapes, tilings = (("gate_up", (256, 128)),), ((128, 128, 128),)
    bf = jnp.bfloat16
    ks = jax.random.split(jax.random.key(0), 4)
    G = args.held
    by_load = {k: jnp.asarray(v // (64 if interpret else 1))
               for k, v in loads(G).items()}

    def timed(fn, *a):
        jax.block_until_ready(fn(*a))
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(args.calls):
                out = fn(*a)
            jax.block_until_ready(out)
            best = min(best, (time.perf_counter() - t0) / args.calls)
        return round(best * 1e3, 4)

    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    out = open(os.path.join(ROOT, "chiprun_out",
                            "trinity_experts_sweep.jsonl"), "a")

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()

    if args.lengths:
        if interpret:
            args.calls, args.lengths = 1, [128, 256]
        sweep_lengths(args, timed, emit)
        return 0
    for shape, (k, n) in shapes:
        x = jax.random.normal(ks[0], (args.rows, k), bf)
        dy = jax.random.normal(ks[1], (args.rows, n), bf)
        w = (jax.random.normal(ks[2], (G, k, n), jnp.float32) * 0.02
             ).astype(bf)
        for tiling in tilings:
            kernels = {
                "gmm": jax.jit(lambda load, x, dy, w, t=tiling: mb.gmm(
                    x, w, load, bf, t, interpret=interpret)),
                "gmm_t": jax.jit(lambda load, x, dy, w, t=tiling: mb.gmm(
                    dy, w, load, bf, t, transpose_rhs=True,
                    interpret=interpret)),
                "tgmm": jax.jit(lambda load, x, dy, w, t=tiling: mb.tgmm(
                    x.swapaxes(0, 1), dy, load, jnp.float32, t,
                    num_actual_groups=G, interpret=interpret))}
            for kind, fn in kernels.items():
                rec = {"kernel": kind, "shape": shape, "tiling": tiling}
                try:
                    rec["ms"] = {name: timed(fn, load, x, dy, w)
                                 for name, load in by_load.items()}
                except Exception as e:     # the compiler refused the tiling
                    rec["error"] = (repr(e).splitlines() or ["?"])[0][:300]
                emit(rec)
    return 0


if __name__ == "__main__":
    sys.exit(main())
