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
table ``ops/moe_ops.py:held_ladder`` was chosen from), at the four shares
that run the held path (``SHARES``: Trinity-Mini's 16 of 128 experts of width
1024 and JoyAI-LLM-Flash's 16 of 256 of width 768, 8192 tokens of 2048, 8
experts a token; LFM2's 8 of 32 of width 1792, 16384 tokens of 2048, 4 a
token; SmallThinker's 8 of 64 of width 768, 16384 tokens of 2560, 6 a token;
``--shares`` picks among them).  Per length, in
milliseconds: the held path's four row movements alone as XLA
writes them (``gather_rows``: the forward's row gather; ``unsort_sum``:
un-sort, mask, weighted sum; ``cot_gather``: the backward's cotangent gather
with the weights' gradient and ``dy``; ``gather_back``: the gather back to
tokens), the two un-sorts by the held row (PR 42, ``pallas/held_rows.py``:
``rows_sum`` beside ``unsort_sum``, ``rows_back`` beside ``gather_back``, which
do not depend on the length), ``empty_gmm`` (the nine grouped-matmul calls of a layer's forward
and backward with no row routed here, at that many buffer rows),
``fronts`` (the six copies that put a rung's rows at the front of a
full-length buffer: the rows, the gate's output twice, ``dy`` and the gate's
two cotangents) and ``whole``: ``moe_ffn`` +
``moe_ffn_grad`` through the lowerings themselves, the ladder forced to
``(length, full)``, under a routing that sends the held experts their even
share (``even``) and one that sends them nothing (``none``).

    chiprun -- python3 tools/trinity_experts_sweep.py \\
        --lengths 4096,8192,16384,32768,65536

With ``--unsorts`` it times the two un-sorts alone, XLA's by the slot against
the kernel's by the held row (PR 42), per share and load: no slot held, a
fresh router's even share, one and a half times it, and every slot of every
token held.  ``slots_*``: XLA's gather, mask and sum at the rung of
``held_ladder`` that load selects (``slots_back`` with the whole-buffer
``dxs_g + dxs_u`` before it, as the lowering had it), a token's slots
adjacent and summed over an ``[S, k, d]`` view; ``major_*`` (PR 63): the same
through ``moe_ops._sum_over_slots`` slot-major, the view ``[k, S, d]``, which
the lowerings take where ``k`` is no multiple of 8; ``rows_*``: the kernel,
where it takes the shapes;
``same_*`` (the kernel against ``slots_*``) and ``same_major_*``
(``major_*`` against ``slots_*``): whether the two gave the same float32 to
the bit on this device
(where not, ``ulps_*``: the largest distance in units in the last place of
the sum of the terms' magnitudes, and ``differ_*``: the share of the elements
that differ).
One more row at OLMoE's sizes (131072 slots of 2048, every expert held),
and since PR 63 four a share at Nemotron-3-Nano's (8192 x 6 slots of 2688)
and Xing4.0's (4096 x 4 of 3584): the two whose lowerings sum slot-major;
Trinity's rows are the record of what k = 8 would gain or lose by it.

    chiprun -- python3 tools/trinity_experts_sweep.py --unsorts
"""

import argparse
import functools
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


#: (name, router outputs, expert width, tokens, model width, experts a token,
#: held experts) of the four shares that run the held path
SHARES = (("trinity_mini", 128, 1024, 8192, 2048, 8, 16),
          ("joyai_llm_flash", 256, 768, 8192, 2048, 8, 16),
          ("lfm2_8b_a1b", 32, 1792, 16384, 2048, 4, 8),
          ("smallthinker_21b_a3b", 64, 768, 16384, 2560, 6, 8))
#: OLMoE's layer, every expert held: the un-sorts timed only (--unsorts)
ALL_HELD = ("olmoe_1b_7b", 64, 1024, 16384, 2048, 8, 64)
#: the two shares whose experts a token are no multiple of 8 on XLA's gather
#: (--unsorts, PR 63): Nemotron-3-Nano's 8 of 128 (ladder 6144 / 12288 /
#: 49152) and Xing4.0's 8 of 64 (4096 / 16384)
NOT_BY_EIGHT = (("nemotron3_nano_30b_a3b", 128, 1856, 8192, 2688, 6, 8),
                ("xing4_29b_a4b", 64, 1024, 4096, 3584, 4, 8))
TOY = ("toy", 32, 128, 64, 128, 4, 4)


def routing(rng, S, k, E, G, share):
    """``top_e`` [S, k] int32 whose slots choose a held expert (``< G``) with
    probability ``share`` (a token's experts distinct), or uniformly over all
    ``E`` where ``share`` is None."""
    import numpy as np
    if share is None:
        return np.stack([rng.choice(E, k, replace=False)
                         for _ in range(S)]).astype(np.int32)
    n = np.clip(rng.binomial(k, share, S), max(0, k - (E - G)), min(k, G))
    return np.stack([np.concatenate([
        rng.choice(G, h, replace=False),
        G + rng.choice(E - G, k - h, replace=False)]) for h in n]
    ).astype(np.int32)


def sweep_unsorts(args, timed, emit):
    """The two un-sorts alone, by the slot in both index orders and by the
    held row, per share and load (module docstring)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops import moe_ops
    from paddle_tpu.pallas import held_rows as hr
    interpret = jax.default_backend() != "tpu"
    f32, bf = jnp.float32, jnp.bfloat16
    rng = np.random.default_rng(0)
    shares = (TOY,) if interpret else \
        tuple(s for s in SHARES + (ALL_HELD,) + NOT_BY_EIGHT
              if s[0] in args.shares)

    def distance(rec, tag, want, got, old, operands):
        """Whether two un-sorts gave the same float32 to the bit; where
        not, how far apart: in units in the last place of the sum of the
        terms' magnitudes (a token's terms cancel: the sum's own last place
        says nothing), and in how many of the elements."""
        rec["same_" + tag] = bool((want == got).all())
        if not rec["same_" + tag]:
            size = np.asarray(old(*(jnp.abs(a) if a.dtype == bf else a
                                    for a in operands)), np.float32)
            rec["ulps_" + tag] = float((np.abs(got - want) /
                                        np.spacing(size)).max())
            rec["differ_" + tag] = float((want != got).mean())

    for name, E, _, S, d, k, G in shares:
        full = S * min(k, G)
        ladder = (full,) if G == E else moe_ops.held_ladder(S, k, G, E)
        ks = jax.random.split(jax.random.key(2), 4)
        y, a, b = (jax.random.normal(kk, (full, d), bf) for kk in ks[:3])
        top_p = jax.random.uniform(ks[3], (S, k), f32)
        even = G / E
        loads = {"all": 1.0} if G == E else {
            "none": 0.0, "even": None, "heavy": 1.5 * even, "all": 1.0}
        by_row = hr.fits(S, k, d, full, bf)
        for load, share in loads.items():
            held, _, place = jax.jit(
                lambda t: moe_ops._held_slots(t, 0, G, k))(
                    jnp.asarray(routing(rng, S, k, E, G, share)))
            held_rows = int(held.sum())
            rows = ladder[int(moe_ops.held_rung(held_rows, ladder))]

            def slots_sum(major, y, place, held, top_p):
                return moe_ops._sum_over_slots(
                    y[:rows], jnp.minimum(place, rows - 1), S, k, top_p,
                    held, major)

            def slots_back(major, a, b, place, held):
                return moe_ops._sum_over_slots(
                    (a + b)[:rows], jnp.minimum(place, rows - 1), S, k,
                    held=held, major=major)

            def rows_sum(y, place, held, top_p):
                return hr.held_rows_to_tokens((y,), place, held, k, top_p,
                                              interpret=interpret)

            def rows_back(a, b, place, held):
                return hr.held_rows_to_tokens((a, b), place, held, k,
                                              interpret=interpret)

            rec = {"share": name, "load": load, "slots": S * k,
                   "held_rows": held_rows, "rung": rows}
            calls = {"sum": (slots_sum, rows_sum, (y, place, held, top_p)),
                     "back": (slots_back, rows_back, (a, b, place, held))}
            for what, (slots, by_rows, operands) in calls.items():
                minor, major = (jax.jit(functools.partial(slots, order))
                                for order in (False, True))
                rec["slots_" + what] = timed(minor, *operands)
                rec["major_" + what] = timed(major, *operands)
                want, got = (np.asarray(f(*operands), np.float64)
                             for f in (minor, major))
                distance(rec, "major_" + what, want, got, minor, operands)
                if by_row:
                    new = jax.jit(by_rows)
                    distance(rec, what, want,
                             np.asarray(new(*operands), np.float64), minor,
                             operands)
                    rec["rows_" + what] = timed(new, *operands)
            emit(rec)


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
    from paddle_tpu.pallas import held_rows as hr
    interpret = jax.default_backend() != "tpu"
    shares = (TOY,) if interpret else \
        tuple(s for s in SHARES if s[0] in args.shares)
    f32, bf = jnp.float32, jnp.bfloat16
    ctx = types.SimpleNamespace(amp=False, is_abstract=True)
    tiling = (128, 128, 128) if interpret else moe_ops._GMM_TILING_HELD
    rng = np.random.default_rng(0)
    for name, E, f, S, d, k, G in shares:
        full = S * min(k, G)
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
        top_e = jnp.asarray(routing(rng, S, k, E, G, None))
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

            def rows_sum(y, place, held, top_p):
                return hr.held_rows_to_tokens((y,), place, held, k, top_p,
                                              interpret=interpret)

            def rows_back(dxs, place, held):
                return hr.held_rows_to_tokens((dxs, dxs), place, held, k,
                                              interpret=interpret)

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
                "rows_sum": (rows_sum, y, place, held, top_p),
                "rows_back": (rows_back, y, place, held),
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
    ap.add_argument("--unsorts", action="store_true",
                    help="time the two un-sorts alone, by the slot (XLA) "
                    "and by the held row (pallas/held_rows.py), per share "
                    "and load, instead of the tiles")
    ap.add_argument("--shares", type=lambda s: s.split(","),
                    default=[s[0] for s in SHARES + (ALL_HELD,) +
                             NOT_BY_EIGHT],
                    help="the shares --lengths and --unsorts run "
                    "(comma-separated names of SHARES; olmoe_1b_7b: "
                    "--unsorts' row with every expert held; "
                    "nemotron3_nano_30b_a3b, xing4_29b_a4b: --unsorts' rows "
                    "at k 6 and k 4)")
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

    if args.unsorts:
        if interpret:
            args.calls = 1
        sweep_unsorts(args, timed, emit)
        return 0
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
