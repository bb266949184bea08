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


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--rows", type=int, default=65536)
    ap.add_argument("--held", type=int, default=16)
    ap.add_argument("--calls", type=int, default=20)
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
                line = json.dumps(rec)
                print(line, flush=True)
                out.write(line + "\n")
                out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
