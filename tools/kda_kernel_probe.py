"""Times ``pallas/kda.py``'s two kernels alone on the chip at the
Solar-Open2 cell's shapes ([1, 8192, 8, 128], bf16 Q, K, V, float32 G and
Beta, chunks of 64, beta doubled) beside ``kda_chunked`` (forward, and
``jax.vjp`` forward and back: what ``kda_scan`` and ``kda_scan_grad`` lower
to where the kernels do not run), and says how far the kernels' Out, States
and five gradients are from the jnp form's on the same inputs, ``|x - x_ref|
/ |x_ref|``.  One JSON line a row.

    chiprun -- python3 tools/kda_kernel_probe.py
    chiprun -- python3 tools/kda_kernel_probe.py --heads 16 --beta_once --bounded
    JAX_PLATFORMS=cpu python3 tools/kda_kernel_probe.py --aot

The second is Ling's cell: 16 heads, beta not doubled, log-decays in (-5, 0).
``kda_bwd_ms`` is a layer's backward; ``parent_kda_bwd_ms`` beside it is what
the backward kernel read here while it took ``jax.vjp`` of the chunk (the
commit before the hand-written one, same tool, same inputs).

``--aot``, no chip: both kernels compiled for a described v5e (what Mosaic
refuses, it refuses here) and nothing run.  ``--decay 20``: log-decays near
``-20`` a position."""

import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


#: ``kda_bwd`` as ``jax.vjp`` of the chunk, ms a layer by this tool on a v5e
#: (commit b023604; my chip runs, PR 56: the hand-written one read 3.819,
#: 7.501 and 4.124 as handed in), by (seq, heads, head_dim, chunk,
#: streams, beta doubled); the decays (0.05, bounded, 20) move neither side
PARENT_BWD_MS = {(8192, 8, 128, 64, "bfloat16", True): 6.255,
                 (8192, 16, 128, 64, "bfloat16", False): 12.372,
                 (8192, 8, 128, 64, "float32", True): 6.548}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--head_dim", type=int, default=128)
    ap.add_argument("--chunk", type=int, default=64)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--decay", type=float, default=0.05)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--beta_once", action="store_true",
                    help="beta as it comes, not doubled (Ling)")
    ap.add_argument("--bounded", action="store_true",
                    help="log-decays -5 sigmoid(N(0, 1)), Ling's bounded gate")
    ap.add_argument("--aot", action="store_true")
    args = ap.parse_args()
    if args.aot:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops import kda_ops
    from paddle_tpu.pallas import kda
    kw = dict(chunk=args.chunk, neg_eigval=not args.beta_once)
    shape = (1, args.seq, args.heads, args.head_dim)
    dt = jnp.dtype(args.dtype)
    fwd = jax.jit(lambda *a: kda.kda_fwd(*a, **kw))
    bwd = jax.jit(lambda *a: kda.kda_bwd(*a, **kw))

    if args.aot:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        one = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])

        def s(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one)
        x, g, beta = s(shape, dt), s(shape, jnp.float32), s(shape[:3],
                                                            jnp.float32)
        states = s((1, args.heads, -(-args.seq // args.chunk),
                    args.head_dim, args.head_dim), jnp.float32)
        for name, fn, a in (("kda_fwd", fwd, (x, x, x, g, beta)),
                            ("kda_bwd", bwd, (x, x, x, g, beta, states, x))):
            t0 = time.time()
            mem = fn.lower(*a).compile().memory_analysis()
            print(json.dumps({"kernel": name, "compile_s": round(
                time.time() - t0, 1), "temp_bytes": mem.temp_size_in_bytes,
                "code_bytes": mem.generated_code_size_in_bytes}), flush=True)
        return

    from paddle_tpu.device import on_tpu
    assert on_tpu(), "no TPU: --aot compiles without one"
    r = np.random.RandomState(0)
    q, k, v, w = (jnp.asarray(r.randn(*shape), dt) for _ in range(4))
    g = jnp.asarray(-5.0 / (1 + np.exp(-r.randn(*shape))) if args.bounded
                    else -np.abs(r.randn(*shape)) * args.decay, jnp.float32)
    beta = jnp.asarray(1 / (1 + np.exp(-r.randn(*shape[:3]))), jnp.float32)
    ins = (q, k, v, g, beta)
    ref = functools.partial(kda_ops.kda_chunked, **kw)
    ref_fwd = jax.jit(lambda *a: ref(*a).astype(dt))

    def ref_both(*a):
        out, back = jax.vjp(ref, *a)
        return [x.astype(p.dtype) for x, p in zip(
            back(w.astype(out.dtype)), a)]
    ref_bwd = jax.jit(ref_both)

    def timed(fn, *a):
        jax.block_until_ready(fn(*a))
        t0 = time.perf_counter()
        for _ in range(args.reps):
            out = fn(*a)
        jax.block_until_ready(out)
        return round((time.perf_counter() - t0) / args.reps * 1e3, 3), out

    def rel(x, y):
        x, y = (np.asarray(z, np.float32) for z in (x, y))
        return float(np.linalg.norm(x - y) / max(np.linalg.norm(y), 1e-30))

    ms_f, (out, states) = timed(fwd, *ins)
    ms_b, grads = timed(bwd, *ins, states, w)
    ms_rf, want = timed(ref_fwd, *ins)
    ms_rb, want_g = timed(ref_bwd, *ins)
    chunks = args.heads * -(-args.seq // args.chunk)
    print(json.dumps({
        "shape": shape, "dtype": args.dtype, "neg_eigval": kw["neg_eigval"],
        "decay": "bounded" if args.bounded else args.decay,
        "kda_fwd_ms": ms_f, "kda_bwd_ms": ms_b,
        "parent_kda_bwd_ms": PARENT_BWD_MS.get(
            (args.seq, args.heads, args.head_dim, args.chunk, args.dtype,
             kw["neg_eigval"])),
        "us_a_chunk_and_head": [round(ms_f * 1e3 / chunks, 3),
                                round(ms_b * 1e3 / chunks, 3)],
        "kda_chunked_ms": ms_rf, "kda_chunked_vjp_ms": ms_rb,
        "out_rel": rel(out, want), "finite": bool(
            np.isfinite(np.asarray(out, np.float32)).all()),
        "grads_rel": {s: rel(x, y) for s, x, y in zip(
            ("Q", "K", "V", "G", "Beta"), grads, want_g)}}), flush=True)


if __name__ == "__main__":
    main()
