"""Times ``pallas/short_conv.py``'s two kernels alone on the chip beside the
``jax.numpy`` lowering of ``short_conv`` / ``short_conv_grad``
(``ops/sequence_ops.py``) at the four cells' shapes — Ling's ``[1, 8192,
6144]`` without a bias, Nemotron's with one, Solar-Open2's ``[1, 8192,
3072]``, and LFM2's gated ``[1, 16384, 3 x 2048]`` on ``xla`` alone — ms a
call, the share of the bytes' least time (two streams forward, three
backward, at the chip's 819 GB/s) and how far the kernels' Out, dX, dFilter
and dBias are from the lowering's on the same inputs (largest difference
over the largest value).  One JSON line a shape and tile choice.

    chiprun -- python3 tools/short_conv_probe.py
    chiprun -- python3 tools/short_conv_probe.py --blocks 512x512,1024x1024 --chunks 128x128,64x256
    JAX_PLATFORMS=cpu python3 tools/short_conv_probe.py --aot

``--blocks`` / ``--chunks``: the tile of a grid step and of a chunk inside
it, rows x lanes, every pair of the two lists (default: the tree's,
``pallas/short_conv.py:BLOCK`` and ``CHUNK``, which came from this sweep).
``--aot``, no chip: both kernels compiled for a described v5e at every
choice (what Mosaic refuses, it refuses here) and nothing run."""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

HBM_BYTES_PER_S = 819e9
#: (name, [b, t, d], bias, gated)
SHAPES = (("ling", (1, 8192, 6144), False, False),
          ("nemotron3", (1, 8192, 6144), True, False),
          ("solar", (1, 8192, 3072), False, False),
          ("lfm2", (1, 16384, 3 * 2048), False, True))


class _Abstract:
    """A lowering context that counts nothing."""
    is_abstract = True


def _pairs(text):
    return [tuple(int(n) for n in p.split("x")) for p in text.split(",")]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--blocks", default="")
    ap.add_argument("--chunks", default="")
    ap.add_argument("--shapes", default=",".join(s[0] for s in SHAPES))
    ap.add_argument("--taps", type=int, default=4)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--aot", action="store_true")
    args = ap.parse_args()
    if args.aot:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops import sequence_ops
    from paddle_tpu.pallas import short_conv
    dt = jnp.dtype(args.dtype)
    choices = [(b, c) for b in (_pairs(args.blocks) if args.blocks
                                else [short_conv.BLOCK])
               for c in (_pairs(args.chunks) if args.chunks
                         else [short_conv.CHUNK])]

    def xla_fwd(gated):
        def f(x, w, bias):
            if not gated:
                return sequence_ops._ungated("xla", x, w, bias)
            return sequence_ops._short_conv(
                _Abstract(), {"X": [x], "Filter": [w]}, {})["Out"][0]
        return jax.jit(f)

    def xla_bwd(gated):
        """``(dX, dFilter[, dBias])``."""
        def f(x, w, bias, g):
            if not gated:
                return [v for v in sequence_ops._ungated_grad(
                    "xla", x, w, bias, g) if v is not None]
            out = sequence_ops._short_conv_grad(
                _Abstract(), {"X$X": [x], "X$Filter": [w], "OG$Out": [g]},
                {})
            return [out["IG$X"][0], out["IG$Filter"][0]]
        return jax.jit(f)

    def kernels(block, chunk):
        kw = dict(block=block, chunk=chunk)
        return (jax.jit(lambda x, w, bias: short_conv.short_conv_fwd(
                    x, w, bias, **kw)),
                jax.jit(lambda x, w, bias, g: short_conv.short_conv_bwd(
                    x, w, bias, g, **kw)))

    if args.aot:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        jax.config.update("jax_enable_compilation_cache", False)
        one = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])

        def s(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one)
        for name, shape, has_bias, gated in SHAPES:
            if gated or name not in args.shapes.split(","):
                continue
            x, w = s(shape, dt), s((shape[2], args.taps), jnp.float32)
            bias = s((shape[2],), jnp.float32) if has_bias else None
            for block, chunk in choices:
                fwd, bwd = kernels(block, chunk)
                for kernel, fn, a in (("short_conv_fwd", fwd, (x, w, bias)),
                                      ("short_conv_bwd", bwd,
                                       (x, w, bias, x))):
                    t0 = time.time()
                    mem = fn.lower(*a).compile().memory_analysis()
                    print(json.dumps({
                        "shape": name, "block": block, "chunk": chunk,
                        "kernel": kernel,
                        "compile_s": round(time.time() - t0, 1),
                        "code_bytes": mem.generated_code_size_in_bytes}),
                        flush=True)
        return

    from paddle_tpu.device import on_tpu
    if not on_tpu():
        sys.exit("short_conv_probe: no TPU (--aot compiles without one)")

    def timed(fn, *a):
        jax.block_until_ready(fn(*a))
        t0 = time.perf_counter()
        for _ in range(args.reps):
            out = fn(*a)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / args.reps * 1e3, out

    def far(x, y):
        x, y = (np.asarray(z, np.float32) for z in (x, y))
        return float(np.abs(x - y).max() / max(np.abs(y).max(), 1e-30))

    r = np.random.RandomState(0)
    for name, shape, has_bias, gated in SHAPES:
        if name not in args.shapes.split(","):
            continue
        d = shape[2] // (3 if gated else 1)
        x, g = (jnp.asarray(r.randn(*shape[:2], n), dt)
                for n in (shape[2], d))
        w = jnp.asarray(r.randn(d, args.taps) * 0.5, jnp.float32)
        bias = jnp.asarray(r.randn(d), jnp.float32) if has_bias else None
        stream = shape[0] * shape[1] * d * dt.itemsize
        # X (three streams wide where gated) in, Out out; X, dOut in, dX out
        least = [(shape[2] // d + 1) * stream / HBM_BYTES_PER_S * 1e3,
                 (2 * shape[2] // d + 1) * stream / HBM_BYTES_PER_S * 1e3]
        ms_f, want = timed(xla_fwd(gated), x, w, bias)
        ms_b, want_g = timed(xla_bwd(gated), x, w, bias, g)
        row = {"shape": name, "x": shape, "bias": has_bias, "gated": gated,
               "dtype": dt.name, "least_ms": [round(v, 4) for v in least],
               "xla_ms": [round(ms_f, 4), round(ms_b, 4)],
               "xla_share_pct": [round(100 * least[0] / ms_f, 2),
                                 round(100 * least[1] / ms_b, 2)]}
        if gated:
            print(json.dumps(row), flush=True)
            continue
        for block, chunk in choices:
            fwd, bwd = kernels(block, chunk)
            ms_kf, out = timed(fwd, x, w, bias)
            ms_kb, (dx, dw, db) = timed(bwd, x, w, bias, g)
            apart = {"out": far(out, want), "dx": far(dx, want_g[0]),
                     "dfilter": far(dw, want_g[1])}
            if has_bias:
                apart["dbias"] = far(db, want_g[2])
            print(json.dumps(dict(
                row, block=block, chunk=chunk,
                tiles=short_conv.tiles(shape[1], d, dt, block),
                pallas_ms=[round(ms_kf, 4), round(ms_kb, 4)],
                pallas_share_pct=[round(100 * least[0] / ms_kf, 2),
                                  round(100 * least[1] / ms_kb, 2)],
                apart=apart)), flush=True)


if __name__ == "__main__":
    main()
