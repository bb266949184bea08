"""On-chip sweep of the two kernels the OLMoE cell leans on, at its shapes:

* the grouped expert matmuls (131072 routed rows x 2048 x 1024 over 64
  groups, bf16): ``jax.lax.ragged_dot`` (XLA:TPU's own grouped-matmul
  kernels) against megablox ``gmm``/``tgmm`` at several tilings, forward and
  forward + backward of the gated FFN;
* causal flash attention at [64, 4096, 128] bf16: forward blocks and backward
  blocks of ``paddle_tpu.pallas.flash_attention``.

    chiprun -- python3 tools/olmoe_kernel_sweep.py [--only gmm|flash]

Prints one JSON line per configuration (milliseconds, best of three rounds
of five calls) and writes them to ``chiprun_out/olmoe_kernel_sweep.jsonl``.
A configuration the compiler refuses is a line with ``error``.  A number from
a CPU is meaningless here: the script exits without a TPU.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def bench(fn, *args, rounds=3, calls=5):
    out = fn(*args)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(rounds):
        t = time.perf_counter()
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t) / calls)
    return best * 1e3


def emit(f, rec):
    line = json.dumps(rec)
    print(line, flush=True)
    f.write(line + "\n")
    f.flush()


def sweep_gmm(f, rows=131072, d=2048, width=1024, experts=64):
    from paddle_tpu.ops import moe_ops
    key = jax.random.key(0)
    ks = jax.random.split(key, 5)
    xs = jax.random.normal(ks[0], (rows, d), jnp.bfloat16)
    wg = jax.random.normal(ks[1], (experts, d, width), jnp.float32) * 0.02
    wu = jax.random.normal(ks[2], (experts, d, width), jnp.float32) * 0.02
    wd = jax.random.normal(ks[3], (experts, width, d), jnp.float32) * 0.02
    ids = jax.random.randint(ks[4], (rows,), 0, experts)
    load = jnp.sum(ids[:, None] == jnp.arange(experts)[None], axis=0,
                   dtype=jnp.int32)
    flops_fwd = 3 * 2.0 * rows * d * width
    for impl, tiling in [("ragged_dot", None)] + [
            ("megablox", t) for t in (
                (512, 1024, 1024), (512, 512, 512), (512, 2048, 1024),
                (1024, 1024, 1024), (256, 1024, 1024), (512, 1024, 512),
                (1024, 512, 1024), (512, 2048, 512))]:
        def ffn(xs, wg, wu, wd):
            y = moe_ops.gated_experts(xs, wg, wu, wd, load, jnp.bfloat16,
                                      impl=impl, tiling=tiling)[0]
            return jnp.sum(y.astype(jnp.float32) ** 2)
        rec = {"kernel": "experts", "impl": impl, "tiling": tiling}
        try:
            rec["fwd_ms"] = bench(jax.jit(ffn), xs, wg, wu, wd)
            rec["fwd_bwd_ms"] = bench(
                jax.jit(jax.grad(ffn, argnums=(0, 1, 2, 3))), xs, wg, wu, wd)
            rec["fwd_tflops"] = flops_fwd / rec["fwd_ms"] / 1e9
            rec["fwd_bwd_tflops"] = 3 * flops_fwd / rec["fwd_bwd_ms"] / 1e9
        except Exception as e:       # the compiler refused this tiling
            rec["error"] = str(e).splitlines()[0][:300]
        emit(f, rec)


def sweep_flash(f, bh=64, t=4096, dh=128):
    from paddle_tpu.pallas import flash_attention
    ks = jax.random.split(jax.random.key(1), 3)
    q, k, v = (jax.random.normal(kk, (bh // 16, 16, t, dh), jnp.bfloat16)
               for kk in ks)
    blocks = [(512, 1024), (512, 512), (1024, 512), (1024, 1024),
              (256, 1024), (512, 2048), (256, 512), (1024, 256), (2048, 512),
              (256, 2048)]
    for bq, bk in blocks:
        def fwd(q, k, v):
            return flash_attention(q, k, v, causal=True, block_q=bq,
                                   block_k=bk)
        rec = {"kernel": "flash_fwd", "block_q": bq, "block_k": bk}
        try:
            rec["ms"] = bench(jax.jit(fwd), q, k, v)
        except Exception as e:
            rec["error"] = str(e).splitlines()[0][:300]
        emit(f, rec)
    for impl in ("fused", "split"):
        for bq, bk in blocks:
            def loss(q, k, v):
                o = flash_attention(q, k, v, causal=True, block_q=512,
                                    block_k=1024, block_q_bwd=bq,
                                    block_k_bwd=bk, bwd_impl=impl)
                return jnp.sum(o.astype(jnp.float32) ** 2)
            rec = {"kernel": "flash_fwd_bwd", "bwd_impl": impl,
                   "fwd_blocks": [512, 1024], "block_q_bwd": bq,
                   "block_k_bwd": bk}
            try:
                rec["ms"] = bench(jax.jit(jax.grad(loss, argnums=(0, 1, 2))),
                                  q, k, v)
            except Exception as e:
                rec["error"] = str(e).splitlines()[0][:300]
            emit(f, rec)


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", choices=("gmm", "flash"))
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        sys.exit("olmoe_kernel_sweep: no TPU; a kernel time is never taken "
                 "from another backend")
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    path = os.path.join(ROOT, "chiprun_out", "olmoe_kernel_sweep.jsonl")
    with open(path, "a") as f:
        emit(f, {"device": jax.devices()[0].device_kind,
                 "numpy": np.__version__, "jax": jax.__version__})
        if args.only != "flash":
            sweep_gmm(f)
        if args.only != "gmm":
            sweep_flash(f)


if __name__ == "__main__":
    main()
