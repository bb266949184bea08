"""Long-context (8k/16k) step-time attribution + flash block sweep on the
real chip (VERDICT r3 ask #3: the 512x1024 blocks were tuned on the r1
FORWARD kernel; the bwd kernels had never been swept).

Sections (each prints as it completes; steps chained on device, one
closing sync):
  1. standalone flash attention at the bench shapes: fwd and fwd+bwd,
     swept over (block_q, block_k) x (block_q_bwd, block_k_bwd)
  2. end-to-end fwd vs bwd split at 8k/16k
  3. component scaling: 6 vs 12 layers, head on/off proxy
Run: PYTHONPATH=/root/repo:$PYTHONPATH python tools/longctx_ablate.py
"""
import functools
import itertools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


from _tpu_timing import sync, time_fn  # noqa: E402


def attn_sweep(seq, bh, d=64):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.pallas.flash_attention import flash_attention

    rng = np.random.RandomState(0)
    q = jax.device_put(rng.randn(1, bh, seq, d).astype(np.float32) * 0.1)
    k = jax.device_put(rng.randn(1, bh, seq, d).astype(np.float32) * 0.1)
    v = jax.device_put(rng.randn(1, bh, seq, d).astype(np.float32) * 0.1)
    # attention FLOPs: fwd 4*T^2*d per head-batch (QK^T + PV); bwd 2.5x
    f_fwd = 4 * seq * seq * d * bh
    peak = 197e12

    results = {}
    fwd_blocks = [(256, 1024), (512, 1024), (512, 2048), (1024, 1024),
                  (1024, 2048), (2048, 1024)]
    print(f"--- fwd sweep seq={seq} bh={bh} ---", flush=True)
    for bq, bk in fwd_blocks:
        if bq > seq or bk > seq:
            continue
        fn = jax.jit(lambda a, b_, c, _bq=bq, _bk=bk: flash_attention(
            a, b_, c, block_q=_bq, block_k=_bk).sum())
        try:
            dt = time_fn(fn, q, k, v)
        except Exception as e:
            print(f"fwd {bq}x{bk}: FAIL {str(e)[:80]}", flush=True)
            continue
        results[f"fwd_{bq}x{bk}"] = dt * 1000
        print(f"fwd {bq}x{bk}: {dt*1000:7.2f} ms  "
              f"{f_fwd/dt/peak*100:5.1f}% MFU", flush=True)

    best_fwd = min((v_ for k_, v_ in results.items() if k_.startswith("fwd")),
                   default=None)
    bf = next((k_ for k_, v_ in results.items() if v_ == best_fwd), "")
    bq0, bk0 = (int(x) for x in bf[4:].split("x")) if bf else (512, 1024)

    print(f"--- f+b sweep seq={seq} bh={bh} (fwd {bq0}x{bk0}) ---",
          flush=True)
    f_fb = f_fwd * 3.5   # fwd + dq + dkv recompute-heavy backward
    for bqb, bkb in [(256, 512), (256, 1024), (512, 512), (512, 1024),
                     (512, 2048), (1024, 512), (1024, 1024), (128, 1024)]:
        if bqb > seq or bkb > seq:
            continue

        def loss(a, b_, c, _bqb=bqb, _bkb=bkb):
            return flash_attention(a, b_, c, block_q=bq0, block_k=bk0,
                                   block_q_bwd=_bqb,
                                   block_k_bwd=_bkb).sum()

        gfn = jax.grad(loss, argnums=(0, 1, 2))
        g = jax.jit(lambda a, b_, c: sum(x.sum() for x in gfn(a, b_, c)))
        try:
            dt = time_fn(g, q, k, v)
        except Exception as e:
            print(f"f+b bwd {bqb}x{bkb}: FAIL {str(e)[:80]}", flush=True)
            continue
        results[f"fb_bwd_{bqb}x{bkb}"] = dt * 1000
        print(f"f+b bwd {bqb}x{bkb}: {dt*1000:7.2f} ms  "
              f"{f_fb/dt/peak*100:5.1f}% MFU", flush=True)
    return results


def e2e(seq, batch, train=True, nlayer=12, steps=8, fused_head=True,
        bwd_blocks=None):
    import jax
    import paddle_tpu as pt
    from paddle_tpu import optimizer as opt
    from paddle_tpu.framework import Program, Scope, program_guard, \
        scope_guard
    from paddle_tpu.models import transformer as T

    scope = Scope()
    with scope_guard(scope), program_guard(Program(), Program()):
        cfg = T.BertConfig(max_pos=seq, n_layer=nlayer)
        feeds, logits, loss = T.build_bert_pretrain(
            cfg, seq, fused_head=fused_head, arange_pos=True,
            attn_impl="auto", dropout=0.0)
        if train:
            pt.amp.decorate(opt.AdamOptimizer(1e-4)).minimize(loss)
        else:
            pt.amp.enable()
        exe = pt.Executor()
        exe.run(pt.default_startup_program(), scope=scope)
        rng = np.random.RandomState(0)
        feed = {"src_ids": jax.device_put(rng.randint(
                    1, cfg.vocab_size, (batch, seq)).astype(np.int32)),
                "lm_label": jax.device_put(rng.randint(
                    0, cfg.vocab_size, (batch, seq)).astype(np.int32))}
        lv, = exe.run(feed=feed, fetch_list=[loss.name], scope=scope)
        sync(lv)
        t0 = time.perf_counter()
        for _ in range(steps):
            lv, = exe.run(feed=feed, fetch_list=[loss.name], scope=scope,
                          return_numpy=False)
        sync(lv)
        return (time.perf_counter() - t0) / steps


def main():
    out = {}
    for seq, batch in ((8192, 2), (16384, 1)):
        bh = batch * 12
        out[f"sweep_{seq}"] = attn_sweep(seq, bh)
    if "--sweep-only" in sys.argv:
        print(json.dumps(out))
        return
    for name, kw in (
            ("e2e_8k_train", dict(seq=8192, batch=2)),
            ("e2e_8k_fwd", dict(seq=8192, batch=2, train=False)),
            ("e2e_8k_train_l6", dict(seq=8192, batch=2, nlayer=6)),
            ("e2e_16k_train", dict(seq=16384, batch=1)),
            ("e2e_16k_fwd", dict(seq=16384, batch=1, train=False)),
    ):
        dt = e2e(**kw)
        out[name] = dt * 1000
        print(f"{name:24s} {dt*1000:8.1f} ms/step", flush=True)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
