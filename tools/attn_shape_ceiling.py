"""Per-shape MXU ceiling microbench for the dh=64 attention contractions
(VERDICT r4 → r5 ask #1): the long-context residual was attributed to
"dh=64 fills half the 128-lane MXU contraction" — asserted, never
measured.  This tool measures it on the real chip with SKELETON kernels:
the flash forward minus softmax (QK^T and S·V contractions, S resident in
VMEM, no [T,T] HBM traffic) and the combined backward minus softmax (the
same 5 contractions + the real dk/dv partial writes).  A skeleton is the
per-shape ceiling by construction — it does every matmul and every
unavoidable memory movement of the real kernel and nothing else — so
 real_kernel / skeleton  is the exact softmax/bookkeeping overhead, and
 attention_flops / t_skeleton  is the achievable MFU for the shape.

The d-fill hypothesis is tested by running the forward skeleton at
d=64 vs d=128 (2x the FLOPs): t(128)/t(64) near 1 confirms the half-fill
penalty; near 2 refutes it.

Timing: device-chained loops (one dispatch executes n kernel iterations
via fori_loop with a data dependency, keeping per-dispatch host overhead
out of the window) + min-of-reps slope over two chain lengths (cancels the
fixed cost of the closing sync and its jitter — _tpu_timing.time_fn_slope).

Run: PYTHONPATH=/root/repo:$PYTHONPATH python tools/attn_shape_ceiling.py
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from _tpu_timing import time_fn_slope  # noqa: E402

PEAK = 197e12


def _fwd_skeleton(bh, t, d, block_q, block_k):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nq, nk = t // block_q, t // block_k

    def kern(q_ref, k_ref, v_ref, o_ref, acc):
        j = pl.program_id(2)

        @pl.when(j == 0)
        def _():
            acc[...] = jnp.zeros_like(acc)

        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc[...] += jax.lax.dot_general(
            s, v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(j == pl.num_programs(2) - 1)
        def _():
            o_ref[0] = acc[...]

    return pl.pallas_call(
        kern,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
    )


def _bwd_skeleton(bh, t, d, block_q, block_k):
    """The combined backward's 5 contractions + dk/dv partial outputs,
    with the softmax terms (exp, lse/delta, masks) stripped."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nq, nk = t // block_q, t // block_k

    def kern(q_ref, k_ref, v_ref, do_ref, dq_ref, dkp_ref, dvp_ref, dq_sc):
        j = pl.program_id(2)

        @pl.when(j == 0)
        def _():
            dq_sc[...] = jnp.zeros_like(dq_sc)

        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = s * dp                   # one elementwise op stands in for
        p = s                         # the p/ds algebra; exp/masks cut
        dq_sc[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dvp_ref[0, 0] = jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dkp_ref[0, 0] = jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(j == pl.num_programs(2) - 1)
        def _():
            dq_ref[0] = dq_sc[...]

    part = pl.BlockSpec((1, 1, block_k, d), lambda b, i, j: (b, i, j, 0))
    return pl.pallas_call(
        kern,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            part, part,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), jnp.float32),
            jax.ShapeDtypeStruct((bh, nq, t, d), jnp.float32),
            jax.ShapeDtypeStruct((bh, nq, t, d), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
    )


def _chain_scalar(fn, dep=0):
    """jit(f(n, *args)) running fn n times on device, scalar out; the
    accumulator perturbs args[dep] so the loop body cannot be hoisted."""
    import jax
    import jax.numpy as jnp

    def chained(n, *a):
        def body(i, acc):
            aa = list(a)
            aa[dep] = aa[dep] + acc * 0
            outs = fn(*aa)
            if not isinstance(outs, (list, tuple)):
                outs = [outs]
            return acc + sum(o[..., :8, :].sum() for o in outs)
        return jax.lax.fori_loop(0, n, body, jnp.float32(0))

    return jax.jit(chained)


def probe(t, bh, d=64):
    import jax
    import jax.numpy as jnp
    import importlib
    FA = importlib.import_module('paddle_tpu.pallas.flash_attention')

    bq_f, bk_f = FA._FWD_DEFAULTS.get(t, (512, 1024))
    bq_f, bk_f = min(bq_f, t), min(bk_f, t)
    bq_b, bk_b = FA._BWD_DEFAULTS.get(t, (bq_f, bk_f))
    bq_b, bk_b = min(bq_b, t), min(bk_b, t)
    rng = np.random.RandomState(0)

    def mk(dd):
        return tuple(jax.device_put(
            rng.randn(bh, t, dd).astype(np.float32) * 0.1)
            for _ in range(4))

    q, k, v, do = mk(d)
    out = {"T": t, "bh": bh, "fwd_blocks": [bq_f, bk_f],
           "bwd_blocks": [bq_b, bk_b]}

    fs = _fwd_skeleton(bh, t, d, bq_f, bk_f)
    out["fwd_skel_ms"] = time_fn_slope(
        _chain_scalar(lambda a, b_, c: fs(a, b_, c)), q, k, v,
        n_arg=True) * 1000

    q2, k2, v2, _ = mk(2 * d)
    fs2 = _fwd_skeleton(bh, t, 2 * d, bq_f, bk_f)
    out["fwd_skel_d128_ms"] = time_fn_slope(
        _chain_scalar(lambda a, b_, c: fs2(a, b_, c)), q2, k2, v2,
        n_arg=True) * 1000

    bs = _bwd_skeleton(bh, t, d, bq_b, bk_b)
    out["bwd_skel_ms"] = time_fn_slope(
        _chain_scalar(lambda a, b_, c, dd: bs(a, b_, c, dd)), q, k, v, do,
        n_arg=True) * 1000

    # the real kernels at the same blocks
    q4 = q.reshape(1, bh, t, d)
    k4 = k.reshape(1, bh, t, d)
    v4 = v.reshape(1, bh, t, d)

    def fwd_real(a, b_, c):
        return FA.flash_attention(a, b_, c, block_q=bq_f, block_k=bk_f)

    out["flash_fwd_ms"] = time_fn_slope(
        _chain_scalar(fwd_real), q4, k4, v4, n_arg=True) * 1000

    def loss(a, b_, c):
        return FA.flash_attention(a, b_, c, block_q=bq_f, block_k=bk_f,
                                  block_q_bwd=bq_b,
                                  block_k_bwd=bk_b).sum()

    gfn = jax.grad(loss, argnums=(0, 1, 2))

    def fb_chain(n, a, b_, c):
        def body(i, acc):
            return acc + sum(x.sum() for x in gfn(a + acc * 0, b_, c))
        return jax.lax.fori_loop(0, n, body, jnp.float32(0))

    out["flash_fwd_bwd_ms"] = time_fn_slope(
        jax.jit(fb_chain), q4, k4, v4, n_arg=True) * 1000

    # analysis
    f_fwd = 4 * bh * t * t * d                    # QK + PV, 2 MACs each
    f_bwd = 10 * bh * t * t * d                   # 5 contractions
    fwd_skel, bwd_skel = out["fwd_skel_ms"], out["bwd_skel_ms"]
    out["fwd_skel_mfu"] = round(f_fwd / (fwd_skel / 1e3) / PEAK * 100, 1)
    out["bwd_skel_mfu"] = round(f_bwd / (bwd_skel / 1e3) / PEAK * 100, 1)
    out["fill_ratio"] = round(out["fwd_skel_d128_ms"] /
                              (2 * fwd_skel), 3)
    out["fwd_vs_skel"] = round(out["flash_fwd_ms"] / fwd_skel, 3)
    fb_skel = fwd_skel + bwd_skel     # real bwd recomputes s in-kernel
    out["fb_vs_skel"] = round(out["flash_fwd_bwd_ms"] / fb_skel, 3)
    print(json.dumps(out), flush=True)
    return out


def main():
    cases = [(2048, 24), (8192, 6), (16384, 2)]
    if "--quick" in sys.argv:
        cases = [(8192, 6)]
    if "--t" in sys.argv:
        want = int(sys.argv[sys.argv.index("--t") + 1])
        cases = [c for c in cases if c[0] == want]
    reports = [probe(t, bh) for t, bh in cases]
    print(json.dumps(reports))


if __name__ == "__main__":
    main()
