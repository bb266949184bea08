"""Shared timing for the on-chip ablation tools.

Dispatch is asynchronous, so a timing ends in ``jax.block_until_ready``:
steps are chained on device and ONE closing sync bounds the window.  The
timed callable returns a SCALAR so the sync never times a large copy.
"""
import time

import jax
import numpy as np

sync = jax.block_until_ready


def time_fn(f, *args, iters=8):
    out = f(*args)
    assert np.asarray(out).size == 1, "time_fn needs a scalar-returning f"
    sync(out)
    t0 = time.perf_counter()
    outs = [f(*args) for _ in range(iters)]
    sync(outs[-1])
    return (time.perf_counter() - t0) / iters


def time_fn_slope(f, *args, iters=(8, 40), reps=3, n_arg=False):
    """Timing for sub-ms kernels, free of the fixed per-window cost (the
    closing sync and the first dispatch).  Three defenses compose: (1)
    time TWO iteration counts and take the slope — the fixed term cancels
    exactly; (2) take the MIN over ``reps`` repetitions of each leg —
    host delays are strictly additive, so min is the clean estimator; (3)
    with ``n_arg=True``, ``f(n, *args)`` chains its n iterations ON DEVICE
    (one dispatch, one sync), keeping per-dispatch host overhead out of
    multi-dispatch runs."""
    lo, hi = iters
    if n_arg:
        out = f(lo, *args)
    else:
        out = f(*args)
    assert np.asarray(out).size == 1, "time_fn_slope needs a scalar f"
    sync(out)

    def run(n):
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            if n_arg:
                sync(f(n, *args))
            else:
                outs = [f(*args) for _ in range(n)]
                sync(outs[-1])
            best = min(best, time.perf_counter() - t0)
        return best

    t_lo = run(lo)
    t_hi = run(hi)
    return max(t_hi - t_lo, 1e-9) / (hi - lo)
