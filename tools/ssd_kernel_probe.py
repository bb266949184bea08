"""Times ``pallas/ssd.py``'s two kernels alone on the chip at the
Nemotron-3-Nano cell's shapes ([1, 8192, 64, 64] bf16 X, 8 groups of state
128, float32 Dt with a DtBias, chunks of 128) beside the ``jax.numpy``
lowering of ``ssd_scan`` / ``ssd_scan_grad`` (``ops/ssd_ops.py``: what runs
where the kernels do not), and says how far the kernels' Out, States and
seven gradients are from the ``jax.numpy`` form's and from the token-by-token
recurrence's on the same inputs, ``|x - x_ref| / |x_ref|``.  One JSON line.

    chiprun -- python3 tools/ssd_kernel_probe.py
    chiprun -- python3 tools/ssd_kernel_probe.py --leave_out diag,state
    JAX_PLATFORMS=cpu python3 tools/ssd_kernel_probe.py --aot

``share_of_least_time``: ``nemotron3_flops.ssd_work``'s least time of a
layer's forward and backward (the bytes set it) over the kernel's.  Dt is
``dt_scale * N(0, 1) + dt_shift`` before the softplus: the default gives
fresh weights' steps, ``Delta`` in 1e-3 .. 1e-1, where ``Delta A`` is small
and ``exp`` beside 0 is the device's own (PERF.md section 7 row 55).

``--leave_out a,b[:c:..]``: the kernels compiled WITHOUT the named parts, one
reading a colon-separated set, an empty set the whole kernels (the
numbers are then wrong and not compared; the op never does this): what a
part costs is the reading with everything minus the reading without it.
Parts: ``diag`` (the products a head with ``L``, both kernels), ``decays``
(the mask and ``exp`` that make ``L``), ``state``
(the earlier state's part and the state's update; backward: everything that
reads ``S_0`` or ``dS``), ``steps`` (the once-a-chunk softplus, cumulated
sum and transposes), ``cols`` (backward: the per-head columns of dDelta and
dcum), ``bc`` (backward: dB and dC), ``dots`` (EVERY product: what is left
is loads, stores and the VPU's work).

``--aot``, no chip: both kernels compiled for a described v5e (what Mosaic
refuses, it refuses here) and nothing run."""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def recurrence(x, dt, a_log, b, c, d, dt_bias):
    """Mamba-2 one position at a time in float32 at ``highest``: y [b, t,
    H, P] float32 from x [b, t, H, P], b / c [b, t, G, N]."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    x, dt, b, c = (v.astype(f32) for v in (x, dt, b, c))
    r = x.shape[2] // b.shape[2]
    delta = dt if dt_bias is None else jax.nn.softplus(dt + dt_bias)
    a = -jnp.exp(a_log.astype(f32))
    b, c = (jnp.repeat(v, r, axis=2) for v in (b, c))

    def step(s, at):
        x_t, d_t, b_t, c_t = at             # [b, H, P], [b, H], [b, H, N]
        s = jnp.exp(d_t * a)[..., None, None] * s \
            + (d_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return s, jnp.einsum("bhpn,bhn->bhp", s, c_t,
                             precision="highest")
    s0 = jnp.zeros(x.shape[:1] + x.shape[2:] + b.shape[-1:], f32)
    _, y = jax.lax.scan(step, s0, tuple(
        jnp.moveaxis(v, 1, 0) for v in (x, delta, b, c)))
    return jnp.moveaxis(y, 0, 1) + x * d.astype(f32)[:, None]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--heads", type=int, default=64)
    ap.add_argument("--head_dim", type=int, default=64)
    ap.add_argument("--groups", type=int, default=8)
    ap.add_argument("--state", type=int, default=128)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--dt_scale", type=float, default=1.0)
    ap.add_argument("--dt_shift", type=float, default=-4.0)
    ap.add_argument("--no_bias", action="store_true")
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--leave_out", default="")
    ap.add_argument("--no_reference", action="store_true",
                    help="the kernels alone: no jnp lowering, no recurrence")
    ap.add_argument("--check_seq", type=int, default=1024,
                    help="the length the recurrence is compared at")
    ap.add_argument("--aot", action="store_true")
    args = ap.parse_args()
    if args.aot:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.pallas import ssd
    sets = [frozenset(p for p in one.split(",") if p)
            for one in args.leave_out.split(":")]
    assert all(one <= ssd.PARTS for one in sets), sorted(ssd.PARTS)
    bsz, h, p = args.batch, args.heads, args.head_dim
    g, n = args.groups, args.state
    dt = jnp.dtype(args.dtype)
    has_bias = not args.no_bias
    f32 = jnp.float32

    class At:
        """The kernels, the ``jax.numpy`` lowering and the recurrence at one
        length.  The streams come in and go out as the program's ``[b, t,
        .]`` tensors do: the reshapes to the op's four axes and back cancel
        inside the jit."""

        def __init__(self, t, leave_out=frozenset()):
            self.t, self.leave_out = t, leave_out
            assert ssd.fits((bsz, t, h, p), (bsz, t, g, n), ssd.CHUNK,
                            [dt] * 3)
            self.fwd, self.bwd = jax.jit(self._fwd), jax.jit(self._bwd)

        def shaped(self, a):
            x, steps, a_log, b, c, d, bias = a
            t = self.t
            return (x.reshape(bsz, t, h, p), steps, a_log,
                    b.reshape(bsz, t, g, n), c.reshape(bsz, t, g, n), d,
                    bias if has_bias else None)

        def flat(self, v):
            return v if v is None or v.ndim != 4 else v.reshape(
                bsz, self.t, -1)

        def _fwd(self, *a):
            out, states = ssd.ssd_fwd(*self.shaped(a),
                                      leave_out=self.leave_out)
            return self.flat(out), states

        def _bwd(self, *a):
            return [self.flat(v) for v in ssd.ssd_bwd(
                *self.shaped(a[:7]), a[7],
                a[8].reshape(bsz, self.t, h, p), leave_out=self.leave_out)]

        def abstract(self, s):
            x, bc = s((bsz, self.t, h * p), dt), s((bsz, self.t, g * n), dt)
            per = s((h,), f32)
            ins = (x, s((bsz, self.t, h), f32), per, bc, bc, per, per)
            return ins, s((bsz, h, self.t // ssd.CHUNK, p, n), f32), x

        def values(self, seed=0):
            r = np.random.RandomState(seed)
            t = self.t
            x, w = (jnp.asarray(r.randn(bsz, t, h * p), dt)
                    for _ in range(2))
            b, c = (jnp.asarray(r.randn(bsz, t, g * n) * n ** -0.25, dt)
                    for _ in range(2))
            steps = jnp.asarray(
                r.randn(bsz, t, h) * args.dt_scale + args.dt_shift, f32)
            if not has_bias:
                steps = jax.nn.softplus(steps)
            a_log = jnp.asarray(np.log(r.uniform(1, 16, h)), f32)
            d = jnp.asarray(r.randn(h), f32)
            bias = jnp.asarray(r.randn(h) * 0.3, f32)
            return (x, steps, a_log, b, c, d, bias), w

        def reference(self, fn, w):
            """``fn`` (the op's arguments -> Out) as ``(Out, the seven
            gradients)`` under the cotangent ``w``, float32."""
            def both(*a):
                out, back = jax.vjp(lambda *v: fn(*self.shaped(v)), *a)
                return self.flat(out), back(
                    w.reshape(out.shape).astype(out.dtype))
            return jax.jit(both)

    if args.aot:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        one = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
        for left_out in sets:
            at = At(args.seq, left_out)
            ins, states, x = at.abstract(
                lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                          sharding=one))
            for name, fn, a in (("ssd_fwd", at.fwd, ins),
                                ("ssd_bwd", at.bwd, ins + (states, x))):
                t0 = time.time()
                mem = fn.lower(*a).compile().memory_analysis()
                print(json.dumps({
                    "kernel": name, "left_out": sorted(left_out),
                    "compile_s": round(time.time() - t0, 1),
                    "temp_bytes": mem.temp_size_in_bytes,
                    "code_bytes": mem.generated_code_size_in_bytes}),
                    flush=True)
        return

    from paddle_tpu.device import on_tpu
    assert on_tpu(), "no TPU: --aot compiles without one"

    def timed(fn, *a):
        jax.block_until_ready(fn(*a))
        t0 = time.perf_counter()
        for _ in range(args.reps):
            out = fn(*a)
        jax.block_until_ready(out)
        return round((time.perf_counter() - t0) / args.reps * 1e3, 3), out

    def rel(x, y):
        x, y = (np.asarray(z, np.float32).ravel() for z in (x, y))
        return float(np.linalg.norm(x - y) / max(np.linalg.norm(y), 1e-30))

    for left_out in sets:
        print(json.dumps(_readings(args, left_out, At, timed, rel)),
              flush=True)


def _readings(args, left_out, At, timed, rel):
    """One line of the probe: the kernels' times at ``args.seq`` without the
    parts ``left_out`` names and, with nothing left out, the ``jax.numpy``
    lowering's beside them and the distances."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark.nemotron3_flops import ssd_flops_per_chunk
    from paddle_tpu.ops import ssd_ops
    from paddle_tpu.pallas import ssd
    bsz, h, p, g, n = (args.batch, args.heads, args.head_dim, args.groups,
                       args.state)
    dt, has_bias = jnp.dtype(args.dtype), not args.no_bias
    at, t = At(args.seq, left_out), args.seq
    ins, w = at.values()
    ms_f, (out, states) = timed(at.fwd, *ins)
    ms_b, grads = timed(at.bwd, *ins, states, w)
    # nemotron3_flops.ssd_work's count, at these shapes
    act = jnp.dtype(dt).itemsize
    flops = bsz * (t // ssd.CHUNK) * ssd_flops_per_chunk(ssd.CHUNK, h, p, g, n)
    streams = bsz * t * (h * p + 2 * g * n + h) * act
    out_b, states_b = bsz * t * h * p * act, states.size * 4

    def least_ms(fl, by):
        return max(fl / 197e12, by / 819e9) * 1e3
    least = [least_ms(flops, streams + out_b + states_b),
             least_ms(2 * flops, 2 * (streams + out_b) + states_b)]
    steps_n = bsz * g * (t // ssd.CHUNK)
    line = {"shape": [bsz, t, h, p], "groups": g, "state": n,
            "dtype": args.dtype, "bias": has_bias,
            "dt": [args.dt_scale, args.dt_shift],
            "left_out": sorted(left_out),
            "ssd_fwd_ms": ms_f, "ssd_bwd_ms": ms_b,
            "us_a_chunk_and_group": [round(ms_f * 1e3 / steps_n, 3),
                                     round(ms_b * 1e3 / steps_n, 3)],
            "least_ms": [round(v, 3) for v in least],
            "share_of_least_time": [round(least[0] / ms_f, 4),
                                    round(least[1] / ms_b, 4)]}
    if not args.no_reference and not left_out:
        slots = ("X", "Dt", "ALog", "B", "C", "D", "DtBias")[:6 + has_bias]

        def chunked(*a, **kw):
            return ssd_ops.ssd_chunked(*a, chunk=ssd.CHUNK, **kw)
        live = ins if has_bias else ins[:6]
        pad = () if has_bias else (None,)
        ms_rf, (want, want_s) = timed(jax.jit(lambda *a: chunked(
            *at.shaped(a + pad), with_states=True)), *live)
        ms_rb, (_, want_g) = timed(at.reference(
            lambda *a: chunked(*a), w), *ins)
        mine = [gr for gr in grads if gr is not None]
        line.update({
            "ssd_chunked_ms": ms_rf, "ssd_chunked_vjp_ms": ms_rb,
            "finite": bool(np.isfinite(np.asarray(out, np.float32)).all()),
            "out_rel_jnp": rel(out, want.astype(dt)),
            "states_rel_jnp": rel(states, want_s),
            "grads_rel_jnp": {s: rel(x, y) for s, x, y in zip(
                slots, mine, want_g)}})
        # the recurrence keeps a state a position for its way back: at a
        # shorter length, the three forms on the same inputs
        short = At(args.check_seq)
        ins, w = short.values(1)
        out, states = short.fwd(*ins)
        mine = [gr for gr in short.bwd(*ins, states, w) if gr is not None]
        want, want_g = short.reference(lambda *a: chunked(*a), w)(*ins)
        rec, rec_g = short.reference(recurrence, w)(*ins)
        line["at_seq_%d" % args.check_seq] = {
            "out_rel": {"kernel_to_recurrence": rel(out, rec.astype(dt)),
                        "jnp_to_recurrence": rel(want.astype(dt),
                                                 rec.astype(dt)),
                        "kernel_to_jnp": rel(out, want.astype(dt))},
            "grads_kernel_to_recurrence": {s: rel(x, y) for s, x, y in zip(
                slots, mine, rec_g)},
            "grads_jnp_to_recurrence": {s: rel(x, y) for s, x, y in zip(
                slots, want_g, rec_g)}}
    return line


if __name__ == "__main__":
    main()
