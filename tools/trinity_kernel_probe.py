"""Times the flash attention kernels alone at the Trinity-Mini cell's shapes
on the chip: [1, 32, 8192, 128] queries over 4 K/V heads in bf16, the window
of 2048 against the full causal half, the fused backward against the
split one, and (``--blocks``) other block sizes.  Prints one JSON line per
case: forward ms, forward + backward ms, the backward's temporaries and, for
the block tables' own choice, how far the output and the three gradients are
from ``mha_reference`` (the dense-mask oracle, float32 at ``highest`` over
the same bf16 inputs, one K/V head's group of query heads at a time):
``|x - x_ref| / |x_ref|``, where bf16 kernels read a few 1e-3 and a block
skipped or summed wrongly reads 0.1 or more.

    chiprun -- python3 tools/trinity_kernel_probe.py

``--forward`` (PR 39; the sweep itself is ``tools/joyai_kernel_probe.py``'s)
times the forward half alone over ``--fwd_blocks`` under the window and
full, each row against the oracle's output and, with ``--parent PATH``
(another checkout of this repo), against that checkout's kernel: its time,
and whether ``Out`` and ``Lse`` are its bits.  ``--aot``, no chip: each
forward row compiled for a described v5e, the VMEM limit the call asks for
and the least that compiles it.  ``--window 0``: the full causal half alone
(OLMoE's ``--seq 4096 --heads 64 --kv_heads 64 --window 0``).
``--block_diffusion B`` (PR 61): block diffusion's three-part mask over the
``--seq`` rows (a noisy and a clean copy of ``seq / 2`` tokens in blocks of
``B``) in the window's place, then the full causal half at the same length
(SDAR's cell: ``--seq 16384 --block_diffusion 4 --oracle_heads 1``).

``--subs 0,128,256,512`` (PR 62): the sub-tile a masked tile pair is run by
(``_SUB_FWD`` / ``_SUB_BWD`` of the kernels' module; 0: every masked tile
pair whole, the kernels before PR 62) over ``SUB_CASES``, the masks and
lengths the listed cells run at the tables' own blocks: one row a case and
a sub-tile with forward ms, backward ms (forward + backward less the
forward), the distances from the oracle, the tile pairs a head by state and
the masked ones' sub-tiles by state; then, from the rows at 0, the cost of a
free, a masked and a DEAD grid step a head by least squares over the cases
(ms = heads x (free, masked, dead) . cost + a call's constant), which is
what a compacted grid could save (PERF.md section 7 row 62b).  Every row
names its device and its backward kernel (the first of ``--impls``: ``split``
times the dQ and dK/dV pair); without a TPU ``--subs`` exits and prints no
row.

    chiprun -- python3 tools/trinity_kernel_probe.py --forward --parent .scratch/parent
    JAX_PLATFORMS=cpu python3 tools/trinity_kernel_probe.py --aot
    chiprun -- python3 tools/trinity_kernel_probe.py --subs 0,128,256,512
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tools"))
import joyai_kernel_probe as probe  # noqa: E402  (the forward sweep)


#: ``--subs``: name -> (seq, heads, kv_heads, d_qk, d_rope, window or 0,
#: block_diffusion or 0): SDAR's call and the causal half at its length,
#: Trinity's two kinds of layer, JoyAI's two-product score (128 + 64 | 128,
#: one rotary head), OLMoE's length, LFM2's call (d 64)
SUB_CASES = {
    "block_diffusion_4@16384": (16384, 32, 4, 128, 0, 0, 4),
    "causal@16384": (16384, 32, 4, 128, 0, 0, 0),
    "causal@8192": (8192, 32, 4, 128, 0, 0, 0),
    "window_2048@8192": (8192, 32, 4, 128, 0, 2048, 0),
    "causal_128+64@8192": (8192, 32, 32, 128, 64, 0, 0),
    "causal@4096": (4096, 64, 64, 128, 0, 0, 0),
    "causal_d64@16384": (16384, 32, 8, 64, 0, 0, 0),
}


def _timer(iters):
    """``timed(fn, *args)``: ms a call over ``iters`` calls after one."""
    import jax

    def timed(fn, *a):
        jax.block_until_ready(fn(*a))
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*a)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / iters * 1e3
    return timed


def _off(got, want):
    """``|got - want| / |want|`` in float32."""
    import jax.numpy as jnp
    return float(jnp.linalg.norm(got.astype(jnp.float32) - want)
                 / jnp.linalg.norm(want))


def sub_sweep(args, F):
    """The ``--subs`` rows and the grid steps' costs.  On a TPU alone: the
    sub-tiles, the table above the kernels' block tables and the cost of a
    dead step are read from these rows, and an interpreter's milliseconds
    under the cases' names would pass for them."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    subs = [int(x) for x in args.subs.split(",")]
    impl = args.impls.split(",")[0]
    device = jax.devices()[0].device_kind
    fit, timed, off = [], _timer(args.iters), _off

    for name, (seq, heads, kv_heads, d, d_r, window, bd) in SUB_CASES.items():
        if args.cases and name not in args.cases.split(","):
            continue
        key = jax.random.PRNGKey(seq + d_r + window + bd)
        rand = lambda i, h, w: jax.random.normal(  # noqa: E731
            jax.random.fold_in(key, i), (1, h, seq, w), jnp.bfloat16)
        q, do, k, v = rand(0, heads, d), rand(1, heads, d), \
            rand(2, kv_heads, d), rand(3, kv_heads, d)
        ops = (q, k, v) + ((rand(4, heads, d_r), rand(5, 1, d_r)) if d_r
                           else ())
        mask = F.block_diffusion(seq, bd) if bd else (window or None)
        kw = dict(causal=not bd, window=mask, bwd_impl=impl)

        def attn(q, k, v, *rope, fn=F.flash_attention, **kw):
            return fn(q, k, v, **kw, **(dict(q_rope=rope[0], k_rope=rope[1])
                                        if rope else {}))

        @jax.jit
        def head(dog, *one):
            # one query head a call: a head's dense scores are 1 GB at 16384
            with jax.default_matmul_precision("highest"):
                o, back = jax.vjp(
                    lambda *a: attn(*a, fn=F.mha_reference, causal=not bd,
                                    window=mask),
                    *(a.astype(jnp.float32) for a in one))
                return (o,) + back(dog.astype(jnp.float32))

        def oracle(do, *ops):
            # dK and dV (and the rotary key's) summed over a K/V head's group
            group = heads // kv_heads
            parts = []
            for h in range(heads):
                g = h // group
                one = [ops[0][:, h:h + 1], ops[1][:, g:g + 1],
                       ops[2][:, g:g + 1]]
                if d_r:
                    one += [ops[3][:, h:h + 1], ops[4]]
                parts.append(head(do[:, h:h + 1], *one))
            parts = list(zip(*parts))
            cat = lambda x: jnp.concatenate(x, axis=1)  # noqa: E731
            fold = lambda x: cat(x).reshape(  # noqa: E731
                1, -1, group, *x[0].shape[2:]).sum(axis=2)
            out = [cat(parts[0]), cat(parts[1]), fold(parts[2]),
                   fold(parts[3])]
            if d_r:
                out += [cat(parts[4]), sum(parts[5])]
            return out
        want = oracle(do, *ops)
        (bq, bk), (bq_b, bk_b) = F.flash_blocks(q, k, v, **kw)
        pairs = F.grid_tile_pairs(not bd, mask, bq, bk, seq, seq)
        for sub in subs:
            F._SUB_FWD = F._SUB_BWD = sub
            fwd = jax.jit(lambda *a: attn(*a, **kw))
            both = jax.jit(lambda do, *a: jax.vjp(
                lambda *a: attn(*a, **kw), *a)[1](do))
            row = {"case": name, "sub": sub, "device": device,
                   "bwd_impl": F.flash_bwd_kernel(
                       *ops[:3], **kw, **(dict(q_rope=ops[3], k_rope=ops[4])
                                          if d_r else {})),
                   "blocks": [bq, bk, bq_b, bk_b],
                   "tile_pairs_fwd": dict(zip(("free", "masked", "dead"),
                                              pairs))}
            try:
                row["fwd_ms"] = timed(fwd, *ops)
                row["bwd_ms"] = timed(both, do, *ops) - row["fwd_ms"]
                got = (fwd(*ops),) + tuple(both(do, *ops))
                row.update({f"{n}_rel": off(g, w) for n, g, w in zip(
                    ("o", "dq", "dk", "dv", "dqr", "dkr"), got, want)})
                row["subtiles_fwd"] = F.subtile_counts(
                    not bd, mask, bq, bk, seq, seq, False, sub)
                row["subtiles_bwd"] = F.subtile_counts(
                    not bd, mask, bq_b, bk_b, seq, seq, False, sub)
            except Exception as e:                 # VMEM: say and go on
                row["error"] = str(e)[:300]
            print(json.dumps(row), flush=True)
            if not sub and not d_r and "error" not in row:
                # a pass's steps cost what its blocks make them: the fit
                # takes the rows at the blocks of the first
                fit.append((heads, {
                    "fwd": ((bq, bk), pairs, row["fwd_ms"]),
                    "bwd": ((bq_b, bk_b), F.grid_tile_pairs(
                        not bd, mask, bq_b, bk_b, seq, seq),
                        row["bwd_ms"])}))
    for which in ("fwd", "bwd"):
        rows = [(h, *by[which][1:]) for h, by in fit
                if by[which][0] == fit[0][1][which][0]]
        if len(rows) < 4:
            continue
        a = np.array([[h * f, h * m, h * dd, 1.0]
                      for h, (f, m, dd), _ in rows])
        y = np.array([r[2] for r in rows])
        cost, *_ = np.linalg.lstsq(a, y, rcond=None)
        print(json.dumps({
            "grid_step_us": which, "cases": len(rows),
            **{n: float(c) * 1e3 for n, c in zip(
                ("free", "masked", "dead"), cost)},
            "call_ms": float(cost[3]),
            "residual_ms": [float(x) for x in a @ cost - y]}), flush=True)


def _windows(args):
    """The window (or ``--block_diffusion``'s mask form) and the full causal
    half; ``--window 0``: the latter."""
    if args.block_diffusion:
        import importlib
        F = importlib.import_module("paddle_tpu.pallas.flash_attention")
        return (F.block_diffusion(args.seq, args.block_diffusion), None)
    return (args.window, None) if args.window else (None,)


def _causal(window):
    """A mask form is the whole mask; a window is an edge of the causal
    half."""
    return window is None or isinstance(window, int)


def aot(args, F):
    """The forward rows at this shape compiled for a described v5e chip."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    one = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    group = args.heads // args.kv_heads

    def s(heads):
        return jax.ShapeDtypeStruct((heads, args.seq, args.head_dim),
                                    jnp.bfloat16, sharding=one)
    for window in _windows(args):
        print(json.dumps({"window": window}), flush=True)
        probe.aot_forward(
            F, probe._blocks(args.fwd_blocks), lambda bq, bk: jax.jit(
                lambda q, k, v: F._flash_fwd_pallas(
                    q, k, v, None, _causal(window), args.head_dim ** -0.5,
                    bq, bk, 0,
                    False,
                    window, group)
            ).lower(s(args.heads), s(args.kv_heads), s(args.kv_heads)))


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--kv_heads", type=int, default=4)
    ap.add_argument("--window", type=int, default=2048)
    ap.add_argument("--block_diffusion", type=int, default=0)
    ap.add_argument("--head_dim", type=int, default=128,
                    help="the heads' width (LFM2: --seq 16384 --heads 32 "
                    "--kv_heads 8 --head_dim 64 --window 0)")
    ap.add_argument("--blocks", default="",
                    help="bq_fwd,bk_fwd,bq_bwd,bk_bwd[;...] beside defaults")
    ap.add_argument("--impls", default="fused,split",
                    help="the backward kernels to time at each block choice")
    ap.add_argument("--forward", action="store_true",
                    help="the forward half alone over --fwd_blocks")
    ap.add_argument("--fwd_blocks", default=probe.FWD_BLOCKS)
    ap.add_argument("--parent", default="", help="--forward: another "
                    "checkout of this repo, its forward on the same rows")
    ap.add_argument("--aot", action="store_true", help="compile each "
                    "--fwd_blocks row for a described v5e, run nothing")
    ap.add_argument("--subs", default="", help="the sub-tiles to sweep "
                    "over SUB_CASES (0: masked tile pairs whole), e.g. "
                    "0,128,256,512")
    ap.add_argument("--cases", default="", help="--subs: these of "
                    "SUB_CASES alone, comma-separated")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--oracle_heads", type=int, default=0,
                    help="query heads of a group the oracle takes at a time "
                    "(default the whole group; at 16384 one head's dense "
                    "scores are 1 GB)")
    args = ap.parse_args()
    import importlib
    import jax
    import jax.numpy as jnp
    import paddle_tpu  # noqa: F401
    F = importlib.import_module("paddle_tpu.pallas.flash_attention")
    if args.aot:
        return aot(args, F)
    interpret = jax.default_backend() != "tpu"
    if args.subs:
        if interpret:
            sys.exit("--subs times the kernels and needs a TPU: "
                     f"the backend is {jax.default_backend()}")
        return sub_sweep(args, F)
    if interpret:                                  # a rehearsal of the path
        args.seq, args.window, args.iters = 64, 16, 1
        args.block_diffusion = min(args.block_diffusion, 4)
        args.fwd_blocks = "16,16;32,16"
    key = jax.random.PRNGKey(0)
    shape = lambda h: (1, h, args.seq,  # noqa
                       args.head_dim if not interpret else 16)
    q, do = (jax.random.normal(jax.random.fold_in(key, i), shape(args.heads),
                               jnp.bfloat16) for i in (0, 1))
    k, v = (jax.random.normal(jax.random.fold_in(key, i),
                              shape(args.kv_heads), jnp.bfloat16)
            for i in (2, 3))
    blocks = [None] + [tuple(int(x) for x in b.split(","))
                       for b in args.blocks.split(";") if b]

    timed, off = _timer(args.iters), _off

    def oracle(window):
        """o, dq, dk, dv of ``mha_reference``, a group at a time (the dense
        scores of 8 heads at 8192 are 2 GB) or ``--oracle_heads`` of a
        group at a time, their dK and dV added up."""
        group = args.heads // args.kv_heads
        some = args.oracle_heads or group

        @jax.jit
        def one(qg, kg, vg, dog):
            f32 = [a.astype(jnp.float32) for a in (qg, kg, vg)]
            with jax.default_matmul_precision("highest"):
                o, back = jax.vjp(lambda q, k, v: F.mha_reference(
                    q, k, v, causal=_causal(window), window=window), *f32)
                return (o,) + back(dog.astype(jnp.float32))

        def of_group(i):
            end = (i + 1) * group
            subs = [one(q[:, h:min(h + some, end)], k[:, i:i + 1],
                        v[:, i:i + 1], do[:, h:min(h + some, end)])
                    for h in range(i * group, end, some)]
            o, dq, dk, dv = zip(*subs)
            return (jnp.concatenate(o, axis=1), jnp.concatenate(dq, axis=1),
                    sum(dk), sum(dv))

        parts = [of_group(i) for i in range(args.kv_heads)]
        return [jnp.concatenate(x, axis=1) for x in zip(*parts)]

    parent = probe.load_parent(args.parent) if args.parent else None
    for window in _windows(args):
        want = oracle(window)
        if args.forward:
            probe.forward_sweep(F, parent, q, k, v,
                                probe._blocks(args.fwd_blocks), timed,
                                want[0], causal=_causal(window),
                                window=window,
                                interpret=interpret)
            continue
        for blk in blocks:
            for impl in args.impls.split(","):
                kw = dict(causal=_causal(window), window=window, bwd_impl=impl,
                          interpret=interpret)
                if blk:
                    kw.update(block_q=blk[0], block_k=blk[1],
                              block_q_bwd=blk[2], block_k_bwd=blk[3])
                elif interpret:
                    kw.update(block_q=16, block_k=16)
                fwd = jax.jit(lambda q, k, v: F.flash_attention(q, k, v,
                                                                **kw))
                both = jax.jit(lambda q, k, v, do: jax.vjp(
                    lambda q, k, v: F.flash_attention(q, k, v, **kw),
                    q, k, v)[1](do))
                try:
                    row = {"window": window if _causal(window)
                           else str(window), "blocks": blk, "bwd": impl,
                           "fwd_ms": timed(fwd, q, k, v),
                           "fwd_bwd_ms": timed(both, q, k, v, do)}
                    mem = both.lower(q, k, v, do).compile().memory_analysis()
                    row["temp_gb"] = getattr(mem, "temp_size_in_bytes", 0) / 1e9
                    if blk is None:
                        got = (fwd(q, k, v),) + tuple(both(q, k, v, do))
                        row.update({f"{n}_rel": off(g, w) for n, g, w in zip(
                            ("o", "dq", "dk", "dv"), got, want)})
                except Exception as e:             # VMEM, HBM: say and go on
                    row = {"window": window, "blocks": blk, "bwd": impl,
                           "error": str(e)[:200]}
                print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
