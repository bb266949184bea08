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

    chiprun -- python3 tools/trinity_kernel_probe.py --forward --parent .scratch/parent
    JAX_PLATFORMS=cpu python3 tools/trinity_kernel_probe.py --aot
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

    def timed(fn, *a):
        jax.block_until_ready(fn(*a))
        t0 = time.perf_counter()
        for _ in range(args.iters):
            out = fn(*a)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / args.iters * 1e3

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

    def off(got, want):
        got = got.astype(jnp.float32)
        return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))

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
