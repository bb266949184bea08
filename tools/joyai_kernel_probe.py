"""Times the flash attention kernels alone at the JoyAI-LLM-Flash cell's
shapes on the chip: latent attention's [1, 32, 8192, 192] queries and keys
over [1, 32, 8192, 128] values in bf16, the causal half, forward and
forward + backward, over block sizes and the two backward kernels (fused,
split); and what the rotary key costs as the program ships it:
the 64-wide rotary key is one head
for all 32, broadcast and concatenated behind each head's 128-wide content
part outside the kernel (``build``: Q's and K's concatenation, forward, and
their transposes, backward, as XLA fuses them alone), which is the most a
kernel that took the score as two products could save.  Since PR 48 the
kernels take it so (``q_rope=``, ``k_rope=``), and the ``pieces`` rows time
both forms from the same four pieces at ``--piece_seqs`` (JoyAI's 8192 and
Xing4.0's 4096): ``build + one product`` (the concatenation and broadcast,
then the kernels at 192 | 128, differentiated back to the pieces) against
``two products`` (the kernels on the pieces), forward and forward + backward,
each with its distances from ``mha_reference``.  ``128/128`` rows are
the same kernels at Trinity's width, for the rate.  Prints one JSON line per
case: forward ms, forward + backward ms, the backward's temporaries and, for
the block tables' own choice, how far the output and the gradients are from
``mha_reference`` (float32 at ``highest`` over the same bf16 inputs, eight
heads at a time).

    chiprun -- python3 tools/joyai_kernel_probe.py
    JAX_PLATFORMS=cpu python3 tools/joyai_kernel_probe.py --aot   # compiles
        each block choice for a described v5e, runs nothing: which fit VMEM,
        which backward the entry point runs, the VMEM limit each call asks
        for and, for the forward, the least limit that compiles the row;
        then the same rows for the two-product kernels (``"rope": 64``)

``--forward`` (PR 39) times the forward half alone (``flash_attention_fwd``:
the kernel and what hands ``lse`` on) over ``--fwd_blocks``, the 2048-wide
rows among them, each against the dense oracle's output; with ``--parent
PATH`` (another checkout of this repo, e.g. ``git archive`` of the parent
commit unpacked in an ignored directory) the same rows through that
checkout's kernel too, and whether ``Out`` and ``Lse`` are its bits.

    chiprun -- python3 tools/joyai_kernel_probe.py --forward --parent .scratch/parent
"""

import argparse
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

FWD_BLOCKS = ("512,1024;1024,1024;512,512;1024,512;256,1024;512,2048;"
              "1024,2048;2048,1024;2048,512;2048,2048")
# "fused" keeps nothing in HBM that grows with T² and asks for the VMEM its
# shapes need (PR 37), which is what let the 1024 x 1024 and 2048-wide blocks
# compile at all
BWD_BLOCKS = ("fused,1024,512;fused,512,512;fused,1024,1024;fused,512,1024;"
              "fused,2048,512;fused,512,2048;fused,1024,256;fused,256,1024;"
              "split,1024,512;split,512,512;"
              "split,512,1024;split,1024,1024")


def _blocks(text):
    return [tuple(x if i == 0 and not x.isdigit() else int(x)
                  for i, x in enumerate(b.split(",")))
            for b in text.split(";") if b]


def load_parent(path):
    """``pallas/flash_attention.py`` of another checkout as a module beside
    this tree's (its relative imports are this tree's ``paddle_tpu``)."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "paddle_tpu.pallas._parent_flash_attention", os.path.join(
            path, "paddle_tpu", "pallas", "flash_attention.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def forward_sweep(F, P, q, k, v, rows, timed, want_o=None, **kw):
    """One JSON line a block row: ``flash_attention_fwd`` of this tree timed
    alone, how far its output is from the oracle's ``want_o`` and, with the
    parent's module ``P``, the parent's time and whether ``Out`` and ``Lse``
    are the parent's to the bit (a row the parent's kernel cannot compile
    says so and compares nothing)."""
    import jax
    import jax.numpy as jnp

    def half(mod, bq, bk):
        return jax.jit(lambda q, k, v: mod.flash_attention_fwd(
            q, k, v, block_q=bq, block_k=bk, **kw))
    for bq, bk in rows:
        row = {"case": "fwd", "blocks": [bq, bk], "window": kw.get("window")}
        try:
            fwd = half(F, bq, bk)
            row["fwd_ms"] = timed(fwd, q, k, v)
            o, lse = fwd(q, k, v)
            if want_o is not None:
                row["o_rel"] = float(
                    jnp.linalg.norm(o.astype(jnp.float32) - want_o)
                    / jnp.linalg.norm(want_o))
        except Exception as e:                     # VMEM: say and go on
            row["error"] = str(e).strip().splitlines()[-1][:200]
            print(json.dumps(row), flush=True)
            continue
        if P is not None:
            try:
                was = half(P, bq, bk)
                row["parent_fwd_ms"] = timed(was, q, k, v)
                po, plse = was(q, k, v)
                row["out_bits_equal"] = bool(jnp.array_equal(o, po))
                row["lse_bits_equal"] = bool(jnp.array_equal(lse, plse))
            except Exception as e:
                row["parent_error"] = str(e).strip().splitlines()[-1][:200]
        print(json.dumps(row), flush=True)


#: MiB of scoped VMEM tried in turn for a forward row (Mosaic's default: 16)
VMEM_LADDER = (8, 12, 16, 20, 24, 28, 32, 40, 48, 64, 96)


def aot_forward(F, rows, lower):
    """For each forward block row: does it compile under the limit the call
    asks for (``_fwd_vmem_bytes``), and the least of ``VMEM_LADDER`` that
    compiles it.  ``lower(bq, bk)`` lowers a fresh jit of the forward for a
    described chip."""
    asks = F._fwd_vmem_bytes
    for bq, bk in rows:
        row = {"fwd": [bq, bk]}
        limits = []

        def spy(*a, **kw):
            limits.append(asks(*a, **kw))
            return limits[-1]
        F._fwd_vmem_bytes = spy
        try:
            lower(bq, bk).compile()
            row["compiles"] = True
        except Exception as e:
            row.update(compiles=False,
                       error=str(e).strip().splitlines()[-1][:160])
        row["vmem_limit_mib"] = limits[-1] / 2 ** 20
        row["least_mib"] = None
        for mib in VMEM_LADDER:
            F._fwd_vmem_bytes = lambda *a, **kw: mib << 20
            try:
                lower(bq, bk).compile()
                row["least_mib"] = mib
                break
            except Exception:
                pass
        F._fwd_vmem_bytes = asks
        print(json.dumps(row), flush=True)


def aot(args, F):
    """Every block choice compiled for a described v5e chip (nothing runs):
    the Mosaic compiler's verdict on the VMEM a block takes."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    bh, t = args.heads, args.seq

    def s(w, dtype=jnp.dtype(args.dtype)):
        return jax.ShapeDtypeStruct((bh, t, w), dtype, sharding=one)
    sm = args.d_qk ** -0.5
    lse = jax.ShapeDtypeStruct((bh, t), jnp.float32, sharding=one)
    # the score as one product at d_qk, then as two at d_qk - d_rope + d_rope
    # with ONE rotary key head for all (the collapsed [1, t, d_rope])
    for d_r in (0, args.d_rope):
        d = args.d_qk - d_r
        rope = {}
        if d_r:
            print(json.dumps({"rope": d_r, "widths": f"{d}+{d_r}/{args.d_v}"}),
                  flush=True)
            rope = dict(q_rope=s(d_r), k_rope=jax.ShapeDtypeStruct(
                (1, t, d_r), jnp.dtype(args.dtype), sharding=one))
        aot_forward(F, _blocks(args.fwd_blocks), lambda bq, bk: jax.jit(
            lambda q, k, v, **r: F._flash_fwd_pallas(
                q, k, v, None, True, sm, bq, bk, 0, False, **r)
        ).lower(s(d), s(d), s(args.d_v), **rope))
        if args.forward:
            continue
        for impl, bq, bk in _blocks(args.bwd_blocks):
            fn = jax.jit(lambda q, k, v, o, lse, do, **r: F._flash_bwd_pallas(
                q, k, v, o, lse, do, True, sm, bq, bk, 0, False, impl=impl,
                **r))
            try:
                c = fn.lower(s(d), s(d), s(args.d_v), s(args.d_v), lse,
                             s(args.d_v), **rope).compile()
                row = {"bwd": [impl, bq, bk], "compiles": True, "temp_gb":
                       c.memory_analysis().temp_size_in_bytes / 1e9}
            except Exception as e:
                row = {"bwd": [impl, bq, bk], "compiles": False,
                       "error": str(e).strip().splitlines()[-1][:160]}
            # what the entry point runs for this request, and the VMEM it
            # asks for (the split kernels: Mosaic's default 16 MiB)
            row["runs"] = F._bwd_kernel_name(s(d), s(d), s(args.d_v), bq, bk,
                                             impl, d_r)
            row["vmem_limit_mib"] = F._fused_vmem_bytes(
                t, d, args.d_v, bq, bk, jnp.dtype(args.dtype).itemsize, d_r
            ) / 2 ** 20 if row["runs"] == "fused" else 16
            print(json.dumps(row), flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--d_qk", type=int, default=192)
    ap.add_argument("--d_v", type=int, default=128)
    ap.add_argument("--d_rope", type=int, default=64)
    ap.add_argument("--fwd_blocks", default=FWD_BLOCKS)
    ap.add_argument("--bwd_blocks", default=BWD_BLOCKS)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--piece_seqs", default="8192,4096",
                    help="lengths of the pieces rows (build + one product "
                    "against two products); empty: none")
    ap.add_argument("--pieces_only", action="store_true",
                    help="the pieces rows and nothing else")
    ap.add_argument("--aot", action="store_true")
    ap.add_argument("--forward", action="store_true",
                    help="the forward half alone over --fwd_blocks")
    ap.add_argument("--parent", default="", help="--forward: another "
                    "checkout of this repo whose forward runs the same rows, "
                    "Out and Lse compared to the bit")
    ap.add_argument("--dtype", default="bfloat16",
                    help="--aot: the inputs' type (the cell's float32 "
                    "forward check runs the kernels on float32)")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import paddle_tpu  # noqa: F401
    F = importlib.import_module("paddle_tpu.pallas.flash_attention")
    if args.aot:
        return aot(args, F)
    interpret = jax.default_backend() != "tpu"
    if interpret:                                  # a rehearsal of the path
        args.seq, args.heads, args.iters = 64, 4, 1
        args.d_qk, args.d_v, args.d_rope = 24, 16, 8
        args.fwd_blocks, args.bwd_blocks = "16,16", "fused,16,16"
        args.piece_seqs = "64,32"
    h, t = args.heads, args.seq
    d_nope = args.d_qk - args.d_rope
    key = jax.random.PRNGKey(0)

    def rand(i, heads, w):
        return jax.random.normal(jax.random.fold_in(key, i),
                                 (1, heads, t, w), jnp.bfloat16)
    q_nope, q_rope = rand(0, h, d_nope), rand(1, h, args.d_rope)
    k_nope, k_rope = rand(2, h, d_nope), rand(3, 1, args.d_rope)
    v, do = rand(4, h, args.d_v), rand(5, h, args.d_v)
    sm = args.d_qk ** -0.5

    def build(q_nope, q_rope, k_nope, k_rope):
        """Q and K as the kernel reads them: the program's concat and
        broadcast."""
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, k_nope.shape[:3]
                                      + (args.d_rope,))], axis=-1)
        return q, k
    q, k = jax.jit(build)(q_nope, q_rope, k_nope, k_rope)

    def timed(fn, *a):
        jax.block_until_ready(fn(*a))
        t0 = time.perf_counter()
        for _ in range(args.iters):
            out = fn(*a)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / args.iters * 1e3

    def say(row, fn):
        try:
            fn(row)
        except Exception as e:                     # VMEM, HBM: say and go on
            row["error"] = str(e).strip().splitlines()[-1][:200]
        print(json.dumps(row), flush=True)

    def pieces_rows(t):
        """Both forms from the same four pieces at length ``t``: forward and
        forward + backward (to the pieces' gradients), and how far each is
        from ``mha_reference`` (float32 at ``highest``, eight heads at a time,
        the rotary key's gradient summed over all)."""
        p = [a[:, :, :t] for a in (q_nope, q_rope, k_nope, k_rope, v)]
        dot = do[:, :, :t]
        kw = dict(causal=True, sm_scale=sm, interpret=interpret)
        if interpret:
            kw.update(block_q=16, block_k=16)

        def one(qn, qr, kn, kr, v):
            return F.flash_attention(*build(qn, qr, kn, kr), v, **kw)

        def two(qn, qr, kn, kr, v):
            return F.flash_attention(qn, kn, v, q_rope=qr, k_rope=kr, **kw)

        @jax.jit
        def oracle_part(qn, qr, kn, kr, v, dog):
            f32 = [a.astype(jnp.float32) for a in (qn, qr, kn, kr, v)]
            with jax.default_matmul_precision("highest"):
                o, back = jax.vjp(
                    lambda qn, qr, kn, kr, v: F.mha_reference(
                        qn, kn, v, causal=True, sm_scale=sm, q_rope=qr,
                        k_rope=kr), *f32)
                return (o,) + back(dog.astype(jnp.float32))
        g = min(8, h)
        parts = [oracle_part(p[0][:, i:i + g], p[1][:, i:i + g],
                             p[2][:, i:i + g], p[3], p[4][:, i:i + g],
                             dot[:, i:i + g]) for i in range(0, h, g)]
        want = [sum(x) if n == 4 else jnp.concatenate(x, axis=1)
                for n, x in enumerate(zip(*parts))]   # 4: dk_rope, summed
        names = ("o", "dq_nope", "dq_rope", "dk_nope", "dk_rope", "dv")
        for case, f in (("build + one product", one), ("two products", two)):
            def row_of(row, f=f):
                fwd = jax.jit(f)
                both = jax.jit(lambda *a: jax.vjp(f, *a[:5])[1](a[5]))
                row["fwd_ms"] = timed(fwd, *p)
                row["fwd_bwd_ms"] = timed(both, *p, dot)
                row["temp_gb"] = both.lower(*p, dot).compile(
                ).memory_analysis().temp_size_in_bytes / 1e9
                got = (fwd(*p),) + tuple(both(*p, dot))
                row.update({f"{n}_rel": off(a, w)
                            for n, a, w in zip(names, got, want)})
            say({"case": case, "pieces": [h, t, f"{d_nope}+{args.d_rope}",
                                          args.d_v]}, row_of)

    def off(got, want):
        got = got.astype(jnp.float32)
        return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))

    if not args.forward:
        for t_piece in (int(x) for x in args.piece_seqs.split(",") if x):
            pieces_rows(t_piece)
    if args.pieces_only:
        return

    # what building Q and K costs alone, forward and with its transpose
    def build_row(row):
        dq, dk = jnp.ones_like(q), jnp.ones_like(k)
        row["fwd_ms"] = timed(jax.jit(build), q_nope, q_rope, k_nope, k_rope)
        row["fwd_bwd_ms"] = timed(jax.jit(lambda *a: jax.vjp(build, *a[:4])[
            1](a[4:])), q_nope, q_rope, k_nope, k_rope, dq, dk)
        row["bytes_written_fwd"] = int(q.size + k.size) * 2
    if not args.forward:
        say({"case": "build q and k"}, build_row)

    def attn(kw):
        return lambda q, k, v: F.flash_attention(
            q, k, v, causal=True, sm_scale=sm, interpret=interpret, **kw)

    def vjp_of(f):
        return jax.jit(lambda q, k, v, do: jax.vjp(f, q, k, v)[1](do))

    def oracle():
        @jax.jit
        def one(qg, kg, vg, dog):
            f32 = [a.astype(jnp.float32) for a in (qg, kg, vg)]
            with jax.default_matmul_precision("highest"):
                o, back = jax.vjp(lambda q, k, v: F.mha_reference(
                    q, k, v, causal=True, sm_scale=sm), *f32)
                return (o,) + back(dog.astype(jnp.float32))
        g = min(8, h)
        parts = [one(*(a[:, i:i + g] for a in (q, k, v, do)))
                 for i in range(0, h, g)]
        return [jnp.concatenate(x, axis=1) for x in zip(*parts)]

    if args.forward:
        rows = _blocks(args.fwd_blocks)
        return forward_sweep(
            F, load_parent(args.parent) if args.parent else None, q, k, v,
            rows, timed, oracle()[0], causal=True, sm_scale=sm,
            interpret=interpret)

    # the tables' own choice, against the oracle
    def default_row(row):
        kw = dict(block_q=16, block_k=16) if interpret else {}
        fwd, both = jax.jit(attn(kw)), vjp_of(attn(kw))
        row["fwd_ms"] = timed(fwd, q, k, v)
        row["fwd_bwd_ms"] = timed(both, q, k, v, do)
        row["temp_gb"] = both.lower(q, k, v, do).compile(
        ).memory_analysis().temp_size_in_bytes / 1e9
        got = (fwd(q, k, v),) + tuple(both(q, k, v, do))
        row.update({f"{n}_rel": off(g, w) for n, g, w in zip(
            ("o", "dq", "dk", "dv"), got, oracle())})
    say({"case": "tables", "d_qk": args.d_qk, "d_v": args.d_v}, default_row)

    for bq, bk in _blocks(args.fwd_blocks):
        say({"case": "fwd", "blocks": [bq, bk]}, lambda row: row.update(
            fwd_ms=timed(jax.jit(attn(dict(block_q=bq, block_k=bk))),
                         q, k, v)))
    fq, fk = _blocks(args.fwd_blocks)[0]
    for impl, bq, bk in _blocks(args.bwd_blocks):
        def bwd_row(row):
            both = vjp_of(attn(dict(block_q=fq, block_k=fk, block_q_bwd=bq,
                                    block_k_bwd=bk, bwd_impl=impl)))
            row["fwd_bwd_ms"] = timed(both, q, k, v, do)
            row["temp_gb"] = both.lower(q, k, v, do).compile(
            ).memory_analysis().temp_size_in_bytes / 1e9
        say({"case": "bwd", "fwd_blocks": [fq, fk], "bwd": impl,
             "blocks": [bq, bk]}, bwd_row)

    # the same kernels at 128/128 (Trinity's width, every head its own K/V)
    if not interpret:
        def narrow(row):
            q8, k8 = q[..., :128], k[..., :128]
            f = lambda q, k, v: F.flash_attention(q, k, v, causal=True)  # noqa
            row["fwd_ms"] = timed(jax.jit(f), q8, k8, v)
            row["fwd_bwd_ms"] = timed(vjp_of(f), q8, k8, v, do)
        say({"case": "tables", "d_qk": 128, "d_v": 128}, narrow)


if __name__ == "__main__":
    main()
