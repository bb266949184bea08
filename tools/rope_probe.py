"""The rotary embedding alone at the cells' shapes: the jnp form XLA lowers
(``ops/attention_ops.py:_rope_xla`` and ``jax.vjp`` of it) against the kernel
(``pallas/rope.py``), forward and gradient.

On the chip: the time of a call (ten chained in one jitted loop, the median of
five), the bytes of the tensor in and out over that time, and whether both
forms gave the same bits.

    chiprun -- python3 tools/rope_probe.py [--shapes trinity_q,...]
        [--blocks 1048576x512,2097152x1024,...]

``--blocks``: the kernel again under other ``_BLOCK_BYTES x _MAX_BLOCK_T``.

``--aot``, here, nothing run: both forms compiled for a described v5e; the
compiled module's ``bytes accessed`` (XLA's own instructions: a custom call
counts for nothing there, so the kernel's row adds the tensor in and out and
its two table blocks by hand), its temporaries and its instructions by kind.

    JAX_PLATFORMS=cpu python3 tools/rope_probe.py --aot
"""

import argparse
import collections
import json
import os
import re
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: name -> (shape, head_dim, interleaved, calls a step): what each cell's
#: step hands the op, bf16
SHAPES = {
    "trinity_q": ((1, 32, 8192, 128), 128, False, 8),
    "trinity_k": ((1, 4, 8192, 128), 128, False, 8),
    "joyai_q_rope": ((1, 32, 8192, 64), 64, True, 17),
    "joyai_k_r": ((1, 1, 8192, 64), 64, True, 17),
    "olmoe_q": ((4, 4096, 2048), 128, False, 4),
    "lfm2_q": ((1, 32, 16384, 64), 64, False, 2),
    "lfm2_k": ((1, 8, 16384, 64), 64, False, 2),
    "smallthinker_q": ((1, 28, 16384, 128), 128, False, 8),
    "smallthinker_k": ((1, 4, 16384, 128), 128, False, 8),
}
THETA = 10000.0
CHAIN = 10


def forms(head_dim, interleaved):
    """name -> the function of one tensor."""
    import jax
    from paddle_tpu.ops.attention_ops import _rope_xla
    from paddle_tpu.pallas import rope as kernel

    def xla(x):
        return _rope_xla(x, head_dim, THETA, interleaved)
    return {
        "xla_fwd": xla,
        "xla_bwd": lambda g: jax.vjp(xla, g)[1](g)[0],
        "kernel_fwd": lambda x: kernel.rope(x, head_dim, THETA, interleaved),
        "kernel_bwd": lambda g: kernel.rope(g, head_dim, THETA, interleaved,
                                            transpose=True)}


def aot(names):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", False)
    for name in names:
        shape, dh, interleaved, _ = SHAPES[name]
        x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one)
        tensor = 2 * 2 * int(np.prod(shape))
        for form, fn in forms(dh, interleaved).items():
            compiled = jax.jit(fn).lower(x).compile()
            text = compiled.as_text()
            entry = text[text.index("ENTRY"):]
            kinds = collections.Counter(re.findall(
                r" = \S+ (fusion|copy|custom-call|convert|transpose)\(",
                entry))
            cost = compiled.cost_analysis()
            cost = cost[0] if isinstance(cost, list) else cost
            accessed = cost.get("bytes accessed", 0.0)
            if form.startswith("kernel"):
                accessed += tensor + 2 * 4 * shape[-2] * dh
            print(json.dumps({
                "shape": name, "form": form, "tensor_in_out_mb": tensor / 1e6,
                "bytes_accessed_mb": round(accessed / 1e6, 1),
                "temporaries_mb": round(
                    compiled.memory_analysis().temp_size_in_bytes / 1e6, 1),
                "instructions": dict(kinds)}), flush=True)


def on_chip(names, blocks):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.pallas import rope as kernel
    if jax.default_backend() != "tpu":
        sys.exit("rope_probe: no TPU (use --aot here)")
    rows = []
    blocks = [(kernel._BLOCK_BYTES, kernel._MAX_BLOCK_T)] + blocks

    def timed(fn, x):
        loop = jax.jit(lambda v: jax.lax.fori_loop(
            0, CHAIN, lambda _, a: fn(a), v))
        loop(x).block_until_ready()
        took = []
        for _ in range(5):
            t0 = time.perf_counter()
            loop(x).block_until_ready()
            took.append((time.perf_counter() - t0) / CHAIN)
        return statistics.median(took) * 1e3

    def row_of(name, form, fn, x, **more):
        shape, _, _, calls = SHAPES[name]
        tensor = 2 * 2 * int(np.prod(shape))
        row = dict(shape=name, form=form, **more)
        try:
            ms = timed(fn, x)
        except Exception as e:                           # noqa: BLE001
            row["error"] = re.sub(r"\s+", " ", str(e))[:300]
        else:
            row.update(ms=round(ms, 4), gb_per_s=round(tensor / ms / 1e6, 1),
                       ms_a_step=round(ms * calls, 3))
        rows.append(row)
        print(json.dumps(row), flush=True)

    for name in names:
        shape, dh, interleaved, _ = SHAPES[name]
        x = jax.random.normal(jax.random.PRNGKey(3), shape,
                              jnp.float32).astype(jnp.bfloat16)
        fns = forms(dh, interleaved)
        once = {form: np.asarray(jax.jit(fn)(x).astype(jnp.float32))
                for form, fn in fns.items()}
        for d in ("fwd", "bwd"):
            row_of(name, "xla_" + d, fns["xla_" + d], x)
        for bb, mt in blocks:
            kernel._BLOCK_BYTES, kernel._MAX_BLOCK_T = bb, mt
            kernel._call.cache_clear()
            for d in ("fwd", "bwd"):
                diff = once["kernel_" + d] != once["xla_" + d]
                row_of(name, "kernel_" + d, fns["kernel_" + d], x,
                       block_bytes=bb, max_block_t=mt,
                       blocks=kernel.blocks(*shape[-3:], 2),
                       bits_differ=int(diff.sum()))
        kernel._BLOCK_BYTES, kernel._MAX_BLOCK_T = blocks[0]
        kernel._call.cache_clear()
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "rope_probe.jsonl"),
              "a") as f:
        for row in rows:
            f.write(json.dumps(row) + "\n")


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--aot", action="store_true")
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--blocks", default="")
    args = ap.parse_args()
    names = args.shapes.split(",")
    if args.aot:
        return aot(names)
    blocks = [tuple(int(n) for n in b.split("x"))
              for b in args.blocks.split(",") if b]
    return on_chip(names, blocks)


if __name__ == "__main__":
    sys.exit(main())
