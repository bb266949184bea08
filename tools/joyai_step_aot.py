"""Compiles the JoyAI-LLM-Flash cell's training step at its timed sizes for
a described v5e chip, with no chip (on-chip-measurement guide, section 2):
builds the cell as ``benchmark/models/joyai_llm_flash.py`` does, catches the
executor's first step before it runs, and hands its lowering (the Pallas
kernels and megablox as a TPU would take them: ``device.on_tpu`` is steered
here, in the tool) to the TPU compiler.  Prints the executable's temporaries,
arguments and the count of XLA's own rematerialised instructions
(``.remat`` in the compiled text), with and without ``--recompute``: whether
the step fits beside its state, and what fitting costs (PERF.md section 7,
row 31).  Nothing runs: no time comes from this.  The adapter has the
recomputing step only (the traffic file's); without ``--recompute`` this
tool puts a pass-through in ``RecomputeOptimizer``'s place.

    JAX_PLATFORMS=cpu python3 tools/joyai_step_aot.py [--recompute] [--layers N]

``--run``, on the chip: the same step handed to the chip's own compiler and
run once, to see the chip refuse or take what the compiler here refused or
took (prints whether it ran, the loss and the peak memory).

    chiprun -- python3 tools/joyai_step_aot.py --run
"""

import argparse
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tools"))


class _NoRecompute:
    """In ``RecomputeOptimizer``'s place: the optimizer itself, the
    checkpoints dropped."""

    def __init__(self, inner):
        self._inner = inner

    def _set_checkpoints(self, checkpoints):
        pass

    def __getattr__(self, name):
        return getattr(self._inner, name)


def run_on_chip(args):
    """The step built as the cell builds it, compiled by the chip's own
    compiler and run once."""
    import numpy as np
    from benchmark import harness
    from benchmark.models import joyai_llm_flash as adapter
    from paddle_tpu import optimizer as opt
    if not args.recompute:
        opt.RecomputeOptimizer = _NoRecompute
    config = harness.load_json("benchmark/configs/joyai_llm_flash.json")
    traffic = harness.load_traffic("lm_mtp_s8192")
    out = {"recompute": args.recompute, "ran": False}
    try:
        m = adapter.build_train(config, traffic, 7, 1, True)
        loss, = m["exe"].run(m["program"], feed=m["ring"][0],
                             fetch_list=[m["loss"]], scope=m["scope"])
        out.update(ran=True, loss=float(np.asarray(loss)),
                   peak_hbm_gb=harness.memory_peak_bytes() / 1e9)
    except Exception as e:                       # noqa: BLE001
        out["error"] = re.sub(r"\s+", " ", f"{type(e).__name__}: {e}")[:900]
    print(json.dumps(out), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "joyai_step_run.jsonl"),
              "a") as f:
        f.write(json.dumps(out) + "\n")
    return 0


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--recompute", action="store_true")
    ap.add_argument("--run", action="store_true")
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--seq", type=int, default=0)
    ap.add_argument("--dump", default="")
    args = ap.parse_args()
    if args.run:
        return run_on_chip(args)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    import paddle_tpu  # noqa: F401
    from paddle_tpu import device
    from paddle_tpu.ops import fused_ops
    from paddle_tpu.pallas import layer_norm
    import importlib
    flash = importlib.import_module("paddle_tpu.pallas.flash_attention")
    for mod in (device, fused_ops, layer_norm, flash):
        mod.on_tpu = lambda: True
    from benchmark import harness
    from benchmark.models import joyai_llm_flash as adapter
    import dp_arith_check

    config = harness.load_json("benchmark/configs/joyai_llm_flash.json")
    traffic = harness.load_traffic("lm_mtp_s8192")
    if not args.recompute:
        from paddle_tpu import optimizer as opt
        opt.RecomputeOptimizer = _NoRecompute
    if args.layers:
        config["num_hidden_layers"] = args.layers
    if args.seq:
        traffic["seq_len"] = args.seq
    m = adapter.build_train(config, traffic, 7, 1, False)
    cb, step_args = dp_arith_check.caught_step(lambda: m["exe"].run(
        m["program"], feed=m["ring"][0], fetch_list=[m["loss"]],
        scope=m["scope"], return_numpy=False))
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one), step_args)
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        compiled = cb.jitted.lower(*shapes).compile()
    except Exception as e:                       # noqa: BLE001
        print(json.dumps({"recompute": args.recompute, "compiles": False,
                          "error": re.sub(r"\s+", " ", str(e))[:700]}))
        return 1
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    if args.dump:
        with open(args.dump, "w") as f:
            f.write(text)
    print(json.dumps({
        "recompute": args.recompute, "compiles": True,
        "layers": config["num_hidden_layers"], "seq": traffic["seq_len"],
        "temp_gb": mem.temp_size_in_bytes / 1e9,
        "argument_gb": mem.argument_size_in_bytes / 1e9,
        "output_gb": mem.output_size_in_bytes / 1e9,
        "alias_gb": mem.alias_size_in_bytes / 1e9,
        "remat_instructions": len(re.findall(r"\.remat\d* = ", text)),
        "parameters_m": sum(int(np.prod(p.shape))
                            for p in m["parameters"]) / 1e6}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
