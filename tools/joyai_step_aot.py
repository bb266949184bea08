"""Compiles the JoyAI-LLM-Flash cell's training step at its timed sizes for
a described v5e chip, with no chip (on-chip-measurement guide, section 2):
builds the cell as ``benchmark/models/joyai_llm_flash.py`` does, catches the
executor's first step before it runs, and hands its lowering (the Pallas
kernels and megablox as a TPU would take them: ``device.on_tpu`` is steered
here, in the tool) to the TPU compiler.  Prints the executable's temporaries,
arguments, the count of XLA's own rematerialised instructions (``.remat`` in
the compiled text), ``reads_after_update`` (must be empty: a donated
parameter read again behind its optimizer update) and which backward kernel
each ``flash_attention_grad`` of the step got
(``paddle_tpu_flash_bwd_kernel_total``), the form each forward lowering
writes ``lse`` in (``paddle_tpu_flash_lowerings_total{lse}``) and the form each
``rope`` and ``rope_grad`` got (``paddle_tpu_rope_lowerings_total``, the
frequency-table form told from ``theta``'s) and, for ``--cell xing4``, the
hyper-connection ops' lowerings (``paddle_tpu_hc_lowerings_total``) and,
for ``--cell solar`` and ``--cell ling``, the chunked scan's
(``paddle_tpu_kda_lowerings_total``) and its gate's
(``paddle_tpu_kda_gate_lowerings_total``), and the routing groups of every
``moe_ffn`` lowering (``paddle_tpu_moe_lowerings_total{groups, gated,
slot_sum}``),
for ``--cell nemotron3`` the state-space scan's
(``paddle_tpu_ssd_lowerings_total``), and for every cell that holds one
every ``short_conv`` lowering's taps, form, bias and ``impl``
(``paddle_tpu_short_conv_lowerings_total``), with
and without ``--recompute``: whether the step fits beside its state, and what fitting
costs (PERF.md section 7, row 31).  Nothing runs: no time comes from this.  The adapter has the
recomputing step only (the traffic file's); without ``--recompute`` this
tool puts a pass-through in ``RecomputeOptimizer``'s place.

    JAX_PLATFORMS=cpu python3 tools/joyai_step_aot.py [--recompute] [--layers N]

``--run``, on the chip: the same step (``--cell``'s) handed to the chip's
own compiler and run once under a watchdog, to see the chip refuse, take or
never finish what the compiler here refused or took (prints whether it ran,
the loss and the peak memory; a compile that passes here is not a run).

    chiprun -- python3 tools/joyai_step_aot.py --cell xing4 --run

``--fingerprint``: the sha256 of the step's lowered text with its debug
info, which is what the persistent compile cache keys on; two processes
under different ``PYTHONHASHSEED`` must print the same one (ISSUE 47).

    PYTHONHASHSEED=1 python3 tools/joyai_step_aot.py --cell xing4 --fingerprint
"""

import argparse
import collections
import hashlib
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

    def _set_checkpoints(self, checkpoints, **kw):
        pass

    def __getattr__(self, name):
        return getattr(self._inner, name)


#: cell -> (configuration, which is also its adapter module; traffic mix)
CELLS = {"joyai": ("joyai_llm_flash", "lm_mtp_s8192"),
         "trinity": ("trinity_mini", "lm_s8192"),
         "olmoe": ("olmoe_1b_7b", "lm_s4096"),
         "smallthinker": ("smallthinker_21b_a3b", "lm_s16384"),
         "lfm2": ("lfm2_8b_a1b", "lm_s16384_r64"),
         "xing4": ("xing4_29b_a4b", "lm_s4096_r64"),
         "solar": ("solar_open2_250b", "lm_s8192_r64"),
         "ling": ("ling3_flash_vl", "lm_s8192_r64"),
         "nemotron3": ("nemotron3_nano_30b_a3b", "lm_s8192_r64"),
         "sdar": ("sdar_30b_a3b", "bd_s8192_b4_r64")}


def reads_after_update(text):
    """In a scheduled compiled step: the donated entry parameters (state
    aliased with an output) that some instruction reads again after the
    instruction that writes that output in place.  None is sound; one is a
    forward matmul that XLA rematerialised behind its weight's optimizer
    update, which then multiplies by the *updated* weight (PERF.md section
    6, PR 35: Trinity's step read one gradient leaf a third off)."""
    lines = text.split("\n")
    alias = {int(o): int(p) for o, p in
             re.findall(r"\{(\d+)\}: \((\d+), \{\}", lines[0])}
    entry = next(i for i, line in enumerate(lines)
                 if line.startswith("ENTRY"))
    end = next(i for i in range(entry, len(lines)) if lines[i] == "}")
    lines = lines[:end]
    defs, params, root = {}, {}, None
    for i in range(entry, end):
        m = re.match(r"\s*(ROOT )?%([\w.\-]+) = ", lines[i])
        if not m:
            continue
        defs[m.group(2)] = i
        p = re.search(r" parameter\((\d+)\)", lines[i])
        if p:
            params[int(p.group(1))] = m.group(2)
        root = i if m.group(1) else root
    outs = [o.strip().lstrip("%") for o in re.search(
        r"tuple\((.*)\)", re.sub(r"/\*index=\d+\*/", "", lines[root])
    ).group(1).split(", ")]
    found = []
    for out, pno in sorted(alias.items()):
        if outs[out] == params[pno]:
            continue
        gte = re.search(r"get-tuple-element\(%([\w.\-]+)\)",
                        lines[defs[outs[out]]])
        written = defs[gte.group(1)] if gte else defs[outs[out]]
        use = re.compile("%" + re.escape(params[pno]) + r"[,)]")
        late = [re.match(r"\s*(?:ROOT )?%([\w.\-]+) = ", lines[i]).group(1)
                for i in range(written + 1, len(lines))
                if use.search(lines[i])]
        if late:
            found.append({"parameter": params[pno], "read_by": late[0]})
    return found


def run_on_chip(args):
    """The step built as the cell builds it, compiled by the chip's own
    compiler and run once, under a watchdog: a step that never ends on the
    device (PR 63 met one: PERF.md section 7, row 45) leaves every thread's
    stack on standard error after ``--watchdog`` seconds and exits."""
    import faulthandler
    import importlib
    import numpy as np
    from benchmark import harness
    from paddle_tpu import optimizer as opt
    faulthandler.dump_traceback_later(args.watchdog, exit=True)
    if not args.recompute:
        opt.RecomputeOptimizer = _NoRecompute
    config_name, traffic_name = CELLS[args.cell]
    adapter = importlib.import_module("benchmark.models." + config_name)
    config = harness.load_json(f"benchmark/configs/{config_name}.json")
    traffic = harness.load_traffic(traffic_name)
    if args.recompute and "recompute" in traffic:
        traffic["recompute"] = True
    out = {"cell": args.cell, "recompute": args.recompute, "ran": False}
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
    ap.add_argument("--watchdog", type=int, default=420, help="--run: give "
                    "up after this many seconds and print where every "
                    "thread stood")
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--seq", type=int, default=0)
    ap.add_argument("--dump", default="")
    ap.add_argument("--error_chars", type=int, default=700, help="how much "
                    "of the compiler's refusal to print (its list of the "
                    "largest allocations at the peak is some 60000 long)")
    ap.add_argument("--lowered", default="", help="write the step's lowered "
                    "StableHLO text there, locations stripped, and compile "
                    "nothing: what two commits' steps are diffed by")
    ap.add_argument("--fingerprint", action="store_true", help="print the "
                    "sha256 of the step's lowered text WITH its debug info "
                    "(locations and pt.<role>/<op> scopes: what the "
                    "persistent compile cache keys on, which --lowered "
                    "strips) and compile nothing: run it under two "
                    "PYTHONHASHSEEDs, two processes must print one key")
    ap.add_argument("--cell", default="joyai", choices=sorted(CELLS),
                    help="the other cell that runs moe_ffn's held path: "
                    "trinity, or the third that runs the flash kernels: "
                    "olmoe (their steps have no recomputation: leave "
                    "--recompute out, as for smallthinker and lfm2; lfm2's "
                    "adapter builds ISSUE 40's fallback where the traffic "
                    "says recompute, which --recompute sets, and so does "
                    "xing4's, whose timed step is the plain one; solar's "
                    "timed step recomputes: pass --recompute for it, and "
                    "leave it out to see the plain step refused; ling's, "
                    "nemotron3's and sdar's likewise)")
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
    from paddle_tpu.ops import attention_ops
    import importlib
    flash = importlib.import_module("paddle_tpu.pallas.flash_attention")
    for mod in (device, flash):
        mod.on_tpu = lambda: True
    from benchmark import harness
    import dp_arith_check

    config_name, traffic_name = CELLS[args.cell]
    adapter = importlib.import_module("benchmark.models." + config_name)
    config = harness.load_json(f"benchmark/configs/{config_name}.json")
    traffic = harness.load_traffic(traffic_name)
    if not args.recompute:
        from paddle_tpu import optimizer as opt
        opt.RecomputeOptimizer = _NoRecompute
    if args.layers:
        config["num_hidden_layers"] = args.layers
    if args.seq:
        traffic["seq_len"] = args.seq
    if args.recompute and args.cell in ("lfm2", "xing4", "solar", "ling",
                                        "nemotron3", "sdar"):
        traffic["recompute"] = True      # the adapter builds the fallback
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

    def counted(counter, *names):
        """A counter's series that this process moved, by those labels."""
        return {"/".join(labels[n] for n in names): int(cell.get())
                for labels, cell in counter.series() if cell.get()}

    def flash_fwd_lse():
        """The step's forward lowerings (a recomputed clone counts) by the
        form lse leaves the kernel in, window and widths."""
        return counted(attention_ops.FLASH_LOWERINGS_CTR,
                       "lse", "window", "widths")

    def rope_lowerings():
        """The step's rope and rope_grad lowerings (a recomputed clone
        counts) by form (kernel | xla), pairing, width and where the
        frequencies come from (theta | table)."""
        return counted(attention_ops.ROPE_LOWERINGS_CTR,
                       "form", "pairing", "width", "frequencies")

    def hc_lowerings():
        """The step's hc_pre / hc_post lowerings and their grads' (a
        recomputed clone counts) by op, streams, iterations and form."""
        from paddle_tpu.ops import hc_ops
        return counted(hc_ops.HC_LOWERINGS_CTR, "op", "n", "sinkhorn_iters",
                       "impl")

    def kda_lowerings():
        """The step's kda_scan and kda_scan_grad lowerings (a recomputed
        clone counts) by heads, width, chunk, form and beta's doubling."""
        from paddle_tpu.ops import kda_ops
        return counted(kda_ops.KDA_LOWERINGS_CTR, "heads", "head_dim",
                       "chunk", "impl", "neg_eigval")

    def kda_gate_lowerings():
        """The step's kda_gate lowerings (its grad op's vjp and a recomputed
        clone count) by the gate's form and the rank of what feeds it."""
        from paddle_tpu.ops import kda_ops
        return counted(kda_ops.KDA_GATE_LOWERINGS_CTR, "form", "rank")

    def moe_groups():
        """The step's moe_ffn forward lowerings (a recomputed clone counts)
        by the experts routed over, those held, the routing groups,
        whether the experts are gated (three grouped matmuls) or not, and
        the order the un-sorts' gather brings a token's slots home in."""
        from paddle_tpu.ops import moe_ops
        return counted(moe_ops.MOE_LOWERINGS_CTR, "experts", "held",
                       "groups", "gated", "slot_sum")

    def ssd_lowerings():
        """The step's ssd_scan and ssd_scan_grad lowerings (a recomputed
        clone counts) by form and chunk."""
        from paddle_tpu.ops import ssd_ops
        return counted(ssd_ops.SSD_LOWERINGS_CTR, "impl", "chunk")

    def short_conv_lowerings():
        """The step's short_conv and short_conv_grad lowerings (a recomputed
        clone counts) by taps, gate, activation, bias and what implements
        it (pallas: the kernel pair of ``pallas/short_conv.py``)."""
        from paddle_tpu.ops import sequence_ops
        return counted(sequence_ops.SHORT_CONV_LOWERINGS_CTR, "taps",
                       "gated", "act", "bias", "impl")
    if args.fingerprint:
        text = cb.jitted.lower(*shapes).as_text(debug_info=True)
        print(json.dumps({
            "cell": args.cell, "layers": config["num_hidden_layers"],
            "seq": traffic["seq_len"], "bytes": len(text),
            "hash_seed": os.environ.get("PYTHONHASHSEED", "random"),
            "fingerprint": hashlib.sha256(text.encode()).hexdigest()}))
        return 0
    if args.lowered:
        text = re.sub(r"loc\(.*?\)", "", cb.jitted.lower(*shapes).as_text())
        with open(args.lowered, "w") as f:
            f.write(text)
        # what each flash_fwd call of the step returns: (Out, lse), the
        # latter [bh, 1, Tq] rows or the [bh, Tq, 128] lane broadcast
        results = collections.Counter(
            line.rsplit("->", 1)[1].strip() for line in text.split("\n")
            if "custom_call" in line and '"flash_fwd"' in line)
        print(json.dumps({"cell": args.cell, "lowered": args.lowered,
                          "bytes": len(text), "flash_fwd_results": results,
                          "flash_fwd_lse": flash_fwd_lse(),
                          "rope_lowerings": rope_lowerings(),
                          "hc_lowerings": hc_lowerings()}))
        return 0
    try:
        compiled = cb.jitted.lower(*shapes).compile()
    except Exception as e:                       # noqa: BLE001
        print(json.dumps({"recompute": args.recompute, "compiles": False,
                          "error": re.sub(r"\s+", " ",
                                          str(e))[:args.error_chars]}))
        return 1
    # the plan through the function the executor's hook calls where a block
    # compiles (hbm.compiled_plan; PR 51): what this prints with no chip is
    # what the chip's hbm_step_* per-layer metrics read
    from paddle_tpu import hbm, memory
    from paddle_tpu.framework.executor import _resolve_hbm_info
    mem, classes = hbm.compiled_plan(
        compiled, shapes, (cb.feed_names, cb.persist_ro, cb.persist_rw),
        _resolve_hbm_info(cb, m["program"], step_args[0]))
    plan = dict(memory.plan_parts(mem), argument_classes=classes)
    print("plan: " + memory.format_plan(plan), file=sys.stderr)
    text = compiled.as_text()
    if args.dump:
        with open(args.dump, "w") as f:
            f.write(text)
    print(json.dumps({
        "cell": args.cell, "recompute": args.recompute, "compiles": True,
        "layers": config["num_hidden_layers"], "seq": traffic["seq_len"],
        "temp_gb": mem.temp_size_in_bytes / 1e9,
        "peak_gb": mem.peak_memory_in_bytes / 1e9,
        "argument_gb": mem.argument_size_in_bytes / 1e9,
        "output_gb": mem.output_size_in_bytes / 1e9,
        "alias_gb": mem.alias_size_in_bytes / 1e9,
        # the four per-layer readings' parts, as the chip's line names them
        "hbm_step_arguments_gb": plan["argument_bytes"] / 1e9,
        "hbm_step_temporaries_gb": plan["temp_bytes"] / 1e9,
        "hbm_step_unaliased_outputs_gb":
            (plan["output_bytes"] - plan["alias_bytes"]) / 1e9,
        "code_gb": plan["generated_code_bytes"] / 1e9,
        "arguments_by_class_gb": {c: n / 1e9 for c, n in classes.items()},
        "remat_instructions": len(re.findall(r"\.remat\d* = ", text)),
        "reads_after_update": reads_after_update(text),
        # which backward each flash_attention_grad lowering of the step got
        "flash_bwd_kernels": counted(attention_ops.FLASH_BWD_KERNEL_CTR,
                                     "kernel", "window", "widths"),
        "flash_fwd_lse": flash_fwd_lse(),
        # every flash lowering by mask form, block length, implementation
        # and kernel, and under a mask form its tile pairs by their fate
        "flash_masks": counted(attention_ops.FLASH_MASK_LOWERINGS_CTR,
                               "mask", "block", "impl", "kernel"),
        "flash_tile_pairs": counted(attention_ops.FLASH_TILE_PAIRS_CTR,
                                    "mask", "pass", "state"),
        # the masked tile pairs' sub-tiles by what the kernels run of them;
        # "whole": masked tile pairs that run all of their scores (PR 62)
        "flash_subtiles": counted(attention_ops.FLASH_SUBTILES_CTR,
                                  "mask", "pass", "state"),
        "rope_lowerings": rope_lowerings(),
        "hc_lowerings": hc_lowerings(),
        "kda_lowerings": kda_lowerings(),
        "kda_gate_lowerings": kda_gate_lowerings(),
        "moe_groups": moe_groups(),
        "ssd_lowerings": ssd_lowerings(),
        "short_conv_lowerings": short_conv_lowerings(),
        "parameters_m": sum(int(np.prod(p.shape))
                            for p in m["parameters"]) / 1e6}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
