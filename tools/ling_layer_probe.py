"""Where the Ling cell's float32 forward program leaves its reference: the
program's block outputs (the recompute boundaries of ``build_ling_pretrain``)
against the reference's, block by block, on the timed sequence at published
widths, float32 at ``highest`` on both sides.  ``--xla_scan``: the scan by
``kda_chunked``'s plain ``jax.numpy`` in the kernels' place (``pallas/kda.fits``
answers no), to tell the kernels from the chunked form.

    chiprun -- python3 tools/ling_layer_probe.py --seed 7 [--xla_scan]
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--xla_scan", action="store_true")
    ap.add_argument("--seq", type=int, default=0)
    ap.add_argument("--exp", action="store_true", help="the reference's "
                    "decay as exp(g) a step, as it was first written, in "
                    "place of 1 + expm1(g)")
    ap.add_argument("--stages", action="store_true", help="block 0's KDA "
                    "sublayer stage by stage: the convolution's output, the "
                    "log-decay, beta, the scan's output, each against the "
                    "reference's from the same (exact) embedding, and the "
                    "scan's output against the reference's recurrence over "
                    "the PROGRAM's own q, k, v, g, beta")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark import harness
    from benchmark.models import _train, ling3_flash_vl as adapter
    from benchmark.reference import ling3_flash_vl as reference
    from paddle_tpu.framework import (Program, Scope, program_guard,
                                      scope_guard)
    from paddle_tpu.models import transformer as T
    from paddle_tpu.pallas import kda
    if args.xla_scan:
        kda.fits = lambda *a, **k: False
    if args.exp:
        reference.jnp = type("jnp_with_exp", (), {
            "__getattr__": lambda self, n: getattr(jnp, n),
            "expm1": staticmethod(lambda x: jnp.exp(x) - 1.0)})()
    on_chip = jax.default_backend() == "tpu"
    config = harness.load_json("benchmark/configs/ling3_flash_vl.json")
    traffic = harness.load_traffic("lm_s8192_r64")
    if not on_chip:
        sys.path.insert(0, os.path.join(ROOT, "tests", "benchmark"))
        import test_ling3_cell
        config, traffic = test_ling3_cell.toy_ling()
    seq = args.seq or traffic["seq_len"]
    cfg = adapter.ling_config(config)
    scope, main_p, startup = Scope(), Program(), Program()
    with scope_guard(scope), program_guard(main_p, startup):
        bounds = []
        _, parts, loss = T.build_ling_pretrain(cfg, seq, checkpoints=bounds)
        exe = _train.executor(on_chip)
        exe.run(startup, scope=scope,
                seed=harness.exe_seed(traffic["weights_seed"]))
    feed = adapter.make_batch(_train.rng_of(args.seed), cfg, 1, seq)
    ops = main_p.global_block().ops
    first = {t: next(op for op in ops if op.type == t)
             for t in ("short_conv", "kda_gate", "kda_scan")}
    stage_names = [first["short_conv"].outputs["Out"][0],
                   first["kda_gate"].outputs["G"][0],
                   first["kda_gate"].outputs["Beta"][0],
                   first["kda_scan"].outputs["Out"][0]]
    with jax.default_matmul_precision("highest"):
        got = exe.run(main_p, feed=feed, scope=scope,
                      fetch_list=[b.name for b in bounds]
                      + [parts["hidden"].name]
                      + (stage_names if args.stages else []))
    got = [np.asarray(g, np.float32)[0] for g in got]
    stages = got[len(bounds) + 1:]
    params = adapter.reference_params(
        lambda n: jnp.asarray(scope.find_var(n), jnp.float32), cfg)
    kw = adapter.reference_kw(cfg, traffic.get("reference_q_block", 512))
    one = jax.jit(lambda h, blk: reference.block(h, blk, kw)[0])

    def rel(a, b):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        return float(np.linalg.norm(a - b) / np.linalg.norm(b))


    out = {"seed": args.seed, "xla_scan": args.xla_scan, "seq": seq,
           "reference_decay": "exp" if args.exp else "1+expm1",
           "blocks": []}
    if args.stages:
        blk, dh = params["blocks"][0], cfg.d_head
        hk = cfg.n_kda_head

        @jax.jit
        def reference_stages(x):
            z = reference.rms(x, blk["ln1_w"], kw["eps"])
            pre = [z @ blk[w] for w in ("wq", "wk", "wv")]
            conv = jnp.concatenate([reference.causal_conv_silu(p, blk[c])
                                    for p, c in zip(pre, ("conv_q", "conv_k",
                                                          "conv_v"))], -1)
            g = reference.bounded_gate(
                (z @ blk["wf"] + blk["dt_bias"]).reshape(-1, hk, dh),
                blk["a_log"], kw["lower_bound"])
            beta = jax.nn.sigmoid(z @ blk["w_beta"])
            return conv, g, beta

        @jax.jit
        def recurrence_over(conv, g, beta):
            q, k, v = (a.reshape(-1, hk, dh) for a in jnp.split(conv, 3, -1))
            return jax.vmap(lambda *a: reference.recurrence(*a, 128),
                            in_axes=1, out_axes=1)(
                reference.unit(q) * dh ** -0.5, reference.unit(k), v, g,
                beta)

        with jax.default_matmul_precision("highest"):
            conv, g, beta = reference_stages(jnp.asarray(got[0]))
            o_ref = recurrence_over(conv, g, beta)
            o_same = recurrence_over(*(jnp.asarray(a) for a in stages[:3]))
        out["stages"] = {
            "short_conv": rel(stages[0], conv), "g": rel(stages[1], g),
            "g_range": [float(np.min(stages[1])), float(np.max(stages[1]))],
            "beta": rel(stages[2], beta), "scan": rel(stages[3], o_ref),
            "scan_over_the_programs_inputs": rel(stages[3], o_same)}
    with jax.default_matmul_precision("highest"):
        h = params["wte"][jnp.asarray(feed["src_ids"][0])]
        out["embedding"] = rel(got[0], h)
        for i, blk in enumerate(params["blocks"]):
            # the reference's block over the PROGRAM's input: this block's
            # own difference; and over its own input: the carried one
            own = one(jnp.asarray(got[i]), blk)
            h = one(h, blk)
            kind = ("mla" if i in cfg.mla_layers else "kda") + (
                "+dense" if i in cfg.dense_layers else "+experts")
            out["blocks"].append({
                "block": i, "kind": kind,
                "own_input": rel(got[i + 1] - got[i], np.asarray(own)
                                 - got[i]),
                "carried": rel(got[i + 1], h)})
    print(json.dumps(out), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "ling_layer_probe.jsonl"),
              "a") as f:
        f.write(json.dumps(out) + "\n")


if __name__ == "__main__":
    main()
