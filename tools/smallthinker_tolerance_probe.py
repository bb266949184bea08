"""The readings that set the SmallThinker cell's loss tolerances, on the chip
at published widths and on the timed sequence (the weights from the traffic's
``weights_seed``, the ids from ``--seed``): how far the reference moves when
it is computed in the nearest precision below the one a check states (as
``tools/trinity_tolerance_probe.py`` reads them for Trinity's cell), and the
cell's own decision on each: ``control``, not correct and by which limits.

* the float32 forward check: the reference with every parameter, and so
  every activation, in bf16 in the program's place, against the float32
  reference at ``highest``: loss, share of tokens whose 6 of 64 experts
  differ in some layer, final-norm output over the other tokens;
* the AMP first-loss check: the same bf16 reference with its weights rounded
  through float8_e4m3 first: loss and final-norm output over all tokens;
* the AMP first-gradient check: ``jax.grad`` of both of those against
  ``jax.grad`` of the float32 reference, leaf by leaf, as
  ``models/smallthinker_21b_a3b.py:gradient_difference`` compares the step's
  (each kind as ``[its leaves together, its worst leaf, that leaf's name]``).

    chiprun -- python3 tools/smallthinker_tolerance_probe.py --seeds 7,11

``--cell lfm2`` (PR 40): the same three readings for the LFM2 cell, its
adapter, reference, configuration and traffic in SmallThinker's place (4 of
32 experts; the table read by the lookup and the head).  ``--cell xing4`` (PR
45): the Xing4.0 cell's (4 of 64 experts; a fourth kind of gradient leaf,
the hyper-connections' ``maps``), and one reading more: the first
hyper-connection's ``H_res`` by the reference in float32 and in bf16, row and
column sums against 1 (``h_res_sums``: Sinkhorn-Knopp run in bf16).
``--cell solar`` (PR 49): the Solar-Open2 cell's (8 of 320 experts; a fourth
kind of gradient leaf, the KDA layers' own parameters ``kda``; the
reference's token-by-token recurrence run in bf16 is the control of the
scan).  ``--cell ling`` (PR 55): the Ling-3.0-flash cell's (8 of 512 experts
under group-limited selection; a fifth kind, ``mla``: the latent-attention
layer's norms and head gate).  ``--cell nemotron3`` (PR 57): the
Nemotron-3-Nano cell's (8 of 128 un-gated experts; the kinds ``mamba``, the
Mamba-2 blocks' own parameters, and ``attention``; the reference's
token-by-token state-space recurrence run in bf16 is the control of the scan).
``--cell sdar`` (PR 61): the SDAR cell's under block diffusion (16 of 128
experts; four feeds, the adapter's ``FEEDS``; the experts compared over the
stream's 2L rows, the final-norm output over its noisy half; the kind
``attention``: what reaches the loss through the flash kernels under the
mask).
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


#: cell -> (configuration = adapter = reference, traffic, the adapter's
#: configuration function, the builder, the cell's test module and its toy,
#: what the builder takes to build the forward alone)
CELLS = {"smallthinker": ("smallthinker_21b_a3b", "lm_s16384",
                          "smallthinker_config", "build_smallthinker_pretrain",
                          "test_smallthinker_cell", "toy_smallthinker",
                          {"is_test": True}),
         "lfm2": ("lfm2_8b_a1b", "lm_s16384_r64", "lfm2_config",
                  "build_lfm2_pretrain", "test_lfm2_cell", "toy_lfm2",
                  {"is_test": True}),
         "xing4": ("xing4_29b_a4b", "lm_s4096_r64", "xing_config",
                   "build_joyai_pretrain", "test_xing4_cell", "toy_xing",
                   {}),
         "solar": ("solar_open2_250b", "lm_s8192_r64", "solar_config",
                   "build_solar_open2_pretrain", "test_solar_open2_cell",
                   "toy_solar", {"is_test": True}),
         "ling": ("ling3_flash_vl", "lm_s8192_r64", "ling_config",
                  "build_ling_pretrain", "test_ling3_cell", "toy_ling", {}),
         "nemotron3": ("nemotron3_nano_30b_a3b", "lm_s8192_r64",
                       "nemotron_config", "build_nemotron_h_pretrain",
                       "test_nemotron3_cell", "toy_nemotron", {}),
         "sdar": ("sdar_30b_a3b", "bd_s8192_b4_r64", "sdar_config",
                  "build_sdar_pretrain", "test_sdar_cell", "toy_sdar", {})}


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seeds", default="7")
    ap.add_argument("--cell", default="smallthinker", choices=sorted(CELLS))
    args = ap.parse_args()
    import importlib
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark import harness
    from benchmark.models import _train, olmoe_1b_7b as olmoe
    name, mix, config_of, builder, test_module, toy_of, forward_kw = \
        CELLS[args.cell]
    adapter = importlib.import_module("benchmark.models." + name)
    reference = importlib.import_module("benchmark.reference." + name)
    on_chip = jax.default_backend() == "tpu"
    config = harness.load_json(f"benchmark/configs/{name}.json")
    traffic = harness.load_traffic(mix)
    if not on_chip:                      # a rehearsal of the path, no reading
        sys.path.insert(0, os.path.join(ROOT, "tests", "benchmark"))
        config, toy = getattr(importlib.import_module(test_module), toy_of)()
        traffic.update(seq_len=toy["seq_len"],
                       reference_q_block=toy["reference_q_block"])
    tol = config["loss_tolerance"]
    # the weights alone: the startup program of the forward-only model
    from paddle_tpu.framework import (Program, Scope, program_guard,
                                      scope_guard)
    from paddle_tpu.models import transformer as T
    cfg = getattr(adapter, config_of)(config)
    q_block = traffic["reference_q_block"]
    kinds = getattr(adapter, "KINDS", ("rest", "experts", "router"))
    for seed in map(int, args.seeds.split(",")):
        scope, main_p, startup = Scope(), Program(), Program()
        with scope_guard(scope), program_guard(main_p, startup):
            getattr(T, builder)(cfg, traffic["seq_len"], **forward_kw)
            if hasattr(adapter, "scale_initial_values"):     # sdar's own
                adapter.scale_initial_values(
                    startup, config["assumed"]["initial_scale"])
            _train.executor(on_chip).run(
                startup, scope=scope, seed=harness.exe_seed(
                    traffic["weights_seed"]))
        feed = adapter.make_batch(_train.rng_of(seed), cfg, 1,
                                  traffic["seq_len"])
        params = adapter.reference_params(
            lambda n: jnp.asarray(scope.find_var(n), jnp.float32), cfg)
        for name in list(scope.local_var_names()):   # the reference's stay
            scope.erase(name)

        def cast(through=None):
            def one(a):
                a = a if through is None else a.astype(through)
                return a.astype(jnp.bfloat16)
            return jax.tree_util.tree_map(one, params)

        def against_float32(p):
            s = reference.sequence_sums(
                p, *(jnp.asarray(feed[k]) for k in getattr(
                    adapter, "FEEDS", ("src_ids", "lm_label"))),
                **adapter.reference_kw(cfg, q_block))
            got = float(reference.loss_of_sums(s)["loss"])
            top = np.asarray(s["top_e"])
            want, ref_top, per_token = adapter.reference_loss(
                reference, params, feed, cfg,
                hidden=np.asarray(s["hidden"], np.float32), q_block=q_block)
            differ = olmoe.tokens_that_differ(top, ref_top)
            # the rows the final norm's output has: all, or (sdar) a
            # doubled stream's noisy half
            others = ~(adapter.noisy_rows(differ, 1, traffic["seq_len"])
                       if hasattr(adapter, "noisy_rows") else differ)
            return {"loss_rel": _train.rel_err(got, want),
                    "top_k_differ_share": float(differ.mean()),
                    "hidden_rel_others": olmoe.hidden_difference(per_token,
                                                                 others),
                    "hidden_rel_all": olmoe.hidden_difference(per_token)}

        _, g_ref = adapter.reference_gradient(reference, params, feed, cfg,
                                              q_block)

        def gradient_against_float32(p):
            _, g = adapter.reference_gradient(reference, p, feed, cfg,
                                              q_block)
            off = adapter.gradient_difference(g_ref, g)
            return {"gradient": {k: v if k in ("all", "leaves") else list(v)
                                 for k, v in off.items()}}

        out = {"device": jax.devices()[0].device_kind, "seed": seed}
        for name, through in (("bf16", None),
                              ("fp8_weights_bf16", jnp.float8_e4m3fn)):
            r = against_float32(cast(through))
            r.update(gradient_against_float32(cast(through)))
            out[name] = r
        # the cell's own decision, with the control in the program's place:
        # the float32 forward check reads the bf16 reference, the AMP checks
        # the bf16 reference over float8 weights
        f, a = out["bf16"], out["fp8_weights_bf16"]
        failed = {
            "relative": f["loss_rel"] > tol["relative"],
            "top_k_differ_share":
                f["top_k_differ_share"] > tol["top_k_differ_share"],
            "hidden_relative": f["hidden_rel_others"] > tol["hidden_relative"],
            "first_training_loss_relative":
                a["loss_rel"] > tol.get("first_training_loss_relative",
                                        float("inf")),
            "first_hidden_relative":
                a["hidden_rel_all"] > tol["first_hidden_relative"]}
        if hasattr(reference, "hc_maps"):
            # Sinkhorn-Knopp in the precision below: the first
            # hyper-connection's H_res over the timed tokens' embeddings
            kw = adapter.reference_kw(cfg, q_block)
            for what, p in (("float32", params), ("bf16", cast())):
                x = reference.entry(p["wte"][jnp.asarray(
                    feed["src_ids"][0])], kw["hc_mult"])
                h_res = reference.hc_maps(
                    x, p["blocks"][0]["hc_attn"], kw["eps"], kw["hc_iters"],
                    kw["hc_eps"], kw["hc_clamp"])[2]
                out[f"h_res_sums_{what}"] = adapter.stochastic_off(
                    [np.asarray(h_res, np.float32)], kw["hc_mult"])
            failed["h_res_sums"] = out["h_res_sums_bf16"] > tol["h_res_sums"]
        for kind in kinds:
            failed[f"first_gradient_{kind}_relative"] = \
                a["gradient"][kind][adapter.DECIDES[kind]] > \
                tol[f"first_gradient_{kind}_relative"]
        failed["first_gradient_all_relative"] = \
            a["gradient"]["all"] > tol["first_gradient_all_relative"]
        out["control"] = {"correct": not any(failed.values()),
                          "not_correct_by": sorted(k for k, v in
                                                   failed.items() if v)}
        print(json.dumps(out), flush=True)
        os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
        with open(os.path.join(ROOT, "chiprun_out",
                               f"{args.cell}_tolerance_probe.jsonl"),
                  "a") as fh:
            fh.write(json.dumps(out) + "\n")
        del params, g_ref


if __name__ == "__main__":
    main()
