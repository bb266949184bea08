"""The readings that set the Trinity-Mini cell's loss tolerances, on the chip
at published widths: how far the reference moves when it is computed in the
nearest precision below the one a check states (as
``tools/olmoe_tolerance_probe.py`` reads them for OLMoE's cell).

* the float32 forward check: the reference with every parameter, and so
  every activation, in bf16 in the program's place, against the float32
  reference at ``highest``: loss, share of tokens whose 8 of 128 experts
  differ in some layer, final-norm output over the other tokens;
* the AMP first-loss check: the same bf16 reference with its weights rounded
  through float8_e4m3 first: loss and final-norm output over all tokens;
* the AMP first-gradient check: ``jax.grad`` of both of those against
  ``jax.grad`` of the float32 reference, leaf by leaf, as
  ``models/trinity_mini.py:gradient_difference`` compares the step's.

    chiprun -- python3 tools/trinity_tolerance_probe.py --seed 7
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
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark import harness
    from benchmark.models import (_train, olmoe_1b_7b as olmoe,
                                  trinity_mini as adapter)
    from benchmark.reference import trinity_mini as reference
    on_chip = jax.default_backend() == "tpu"
    config = harness.load_json("benchmark/configs/trinity_mini.json")
    traffic = harness.load_traffic("lm_s8192")
    if not on_chip:                      # a rehearsal of the path, no reading
        config.update(hidden_size=64, num_attention_heads=4,
                      num_key_value_heads=2, head_dim=16,
                      intermediate_size=96, moe_intermediate_size=32,
                      num_experts=4, num_experts_per_tok=2, vocab_size=128,
                      sliding_window=8)
        config["assumed"].update(router_outputs=8)
        traffic.update(seq_len=32, reference_q_block=16)
    # the weights alone: the startup program of the forward-only model
    from paddle_tpu.framework import (Program, Scope, program_guard,
                                      scope_guard)
    from paddle_tpu.models import transformer as T
    cfg = adapter.trinity_config(config)
    scope, main_p, startup = Scope(), Program(), Program()
    with scope_guard(scope), program_guard(main_p, startup):
        T.build_trinity_pretrain(cfg, traffic["seq_len"], is_test=True)
        _train.executor(on_chip).run(startup, scope=scope,
                                     seed=harness.exe_seed(args.seed))
    feed = adapter.make_batch(_train.rng_of(args.seed, 7), cfg, 1,
                              traffic["seq_len"])
    params = adapter.reference_params(
        lambda n: jnp.asarray(scope.find_var(n), jnp.float32), cfg)
    q_block = traffic["reference_q_block"]

    def cast(through=None):
        def one(a):
            a = a if through is None else a.astype(through)
            return a.astype(jnp.bfloat16)
        return jax.tree_util.tree_map(one, params)

    def against_float32(p):
        s = reference.sequence_sums(
            p, jnp.asarray(feed["src_ids"]), jnp.asarray(feed["lm_label"]),
            **adapter.reference_kw(cfg, q_block))
        got = float(reference.loss_of_sums(s)["loss"])
        top = np.asarray(s["top_e"])
        want, ref_top, per_token = adapter.reference_loss(
            reference, params, feed, cfg,
            hidden=np.asarray(s["hidden"], np.float32), q_block=q_block)
        differ = olmoe.tokens_that_differ(top, ref_top)
        return {"loss_rel": _train.rel_err(got, want),
                "top_k_differ_share": float(differ.mean()),
                "hidden_rel_others": olmoe.hidden_difference(per_token,
                                                             ~differ),
                "hidden_rel_all": olmoe.hidden_difference(per_token)}

    def gradient_against_float32(p, g_ref):
        """The reference's gradient computed over ``p`` in the AMP step's
        place, as ``check_first_loss`` compares the step's."""
        _, g = adapter.reference_gradient(reference, p, feed, cfg, q_block)
        off = adapter.gradient_difference(g_ref, g)
        return {"gradient": {k: v if k == "all" else list(v)
                             for k, v in off.items()}}

    for name in list(scope.local_var_names()):     # the reference's stay
        scope.erase(name)
    out = {"device": jax.devices()[0].device_kind, "seed": args.seed,
           "bf16": against_float32(cast()),
           "fp8_weights_bf16": against_float32(cast(jnp.float8_e4m3fn))}
    _, g_ref = adapter.reference_gradient(reference, params, feed, cfg,
                                          q_block)
    out["bf16"].update(gradient_against_float32(cast(), g_ref))
    out["fp8_weights_bf16"].update(
        gradient_against_float32(cast(jnp.float8_e4m3fn), g_ref))
    print(json.dumps(out), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           "trinity_tolerance_probe.jsonl"), "a") as f:
        f.write(json.dumps(out) + "\n")


if __name__ == "__main__":
    main()
