"""The readings that set the JoyAI-LLM-Flash cell's loss tolerances, on the
chip at published widths: how far the reference moves when it is computed in
the nearest precision below the one a check states (as
``tools/trinity_tolerance_probe.py`` reads them for Trinity's cell).

* the float32 forward check: the reference with every parameter, and so
  every activation, in bf16 in the program's place, against the float32
  reference at ``highest``: the loss and both its terms, the share of tokens
  whose 8 of 256 experts differ in some expert layer or the MTP module, the
  final-norm and MTP-norm outputs over the other tokens;
* the AMP first-loss check: the same bf16 reference with its weights rounded
  through float8_e4m3 first: loss, terms and both outputs over all tokens;
* the AMP first-gradient check: ``jax.grad`` of both of those against
  ``jax.grad`` of the float32 reference, leaf by leaf, as
  ``models/trinity_mini.py:gradient_difference`` compares the step's;
* the first update: the reference's AdamW step from the control's gradient
  with the new parameters kept in bf16, against the reference's step from
  the float32 gradient and from the control's own;
* **the cell's own decision on the control** (``control``): the readings
  above under the names ``models/joyai_llm_flash.py:decide`` holds to the
  configuration's limits, the bf16 reference in the float32 program's place
  and the float8-weights one in the AMP step's, and what it decides: not
  correct, and by which limits.

    chiprun -- python3 tools/joyai_tolerance_probe.py --seeds 7,11
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def toy(config, traffic):
    """Toy sizes for a rehearsal of the path on the CPU (no reading)."""
    config.update(hidden_size=64, num_attention_heads=4, q_lora_rank=24,
                  kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
                  qk_head_dim=24, v_head_dim=12, intermediate_size=96,
                  moe_intermediate_size=32, n_routed_experts=4,
                  num_experts_per_tok=2, vocab_size=128, num_hidden_layers=2)
    config["assumed"].update(router_outputs=8, expert_offset=2)
    traffic.update(seq_len=32, reference_q_block=16)


def one_seed(seed, config, traffic, on_chip, gradients=True):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark import harness
    from benchmark.models import (_train, joyai_llm_flash as adapter,
                                  olmoe_1b_7b as olmoe,
                                  trinity_mini as trinity)
    from benchmark.reference import joyai_llm_flash as reference
    from paddle_tpu.framework import (Program, Scope, program_guard,
                                      scope_guard)
    from paddle_tpu.models import transformer as T
    cfg = adapter.joyai_config(config)
    lam = traffic["mtp_loss_weight"]
    # the weights alone: the startup program of the forward-only model
    scope, main_p, startup = Scope(), Program(), Program()
    with scope_guard(scope), program_guard(main_p, startup):
        T.build_joyai_pretrain(cfg, traffic["seq_len"], lam)
        _train.executor(on_chip).run(startup, scope=scope,
                                     seed=harness.exe_seed(seed))
    feed = adapter.make_batch(_train.rng_of(seed, 7), cfg, 1,
                              traffic["seq_len"])
    params = adapter.reference_params(
        lambda n: jnp.asarray(scope.find_var(n), jnp.float32), cfg)
    q_block = traffic["reference_q_block"]
    for name in list(scope.local_var_names()):     # the reference's stay
        scope.erase(name)

    def cast(through=None):
        def one(a):
            a = a if through is None else a.astype(through)
            return a.astype(jnp.bfloat16)
        return jax.tree_util.tree_map(one, params)

    def against_float32(p):
        s = reference.sequence_sums(
            p, *(jnp.asarray(feed[k])
                 for k in ("src_ids", "lm_label", "mtp_label")),
            **adapter.reference_kw(cfg, q_block))
        got = reference.loss_of_sums(s, lam)
        top = np.asarray(s["top_e"])
        want, ref_top, per_token = adapter.reference_loss(
            reference, params, feed, cfg, lam, hidden=[
                np.asarray(s[k], np.float32)
                for k in ("hidden", "mtp_hidden")], q_block=q_block)
        differ = olmoe.tokens_that_differ(top, ref_top)
        return {"loss_rel": {k: _train.rel_err(float(got[k]), want[k])
                             for k in ("loss", "main", "mtp")},
                "top_k_differ_share": float(differ.mean()),
                "hidden_rel_others": [olmoe.hidden_difference(p_, ~differ)
                                      for p_ in per_token],
                "hidden_rel_all": [olmoe.hidden_difference(p_)
                                   for p_ in per_token]}

    def gradient_against_float32(p, g_ref):
        _, g = adapter.reference_gradient(reference, p, feed, cfg, lam,
                                          q_block)
        off = trinity.gradient_difference(g_ref, g)
        return {"gradient": {k: v if k == "all" else list(v)
                             for k, v in off.items()}}

    out = {"device": jax.devices()[0].device_kind, "seed": seed,
           "bf16": against_float32(cast()),
           "fp8_weights_bf16": against_float32(cast(jnp.float8_e4m3fn))}
    if not gradients:
        return out
    _, g_ref = adapter.reference_gradient(reference, params, feed, cfg, lam,
                                          q_block)
    out["bf16"].update(gradient_against_float32(cast(), g_ref))
    _, g_low = adapter.reference_gradient(
        reference, cast(jnp.float8_e4m3fn), feed, cfg, lam, q_block)
    g_off = trinity.gradient_difference(g_ref, g_low)
    out["fp8_weights_bf16"]["gradient"] = {
        k: v if k == "all" else list(v) for k, v in g_off.items()}

    # the cell's decision with the controls in the program's place
    adamw = dict(lr=traffic["learning_rate"],
                 weight_decay=traffic["weight_decay"])
    theta0 = jax.tree_util.tree_map(np.asarray, params)
    wrote = jax.tree_util.tree_map(
        lambda t, g: reference.adamw_first_step(t, g, store=jnp.bfloat16,
                                                **adamw), theta0, g_low)
    u_off, u_own = adapter.update_difference(reference, theta0, wrote, g_ref,
                                             g_low, adamw)
    b, f = out["bf16"], out["fp8_weights_bf16"]
    r = {"f32_loss": b["loss_rel"]["loss"], "f32_main": b["loss_rel"]["main"],
         "f32_mtp": b["loss_rel"]["mtp"],
         "f32_share": b["top_k_differ_share"],
         "f32_hidden": b["hidden_rel_others"][0],
         "f32_mtp_hidden": b["hidden_rel_others"][1],
         "first_loss": f["loss_rel"]["loss"],
         "first_terms": max(f["loss_rel"]["main"], f["loss_rel"]["mtp"]),
         "first_hidden": f["hidden_rel_all"][0],
         "first_mtp_hidden": f["hidden_rel_all"][1],
         # the program against itself: nothing a precision moves
         "first_forward": 0.0, "replay": 0.0, "dropless": True,
         "update_of_gradient": u_own,
         "gradient_all": g_off["all"], "update_all": u_off["all"]}
    for k in adapter.KINDS:
        r[f"gradient_{k}"], r[f"update_{k}"] = g_off[k][0], u_off[k][0]
    ok, failed = adapter.decide(config["loss_tolerance"], r)
    out["control"] = {"readings": r, "ok": ok, "failed": failed}
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seeds", default="7")
    args = ap.parse_args()
    import jax
    from benchmark import harness
    on_chip = jax.default_backend() == "tpu"
    config = harness.load_json("benchmark/configs/joyai_llm_flash.json")
    traffic = harness.load_traffic("lm_mtp_s8192")
    if not on_chip:
        toy(config, traffic)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    for seed in (int(x) for x in args.seeds.split(",")):
        out = one_seed(seed, config, traffic, on_chip)
        print(json.dumps(out), flush=True)
        with open(os.path.join(ROOT, "chiprun_out",
                               "joyai_tolerance_probe.jsonl"), "a") as f:
            f.write(json.dumps(out) + "\n")


if __name__ == "__main__":
    main()
