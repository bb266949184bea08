"""The two readings that set the OLMoE cell's loss tolerances, on the chip at
published widths: how far the reference moves when it is computed in the
nearest precision below the one a check states.

* the float32 forward check (``loss_tolerance.relative``): the reference with
  every parameter, and so every activation, in bf16, against the float32
  reference at ``highest``;
* the AMP first-loss check (``first_training_loss_relative``; bf16 compute
  over float32 master weights): the same bf16 reference with its weights
  rounded through float8_e4m3 first, against the same.

Read on the loss and on the final-norm output (``|got - want| / |want|``):
the loss of fresh weights averages rounding away over thousands of tokens and
cannot tell the precisions apart; the hidden state can, so each check holds
both.  Both hidden differences have to come out above their tolerance (a program computing
in the lower precision would be called not correct), and the program's own
differences over the seeds below it; PERF.md holds the readings.

    chiprun -- python3 tools/olmoe_tolerance_probe.py --seed 7
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
    ap.add_argument("--sequences", type=int, default=2)
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark import harness
    from benchmark.models import _train, olmoe_1b_7b as adapter
    from benchmark.reference import olmoe_1b_7b as reference
    on_chip = jax.default_backend() == "tpu"
    config = harness.load_json("benchmark/configs/olmoe_1b_7b.json")
    traffic = harness.load_traffic("lm_s4096")
    if not on_chip:                      # a rehearsal of the path, no reading
        config.update(hidden_size=64, num_attention_heads=4,
                      num_key_value_heads=4, intermediate_size=32,
                      num_experts=8, num_experts_per_tok=2, vocab_size=128)
        traffic.update(seq_len=32, batch_per_chip=2)
    built = adapter.build_train(config, traffic, args.seed, 1, on_chip)
    cfg, scope = built["cfg"], built["scope"]
    feed = adapter.make_batch(_train.rng_of(args.seed, 7), cfg,
                              args.sequences, traffic["seq_len"])
    params = adapter.reference_params(
        lambda n: jnp.asarray(scope.find_var(n), jnp.float32), cfg)
    kw = dict(n_head=cfg.n_head, top_k=cfg.top_k, eps=float(cfg.rms_eps),
              theta=float(cfg.rope_theta))

    def run(p):
        """(loss, final-norm output [B, T, d]) of the reference over ``p``,
        one sequence at a time."""
        total, hidden = None, []
        for i in range(args.sequences):
            s = reference.sequence_sums(
                p, jnp.asarray(feed["src_ids"][i:i + 1]),
                jnp.asarray(feed["lm_label"][i:i + 1]), **kw)
            s.pop("top_e")
            hidden.append(np.asarray(s.pop("hidden"), np.float32))
            total = s if total is None else \
                jax.tree_util.tree_map(jnp.add, total, s)
        loss = reference.loss_of_sums(total, cfg.lb_coef, cfg.z_coef)["loss"]
        return float(loss), np.concatenate(hidden)

    def in_bf16(through=None):
        """The same with every parameter (and so every activation) bf16;
        ``through``: a narrower type the weights are rounded through
        first."""
        def cast(a):
            a = a if through is None else a.astype(through)
            return a.astype(jnp.bfloat16)
        loss, hidden = run(jax.tree_util.tree_map(cast, params))
        return loss, float(np.linalg.norm(hidden - exact_hidden)
                           / np.linalg.norm(exact_hidden))

    exact, exact_hidden = run(params)
    (bf16, bf16_hidden), (fp8, fp8_hidden) = in_bf16(), in_bf16(
        jnp.float8_e4m3fn)
    rel = lambda x: abs(x - exact) / abs(exact)  # noqa: E731
    out = {"device": jax.devices()[0].device_kind, "seed": args.seed,
           "sequences": args.sequences, "reference_float32": exact,
           "reference_bf16": bf16, "bf16_rel": rel(bf16),
           "bf16_hidden_rel": bf16_hidden,
           "reference_fp8_weights_bf16": fp8, "fp8_rel": rel(fp8),
           "fp8_hidden_rel": fp8_hidden}
    print(json.dumps(out), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           "olmoe_tolerance_probe.jsonl"), "a") as f:
        f.write(json.dumps(out) + "\n")


if __name__ == "__main__":
    main()
