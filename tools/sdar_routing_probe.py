"""What moves the SDAR cell's step time from document to document, on the
chip at published widths: the rows each document sends to every expert of
every layer (the forward-only AMP program, ExpertLoad ``[128]`` a layer),
the timed training step's wall milliseconds on the same documents (each step
synced), and from the two: how the step time follows the rows on the held
experts, and, for each of the eight shares of 16 experts a chip of the
deployment could hold, how much those rows vary from document to document.
``--scales`` runs it under other initial values too, all settings on one
compiled step: it is how the configuration's ``assumed.initial_scale`` was
chosen (under the repo's defaults every row of a document chooses the same
8 experts from layer 1 on, and a share holds 0, 1, 2 ... of them).

    chiprun -- python3 tools/sdar_routing_probe.py --seed 7 --docs 32 \
        --scales ";word_embedding=0.01,attn.out.w=10"

Prints one JSON line a document (``ms``, ``held_rows`` a layer at the
configuration's own ``expert_offset``) and a last line a setting with the
correlation and the eight shares' mean and standard deviation of a
document's held rows, all layers together.  Without a TPU: the cell at toy
widths, a rehearsal of the path.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--docs", type=int, default=32)
    ap.add_argument("--scales", default="", help="settings apart by ';', "
                    "each 'name=factor,...': every parameter whose name "
                    "ends in NAME times FACTOR, on the configuration's own "
                    "initial values, before anything runs (how the crowding "
                    "follows the size of a token's own vector beside what "
                    "attention adds to every row alike); all settings run "
                    "in one process on one compiled step")
    args = ap.parse_args()
    import jax
    import numpy as np
    from benchmark import harness
    adapter = harness.load_module("models", "sdar_30b_a3b")
    trinity = harness.load_module("models", "trinity_mini")
    on_chip = jax.default_backend() == "tpu"
    config = harness.load_json("benchmark/configs/sdar_30b_a3b.json")
    traffic = harness.load_traffic("bd_s8192_b4_r64")
    if not on_chip:
        config, traffic = toy(config, traffic)
        args.docs = min(args.docs, 4)
    traffic["ring"] = max(traffic["ring"], args.docs)
    m = adapter.build_train(config, traffic, args.seed, 1, on_chip)
    cfg, exe, scope = m["cfg"], m["exe"], m["scope"]
    docs = m["ring"][:args.docs]
    names = [v.name for v in m["parameters"]]
    initial = {n: np.asarray(scope.find_var(n)) for n in names}
    fwd = adapter._forward_program(cfg, traffic["seq_len"], scope, amp=True)
    step = lambda feed: float(np.asarray(exe.run(  # noqa: E731
        m["program"], feed=feed, fetch_list=[m["loss"]], scope=scope)[0]))
    step(docs[0])                                 # compiles or loads
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    for setting in args.scales.split(";"):
        scales = dict((k, float(v)) for k, v in
                      (kv.split("=") for kv in setting.split(",") if kv))
        trinity._initial_state(m)                 # moments and step counter
        for n in names:
            f = [v for k, v in scales.items() if n.endswith(k)]
            scope.set_var(n, jax.numpy.asarray(initial[n] * (f[0] if f else 1.0)))
        out = probe(adapter, m, fwd, docs, step)
        out.update(seed=args.seed, scales=scales)
        print(json.dumps(out), flush=True)
        with open(os.path.join(ROOT, "chiprun_out",
                               "sdar_routing_probe.jsonl"), "a") as f:
            f.write(json.dumps(out) + "\n")
    return 0


def toy(config, traffic):
    """The cell at toy widths: the path rehearsed where there is no chip."""
    config = dict(config, hidden_size=64, num_attention_heads=8,
                  num_key_value_heads=1, head_dim=16, moe_intermediate_size=32,
                  num_experts=4, num_experts_per_tok=2, num_hidden_layers=2,
                  vocab_size=128)
    config["assumed"] = dict(config["assumed"], router_outputs=16,
                             expert_offset=4, mask_token_id=127)
    return config, dict(traffic, seq_len=40, ring=4, mask_token_id=127)


def probe(adapter, m, fwd, docs, step):
    """One setting: every document's rows an expert a layer at the weights
    in the scope, then the timed step on each document, synced."""
    import jax
    import numpy as np
    cfg, exe, scope = m["cfg"], m["exe"], m["scope"]
    loads = np.stack([np.stack(adapter._run_forward(exe, scope, fwd, feed,
                                                    cfg)[2])
                      for feed in docs])          # [docs, layers, experts]
    ms = []
    for feed in docs:
        feed = {k: jax.device_put(v) for k, v in feed.items()}
        t0 = time.perf_counter()
        step(feed)
        ms.append((time.perf_counter() - t0) * 1e3)
    held, off = cfg.n_held, cfg.expert_offset
    own = loads[:, :, off:off + held].sum(axis=2)            # [docs, layers]
    for i, t in enumerate(ms):
        print(json.dumps({"doc": i, "ms": round(t, 2),
                          "held_rows": own[i].astype(int).tolist(),
                          "masked": int((docs[i]["lm_label"] > 0).sum())}),
              flush=True)
    shares = {}
    for share in range(cfg.n_experts // held):
        rows = loads[:, :, share * held:(share + 1) * held].sum(axis=(1, 2))
        shares[str(share * held)] = {
            "mean": float(rows.mean()), "std": float(rows.std()),
            "by_layer_std": loads[:, :, share * held:(share + 1) * held]
            .sum(axis=2).std(axis=0).astype(int).tolist()}
    total = own.sum(axis=1)
    fit = len(ms) > 2 and total.std() > 0
    return {"docs": len(ms), "expert_offset": off,
            "ms_mean": float(np.mean(ms)), "ms_std": float(np.std(ms)),
            "held_rows_mean_by_layer": own.mean(axis=0).astype(int).tolist(),
            "held_rows_std_by_layer": own.std(axis=0).astype(int).tolist(),
            "corr_ms_held_rows": float(np.corrcoef(ms, total)[0, 1])
            if fit else None,
            "ms_per_1000_held_rows": float(np.polyfit(total, ms, 1)[0] * 1e3)
            if fit else None,
            "busiest_expert_share_by_layer": (
                loads.max(axis=2) / loads.sum(axis=2)).mean(axis=0).round(
                    3).tolist(),
            "shares": shares}


if __name__ == "__main__":
    sys.exit(main())
