"""The SmallThinker cell under a changed traffic, for PERF.md's two control
sets and nothing else (the benchmark runs the traffic file as it is): what
each part of the traffic's decision bought in the spread of
``train_samples_per_s`` over seeds.

    chiprun -- python3 tools/smallthinker_variant.py --seed 11 \
        --traffic weights_seed=seed          (or lr_start=0.0004)

``weights_seed=seed``: the weights follow ``--seed`` like the ids, as in the
other share cells.  ``lr_start=0.0004``: the rate is 4e-4 from step 0.  Any
other key likewise (``ring=64,lr_warmup_steps=20000``: a sequence of its own
every step and a rate under 1e-6 in the window).
``--no_checks`` leaves out the comparisons with the reference (two minutes a
run): the window and the rows alone.  The last line is the harness's result
object, as ``benchmark/run.py`` prints it.
"""

import argparse
import json
import os
import sys
import time

T0 = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CELL = "smallthinker_21b_a3b_lm_s16384"


def rows_alone(model):
    """In ``check_first_loss``'s place: the rows on the held experts at the
    initial weights and as the window left them, nothing compared."""
    def check(config, traffic, built, first_loss, first_feed, reference):
        cfg = built["cfg"]
        fwd = model._forward_program(cfg, traffic["seq_len"], built["scope"],
                                     amp=True)
        close = model._trinity._routing_at_close(built, fwd, first_feed)
        model._trinity._initial_state(built)
        start = model._trinity._routing_at_close(built, fwd, first_feed)

        def held(loads):
            return [int(v[cfg.expert_offset:cfg.expert_offset
                          + cfg.n_held].sum()) for v in loads]
        return {"ok": True, "detail": "nothing compared (--no_checks); rows "
                f"on the {cfg.n_held} held experts {held(start)} at the "
                f"initial weights and {held(close)} as the window left them"}
    return check


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--traffic", default="",
                    help="key=json,key=json laid over traffic/lm_s16384.json")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--no_checks", action="store_true")
    ap.add_argument("--flash", default="",
                    help="fwd=BQxBK,bwd=BQxBK:impl,wbwd=BQxBK:impl laid over "
                    "the flash tables' rows at 16384 for score width 128 "
                    "(forward, full backward, window backward): the block "
                    "sweep in the cell's own step")
    args = ap.parse_args()
    import jax
    from benchmark import harness
    on_chip = jax.default_backend() == "tpu"
    traffic = harness.load_traffic("lm_s16384")
    for pair in filter(None, args.traffic.split(",")):
        key, value = pair.split("=", 1)
        traffic[key] = args.seed if value == "seed" else json.loads(value)
    if args.flash:
        import importlib
        F = importlib.import_module("paddle_tpu.pallas.flash_attention")
        tables = {"fwd": F._FWD_DEFAULTS_D128, "bwd": F._BWD_DEFAULTS_D128,
                  "wbwd": F._BWD_WINDOW_DEFAULTS_D128}
        for pair in args.flash.split(","):
            key, value = pair.split("=")
            blocks, _, impl = value.partition(":")
            tables[key][16384] = tuple(map(int, blocks.split("x"))) + (
                (impl,) if impl else ())
    config = None
    if not on_chip:                      # a rehearsal of the path, no reading
        sys.path.insert(0, os.path.join(ROOT, "tests", "benchmark"))
        import test_smallthinker_cell
        config, toy = test_smallthinker_cell.toy_smallthinker()
        traffic.update(seq_len=toy["seq_len"],
                       reference_q_block=toy["reference_q_block"])
    if args.no_checks:
        model = harness.load_module("models", "smallthinker_21b_a3b")
        model.check_first_loss = rows_alone(model)
    result = harness.run_cell(CELL, args.seed, args.seconds, bool(args.trace),
                              t_process_start=T0, on_chip=on_chip,
                              config=config, traffic=traffic)
    result["traffic_changed"] = args.traffic
    result["flash_tables_changed"] = args.flash
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
