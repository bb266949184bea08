#!/usr/bin/env python
"""GSPMD smoke (wired into tools/ci.sh): the ISSUE-16 acceptance
scenario on a multi-device CPU mesh (dp:2 x mp:2 via
--xla_force_host_platform_device_count).

1. **Planner pick under memory pressure**: a transformer whose
   single-chip static HBM plan exceeds ``FLAGS_memory_budget_mb`` gets
   a planner-chosen rule table that is NOT ``replicated``, fits the
   per-shard budget, and publishes its decision
   (``paddle_tpu_gspmd_rule_choices_total`` +
   ``paddle_tpu_gspmd_per_shard_peak_bytes``).

2. **Parity + ZeRO-1 gauge**: the sharded run's losses equal the
   single-chip baseline's, an Adam moment lives dp-sharded in the
   scope, and the HBM plane's per-class attribution shows ``opt_state``
   live bytes shrunk by ZeRO-1 + mp sharding (per-device accounting —
   the gauge-verified acceptance gate).

3. **Headroom gauge sanity**: with the budget flag set, the accountant
   publishes budget/live/headroom gauges whose arithmetic re-adds
   exactly (headroom == budget - live from the same sample).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_xf = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _xf:
    os.environ["XLA_FLAGS"] = \
        (_xf + " --xla_force_host_platform_device_count=4").strip()

import numpy as np  # noqa: E402

MB = 1 << 20
AXES = {"dp": 2, "mp": 2}


def fail(msg):
    print(f"GSPMD SMOKE FAILED: {msg}")
    sys.exit(1)


def build_bert():
    from paddle_tpu import optimizer as opt
    from paddle_tpu.models import transformer as T
    cfg = T.BertConfig(vocab_size=64, d_model=16, n_layer=2, n_head=4,
                       d_inner=32, max_pos=32, dropout=0.0)
    _, _, loss = T.build_bert_pretrain(cfg, seq_len=8)
    opt.AdamOptimizer(learning_rate=0.01).minimize(loss)
    return loss


def feed_data(rng):
    return {"src_ids": rng.randint(1, 64, (8, 8)).astype("int64"),
            "pos_ids": np.tile(np.arange(8), (8, 1)).astype("int64"),
            "lm_label": rng.randint(0, 64, (8, 8)).astype("int64")}



def pick_budget():
    """Gate 1: derive a budget the single-chip plan exceeds but a
    sharded table fits, and check the planner lands on it."""
    import paddle_tpu as pt
    from paddle_tpu import monitor
    from paddle_tpu.analysis.memory import plan_memory
    from paddle_tpu.framework import (Program, program_guard, unique_name)
    from paddle_tpu.framework.scope import Scope, scope_guard
    from paddle_tpu.parallel import choose_rules

    main, start = Program(), Program()
    with unique_name.guard(), program_guard(main, start), \
            scope_guard(Scope()):
        loss = build_bert()
    single_chip = plan_memory(main, [loss.name], batch_size=8).peak_bytes
    _, rep = choose_rules(main, AXES, fetch_names=[loss.name],
                          batch_size=8)
    peaks = {r["rules"]: r["per_shard_peak_bytes"] for r in rep}
    budget_bytes = (min(peaks.values()) + peaks["replicated"]) // 2
    if single_chip <= budget_bytes:
        fail(f"single-chip plan {single_chip} does not exceed the "
             f"derived budget {budget_bytes}")
    budget_mb = budget_bytes / MB

    ch0 = monitor.counter_totals().get(
        "paddle_tpu_gspmd_rule_choices_total", 0)
    table, rep2 = choose_rules(main, AXES, fetch_names=[loss.name],
                               batch_size=8, budget_mb=budget_mb)
    chosen = next(r for r in rep2 if r["chosen"])
    if table.name == "replicated":
        fail(f"planner stayed replicated under pressure: {rep2}")
    if not chosen["fits"]:
        fail(f"planner-chosen table does not fit the budget: {chosen}")
    if next(r for r in rep2 if r["rules"] == "replicated")["fits"]:
        fail("replicated fits the pressure budget - gate is vacuous")
    ch1 = monitor.counter_totals().get(
        "paddle_tpu_gspmd_rule_choices_total", 0)
    if ch1 - ch0 < 1:
        fail("rule-choice counter did not move")
    peak_gauge = monitor.REGISTRY.get(
        "paddle_tpu_gspmd_per_shard_peak_bytes").value()
    if peak_gauge != chosen["per_shard_peak_bytes"]:
        fail(f"per-shard peak gauge {peak_gauge} != chosen "
             f"{chosen['per_shard_peak_bytes']}")
    print(f"gspmd smoke 1 OK: single-chip plan {single_chip}B > budget "
          f"{budget_bytes}B -> planner chose {table.name!r} "
          f"(per-shard peak {chosen['per_shard_peak_bytes']}B, "
          f"{chosen['bound']}-bound)")
    return budget_mb, table.name


def run_session(compiled_fn, steps=4):
    """One training session under fresh name generator + scope; returns
    (losses, opt_state class bytes after drain, scope, program)."""
    import paddle_tpu as pt
    from paddle_tpu import hbm, monitor
    from paddle_tpu.framework import (Executor, Program, program_guard,
                                      unique_name)
    from paddle_tpu.framework.scope import Scope, global_scope, scope_guard

    main, start = Program(), Program()
    with unique_name.guard(), program_guard(main, start), \
            scope_guard(Scope()):
        loss = build_bert()
        main.random_seed = 5
        compiled = compiled_fn(main, loss)
        exe = Executor()
        exe.run(pt.default_startup_program(), seed=11)
        rng = np.random.RandomState(3)
        out = []
        for _ in range(steps):
            lv, = exe.run(compiled, feed=feed_data(rng),
                          fetch_list=[loss.name])
            out.append(float(np.asarray(lv)))
        exe.drain()
        if not hbm.ACCOUNTANT.drain(30):
            fail("accountant did not drain")
        cls = {lbl["cls"]: c.get() for lbl, c in
               monitor.REGISTRY.get(
                   "paddle_tpu_hbm_class_bytes").series()}
        return out, cls.get("opt_state", 0), global_scope(), main


def check_parity_and_gauges(budget_mb, expect_rules):
    """Gates 2+3: loss parity, dp-sharded moment, opt_state shrink,
    headroom arithmetic."""
    import paddle_tpu as pt
    from paddle_tpu import monitor

    pt.set_flags({"FLAGS_hbm_telemetry": True})
    base_losses, base_opt, _, _ = run_session(lambda m, l: None)
    if base_opt <= 0:
        fail(f"baseline opt_state attribution missing: {base_opt}")

    pt.set_flags({"FLAGS_memory_budget_mb": max(int(budget_mb), 1)})
    try:
        sh_losses, sh_opt, scope, prog = run_session(
            lambda m, l: pt.CompiledProgram(m).with_gspmd(
                axes=AXES, rules="auto", zero_stage=1,
                fetch_names=[l.name], batch_size=8,
                budget_mb=budget_mb))
        stamp = prog._attrs.get("partition") or {}
        if stamp.get("rules") != expect_rules:
            fail(f"with_gspmd planner chose {stamp.get('rules')!r}, "
                 f"choose_rules said {expect_rules!r}")
        if not stamp.get("params"):
            fail("chosen table sharded no params")
        if not np.allclose(base_losses, sh_losses, rtol=2e-4, atol=1e-5):
            fail(f"loss parity broke: single-chip {base_losses} vs "
                 f"sharded {sh_losses}")
        specs = [getattr(getattr(scope.find_var(n), "sharding", None),
                         "spec", None)
                 for n in scope.local_var_names() if "moment1" in n]
        if not any(s and s[0] == "dp" for s in specs):
            fail(f"no ZeRO-1 dp-sharded moment in scope: {specs}")
        if sh_opt >= 0.7 * base_opt:
            fail(f"ZeRO-1 did not shrink opt_state live bytes: "
                 f"{sh_opt} vs baseline {base_opt}")

        reg = monitor.REGISTRY
        budget = reg.get("paddle_tpu_hbm_budget_bytes").value()
        live = reg.get("paddle_tpu_hbm_live_bytes").value()
        headroom = reg.get("paddle_tpu_hbm_headroom_bytes").value()
        if budget != max(int(budget_mb), 1) * MB:
            fail(f"budget gauge {budget} != FLAGS_memory_budget_mb")
        if live <= 0:
            fail(f"live gauge unset: {live}")
        if headroom != budget - live:
            fail(f"headroom does not re-add: {headroom} != "
                 f"{budget} - {live}")
    finally:
        pt.set_flags({"FLAGS_memory_budget_mb": 0})
    print(f"gspmd smoke 2 OK: parity over {len(sh_losses)} steps "
          f"(losses {sh_losses}), moment dp-sharded, opt_state "
          f"{int(sh_opt)}B vs single-chip {int(base_opt)}B "
          f"({sh_opt / base_opt:.2f}x)")
    print(f"gspmd smoke 3 OK: headroom gauge re-adds "
          f"({int(budget)} - {int(live)} = {int(headroom)})")


def main():
    budget_mb, expect_rules = pick_budget()
    check_parity_and_gauges(budget_mb, expect_rules)
    print("GSPMD SMOKE OK")


if __name__ == "__main__":
    main()
