#!/usr/bin/env python
"""Sharding-analysis smoke (wired into tools/ci.sh): the ISSUE-20
acceptance scenario on a multi-device CPU mesh (dp:2 x mp:2 via
--xla_force_host_platform_device_count).

1. **Blessed table analyzes clean**: the 2-layer BERT under the shipped
   ``mp_hidden`` table produces a reshard plan with ZERO unexplained
   edges — every priced collective carries a semantic reason
   (partial_sum / grad_partial / norm_stats / ...) — and the verify
   stamp (``_attrs["verify"]["sharding"]``) plus the
   ``#resh=<n>x<sha8>`` collective-fingerprint fold both carry the
   same plan token.

2. **Conflicting table refused before dispatch**: a deliberately
   overcommitted rule table (two logical axes onto one mesh axis)
   raises ``ProgramVerificationError`` naming ``mesh_axis_overuse`` at
   ``compiler.optimize`` time, with the executor's dispatched-step
   counter unmoved — the bad program never reaches XLA.

3. **Static plan == measured bytes**: over N dispatched training steps
   the ``paddle_tpu_collective_bytes_total`` counter moves by exactly
   N x the static plan's payload bytes (the executor's byte cells are
   pre-bound from the reshard-plan projection, so the static plan IS
   the measured accounting — exact by construction).
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

AXES = {"dp": 2, "mp": 2}
#: two logical axes onto "mp" -> every matmul operand would carry
#: ('mp', 'mp'); the verifier must refuse with mesh_axis_overuse
BAD_RULES = {"embed": "mp", "mlp": "mp", "batch": "dp"}
STEPS = 3


def fail(msg):
    print(f"SHARDING SMOKE FAILED: {msg}")
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



def _dispatched():
    from paddle_tpu import monitor
    return monitor.counter_totals().get(
        "paddle_tpu_executor_steps_dispatched", 0)


def check_blessed_and_measured():
    """Gates 1+3: mp_hidden analyzes with zero unexplained edges, the
    verify stamp carries the plan, and the measured collective-bytes
    counter reproduces the static plan exactly."""
    import paddle_tpu as pt
    from paddle_tpu import monitor
    from paddle_tpu.analysis.sharding import plan_sharding
    from paddle_tpu.framework import (Executor, Program, program_guard,
                                      unique_name)
    from paddle_tpu.framework.scope import Scope, scope_guard

    main, start = Program(), Program()
    with unique_name.guard(), program_guard(main, start), \
            scope_guard(Scope()):
        loss = build_bert()
        main.random_seed = 5
        compiled = pt.CompiledProgram(main).with_gspmd(
            axes=AXES, rules="mp_hidden", zero_stage=1,
            fetch_names=[loss.name], batch_size=8)
        exe = Executor()
        exe.run(pt.default_startup_program(), seed=11)
        rng = np.random.RandomState(3)
        feed0 = feed_data(rng)

        # -- gate 1: static plan + verify stamp, before any dispatch --
        plan = plan_sharding(main, [loss.name], batch_size=8)
        if plan is None:
            fail("mp_hidden program produced no sharding plan")
        if plan.unexplained:
            fail(f"{len(plan.unexplained)} unexplained reshard edge(s) "
             f"under mp_hidden: "
             f"{[(e.var, e.op_type) for e in plan.unexplained]}")
        if not plan.edges:
            fail("mp_hidden plan priced no reshard edges at all")
        bad = [d for d in plan.diagnostics if d.severity == "error"]
        if bad:
            fail(f"blessed table raised error diagnostics: {bad}")

        # one warm-up dispatch compiles + runs verify/optimize inline
        losses = [float(np.asarray(exe.run(
            compiled, feed=feed0, fetch_list=[loss.name])[0]))]

        stamp = (main._attrs.get("verify") or {}).get("sharding") or {}
        if not stamp:
            fail("_attrs['verify']['sharding'] was not stamped")
        if stamp.get("n_unexplained", -1) != 0:
            fail(f"verify stamp reports unexplained edges: {stamp}")
        # the verifier stamps its batch=1 baseline plan
        plan1 = plan_sharding(main, [loss.name], batch_size=1)
        if stamp.get("fingerprint") != plan1.fingerprint:
            fail(f"verify stamp fingerprint {stamp.get('fingerprint')} "
                 f"!= offline batch-1 plan {plan1.fingerprint}")
        cfp = (main._attrs.get("verify") or {}).get(
            "collective_fingerprint", "")
        if f"#resh={plan1.resh_token}" not in cfp:
            fail(f"collective fingerprint does not fold the reshard "
                 f"plan token {plan1.resh_token!r}: {cfp!r}")
        if not cfp.endswith("#rules=mp_hidden"):
            fail(f"collective fingerprint lost the rules suffix: {cfp!r}")

        # -- gate 3: measured bytes == steps x static plan payload --
        ctr = "paddle_tpu_collective_bytes_total"
        b0 = monitor.counter_totals().get(ctr, 0)
        d0 = _dispatched()
        for _ in range(STEPS):
            lv, = exe.run(compiled, feed=feed_data(rng),
                          fetch_list=[loss.name])
            losses.append(float(np.asarray(lv)))
        exe.drain()
        db = monitor.counter_totals().get(ctr, 0) - b0
        dd = _dispatched() - d0
        if dd != STEPS:
            fail(f"dispatch counter moved {dd}, expected {STEPS}")
        if db != STEPS * plan.payload_bytes:
            fail(f"measured collective bytes {db} != {STEPS} steps x "
                 f"static plan payload {plan.payload_bytes}")
        if any(not np.isfinite(v) for v in losses):
            fail(f"non-finite loss under mp_hidden: {losses}")

    print(f"sharding smoke 1 OK: mp_hidden plan has {len(plan.edges)} "
          f"edge(s), 0 unexplained; verify stamp + fingerprint fold "
          f"carry #resh={plan1.resh_token}")
    print(f"sharding smoke 3 OK: measured {int(db)}B over {STEPS} "
          f"steps == {STEPS} x static {int(plan.payload_bytes)}B")


def check_conflicting_refused():
    """Gate 2: the overcommitted table is refused at optimize time —
    ProgramVerificationError naming mesh_axis_overuse, zero dispatches."""
    import paddle_tpu as pt
    from paddle_tpu.analysis import ProgramVerificationError
    from paddle_tpu.framework import (Executor, Program, program_guard,
                                      unique_name)
    from paddle_tpu.framework.scope import Scope, scope_guard

    main, start = Program(), Program()
    with unique_name.guard(), program_guard(main, start), \
            scope_guard(Scope()):
        loss = build_bert()
        compiled = pt.CompiledProgram(main).with_gspmd(
            axes=AXES, rules=BAD_RULES, fetch_names=[loss.name],
            batch_size=8)
        exe = Executor()
        exe.run(pt.default_startup_program(), seed=11)
        d0 = _dispatched()
        try:
            exe.run(compiled,
                    feed=feed_data(np.random.RandomState(3)),
                    fetch_list=[loss.name])
        except ProgramVerificationError as e:
            msg = str(e)
            if "mesh_axis_overuse" not in msg:
                fail(f"refusal does not name mesh_axis_overuse: {msg}")
        else:
            fail("conflicting rule table was NOT refused at optimize "
                 "time")
        dd = _dispatched() - d0
        if dd != 0:
            fail(f"refused program still dispatched {dd} step(s)")
    print("sharding smoke 2 OK: overcommitted table refused with "
          "mesh_axis_overuse at optimize time, 0 steps dispatched")


def main():
    check_blessed_and_measured()
    check_conflicting_refused()
    print("SHARDING SMOKE OK")


if __name__ == "__main__":
    main()
