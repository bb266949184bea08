"""Measure the on-chip peak-HBM allocation plan for the RN50 and BERT
bench steps (VERDICT r3 missing #3 / ask #5).

Two measurements, one process (this process owns the chip while it runs):
the compiled executable's XLA buffer assignment (memory_analysis):
arguments + temporaries + outputs - aliased(donated) — the bytes the runtime
reserves for ONE training step, which the executor records for every block
where it compiles (hbm.record_compiled_plan; the arguments by class too) —
and the allocator's own ``device.memory_stats()`` counters after both steps.

Run on a chip:
    python tools/record_hbm.py
Prints each step's plan as the residency summary does (memory.format_plan:
what tools/joyai_step_aot.py prints with no chip) and one JSON object
{"device", "memory_stats", "plans"} on the last line.
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402


def _one_step_rn50():
    import jax
    import paddle_tpu as pt
    from paddle_tpu import optimizer as opt
    from paddle_tpu.device import is_tpu
    from paddle_tpu.framework import Program, Scope, program_guard, \
        scope_guard
    from paddle_tpu.models.resnet import build_resnet_train

    on_tpu = is_tpu(jax.devices()[0])
    scope = Scope()
    with scope_guard(scope), program_guard(Program(), Program()):
        if on_tpu:
            class_dim, image, batch = 1000, (3, 224, 224), 256
        else:
            class_dim, image, batch = 10, (3, 32, 32), 4
        (img, label), pred, loss, accs = build_resnet_train(
            class_dim=class_dim, depth=50, image_shape=image)
        optimizer = pt.amp.decorate(
            opt.MomentumOptimizer(learning_rate=0.1, momentum=0.9))
        optimizer.minimize(loss)
        exe = pt.Executor()
        exe.run(pt.default_startup_program(), scope=scope)
        rng = np.random.RandomState(0)
        feed = {"image": rng.rand(batch, *image).astype(np.float32),
                "label": rng.randint(0, class_dim,
                                     (batch, 1)).astype(np.int32)}
        lv, = exe.run(feed=feed, fetch_list=[loss.name], scope=scope)
        float(np.asarray(lv))


def _one_step_bert():
    import jax
    import paddle_tpu as pt
    from paddle_tpu import optimizer as opt
    from paddle_tpu.device import is_tpu
    from paddle_tpu.framework import Program, Scope, program_guard, \
        scope_guard
    from paddle_tpu.models import transformer as T

    on_tpu = is_tpu(jax.devices()[0])
    scope = Scope()
    with scope_guard(scope), program_guard(Program(), Program()):
        if on_tpu:
            cfg = T.BertConfig()
            batch, seq_len = 128, 128
        else:
            cfg = T.BertConfig(vocab_size=1024, d_model=128, n_layer=2,
                               n_head=4, d_inner=256, max_pos=128)
            batch, seq_len = 4, 64
        feeds, logits, loss = T.build_bert_pretrain(
            cfg, seq_len, fused_head=True, arange_pos=True)
        optimizer = pt.amp.decorate(opt.AdamOptimizer(learning_rate=1e-4))
        optimizer.minimize(loss)
        exe = pt.Executor()
        exe.run(pt.default_startup_program(), scope=scope)
        rng = np.random.RandomState(0)
        feed = {"src_ids": rng.randint(1, cfg.vocab_size,
                                       (batch, seq_len)).astype(np.int32),
                "lm_label": rng.randint(0, cfg.vocab_size,
                                        (batch, seq_len)).astype(np.int32)}
        lv, = exe.run(feed=feed, fetch_list=[loss.name], scope=scope)
        float(np.asarray(lv))


def main():
    import jax
    from paddle_tpu import memory

    plans, failed = {}, []
    for name, fn in (("resnet50_b256_train_step", _one_step_rn50),
                     ("bert_base_b128_s128_train_step", _one_step_bert)):
        before = set(memory.hbm_plans())
        try:
            fn()
        except Exception as e:  # boundary: report, measure the next, exit 1
            import traceback
            traceback.print_exc()
            plans[name] = {"error": str(e)[:300]}
            failed.append(name)
            continue
        # the step's plan is the newest train block's (the startup
        # program's block is recorded too, as 'other')
        new = {k: v for k, v in memory.hbm_plans().items()
               if k not in before and v["block"] == "train"}
        if new:
            tag, plan = list(new.items())[-1]
            plans[name] = dict(plan, fetch=tag[:80])
            print(f"{name}: {memory.format_plan(plan)}")
    dev = jax.devices()[0]
    print(json.dumps({
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "memory_stats": {k: int(v) for k, v in
                         memory.device_memory_stats().items()
                         if "bytes" in k},
        "plans": plans}))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
