#!/usr/bin/env python
"""Fusion smoke (CI gate): the cost-guided fusion pass must

1. rewrite NOTHING when ``FLAGS_graph_fusion`` is off (zero decisions,
   zero fused ops dispatched);
2. with the flag on, apply >= 1 dense-epilogue rewrite on the toy
   training program and leave its conv+bn+relu to XLA, with the fused
   program verifier-clean and the collective fingerprint unchanged;
3. keep loss parity fused-vs-unfused within float tolerance over
   several SGD steps (same params, same per-step seeds).
"""

import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

import paddle_tpu as pt  # noqa: E402
from paddle_tpu import layers, monitor  # noqa: E402
from paddle_tpu import optimizer as opt  # noqa: E402
from paddle_tpu.analysis import fusion  # noqa: E402
from paddle_tpu.framework import (Program, Scope, program_guard,  # noqa: E402
                                  scope_guard)


def counter_total(name, **labels):
    fam = monitor.REGISTRY.get(name)
    if fam is None:
        return 0
    return sum(cell.get() for lbl, cell in fam.series()
               if all(lbl.get(k) == v for k, v in labels.items()))


def main():
    scope = Scope()
    with scope_guard(scope), program_guard(Program(), Program()):
        img = layers.data("image", shape=[3, 8, 8], dtype="float32")
        label = layers.data("label", shape=[1], dtype="int64")
        conv = layers.conv2d(img, num_filters=8, filter_size=1,
                             padding=0, bias_attr=False)
        bn = layers.batch_norm(conv, act="relu")
        pool = layers.pool2d(bn, global_pooling=True, pool_type="avg")
        fc1 = layers.fc(pool, size=16, act="gelu")
        drop = layers.dropout(fc1, dropout_prob=0.1,
                              dropout_implementation="upscale_in_train")
        pred = layers.fc(drop, size=10, act="softmax")
        loss = layers.mean(layers.cross_entropy(pred, label))
        opt.SGDOptimizer(learning_rate=0.1).minimize(loss)
        prog = pt.default_main_program()

        exe0 = pt.Executor()
        exe0.run(pt.default_startup_program(), scope=scope, seed=42)
        snap = {n: np.copy(np.asarray(scope.find_var(n)))
                for n in scope.local_var_names()}
        rng = np.random.RandomState(0)
        feed = {"image": rng.rand(4, 3, 8, 8).astype(np.float32),
                "label": rng.randint(0, 10, (4, 1)).astype(np.int64)}

        def run(steps=4):
            for n, v in snap.items():
                scope.set_var(n, np.copy(v))
            exe = pt.Executor()
            out = []
            for i in range(steps):
                lv, = exe.run(prog, feed=feed, fetch_list=[loss.name],
                              scope=scope, seed=123 + i)
                out.append(float(np.asarray(lv)))
            return out

        # -- gate 1: disabled => zero fusion ------------------------------
        pt.set_flags({"FLAGS_graph_fusion": False})
        before = counter_total("paddle_tpu_fusion_candidates_total")
        base = run()
        assert counter_total("paddle_tpu_fusion_candidates_total") == \
            before, "fusion decisions counted with FLAGS_graph_fusion off"
        fused_prog = fusion.fuse_program(prog, (loss.name,))
        assert fused_prog is prog, "fuse_program rewrote with gate off"
        print(f"gate 1 OK: disabled => untouched (loss {base[0]:.4f} -> "
              f"{base[-1]:.4f})")

        # -- gate 2: enabled => applied + verifier-clean + fp stable ------
        pt.set_flags({"FLAGS_graph_fusion": True})
        fusion.clear_cache()
        fused_prog = fusion.fuse_program(
            prog, (loss.name,), feed_shapes={"image": (4, 3, 8, 8)})
        assert fused_prog is not prog, "no rewrite with gate on"
        rep = fused_prog._attrs["fusion"]
        by = {}
        for c in rep["candidates"]:
            if c["verdict"] == "applied":
                by[c["pattern"]] = by.get(c["pattern"], 0) + 1
        assert by.get("dense_epilogue", 0) >= 1, rep
        assert rep["collective_fingerprint_ok"], rep
        from paddle_tpu.analysis import verify_program
        post = verify_program(fused_prog, (loss.name,))
        assert post.ok, post.diagnostics
        types = [op.type for op in fused_prog.global_block().ops]
        assert "fused_dense_act" in types and "conv2d" in types and \
            "batch_norm" in types, types
        print(f"gate 2 OK: applied={rep['applied']} ({by}), "
              "verifier clean, collective fingerprint unchanged")

        # -- gate 3: loss parity ------------------------------------------
        fused_losses = run()
        worst = max(abs(a - b) for a, b in zip(base, fused_losses))
        assert worst < 5e-3, (base, fused_losses)
        print(f"gate 3 OK: loss parity fused-vs-unfused (max diff "
              f"{worst:.2e})")
    print("fusion smoke OK")


if __name__ == "__main__":
    main()
