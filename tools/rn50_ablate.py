"""ResNet-50 step-time ablation on the real chip (round-3 perf work).

Locates where the 113 ms step goes: fwd vs bwd, stem, per-stage cost,
batch size, s2d stem.  Timing: steps are chained through the executor's
persistable state with ONE closing host sync (fetching the scalar loss).

Run: PYTHONPATH=/root/repo:$PYTHONPATH python tools/rn50_ablate.py
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def timed(build, feed_fn, steps=24):
    import jax
    import paddle_tpu as pt
    from paddle_tpu.framework import Program, Scope, program_guard, \
        scope_guard
    scope = Scope()
    with scope_guard(scope), program_guard(Program(), Program()):
        loss = build()
        exe = pt.Executor()
        exe.run(pt.default_startup_program(), scope=scope)
        feed = {k: jax.device_put(v) for k, v in feed_fn().items()}
        lv, = exe.run(feed=feed, fetch_list=[loss.name], scope=scope)
        l0 = float(np.asarray(lv))
        t0 = time.perf_counter()
        for _ in range(steps):
            lv, = exe.run(feed=feed, fetch_list=[loss.name], scope=scope,
                          return_numpy=False)
        lN = float(np.asarray(lv))
        dt = (time.perf_counter() - t0) / steps
    return dt, l0, lN


def rn50_build(batch, s2d=False, train=True, stages=4, class_dim=1000):
    import paddle_tpu as pt
    from paddle_tpu import layers, optimizer as opt
    from paddle_tpu.models import resnet as R

    def build():
        shape = (12, 112, 112) if s2d else (3, 224, 224)
        img = layers.data("image", shape=list(shape), dtype="float32")
        label = layers.data("label", shape=[1], dtype="int64")
        if stages == 4:
            pred = R.resnet(img, class_dim, 50, s2d_stem=s2d)
            loss = layers.mean(layers.cross_entropy(pred, label))
        else:
            # truncated model: stem [+ pool] + stages[0:stages]
            x = R.conv_bn_layer(img, 64, 3 if s2d else 7,
                                stride=1 if s2d else 2, act="relu",
                                name="stem")
            x = layers.pool2d(x, pool_size=3, pool_stride=2, pool_padding=1)
            filters = [64, 128, 256, 512]
            counts = [3, 4, 6, 3]
            for stage in range(stages):
                for blk in range(counts[stage]):
                    stride = 2 if blk == 0 and stage > 0 else 1
                    x = R.bottleneck_block(x, filters[stage], stride,
                                           f"res{stage}_{blk}")
            loss = layers.mean(x)
        if train:
            optimizer = pt.amp.decorate(
                opt.MomentumOptimizer(learning_rate=0.1, momentum=0.9))
            optimizer.minimize(loss)
        else:
            pt.amp.enable()
        return loss

    def feed_fn():
        rng = np.random.RandomState(0)
        shape = (12, 112, 112) if s2d else (3, 224, 224)
        return {
            "image": rng.rand(batch, *shape).astype(np.float32),
            "label": rng.randint(0, class_dim, (batch, 1)).astype(np.int32),
        }
    return build, feed_fn


def main():
    results = {}

    def run(name, *a, steps=24, **kw):
        b, f = rn50_build(*a, **kw)
        dt, l0, lN = timed(b, f, steps=steps)
        results[name] = round(dt * 1000, 2)
        print(f"{name:32s} {dt*1000:8.2f} ms/step   loss {l0:.3f}->{lN:.3f}",
              flush=True)

    run("base_b256_train", 256)
    run("base_b256_fwd", 256, train=False)
    run("s2d_b256_train", 256, s2d=True)
    run("s2d_b256_fwd", 256, s2d=True, train=False)
    run("base_b512_train", 512, steps=12)
    run("s2d_b512_train", 512, s2d=True, steps=12)
    # per-stage accumulation (train): stempool -> +stage0 -> ... -> +stage3
    for k in range(5):
        run(f"trunc_stages{k}_b256_train", 256, stages=k)
    print(json.dumps(results))


if __name__ == "__main__":
    main()
