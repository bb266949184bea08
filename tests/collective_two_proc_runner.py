"""Two-process collective trainer (ref test_dist_base.py:442 pattern).

Launched by ``paddle_tpu.distributed.launch --nproc_per_node 2`` (the env
contract provides rank/endpoints).  Each process joins the cluster via
``init_parallel_env`` (jax.distributed over the CPU backend — one device
per process, two global devices), transpiles GradAllReduce, trains a
deterministic model on the SAME global batch, and prints its per-step
losses as one JSON line tagged LOSSES.  The pytest driver compares them
against a single-process run of the identical program.
"""

import json
import os
import sys

import numpy as np


def build_and_train(steps=4):
    import paddle_tpu as pt
    from paddle_tpu import layers
    from paddle_tpu import optimizer as opt
    from paddle_tpu.distributed.transpiler import GradAllReduce
    from paddle_tpu.distributed.env import Env, init_parallel_env
    from paddle_tpu.framework import (Program, Scope, program_guard,
                                      scope_guard)

    env = Env()
    world = env.world_size
    if world > 1:
        init_parallel_env()

    scope = Scope()
    with scope_guard(scope), program_guard(Program(), Program()):
        x = layers.data("x", shape=[8], dtype="float32")
        y = layers.data("y", shape=[1], dtype="float32")
        h = layers.fc(x, size=16, act="tanh")
        pred = layers.fc(h, size=1)
        loss = layers.mean(layers.square_error_cost(pred, y))
        opt.SGDOptimizer(0.1).minimize(loss)
        if world > 1:
            GradAllReduce().transpile(
                rank=env.rank, endpoints=env.trainer_endpoints,
                current_endpoint=env.current_endpoint)
        exe = pt.Executor()
        exe.run(pt.default_startup_program(), scope=scope, seed=42)

        rng = np.random.RandomState(7)           # same batch everywhere
        xv = rng.rand(8, 8).astype(np.float32)
        yv = xv.sum(1, keepdims=True).astype(np.float32)
        losses = []
        for _ in range(steps):
            lv, = exe.run(feed={"x": xv, "y": yv},
                          fetch_list=[loss.name], scope=scope)
            arr = np.asarray(lv)
            # collective mode returns per-rank stacked losses; equal-size
            # shards make their mean the global-batch mean
            losses.append(float(arr.mean()))
        return losses


def ring_attention_check():
    """Ring attention with the sp ring spanning REAL processes: each of
    the two processes hosts one device of a global 2-device mesh; KV
    shards rotate cross-process via ppermute.  The local output shard is
    compared against a fully-local dense reference — the multi-host
    long-context proof (SURVEY §5.7/§5.8)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from jax import shard_map
    from paddle_tpu.pallas import mha_reference, ring_attention

    B, H, T, D = 1, 2, 16, 8
    rng = np.random.RandomState(11)
    q, k, v = (rng.randn(B, H, T, D).astype(np.float32) * 0.3
               for _ in range(3))
    mesh = Mesh(np.array(jax.devices()), ("sp",))   # 2 global devices
    sh = NamedSharding(mesh, P(None, None, "sp", None))

    def mk(a):
        return jax.make_array_from_callback(a.shape, sh,
                                            lambda idx: a[idx])

    spec = P(None, None, "sp", None)
    fn = jax.jit(shard_map(
        lambda q, k, v: ring_attention(q, k, v, "sp", causal=False),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec))
    out = fn(mk(q), mk(k), mk(v))
    ref = np.asarray(mha_reference(jnp.asarray(q), jnp.asarray(k),
                                   jnp.asarray(v), causal=False))
    shard = out.addressable_shards[0]
    err = float(np.abs(np.asarray(shard.data) - ref[shard.index]).max())
    return {"ok": bool(err < 2e-4), "max_err": err}


def _gspmd_run(make_optimizer, zero_stage=0, steps=4):
    """Shared harness for the multi-host GSPMD checks: build, seed, slice
    this host's half of the global batch, train, return losses."""
    import paddle_tpu as pt
    from paddle_tpu import layers
    from paddle_tpu.distributed.env import Env
    from paddle_tpu.framework import (Program, Scope, program_guard,
                                      scope_guard)

    env = Env()
    scope = Scope()
    main_p, start_p = Program(), Program()
    with scope_guard(scope), program_guard(main_p, start_p):
        main_p.random_seed = 7
        start_p.random_seed = 7
        x = layers.data("x", shape=[8], dtype="float32")
        y = layers.data("y", shape=[1], dtype="float32")
        h = layers.fc(x, size=16, act="tanh")
        pred = layers.fc(h, size=1)
        loss = layers.mean(layers.square_error_cost(pred, y))
        make_optimizer().minimize(loss)
        compiled = pt.CompiledProgram(main_p).with_distributed(
            axes={"dp": 2}, zero_stage=zero_stage) \
            if env.world_size > 1 else None
        exe = pt.Executor()
        exe.run(pt.default_startup_program(), scope=scope, seed=42)
        rng = np.random.RandomState(7)
        xv = rng.rand(8, 8).astype(np.float32)       # GLOBAL batch
        yv = xv.sum(1, keepdims=True).astype(np.float32)
        if env.world_size > 1:                       # this host's half
            half = 8 // 2
            sl = slice(env.rank * half, (env.rank + 1) * half)
            xv, yv = xv[sl], yv[sl]
        losses = []
        for _ in range(steps):
            lv, = exe.run(compiled, feed={"x": xv, "y": yv},
                          fetch_list=[loss.name], scope=scope)
            losses.append(float(np.asarray(lv)))
        return losses


def gspmd_zero_train(steps=4):
    """ZeRO-1 with the dp axis spanning the two PROCESSES: Adam moments
    are sharded over a cross-host axis, so their first-step host-full
    values must be converted by slicing each device's shard out of the
    full copy (executor _to_global_arrays conv_state — the r3 advisor's
    multi-process zero_stage=1 failure mode)."""
    from paddle_tpu import optimizer as opt
    return _gspmd_run(lambda: opt.AdamOptimizer(0.05), zero_stage=1,
                      steps=steps)


def gspmd_train(steps=4):
    """with_distributed() over the GLOBAL mesh (dp axis spans the two
    processes): each host feeds its half of the global batch; the
    executor assembles global arrays and pjit runs true multi-host
    GSPMD — the NCCL-rank analog of the reference's multi-node DP."""
    from paddle_tpu import optimizer as opt
    return _gspmd_run(lambda: opt.SGDOptimizer(0.1), zero_stage=0,
                      steps=steps)


def main():
    import jax
    jax.config.update("jax_platforms", "cpu")
    losses = build_and_train()
    print("LOSSES " + json.dumps(losses), flush=True)
    from paddle_tpu.distributed.env import Env
    if Env().world_size == 2:
        print("RING " + json.dumps(ring_attention_check()), flush=True)
    print("GSPMD " + json.dumps(gspmd_train()), flush=True)
    print("ZERO " + json.dumps(gspmd_zero_train()), flush=True)


if __name__ == "__main__":
    main()
