"""Single-device vs multi-device parity tests on the 8-device virtual CPU
mesh — the reference's main correctness harness for its multi-device
executor (ref ``tests/unittests/parallel_executor_test_base.py`` +
``test_parallel_executor_mnist.py``: same model single vs parallel, assert
loss equality), re-targeted at GSPMD sharding."""

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.framework import Executor, Program, program_guard
from paddle_tpu.framework.scope import Scope, scope_guard
from paddle_tpu import optimizer as opt


def _build_mlp(seed):
    np.random.seed(seed)
    x = layers.data("x", shape=[8], dtype="float32")
    y = layers.data("y", shape=[1], dtype="int64")
    h = layers.fc(x, size=16, act="relu")
    pred = layers.fc(h, size=4, act="softmax")
    loss = layers.mean(layers.cross_entropy(pred, y))
    opt.SGDOptimizer(learning_rate=0.1).minimize(loss)
    return loss


def _train(compiled, loss, steps=5, seed=123):
    exe = Executor()
    pt.default_main_program().random_seed = 7
    pt.default_startup_program().random_seed = 7
    exe.run(pt.default_startup_program(), seed=99)
    rng = np.random.RandomState(seed)
    losses = []
    for _ in range(steps):
        xv = rng.rand(16, 8).astype(np.float32)
        yv = rng.randint(0, 4, (16, 1)).astype(np.int64)
        target = compiled if compiled is not None else None
        lv, = exe.run(target, feed={"x": xv, "y": yv},
                      fetch_list=[loss.name])
        losses.append(float(np.asarray(lv)))
    return losses


def test_data_parallel_matches_single_device():
    """sync-DP loss == single-device loss (ref test_dist_base parity,
    delta ≤ 1e-5)."""
    main1, start1 = Program(), Program()
    with program_guard(main1, start1), scope_guard(Scope()):
        loss1 = _build_mlp(0)
        single = _train(None, loss1)

    main2, start2 = Program(), Program()
    with program_guard(main2, start2), scope_guard(Scope()):
        loss2 = _build_mlp(0)
        compiled = pt.CompiledProgram(main2).with_data_parallel(
            loss_name=loss2.name)
        parallel = _train(compiled, loss2)

    np.testing.assert_allclose(single, parallel, rtol=1e-5, atol=1e-6)


def test_tensor_parallel_bert_matches_single():
    """dp×mp GSPMD run equals single-device run — the capability the
    reference lacks entirely (SURVEY §2.5 'What it LACKS: TP')."""
    from paddle_tpu.models import transformer as T

    def build():
        cfg = T.BertConfig(vocab_size=64, d_model=16, n_layer=2, n_head=4,
                           d_inner=32, max_pos=32, dropout=0.0)
        _, logits, loss = T.build_bert_pretrain(cfg, seq_len=8)
        opt.SGDOptimizer(learning_rate=0.05).minimize(loss)
        return loss

    def feed_data(rng):
        return {"src_ids": rng.randint(1, 64, (8, 8)).astype("int64"),
                "pos_ids": np.tile(np.arange(8), (8, 1)).astype("int64"),
                "lm_label": rng.randint(0, 64, (8, 8)).astype("int64")}

    def run(compiled_fn, steps=3):
        main, start = Program(), Program()
        with program_guard(main, start), scope_guard(Scope()):
            loss = build()
            compiled = compiled_fn(main, loss)
            exe = Executor()
            main.random_seed = 5
            exe.run(pt.default_startup_program(), seed=11)
            rng = np.random.RandomState(3)
            out = []
            for _ in range(steps):
                lv, = exe.run(compiled, feed=feed_data(rng),
                              fetch_list=[loss.name])
                out.append(float(np.asarray(lv)))
            return out

    single = run(lambda m, l: None)
    from paddle_tpu.models.transformer import annotate_tensor_parallel

    def make_tp(m, l):
        annotate_tensor_parallel(m)
        return pt.CompiledProgram(m).with_distributed(
            axes={"dp": 2, "mp": 4})
    tp = run(make_tp)
    np.testing.assert_allclose(single, tp, rtol=2e-4, atol=1e-5)


def test_dp_actually_shards_batch():
    """The feed must land sharded across the dp axis (not replicated)."""
    import jax
    main, start = Program(), Program()
    with program_guard(main, start), scope_guard(Scope()):
        x = layers.data("x", shape=[4], dtype="float32")
        y = layers.fc(x, size=2)
        compiled = pt.CompiledProgram(main).with_data_parallel(
            loss_name=None)
        exe = Executor()
        exe.run(pt.default_startup_program())
        out = exe.run(compiled, feed={"x": np.ones((16, 4), np.float32)},
                      fetch_list=[y], return_numpy=False)[0]
        assert out.shape == (16, 2)
        # the fc ran under the mesh: its output sharding spans 8 devices
        assert len(out.sharding.device_set) == 8


def test_hierarchical_mesh_and_allreduce():
    """2-level dcn×ici mesh: hierarchical psum == flat psum (ref
    NCCLCommunicator hierarchical allreduce semantics)."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.parallel import (hierarchical_allreduce,
                                     make_hierarchical_mesh)

    mesh = make_hierarchical_mesh(2, 4)
    x = jnp.arange(8.0)

    def f(v):
        return hierarchical_allreduce(v)

    out = shard_map(f, mesh=mesh, in_specs=P(("dcn", "ici")),
                    out_specs=P())(x)
    assert float(out[0]) == float(x.sum())


def test_trainer_factory_api():
    from paddle_tpu.trainer_factory import TrainerFactory
    from paddle_tpu.trainer_desc import DistMultiTrainer
    from paddle_tpu.device_worker import DownpourSGD
    t = TrainerFactory()._create_trainer(
        {"trainer": "DistMultiTrainer", "device_worker": "DownpourSGD",
         "thread_num": 4, "fetch_var_names": ["loss"], "fetch_info": ["l"]})
    assert isinstance(t, DistMultiTrainer)
    assert isinstance(t._device_worker, DownpourSGD)
    assert t._thread_num == 4
    assert t._desc()["fetch_vars"] == ["loss"]


def test_zero1_optimizer_state_sharding():
    """ZeRO-1 (`with_distributed(zero_stage=1)`): Adam moments live
    SHARDED over dp in the scope between steps, while training losses
    match the replicated run exactly."""
    import jax

    def run(zero):
        main, start = Program(), Program()
        with program_guard(main, start), scope_guard(Scope()):
            main.random_seed = 7
            start.random_seed = 7
            x = layers.data("x", shape=[16], dtype="float32")
            y = layers.data("y", shape=[1], dtype="int64")
            h = layers.fc(x, size=32, act="relu", name="z1_fc1")
            pred = layers.fc(h, size=4, act="softmax", name="z1_fc2")
            loss = layers.mean(layers.cross_entropy(pred, y))
            opt.AdamOptimizer(learning_rate=0.01).minimize(loss)
            compiled = pt.CompiledProgram(main).with_distributed(
                axes={"dp": 8}, zero_stage=1 if zero else 0)
            exe = Executor()
            exe.run(pt.default_startup_program(), seed=99)
            rng = np.random.RandomState(3)
            losses = []
            from paddle_tpu.framework.scope import global_scope
            for _ in range(4):
                xv = rng.rand(16, 16).astype(np.float32)
                yv = rng.randint(0, 4, (16, 1)).astype(np.int64)
                lv, = exe.run(compiled, feed={"x": xv, "y": yv},
                              fetch_list=[loss.name])
                losses.append(float(np.asarray(lv)))
            scope = global_scope()
            moment = next(
                (scope.find_var(n) for n in scope.local_var_names()
                 if "moment1" in n and "z1_fc1.w" in n), None)
            return losses, moment

    base_losses, m0 = run(zero=False)
    zero_losses, m1 = run(zero=True)
    np.testing.assert_allclose(base_losses, zero_losses,
                               rtol=2e-4, atol=1e-6)
    assert m1 is not None
    # the ZeRO run's moment is partitioned over dp (dim 0 spec 'dp');
    # the baseline's is fully replicated on every device
    spec = m1.sharding.spec
    assert spec and spec[0] == "dp", f"moment not dp-sharded: {spec}"
    assert m0.sharding.spec[0] is None if m0.sharding.spec else True


def test_zero1_composes_with_tensor_parallel():
    """ZeRO-1 must COMBINE with TP: an accumulator of an mp-sharded
    param gets dim-0 dp sharding on top of the inherited mp spec."""
    main, start = Program(), Program()
    with program_guard(main, start), scope_guard(Scope()):
        x = layers.data("x", shape=[8], dtype="float32")
        w = layers.create_parameter([8, 16], "float32", name="ztp_w")
        w.dist_spec = (None, "mp")          # Megatron column-parallel
        loss = layers.mean(layers.matmul(x, w) ** 2)
        opt.AdamOptimizer(learning_rate=0.01).minimize(loss)
        compiled = pt.CompiledProgram(main).with_distributed(
            axes={"dp": 2, "mp": 4}, zero_stage=1)
        exe = Executor()
        exe.run(pt.default_startup_program(), seed=5)
        lv, = exe.run(compiled,
                      feed={"x": np.ones((4, 8), np.float32)},
                      fetch_list=[loss.name])
        assert np.isfinite(float(np.asarray(lv)))
        from paddle_tpu.framework.scope import global_scope
        scope = global_scope()
        moment = next(
            (scope.find_var(n) for n in scope.local_var_names()
             if "moment1" in n and "ztp_w" in n), None)
    assert moment is not None
    spec = moment.sharding.spec
    assert tuple(spec) == ("dp", "mp"), f"want (dp, mp), got {spec}"
