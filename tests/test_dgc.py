"""DGC sparse-allreduce tests (ref details/sparse_all_reduce_op_handle.cc,
DGCMomentumOptimizer optimizer.py:809)."""

import numpy as np

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu import optimizer as opt
from paddle_tpu.framework import Executor
from paddle_tpu.framework.core import Program, program_guard
from paddle_tpu.framework.scope import Scope, scope_guard
from paddle_tpu.parallel import DGCGradAllReduce

_EPS = ",".join(f"127.0.0.1:{6170 + i}" for i in range(8))


def _build():
    np.random.seed(0)
    x = layers.data("x", shape=[8], dtype="float32")
    y = layers.data("y", shape=[1], dtype="int64")
    h = layers.fc(x, size=16, act="relu")
    pred = layers.fc(h, size=4, act="softmax")
    loss = layers.mean(layers.cross_entropy(pred, y))
    return loss


def _feeds(steps):
    rng = np.random.RandomState(1)
    out = []
    for _ in range(steps):
        x = rng.rand(16, 8).astype("float32")
        y = x[:, :4].argmax(1).reshape(-1, 1).astype("int64")  # learnable
        out.append({"x": x, "y": y})
    return out


def _run(optimizer, transpile, steps=6):
    main, start = Program(), Program()
    with program_guard(main, start), scope_guard(Scope()):
        loss = _build()
        optimizer().minimize(loss)
        if transpile:
            DGCGradAllReduce().transpile(
                rank=0, endpoints=_EPS, current_endpoint="127.0.0.1:6170")
        exe = Executor()
        exe.run(pt.default_startup_program(), seed=42)
        out = []
        for f in _feeds(steps):
            lv, = exe.run(feed=f, fetch_list=[loss.name])
            out.append(float(np.asarray(lv).mean()))
        return out


def test_dgc_rampup_matches_dense_momentum():
    """Before rampup_begin_step DGC == plain sync momentum DP (dense
    mean-grad phase)."""
    dense = _run(lambda: opt.MomentumOptimizer(0.1, 0.9), transpile=False,
                 steps=4)
    dgc = _run(lambda: opt.DGCMomentumOptimizer(
        0.1, 0.9, rampup_begin_step=1000), transpile=True, steps=4)
    np.testing.assert_allclose(dense, dgc, rtol=1e-4, atol=1e-5)


def test_dgc_sparse_phase_trains():
    """Sparse phase (sparsity .9) must still converge on the task."""
    out = _run(lambda: opt.DGCMomentumOptimizer(
        0.1, 0.9, rampup_begin_step=0, sparsity=[0.9]),
        transpile=True, steps=25)
    first, last = np.mean(out[:5]), np.mean(out[-5:])
    assert last < first - 0.1, f"no progress: {first} -> {last}"


def test_dgc_op_units():
    """dgc_allreduce state mechanics single-device: top-1 of |v| is synced,
    selected u/v slots reset, unselected accumulate."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.framework import registry

    info = registry.get_op_info("dgc_allreduce")

    class Ctx:
        collective_axis = None

    g = jnp.array([1.0, -3.0, 0.5, 0.25])
    u = jnp.zeros(4)
    v = jnp.zeros(4)
    s = jnp.zeros(1)
    outs = info.lower(Ctx(), {"X": [g], "U": [u], "V": [v], "Step": [s]},
                      {"mu": 0.0, "sparsity": 0.75, "rampup_begin_step": 0})
    out = np.asarray(outs["Out"][0])
    np.testing.assert_allclose(out, [0, -3.0, 0, 0], atol=1e-6)
    np.testing.assert_allclose(np.asarray(outs["UOut"][0]),
                               [1.0, 0, 0.5, 0.25], atol=1e-6)
    np.testing.assert_allclose(np.asarray(outs["VOut"][0]),
                               [1.0, 0, 0.5, 0.25], atol=1e-6)
    assert float(outs["StepOut"][0][0]) == 1.0


def test_dgc_nesterov_rampup_parity_and_clip():
    dense = _run(lambda: opt.MomentumOptimizer(0.1, 0.9, use_nesterov=True),
                 transpile=False, steps=4)
    dgc = _run(lambda: opt.DGCMomentumOptimizer(
        0.1, 0.9, use_nesterov=True, rampup_begin_step=1000),
        transpile=True, steps=4)
    np.testing.assert_allclose(dense, dgc, rtol=1e-4, atol=1e-5)
    # local_grad_clip_norm wires a dgc_clip_by_norm op and still trains
    out = _run(lambda: opt.DGCMomentumOptimizer(
        0.1, 0.9, rampup_begin_step=0, sparsity=[0.9],
        local_grad_clip_norm=1.0), transpile=True, steps=8)
    assert all(np.isfinite(out))


def test_dgc_eager_mode_degrades_to_momentum():
    """EagerBlock has no .ops — the DGC tag must not crash dygraph mode."""
    import paddle_tpu.dygraph as dg
    with dg.guard():
        layer = dg.nn.FC("fc_eager", size=2)
        x = dg.to_variable(np.ones((2, 3), np.float32))
        t = dg.default_tracer()
        loss = t.trace_op("mean", {"X": [layer(x)]}, {})["Out"][0]
        o = opt.DGCMomentumOptimizer(0.1, 0.9)
        o.minimize(loss, parameter_list=layer.parameters())


def test_an_op_type_has_one_lowering():
    """A second ``register_op`` of a registered type by another function
    raises and leaves the first in place (import order used to pick between
    two ``dgc_momentum`` lowerings); the same function may register again,
    as a reloaded module does."""
    import pytest

    from paddle_tpu.framework import registry
    from paddle_tpu.parallel import dgc

    held = registry.get_op_info("dgc_momentum")
    assert held.lower is dgc._dgc_momentum       # plain SGD on the u buffer

    def other(ctx, ins, attrs):
        return {}
    with pytest.raises(ValueError, match="dgc_momentum.*already registered"):
        registry.register_op("dgc_momentum", other, no_grad=True)
    assert registry.get_op_info("dgc_momentum").lower is dgc._dgc_momentum
    registry.register_op("dgc_momentum", dgc._dgc_momentum, no_grad=True)
    assert registry.get_op_info("dgc_momentum").no_grad
