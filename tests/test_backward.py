"""append_backward tests: analytic grads vs numeric central differences —
the OpTest check_grad pattern (ref tests/unittests/op_test.py:767,
get_numeric_gradient:46)."""

import itertools

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.framework import (Executor, Program, append_backward,
                                  grad_var_name, program_guard)
from paddle_tpu.framework.core import default_main_program
from paddle_tpu.framework.registry import register_op


def _numeric_grad(run_loss, x0, eps=1e-3):
    g = np.zeros_like(x0)
    flat = x0.reshape(-1)
    for i in range(flat.size):
        orig = flat[i]
        flat[i] = orig + eps
        lp = run_loss(x0)
        flat[i] = orig - eps
        lm = run_loss(x0)
        flat[i] = orig
        g.reshape(-1)[i] = (lp - lm) / (2 * eps)
    return g


def test_fc_grad_matches_numeric():
    np.random.seed(0)
    x = layers.data("x", shape=[4], dtype="float32", stop_gradient=False)
    x.stop_gradient = False
    y = layers.fc(x, size=3)
    loss = layers.mean(y)
    append_backward(loss)
    block = default_main_program().global_block()
    xg = block.var(grad_var_name("x"))

    exe = Executor()
    exe.run(pt.default_startup_program())
    xv = np.random.rand(2, 4).astype(np.float32)

    def run_loss(xval):
        out, = exe.run(feed={"x": xval.astype(np.float32)},
                       fetch_list=[loss])
        return float(out)

    got, = exe.run(feed={"x": xv}, fetch_list=[xg])
    want = _numeric_grad(run_loss, xv.copy())
    np.testing.assert_allclose(got, want, rtol=1e-2, atol=1e-3)


def test_grad_accumulation_multi_consumer():
    """A var consumed by two ops must get summed grads
    (ref backward.py _addup_repetitive_outputs_)."""
    x = layers.data("x", shape=[3], dtype="float32")
    x.stop_gradient = False
    a = layers.scale(x, scale=2.0)
    b = layers.scale(x, scale=3.0)
    loss = layers.mean(a + b)
    append_backward(loss)
    block = default_main_program().global_block()
    xg = block.var(grad_var_name("x"))
    exe = Executor()
    exe.run(pt.default_startup_program())
    out, = exe.run(feed={"x": np.ones((2, 3), np.float32)}, fetch_list=[xg])
    np.testing.assert_allclose(out, np.full((2, 3), 5.0 / 6.0), rtol=1e-5)


def test_softmax_ce_custom_grad():
    np.random.seed(1)
    x = layers.data("x", shape=[5], dtype="float32")
    x.stop_gradient = False
    label = layers.data("label", shape=[1], dtype="int64")
    loss = layers.mean(layers.softmax_with_cross_entropy(x, label))
    append_backward(loss)
    block = default_main_program().global_block()
    xg = block.var(grad_var_name("x"))
    exe = Executor()
    exe.run(pt.default_startup_program())
    xv = np.random.randn(4, 5).astype(np.float32)
    lv = np.random.randint(0, 5, (4, 1)).astype(np.int64)

    def run_loss(xval):
        out, = exe.run(feed={"x": xval.astype(np.float32), "label": lv},
                       fetch_list=[loss])
        return float(out)

    got, = exe.run(feed={"x": xv, "label": lv}, fetch_list=[xg])
    want = _numeric_grad(run_loss, xv.copy(), eps=1e-2)
    np.testing.assert_allclose(got, want, rtol=5e-2, atol=1e-3)


def test_stop_gradient_blocks_grad():
    x = layers.data("x", shape=[3], dtype="float32")
    x.stop_gradient = False
    w = layers.scale(x, scale=2.0)
    w.stop_gradient = True
    loss = layers.mean(w + x)
    append_backward(loss)
    block = default_main_program().global_block()
    exe = Executor()
    exe.run(pt.default_startup_program())
    xg = block.var(grad_var_name("x"))
    out, = exe.run(feed={"x": np.ones((1, 3), np.float32)}, fetch_list=[xg])
    # only the identity path contributes: d(mean(x))/dx = 1/3
    np.testing.assert_allclose(out, np.full((1, 3), 1.0 / 3.0), rtol=1e-5)


def test_fanout_with_consuming_grad_op():
    """Multi-reader fan-out where one consumer's grad op also reads the
    shared grad name: contributions are summed before that reader."""
    x = layers.data("x", shape=[3], dtype="float32")
    x.stop_gradient = False
    b = layers.scale(x, scale=2.0)              # b = 2x
    c = layers.scale(b, scale=3.0)              # consumer of b
    loss = layers.mean(b) + layers.mean(c) + layers.mean(b * b)
    append_backward(loss)
    exe = Executor()
    exe.run(pt.default_startup_program())
    xv = np.array([[1.0, 2.0, 3.0]], np.float32)
    out, = exe.run(feed={"x": xv}, fetch_list=[grad_var_name("x")])
    # d/dx [ mean(2x) + mean(6x) + mean(4x^2) ] = (2 + 6 + 8x)/3
    np.testing.assert_allclose(out, (8.0 + 8.0 * xv) / 3.0, rtol=1e-5)


def _four_streams(ctx, ins, attrs):
    x, = ins["X"]
    return {"Out": [x * float(i + 1) for i in range(4)]}


def test_a_slots_gradient_sums_precede_their_reader_in_slot_order():
    """One grad op reading a four-variable slot whose gradients each have
    two contributions (Xing4.0's ``hc_post_grad`` over the four residual
    streams): the four ``sum`` ops enter the program in the slot's order
    whatever the variables are called.  Permuting the names permutes their
    hashes, so a walk over a ``set`` of them fails for some permutation in
    any process, and the program's op order is the compile cache's key."""
    register_op("toy_four_streams", _four_streams)
    for names in itertools.permutations(["s_a", "s_b", "s_c", "s_d"]):
        main = Program()
        with program_guard(main, Program()):
            x = layers.data("x", shape=[3], dtype="float32")
            x.stop_gradient = False
            block = main.global_block()
            streams = [block.create_var(name=n, shape=x.shape,
                                        dtype="float32") for n in names]
            block.append_op("toy_four_streams", inputs={"X": [x.name]},
                            outputs={"Out": list(names)})
            loss = layers.mean(layers.sums(
                [layers.scale(s, scale=k) for s in streams
                 for k in (2.0, 3.0)]))
            append_backward(loss)
        ops = block.ops
        at = next(i for i, op in enumerate(ops)
                  if op.type == "toy_four_streams_grad")
        assert ops[at].input("OG$Out") == [grad_var_name(n) for n in names]
        sums = ops[at - 4:at]
        assert [op.type for op in sums] == ["sum"] * 4, names
        assert [op.output("Out") for op in sums] == \
            [[grad_var_name(n)] for n in names], names
        assert all(len(op.input("X")) == 2 for op in sums)
    # the last permutation's numbers: d/dx mean(5 * (1+2+3+4) * x) = 50 / 3
    exe = Executor()
    out, = exe.run(main, feed={"x": np.ones((1, 3), np.float32)},
                   fetch_list=[grad_var_name("x")])
    np.testing.assert_allclose(out, np.full((1, 3), 50.0 / 3.0), rtol=1e-5)
