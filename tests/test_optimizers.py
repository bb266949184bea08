"""Optimizer tests — each optimizer must reduce loss on a tiny regression
problem, and SGD/Adam must match hand-computed numpy updates (≈ ref
tests/unittests/test_sgd_op.py, test_adam_op.py, test_momentum_op.py...)."""

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.framework import Executor, Program, program_guard
from paddle_tpu.framework.scope import global_scope
from paddle_tpu import optimizer as opt


def _build_and_train(opt_factory, steps=60):
    np.random.seed(0)
    x = layers.data("x", shape=[4], dtype="float32")
    y = layers.data("y", shape=[1], dtype="float32")
    pred = layers.fc(x, size=1)
    loss = layers.mean(layers.square_error_cost(pred, y))
    optimizer = opt_factory()
    optimizer.minimize(loss)
    exe = Executor()
    exe.run(pt.default_startup_program())
    w_true = np.array([[1.0], [-2.0], [3.0], [0.5]], np.float32)
    losses = []
    for i in range(steps):
        xv = np.random.rand(16, 4).astype(np.float32)
        yv = xv @ w_true
        lv, = exe.run(feed={"x": xv, "y": yv}, fetch_list=[loss])
        losses.append(float(lv))
    return losses


@pytest.mark.parametrize("factory", [
    lambda: opt.SGD(learning_rate=0.1),
    lambda: opt.Momentum(learning_rate=0.05, momentum=0.9),
    lambda: opt.Momentum(learning_rate=0.05, momentum=0.9, use_nesterov=True),
    lambda: opt.Adam(learning_rate=0.1),
    lambda: opt.AdamW(learning_rate=0.1, weight_decay=0.01),
    lambda: opt.Adamax(learning_rate=0.1),
    lambda: opt.Adagrad(learning_rate=0.5),
    lambda: opt.DecayedAdagrad(learning_rate=0.5),
    lambda: opt.Adadelta(learning_rate=10.0),
    lambda: opt.RMSProp(learning_rate=0.05),
    lambda: opt.RMSProp(learning_rate=0.05, centered=True, momentum=0.9),
    lambda: opt.Ftrl(learning_rate=0.5),
    lambda: opt.Lamb(learning_rate=0.05),
    lambda: opt.LarsMomentum(learning_rate=30.0, momentum=0.9),
], ids=["sgd", "momentum", "nesterov", "adam", "adamw", "adamax", "adagrad",
        "decayed_adagrad", "adadelta", "rmsprop", "rmsprop_centered", "ftrl",
        "lamb", "lars"])
def test_optimizer_decreases_loss(factory):
    losses = _build_and_train(factory)
    # per-batch losses are noisy: compare head vs tail windows
    assert np.mean(losses[-4:]) < np.mean(losses[:4]) * 0.9, losses


def test_sgd_exact_update():
    x = layers.data("x", shape=[2], dtype="float32")
    pred = layers.fc(x, size=1, bias_attr=False)
    loss = layers.mean(pred)
    optimizer = opt.SGD(learning_rate=0.5)
    optimizer.minimize(loss)
    exe = Executor()
    exe.run(pt.default_startup_program())
    w0 = np.asarray(global_scope().find_var("fc_0.w_0")).copy()
    xv = np.array([[1.0, 2.0], [3.0, 4.0]], np.float32)
    exe.run(feed={"x": xv}, fetch_list=[loss])
    w1 = np.asarray(global_scope().find_var("fc_0.w_0"))
    # dL/dW = x^T @ (1/2) / 1  →  mean over batch&dim: grad = mean_b x / 1
    grad = xv.mean(axis=0)[:, None] / 1.0
    np.testing.assert_allclose(w1, w0 - 0.5 * grad, rtol=1e-5)


def test_adam_exact_first_step():
    x = layers.data("x", shape=[2], dtype="float32")
    pred = layers.fc(x, size=1, bias_attr=False)
    loss = layers.mean(pred)
    b1, b2, eps, lr = 0.9, 0.999, 1e-8, 0.1
    optimizer = opt.Adam(learning_rate=lr, beta1=b1, beta2=b2, epsilon=eps)
    optimizer.minimize(loss)
    exe = Executor()
    exe.run(pt.default_startup_program())
    w0 = np.asarray(global_scope().find_var("fc_0.w_0")).copy()
    xv = np.array([[1.0, 2.0], [3.0, 4.0]], np.float32)
    exe.run(feed={"x": xv}, fetch_list=[loss])
    w1 = np.asarray(global_scope().find_var("fc_0.w_0"))
    g = xv.mean(axis=0)[:, None]
    m = (1 - b1) * g
    v = (1 - b2) * g * g
    lr_t = lr * np.sqrt(1 - b2) / (1 - b1)
    expect = w0 - lr_t * m / (np.sqrt(v) + eps)
    np.testing.assert_allclose(w1, expect, rtol=1e-4)


def test_lr_scheduler_noam():
    x = layers.data("x", shape=[2], dtype="float32")
    pred = layers.fc(x, size=1)
    loss = layers.mean(layers.square(pred))
    lr = layers.learning_rate_scheduler.noam_decay(128, warmup_steps=10)
    optimizer = opt.Adam(learning_rate=lr)
    optimizer.minimize(loss)
    exe = Executor()
    exe.run(pt.default_startup_program())
    xv = np.ones((4, 2), np.float32)
    lrs = []
    for _ in range(3):
        lv, = exe.run(feed={"x": xv}, fetch_list=[lr])
        lrs.append(float(np.asarray(lv).reshape(-1)[0]))
    # warmup: lr increases
    assert lrs[1] > lrs[0] and lrs[2] > lrs[1]
    expect = (128 ** -0.5) * (1 * 10 ** -1.5)
    np.testing.assert_allclose(lrs[0], expect, rtol=1e-5)


def test_l2_regularizer_changes_update():
    x = layers.data("x", shape=[2], dtype="float32")
    pred = layers.fc(x, size=1, bias_attr=False)
    loss = layers.mean(pred)
    optimizer = opt.SGD(learning_rate=0.5,
                        regularization=pt.regularizer.L2Decay(0.1))
    optimizer.minimize(loss)
    exe = Executor()
    exe.run(pt.default_startup_program())
    w0 = np.asarray(global_scope().find_var("fc_0.w_0")).copy()
    xv = np.array([[1.0, 2.0], [3.0, 4.0]], np.float32)
    exe.run(feed={"x": xv}, fetch_list=[loss])
    w1 = np.asarray(global_scope().find_var("fc_0.w_0"))
    grad = xv.mean(axis=0)[:, None] + 0.1 * w0
    np.testing.assert_allclose(w1, w0 - 0.5 * grad, rtol=1e-5)


def test_global_norm_clip():
    x = layers.data("x", shape=[2], dtype="float32")
    pred = layers.fc(x, size=1, bias_attr=False)
    loss = layers.mean(pred)
    optimizer = opt.SGD(learning_rate=1.0,
                        grad_clip=pt.GradientClipByGlobalNorm(0.001))
    optimizer.minimize(loss)
    exe = Executor()
    exe.run(pt.default_startup_program())
    w0 = np.asarray(global_scope().find_var("fc_0.w_0")).copy()
    xv = np.array([[1.0, 2.0], [3.0, 4.0]], np.float32)
    exe.run(feed={"x": xv}, fetch_list=[loss])
    w1 = np.asarray(global_scope().find_var("fc_0.w_0"))
    # update magnitude bounded by clip norm
    assert np.abs(w1 - w0).sum() <= 0.01
