"""ResNet's training step under the program's default flags (PR 31).

The ``conv_bn_relu`` rewrite, its ``fused_conv1x1_bn`` op and the Mosaic
kernel under it are gone: a bottleneck ResNet's 1x1 convolutions,
batch-statistics BN and ReLU are lowered by ``conv2d``, ``batch_norm``,
``relu`` and their grad ops, which XLA fuses itself.  These tests guard what
now carries the ``resnet50_imagenet_b256`` cell, at toy widths on the CPU:
depth 50's bottleneck block, ``amp.decorate`` + momentum as the cell trains
it, the site names of ``benchmark/reference/resnet50.py``.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu import optimizer as opt
from paddle_tpu.analysis import cost, fusion
from paddle_tpu.framework import (Program, Scope, ir, program_guard,
                                  registry, scope_guard)
from paddle_tpu.framework.executor import LowerCtx
from paddle_tpu.models.resnet import bottleneck_block, conv_bn_layer
from paddle_tpu.param_attr import ParamAttr

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "tools")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from benchmark import flops, harness  # noqa: E402
from benchmark.reference import resnet50 as reference  # noqa: E402
import dp_arith_check  # noqa: E402

WIDTHS, BLOCKS, CLASSES, IMAGE, BATCH = (8, 16), (2, 1), 10, 32, 16
LR, MU = 0.01, 0.9


@pytest.fixture(autouse=True)
def _default_flags():
    pt.set_flags({"FLAGS_graph_fusion": True})
    fusion.clear_cache()
    yield
    pt.set_flags({"FLAGS_graph_fusion": True})
    fusion.clear_cache()


def _sites():
    """The toy's convolutions under the reference's names: two stages of
    bottlenecks behind the 7x7 stem, shortcuts where depth 50 has them."""
    sites = flops.resnet50_conv_sites(IMAGE, widths=WIDTHS, blocks=BLOCKS)
    by_name = {s["name"]: s for s in sites}
    by_name["stem"]["cout"] = WIDTHS[0]          # the stem at toy width too
    by_name["res0_0.b0"]["cin"] = by_name["res0_0.short"]["cin"] = WIDTHS[0]
    return sites


def _build(amp=True):
    """(scope, main program, loss) of the toy ResNet after its startup
    program ran from a fixed seed."""
    scope, main, startup = Scope(), Program(), Program()
    with scope_guard(scope), program_guard(main, startup):
        img = layers.data("image", shape=[3, IMAGE, IMAGE], dtype="float32")
        label = layers.data("label", shape=[1], dtype="int64")
        x = conv_bn_layer(img, WIDTHS[0], 7, stride=2, act="relu",
                          name="stem")
        x = layers.pool2d(x, pool_size=3, pool_stride=2, pool_padding=1)
        for stage, (width, count) in enumerate(zip(WIDTHS, BLOCKS)):
            for blk in range(count):
                x = bottleneck_block(x, width,
                                     2 if blk == 0 and stage > 0 else 1,
                                     f"res{stage}_{blk}")
        pool = layers.pool2d(x, global_pooling=True, pool_type="avg")
        pred = layers.fc(pool, size=CLASSES, act="softmax",
                         param_attr=ParamAttr(name="fc_out.w"),
                         bias_attr=ParamAttr(name="fc_out.b"))
        loss = layers.mean(layers.cross_entropy(pred, label))
        sgd = opt.MomentumOptimizer(learning_rate=LR, momentum=MU)
        (pt.amp.decorate(sgd) if amp else sgd).minimize(loss)
        pt.Executor().run(startup, scope=scope, seed=3)
    return scope, main, loss


def _feed():
    rng = np.random.RandomState(0)
    return {"image": rng.rand(BATCH, 3, IMAGE, IMAGE).astype(np.float32),
            "label": rng.randint(0, CLASSES, (BATCH, 1)).astype(np.int64)}


def _train(scope, main, loss, steps=3):
    exe, feed = pt.Executor(), _feed()
    return [float(np.asarray(exe.run(main, feed=feed, fetch_list=[loss.name],
                                     scope=scope)[0]))
            for _ in range(steps)]


def _types(program):
    return [op.type for op in program.global_block().ops]


# (a) the pass hands a ResNet back as it was built
def test_toy_resnet_goes_through_the_fusion_pass_unchanged():
    _, main, loss = _build()
    before = _types(main)
    fused = fusion.fuse_program(
        main, (loss.name,),
        feed_shapes={"image": (BATCH, 3, IMAGE, IMAGE)})
    assert fused is main and _types(fused) == before
    assert not [t for t in before if t.startswith("fused_")]
    n_conv = len(_sites())
    n_relu = 1 + 3 * sum(BLOCKS)
    for fwd, n in (("conv2d", n_conv), ("batch_norm", n_conv),
                   ("relu", n_relu)):
        assert before.count(fwd) == n, (fwd, before.count(fwd))
    for grad, n in (("conv2d_grad", n_conv),
                    ("batch_norm_explicit_grad", n_conv),
                    ("relu_grad", n_relu)):
        assert before.count(grad) == n, (grad, before.count(grad))
    # no pattern has a subject here: the classifier's mul + bias add ends
    # in a softmax, not in the activation ``dense_epilogue`` folds
    report = fusion.analyze_program(main, (loss.name,), batch_size=BATCH)
    assert report.decisions == [] and report.applied == 0


# (b) no kernel in the step, traced and lowered as on a TPU
def test_lowered_step_holds_no_mosaic_call(monkeypatch):
    scope, main, loss = _build()
    exe = pt.Executor()
    # every lowering that picks a kernel by platform asks device.on_tpu(),
    # which asks jax.default_backend(): answer as the chip would, so that a
    # kernel on the step's path shows here as it would there
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cb, args = dp_arith_check.caught_step(lambda: exe.run(
        main, feed=_feed(), fetch_list=[loss.name], scope=scope))
    jaxpr = str(jax.make_jaxpr(cb.jitted)(*args))
    assert "pallas_call" not in jaxpr
    assert "conv_general_dilated" in jaxpr          # the step was traced
    text = cb.jitted.trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "stablehlo.convolution" in text
    for word in ("tpu_custom_call", "mosaic", "pallas"):
        assert word not in text.lower(), word


# (c) one program: the flag no longer changes a ResNet's step
def test_default_flags_train_the_flag_off_step_to_the_bit():
    losses = {}
    for on in (True, False):
        pt.set_flags({"FLAGS_graph_fusion": on})
        losses[on] = _train(*_build())
    assert losses[True] == losses[False], losses
    assert len(set(losses[True])) == 3               # and it trains


def _reference_params(scope):
    def g(name):
        return jnp.asarray(np.asarray(scope.find_var(name), np.float32))

    names = [s["name"] for s in _sites()]
    return {"convs": {n: g(f"{n}.conv.w") for n in names},
            "bn": {n: (g(f"{n}.bn.scale"), g(f"{n}.bn.offset"))
                   for n in names},
            "fc_w": g("fc_out.w"), "fc_b": g("fc_out.b")}


# (d) the step against the cell's float32 reference, three steps of
# momentum SGD on both sides: the AMP step inside the cell's own limit
# (read here: 1.5e-4, 6.8e-4, 1.2e-3), the float32 step to rounding
# (5.6e-7, 3.9e-7, 5.1e-5)
@pytest.mark.parametrize("amp", [True, False], ids=["amp", "f32"])
def test_three_steps_stay_inside_the_cells_limit_of_the_reference(amp):
    scope, main, loss = _build(amp=amp)
    params = _reference_params(scope)
    got = _train(scope, main, loss)

    feed = _feed()
    image, label = jnp.asarray(feed["image"]), jnp.asarray(feed["label"])[:, 0]
    config = harness.load_json("benchmark/configs/resnet50.json")
    eps = float(config["bn_epsilon"])
    step = jax.value_and_grad(
        lambda p: reference.train_loss(p, image, label, eps=eps,
                                       blocks=BLOCKS))
    velocity = jax.tree.map(jnp.zeros_like, params)
    want = []
    for _ in range(3):
        value, grads = step(params)
        want.append(float(value))
        velocity = jax.tree.map(lambda v, g: MU * v + g, velocity, grads)
        params = jax.tree.map(lambda p, v: p - LR * v, params, velocity)

    limit = config["loss_tolerance"]["relative"] if amp else 2e-4
    errs = [abs(a - b) / abs(b) for a, b in zip(got, want)]
    assert max(errs) <= limit, (got, want, errs)
    assert want[0] != want[-1]


def _plain_bn(x, scale, bias, eps=1e-5):
    xf = x.astype(jnp.float32)
    m = jnp.mean(xf, axis=(0, 2, 3), keepdims=True)
    v = jnp.mean(jnp.square(xf - m), axis=(0, 2, 3), keepdims=True)
    return (xf - m) / jnp.sqrt(v + eps) * scale[None, :, None, None] \
        + bias[None, :, None, None]


# (e) the grad op every BN site of the step now goes through
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("shape", [(2, 256, 56, 56), (4, 512, 7, 7)],
                         ids=["res0_b2_1x1", "res3_b1_3x3"])
def test_batch_norm_explicit_grad_matches_jax_grad(shape, dtype):
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(*shape).astype(np.float32) * 1.5 + 0.3, dtype)
    gy = jnp.asarray(rng.randn(*shape).astype(np.float32), dtype)
    scale = jnp.asarray(rng.rand(shape[1]).astype(np.float32) + 0.5)
    bias = jnp.asarray(rng.randn(shape[1]).astype(np.float32))

    got = registry.get_op_info("batch_norm_explicit_grad").lower(
        LowerCtx(0),
        {"X$X": [x], "X$Scale": [scale], "X$Bias": [bias], "OG$Y": [gy]},
        {"epsilon": 1e-5, "momentum": 0.9, "data_layout": "NCHW",
         "is_test": False, "use_global_stats": False})
    want = jax.grad(
        lambda *a: jnp.sum(_plain_bn(*a) * gy.astype(jnp.float32)),
        argnums=(0, 1, 2))(x, scale, bias)

    assert got["IG$X"][0].dtype == dtype and got["IG$X"][0].shape == shape
    # read over three draws: float32 2e-7 / 2e-6 / 0; bf16 dX 8e-3, and
    # dScale, dBias 2.3e-2 to 6.2e-2 (each a sum of 196 or 6272 zero-mean
    # bf16 terms that cancel to noise, against the largest channel's)
    tols = (1e-4, 1e-4, 1e-4) if dtype == jnp.float32 else (2e-2, 0.1, 0.1)
    for slot, w, tol in zip(("IG$X", "IG$Scale", "IG$Bias"), want, tols):
        g = np.asarray(got[slot][0], np.float32)
        w = np.asarray(w, np.float32)
        err = np.max(np.abs(g - w)) / np.max(np.abs(w))
        assert err < tol, (slot, err)


# (f) the cost model prices the step by its conv2d ops
def test_cost_model_counts_the_toys_conv2d_flops():
    assert not [k for k in cost._CLASS_OF if "conv1x1" in k]
    _, main, loss = _build()
    plan = cost.plan_cost(main, (loss.name,), batch_size=BATCH)
    want = [2 * BATCH * s["cout"] * s["hout"] ** 2 * s["cin"] * s["k"] ** 2
            for s in _sites()]
    fwd = [r for r in plan.per_op if r[1] == "conv2d"]
    bwd = [r for r in plan.per_op if r[1] == "conv2d_grad"]
    assert sorted(r[3] for r in fwd) == sorted(want)
    assert sorted(r[3] for r in bwd) == sorted(2 * f for f in want)
    assert {r[2] for r in fwd + bwd} == {"conv"}
    assert plan.per_class["conv"] == 3 * sum(want)


# (g) the training-time pass is gone, the inference-time fold is not
def test_train_fuse_pass_is_unknown_and_the_inference_fold_stays():
    with pytest.raises(KeyError, match="no pass registered under "
                       "'conv_bn_train_fuse_pass'"):
        ir.get_pass("conv_bn_train_fuse_pass")
    assert "conv_bn_fuse_pass" in ir.registered_passes()
    scope = Scope()
    with scope_guard(scope), program_guard(Program(), Program()):
        img = layers.data("img", shape=[3, 8, 8], dtype="float32")
        out = layers.batch_norm(
            layers.conv2d(img, num_filters=4, filter_size=1, bias_attr=False),
            is_test=True)
        prog = pt.default_main_program().clone(for_test=True)
        exe = pt.Executor()
        exe.run(pt.default_startup_program(), scope=scope)
        xv = np.random.RandomState(3).rand(2, 3, 8, 8).astype(np.float32)
        want, = exe.run(prog, feed={"img": xv}, fetch_list=[out.name],
                        scope=scope)
        g = ir.get_pass("conv_bn_fuse_pass", scope=scope).apply(
            ir.Graph(prog))
        assert g.attrs["conv_bn_fuse_count"] == 1
        assert not g.ops_of_type("batch_norm")
        got, = exe.run(g.to_program(), feed={"img": xv},
                       fetch_list=[out.name], scope=scope)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
