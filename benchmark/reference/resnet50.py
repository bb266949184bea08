"""ResNet-50 (He et al. 2015) in the v1.5 layout (stride 2 on the 3x3 of a
down-sampling bottleneck, as torchvision builds it): the training-mode loss
of a batch — batch-statistics BatchNorm, float32, convolutions at precision
``highest``.

Parameters: ``{"convs": {site: w [Cout, Cin, k, k]}, "bn": {site: (scale,
offset)}, "fc_w" [2048, classes], "fc_b"}`` with the site names of
``benchmark/flops.py:resnet50_conv_sites``.
"""

import functools

import jax
import jax.numpy as jnp

from .. import flops


def _conv(x, w, stride):
    k = w.shape[-1]
    p = (k - 1) // 2
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), ((p, p), (p, p)),
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        precision=jax.lax.Precision.HIGHEST)


def _bn(x, scale, offset, eps):
    m = jnp.mean(x, axis=(0, 2, 3), keepdims=True)
    v = jnp.mean(jnp.square(x - m), axis=(0, 2, 3), keepdims=True)
    return (x - m) / jnp.sqrt(v + eps) * scale[None, :, None, None] \
        + offset[None, :, None, None]


@functools.partial(jax.jit, static_argnames=("eps", "blocks"))
def train_loss(params, image, label, eps=1e-5, blocks=(3, 4, 6, 3)):
    """image [B, 3, H, W] float32, label [B] int -> mean cross-entropy."""
    sites = {s["name"]: s for s in flops.resnet50_conv_sites(
        image.shape[-1], blocks=blocks)}

    def cbr(x, name, relu=True):
        y = _bn(_conv(x, params["convs"][name], sites[name]["stride"]),
                *params["bn"][name], eps)
        return jax.nn.relu(y) if relu else y

    x = cbr(image, "stem")
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 1, 3, 3),
                              (1, 1, 2, 2), ((0, 0), (0, 0), (1, 1), (1, 1)))
    for stage, n in enumerate(blocks):
        for b in range(n):
            p = f"res{stage}_{b}"
            y = cbr(cbr(cbr(x, p + ".b0"), p + ".b1"), p + ".b2", relu=False)
            sc = cbr(x, p + ".short", relu=False) \
                if p + ".short" in sites else x
            x = jax.nn.relu(sc + y)
    pooled = jnp.mean(x, axis=(2, 3))
    with jax.default_matmul_precision("highest"):
        lg = pooled @ params["fc_w"] + params["fc_b"]
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, label[:, None], axis=1))
