"""The layer DSL: Python functions appending ops to the default main program.

ref ``python/paddle/fluid/layers/nn.py`` (14.4k LoC, 187 exports — ``fc`` at
:231 is the canonical pattern: LayerHelper → create params → append ops →
bias → activation).  Signatures follow the reference so user code ports
unchanged; all compute lowers through the XLA block compiler.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Union

import numpy as np

from ..framework.core import Variable, convert_dtype
from ..layer_helper import LayerHelper
from ..initializer import ConstantInitializer
from .math_ops import _elementwise_binary, scale  # re-export


# ---------------------------------------------------------------------------
# dense / embedding
# ---------------------------------------------------------------------------

def fc(input, size, num_flatten_dims=1, param_attr=None, bias_attr=None,
       act=None, name=None):
    """ref layers/nn.py:231 — mul(+sum) + elementwise_add + act."""
    helper = LayerHelper("fc", input=input, param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    inputs = input if isinstance(input, (list, tuple)) else [input]
    dtype = inputs[0].dtype
    mul_results = []
    pattrs = param_attr if isinstance(param_attr, (list, tuple)) \
        else [param_attr] * len(inputs)
    for inp, pa in zip(inputs, pattrs):
        in_dim = int(np.prod(inp.shape[num_flatten_dims:]))
        w = helper.create_parameter(pa, shape=[in_dim, size], dtype=dtype)
        tmp = helper.create_variable_for_type_inference(dtype)
        helper.append_op("mul", inputs={"X": [inp], "Y": [w]},
                         outputs={"Out": [tmp]},
                         attrs={"x_num_col_dims": num_flatten_dims,
                                "y_num_col_dims": 1})
        mul_results.append(tmp)
    if len(mul_results) == 1:
        pre_bias = mul_results[0]
    else:
        pre_bias = helper.create_variable_for_type_inference(dtype)
        helper.append_op("sum", inputs={"X": mul_results},
                         outputs={"Out": [pre_bias]})
    pre_act = helper.append_bias_op(pre_bias, dim_start=num_flatten_dims)
    return helper.append_activation(pre_act)


def embedding(input, size, is_sparse=False, is_distributed=False,
              padding_idx=None, param_attr=None, dtype="float32"):
    """ref layers/nn.py embedding → lookup_table op."""
    helper = LayerHelper("embedding", param_attr=param_attr)
    w = helper.create_parameter(param_attr, shape=list(size), dtype=dtype)
    out = helper.create_variable_for_type_inference(dtype)
    pad = -1 if padding_idx is None else (
        padding_idx if padding_idx >= 0 else size[0] + padding_idx)
    helper.append_op("lookup_table", inputs={"W": [w], "Ids": [input]},
                     outputs={"Out": [out]},
                     attrs={"padding_idx": pad, "is_sparse": is_sparse,
                            "is_distributed": is_distributed})
    return out


def matmul(x, y, transpose_x=False, transpose_y=False, alpha=1.0, name=None):
    helper = LayerHelper("matmul", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("matmul", inputs={"X": [x], "Y": [y]},
                     outputs={"Out": [out]},
                     attrs={"transpose_X": transpose_x,
                            "transpose_Y": transpose_y, "alpha": float(alpha)})
    return out


def mul(x, y, x_num_col_dims=1, y_num_col_dims=1, name=None):
    helper = LayerHelper("mul", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("mul", inputs={"X": [x], "Y": [y]},
                     outputs={"Out": [out]},
                     attrs={"x_num_col_dims": x_num_col_dims,
                            "y_num_col_dims": y_num_col_dims})
    return out


# ---------------------------------------------------------------------------
# conv / pool
# ---------------------------------------------------------------------------

def _pair(v):
    return list(v) if isinstance(v, (list, tuple)) else [v, v]


def conv2d(input, num_filters, filter_size, stride=1, padding=0, dilation=1,
           groups=None, param_attr=None, bias_attr=None, use_cudnn=True,
           act=None, name=None, data_format="NCHW"):
    """ref layers/nn.py conv2d → conv2d op + bias + act."""
    helper = LayerHelper("conv2d", input=input, param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    dtype = input.dtype
    groups = groups or 1
    num_channels = input.shape[1] if data_format == "NCHW" else input.shape[-1]
    fs = _pair(filter_size)
    filter_shape = [num_filters, num_channels // groups] + fs
    import math
    std = (2.0 / (fs[0] * fs[1] * num_channels)) ** 0.5
    from ..initializer import NormalInitializer
    w = helper.create_parameter(param_attr, shape=filter_shape, dtype=dtype,
                                default_initializer=NormalInitializer(0.0, std))
    pre_bias = helper.create_variable_for_type_inference(dtype)
    helper.append_op("conv2d", inputs={"Input": [input], "Filter": [w]},
                     outputs={"Output": [pre_bias]},
                     attrs={"strides": _pair(stride), "paddings": _pair(padding),
                            "dilations": _pair(dilation), "groups": groups,
                            "data_format": data_format})
    if bias_attr is False:
        pre_act = pre_bias
    else:
        b = helper.create_parameter(bias_attr, shape=[num_filters],
                                    dtype=dtype, is_bias=True)
        pre_act = helper.create_variable_for_type_inference(dtype)
        helper.append_op("elementwise_add",
                         inputs={"X": [pre_bias], "Y": [b]},
                         outputs={"Out": [pre_act]}, attrs={"axis": 1})
    return helper.append_activation(pre_act)


def conv2d_transpose(input, num_filters, output_size=None, filter_size=None,
                     padding=0, stride=1, dilation=1, groups=None,
                     param_attr=None, bias_attr=None, use_cudnn=True,
                     act=None, name=None):
    helper = LayerHelper("conv2d_transpose", input=input, act=act,
                         bias_attr=bias_attr, name=name)
    dtype = input.dtype
    groups = groups or 1
    in_c = input.shape[1]
    if filter_size is None:
        # derive from output_size (ref conv2d_transpose filter inference)
        h = input.shape[2]
        osz = _pair(output_size)
        st, pd = _pair(stride), _pair(padding)
        filter_size = [osz[0] - (h - 1) * st[0] + 2 * pd[0],
                       osz[1] - (input.shape[3] - 1) * st[1] + 2 * pd[1]]
    fs = _pair(filter_size)
    w = helper.create_parameter(param_attr,
                                shape=[in_c, num_filters // groups] + fs,
                                dtype=dtype)
    pre_bias = helper.create_variable_for_type_inference(dtype)
    helper.append_op("conv2d_transpose",
                     inputs={"Input": [input], "Filter": [w]},
                     outputs={"Output": [pre_bias]},
                     attrs={"strides": _pair(stride), "paddings": _pair(padding),
                            "dilations": _pair(dilation), "groups": groups})
    if bias_attr is False:
        pre_act = pre_bias
    else:
        b = helper.create_parameter(bias_attr, shape=[num_filters],
                                    dtype=dtype, is_bias=True)
        pre_act = helper.create_variable_for_type_inference(dtype)
        helper.append_op("elementwise_add",
                         inputs={"X": [pre_bias], "Y": [b]},
                         outputs={"Out": [pre_act]}, attrs={"axis": 1})
    return helper.append_activation(pre_act)


def pool2d(input, pool_size=-1, pool_type="max", pool_stride=1, pool_padding=0,
           global_pooling=False, use_cudnn=True, ceil_mode=False, name=None,
           exclusive=True, data_format="NCHW"):
    helper = LayerHelper("pool2d", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("pool2d", inputs={"X": [input]}, outputs={"Out": [out]},
                     attrs={"pooling_type": pool_type,
                            "ksize": _pair(pool_size),
                            "strides": _pair(pool_stride),
                            "paddings": _pair(pool_padding),
                            "global_pooling": global_pooling,
                            "ceil_mode": ceil_mode, "exclusive": exclusive,
                            "data_format": data_format})
    return out


def adaptive_pool2d(input, pool_size, pool_type="max", require_index=False,
                    name=None):
    helper = LayerHelper("adaptive_pool2d", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("pool2d", inputs={"X": [input]}, outputs={"Out": [out]},
                     attrs={"pooling_type": pool_type,
                            "ksize": _pair(pool_size), "strides": [1, 1],
                            "paddings": [0, 0], "adaptive": True})
    return out


# ---------------------------------------------------------------------------
# normalization
# ---------------------------------------------------------------------------

def batch_norm(input, act=None, is_test=False, momentum=0.9, epsilon=1e-5,
               param_attr=None, bias_attr=None, data_layout="NCHW",
               in_place=False, name=None, moving_mean_name=None,
               moving_variance_name=None, do_model_average_for_mean_and_var=False,
               use_global_stats=False):
    """ref layers/nn.py batch_norm → batch_norm op with 4 params."""
    helper = LayerHelper("batch_norm", act=act, name=name)
    dtype = input.dtype
    c = input.shape[1] if data_layout == "NCHW" else input.shape[-1]
    scale = helper.create_parameter(
        param_attr, shape=[c], dtype=dtype,
        default_initializer=ConstantInitializer(1.0))
    bias = helper.create_parameter(bias_attr, shape=[c], dtype=dtype,
                                   is_bias=True)
    from ..param_attr import ParamAttr
    mean = helper.create_parameter(
        ParamAttr(name=moving_mean_name, trainable=False),
        shape=[c], dtype="float32",
        default_initializer=ConstantInitializer(0.0))
    variance = helper.create_parameter(
        ParamAttr(name=moving_variance_name, trainable=False),
        shape=[c], dtype="float32",
        default_initializer=ConstantInitializer(1.0))
    mean.stop_gradient = True
    variance.stop_gradient = True

    saved_mean = helper.create_variable_for_type_inference("float32", True)
    saved_var = helper.create_variable_for_type_inference("float32", True)
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        "batch_norm",
        inputs={"X": [input], "Scale": [scale], "Bias": [bias],
                "Mean": [mean], "Variance": [variance]},
        outputs={"Y": [out], "MeanOut": [mean], "VarianceOut": [variance],
                 "SavedMean": [saved_mean], "SavedVariance": [saved_var]},
        attrs={"momentum": momentum, "epsilon": epsilon,
               "is_test": is_test, "data_layout": data_layout,
               "use_global_stats": use_global_stats})
    return helper.append_activation(out)


def layer_norm(input, scale=True, shift=True, begin_norm_axis=1, epsilon=1e-5,
               param_attr=None, bias_attr=None, act=None, name=None):
    helper = LayerHelper("layer_norm", act=act, name=name)
    dtype = input.dtype
    norm_dim = int(np.prod(input.shape[begin_norm_axis:]))
    inputs = {"X": [input]}
    if scale:
        s = helper.create_parameter(param_attr, shape=[norm_dim], dtype=dtype,
                                    default_initializer=ConstantInitializer(1.0))
        inputs["Scale"] = [s]
    if shift:
        b = helper.create_parameter(bias_attr, shape=[norm_dim], dtype=dtype,
                                    is_bias=True)
        inputs["Bias"] = [b]
    mean = helper.create_variable_for_type_inference("float32", True)
    var = helper.create_variable_for_type_inference("float32", True)
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op("layer_norm", inputs=inputs,
                     outputs={"Y": [out], "Mean": [mean], "Variance": [var]},
                     attrs={"epsilon": epsilon,
                            "begin_norm_axis": begin_norm_axis})
    return helper.append_activation(out)


def rms_norm(input, begin_norm_axis=1, epsilon=1e-5, param_attr=None,
             name=None):
    """``w * x / sqrt(mean(x^2) + epsilon)`` over the axes from
    ``begin_norm_axis`` (no mean subtraction, no bias; float32 inside).
    ``param_attr=False`` leaves the scale out."""
    helper = LayerHelper("rms_norm", name=name)
    inputs = {"X": [input]}
    if param_attr is not False:
        norm_dim = int(np.prod(input.shape[begin_norm_axis:]))
        inputs["Scale"] = [helper.create_parameter(
            param_attr, shape=[norm_dim], dtype=input.dtype,
            default_initializer=ConstantInitializer(1.0))]
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("rms_norm", inputs=inputs, outputs={"Y": [out]},
                     attrs={"epsilon": epsilon,
                            "begin_norm_axis": begin_norm_axis})
    return out


def rope(x, head_dim, theta=10000.0, name=None, interleaved=False,
         rope_scaling=None):
    """Rotary position embedding over [batch, T, n * head_dim], before the
    head split: position = index along axis 1, rotate-half pairing
    (dimension ``i`` of a head with ``i + head_dim / 2``), pair ``i`` turning
    by ``pos * theta^(-2i / head_dim)`` or, with ``rope_scaling`` (a
    configuration's YaRN group, a dict), by ``pos *`` a per-pair frequency
    table worked out on the host from it (``pallas.rope.yarn_frequencies``;
    the softmax's length scaling is the attention's ``sm_scale``, not in
    here).  A 4-D input is
    [batch, heads, T, head_dim], after the split: position = index along
    axis 2.  ``interleaved=True`` pairs adjacent dimensions ``(2i, 2i + 1)``
    instead (``rope_interleave`` of the DeepSeek family); a model that
    rotates only a slice of the head splits it off first
    (``models.transformer.latent_attention``).  Neither form fuses with the
    projection or the norm round it: on a TPU the op is one pass of
    ``pallas/rope.py`` over the tensor where its shape allows (a head of 64
    lanes, 4-D, or of whole 128-lane tiles; a length that divides into
    blocks; one device) and XLA's four to nine passes elsewhere (the op's
    docstring, ``ops/attention_ops.py``); its gradient is ``rope_grad``, the
    same rotation turned back."""
    helper = LayerHelper("rope", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    attrs = {"head_dim": int(head_dim), "theta": float(theta)}
    if interleaved:
        attrs["interleaved"] = True
    if rope_scaling is not None:
        attrs["rope_scaling"] = {k: rope_scaling[k]
                                 for k in sorted(rope_scaling)}
    helper.append_op("rope", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs=attrs)
    return out


def group_norm(input, groups, epsilon=1e-5, param_attr=None, bias_attr=None,
               act=None, data_layout="NCHW", name=None):
    helper = LayerHelper("group_norm", act=act, name=name)
    dtype = input.dtype
    c = input.shape[1]
    inputs = {"X": [input]}
    if param_attr is not False:
        s = helper.create_parameter(param_attr, shape=[c], dtype=dtype,
                                    default_initializer=ConstantInitializer(1.0))
        inputs["Scale"] = [s]
    if bias_attr is not False:
        b = helper.create_parameter(bias_attr, shape=[c], dtype=dtype,
                                    is_bias=True)
        inputs["Bias"] = [b]
    mean = helper.create_variable_for_type_inference("float32", True)
    var = helper.create_variable_for_type_inference("float32", True)
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op("group_norm", inputs=inputs,
                     outputs={"Y": [out], "Mean": [mean], "Variance": [var]},
                     attrs={"epsilon": epsilon, "groups": groups})
    return helper.append_activation(out)


def l2_normalize(x, axis, epsilon=1e-12, name=None):
    helper = LayerHelper("l2_normalize", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    norm = helper.create_variable_for_type_inference(x.dtype, True)
    helper.append_op("l2_normalize", inputs={"X": [x]},
                     outputs={"Out": [out], "Norm": [norm]},
                     attrs={"axis": axis, "epsilon": epsilon})
    return out


def spectral_norm(weight, dim=0, power_iters=1, eps=1e-12, name=None):
    """ref layers/nn.py spectral_norm → spectral_norm op (weight / σ_max
    via power iteration over persistable u/v buffers)."""
    helper = LayerHelper("spectral_norm", name=name)
    h = weight.shape[dim]
    w = int(np.prod(weight.shape)) // h
    from ..param_attr import ParamAttr
    from ..initializer import NormalInitializer
    u = helper.create_parameter(
        ParamAttr(initializer=NormalInitializer(0.0, 1.0),
                  trainable=False),
        shape=[h], dtype=weight.dtype)
    v = helper.create_parameter(
        ParamAttr(initializer=NormalInitializer(0.0, 1.0),
                  trainable=False),
        shape=[w], dtype=weight.dtype)
    out = helper.create_variable_for_type_inference(weight.dtype)
    helper.append_op("spectral_norm",
                     inputs={"Weight": [weight], "U": [u], "V": [v]},
                     outputs={"Out": [out]},
                     attrs={"dim": dim, "power_iters": power_iters,
                            "eps": eps})
    return out


def data_norm(input, act=None, epsilon=1e-5, param_attr=None,
              data_layout="NCHW", in_place=False, name=None,
              moving_mean_name=None, moving_variance_name=None,
              do_model_average_for_mean_and_var=False):
    helper = LayerHelper("data_norm", act=act, name=name)
    dtype = input.dtype
    c = input.shape[1]
    bsize = helper.create_parameter(
        None, shape=[c], dtype=dtype,
        default_initializer=ConstantInitializer(1e4))
    bsum = helper.create_parameter(
        None, shape=[c], dtype=dtype,
        default_initializer=ConstantInitializer(0.0))
    bsqr = helper.create_parameter(
        None, shape=[c], dtype=dtype,
        default_initializer=ConstantInitializer(1e4))
    means = helper.create_variable_for_type_inference(dtype, True)
    scales = helper.create_variable_for_type_inference(dtype, True)
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op("data_norm",
                     inputs={"X": [input], "BatchSize": [bsize],
                             "BatchSum": [bsum], "BatchSquareSum": [bsqr]},
                     outputs={"Y": [out], "Means": [means], "Scales": [scales]},
                     attrs={"epsilon": epsilon})
    return helper.append_activation(out)


# ---------------------------------------------------------------------------
# softmax / losses
# ---------------------------------------------------------------------------

def softmax(input, use_cudnn=False, name=None, axis=-1):
    helper = LayerHelper("softmax", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("softmax", inputs={"X": [input]}, outputs={"Out": [out]},
                     attrs={"axis": axis})
    return out


def softmax_with_cross_entropy(logits, label, soft_label=False,
                               ignore_index=-100, numeric_stable_mode=True,
                               return_softmax=False, axis=-1):
    helper = LayerHelper("softmax_with_cross_entropy")
    sm = helper.create_variable_for_type_inference(logits.dtype)
    loss = helper.create_variable_for_type_inference(logits.dtype)
    helper.append_op("softmax_with_cross_entropy",
                     inputs={"Logits": [logits], "Label": [label]},
                     outputs={"Softmax": [sm], "Loss": [loss]},
                     attrs={"soft_label": soft_label,
                            "ignore_index": ignore_index, "axis": axis})
    if return_softmax:
        return loss, sm
    return loss


def cross_entropy(input, label, soft_label=False, ignore_index=-100):
    helper = LayerHelper("cross_entropy")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("cross_entropy", inputs={"X": [input], "Label": [label]},
                     outputs={"Y": [out]},
                     attrs={"soft_label": soft_label,
                            "ignore_index": ignore_index})
    return out


def square_error_cost(input, label):
    helper = LayerHelper("square_error_cost")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("square_error_cost",
                     inputs={"X": [input], "Y": [label]},
                     outputs={"Out": [out]})
    return out


def sigmoid_cross_entropy_with_logits(x, label, ignore_index=-100, name=None,
                                      normalize=False):
    helper = LayerHelper("sigmoid_cross_entropy_with_logits", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("sigmoid_cross_entropy_with_logits",
                     inputs={"X": [x], "Label": [label]},
                     outputs={"Out": [out]},
                     attrs={"ignore_index": ignore_index,
                            "normalize": normalize})
    return out


def smooth_l1(x, y, inside_weight=None, outside_weight=None, sigma=None):
    helper = LayerHelper("smooth_l1")
    loss = helper.create_variable_for_type_inference(x.dtype)
    diff = helper.create_variable_for_type_inference(x.dtype, True)
    inputs = {"X": [x], "Y": [y]}
    if inside_weight is not None:
        inputs["InsideWeight"] = [inside_weight]
    if outside_weight is not None:
        inputs["OutsideWeight"] = [outside_weight]
    helper.append_op("smooth_l1_loss", inputs=inputs,
                     outputs={"Out": [loss], "Diff": [diff]},
                     attrs={"sigma": sigma or 1.0})
    return loss


def huber_loss(input, label, delta):
    helper = LayerHelper("huber_loss")
    out = helper.create_variable_for_type_inference(input.dtype)
    resid = helper.create_variable_for_type_inference(input.dtype, True)
    helper.append_op("huber_loss", inputs={"X": [input], "Y": [label]},
                     outputs={"Out": [out], "Residual": [resid]},
                     attrs={"delta": delta})
    return out


def log_loss(input, label, epsilon=1e-4, name=None):
    helper = LayerHelper("log_loss", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("log_loss", inputs={"Predicted": [input], "Labels": [label]},
                     outputs={"Loss": [out]}, attrs={"epsilon": epsilon})
    return out


def kldiv_loss(x, target, reduction="mean", name=None):
    helper = LayerHelper("kldiv_loss", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("kldiv_loss", inputs={"X": [x], "Target": [target]},
                     outputs={"Loss": [out]}, attrs={"reduction": reduction})
    return out


def label_smooth(label, prior_dist=None, epsilon=0.1, dtype="float32",
                 name=None):
    helper = LayerHelper("label_smooth", name=name)
    out = helper.create_variable_for_type_inference(dtype)
    inputs = {"X": [label]}
    if prior_dist is not None:
        inputs["PriorDist"] = [prior_dist]
    helper.append_op("label_smooth", inputs=inputs, outputs={"Out": [out]},
                     attrs={"epsilon": float(epsilon)})
    return out


def rank_loss(label, left, right, name=None):
    helper = LayerHelper("rank_loss", name=name)
    out = helper.create_variable_for_type_inference(left.dtype)
    helper.append_op("rank_loss",
                     inputs={"Label": [label], "Left": [left], "Right": [right]},
                     outputs={"Out": [out]})
    return out


def margin_rank_loss(label, left, right, margin=0.1, name=None):
    helper = LayerHelper("margin_rank_loss", name=name)
    out = helper.create_variable_for_type_inference(left.dtype)
    act = helper.create_variable_for_type_inference(left.dtype, True)
    helper.append_op("margin_rank_loss",
                     inputs={"Label": [label], "X1": [left], "X2": [right]},
                     outputs={"Out": [out], "Activated": [act]},
                     attrs={"margin": margin})
    return out


def npair_loss(anchor, positive, labels, l2_reg=0.002):
    helper = LayerHelper("npair_loss")
    out = helper.create_variable_for_type_inference(anchor.dtype)
    helper.append_op("npair_loss",
                     inputs={"Anchor": [anchor], "Positive": [positive],
                             "Labels": [labels]},
                     outputs={"Out": [out]}, attrs={"l2_reg": l2_reg})
    return out


def dice_loss(input, label, epsilon=1e-5):
    from . import tensor as T
    label = T.cast(label, input.dtype)
    reduce_dims = list(range(1, len(input.shape)))
    inse = reduce_sum(input * label, dim=reduce_dims)
    dice_denominator = reduce_sum(input, dim=reduce_dims) + \
        reduce_sum(label, dim=reduce_dims)
    dice_score = 1 - inse * 2 / (dice_denominator + epsilon)
    return reduce_mean(dice_score)


# ---------------------------------------------------------------------------
# dropout / misc
# ---------------------------------------------------------------------------

def dropout(x, dropout_prob, is_test=False, seed=None, name=None,
            dropout_implementation="downgrade_in_infer"):
    """ref layers/nn.py dropout / operators/dropout_op.cc.

    TPU note: the keep mask is drawn as uint8 random bits (one byte per
    element — bit generation is the dominant dropout cost on TPU), so the
    effective drop probability is quantized to multiples of 1/256 (up to
    ~0.2% absolute bias vs the requested rate), and any tiny nonzero
    ``dropout_prob`` drops at least ~0.39% of elements rather than
    silently becoming a no-op.
    """
    import zlib
    helper = LayerHelper("dropout", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    mask = helper.create_variable_for_type_inference("uint8", True)
    # per-op RNG tag (derived from the unique out name when the user gives
    # no seed): forward and backward fold the same tag into the per-step
    # key and regenerate identical bits, so the mask is never stored.
    # An explicit seed IS the tag — as in the reference's fix_seed path
    # (dropout_op.cc), two ops given the same seed draw the same pattern.
    tag = seed if seed is not None else \
        (zlib.crc32(out.name.encode()) & 0x7FFFFFFF) or 1
    helper.append_op("dropout", inputs={"X": [x]},
                     outputs={"Out": [out], "Mask": [mask]},
                     attrs={"dropout_prob": dropout_prob, "is_test": is_test,
                            "seed": tag,
                            "dropout_implementation": dropout_implementation})
    return out


def one_hot(input, depth, allow_out_of_range=False):
    helper = LayerHelper("one_hot")
    out = helper.create_variable_for_type_inference("float32")
    helper.append_op("one_hot", inputs={"X": [input]}, outputs={"Out": [out]},
                     attrs={"depth": depth})
    return out


def autoincreased_step_counter(counter_name=None, begin=1, step=1):
    """ref layers/nn.py — persistable int64 step counter incremented per run."""
    helper = LayerHelper("global_step_counter")
    counter = helper.main_program.global_block().create_var(
        name=counter_name or "@STEP_COUNTER@", shape=(), dtype="int64",
        persistable=True, stop_gradient=True)
    from ..framework.core import default_startup_program
    sb = default_startup_program().global_block()
    if not sb.var_local(counter.name):
        sb.create_var(name=counter.name, shape=(), dtype="int64",
                      persistable=True)
        sb.append_op("fill_constant", outputs={"Out": [counter.name]},
                     attrs={"shape": [], "dtype": "int64",
                            "value": float(begin - step)})
    helper.append_op("increment", inputs={"X": [counter]},
                     outputs={"Out": [counter]}, attrs={"step": float(step)})
    return counter


def topk(input, k, name=None):
    helper = LayerHelper("top_k", name=name)
    values = helper.create_variable_for_type_inference(input.dtype)
    indices = helper.create_variable_for_type_inference("int64", True)
    inputs = {"X": [input]}
    attrs = {}
    if isinstance(input, Variable) and isinstance(k, Variable):
        inputs["K"] = [k]
    else:
        attrs["k"] = int(k)
    helper.append_op("top_k", inputs=inputs,
                     outputs={"Out": [values], "Indices": [indices]},
                     attrs=attrs)
    return values, indices


def transpose(x, perm, name=None):
    helper = LayerHelper("transpose", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    xshape = helper.create_variable_for_type_inference(x.dtype, True)
    helper.append_op("transpose2", inputs={"X": [x]},
                     outputs={"Out": [out], "XShape": [xshape]},
                     attrs={"axis": list(perm)})
    return out


def reshape(x, shape, actual_shape=None, act=None, inplace=False, name=None):
    helper = LayerHelper("reshape2", act=act, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    xshape = helper.create_variable_for_type_inference(x.dtype, True)
    helper.append_op("reshape2", inputs={"X": [x]},
                     outputs={"Out": [out], "XShape": [xshape]},
                     attrs={"shape": list(shape)})
    return helper.append_activation(out)


def squeeze(input, axes, name=None):
    helper = LayerHelper("squeeze", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    xshape = helper.create_variable_for_type_inference(input.dtype, True)
    helper.append_op("squeeze2", inputs={"X": [input]},
                     outputs={"Out": [out], "XShape": [xshape]},
                     attrs={"axes": list(axes)})
    return out


def unsqueeze(input, axes, name=None):
    helper = LayerHelper("unsqueeze", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    xshape = helper.create_variable_for_type_inference(input.dtype, True)
    helper.append_op("unsqueeze2", inputs={"X": [input]},
                     outputs={"Out": [out], "XShape": [xshape]},
                     attrs={"axes": list(axes)})
    return out


def flatten(x, axis=1, name=None):
    helper = LayerHelper("flatten", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    xshape = helper.create_variable_for_type_inference(x.dtype, True)
    helper.append_op("flatten2", inputs={"X": [x]},
                     outputs={"Out": [out], "XShape": [xshape]},
                     attrs={"axis": axis})
    return out


def stack(x, axis=0):
    helper = LayerHelper("stack")
    xs = x if isinstance(x, (list, tuple)) else [x]
    out = helper.create_variable_for_type_inference(xs[0].dtype)
    helper.append_op("stack", inputs={"X": xs}, outputs={"Y": [out]},
                     attrs={"axis": axis})
    return out


def unstack(x, axis=0, num=None):
    helper = LayerHelper("unstack")
    num = num or x.shape[axis]
    outs = [helper.create_variable_for_type_inference(x.dtype)
            for _ in range(num)]
    helper.append_op("unstack", inputs={"X": [x]}, outputs={"Y": outs},
                     attrs={"axis": axis, "num": num})
    return outs


def split(input, num_or_sections, dim=-1, name=None):
    helper = LayerHelper("split", name=name)
    dim = dim if dim >= 0 else dim + len(input.shape)
    if isinstance(num_or_sections, int):
        n = num_or_sections
        attrs = {"num": n, "axis": dim, "sections": []}
    else:
        n = len(num_or_sections)
        attrs = {"num": 0, "axis": dim, "sections": list(num_or_sections)}
    outs = [helper.create_variable_for_type_inference(input.dtype)
            for _ in range(n)]
    helper.append_op("split", inputs={"X": [input]}, outputs={"Out": outs},
                     attrs=attrs)
    return outs


def expand(x, expand_times, name=None):
    helper = LayerHelper("expand", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("expand", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"expand_times": list(expand_times)})
    return out


def slice(input, axes, starts, ends):
    helper = LayerHelper("slice")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("slice", inputs={"Input": [input]},
                     outputs={"Out": [out]},
                     attrs={"axes": list(axes), "starts": list(starts),
                            "ends": list(ends)})
    return out


def strided_slice(input, axes, starts, ends, strides):
    helper = LayerHelper("strided_slice")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("strided_slice", inputs={"Input": [input]},
                     outputs={"Out": [out]},
                     attrs={"axes": list(axes), "starts": list(starts),
                            "ends": list(ends), "strides": list(strides)})
    return out


def gather(input, index, overwrite=True):
    helper = LayerHelper("gather")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("gather", inputs={"X": [input], "Index": [index]},
                     outputs={"Out": [out]})
    return out


def gather_nd(input, index, name=None):
    helper = LayerHelper("gather_nd", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("gather_nd", inputs={"X": [input], "Index": [index]},
                     outputs={"Out": [out]})
    return out


def scatter(input, index, updates, name=None, overwrite=True):
    helper = LayerHelper("scatter", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("scatter",
                     inputs={"X": [input], "Ids": [index], "Updates": [updates]},
                     outputs={"Out": [out]}, attrs={"overwrite": overwrite})
    return out


def scatter_nd_add(ref, index, updates, name=None):
    helper = LayerHelper("scatter_nd_add", name=name)
    out = helper.create_variable_for_type_inference(ref.dtype)
    helper.append_op("scatter_nd_add",
                     inputs={"X": [ref], "Index": [index], "Updates": [updates]},
                     outputs={"Out": [out]})
    return out


def pad(x, paddings, pad_value=0.0, name=None):
    helper = LayerHelper("pad", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("pad", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"paddings": list(paddings),
                            "pad_value": float(pad_value)})
    return out


def pad2d(input, paddings=[0, 0, 0, 0], mode="constant", pad_value=0.0,
          data_format="NCHW", name=None):
    helper = LayerHelper("pad2d", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("pad2d", inputs={"X": [input]}, outputs={"Out": [out]},
                     attrs={"paddings": list(paddings), "mode": mode,
                            "pad_value": float(pad_value),
                            "data_format": data_format})
    return out


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------

def _reduce(op_type, input, dim=None, keep_dim=False, name=None):
    helper = LayerHelper(op_type, name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    if dim is not None and not isinstance(dim, (list, tuple)):
        dim = [dim]
    helper.append_op(op_type, inputs={"X": [input]}, outputs={"Out": [out]},
                     attrs={"dim": dim, "keep_dim": keep_dim,
                            "reduce_all": dim is None})
    return out


def reduce_sum(input, dim=None, keep_dim=False, name=None):
    return _reduce("reduce_sum", input, dim, keep_dim, name)


def reduce_mean(input, dim=None, keep_dim=False, name=None):
    return _reduce("reduce_mean", input, dim, keep_dim, name)


def reduce_max(input, dim=None, keep_dim=False, name=None):
    return _reduce("reduce_max", input, dim, keep_dim, name)


def reduce_min(input, dim=None, keep_dim=False, name=None):
    return _reduce("reduce_min", input, dim, keep_dim, name)


def reduce_prod(input, dim=None, keep_dim=False, name=None):
    return _reduce("reduce_prod", input, dim, keep_dim, name)


def reduce_all(input, dim=None, keep_dim=False, name=None):
    return _reduce("reduce_all", input, dim, keep_dim, name)


def reduce_any(input, dim=None, keep_dim=False, name=None):
    return _reduce("reduce_any", input, dim, keep_dim, name)


def mean(x, name=None):
    helper = LayerHelper("mean", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("mean", inputs={"X": [x]}, outputs={"Out": [out]})
    return out


# ---------------------------------------------------------------------------
# elementwise wrappers (ref layers/nn.py elementwise_* exports)
# ---------------------------------------------------------------------------

def elementwise_add(x, y, axis=-1, act=None, name=None):
    return _elementwise_binary(x, y, "elementwise_add", axis=axis, act=act)


def elementwise_sub(x, y, axis=-1, act=None, name=None):
    return _elementwise_binary(x, y, "elementwise_sub", axis=axis, act=act)


def elementwise_mul(x, y, axis=-1, act=None, name=None):
    return _elementwise_binary(x, y, "elementwise_mul", axis=axis, act=act)


def elementwise_div(x, y, axis=-1, act=None, name=None):
    return _elementwise_binary(x, y, "elementwise_div", axis=axis, act=act)


def elementwise_max(x, y, axis=-1, act=None, name=None):
    return _elementwise_binary(x, y, "elementwise_max", axis=axis, act=act)


def elementwise_min(x, y, axis=-1, act=None, name=None):
    return _elementwise_binary(x, y, "elementwise_min", axis=axis, act=act)


def elementwise_pow(x, y, axis=-1, act=None, name=None):
    return _elementwise_binary(x, y, "elementwise_pow", axis=axis, act=act)


def elementwise_mod(x, y, axis=-1, act=None, name=None):
    return _elementwise_binary(x, y, "elementwise_mod", axis=axis, act=act)


def elementwise_floordiv(x, y, axis=-1, act=None, name=None):
    return _elementwise_binary(x, y, "elementwise_floordiv", axis=axis, act=act)


# simple unary layer wrappers -------------------------------------------------

def _unary(op_type, x, name=None, **attrs):
    helper = LayerHelper(op_type, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(op_type, inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs=attrs)
    return out


def relu(x, name=None): return _unary("relu", x, name)
def sigmoid(x, name=None): return _unary("sigmoid", x, name)
def tanh(x, name=None): return _unary("tanh", x, name)
def exp(x, name=None): return _unary("exp", x, name)
def log(x, name=None): return _unary("log", x, name)
def sqrt(x, name=None): return _unary("sqrt", x, name)
def rsqrt(x, name=None): return _unary("rsqrt", x, name)
def square(x, name=None): return _unary("square", x, name)
def abs(x, name=None): return _unary("abs", x, name)
def ceil(x, name=None): return _unary("ceil", x, name)
def floor(x, name=None): return _unary("floor", x, name)
def cos(x, name=None): return _unary("cos", x, name)
def sin(x, name=None): return _unary("sin", x, name)
def round(x, name=None): return _unary("round", x, name)
def reciprocal(x, name=None): return _unary("reciprocal", x, name)
def softplus(x, name=None): return _unary("softplus", x, name)
def softsign(x, name=None): return _unary("softsign", x, name)
def logsigmoid(x, name=None): return _unary("logsigmoid", x, name)
def sign(x, name=None): return _unary("sign", x, name)
def erf(x, name=None): return _unary("erf", x, name)
def gelu(x, approximate=False, name=None):
    return _unary("gelu", x, name, approximate=approximate)
def leaky_relu(x, alpha=0.02, name=None):
    return _unary("leaky_relu", x, name, alpha=alpha)
def elu(x, alpha=1.0, name=None): return _unary("elu", x, name, alpha=alpha)
def relu6(x, threshold=6.0, name=None):
    return _unary("relu6", x, name, threshold=threshold)
def selu(x, scale=None, alpha=None, name=None):
    attrs = {}
    if scale is not None: attrs["scale"] = scale
    if alpha is not None: attrs["alpha"] = alpha
    return _unary("selu", x, name, **attrs)
def pow(x, factor=1.0, name=None): return _unary("pow", x, name, factor=factor)
def stanh(x, scale_a=0.67, scale_b=1.7159, name=None):
    return _unary("stanh", x, name, scale_a=scale_a, scale_b=scale_b)
def hard_sigmoid(x, slope=0.2, offset=0.5, name=None):
    return _unary("hard_sigmoid", x, name, slope=slope, offset=offset)
def hard_swish(x, threshold=6.0, scale=6.0, offset=3.0, name=None):
    return _unary("hard_swish", x, name, threshold=threshold, scale=scale,
                  offset=offset)
def swish(x, beta=1.0, name=None): return _unary("swish", x, name, beta=beta)
def soft_relu(x, threshold=40.0, name=None):
    return _unary("soft_relu", x, name, threshold=threshold)
def brelu(x, t_min=0.0, t_max=24.0, name=None):
    return _unary("brelu", x, name, t_min=t_min, t_max=t_max)
def thresholded_relu(x, threshold=1.0, name=None):
    return _unary("thresholded_relu", x, name, threshold=threshold)
def maxout(x, groups, name=None): return _unary("maxout", x, name, groups=groups)
def logical_not(x, out=None, name=None): return _unary("logical_not", x, name)


def prelu(x, mode, param_attr=None, name=None):
    helper = LayerHelper("prelu", name=name)
    if mode == "all":
        alpha_shape = [1]
    elif mode == "channel":
        alpha_shape = [x.shape[1]]
    else:
        alpha_shape = [int(np.prod(x.shape[1:]))]
    alpha = helper.create_parameter(
        param_attr, shape=alpha_shape, dtype=x.dtype,
        default_initializer=ConstantInitializer(0.25))
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("prelu", inputs={"X": [x], "Alpha": [alpha]},
                     outputs={"Out": [out]}, attrs={"mode": mode})
    return out


def clip(x, min, max, name=None):
    return _unary("clip", x, name, min=float(min), max=float(max))


def clip_by_norm(x, max_norm, name=None):
    return _unary("clip_by_norm", x, name, max_norm=float(max_norm))


def _binary_logical(op_type, x, y, name=None):
    helper = LayerHelper(op_type, name=name)
    out = helper.create_variable_for_type_inference("bool")
    helper.append_op(op_type, inputs={"X": [x], "Y": [y]},
                     outputs={"Out": [out]})
    return out


def logical_and(x, y, out=None, name=None):
    return _binary_logical("logical_and", x, y, name)


def logical_or(x, y, out=None, name=None):
    return _binary_logical("logical_or", x, y, name)


def logical_xor(x, y, out=None, name=None):
    return _binary_logical("logical_xor", x, y, name)


def shape(input):
    helper = LayerHelper("shape")
    out = helper.create_variable_for_type_inference("int32", True)
    helper.append_op("shape", inputs={"Input": [input]},
                     outputs={"Out": [out]})
    return out


def rank(input):
    return len(input.shape)


def size(input):
    helper = LayerHelper("size")
    out = helper.create_variable_for_type_inference("int64", True)
    helper.append_op("size", inputs={"Input": [input]}, outputs={"Out": [out]})
    return out


def uniform_random(shape, dtype="float32", min=-1.0, max=1.0, seed=0):
    helper = LayerHelper("uniform_random")
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op("uniform_random", outputs={"Out": [out]},
                     attrs={"shape": list(shape), "dtype": dtype, "min": min,
                            "max": max, "seed": seed})
    return out


def uniform_random_batch_size_like(input, shape, dtype="float32",
                                   input_dim_idx=0, output_dim_idx=0,
                                   min=-1.0, max=1.0, seed=0):
    helper = LayerHelper("uniform_random_batch_size_like")
    out = helper.create_variable_for_type_inference(dtype)
    shape = list(shape)
    shape[output_dim_idx] = input.shape[input_dim_idx]
    helper.append_op("uniform_random", outputs={"Out": [out]},
                     attrs={"shape": shape, "dtype": dtype, "min": min,
                            "max": max, "seed": seed})
    return out


def gaussian_random(shape, mean=0.0, std=1.0, seed=0, dtype="float32"):
    helper = LayerHelper("gaussian_random")
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op("gaussian_random", outputs={"Out": [out]},
                     attrs={"shape": list(shape), "mean": mean, "std": std,
                            "seed": seed, "dtype": dtype})
    return out


def gaussian_random_batch_size_like(input, shape, input_dim_idx=0,
                                    output_dim_idx=0, mean=0.0, std=1.0,
                                    seed=0, dtype="float32"):
    helper = LayerHelper("gaussian_random_batch_size_like")
    out = helper.create_variable_for_type_inference(dtype)
    shape = list(shape)
    shape[output_dim_idx] = input.shape[input_dim_idx]
    helper.append_op("gaussian_random", outputs={"Out": [out]},
                     attrs={"shape": shape, "mean": mean, "std": std,
                            "seed": seed, "dtype": dtype})
    return out


def sampling_id(x, min=0.0, max=1.0, seed=0, dtype="int64"):
    """Shadowed by layers.structured.sampling_id (the package export);
    kept for direct ``layers.nn`` imports."""
    from .structured import sampling_id as _impl
    return _impl(x, min=min, max=max, seed=seed, dtype=dtype)


def sums(input, out=None):
    helper = LayerHelper("sum")
    out = out or helper.create_variable_for_type_inference(input[0].dtype)
    helper.append_op("sum", inputs={"X": list(input)}, outputs={"Out": [out]})
    return out


def image_resize(input, out_shape=None, scale=None, name=None,
                 resample="BILINEAR", actual_shape=None, align_corners=True,
                 align_mode=1):
    helper = LayerHelper("image_resize", name=name)
    if out_shape is None:
        out_shape = [int(input.shape[2] * scale), int(input.shape[3] * scale)]
    op = {"BILINEAR": "bilinear_interp", "NEAREST": "nearest_interp",
          "TRILINEAR": "trilinear_interp"}[resample]
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(op, inputs={"X": [input]}, outputs={"Out": [out]},
                     attrs={"out_h": out_shape[0], "out_w": out_shape[1],
                            "align_corners": align_corners,
                            "align_mode": align_mode})
    return out


def resize_bilinear(input, out_shape=None, scale=None, name=None,
                    actual_shape=None, align_corners=True, align_mode=1):
    return image_resize(input, out_shape, scale, name, "BILINEAR",
                        actual_shape, align_corners, align_mode)


def resize_nearest(input, out_shape=None, scale=None, name=None,
                   actual_shape=None, align_corners=True):
    return image_resize(input, out_shape, scale, name, "NEAREST",
                        actual_shape, align_corners)


def resize_trilinear(input, out_shape=None, scale=None, name=None,
                     actual_shape=None, align_corners=True, align_mode=1):
    helper = LayerHelper("resize_trilinear", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("trilinear_interp", inputs={"X": [input]},
                     outputs={"Out": [out]},
                     attrs={"out_d": out_shape[0], "out_h": out_shape[1],
                            "out_w": out_shape[2],
                            "align_corners": align_corners})
    return out


def pixel_shuffle(x, upscale_factor):
    return _unary("pixel_shuffle", x, None, upscale_factor=upscale_factor)


def space_to_depth(x, blocksize, name=None):
    return _unary("space_to_depth", x, name, blocksize=blocksize)


def shuffle_channel(x, group, name=None):
    return _unary("shuffle_channel", x, name, group=group)


def temporal_shift(x, seg_num, shift_ratio=0.25, name=None):
    return _unary("temporal_shift", x, name, seg_num=seg_num,
                  shift_ratio=shift_ratio)


def grid_sampler(x, grid, name=None):
    helper = LayerHelper("grid_sampler", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("grid_sampler", inputs={"X": [x], "Grid": [grid]},
                     outputs={"Output": [out]})
    return out


def affine_channel(x, scale=None, bias=None, data_layout="NCHW", name=None,
                   act=None):
    helper = LayerHelper("affine_channel", name=name, act=act)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("affine_channel",
                     inputs={"X": [x], "Scale": [scale], "Bias": [bias]},
                     outputs={"Out": [out]},
                     attrs={"data_layout": data_layout})
    return helper.append_activation(out)


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1, name=None):
    helper = LayerHelper("unfold", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    k = kernel_sizes if isinstance(kernel_sizes, (list, tuple)) \
        else [kernel_sizes] * 2
    s = strides if isinstance(strides, (list, tuple)) else [strides] * 2
    p = paddings if isinstance(paddings, (list, tuple)) else [paddings] * 4
    d = dilations if isinstance(dilations, (list, tuple)) else [dilations] * 2
    helper.append_op("unfold", inputs={"X": [x]}, outputs={"Y": [out]},
                     attrs={"kernel_sizes": list(k), "strides": list(s),
                            "paddings": list(p), "dilations": list(d)})
    return out


def im2sequence(input, filter_size=1, stride=1, padding=0, input_image_size=None,
                out_stride=1, name=None):
    helper = LayerHelper("im2sequence", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    k = _pair(filter_size)
    s = _pair(stride)
    p = padding if isinstance(padding, (list, tuple)) else [padding] * 4
    if len(p) == 2:
        p = [p[0], p[1], p[0], p[1]]
    helper.append_op("im2sequence", inputs={"X": [input]},
                     outputs={"Out": [out]},
                     attrs={"kernels": k, "strides": s, "paddings": list(p)})
    return out


def bilinear_tensor_product(x, y, size, act=None, name=None, param_attr=None,
                            bias_attr=None):
    helper = LayerHelper("bilinear_tensor_product", act=act, name=name)
    dtype = x.dtype
    w = helper.create_parameter(param_attr,
                                shape=[size, x.shape[1], y.shape[1]],
                                dtype=dtype)
    inputs = {"X": [x], "Y": [y], "Weight": [w]}
    if bias_attr is not False:
        b = helper.create_parameter(bias_attr, shape=[1, size], dtype=dtype,
                                    is_bias=True)
        inputs["Bias"] = [b]
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op("bilinear_tensor_product", inputs=inputs,
                     outputs={"Out": [out]})
    return helper.append_activation(out)


def lrn(input, n=5, k=1.0, alpha=1e-4, beta=0.75, name=None):
    helper = LayerHelper("lrn", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    mid = helper.create_variable_for_type_inference(input.dtype, True)
    helper.append_op("lrn", inputs={"X": [input]},
                     outputs={"Out": [out], "MidOut": [mid]},
                     attrs={"n": n, "k": k, "alpha": alpha, "beta": beta})
    return out


def flash_attention(q, k, v, bias=None, causal=False, sm_scale=None,
                    block_q=None, block_k=None, name=None, window=None,
                    q_rope=None, k_rope=None, block_diffusion=None):
    """Fused online-softmax attention over [b, h, T, d] tensors.

    ``window`` (with ``causal``): key ``j`` is visible to query ``i`` iff
    ``0 <= i - j < window``; the kernels skip the blocks outside the band.
    ``block_diffusion=B`` (alone: no ``causal``, no ``window``): T is a
    noisy copy and a clean copy of one sequence, each in blocks of B, under
    block diffusion's three-part mask
    (``pallas.flash_attention.BlockDiffusion``); the kernels skip its dead
    blocks alike and no bias tensor exists.
    K and V may have fewer heads than Q ([b, h_kv, T, d], ``h % h_kv ==
    0``): query head ``i`` reads KV head ``i // (h // h_kv)`` in the kernel.
    TPU-native replacement for the matmul→softmax→matmul chain of the
    reference Transformer recipe (ref dist_transformer.py:1034
    scaled_dot_product_attention) — Pallas kernel on TPU, O(T) memory.
    block_q/block_k default to the kernel's tuned sizes.  Lse, the op's
    second output ([b, h, T] float32), is what its grad op reads beside Out.
    ``q_rope`` [b, h, Tq, d_r] and ``k_rope`` [b, h_r, Tk, d_r] (``h % h_r
    == 0``; both or neither): the score of a pair becomes ``(q·k + q_rope·
    k_rope) · sm_scale``, a second product inside the kernels, so that a
    rotary part kept apart (latent attention's one 64-wide rotary key for
    every head) is neither concatenated nor broadcast in HBM; ``sm_scale``
    then defaults to ``(d + d_r) ** -0.5``.
    """
    helper = LayerHelper("flash_attention", name=name)
    out = helper.create_variable_for_type_inference(q.dtype)
    lse = helper.create_variable_for_type_inference("float32", True)
    inputs = {"Q": [q], "K": [k], "V": [v]}
    if bias is not None:
        inputs["Bias"] = [bias]
    if (q_rope is None) != (k_rope is None):
        raise ValueError("q_rope and k_rope come together")
    if q_rope is not None:
        inputs["QRope"], inputs["KRope"] = [q_rope], [k_rope]
    attrs = {"causal": causal, "sm_scale": sm_scale or 0.0,
             "block_q": block_q or 0, "block_k": block_k or 0}
    if window:
        attrs["window"] = int(window)
    if block_diffusion:
        attrs["block_diffusion"] = int(block_diffusion)
    helper.append_op("flash_attention", inputs=inputs,
                     outputs={"Out": [out], "Lse": [lse]}, attrs=attrs)
    return out


def ring_attention(q, k, v, causal=False, sm_scale=None, axis_name="sp",
                   name=None):
    """Sequence-parallel attention: KV shards rotate over the mesh's
    ``sp`` axis (paddle_tpu.pallas.ring_attention); degrades to
    flash_attention when no sp axis is active.  The long-context
    capability the reference lacks (SURVEY §5.7)."""
    helper = LayerHelper("ring_attention", name=name)
    out = helper.create_variable_for_type_inference(q.dtype)
    helper.append_op("ring_attention", inputs={"Q": [q], "K": [k], "V": [v]},
                     outputs={"Out": [out]},
                     attrs={"causal": causal, "sm_scale": sm_scale or 0.0,
                            "axis_name": axis_name})
    return out


# ---------------------------------------------------------------------------
# similarity / losses / misc wrappers (ref layers/nn.py assorted exports)
# ---------------------------------------------------------------------------

def cos_sim(X, Y):
    """ref layers/nn.py cos_sim → cos_sim op."""
    helper = LayerHelper("cos_sim")
    out = helper.create_variable_for_type_inference(X.dtype)
    xnorm = helper.create_variable_for_type_inference(X.dtype)
    ynorm = helper.create_variable_for_type_inference(X.dtype)
    helper.append_op("cos_sim", inputs={"X": [X], "Y": [Y]},
                     outputs={"Out": [out], "XNorm": [xnorm],
                              "YNorm": [ynorm]})
    return out


def bpr_loss(input, label, name=None):
    """Bayesian personalized ranking loss (ref bpr_loss_op.cc)."""
    helper = LayerHelper("bpr_loss", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("bpr_loss", inputs={"X": [input], "Label": [label]},
                     outputs={"Y": [out]})
    return out


def center_loss(input, label, num_classes, alpha, param_attr=None,
                update_center=True):
    """ref layers/nn.py center_loss → center_loss op w/ centers parameter."""
    helper = LayerHelper("center_loss", param_attr=param_attr)
    dtype = input.dtype
    from ..param_attr import ParamAttr
    if param_attr is None:
        # centers are updated by the op itself, not by the optimizer
        param_attr = ParamAttr(trainable=False)
    centers = helper.create_parameter(param_attr,
                                      shape=[num_classes, input.shape[1]],
                                      dtype=dtype,
                                      default_initializer=ConstantInitializer(0.0))
    centers.stop_gradient = True
    from .tensor import fill_constant
    lr = fill_constant(shape=[1], dtype=dtype, value=float(alpha))
    loss = helper.create_variable_for_type_inference(dtype)
    sample_centers = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        "center_loss",
        inputs={"X": [input], "Label": [label], "Centers": [centers],
                "CenterUpdateRate": [lr]},
        outputs={"Loss": [loss], "SampleCenterDiff": [sample_centers],
                 "CentersOut": [centers]},
        attrs={"cluster_num": num_classes, "need_update": update_center})
    return loss


def multiplex(inputs, index):
    """Row-wise select across candidate tensors (ref multiplex_op.cc)."""
    helper = LayerHelper("multiplex")
    out = helper.create_variable_for_type_inference(inputs[0].dtype)
    helper.append_op("multiplex", inputs={"X": list(inputs), "Ids": [index]},
                     outputs={"Out": [out]})
    return out


def where(condition):
    """Indices of true elements, padded to static shape (ref where_op /
    where_index)."""
    helper = LayerHelper("where")
    out = helper.create_variable_for_type_inference("int64", True)
    helper.append_op("where", inputs={"Condition": [condition]},
                     outputs={"Out": [out]})
    return out


def crop(x, shape=None, offsets=None, name=None):
    """Static crop (ref crop_op.cc); shape/offsets are python lists."""
    helper = LayerHelper("crop", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    if shape is None:
        # the build-time batch dim is -1; "crop to own shape" = identity crop
        shape = [s for s in x.shape]
    shape = [x.shape[i] if s == -1 and i > 0 else s
             for i, s in enumerate(shape)]
    helper.append_op("crop", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"shape": list(shape),
                            "offsets": list(offsets or [0] * len(x.shape))})
    return out


def crop_tensor(x, shape=None, offsets=None, name=None):
    """ref crop_tensor_op.cc — static-shape variant under XLA."""
    helper = LayerHelper("crop_tensor", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("crop_tensor", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"shape": list(shape or []),
                            "offsets": list(offsets or [0] * len(x.shape))})
    return out


def random_crop(x, shape, seed=None):
    """ref random_crop_op.cc — crop trailing dims to `shape` at random."""
    helper = LayerHelper("random_crop")
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("random_crop", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"shape": list(shape)})
    return out


def mean_iou(input, label, num_classes):
    """ref mean_iou_op.cc: per-batch mean IoU + per-class wrong/correct."""
    helper = LayerHelper("mean_iou")
    miou = helper.create_variable_for_type_inference("float32", True)
    wrong = helper.create_variable_for_type_inference("int32", True)
    correct = helper.create_variable_for_type_inference("int32", True)
    helper.append_op("mean_iou",
                     inputs={"Predictions": [input], "Labels": [label]},
                     outputs={"OutMeanIou": [miou], "OutWrong": [wrong],
                              "OutCorrect": [correct]},
                     attrs={"num_classes": num_classes})
    return miou, wrong, correct


def unique(x, dtype="int32"):
    """ref unique_op.cc (padded to static size under XLA)."""
    helper = LayerHelper("unique")
    out = helper.create_variable_for_type_inference(x.dtype, True)
    index = helper.create_variable_for_type_inference(dtype, True)
    helper.append_op("unique", inputs={"X": [x]},
                     outputs={"Out": [out], "Index": [index]})
    return out, index


def unique_with_counts(x, dtype="int32"):
    helper = LayerHelper("unique_with_counts")
    out = helper.create_variable_for_type_inference(x.dtype, True)
    index = helper.create_variable_for_type_inference(dtype, True)
    count = helper.create_variable_for_type_inference(dtype, True)
    helper.append_op("unique_with_counts", inputs={"X": [x]},
                     outputs={"Out": [out], "Index": [index],
                              "Count": [count]})
    return out, index, count


def shard_index(input, index_num, nshards, shard_id, ignore_value=-1):
    """ref shard_index_op.cc — map global ids to shard-local ids."""
    helper = LayerHelper("shard_index")
    out = helper.create_variable_for_type_inference(input.dtype, True)
    helper.append_op("shard_index", inputs={"X": [input]},
                     outputs={"Out": [out]},
                     attrs={"index_num": index_num, "nshards": nshards,
                            "shard_id": shard_id,
                            "ignore_value": ignore_value})
    return out


def pad_constant_like(x, y, pad_value=0.0, name=None):
    helper = LayerHelper("pad_constant_like", name=name)
    out = helper.create_variable_for_type_inference(y.dtype)
    helper.append_op("pad_constant_like", inputs={"X": [x], "Y": [y]},
                     outputs={"Out": [out]},
                     attrs={"pad_value": float(pad_value)})
    return out


def scatter_nd(index, updates, shape, name=None):
    """ref layers/nn.py scatter_nd — scatter_nd_add onto zeros."""
    helper = LayerHelper("scatter_nd", name=name)
    out = helper.create_variable_for_type_inference(updates.dtype)
    helper.append_op("scatter_nd",
                     inputs={"Index": [index], "Updates": [updates]},
                     outputs={"Out": [out]}, attrs={"shape": list(shape)})
    return out


def hash(input, hash_size, num_hash=1, name=None):
    """ref hash_op.cc — num_hash hashed id columns mod hash_size."""
    helper = LayerHelper("hash", name=name)
    out = helper.create_variable_for_type_inference("int64", True)
    helper.append_op("hash", inputs={"X": [input]}, outputs={"Out": [out]},
                     attrs={"num_hash": num_hash, "mod_by": hash_size})
    return out


def similarity_focus(input, axis, indexes, name=None):
    helper = LayerHelper("similarity_focus", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("similarity_focus", inputs={"X": [input]},
                     outputs={"Out": [out]},
                     attrs={"axis": axis, "indexes": list(indexes)})
    return out


def add_position_encoding(input, alpha, beta, name=None):
    """ref add_position_encoding_op.cc — sinusoidal position encoding."""
    helper = LayerHelper("add_position_encoding", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("add_position_encoding", inputs={"X": [input]},
                     outputs={"Out": [out]},
                     attrs={"alpha": float(alpha), "beta": float(beta)})
    return out


def fsp_matrix(x, y):
    """Flow-of-solution-procedure matrix for distillation (ref fsp_op.cc)."""
    helper = LayerHelper("fsp")
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("fsp", inputs={"X": [x], "Y": [y]},
                     outputs={"Out": [out]})
    return out


def teacher_student_sigmoid_loss(input, label, soft_max_up_bound=15.0,
                                 soft_max_lower_bound=-15.0):
    helper = LayerHelper("teacher_student_sigmoid_loss")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("teacher_student_sigmoid_loss",
                     inputs={"X": [input], "Label": [label]},
                     outputs={"Y": [out]},
                     attrs={"soft_max_up_bound": float(soft_max_up_bound),
                            "soft_max_lower_bound": float(soft_max_lower_bound)})
    return out


def tree_conv(nodes_vector, edge_set, output_size, num_filters=1,
              max_depth=2, act="tanh", param_attr=None, bias_attr=None,
              name=None):
    """Tree-based convolution (ref tree_conv_op.cc)."""
    helper = LayerHelper("tree_conv", param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    dtype = nodes_vector.dtype
    feature_size = nodes_vector.shape[2]
    w = helper.create_parameter(param_attr,
                                shape=[feature_size, 3, output_size,
                                       num_filters],
                                dtype=dtype)
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op("tree_conv",
                     inputs={"NodesVector": [nodes_vector],
                             "EdgeSet": [edge_set], "Filter": [w]},
                     outputs={"Out": [out]},
                     attrs={"max_depth": max_depth})
    if bias_attr is not False:
        out = helper.append_bias_op(out, dim_start=2)
    return helper.append_activation(out)


def merge_selected_rows(x, name=None):
    helper = LayerHelper("merge_selected_rows", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("merge_selected_rows", inputs={"X": [x]},
                     outputs={"Out": [out]})
    return out


def get_tensor_from_selected_rows(x, name=None):
    helper = LayerHelper("get_tensor_from_selected_rows", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("get_tensor_from_selected_rows", inputs={"X": [x]},
                     outputs={"Out": [out]})
    return out


def row_conv(input, future_context_size, param_attr=None, act=None):
    """Lookahead row convolution (ref row_conv_op.cc)."""
    helper = LayerHelper("row_conv", param_attr=param_attr, act=act)
    dtype = input.dtype
    filter_shape = [future_context_size + 1, input.shape[-1]]
    w = helper.create_parameter(param_attr, shape=filter_shape, dtype=dtype)
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op("row_conv", inputs={"X": [input], "Filter": [w]},
                     outputs={"Out": [out]})
    return helper.append_activation(out)


def py_func(func, x, out, backward_func=None, skip_vars_in_backward_input=None):
    """Host-python op via jax.pure_callback (ref py_func_op.cc).

    ``out`` vars must be pre-created with concrete shapes
    (``create_variable`` style); a leading -1 is bound to the batch size at
    trace time.  ``backward_func(*x, *out, *out_grads) -> x_grads`` enables
    reverse-mode through the callback.
    """
    from ..ops.control_flow_ops import PY_FUNC_TABLE
    helper = LayerHelper("py_func")
    xs = x if isinstance(x, (list, tuple)) else [x]
    outs = out if isinstance(out, (list, tuple)) else [out]
    fid = len(PY_FUNC_TABLE)
    PY_FUNC_TABLE[fid] = {"forward": func, "backward": backward_func}
    helper.append_op("py_func", inputs={"X": list(xs)},
                     outputs={"Out": list(outs)},
                     attrs={"func_id": fid,
                            "out_shapes": [list(o.shape) for o in outs],
                            "out_dtypes": [o.dtype for o in outs]})
    return out


def fake_quantize_abs_max(x, bit_length=8):
    """ref operators/fake_quantize_op.cc (QAT building block)."""
    helper = LayerHelper("fake_quantize_abs_max")
    out = helper.create_variable_for_type_inference(x.dtype)
    scale = helper.create_variable_for_type_inference("float32")
    helper.append_op("fake_quantize_abs_max", inputs={"X": [x]},
                     outputs={"Out": [out], "OutScale": [scale]},
                     attrs={"bit_length": bit_length})
    return out


def fake_quantize_dequantize_abs_max(x, bit_length=8):
    """Fused quant-dequant with STE grad (QAT workhorse)."""
    helper = LayerHelper("fake_quantize_dequantize_abs_max")
    out = helper.create_variable_for_type_inference(x.dtype)
    scale = helper.create_variable_for_type_inference("float32")
    helper.append_op("fake_quantize_dequantize_abs_max",
                     inputs={"X": [x]},
                     outputs={"Out": [out], "OutScale": [scale]},
                     attrs={"bit_length": bit_length})
    return out


def fused_lm_head_ce(x, size, label, param_attr=None, bias_attr=None,
                     ignore_index=-100, chunk_size=1024, table=None):
    """Chunked LM-head + cross-entropy: O(chunk × vocab) memory instead of
    materializing [tokens, vocab] logits (TPU-native; no fluid analog).
    Two weight orientations.  Without ``table`` it owns its projection
    parameters like ``fc``: a [d_in, size] weight (and a bias unless
    ``bias_attr=False``).  ``table``: an embedding's [size, d_in] parameter
    (``layers.embedding``'s, tied input and output embeddings) read as the
    head's weight, ``logits = x table^T``; nothing is created, there is no
    bias, and the table's gradient is the sum of the lookup's and the
    head's (``backward.py`` adds up a parameter's readers).  The op then
    carries ``w_layout="vd"`` and ``table_reads``, the forward ops of the
    block that read the table, this one among them; without ``table`` the op
    and its lowering are what they were."""
    helper = LayerHelper("fused_lm_head_ce", param_attr=param_attr,
                         bias_attr=bias_attr)
    d_in = int(x.shape[-1])
    attrs = {"ignore_index": ignore_index, "chunk_size": chunk_size}
    if table is not None:
        if tuple(table.shape) != (size, d_in):
            raise ValueError(f"table {tuple(table.shape)} is not [size, "
                             f"d_in] = [{size}, {d_in}]")
        if bias_attr is not False:
            raise ValueError("a head that reads a table has no bias: pass "
                             "bias_attr=False")
        w = table
        block = helper.main_program.current_block()
        attrs.update(w_layout="vd", table_reads=1 + sum(
            table.name in op.input_arg_names() for op in block.ops))
    else:
        w = helper.create_parameter(param_attr, shape=[d_in, size],
                                    dtype=x.dtype)
    inputs = {"X": [x], "W": [w], "Label": [label]}
    if bias_attr is not False:
        b = helper.create_parameter(bias_attr, shape=[size], dtype=x.dtype,
                                    is_bias=True)
        inputs["Bias"] = [b]
    loss = helper.create_variable_for_type_inference("float32")
    helper.append_op(
        "fused_lm_head_ce", inputs=inputs, outputs={"Loss": [loss]},
        attrs=attrs)
    return loss


def short_conv(x, filter_size=3, param_attr=None, name=None, gated=True,
               bias_attr=None):
    """The core of a gated short-convolution operator over ``x`` [b, t, 3 d],
    an input projection split in three ``B | C | u``: ``C * conv(B * u)``
    with ``conv`` a causal depthwise convolution of ``filter_size`` taps over
    the ``d`` channels (``c[t] = sum_j w[:, j] * g[t - (filter_size - 1) +
    j]``, zeros before the sequence starts), no bias, no activation
    (``short_conv`` op; its filter ``w`` is [d, filter_size]).  Returns
    [b, t, d]; the projections before and after are the caller's ``fc``.

    ``gated=False``: ``silu(conv(x))`` over ``x`` [b, t, d] as it is (the
    convolution in front of a linear-attention layer's Q, K and V); the
    filter is [d, filter_size] again.  ``bias_attr`` (ungated only; None: no
    bias, the op as it was): a [d] bias, zero at first, added to the
    convolution before the SiLU, ``silu(conv(x) + b)`` (a state-space
    mixer's ``use_conv_bias``)."""
    helper = LayerHelper("short_conv", name=name)
    d = int(x.shape[-1])
    attrs = {}
    if gated:
        if d % 3:
            raise ValueError(f"the last axis ({d}) is not three equal parts")
        d //= 3
    else:
        attrs = {"gated": False}
    w = helper.create_parameter(param_attr, shape=[d, int(filter_size)],
                                dtype=x.dtype)
    inputs = {"X": [x], "Filter": [w]}
    if bias_attr is not None:
        if gated:
            raise ValueError("short_conv: a bias belongs to the ungated form")
        inputs["Bias"] = [helper.create_parameter(
            bias_attr, shape=[d], dtype=x.dtype,
            default_initializer=ConstantInitializer(0.0))]
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("short_conv", inputs=inputs, outputs={"Out": [out]},
                     attrs=attrs)
    return out


def kda_gate(x, beta_logits, n_head, param_prefix="kda", name=None,
             lower_bound=None, rank=None):
    """The two gates of a gated delta-rule layer with a per-channel decay
    (KDA, arXiv:2510.26692), float32 whatever AMP says: ``g = -exp(A_log_h)
    softplus(x + dt_bias)`` [b, t, n_head, d] from ``x`` [b, t, n_head * d]
    (the log of the decay, ``<= 0``), and ``beta = sigmoid(beta_logits)``
    [b, t, n_head].  With ``lower_bound`` (negative) the decay's gate is the
    bounded form, ``g = lower_bound * sigmoid(exp(A_log_h) (x + dt_bias))`` in
    ``(lower_bound, 0)``.  ``rank`` (an int or ``"full"``) says what made
    ``x`` and only labels ``paddle_tpu_kda_gate_lowerings_total``.
    Parameters, float32: ``<prefix>.A_log`` [n_head], ``log
    U(1, 16)``, and ``<prefix>.dt_bias`` [n_head * d], the inverse softplus
    of ``exp U(log 1e-3, log 1e-1)`` (the published layer's initial values);
    both are drawn here, from the parameter's name, and not by the startup
    program's seed.  Returns ``(g, beta)`` for :func:`kda_scan`."""
    import zlib
    from ..initializer import NumpyArrayInitializer
    from ..param_attr import ParamAttr
    helper = LayerHelper("kda_gate", name=name)
    width = int(x.shape[-1])
    rng = np.random.RandomState(zlib.crc32(param_prefix.encode()))
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), width))
    values = {"A_log": np.log(rng.uniform(1.0, 16.0, int(n_head))),
              "dt_bias": dt + np.log(-np.expm1(-dt))}
    a_log, dt_bias = (helper.create_parameter(
        ParamAttr(name=f"{param_prefix}.{what}",
                  initializer=NumpyArrayInitializer(
                      values[what].astype(np.float32))),
        shape=list(values[what].shape), dtype="float32")
        for what in ("A_log", "dt_bias"))
    g = helper.create_variable_for_type_inference("float32")
    beta = helper.create_variable_for_type_inference("float32")
    attrs = {}
    if lower_bound is not None:
        if not lower_bound < 0:
            raise ValueError(f"kda_gate lower_bound {lower_bound!r}: the "
                             "log of a decay is negative")
        attrs["lower_bound"] = float(lower_bound)
    if rank is not None:
        attrs["rank"] = str(rank)
    helper.append_op(
        "kda_gate", inputs={"X": [x], "B": [beta_logits], "ALog": [a_log],
                            "DtBias": [dt_bias]},
        outputs={"G": [g], "Beta": [beta]}, attrs=attrs)
    return g, beta


def kda_scan(q, k, v, g, beta, chunk=64, neg_eigval=False, name=None):
    """Gated delta-rule linear attention with a per-channel decay (``kda_scan``
    op; ``ops/kda_ops.py`` has the equations): per head a ``d_k x d_v`` state,
    ``S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t
    v_t^T``, ``o_t = S_t^T q_t``, from zero, run in chunks of ``chunk``
    positions (on a TPU, at heads of whole lane tiles, by the kernels of
    ``pallas/kda.py``; else one ``lax.scan`` over the chunk states; a ``t``
    that is no multiple is padded inside).  ``q``, ``k`` [b, t, h, d_k],
    ``v`` [b, t, h, d_v], ``g`` [b, t, h, d_k] the LOG of the decay, ``beta`` [b, t, h] in
    (0, 1); ``neg_eigval`` doubles beta inside; q and k are divided by their
    norms over d_k inside and q scaled by ``d_k^-0.5``.  Float32 inside;
    returns [b, t, h, d_v] in q's dtype.  States, the op's second output
    (the float32 state before every chunk), is what ``kda_scan_grad`` reads
    where the kernels run; it carries no gradient."""
    helper = LayerHelper("kda_scan", name=name)
    out = helper.create_variable_for_type_inference(q.dtype)
    states = helper.create_variable_for_type_inference("float32", True)
    helper.append_op(
        "kda_scan", inputs={"Q": [q], "K": [k], "V": [v], "G": [g],
                            "Beta": [beta]},
        outputs={"Out": [out], "States": [states]},
        attrs={"chunk": int(chunk), "neg_eigval": bool(neg_eigval)})
    return out


def ssd_scan(x, dt, a_log, b, c, d, dt_bias=None, chunk=128, name=None):
    """Mamba-2's state-space recurrence in its chunked (SSD) form
    (``ssd_scan`` op; ``ops/ssd_ops.py`` has the equations): per head a ``P
    x N`` state, ``S_t = exp(Delta_t A) S_{t-1} + Delta_t x_t B_t^T``, ``y_t
    = S_t C_t + D x_t``, from zero, ``A = -exp(a_log)`` a scalar a head.
    ``x`` [b, t, H, P]; ``dt`` [b, t, H]; ``a_log``, ``d`` and ``dt_bias``
    [H] (variables: the model's parameters); ``b``, ``c`` [b, t, G, N], head
    ``h`` reading group ``h // (H / G)``.  With ``dt_bias`` the step is
    ``Delta = softplus(dt + dt_bias)``, computed inside in float32; without
    it ``dt`` is the step itself.  Run in chunks of ``chunk`` positions (a
    ``t`` that is no multiple is padded inside), float32 inside whatever AMP
    says; returns [b, t, H, P] in x's dtype.  States, the op's second output
    (the float32 state before every chunk), is what ``ssd_scan_grad`` starts
    each chunk from; it carries no gradient."""
    helper = LayerHelper("ssd_scan", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    states = helper.create_variable_for_type_inference("float32", True)
    inputs = {"X": [x], "Dt": [dt], "ALog": [a_log], "B": [b], "C": [c],
              "D": [d]}
    if dt_bias is not None:
        inputs["DtBias"] = [dt_bias]
    helper.append_op("ssd_scan", inputs=inputs,
                     outputs={"Out": [out], "States": [states]},
                     attrs={"chunk": int(chunk)})
    return out


def gated_rms_norm(x, z, groups=1, epsilon=1e-5, param_attr=None, name=None):
    """``w * rms_g(x * silu(z))`` over [.., d]: the gate first, then the RMS
    over each of ``groups`` consecutive groups of ``d / groups`` channels,
    then one learned scale a channel (``w`` [d], ones at first).  Float32
    inside (``gated_rms_norm`` op, ``ops/ssd_ops.py``); returns x's dtype."""
    helper = LayerHelper("gated_rms_norm", name=name)
    d = int(x.shape[-1])
    if d % int(groups):
        raise ValueError(f"{d} channels in {groups} groups")
    w = helper.create_parameter(param_attr, shape=[d], dtype=x.dtype,
                                default_initializer=ConstantInitializer(1.0))
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("gated_rms_norm",
                     inputs={"X": [x], "Z": [z], "Scale": [w]},
                     outputs={"Y": [out]},
                     attrs={"groups": int(groups), "epsilon": float(epsilon)})
    return out


def hc_pre(xs, sinkhorn_iters=20, eps=1e-6, rms_eps=1e-6,
           res_clamp=(-30.0, 30.0), param_prefix="hc", init_std=0.02,
           name=None):
    """The read side of a manifold-constrained hyper-connection
    (arXiv:2512.24880 §4; ``ops/hc_ops.py`` has the equations) over a
    residual stream that is ``n = len(xs)`` streams wide, each a variable [b,
    t, C]: returns ``(u, h_post, h_res)``, ``u`` [b, t, C] the sublayer's
    input (before its norm), a token-dependent mix of the streams, and the
    two float32 maps :func:`hc_post` writes the sublayer's output back with:
    ``h_post`` [b, t, n] and ``h_res`` [b, t, n * n], doubly stochastic by
    ``sinkhorn_iters`` Sinkhorn-Knopp iterations (``eps`` in both
    denominators, the logits clamped to ``res_clamp`` before the
    exponential).  Parameters, float32: ``<prefix>.phi`` [n C, 2 n + n^2]
    (N(0, ``init_std``); rows ``j C .. (j + 1) C`` meet stream ``j``),
    ``<prefix>.alpha`` [3] (0.01) and ``<prefix>.bias`` [2 n + n^2], which
    starts the block as the plain residual it replaces: ``logit(1 / n)`` for
    the read (the streams' mean), 0 for the write (``h_post`` = 1) and 4 on
    the diagonal of the streams' own map (near the identity)."""
    from ..initializer import NormalInitializer, NumpyArrayInitializer
    from ..param_attr import ParamAttr
    helper = LayerHelper("hc_pre", name=name)
    xs = list(xs)
    n = len(xs)
    width, m = n * int(xs[0].shape[-1]), 2 * n + n * n

    def param(what, shape, init):
        return helper.create_parameter(
            ParamAttr(name=f"{param_prefix}.{what}", initializer=init),
            shape=shape, dtype="float32")

    bias = np.zeros(m, np.float32)
    bias[:n] = -np.log(n - 1.0) if n > 1 else 30.0
    bias[2 * n:] = 4.0 * np.eye(n, dtype=np.float32).ravel()
    phi = param("phi", [width, m], NormalInitializer(0.0, init_std))
    alpha = param("alpha", [3], ConstantInitializer(0.01))
    b = param("bias", [m], NumpyArrayInitializer(bias))
    u = helper.create_variable_for_type_inference(xs[0].dtype)
    h_post = helper.create_variable_for_type_inference("float32")
    h_res = helper.create_variable_for_type_inference("float32")
    helper.append_op(
        "hc_pre", inputs={"X": xs, "Phi": [phi], "Alpha": [alpha],
                          "Bias": [b]},
        outputs={"U": [u], "HPost": [h_post], "HRes": [h_res]},
        attrs={"n": n, "sinkhorn_iters": int(sinkhorn_iters),
               "eps": float(eps), "rms_eps": float(rms_eps),
               "res_clamp": [float(res_clamp[0]), float(res_clamp[1])]})
    return u, h_post, h_res


def hc_post(xs, y, h_post, h_res, sinkhorn_iters=20, name=None):
    """The write side of a hyper-connection: the ``n`` next streams (a list
    of variables [b, t, C]) from the streams ``xs``, the sublayer's output
    ``y`` [b, t, C] and :func:`hc_pre`'s two maps: stream ``i`` is ``sum_j
    h_res[i, j] xs[j] + h_post[i] y`` (``hc_post`` op, no parameters)."""
    helper = LayerHelper("hc_post", name=name)
    xs = list(xs)
    outs = [helper.create_variable_for_type_inference(x.dtype) for x in xs]
    helper.append_op(
        "hc_post", inputs={"X": xs, "Y": [y], "HPost": [h_post],
                           "HRes": [h_res]}, outputs={"Out": outs},
        attrs={"n": len(xs), "sinkhorn_iters": int(sinkhorn_iters)})
    return outs


def switch_moe_ffn(x, num_experts, d_inner, capacity_factor=1.25,
                   act="relu", param_prefix="moe", name=None):
    """Switch-Transformer mixture-of-experts FFN over [b, t, d] input.

    Returns (out, aux_loss).  Expert weights carry dist_spec ("ep", ...)
    so a mesh with an ``ep`` axis shards the experts (GSPMD inserts the
    dispatch/combine all-to-alls); on an ep-less mesh the annotations are
    inert and the layer runs dense.  No reference counterpart — TPU-native
    capability behind parallel/mesh.py's ``ep`` axis.
    """
    helper = LayerHelper("switch_ffn", name=name)
    d = int(x.shape[-1])
    E, F = int(num_experts), int(d_inner)

    def _p(suffix, shape, ep_spec, is_bias=False):
        from ..param_attr import ParamAttr
        v = helper.create_parameter(
            ParamAttr(name=f"{param_prefix}.{suffix}"), shape, x.dtype,
            is_bias=is_bias)
        v.dist_spec = ep_spec
        return v

    gate_w = _p("gate.w", [d, E], None)
    w1 = _p("w1", [E, d, F], ("ep", None, None))
    b1 = _p("b1", [E, F], ("ep", None), is_bias=True)
    w2 = _p("w2", [E, F, d], ("ep", None, None))
    b2 = _p("b2", [E, d], ("ep", None), is_bias=True)

    out = helper.create_variable_for_type_inference(x.dtype)
    aux = helper.create_variable_for_type_inference("float32")
    helper.append_op(
        "switch_ffn",
        inputs={"X": [x], "GateW": [gate_w], "W1": [w1], "B1": [b1],
                "W2": [w2], "B2": [b2]},
        outputs={"Out": [out], "AuxLoss": [aux]},
        attrs={"capacity_factor": float(capacity_factor), "act": act})
    return out, aux


def moe_ffn(x, num_experts, top_k, d_expert, norm_topk_prob=False,
            param_prefix="moe", initializer=None, name=None,
            score_func="softmax", select_bias=False, norm_eps=0.0,
            route_scale=1.0, num_held=None, expert_offset=0, act="silu",
            router_x=None, n_group=1, topk_group=1, gated=True):
    """Dropless top-k mixture of gated experts (SiLU on the gate branch, or
    ReLU with ``act="relu"``) over [b, t, d] input
    (``moe_ffn`` op: sorted rows + grouped matmuls, no capacity, no dropped
    token).  Returns ``(out, lb_loss, z_loss, expert_load)``: the
    load-balancing loss ``E * sum_e f_e P_e``, the router z-loss (mean
    squared logsumexp of the router logits) — add small multiples of both
    to the training loss — and the [E] int32 rows each expert received.
    The op's fifth output, ``TopExperts`` [b, t, k] (each token's experts),
    is a variable of the block for whoever wants to fetch it
    (``op.outputs["TopExperts"]``).  No bias anywhere.  Expert weights carry dist_spec ("ep", ...) like
    ``switch_moe_ffn``'s.

    Routing options (``ops/moe_ops.py``): ``score_func`` ``softmax`` |
    ``sigmoid``; ``select_bias=True`` creates ``<prefix>.select_bias``
    [num_experts], zero, not trainable, added to the scores for the choice
    of experts only; ``norm_eps`` joins the renormalising sum;
    ``route_scale`` multiplies the weights; ``n_group`` > 1: group-limited
    selection (DeepSeek-V3's, Ling 2.0's): the experts in ``n_group`` groups of
    consecutive experts, a group's score the sum of its two largest (biased)
    scores, the ``topk_group`` best groups kept and the ``top_k`` chosen among
    their experts alone.  ``num_held`` (default all):
    the expert weights are [num_held, ...] and hold experts
    ``expert_offset .. expert_offset + num_held - 1`` of the ``num_experts``
    the router scores; the output is their part of the layer's.
    ``router_x`` [b, t, d]: what the router reads where that is not ``x`` (a
    router placed before attention); the experts read ``x`` either way, and
    the router's gradient goes to ``router_x`` alone.  ``gated=False`` with
    ``act="relu2"``: un-gated experts, ``Wd relu(Wu x)^2``; the op then holds
    ``up.w`` and ``down.w`` and no third weight, and runs two grouped matmuls
    where a gated expert has three."""
    from ..param_attr import ParamAttr
    # at build, not at the first run
    if act not in (("silu", "relu") if gated else ("relu2",)):
        raise ValueError(f"moe_ffn act {act!r}"
                         + ("" if gated else " of un-gated experts"))
    helper = LayerHelper("moe_ffn", name=name)
    d = int(x.shape[-1])
    E, F = int(num_experts), int(d_expert)
    H = E if num_held is None else int(num_held)

    def _p(suffix, shape, ep_spec):
        v = helper.create_parameter(
            ParamAttr(name=f"{param_prefix}.{suffix}",
                      initializer=initializer), shape, x.dtype)
        v.dist_spec = ep_spec
        return v

    ep = ("ep", None, None)
    inputs = {"X": [x], "RouterW": [_p("router.w", [d, E], None)]}
    if gated:
        inputs["GateW"] = [_p("gate.w", [H, d, F], ep)]
    inputs.update(UpW=[_p("up.w", [H, d, F], ep)],
                  DownW=[_p("down.w", [H, F, d], ep)])
    if router_x is not None:
        inputs["RouterX"] = [router_x]
    if select_bias:
        inputs["SelectBias"] = [helper.create_parameter(
            ParamAttr(name=f"{param_prefix}.select_bias",
                      initializer=ConstantInitializer(0.0), trainable=False),
            [E], "float32")]
    out = helper.create_variable_for_type_inference(x.dtype)
    lb = helper.create_variable_for_type_inference("float32")
    z = helper.create_variable_for_type_inference("float32")
    load = helper.create_variable_for_type_inference("int32", True)
    top = helper.create_variable_for_type_inference("int32", True)
    # what moe_ffn_grad reuses: sort order, sorted rows, gate and up
    # projections, the experts' output; the router's logits, its choice (by
    # the column's slot and by index), its weights and its count of the rows
    # an expert, where top_k is a multiple of 8 (ops/moe_ops.py:_narrow)
    saved = [helper.create_variable_for_type_inference(t, True)
             for t in ("int32",) + (x.dtype,) * (4 if gated else 3)
             + ("float32", "int32", "int32", "float32", "int32")
             * (int(top_k) % 8 == 0)]
    attrs = {"top_k": int(top_k), "norm_topk_prob": bool(norm_topk_prob)}
    if score_func != "softmax":
        attrs["score_func"] = str(score_func)
    if norm_eps:
        attrs["norm_eps"] = float(norm_eps)
    if route_scale != 1.0:
        attrs["route_scale"] = float(route_scale)
    if expert_offset:
        attrs["expert_offset"] = int(expert_offset)
    if act != "silu":
        attrs["act"] = str(act)
    if int(n_group) > 1:
        if E % int(n_group) or not 1 <= int(topk_group) <= int(n_group) \
                or int(top_k) > int(topk_group) * (E // int(n_group)):
            raise ValueError(
                f"moe_ffn: {E} experts in {n_group} groups, {topk_group} "
                f"kept, {top_k} a token")
        attrs["n_group"], attrs["topk_group"] = int(n_group), int(topk_group)
    elif int(topk_group) != 1:
        raise ValueError(f"moe_ffn topk_group {topk_group} of one group")
    helper.append_op(
        "moe_ffn", inputs=inputs,
        outputs={"Out": [out], "LbLoss": [lb], "ZLoss": [z],
                 "ExpertLoad": [load], "TopExperts": [top], "Saved": saved},
        attrs=attrs)
    return out, lb, z, load
