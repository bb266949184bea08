"""Registry-wide gradient audit.

The reference sweeps every op's analytic gradient against central-difference
numeric gradients (``python/paddle/fluid/tests/unittests/op_test.py:767``
``check_grad`` / ``get_numeric_gradient`` ``:46``) — one OpTest subclass per
op, ~300 ops.  Here one parameterized harness walks every registered
differentiable op and drives it through the FULL gradient machinery: a tiny
Program containing just the op, ``append_backward`` (hand grad makers +
generic-vjp grad descs + grad dataflow resolution), and the executor.  The
fetched analytic input-gradients are compared against central differences of
the same compiled program.

Ops the sweep cannot meaningfully cover are listed in ``EXCLUDE`` with the
reason; ``test_audit_accounts_for_every_op`` locks the accounting so a newly
registered op must either pass the sweep or be excluded explicitly.

Tolerances: the default is ``rtol=1e-2`` (round-5; matches the reference's
typical per-op ``max_relative_error`` of 5e-3..1e-2).  Ops that genuinely
need more carry an explicit per-op rtol in ``_configs`` with a comment
giving the reason (kinked sampling, bf16 MXU kernels, routing flips) —
the analog of the reference's per-op ``max_relative_error`` overrides.
"""

import numpy as np
import pytest

import jax

import paddle_tpu as fluid  # noqa: F401  (registers core ops)
import paddle_tpu.distributed  # noqa: F401
import paddle_tpu.parallel  # noqa: F401
from paddle_tpu import layers
from paddle_tpu.framework import Executor, Program, program_guard
from paddle_tpu.framework import registry
from paddle_tpu.framework.backward import append_backward
from paddle_tpu.framework.core import grad_var_name
from paddle_tpu.framework.scope import Scope, scope_guard
from paddle_tpu.layer_helper import LayerHelper

SEED = 11          # executor seed: fixes stateful-rng ops across runs
EPS = 1e-2         # central-difference step (f32; ref OpTest uses 5e-3..1e-2)

_FLOAT = ("float32", "float64", "bfloat16", "float16")


def _rng(op_type):
    # stable per-op seed (str hash() is salted per process — it would make
    # the sweep's inputs, and any kink-boundary flakes, non-reproducible)
    import zlib
    return np.random.RandomState(zlib.crc32(op_type.encode()) % (2 ** 31))


class _Cfg:
    """Input recipe for one op: ins {slot: [np arrays]}, attrs, and knobs.

    ``nodiff``: float input slots NOT to differentiate (state/params whose
    grads the op contract doesn't define).  ``loss_outputs``: output slots
    the scalar loss reads (default: every float output) — restricted where
    a hand grad maker only propagates the primary output's gradient, which
    is the reference contract too (e.g. batch_norm propagates dY only).
    """

    def __init__(self, ins, attrs=None, nodiff=(), loss_outputs=None,
                 rtol=1e-2, atol=8e-3, max_elems=8, eps=EPS):
        self.ins = ins
        self.attrs = attrs or {}
        self.nodiff = set(nodiff)
        self.loss_outputs = loss_outputs
        self.rtol, self.atol = rtol, atol
        self.max_elems = max_elems
        self.eps = eps


def _f(rng, *shape, lo=0.5, hi=1.5):
    return rng.uniform(lo, hi, shape).astype(np.float32)


def _i(rng, *shape, n=2):
    return rng.randint(0, n, shape).astype(np.int64)


# ---------------------------------------------------------------------------
# explicit configs for ops the default recipes below can't feed
# ---------------------------------------------------------------------------

def _configs(op):
    r = _rng(op)
    f, i = (lambda *s, **k: _f(r, *s, **k)), (lambda *s, **k: _i(r, *s, **k))
    C = {
        "acos": lambda: _Cfg({"X": [f(2, 3, lo=-0.8, hi=0.8)]}),
        "asin": lambda: _Cfg({"X": [f(2, 3, lo=-0.8, hi=0.8)]}),
        "add_position_encoding": lambda: _Cfg({"X": [f(2, 3, 4)]},
                                      {"alpha": 1.0, "beta": 1.0}),
        "affine_channel": lambda: _Cfg({"X": [f(2, 3, 2, 2)], "Scale": [f(3)],
                                "Bias": [f(3)]}, {"data_layout": "NCHW"}),
        "affine_grid": lambda: _Cfg({"Theta": [f(2, 2, 3)]},
                            {"output_shape": [2, 1, 3, 3]}),
        "batch_norm": lambda: _Cfg(
            {"X": [f(2, 3, 2, 2)], "Scale": [f(3)], "Bias": [f(3)],
             "Mean": [f(3)], "Variance": [f(3)]},
            {"is_test": False, "momentum": 0.9, "epsilon": 1e-5},
            nodiff={"Mean", "Variance"}, loss_outputs=["Y"]),
        "sync_batch_norm": lambda: _Cfg(
            {"X": [f(2, 3, 2, 2)], "Scale": [f(3)], "Bias": [f(3)],
             "Mean": [f(3)], "Variance": [f(3)]},
            {"is_test": False, "momentum": 0.9, "epsilon": 1e-5},
            nodiff={"Mean", "Variance"}, loss_outputs=["Y"]),
        "bilinear_tensor_product": lambda: _Cfg(
            {"X": [f(2, 3)], "Y": [f(2, 4)], "Weight": [f(5, 3, 4)],
             "Bias": [f(1, 5)]}),
        "cast": lambda: _Cfg({"X": [f(2, 3)]},
                     {"in_dtype": "float32", "out_dtype": "float32"}),
        "center_loss": lambda: _Cfg(
            {"X": [f(4, 3)], "Label": [i(4, 1, n=5)], "Centers": [f(5, 3)],
             "CenterUpdateRate": [np.float32([0.1])]},
            {"need_update": False, "cluster_num": 5},
            nodiff={"Centers", "CenterUpdateRate"}, loss_outputs=["Loss"]),
        "clip": lambda: _Cfg({"X": [f(2, 3)]}, {"min": 0.0, "max": 2.0}),
        "clip_by_norm": lambda: _Cfg({"X": [f(2, 3)]}, {"max_norm": 0.8}),
        "conv2d": lambda: _Cfg({"Input": [f(1, 2, 4, 4)], "Filter": [f(3, 2, 3, 3)]},
                       {"strides": [1, 1], "paddings": [0, 0],
                        "dilations": [1, 1], "groups": 1}),
        "conv2d_transpose": lambda: _Cfg(
            {"Input": [f(1, 3, 3, 3)], "Filter": [f(3, 2, 2, 2)]},
            {"strides": [1, 1], "paddings": [0, 0], "dilations": [1, 1],
             "groups": 1}),
        "conv3d": lambda: _Cfg(
            {"Input": [f(1, 2, 3, 3, 3)], "Filter": [f(2, 2, 2, 2, 2)]},
            {"strides": [1, 1, 1], "paddings": [0, 0, 0],
             "dilations": [1, 1, 1], "groups": 1}),
        "conv3d_transpose": lambda: _Cfg(
            {"Input": [f(1, 2, 2, 2, 2)], "Filter": [f(2, 2, 2, 2, 2)]},
            {"strides": [1, 1, 1], "paddings": [0, 0, 0],
             "dilations": [1, 1, 1], "groups": 1}),
        "depthwise_conv2d": lambda: _Cfg(
            {"Input": [f(1, 2, 4, 4)], "Filter": [f(2, 1, 3, 3)]},
            {"strides": [1, 1], "paddings": [0, 0], "dilations": [1, 1],
             "groups": 2}),
        "depthwise_conv2d_transpose": lambda: _Cfg(
            {"Input": [f(1, 2, 3, 3)], "Filter": [f(2, 1, 2, 2)]},
            {"strides": [1, 1], "paddings": [0, 0], "dilations": [1, 1],
             "groups": 2}),
        "crop": lambda: _Cfg({"X": [f(3, 4)]}, {"shape": [2, 2], "offsets": [0, 1]}),
        "crop_tensor": lambda: _Cfg({"X": [f(3, 4)]},
                            {"shape": [2, 2], "offsets": [0, 1]}),
        "cudnn_lstm": lambda: _Cfg(
            {"Input": [f(3, 2, 3)], "W": [f(56)],
             "InitH": [f(1, 2, 2)], "InitC": [f(1, 2, 2)]},
            {"hidden_size": 2, "num_layers": 1, "is_bidirec": False},
            loss_outputs=["Out"]),
        "data_norm": lambda: _Cfg(
            {"X": [f(4, 3)], "BatchSize": [f(3, lo=5, hi=6)],
             "BatchSum": [f(3)], "BatchSquareSum": [f(3, lo=5, hi=6)]},
            nodiff={"BatchSize", "BatchSum", "BatchSquareSum"},
            loss_outputs=["Y"]),
        # deformable convs: bilinear sampling makes the loss kinked at
        # integer offset crossings — central differences straddle the
        # kink (ref OpTest sets max_relative_error=0.05 for these too)
        "deformable_conv": lambda: _Cfg(
            {"Input": [f(1, 2, 4, 4)], "Offset": [f(1, 36, 4, 4, lo=-.2,
                                                    hi=.2)],
             "Mask": [f(1, 18, 4, 4)], "Filter": [f(3, 2, 3, 3)]},
            {"strides": [1, 1], "paddings": [1, 1], "dilations": [1, 1],
             "groups": 1, "deformable_groups": 2, "im2col_step": 1},
            rtol=8e-2, atol=2e-2),
        "deformable_conv_v1": lambda: _Cfg(
            {"Input": [f(1, 2, 4, 4)], "Offset": [f(1, 36, 4, 4, lo=-.2,
                                                    hi=.2)],
             "Filter": [f(3, 2, 3, 3)]},
            {"strides": [1, 1], "paddings": [1, 1], "dilations": [1, 1],
             "groups": 1, "deformable_groups": 2, "im2col_step": 1},
            rtol=8e-2, atol=2e-2),
        "dropout": lambda: _Cfg({"X": [f(2, 6)]},
                        {"dropout_prob": 0.35, "is_test": False, "seed": 7,
                         "dropout_implementation": "upscale_in_train"},
                        loss_outputs=["Out"]),
        "elementwise_max": lambda: _Cfg({"X": [f(2, 3)], "Y": [f(2, 3, lo=2.5,
                                                         hi=3.5)]}),
        "elementwise_min": lambda: _Cfg({"X": [f(2, 3)], "Y": [f(2, 3, lo=2.5,
                                                         hi=3.5)]}),
        "elementwise_mod": lambda: _Cfg({"X": [f(2, 3)], "Y": [f(2, 3, lo=2.5,
                                                         hi=3.5)]}),
        "elementwise_floordiv": lambda: _Cfg({"X": [f(2, 3)],
                                      "Y": [f(2, 3, lo=2.5, hi=3.5)]}),
        "expand": lambda: _Cfg({"X": [f(2, 3)]}, {"expand_times": [2, 2]}),
        "expand_as": lambda: _Cfg({"X": [f(2, 3)], "target_tensor": [f(4, 6)]},
                          nodiff={"target_tensor"}),
        "fc": lambda: _Cfg({"Input": [f(2, 3)], "W": [f(3, 4)], "Bias": [f(4)]},
                   {"in_num_col_dims": 1}),
        # Pallas kernel matmuls run bf16 on the MXU: f32 central
        # differences sample bf16 quantization noise — widen.  The
        # hand-written flash_attention_grad (from the saved Out and Lse)
        # propagates Out's gradient; Lse is a residual, not a result
        "flash_attention": lambda: _Cfg(
            {"Q": [f(1, 2, 8, 4)], "K": [f(1, 2, 8, 4)],
             "V": [f(1, 2, 8, 4)]},
            {"sm_scale": 0.5, "causal": False}, loss_outputs=["Out"],
            rtol=8e-2, atol=2e-2),
        # the score as two products: the op's optional QRope and KRope
        # slots, one rotary key head for both query heads, so IG$KRope is
        # a sum over them
        "flash_attention+rope": lambda: _Cfg(
            {"Q": [f(1, 2, 8, 4)], "K": [f(1, 2, 8, 4)],
             "V": [f(1, 2, 8, 4)], "QRope": [f(1, 2, 8, 2)],
             "KRope": [f(1, 1, 8, 2)]},
            {"sm_scale": 0.5, "causal": True}, loss_outputs=["Out"],
            rtol=8e-2, atol=2e-2),
        # a mask form: block diffusion's three-part mask over a noisy and
        # a clean copy of 4 tokens in blocks of 2, both query heads on one
        # K/V head
        "flash_attention+block_diffusion": lambda: _Cfg(
            {"Q": [f(1, 2, 8, 4)], "K": [f(1, 1, 8, 4)],
             "V": [f(1, 1, 8, 4)]},
            {"sm_scale": 0.5, "causal": False, "block_diffusion": 2},
            loss_outputs=["Out"], rtol=8e-2, atol=2e-2),
        "fsp": lambda: _Cfg({"X": [f(1, 2, 3, 3)], "Y": [f(1, 4, 3, 3)]}),
        # analysis.fusion rewrite target: exact composition of
        # mul+bias+gelu+tagged dropout (mask is a pure function of the
        # fixed executor seed + tag, so central differences see a
        # constant mask)
        "fused_dense_act": lambda: _Cfg(
            {"X": [f(3, 4)], "W": [f(4, 5)], "Bias": [f(5)]},
            {"x_num_col_dims": 1, "bias_axis": 1, "act": "gelu",
             "approximate": False, "dropout_prob": 0.25, "seed": 7,
             "is_test": False,
             "dropout_implementation": "upscale_in_train"}),
        # analysis.fusion rewrite target: gather + add + layer_norm;
        # like layer_norm, only Y's gradient is the op contract
        "fused_embedding_layer_norm": lambda: _Cfg(
            {"Ids": [i(3, 1, n=8)], "W": [f(8, 6)],
             "Addends": [f(3, 6)], "Scale": [f(6)], "Bias": [f(6)]},
            {"padding_idx": -1, "epsilon": 1e-5, "begin_norm_axis": 1},
            loss_outputs=["Out"]),
        "fused_elemwise_activation": lambda: _Cfg(
            {"X": [f(2, 3)], "Y": [f(2, 3)]},
            {"functor_list": ["elementwise_add", "relu"], "axis": -1}),
        "fused_embedding_seq_pool": lambda: _Cfg(
            {"W": [f(10, 4)], "Ids": [i(2, 3, 1, n=10)]},
            {"combiner": "sum", "is_sparse": False}),
        # the chunk body matmuls in bf16 (MXU native): central differences
        # at f32 eps measure bf16 quantization, so widen eps/tol (ref
        # OpTest uses max_relative_error≈0.15 for fp16 kernels likewise)
        "fused_lm_head_ce": lambda: _Cfg(
            {"X": [f(4, 3)], "W": [f(3, 7)], "Bias": [f(7)],
             "Label": [i(4, n=7)]},
            {"chunk_size": 2, "ignore_index": -1}, loss_outputs=["Loss"],
            eps=5e-2, rtol=1.5e-1, atol=5e-2),
        "gather": lambda: _Cfg({"X": [f(5, 3)], "Index": [i(4, n=5)]}, {"axis": 0}),
        "gather_nd": lambda: _Cfg({"X": [f(3, 4)], "Index": [i(2, 2, n=3)]}),
        # bilinear grid sampling is kinked at cell crossings (same class
        # as deformable_conv; ref OpTest max_relative_error=0.61 (!))
        "grid_sampler": lambda: _Cfg({"X": [f(1, 2, 4, 4)],
                              "Grid": [f(1, 3, 3, 2, lo=-.7, hi=.7)]},
                             rtol=8e-2, atol=2e-2),
        "group_norm": lambda: _Cfg({"X": [f(2, 4, 3, 3)], "Scale": [f(4)],
                            "Bias": [f(4)]},
                           {"groups": 2, "epsilon": 1e-5},
                           loss_outputs=["Y"]),
        "gru": lambda: _Cfg({"Input": [f(2, 3, 9)], "Weight": [f(3, 9)],
                     "Bias": [f(1, 9)]},
                    {"gate_activation": "sigmoid", "activation": "tanh"},
                    loss_outputs=["Hidden"]),
        "gru_unit": lambda: _Cfg({"Input": [f(2, 9)], "HiddenPrev": [f(2, 3)],
                          "Weight": [f(3, 9)], "Bias": [f(1, 9)]},
                         loss_outputs=["Hidden"]),
        "hard_shrink": lambda: _Cfg({"X": [f(2, 3, lo=0.8, hi=1.5)]},
                            {"threshold": 0.5}),
        "softshrink": lambda: _Cfg({"X": [f(2, 3, lo=0.8, hi=1.5)]},
                           {"lambda": 0.5}),
        "thresholded_relu": lambda: _Cfg({"X": [f(2, 3, lo=1.2, hi=1.8)]},
                                 {"threshold": 1.0}),
        "hierarchical_sigmoid": lambda: _Cfg(
            {"X": [f(3, 4)], "W": [f(3, 4)], "Label": [i(3, 1, n=4)],
             "Bias": [f(3, 1)]},
            {"num_classes": 4}, loss_outputs=["Out"]),
        "hinge_loss": lambda: _Cfg({"Logits": [f(3, 1, lo=0.2, hi=0.6)],
                            "Labels": [np.float32([[0], [1], [1]])]},
                           nodiff={"Labels"}),
        "im2sequence": lambda: _Cfg({"X": [f(1, 2, 4, 4)]},
                            {"kernels": [2, 2], "strides": [2, 2],
                             "paddings": [0, 0, 0, 0]}),
        "kldiv_loss": lambda: _Cfg({"X": [f(3, 4, lo=-2, hi=-0.5)],
                            "Target": [f(3, 4, lo=0.2, hi=0.8)]},
                           {"reduction": "mean"}, nodiff={"Target"}),
        "linear_chain_crf": lambda: _Cfg(
            {"Emission": [f(2, 3, 4)], "Transition": [f(6, 4)],
             "Label": [i(2, 3, 1, n=4)],
             "Length": [np.int64([3, 2])]},
            loss_outputs=["LogLikelihood"]),
        "log_loss": lambda: _Cfg({"Predicted": [f(3, 1, lo=0.2, hi=0.8)],
                          "Labels": [np.float32([[0], [1], [1]])]},
                         {"epsilon": 1e-4}, nodiff={"Labels"}),
        "lookup_table": lambda: _Cfg({"W": [f(10, 4)], "Ids": [i(3, 1, n=10)]},
                             {"padding_idx": -1}),
        "lookup_table_v2": lambda: _Cfg({"W": [f(10, 4)], "Ids": [i(3, n=10)]},
                                {"padding_idx": -1}),
        "lstm": lambda: _Cfg({"Input": [f(2, 3, 8)], "Weight": [f(2, 8)],
                      "Bias": [f(1, 8)]},
                     {"use_peepholes": False}, loss_outputs=["Hidden"]),
        "lstm_unit": lambda: _Cfg({"X": [f(2, 8)], "C_prev": [f(2, 2)]},
                          {"forget_bias": 0.0}),
        "lstmp": lambda: _Cfg({"Input": [f(2, 3, 8)], "Weight": [f(3, 8)],
                       "ProjWeight": [f(2, 3)], "Bias": [f(1, 8)]},
                      {"use_peepholes": False},
                      loss_outputs=["Projection"]),
        "margin_rank_loss": lambda: _Cfg(
            {"X1": [f(3, 1)], "X2": [f(3, 1, lo=1.8, hi=2.5)],
             "Label": [np.ones((3, 1), np.float32)]},
            {"margin": 0.1}, nodiff={"Label"}),
        "match_matrix_tensor": lambda: _Cfg(
            {"X": [f(1, 3, 4)], "Y": [f(1, 2, 4)], "W": [f(4, 2, 4)]},
            {"dim_t": 2}),
        "matmul": lambda: _Cfg({"X": [f(2, 3)], "Y": [f(3, 4)]},
                       {"transpose_X": False, "transpose_Y": False,
                        "alpha": 1.0}),
        "matmul_v2": lambda: _Cfg({"X": [f(2, 3)], "Y": [f(3, 4)]},
                          {"trans_x": False, "trans_y": False}),
        # max pools: permutation data guarantees every within-window gap
        # >= 0.1 > 2*eps, so central differences can't flip an argmax
        "max_pool2d_with_index": lambda: _Cfg(
            {"X": [(r.permutation(32).astype(np.float32) * 0.1 + 0.05
                    ).reshape(1, 2, 4, 4)]},
            {"ksize": [2, 2], "strides": [2, 2], "paddings": [0, 0]},
            loss_outputs=["Out"]),
        "max_pool3d_with_index": lambda: _Cfg(
            {"X": [(r.permutation(64).astype(np.float32) * 0.1 + 0.05
                    ).reshape(1, 1, 4, 4, 4)]},
            {"ksize": [2, 2, 2], "strides": [2, 2, 2],
             "paddings": [0, 0, 0]}, loss_outputs=["Out"]),
        "spp": lambda: _Cfg(
            {"X": [(r.permutation(32).astype(np.float32) * 0.1 + 0.05
                    ).reshape(1, 2, 4, 4)]}),
        "pool2d": lambda: _Cfg(
            {"X": [(r.permutation(32).astype(np.float32) * 0.1 + 0.05
                    ).reshape(1, 2, 4, 4)]},
            {"pooling_type": "max", "ksize": [2, 2], "strides": [2, 2],
             "paddings": [0, 0]}),
        "reduce_max": lambda: _Cfg(
            {"X": [(r.permutation(6).astype(np.float32) * 0.1 + 0.05
                    ).reshape(2, 3)]}),
        "reduce_min": lambda: _Cfg(
            {"X": [(r.permutation(6).astype(np.float32) * 0.1 + 0.05
                    ).reshape(2, 3)]}),
        "max": lambda: _Cfg(
            {"X": [(r.permutation(6).astype(np.float32) * 0.1 + 0.05
                    ).reshape(2, 3)]}),
        # distinct well-separated values so no cross-group max tie sits
        # within ±eps of another candidate
        "maxout": lambda: _Cfg(
            {"X": [(r.permutation(36).astype(np.float32) * 0.1 + 0.05
                    ).reshape(1, 4, 3, 3)]}, {"groups": 2}),
        "mul": lambda: _Cfg({"X": [f(2, 3)], "Y": [f(3, 4)]},
                    {"x_num_col_dims": 1, "y_num_col_dims": 1}),
        "multiplex": lambda: _Cfg({"Ids": [i(3, 1, n=2)],
                           "X": [f(3, 4), f(3, 4)]}),
        "nce": lambda: _Cfg({"Input": [f(3, 4)], "Weight": [f(6, 4)],
                     "Bias": [f(6)], "Label": [i(3, 1, n=6)]},
                    {"num_total_classes": 6, "num_neg_samples": 2,
                     "sampler": 0, "seed": 3}, loss_outputs=["Cost"]),
        "npair_loss": lambda: _Cfg({"Anchor": [f(3, 4)], "Positive": [f(3, 4)],
                            "Labels": [i(3, n=3).astype(np.float32)]},
                           {"l2_reg": 0.01}, nodiff={"Labels"}),
        "pad": lambda: _Cfg({"X": [f(2, 3)]},
                    {"paddings": [1, 1, 0, 2], "pad_value": 0.3}),
        "pad2d": lambda: _Cfg({"X": [f(1, 2, 3, 3)]},
                      {"paddings": [1, 0, 1, 0], "mode": "constant",
                       "pad_value": 0.0, "data_format": "NCHW"}),
        "pad_constant_like": lambda: _Cfg({"X": [f(4, 5)], "Y": [f(2, 3)]},
                                  {"pad_value": 0.1}, nodiff={"X"}),
        "pool3d": lambda: _Cfg({"X": [f(1, 1, 4, 4, 4)]},
                       {"pooling_type": "avg", "ksize": [2, 2, 2],
                        "strides": [2, 2, 2], "paddings": [0, 0, 0],
                        "global_pooling": False}),
        "prelu": lambda: _Cfg({"X": [np.float32([[-1.2, 0.8, -0.5],
                                         [1.1, -0.9, 0.7]])],
                       "Alpha": [f(1)]}, {"mode": "all"}),
        "prroi_pool": lambda: _Cfg(
            {"X": [f(1, 2, 5, 5)],
             "ROIs": [np.float32([[0.4, 0.4, 3.6, 3.6],
                                  [1.2, 0.7, 4.2, 3.3]])]},
            {"pooled_height": 2, "pooled_width": 2, "spatial_scale": 1.0},
            nodiff={"ROIs"}),
        "psroi_pool": lambda: _Cfg(
            {"X": [f(1, 8, 4, 4)],
             "ROIs": [np.float32([[0.4, 0.4, 3.6, 3.6]])]},
            {"output_channels": 2, "pooled_height": 2, "pooled_width": 2,
             "spatial_scale": 1.0}, nodiff={"ROIs"}),
        "roi_align": lambda: _Cfg(
            {"X": [f(1, 2, 5, 5)],
             "ROIs": [np.float32([[0.4, 0.4, 3.6, 3.6],
                                  [1.2, 0.7, 4.2, 3.3]])]},
            {"pooled_height": 2, "pooled_width": 2, "spatial_scale": 1.0,
             "sampling_ratio": 2}, nodiff={"ROIs"}),
        "roi_pool": lambda: _Cfg(
            {"X": [f(1, 2, 5, 5)],
             "ROIs": [np.float32([[0.4, 0.4, 3.6, 3.6]])]},
            {"pooled_height": 2, "pooled_width": 2, "spatial_scale": 1.0},
            nodiff={"ROIs"}, loss_outputs=["Out"]),
        "rank_loss": lambda: _Cfg({"Label": [np.float32([[1], [0], [1]])],
                           "Left": [f(3, 1)], "Right": [f(3, 1)]},
                          nodiff={"Label"}),
        # piecewise-constant ops: keep inputs clear of the jump points so
        # ±eps stays on one step (analytic 0 == numeric 0)
        "round": lambda: _Cfg({"X": [f(2, 3, lo=0.55, hi=0.95)]}),
        "floor": lambda: _Cfg({"X": [f(2, 3, lo=0.1, hi=0.9)]}),
        "ceil": lambda: _Cfg({"X": [f(2, 3, lo=0.1, hi=0.9)]}),
        "reshape": lambda: _Cfg({"X": [f(2, 3)]}, {"shape": [3, 2]}),
        "reshape2": lambda: _Cfg({"X": [f(2, 3)]}, {"shape": [3, 2]}),
        "reverse": lambda: _Cfg({"X": [f(2, 3)]}, {"axis": [0]}),
        "row_conv": lambda: _Cfg({"X": [f(2, 4, 3)], "Filter": [f(2, 3)]}),
        "short_conv": lambda: _Cfg({"X": [f(2, 5, 9)], "Filter": [f(3, 3)]}),
        # two streams of width 4: Phi [2 * 4, 2 * 2 + 4], three iterations
        "hc_pre": lambda: _Cfg(
            {"X": [f(2, 3, 4), f(2, 3, 4)], "Phi": [f(8, 8, lo=-0.3, hi=0.3)],
             "Alpha": [f(3, lo=0.3, hi=0.9)], "Bias": [f(8, lo=-0.5, hi=0.5)]},
            {"n": 2, "sinkhorn_iters": 3, "eps": 1e-6, "rms_eps": 1e-6,
             "res_clamp": [-30.0, 30.0]}),
        "hc_post": lambda: _Cfg(
            {"X": [f(2, 3, 4), f(2, 3, 4)], "Y": [f(2, 3, 4)],
             "HPost": [f(2, 3, 2)], "HRes": [f(2, 3, 4)]},
            {"n": 2, "sinkhorn_iters": 3}),
        # two heads of width 4 over 6 positions, chunks of 4 (padded inside);
        # States, the chunk states kept for the grad op, carries no gradient
        "kda_scan": lambda: _Cfg(
            {"Q": [f(1, 6, 2, 4)], "K": [f(1, 6, 2, 4)], "V": [f(1, 6, 2, 4)],
             "G": [f(1, 6, 2, 4, lo=-0.9, hi=-0.1)],
             "Beta": [f(1, 6, 2, lo=0.2, hi=0.8)]},
            {"chunk": 4, "neg_eigval": True}, loss_outputs=["Out"]),
        "kda_gate": lambda: _Cfg(
            {"X": [f(1, 3, 8)], "B": [f(1, 3, 2)],
             "ALog": [f(2, lo=0.1, hi=1.0)], "DtBias": [f(8)]},
            loss_outputs=["G", "Beta"]),
        # two groups of two heads of width 4, state 3, over 6 positions in
        # chunks of 4 (padded inside); States, the chunk states kept for the
        # grad op, carries no gradient
        "ssd_scan": lambda: _Cfg(
            {"X": [f(1, 6, 4, 4)], "Dt": [f(1, 6, 4, lo=-0.5, hi=0.5)],
             "ALog": [f(4, lo=0.0, hi=1.0)], "B": [f(1, 6, 2, 3)],
             "C": [f(1, 6, 2, 3)], "D": [f(4)],
             "DtBias": [f(4, lo=-0.5, hi=0.5)]},
            {"chunk": 4}, loss_outputs=["Out"]),
        "gated_rms_norm": lambda: _Cfg(
            {"X": [f(2, 3, 8)], "Z": [f(2, 3, 8, lo=-1.0, hi=1.0)],
             "Scale": [f(8)]}, {"groups": 2, "epsilon": 1e-5}),
        "sample_logits": lambda: _Cfg(
            {"Logits": [f(3, 5)], "Labels": [i(3, 1, n=5)]},
            {"num_samples": 2, "seed": 3}, loss_outputs=["SampledLogits"]),
        "scale": lambda: _Cfg({"X": [f(2, 3)]}, {"scale": 1.7, "bias": 0.2}),
        "scatter": lambda: _Cfg({"X": [f(5, 3)],
                         "Ids": [np.int64([0, 2, 4])],
                         "Updates": [f(3, 3)]}, {"overwrite": True}),
        "scatter_nd": lambda: _Cfg({"Index": [np.int64([[0], [2]])],
                            "Updates": [f(2, 3)]}, {"shape": [4, 3]}),
        "scatter_nd_add": lambda: _Cfg({"X": [f(4, 3)],
                                "Index": [np.int64([[0], [2]])],
                                "Updates": [f(2, 3)]}),
        "sequence_conv": lambda: _Cfg({"X": [f(1, 4, 2)], "Filter": [f(6, 4)]},
                              {"context_length": 3, "context_start": -1}),
        "sequence_reshape": lambda: _Cfg({"X": [f(1, 3, 4)]}, {"new_dim": 2}),
        "sequence_scatter": lambda: _Cfg(
            {"X": [f(2, 4)], "Ids": [i(1, 3, n=4)], "Updates": [f(1, 3)]}),
        "sequence_slice": lambda: _Cfg(
            {"X": [f(1, 4, 3)], "Offset": [np.int64([[1]])],
             "Length": [np.int64([[2]])]}),
        "sigmoid_focal_loss": lambda: _Cfg(
            {"X": [f(3, 4)], "Label": [i(3, 1, n=5)],
             "FgNum": [np.int64([2])]},
            {"gamma": 2.0, "alpha": 0.25}),
        "slice": lambda: _Cfg({"Input": [f(3, 4)]},
                      {"axes": [0, 1], "starts": [0, 1], "ends": [2, 3],
                       "decrease_axis": []}),
        "softmax_with_cross_entropy": lambda: _Cfg(
            {"Logits": [f(4, 5)], "Label": [i(4, 1, n=5)]},
            {"soft_label": False}, loss_outputs=["Loss"]),
        "space_to_depth": lambda: _Cfg({"X": [f(1, 2, 4, 4)]}, {"blocksize": 2}),
        "spectral_norm": lambda: _Cfg({"Weight": [f(3, 4)], "U": [f(3)],
                               "V": [f(4)]},
                              {"dim": 0, "power_iters": 1, "eps": 1e-12},
                              nodiff={"U", "V"}),
        "split": lambda: _Cfg({"X": [f(2, 4)]}, {"axis": 1, "num": 2}),
        "split_byref": lambda: _Cfg({"X": [f(2, 4)]}, {"axis": 1, "num": 2}),
        "strided_slice": lambda: _Cfg({"Input": [f(4, 5)]},
                              {"axes": [0, 1], "starts": [0, 1],
                               "ends": [4, 5], "strides": [2, 2]}),
        # MoE top-1 routing is piecewise: a perturbed gate weight can
        # flip token->expert assignment mid-difference
        "switch_ffn": lambda: _Cfg(
            {"X": [f(2, 2, 3)], "GateW": [f(3, 2)], "W1": [f(2, 3, 5)],
             "B1": [f(2, 5)], "W2": [f(2, 5, 3)], "B2": [f(2, 3)]},
            {"capacity_factor": 2.0}, rtol=8e-2, atol=2e-2),
        "rms_norm": lambda: _Cfg({"X": [f(2, 3, 4)], "Scale": [f(4)]},
                         {"epsilon": 1e-5, "begin_norm_axis": 2}),
        "rope": lambda: _Cfg({"X": [f(2, 3, 8)]},
                     {"head_dim": 4, "theta": 10000.0}),
        # dropless top-2 of 4: a perturbed row or router weight can flip a
        # token's second expert mid-difference, as for switch_ffn; the
        # integer outputs (ExpertLoad, TopExperts) carry no gradient
        "moe_ffn": lambda: _Cfg(
            {"X": [f(2, 3, 4)], "RouterW": [f(4, 4, lo=-2.0, hi=2.0)],
             "GateW": [f(4, 4, 5)], "UpW": [f(4, 4, 5)],
             "DownW": [f(4, 5, 4)]},
            {"top_k": 2, "norm_topk_prob": False},
            loss_outputs=["Out", "LbLoss", "ZLoss"], rtol=8e-2, atol=2e-2),
        "temporal_shift": lambda: _Cfg({"X": [f(4, 4, 2, 2)]},
                               {"seg_num": 2, "shift_ratio": 0.25}),
        "tile": lambda: _Cfg({"X": [f(2, 3)]}, {"repeat_times": [2, 1]}),
        "transpose": lambda: _Cfg({"X": [f(2, 3)]}, {"axis": [1, 0]}),
        "transpose2": lambda: _Cfg({"X": [f(2, 3)]}, {"axis": [1, 0]}),
        "tree_conv": lambda: _Cfg(
            {"NodesVector": [f(1, 4, 3)],
             "EdgeSet": [np.int64([[[0, 1], [0, 2], [1, 3]]])],
             "Filter": [f(3, 3, 2, 4)]}, {"max_depth": 2}),
        "trilinear_interp": lambda: _Cfg({"X": [f(1, 2, 3, 3, 3)]},
                                 {"out_d": 4, "out_h": 4, "out_w": 4}),
        "unfold": lambda: _Cfg({"X": [f(1, 2, 4, 4)]},
                       {"kernel_sizes": [2, 2], "strides": [2, 2],
                        "paddings": [0, 0, 0, 0], "dilations": [1, 1]}),
        "unpool": lambda: _Cfg({"X": [f(1, 1, 2, 2)],
                        "Indices": [np.int64([[[[5, 7], [13, 15]]]])]},
                       {"unpooled_height": 4, "unpooled_width": 4}),
        "var_conv_2d": lambda: _Cfg({"X": [f(1, 3, 4, 4)], "W": [f(2, 3, 2, 2)]},
                            {"output_channel": 2, "input_channel": 3,
                             "kernel_h": 2, "kernel_w": 2,
                             "stride_h": 1, "stride_w": 1}),
        # CTC loss: log-sum-exp over alignment paths is steep in the
        # small-logit regime; f32 forward noise amplifies through the
        # 1e-2 quotient (ref OpTest relaxes CTC grads likewise)
        "warpctc": lambda: _Cfg(
            {"Logits": [f(2, 4, 5)],
             "Label": [i(2, 3, n=4) + 1],
             "LogitsLength": [np.int64([4, 4])],
             "LabelLength": [np.int64([3, 2])]},
            {"blank": 0, "norm_by_times": False}, loss_outputs=["Loss"],
            rtol=8e-2, atol=2e-2),
        "yolov3_loss": lambda: _Cfg(
            {"X": [f(1, 14, 4, 4)],
             "GTBox": [f(1, 3, 4, lo=0.2, hi=0.7)],
             "GTLabel": [i(1, 3, n=2)]},
            {"anchors": [10, 13, 16, 30], "anchor_mask": [0, 1],
             "class_num": 2, "ignore_thresh": 0.7, "downsample_ratio": 32,
             "use_label_smooth": False},
            # GTBox moves the discrete best-anchor assignment and the
            # ignore-threshold mask — kinked; sweep X only (ref OpTest
            # checks only X too)
            nodiff={"GTBox"},
            loss_outputs=["Loss"], rtol=1e-1, atol=3e-2),
    }
    fn = C.get(op)
    return fn() if fn is not None else None


# ---------------------------------------------------------------------------
# documented exclusions (ref OpTest likewise skips these categories)
# ---------------------------------------------------------------------------

EXCLUDE = {
    # TensorArray / LoD / control-flow plumbing: op-level numeric diff is
    # meaningless (stateful array semantics); gradient flow is covered
    # end-to-end by test_control_flow.py / test_while_grad.py
    "array_read": "TensorArray plumbing; covered by test_control_flow",
    "array_write": "TensorArray plumbing; covered by test_control_flow",
    "read_from_array": "TensorArray plumbing; covered by test_control_flow",
    "write_to_array": "TensorArray plumbing; covered by test_control_flow",
    "tensor_array_to_tensor":
        "TensorArray plumbing; covered by test_control_flow",
    "array_to_lod_tensor": "TensorArray plumbing; covered by test_control_flow",
    "lod_tensor_to_array": "TensorArray plumbing; covered by test_control_flow",
    "merge_lod_tensor": "IfElse dataflow; covered by test_control_flow",
    "merge_lod_tensor_infer": "inference-only IfElse dataflow",
    "split_lod_tensor": "IfElse dataflow; covered by test_control_flow",
    "ifelse_merge": "IfElse dataflow; covered by test_control_flow",
    "shrink_rnn_memory": "DynamicRNN internal; covered by test_control_flow",
    "reorder_lod_tensor_by_rank":
        "DynamicRNN internal permutation; covered by test_control_flow",
    "drnn_masked_update": "While-loop internal helper; covered by "
                          "test_while_grad end-to-end",
    "rnn_memory_helper": "RNN scaffold op; covered by test_control_flow",
    # Serving-path fusion ops: the reference registers NO grad kernels for
    # these (they are produced by inference IR passes, never trained through)
    "attention_lstm": "inference-only fusion op (ref has no grad kernel)",
    "fused_embedding_fc_lstm":
        "inference-only fusion op (ref has no grad kernel)",
    "fusion_gru": "inference-only fusion op (ref has no grad kernel)",
    "fusion_lstm": "inference-only fusion op (ref has no grad kernel)",
    "fusion_repeated_fc_relu":
        "inference-only fusion op (ref has no grad kernel)",
    "fusion_seqconv_eltadd_relu":
        "inference-only fusion op (ref has no grad kernel)",
    "fusion_seqexpand_concat_fc":
        "inference-only fusion op (ref has no grad kernel)",
    "fusion_squared_mat_sub":
        "inference-only fusion op (ref has no grad kernel)",
    "conv2d_fusion": "inference-only fusion op (ref has no grad kernel)",
    "conv2d_inception_fusion":
        "inference-only fusion op (ref has no grad kernel)",
    "fused_fc_elementwise_layernorm":
        "inference-only fusion op (ref has no grad kernel)",
    "fusion_seqpool_concat":
        "inference-only fusion op (ref has no grad kernel)",
    "fusion_seqpool_cvm_concat":
        "inference-only fusion op (ref has no grad kernel)",
    "fusion_transpose_flatten_concat":
        "inference-only fusion op (ref has no grad kernel)",
    # straight-through estimators: the analytic grad is DELIBERATELY the
    # identity pass-through, not the derivative of the quantization step
    # function (ref fake_quantize_op.cc grad kernels do the same)
    "fake_quantize_dequantize_abs_max":
        "straight-through estimator: grad is pass-through by design",
    "fake_quantize_dequantize_moving_average_abs_max":
        "straight-through estimator: grad is pass-through by design",
    "fake_channel_wise_quantize_dequantize_abs_max":
        "straight-through estimator: grad is pass-through by design",
    # host/collective/infra
    "py_func": "host callback; grad depends on user-registered backward_func",
    "ring_attention": "needs a shard_map mesh axis; grad parity is measured "
                      "in test_attention + dryrun_multichip",
    "ssd_loss": "bipartite matching is discrete (zero-measure kinks at "
                "match flips); ref OpTest tests forward only too",
    "filter_by_instag": "data-dependent output shape (LoD row filtering)",
    "deformable_psroi_pooling":
        "floor/ceil bin boundaries make the loss kinked in ROI and part "
        "coords; forward parity in test_detection",
    "sequence_topk_avg_pooling":
        "top-k selection is piecewise constant; forward parity locked in "
        "test_compat_ops",
    "get_tensor_from_selected_rows":
        "SelectedRows container shim; identity dataflow",
    "merge_selected_rows": "SelectedRows container shim",
    "allreduce": "collective; exercised by test_distributed + two-process "
                 "suite",
    "broadcast": "collective; exercised by test_distributed",
    "c_allgather": "collective; exercised by test_distributed",
    "c_allreduce_max": "collective; exercised by test_distributed",
    "c_allreduce_min": "collective; exercised by test_distributed",
    "c_allreduce_prod": "collective; exercised by test_distributed",
    "c_allreduce_sum": "collective; exercised by test_distributed",
    "c_broadcast": "collective; exercised by test_distributed",
    "c_reducescatter": "collective; exercised by test_distributed",
    "c_split": "collective; exercised by test_distributed",
    "c_sync_calc_stream": "stream sync no-op on XLA",
    "c_sync_comm_stream": "stream sync no-op on XLA",
}


def _diffable_ops():
    out = []
    for t in registry.registered_ops():
        info = registry._REGISTRY[t]
        if info.no_grad or info.raw or t.endswith("_grad"):
            continue
        out.append(t)
    return out


def _default_config(op_type):
    """Default recipes, tried in order via abstract eval (ref OpTest's
    conventional X/Y/Label slots)."""
    r = _rng(op_type)
    cands = [
        {"X": [_f(r, 2, 3)]},
        {"X": [_f(r, 2, 3)], "Y": [_f(r, 2, 3)]},
        {"X": [_f(r, 4, 3)], "Label": [_i(r, 4, 1, n=3)]},
        {"X": [_f(r, 2, 3, 4, 4)]},
        {"Input": [_f(r, 2, 3)]},
    ]
    for ins in cands:
        if _probe(op_type, ins, {}) is not None:
            return _Cfg(ins)
    return None


def _probe(op_type, ins, attrs):
    """Abstract-eval the lowering; returns {slot: [ShapeDtypeStruct]} or
    None."""
    info = registry._REGISTRY[op_type]
    structs = {
        slot: [jax.ShapeDtypeStruct(a.shape, _canon(a.dtype)) for a in arrs]
        for slot, arrs in ins.items()}
    try:
        outs = jax.eval_shape(
            lambda i: info.lower(registry._AbstractCtx(), i, attrs), structs)
    except Exception:
        return None
    if not isinstance(outs, dict) or not outs:
        return None
    return outs


def _canon(dt):
    import jax.numpy as jnp
    from paddle_tpu.ops.common import canon_dtype
    return canon_dtype(np.dtype(dt).name)


def _is_float(a):
    return np.issubdtype(np.asarray(a).dtype, np.floating)


def _resolve(op_type):
    cfg = _configs(op_type)
    if cfg is None:
        cfg = _default_config(op_type)
    return cfg


AUDIT_OPS = sorted(t for t in _diffable_ops() if t not in EXCLUDE)
#: an op's second recipe, "<op>+<what>": optional slots that change what
#: its grad op computes
AUDIT_VARIANTS = ["flash_attention+rope", "flash_attention+block_diffusion"]


def test_audit_accounts_for_every_op():
    """Every differentiable registered op is swept or explicitly excluded
    with a reason; no stale exclusions."""
    diffable = set(_diffable_ops())
    stale = sorted(k for k in EXCLUDE if k not in diffable)
    assert not stale, f"EXCLUDE entries not in the registry sweep: {stale}"
    assert all(EXCLUDE.values()), "every exclusion needs a reason"
    assert sorted(diffable - set(EXCLUDE)) == AUDIT_OPS


@pytest.mark.parametrize("op_type", AUDIT_OPS + AUDIT_VARIANTS)
def test_check_grad(op_type):
    cfg = _resolve(op_type)
    op_type = op_type.partition("+")[0]
    assert cfg is not None, (
        f"{op_type}: no input config — add one to _configs() or document "
        f"an exclusion in EXCLUDE")
    outs_abs = _probe(op_type, cfg.ins, cfg.attrs)
    assert outs_abs is not None, (
        f"{op_type}: configured inputs fail abstract eval "
        f"(ins shapes {[(s, [a.shape for a in v]) for s, v in cfg.ins.items()]})")

    with program_guard(Program(), Program()), scope_guard(Scope()):
        feed, in_vars, diff_names = {}, {}, []
        for slot, arrs in cfg.ins.items():
            vs = []
            for j, a in enumerate(arrs):
                name = f"in_{slot}_{j}"
                want_grad = _is_float(a) and slot not in cfg.nodiff
                v = layers.data(name, shape=list(a.shape),
                                append_batch_size=False,
                                dtype=str(np.asarray(a).dtype),
                                stop_gradient=not want_grad)
                feed[name] = np.asarray(a)
                vs.append(v)
                if want_grad:
                    diff_names.append(name)
            in_vars[slot] = vs
        helper = LayerHelper(op_type)
        out_vars = {}
        for slot, structs in outs_abs.items():
            out_vars[slot] = [
                helper.create_variable_for_type_inference(
                    np.dtype(s.dtype).name)
                for s in structs if s is not None]
        helper.append_op(op_type, inputs=in_vars, outputs=out_vars,
                         attrs=dict(cfg.attrs))

        loss_slots = cfg.loss_outputs or [
            slot for slot, structs in outs_abs.items()
            if structs and structs[0] is not None
            and np.issubdtype(np.dtype(structs[0].dtype), np.floating)]
        terms = []
        for slot in loss_slots:
            for v in out_vars[slot]:
                terms.append(layers.reduce_sum(layers.square(v)))
        assert terms, f"{op_type}: no float outputs to build a loss from"
        loss = terms[0] if len(terms) == 1 else layers.sum(terms)
        append_backward(loss)
        assert diff_names, f"{op_type}: nothing to differentiate"
        block = loss.block
        missing = [n for n in diff_names if not block.has_var(grad_var_name(n))]
        assert not missing, (
            f"{op_type}: append_backward produced no grad for {missing}")

        exe = Executor()
        fetched = exe.run(feed=feed,
                          fetch_list=[loss.name] +
                          [grad_var_name(n) for n in diff_names],
                          seed=SEED)
        base_loss, analytic = float(np.sum(fetched[0])), fetched[1:]

        def run_loss():
            out, = exe.run(feed=feed, fetch_list=[loss.name], seed=SEED)
            return float(np.sum(out))

        # f32 rounding on the loss sum propagates into the quotient:
        # widen atol accordingly (ref OpTest's max_relative_error knob)
        noise = abs(base_loss) * 1.5e-7 / cfg.eps * 4
        atol = max(cfg.atol, noise)

        idx_rng = np.random.RandomState(1234)
        for name, g_analytic in zip(diff_names, analytic):
            a = feed[name]
            flat = a.reshape(-1)
            n = flat.size
            idxs = (np.arange(n) if n <= cfg.max_elems else
                    np.sort(idx_rng.choice(n, cfg.max_elems, replace=False)))
            ga = np.asarray(g_analytic).reshape(-1)
            assert ga.size == n, (
                f"{op_type}: grad of {name} has {ga.size} elements, "
                f"input has {n}")
            for i in idxs:
                orig = flat[i]
                flat[i] = orig + cfg.eps
                lp = run_loss()
                flat[i] = orig - cfg.eps
                lm = run_loss()
                flat[i] = orig
                gn = (lp - lm) / (2 * cfg.eps)
                err = abs(float(ga[i]) - gn)
                tol = atol + cfg.rtol * max(abs(gn), abs(float(ga[i])))
                assert err <= tol, (
                    f"{op_type}: d loss/d {name}[{i}] analytic "
                    f"{float(ga[i]):.6g} vs numeric {gn:.6g} "
                    f"(err {err:.3g} > tol {tol:.3g}, loss {base_loss:.6g})")
