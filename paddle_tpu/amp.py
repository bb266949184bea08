"""Automatic mixed precision (SURVEY §5.9; ref
``python/paddle/fluid/contrib/mixed_precision/decorator.py:27,208``,
``fp16_lists.py``, ``fp16_utils.py``).

The reference rewrites the ProgramDesc, inserting cast ops around white/black
listed ops and wrapping the optimizer with (dynamic) loss scaling.  The
TPU-native realization casts at lowering time instead: inputs to
matmul-class ops ("white list") are cast to bf16 as the block is traced, and
numerically-sensitive ops ("black list") are forced to f32.  Master weights
stay f32 in the Scope; XLA fuses the cast pairs away, so the effect is pure
bf16 MXU traffic with f32 accumulation — no loss scaling needed for bf16
(the fp16 dynamic-loss-scaling API is kept for parity and for fp16 policies).
"""

from __future__ import annotations

import jax.numpy as jnp

# ops whose FLOPs dominate and that are bf16-safe (ref fp16_lists.py
# white_list)
WHITE_LIST = {
    "mul", "matmul", "matmul_v2", "conv2d", "depthwise_conv2d", "conv3d",
    "conv2d_transpose", "fc", "bilinear_tensor_product",
}

# numerically-sensitive ops forced to f32 (ref fp16_lists.py black_list).
# Norm/softmax ops are NOT here: their lowerings already compute statistics
# in f32 internally and return the input dtype, which keeps the activation
# stream bf16 (the reference had to blacklist them because its kernels were
# dtype-monomorphic).
BLACK_LIST = {
    "softmax_with_cross_entropy", "softmax_with_cross_entropy_grad",
    "cross_entropy", "cross_entropy2",
    "mean", "reduce_mean", "reduce_sum", "sum", "exp", "log",
    "squared_l2_norm", "l2_normalize", "norm",
    "sigmoid_cross_entropy_with_logits",
    "isfinite", "sqrt", "rsqrt", "pow", "logsumexp",
}

# big elementwise traffic (residual adds, bias adds, activations, dropout):
# cast f32→bf16 ONLY when operating on real activation tensors (ndim≥3) so
# scalar/LR-schedule math keeps full precision.  This keeps the residual
# stream bf16 — HBM bandwidth is the usual TPU bottleneck.
BF16_IF_BIG = {
    "elementwise_add", "elementwise_sub", "elementwise_mul", "dropout",
    "gelu", "relu", "tanh", "sigmoid", "swish", "leaky_relu", "relu6",
    "softmax", "layer_norm", "batch_norm", "group_norm", "scale", "concat",
    # float32 inside (statistics, angles), the stream in bf16
    "rms_norm", "rope",
    # float32 inside too; the [d, L] filter is a master weight and stays so
    "short_conv",
    # the streams in bf16; Phi, Alpha, Bias and the maps float32 (the
    # coefficients and Sinkhorn-Knopp are float32 inside: ops/hc_ops.py)
    "hc_pre", "hc_post",
    # Q, K and V in bf16; the log-decay and beta stay float32, as does
    # everything inside the scan (ops/kda_ops.py); kda_gate is in no list:
    # float32 inside in either form of the decay's gate (softplus, or the
    # bounded lower_bound * sigmoid), and its outputs are float32; moe_ffn
    # is in no list either: its router (scores, the group scores of a
    # group-limited selection, the mask and the top-k) is float32 at full
    # precision inside whatever the rows' dtype (ops/moe_ops.py)
    "kda_scan",
    # x, B and C in bf16; dt, A_log, D and dt_bias stay float32 and
    # softplus(dt + dt_bias), the decay and everything inside the scan are
    # float32 (ops/ssd_ops.py); gated_rms_norm is in no list: the gate's
    # product and the groups' statistics are float32 inside whatever its
    # inputs' dtype
    "ssd_scan",
}

_COMPUTE = jnp.bfloat16
_FLOATS = (jnp.float32, jnp.bfloat16, jnp.float16)

# norm ops carry f32 STATE inputs (running mean/var, scale/bias) that must
# not be rounded to bf16 every step — only the activation slot is cast
_SLOT_RESTRICT = {"batch_norm": {"X"}, "layer_norm": {"X"},
                  "group_norm": {"X"}, "rms_norm": {"X"},
                  "short_conv": {"X"}, "hc_pre": {"X"},
                  "hc_post": {"X", "Y"}, "kda_scan": {"Q", "K", "V"},
                  "ssd_scan": {"X", "B", "C"}}

# NOTE: the analysis.fusion targets (fused_dense_act,
# fused_embedding_layer_norm) appear in NO list above on purpose: one
# blanket cast over a fused op would differ from the per-op casts of the
# chain it replaced (e.g. a 2-D bias add stays f32 unfused), so their
# lowerings in ops/fused_ops.py replicate this module's per-stage policy
# internally — keep the three policies in sync when editing the lists.
# moe_ffn (ops/moe_ops.py) is in no list either: its router is float32 at
# full precision and its rows and expert weights bf16, decided inside the
# lowering from ``ctx.amp``.


def _cast_all(ins, target, slots=None):
    out = {}
    for slot, arrs in ins.items():
        if slots is not None and slot not in slots:
            out[slot] = arrs
            continue
        converted = []
        for a in arrs:
            if a is not None and hasattr(a, "dtype") and \
                    a.dtype in _FLOATS and a.dtype != target:
                a = a.astype(target)
            converted.append(a)
        out[slot] = converted
    return out


def cast_ins(op_type: str, ins):
    """Apply the AMP policy to an op's input arrays at trace time."""
    base = op_type[:-5] if op_type.endswith("_grad") else op_type
    if base in WHITE_LIST or op_type in WHITE_LIST:
        return _cast_all(ins, _COMPUTE)
    if base in BLACK_LIST or op_type in BLACK_LIST:
        return _cast_all(ins, jnp.float32)
    if base in BF16_IF_BIG:
        big = any(a is not None and getattr(a, "ndim", 0) >= 3
                  for arrs in ins.values() for a in arrs)
        if big:
            return _cast_all(ins, _COMPUTE, _SLOT_RESTRICT.get(base))
    return ins


def enable(program=None):
    """Turn on bf16 AMP for a program's lowering."""
    from .framework.core import default_main_program
    program = program or default_main_program()
    program._attrs["amp"] = True
    program._bump_version()
    return program


class DynamicLossScaler:
    """Dynamic loss-scaling state machine (ref decorator.py:208
    ``update_loss_scaling``): halve the scale (and SKIP the step) on a
    non-finite gradient, grow it after ``incr_every_n_steps``
    consecutive clean steps.

    What's new here is the observability (this PR's satellite): every
    scale move and every skipped step used to be INVISIBLE — now each
    emits an ``amp.loss_scale`` trace instant in the numerics-anomaly
    record format (``analysis.numerics.record_anomaly``: loss-scale
    events are first-class anomaly records, counted in
    ``paddle_tpu_numerics_anomalies_total{kind}``), the live scale is
    the ``paddle_tpu_amp_scale`` gauge, and skipped steps count in
    ``paddle_tpu_amp_skipped_steps_total`` — a run silently wedged at
    scale 1 with every step skipped is diagnosable from /metrics alone.
    """

    def __init__(self, init_loss_scaling=2 ** 15, incr_every_n_steps=1000,
                 decr_every_n_nan_or_inf=2, incr_ratio=2.0,
                 decr_ratio=0.8, min_scale=1.0):
        self.scale = float(init_loss_scaling)
        self.incr_every_n_steps = int(incr_every_n_steps)
        self.decr_every_n_nan_or_inf = max(int(decr_every_n_nan_or_inf), 1)
        self.incr_ratio = float(incr_ratio)
        self.decr_ratio = float(decr_ratio)
        self.min_scale = float(min_scale)
        self._good_steps = 0
        self._bad_steps = 0
        self._step = 0
        from . import monitor as _monitor
        self._gauge = _monitor.REGISTRY.gauge(
            "paddle_tpu_amp_scale",
            "current dynamic loss scale (fp16 AMP); a scale pinned at "
            "its minimum with skipped steps climbing means the model "
            "is producing non-finite grads every step")
        self._skip_ctr = _monitor.REGISTRY.counter(
            "paddle_tpu_amp_skipped_steps_total",
            "optimizer steps SKIPPED by dynamic loss scaling "
            "(non-finite gradients at the current scale)")
        self._gauge.set(self.scale)

    def _event(self, kind, value=None, detail=None):
        from .analysis import numerics as _numerics
        _numerics.record_anomaly(
            kind, step=self._step, value=value,
            detail=dict(detail or (), scale=self.scale),
            instant="amp.loss_scale")

    def update(self, found_inf) -> bool:
        """Feed one step's found-non-finite verdict; returns True when
        the step's update should be APPLIED, False when it must be
        skipped (grads were non-finite at the current scale)."""
        self._step += 1
        if bool(found_inf):
            self._good_steps = 0
            self._bad_steps += 1
            self._skip_ctr.inc()
            if self._bad_steps >= self.decr_every_n_nan_or_inf:
                self._bad_steps = 0
                old = self.scale
                self.scale = max(self.scale * self.decr_ratio,
                                 self.min_scale)
                self._gauge.set(self.scale)
                self._event("loss_scale_decreased", value=self.scale,
                            detail={"from": old})
            else:
                self._event("step_skipped", value=self.scale)
            return False
        self._bad_steps = 0
        self._good_steps += 1
        if self._good_steps >= self.incr_every_n_steps:
            self._good_steps = 0
            old = self.scale
            self.scale = self.scale * self.incr_ratio
            self._gauge.set(self.scale)
            self._event("loss_scale_increased", value=self.scale,
                        detail={"from": old})
        return True


def decorate(optimizer, amp_lists=None, init_loss_scaling=2 ** 15,
             incr_every_n_steps=1000, decr_every_n_nan_or_inf=2,
             incr_ratio=2.0, decr_ratio=0.8,
             use_dynamic_loss_scaling=True):
    """ref decorator.py:27 — returns an optimizer whose minimize() enables
    bf16 AMP on the program.  bf16 needs no loss scaling (unlike the
    reference's fp16) so the lowering never applies the scale, but the
    scaler STATE MACHINE is real (``.loss_scaler``): fp16-policy callers
    drive it with per-step found-inf verdicts and get the skip/halve/
    grow protocol plus its telemetry (``amp.loss_scale`` instants,
    ``paddle_tpu_amp_scale`` gauge, skipped-step counter)."""

    class _AmpOptimizer:
        def __init__(self, inner):
            self._inner = inner
            self.loss_scaler = (
                DynamicLossScaler(
                    init_loss_scaling=init_loss_scaling,
                    incr_every_n_steps=incr_every_n_steps,
                    decr_every_n_nan_or_inf=decr_every_n_nan_or_inf,
                    incr_ratio=incr_ratio, decr_ratio=decr_ratio)
                if use_dynamic_loss_scaling else None)

        @property
        def _loss_scaling(self):
            return (self.loss_scaler.scale
                    if self.loss_scaler is not None
                    else float(init_loss_scaling))

        def __getattr__(self, name):
            return getattr(self._inner, name)

        def minimize(self, loss, **kw):
            enable(loss.block.program)
            return self._inner.minimize(loss, **kw)

        def backward(self, loss, **kw):
            enable(loss.block.program)
            return self._inner.backward(loss, **kw)

    return _AmpOptimizer(optimizer)
