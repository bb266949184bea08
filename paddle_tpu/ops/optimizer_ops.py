"""Optimizer op lowerings (ref ``operators/optimizers/`` — 40 files).

Each optimizer is one op updating Param (+ accumulators) in place — the
lowered block returns the new values and the Executor writes them back to the
Scope with buffer donation, matching the reference's in-place CUDA kernels.
All are ``no_grad`` (they sit after the grad ops).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..framework.registry import register_op
from .common import X


def _lr(ins):
    lr = X(ins, "LearningRate")
    return lr.reshape(()) if lr is not None and lr.ndim else lr


@register_op("sgd", no_grad=True)
def _sgd(ctx, ins, attrs):
    p, g = X(ins, "Param"), X(ins, "Grad")
    return {"ParamOut": [(p - _lr(ins) * g).astype(p.dtype)]}


@register_op("momentum", no_grad=True)
def _momentum(ctx, ins, attrs):
    p, g, v = X(ins, "Param"), X(ins, "Grad"), X(ins, "Velocity")
    lr = _lr(ins)
    mu = attrs.get("mu", 0.9)
    v_new = mu * v + g
    if attrs.get("use_nesterov", False):
        p_new = p - (g + mu * v_new) * lr
    else:
        p_new = p - lr * v_new
    return {"ParamOut": [p_new.astype(p.dtype)], "VelocityOut": [v_new]}


@register_op("lars_momentum", no_grad=True)
def _lars_momentum(ctx, ins, attrs):
    p, g, v = X(ins, "Param"), X(ins, "Grad"), X(ins, "Velocity")
    lr = _lr(ins)
    mu = attrs.get("mu", 0.9)
    coeff = attrs.get("lars_coeff", 1e-3)
    decay = attrs.get("lars_weight_decay", 5e-4)
    eps = 1e-9
    pn = jnp.sqrt(jnp.sum(jnp.square(p)))
    gn = jnp.sqrt(jnp.sum(jnp.square(g)))
    local_lr = lr * coeff * pn / (gn + decay * pn + eps)
    v_new = mu * v + local_lr * (g + decay * p)
    return {"ParamOut": [(p - v_new).astype(p.dtype)], "VelocityOut": [v_new]}


@register_op("adam", no_grad=True)
def _adam(ctx, ins, attrs):
    """ref operators/optimizers/adam_op.h AdamFunctor."""
    p, g = X(ins, "Param"), X(ins, "Grad")
    m1, m2 = X(ins, "Moment1"), X(ins, "Moment2")
    b1p, b2p = X(ins, "Beta1Pow"), X(ins, "Beta2Pow")
    lr = _lr(ins)
    b1 = attrs.get("beta1", 0.9)
    b2 = attrs.get("beta2", 0.999)
    eps = attrs.get("epsilon", 1e-8)
    m1n = b1 * m1 + (1 - b1) * g
    m2n = b2 * m2 + (1 - b2) * jnp.square(g)
    b1p_ = b1p.reshape(())
    b2p_ = b2p.reshape(())
    lr_t = lr * jnp.sqrt(1 - b2p_) / (1 - b1p_)
    p_new = p - lr_t * m1n / (jnp.sqrt(m2n) + eps)
    return {"ParamOut": [p_new.astype(p.dtype)],
            "Moment1Out": [m1n], "Moment2Out": [m2n],
            "Beta1PowOut": [b1p * b1], "Beta2PowOut": [b2p * b2]}


@register_op("adamw", no_grad=True)
def _adamw(ctx, ins, attrs):
    p = X(ins, "Param")
    coeff = attrs.get("coeff", 0.01)
    lr = _lr(ins)
    outs = _adam(ctx, ins, attrs)
    outs["ParamOut"] = [(outs["ParamOut"][0] - lr * coeff * p).astype(p.dtype)]
    return outs


@register_op("adamax", no_grad=True)
def _adamax(ctx, ins, attrs):
    p, g = X(ins, "Param"), X(ins, "Grad")
    m, inf = X(ins, "Moment"), X(ins, "InfNorm")
    b1p = X(ins, "Beta1Pow").reshape(())
    lr = _lr(ins)
    b1 = attrs.get("beta1", 0.9)
    b2 = attrs.get("beta2", 0.999)
    eps = attrs.get("epsilon", 1e-8)
    m_new = b1 * m + (1 - b1) * g
    inf_new = jnp.maximum(b2 * inf, jnp.abs(g))
    p_new = p - (lr / (1 - b1p)) * (m_new / (inf_new + eps))
    return {"ParamOut": [p_new.astype(p.dtype)], "MomentOut": [m_new],
            "InfNormOut": [inf_new]}


@register_op("adagrad", no_grad=True)
def _adagrad(ctx, ins, attrs):
    p, g, mom = X(ins, "Param"), X(ins, "Grad"), X(ins, "Moment")
    lr = _lr(ins)
    eps = attrs.get("epsilon", 1e-6)
    m_new = mom + jnp.square(g)
    p_new = p - lr * g / (jnp.sqrt(m_new) + eps)
    return {"ParamOut": [p_new.astype(p.dtype)], "MomentOut": [m_new]}


@register_op("decayed_adagrad", no_grad=True)
def _decayed_adagrad(ctx, ins, attrs):
    p, g, mom = X(ins, "Param"), X(ins, "Grad"), X(ins, "Moment")
    lr = _lr(ins)
    decay = attrs.get("decay", 0.95)
    eps = attrs.get("epsilon", 1e-6)
    m_new = decay * mom + (1 - decay) * jnp.square(g)
    p_new = p - lr * g / (jnp.sqrt(m_new) + eps)
    return {"ParamOut": [p_new.astype(p.dtype)], "MomentOut": [m_new]}


@register_op("adadelta", no_grad=True)
def _adadelta(ctx, ins, attrs):
    p, g = X(ins, "Param"), X(ins, "Grad")
    avg_sq, avg_upd = X(ins, "AvgSquaredGrad"), X(ins, "AvgSquaredUpdate")
    rho = attrs.get("rho", 0.95)
    eps = attrs.get("epsilon", 1e-6)
    sq_new = rho * avg_sq + (1 - rho) * jnp.square(g)
    upd = -jnp.sqrt((avg_upd + eps) / (sq_new + eps)) * g
    upd_new = rho * avg_upd + (1 - rho) * jnp.square(upd)
    return {"ParamOut": [(p + upd).astype(p.dtype)],
            "AvgSquaredGradOut": [sq_new], "AvgSquaredUpdateOut": [upd_new]}


@register_op("rmsprop", no_grad=True)
def _rmsprop(ctx, ins, attrs):
    p, g = X(ins, "Param"), X(ins, "Grad")
    ms, mom = X(ins, "MeanSquare"), X(ins, "Moment")
    mg = X(ins, "MeanGrad")
    lr = _lr(ins)
    rho = attrs.get("decay", 0.95)
    eps = attrs.get("epsilon", 1e-6)
    mu = attrs.get("momentum", 0.0)
    centered = attrs.get("centered", False)
    ms_new = rho * ms + (1 - rho) * jnp.square(g)
    outs = {}
    if centered and mg is not None:
        mg_new = rho * mg + (1 - rho) * g
        denom = ms_new - jnp.square(mg_new) + eps
        outs["MeanGradOut"] = [mg_new]
    else:
        denom = ms_new + eps
    mom_new = mu * mom + lr * g * jax.lax.rsqrt(denom)
    outs.update({"ParamOut": [(p - mom_new).astype(p.dtype)],
                 "MomentOut": [mom_new], "MeanSquareOut": [ms_new]})
    return outs


@register_op("ftrl", no_grad=True)
def _ftrl(ctx, ins, attrs):
    p, g = X(ins, "Param"), X(ins, "Grad")
    sq_acc, lin_acc = X(ins, "SquaredAccumulator"), X(ins, "LinearAccumulator")
    lr = _lr(ins)
    l1 = attrs.get("l1", 0.0)
    l2 = attrs.get("l2", 0.0)
    power = attrs.get("lr_power", -0.5)
    new_sq = sq_acc + jnp.square(g)
    if power == -0.5:
        sigma = (jnp.sqrt(new_sq) - jnp.sqrt(sq_acc)) / lr
    else:
        sigma = (jnp.power(new_sq, -power) - jnp.power(sq_acc, -power)) / lr
    new_lin = lin_acc + g - sigma * p
    if power == -0.5:
        denom = jnp.sqrt(new_sq) / lr + 2 * l2
    else:
        denom = jnp.power(new_sq, -power) / lr + 2 * l2
    pre = jnp.clip(new_lin, -l1, l1) - new_lin
    p_new = pre / denom
    return {"ParamOut": [p_new.astype(p.dtype)],
            "SquaredAccumOut": [new_sq], "LinearAccumOut": [new_lin]}


@register_op("lamb", no_grad=True)
def _lamb(ctx, ins, attrs):
    """ref operators/optimizers/lamb_op.h — LAMB for large-batch BERT."""
    p, g = X(ins, "Param"), X(ins, "Grad")
    m1, m2 = X(ins, "Moment1"), X(ins, "Moment2")
    b1p, b2p = X(ins, "Beta1Pow"), X(ins, "Beta2Pow")
    lr = _lr(ins)
    b1 = attrs.get("beta1", 0.9)
    b2 = attrs.get("beta2", 0.999)
    eps = attrs.get("epsilon", 1e-6)
    wd = attrs.get("weight_decay", 0.01)
    m1n = b1 * m1 + (1 - b1) * g
    m2n = b2 * m2 + (1 - b2) * jnp.square(g)
    mhat = m1n / (1 - b1p.reshape(()))
    vhat = m2n / (1 - b2p.reshape(()))
    r = mhat / (jnp.sqrt(vhat) + eps) + wd * p
    pn = jnp.sqrt(jnp.sum(jnp.square(p.astype(jnp.float32))))
    rn = jnp.sqrt(jnp.sum(jnp.square(r.astype(jnp.float32))))
    trust = jnp.where((pn > 0) & (rn > 0), pn / rn, 1.0)
    p_new = p - lr * trust * r
    return {"ParamOut": [p_new.astype(p.dtype)],
            "Moment1Out": [m1n], "Moment2Out": [m2n],
            "Beta1PowOut": [b1p * b1], "Beta2PowOut": [b2p * b2]}


@register_op("proximal_gd", no_grad=True)
def _proximal_gd(ctx, ins, attrs):
    p, g = X(ins, "Param"), X(ins, "Grad")
    lr = _lr(ins)
    l1 = attrs.get("l1", 0.0)
    l2 = attrs.get("l2", 0.0)
    prox = p - lr * g
    p_new = jnp.sign(prox) * jnp.maximum(jnp.abs(prox) - lr * l1, 0.0) / (1 + lr * l2)
    return {"ParamOut": [p_new.astype(p.dtype)]}


@register_op("proximal_adagrad", no_grad=True)
def _proximal_adagrad(ctx, ins, attrs):
    p, g, mom = X(ins, "Param"), X(ins, "Grad"), X(ins, "Moment")
    lr = _lr(ins)
    l1 = attrs.get("l1", 0.0)
    l2 = attrs.get("l2", 0.0)
    m_new = mom + jnp.square(g)
    eff_lr = lr / jnp.sqrt(m_new)
    prox = p - eff_lr * g
    p_new = jnp.sign(prox) * jnp.maximum(jnp.abs(prox) - eff_lr * l1, 0.0) / (1 + eff_lr * l2)
    return {"ParamOut": [p_new.astype(p.dtype)], "MomentOut": [m_new]}


# -- EMA / model-average support ops ----------------------------------------

@register_op("average_accumulates", no_grad=True)
def _average_accumulates(ctx, ins, attrs):
    param = X(ins, "param")
    in_sum1, in_sum2, in_sum3 = X(ins, "in_sum_1"), X(ins, "in_sum_2"), X(ins, "in_sum_3")
    in_num = X(ins, "in_num_accumulates")
    in_old = X(ins, "in_old_num_accumulates")
    in_upd = X(ins, "in_num_updates")
    avg_window = attrs.get("average_window", 0.15)
    max_avg = attrs.get("max_average_window", 10000)
    min_avg = attrs.get("min_average_window", 10000)
    num = in_num + 1
    upd = in_upd + 1
    sum1 = in_sum1 + param
    window = jnp.maximum(jnp.minimum(avg_window * upd.astype(jnp.float32),
                                     float(max_avg)), float(min_avg))
    roll = num.astype(jnp.float32) >= window
    out_sum2 = jnp.where(roll, in_sum2 + sum1, in_sum2)
    out_sum1 = jnp.where(roll, jnp.zeros_like(sum1), sum1)
    out_old = jnp.where(roll, num, in_old)
    out_num = jnp.where(roll, jnp.zeros_like(num), num)
    big = out_old + out_num > max_avg
    out_sum3 = jnp.where(big, out_sum1 + out_sum2, in_sum3)
    return {"out_sum_1": [out_sum1], "out_sum_2": [out_sum2],
            "out_sum_3": [out_sum3], "out_num_accumulates": [out_num],
            "out_old_num_accumulates": [out_old], "out_num_updates": [upd]}
