"""Optimizers: append_backward + per-param optimize ops.

ref ``python/paddle/fluid/optimizer.py:50`` — base ``Optimizer`` creates
accumulators (startup-program-initialized persistables), appends one optimize
op per parameter, and ``minimize`` = append_backward → (regularize, clip) →
apply_gradients.  All 12 reference optimizers are here (SGD:631 Momentum:701
LarsMomentum:1068 Adagrad:1168 Adam:1271 Adamax:1452 DecayedAdagrad:1606
Adadelta:1698 RMSProp:1796 Ftrl:1969 Lamb:2113 + wrappers ExponentialMovingAverage:2457,
ModelAverage:2267).  The whole update lowers into the same XLA step as the
grads, so "fused optimizer" (ref fuse_all_optimizer_ops pass) is automatic.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from .framework import unique_name
from .framework.backward import append_backward
from .framework.core import (Program, Variable, default_main_program,
                             default_startup_program, program_guard)
from .initializer import ConstantInitializer
from .layer_helper import LayerHelper
from .clip import append_gradient_clip_ops, error_clip_callback
from .regularizer import append_regularization_ops


class Optimizer:
    def __init__(self, learning_rate, regularization=None, name=None,
                 grad_clip=None, parameter_list=None):
        self._learning_rate = learning_rate
        self.regularization = regularization
        self._grad_clip = grad_clip
        self._name = name
        self._parameter_list = parameter_list  # dygraph mode (VarBases)
        self._accumulators: Dict[str, Dict[str, Variable]] = {}
        self._learning_rate_var: Optional[Variable] = None
        self.helper: Optional[LayerHelper] = None

    # -- learning rate -------------------------------------------------------
    def _create_global_learning_rate(self):
        if isinstance(self._learning_rate, Variable):
            self._learning_rate_var = self._learning_rate
            return
        if self._learning_rate_var is not None:
            return
        from .layers.tensor import create_global_var
        self._learning_rate_var = create_global_var(
            shape=[1], value=float(self._learning_rate), dtype="float32",
            persistable=True,
            name=unique_name.generate("learning_rate"))

    def _global_learning_rate(self):
        return self._learning_rate_var

    @property
    def learning_rate(self):
        return self._learning_rate

    # -- accumulators --------------------------------------------------------
    def _add_accumulator(self, name, param, dtype=None, fill_value=0.0,
                         shape=None):
        if name in self._accumulators and \
                param.name in self._accumulators[name]:
            return self._accumulators[name][param.name]
        from .dygraph import base as _dy_base
        if _dy_base.in_dygraph_mode():
            from .dygraph.tracer import VarBase as _VB
            shp = list(shape if shape is not None else param.shape)
            acc = _VB(np.full(shp, fill_value,
                              np.dtype(dtype or param.dtype)),
                      name=f"{param.name}_{name}", persistable=True,
                      trainable=False)
            acc.stop_gradient = True
            self._accumulators.setdefault(name, {})[param.name] = acc
            return acc
        block = default_main_program().global_block()
        shape = list(shape if shape is not None else param.shape)
        var = block.create_var(
            name=unique_name.generate(f"{param.name}_{name}"),
            shape=shape, dtype=dtype or param.dtype, persistable=True,
            stop_gradient=True)
        # accumulators shard like their parameter — resolved LAZILY at
        # sharding-build time (compiler.var_shard) so TP annotations applied
        # after minimize() still propagate
        var.shard_like = param.name
        sb = default_startup_program().global_block()
        sb.create_var(name=var.name, shape=shape, dtype=var.dtype,
                      persistable=True)
        sb.append_op("fill_constant", outputs={"Out": [var.name]},
                     attrs={"shape": shape, "dtype": var.dtype,
                            "value": float(fill_value)})
        self._accumulators.setdefault(name, {})[param.name] = var
        return var

    def _get_accumulator(self, name, param):
        return self._accumulators[name][param.name]

    # -- hooks ---------------------------------------------------------------
    def _create_accumulators(self, block, parameters):
        pass

    def _append_optimize_op(self, block, param_and_grad):
        raise NotImplementedError

    def _finish_update(self, block, parameters_and_grads):
        pass

    # -- public API ----------------------------------------------------------
    def backward(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None, callbacks=None):
        return append_backward(loss, parameter_list, no_grad_set)

    def apply_gradients(self, params_grads):
        block = default_main_program().global_block()
        # clip/regularization/LR-decay/update ops are all training-only:
        # tag them so clone(for_test=True) prunes the optimize tail
        # (ref OpRole::kOptimize / _optimized_guard)
        with block.program._op_role_guard("optimize"):
            params_grads = append_gradient_clip_ops(params_grads,
                                                    self._grad_clip)
            params_grads = append_regularization_ops(params_grads,
                                                     self.regularization)
            self._create_global_learning_rate()
            self._create_accumulators(block, [p for p, _ in params_grads])
            for pg in params_grads:
                self._append_optimize_op(block, pg)
            self._finish_update(block, params_grads)
        return []

    def apply_optimize(self, loss, startup_program, params_grads):
        return self.apply_gradients(params_grads)

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None, grad_clip=None):
        if grad_clip is not None:
            self._grad_clip = grad_clip
        from .dygraph import base as _dy_base
        if _dy_base.in_dygraph_mode():
            return self._dygraph_minimize(loss, parameter_list)
        params_grads = self.backward(loss, startup_program, parameter_list,
                                     no_grad_set)
        optimize_ops = self.apply_gradients(params_grads)
        return optimize_ops, params_grads

    # -- dygraph (eager) path ------------------------------------------------
    # The reference shares Optimizer between static and dygraph (the tracer
    # executes the same optimize ops, imperative/tracer.cc).  We do the same:
    # _append_optimize_op runs against an eager block shim that executes the
    # op's registered lowering on the VarBase values immediately.

    def _dygraph_lr_value(self) -> float:
        lr = self._learning_rate
        if callable(lr) and not isinstance(lr, (int, float)):
            lr = lr()  # dygraph LearningRateDecay
        if hasattr(lr, "numpy"):
            lr = float(np.asarray(lr.numpy()).reshape(-1)[0])
        return float(lr)

    def _dygraph_minimize(self, loss, parameter_list=None):
        from .dygraph.eager_apply import EagerBlock, eager_clip_grads
        params = parameter_list if parameter_list is not None \
            else self._parameter_list
        if params is None:
            raise ValueError(
                "dygraph minimize needs parameter_list (pass it to minimize "
                "or the optimizer constructor)")
        if loss is not None and getattr(loss, "grad", None) is None and \
                all(p.grad is None for p in params):
            loss.backward()
        params_grads = [(p, p.grad) for p in params
                        if p.grad is not None and p.trainable]
        params_grads = eager_clip_grads(params_grads, self._grad_clip)
        # regularization as grad += coeff * d(penalty)/d(param); per-param
        # regularizer takes precedence over the global one, matching
        # append_regularization_ops (regularizer.py:62)
        from .regularizer import L2DecayRegularizer
        new_pg = []
        for p, g in params_grads:
            reg = getattr(p, "regularizer", None) or self.regularization
            if reg is not None:
                coeff = getattr(reg, "_coeff", 0.0)
                if coeff:
                    if isinstance(reg, L2DecayRegularizer):
                        g = g + coeff * p.value
                    else:
                        g = g + coeff * np.sign(np.asarray(p.value))
            new_pg.append((p, g))
        params_grads = new_pg
        block = EagerBlock(self._dygraph_lr_value())
        self._eager_block = block
        self._create_accumulators(block, [p for p, _ in params_grads])
        for pg in params_grads:
            self._append_optimize_op(block, pg)
        self._finish_update(block, params_grads)
        self._eager_block = None
        return [], params_grads


class SGDOptimizer(Optimizer):
    """ref optimizer.py:631."""

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        block.append_op("sgd",
                        inputs={"Param": [p], "Grad": [g],
                                "LearningRate": [self._global_learning_rate()]},
                        outputs={"ParamOut": [p]})


class MomentumOptimizer(Optimizer):
    """ref optimizer.py:701."""

    def __init__(self, learning_rate, momentum, use_nesterov=False, **kw):
        super().__init__(learning_rate, **kw)
        self._momentum = momentum
        self._use_nesterov = use_nesterov

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("velocity", p)

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        v = self._get_accumulator("velocity", p)
        block.append_op("momentum",
                        inputs={"Param": [p], "Grad": [g], "Velocity": [v],
                                "LearningRate": [self._global_learning_rate()]},
                        outputs={"ParamOut": [p], "VelocityOut": [v]},
                        attrs={"mu": self._momentum,
                               "use_nesterov": self._use_nesterov})


class LarsMomentumOptimizer(Optimizer):
    """ref optimizer.py:1068."""

    def __init__(self, learning_rate, momentum, lars_coeff=1e-3,
                 lars_weight_decay=5e-4, **kw):
        super().__init__(learning_rate, **kw)
        self._momentum = momentum
        self._lars_coeff = lars_coeff
        self._lars_weight_decay = lars_weight_decay

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("velocity", p)

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        v = self._get_accumulator("velocity", p)
        block.append_op("lars_momentum",
                        inputs={"Param": [p], "Grad": [g], "Velocity": [v],
                                "LearningRate": [self._global_learning_rate()]},
                        outputs={"ParamOut": [p], "VelocityOut": [v]},
                        attrs={"mu": self._momentum,
                               "lars_coeff": self._lars_coeff,
                               "lars_weight_decay": self._lars_weight_decay})


class AdamOptimizer(Optimizer):
    """ref optimizer.py:1271."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, lazy_mode=False, **kw):
        super().__init__(learning_rate, **kw)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment1", p)
            self._add_accumulator("moment2", p)
            self._add_accumulator("beta1_pow_acc", p,
                                  fill_value=self._beta1, shape=[1])
            self._add_accumulator("beta2_pow_acc", p,
                                  fill_value=self._beta2, shape=[1])

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        block.append_op(
            "adam",
            inputs={"Param": [p], "Grad": [g],
                    "LearningRate": [self._global_learning_rate()],
                    "Moment1": [self._get_accumulator("moment1", p)],
                    "Moment2": [self._get_accumulator("moment2", p)],
                    "Beta1Pow": [self._get_accumulator("beta1_pow_acc", p)],
                    "Beta2Pow": [self._get_accumulator("beta2_pow_acc", p)]},
            outputs={"ParamOut": [p],
                     "Moment1Out": [self._get_accumulator("moment1", p)],
                     "Moment2Out": [self._get_accumulator("moment2", p)],
                     "Beta1PowOut": [self._get_accumulator("beta1_pow_acc", p)],
                     "Beta2PowOut": [self._get_accumulator("beta2_pow_acc", p)]},
            attrs={"beta1": self._beta1, "beta2": self._beta2,
                   "epsilon": self._epsilon})


class AdamWOptimizer(AdamOptimizer):
    """Decoupled weight decay (TPU-era addition; ref lamb weight_decay)."""

    def __init__(self, learning_rate=0.001, weight_decay=0.01, **kw):
        super().__init__(learning_rate, **kw)
        self._coeff = weight_decay

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        block.append_op(
            "adamw",
            inputs={"Param": [p], "Grad": [g],
                    "LearningRate": [self._global_learning_rate()],
                    "Moment1": [self._get_accumulator("moment1", p)],
                    "Moment2": [self._get_accumulator("moment2", p)],
                    "Beta1Pow": [self._get_accumulator("beta1_pow_acc", p)],
                    "Beta2Pow": [self._get_accumulator("beta2_pow_acc", p)]},
            outputs={"ParamOut": [p],
                     "Moment1Out": [self._get_accumulator("moment1", p)],
                     "Moment2Out": [self._get_accumulator("moment2", p)],
                     "Beta1PowOut": [self._get_accumulator("beta1_pow_acc", p)],
                     "Beta2PowOut": [self._get_accumulator("beta2_pow_acc", p)]},
            attrs={"beta1": self._beta1, "beta2": self._beta2,
                   "epsilon": self._epsilon, "coeff": self._coeff})


class AdamaxOptimizer(Optimizer):
    """ref optimizer.py:1452."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, **kw):
        super().__init__(learning_rate, **kw)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment", p)
            self._add_accumulator("inf_norm", p)
            self._add_accumulator("beta1_pow_acc", p, fill_value=self._beta1,
                                  shape=[1])

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        block.append_op(
            "adamax",
            inputs={"Param": [p], "Grad": [g],
                    "LearningRate": [self._global_learning_rate()],
                    "Moment": [self._get_accumulator("moment", p)],
                    "InfNorm": [self._get_accumulator("inf_norm", p)],
                    "Beta1Pow": [self._get_accumulator("beta1_pow_acc", p)]},
            outputs={"ParamOut": [p],
                     "MomentOut": [self._get_accumulator("moment", p)],
                     "InfNormOut": [self._get_accumulator("inf_norm", p)]},
            attrs={"beta1": self._beta1, "beta2": self._beta2,
                   "epsilon": self._epsilon})

    def _finish_update(self, block, parameters_and_grads):
        for p, _ in parameters_and_grads:
            b1 = self._get_accumulator("beta1_pow_acc", p)
            block.append_op("scale", inputs={"X": [b1]},
                            outputs={"Out": [b1]},
                            attrs={"scale": self._beta1})


class AdagradOptimizer(Optimizer):
    """ref optimizer.py:1168."""

    def __init__(self, learning_rate, epsilon=1e-6,
                 initial_accumulator_value=0.0, **kw):
        super().__init__(learning_rate, **kw)
        self._epsilon = epsilon
        self._init_acc = initial_accumulator_value

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment", p, fill_value=self._init_acc)

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        m = self._get_accumulator("moment", p)
        block.append_op("adagrad",
                        inputs={"Param": [p], "Grad": [g], "Moment": [m],
                                "LearningRate": [self._global_learning_rate()]},
                        outputs={"ParamOut": [p], "MomentOut": [m]},
                        attrs={"epsilon": self._epsilon})


class DecayedAdagradOptimizer(Optimizer):
    """ref optimizer.py:1606."""

    def __init__(self, learning_rate, decay=0.95, epsilon=1e-6, **kw):
        super().__init__(learning_rate, **kw)
        self._decay, self._epsilon = decay, epsilon

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment", p)

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        m = self._get_accumulator("moment", p)
        block.append_op("decayed_adagrad",
                        inputs={"Param": [p], "Grad": [g], "Moment": [m],
                                "LearningRate": [self._global_learning_rate()]},
                        outputs={"ParamOut": [p], "MomentOut": [m]},
                        attrs={"decay": self._decay, "epsilon": self._epsilon})


class AdadeltaOptimizer(Optimizer):
    """ref optimizer.py:1698."""

    def __init__(self, learning_rate, epsilon=1e-6, rho=0.95, **kw):
        super().__init__(learning_rate, **kw)
        self._epsilon, self._rho = epsilon, rho

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("_avg_squared_grad", p)
            self._add_accumulator("_avg_squared_update", p)

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        sq = self._get_accumulator("_avg_squared_grad", p)
        up = self._get_accumulator("_avg_squared_update", p)
        block.append_op("adadelta",
                        inputs={"Param": [p], "Grad": [g],
                                "AvgSquaredGrad": [sq],
                                "AvgSquaredUpdate": [up]},
                        outputs={"ParamOut": [p], "AvgSquaredGradOut": [sq],
                                 "AvgSquaredUpdateOut": [up]},
                        attrs={"epsilon": self._epsilon, "rho": self._rho})


class RMSPropOptimizer(Optimizer):
    """ref optimizer.py:1796."""

    def __init__(self, learning_rate, rho=0.95, epsilon=1e-6, momentum=0.0,
                 centered=False, **kw):
        super().__init__(learning_rate, **kw)
        self._rho, self._epsilon = rho, epsilon
        self._momentum, self._centered = momentum, centered

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("momentum", p)
            self._add_accumulator("mean_square", p)
            self._add_accumulator("mean_grad", p)

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        block.append_op(
            "rmsprop",
            inputs={"Param": [p], "Grad": [g],
                    "Moment": [self._get_accumulator("momentum", p)],
                    "MeanSquare": [self._get_accumulator("mean_square", p)],
                    "MeanGrad": [self._get_accumulator("mean_grad", p)],
                    "LearningRate": [self._global_learning_rate()]},
            outputs={"ParamOut": [p],
                     "MomentOut": [self._get_accumulator("momentum", p)],
                     "MeanSquareOut": [self._get_accumulator("mean_square", p)],
                     "MeanGradOut": [self._get_accumulator("mean_grad", p)]},
            attrs={"decay": self._rho, "epsilon": self._epsilon,
                   "momentum": self._momentum, "centered": self._centered})


class FtrlOptimizer(Optimizer):
    """ref optimizer.py:1969."""

    def __init__(self, learning_rate, l1=0.0, l2=0.0, lr_power=-0.5, **kw):
        super().__init__(learning_rate, **kw)
        self._l1, self._l2, self._lr_power = l1, l2, lr_power

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("squared", p)
            self._add_accumulator("linear", p)

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        block.append_op(
            "ftrl",
            inputs={"Param": [p], "Grad": [g],
                    "SquaredAccumulator": [self._get_accumulator("squared", p)],
                    "LinearAccumulator": [self._get_accumulator("linear", p)],
                    "LearningRate": [self._global_learning_rate()]},
            outputs={"ParamOut": [p],
                     "SquaredAccumOut": [self._get_accumulator("squared", p)],
                     "LinearAccumOut": [self._get_accumulator("linear", p)]},
            attrs={"l1": self._l1, "l2": self._l2, "lr_power": self._lr_power})


class LambOptimizer(AdamOptimizer):
    """ref optimizer.py:2113 — layer-adaptive large-batch optimizer."""

    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01,
                 beta1=0.9, beta2=0.999, epsilon=1e-6,
                 exclude_from_weight_decay_fn=None, **kw):
        super().__init__(learning_rate, beta1=beta1, beta2=beta2,
                         epsilon=epsilon, **kw)
        self._weight_decay = lamb_weight_decay
        self._exclude_fn = exclude_from_weight_decay_fn

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        wd = self._weight_decay
        if self._exclude_fn is not None and self._exclude_fn(p):
            wd = 0.0
        block.append_op(
            "lamb",
            inputs={"Param": [p], "Grad": [g],
                    "LearningRate": [self._global_learning_rate()],
                    "Moment1": [self._get_accumulator("moment1", p)],
                    "Moment2": [self._get_accumulator("moment2", p)],
                    "Beta1Pow": [self._get_accumulator("beta1_pow_acc", p)],
                    "Beta2Pow": [self._get_accumulator("beta2_pow_acc", p)]},
            outputs={"ParamOut": [p],
                     "Moment1Out": [self._get_accumulator("moment1", p)],
                     "Moment2Out": [self._get_accumulator("moment2", p)],
                     "Beta1PowOut": [self._get_accumulator("beta1_pow_acc", p)],
                     "Beta2PowOut": [self._get_accumulator("beta2_pow_acc", p)]},
            attrs={"beta1": self._beta1, "beta2": self._beta2,
                   "epsilon": self._epsilon, "weight_decay": wd})


class DGCMomentumOptimizer(MomentumOptimizer):
    """ref optimizer.py:809 — deep gradient compression.  Single-process
    semantics equal Momentum; under ``parallel.dgc.DGCGradAllReduce`` the
    tagged momentum ops are rewritten into dgc_allreduce (top-k sparse
    sync with momentum correction) + dgc_momentum."""

    def __init__(self, learning_rate, momentum, rampup_begin_step=0,
                 rampup_step=1, sparsity=(0.999,),
                 local_grad_clip_norm=None, **kw):
        super().__init__(learning_rate, momentum, **kw)
        self._rampup_begin_step = rampup_begin_step
        self._sparsity = sparsity
        self._local_grad_clip_norm = local_grad_clip_norm

    def _append_optimize_op(self, block, param_and_grad):
        super()._append_optimize_op(block, param_and_grad)
        if not hasattr(block, "ops"):
            return  # dygraph EagerBlock: eager DGC degrades to momentum
        op = block.ops[-1]
        op.attrs["dgc"] = True
        op.attrs["rampup_begin_step"] = self._rampup_begin_step
        op.attrs["sparsity"] = float(self._sparsity[-1]) \
            if isinstance(self._sparsity, (list, tuple)) else \
            float(self._sparsity)
        if self._local_grad_clip_norm is not None:
            op.attrs["local_grad_clip_norm"] = \
                float(self._local_grad_clip_norm)


class ExponentialMovingAverage:
    """ref optimizer.py:2457 — EMA shadow params + apply/restore guards."""

    def __init__(self, decay=0.999, thres_steps=None, name=None):
        self._decay = decay
        self._name = name or ""
        self._shadows: Dict[str, Variable] = {}
        self._backups: Dict[str, Variable] = {}

    def update(self):
        block = default_main_program().global_block()
        sb = default_startup_program().global_block()
        for p in block.all_parameters():
            if not p.trainable:
                continue
            sname = f"{self._name}{p.name}.ema"
            shadow = block.create_var(name=sname, shape=p.shape,
                                      dtype=p.dtype, persistable=True,
                                      stop_gradient=True)
            sb.create_var(name=sname, shape=list(p.shape), dtype=p.dtype,
                          persistable=True)
            sb.append_op("fill_constant", outputs={"Out": [sname]},
                         attrs={"shape": list(p.shape), "dtype": p.dtype,
                                "value": 0.0})
            self._shadows[p.name] = shadow
            # shadow = decay*shadow + (1-decay)*param
            tmp = block.create_var(
                name=unique_name.generate(sname + ".tmp"), shape=p.shape,
                dtype=p.dtype, stop_gradient=True)
            block.append_op("scale", inputs={"X": [shadow]},
                            outputs={"Out": [tmp]},
                            attrs={"scale": self._decay})
            tmp2 = block.create_var(
                name=unique_name.generate(sname + ".tmp2"), shape=p.shape,
                dtype=p.dtype, stop_gradient=True)
            block.append_op("scale", inputs={"X": [p]},
                            outputs={"Out": [tmp2]},
                            attrs={"scale": 1.0 - self._decay})
            block.append_op("elementwise_add",
                            inputs={"X": [tmp], "Y": [tmp2]},
                            outputs={"Out": [shadow]})

    def apply(self, executor=None, need_restore=True):
        import contextlib

        @contextlib.contextmanager
        def guard():
            from .framework.scope import global_scope
            scope = global_scope()
            backups = {}
            for pname, shadow in self._shadows.items():
                backups[pname] = scope.find_var(pname)
                sval = scope.find_var(shadow.name)
                if sval is not None:
                    scope.set_var(pname, sval)
            try:
                yield
            finally:
                if need_restore:
                    for pname, v in backups.items():
                        scope.set_var(pname, v)
        return guard()

    def restore(self, executor=None):
        pass


class ModelAverage(Optimizer):
    """ref optimizer.py:2267 — running average of params over a window."""

    def __init__(self, average_window_rate, min_average_window=10000,
                 max_average_window=10000, **kw):
        super().__init__(0.0, **kw)
        self.average_window = average_window_rate
        self.min_average_window = min_average_window
        self.max_average_window = max_average_window
        self.params_grads: List[Tuple[Variable, Variable]] = []
        block = default_main_program().global_block()
        for p in block.all_parameters():
            if p.trainable:
                self._append_average_accumulate_op(p)

    def _append_average_accumulate_op(self, param):
        block = default_main_program().global_block()
        sum1 = self._add_accumulator("sum_1", param)
        sum2 = self._add_accumulator("sum_2", param)
        sum3 = self._add_accumulator("sum_3", param)
        num_acc = self._add_accumulator("num_accumulates", param,
                                        dtype="int64", shape=[1])
        old_num = self._add_accumulator("old_num_accumulates", param,
                                        dtype="int64", shape=[1])
        num_upd = self._add_accumulator("num_updates", param,
                                        dtype="int64", shape=[1])
        block.append_op(
            "average_accumulates",
            inputs={"param": [param], "in_sum_1": [sum1], "in_sum_2": [sum2],
                    "in_sum_3": [sum3], "in_num_accumulates": [num_acc],
                    "in_old_num_accumulates": [old_num],
                    "in_num_updates": [num_upd]},
            outputs={"out_sum_1": [sum1], "out_sum_2": [sum2],
                     "out_sum_3": [sum3], "out_num_accumulates": [num_acc],
                     "out_old_num_accumulates": [old_num],
                     "out_num_updates": [num_upd]},
            attrs={"average_window": self.average_window,
                   "min_average_window": self.min_average_window,
                   "max_average_window": self.max_average_window})

    def apply(self, executor=None, need_restore=True):
        import contextlib

        @contextlib.contextmanager
        def guard():
            from .framework.scope import global_scope
            scope = global_scope()
            backups = {}
            for pname in list(self._accumulators.get("sum_1", {})):
                s1 = scope.find_var(self._accumulators["sum_1"][pname].name)
                s2 = scope.find_var(self._accumulators["sum_2"][pname].name)
                s3 = scope.find_var(self._accumulators["sum_3"][pname].name)
                na = scope.find_var(self._accumulators["num_accumulates"][pname].name)
                on = scope.find_var(self._accumulators["old_num_accumulates"][pname].name)
                if s1 is None:
                    continue
                total = (np.asarray(s1) + np.asarray(s2) + np.asarray(s3))
                cnt = float(np.asarray(na).item() + np.asarray(on).item())
                backups[pname] = scope.find_var(pname)
                if cnt > 0:
                    scope.set_var(pname, total / cnt)
            try:
                yield
            finally:
                if need_restore:
                    for pname, v in backups.items():
                        scope.set_var(pname, v)
        return guard()

    def restore(self, executor=None):
        pass


# fluid-style aliases
SGD = SGDOptimizer
Momentum = MomentumOptimizer
Adagrad = AdagradOptimizer
Adam = AdamOptimizer
AdamW = AdamWOptimizer
Adamax = AdamaxOptimizer
DecayedAdagrad = DecayedAdagradOptimizer
Adadelta = AdadeltaOptimizer
RMSProp = RMSPropOptimizer
Ftrl = FtrlOptimizer
Lamb = LambOptimizer
LarsMomentum = LarsMomentumOptimizer
DGCMomentum = DGCMomentumOptimizer


def __getattr__(name):
    # PipelineOptimizer lives in parallel.pipeline (lazy: avoids a circular
    # import, since pipeline pulls in the executor machinery)
    if name == "PipelineOptimizer":
        from .parallel.pipeline import PipelineOptimizer
        return PipelineOptimizer
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


class RecomputeOptimizer:
    """Activation-rematerialization wrapper (TPU-native; the 2019 reference
    has no recompute — see framework/recompute.py).  Usage mirrors the
    modern fluid API:

        opt = optimizer.RecomputeOptimizer(Adam(1e-4))
        opt._set_checkpoints([layer_out_1, layer_out_2, ...])
        opt.minimize(loss)
    """

    def __init__(self, optimizer):
        self._optimizer = optimizer
        self._checkpoints = []
        self._after_gradient = False

    def _set_checkpoints(self, checkpoints, after_gradient=False):
        """``after_gradient``: each segment is emitted behind the gradient
        of the checkpoint that ends it and fenced together with it
        (``framework.recompute.apply_recompute``): the compiler then cannot
        run the segments again side by side."""
        self._checkpoints = [
            c.name if hasattr(c, "name") else c for c in checkpoints]
        self._after_gradient = bool(after_gradient)

    def __getattr__(self, name):
        return getattr(self._optimizer, name)

    def _apply(self, program):
        # idempotent: minimize() delegates to the inner optimizer whose
        # backward() may already have routed through this wrapper
        if self._checkpoints and not program._attrs.get("__recompute__"):
            from .framework.recompute import apply_recompute
            apply_recompute(program, self._checkpoints,
                            **({"after_gradient": True}
                               if self._after_gradient else {}))
            program._attrs["__recompute__"] = True

    def backward(self, loss, **kw):
        """fluid's documented recompute entry point: backward() builds the
        grad ops, then the program is rewritten for rematerialization."""
        result = self._optimizer.backward(loss, **kw)
        self._apply(loss.block.program)
        return result

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None, grad_clip=None):
        result = self._optimizer.minimize(loss, startup_program,
                                          parameter_list, no_grad_set,
                                          grad_clip=grad_clip)
        self._apply(loss.block.program)
        return result


class LookaheadOptimizer:
    """Lookahead (arXiv:1907.08610; ref ``optimizer.py:2980``): the inner
    optimizer moves the fast weights every step; every k-th step the slow
    weights move toward the fast ones by ``alpha`` and the fast weights
    reset to them.

    TPU-native shape: the reference wraps the sync in a Switch over
    ``step % k`` (dynamic control flow); here the blend runs every step
    under a 0/1 mask — a handful of fused elementwise ops per parameter,
    branch-free under XLA, identical math.
    """

    def __init__(self, inner_optimizer, alpha=0.5, k=5):
        assert inner_optimizer is not None, "inner optimizer can not be None"
        assert 0.0 <= alpha <= 1.0, "alpha must be in [0, 1]"
        assert isinstance(k, int) and k > 0, "k must be a positive int"
        self.inner_optimizer = inner_optimizer
        self.alpha = alpha
        self.k = k
        self.type = "lookahead"

    def __getattr__(self, name):
        return getattr(self.inner_optimizer, name)

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None, grad_clip=None):
        from .framework import default_startup_program
        result = self.inner_optimizer.minimize(
            loss, startup_program=startup_program,
            parameter_list=parameter_list, no_grad_set=no_grad_set,
            grad_clip=grad_clip)

        main_block = loss.block
        startup = startup_program or default_startup_program()
        params = [p.name for p in main_block.program.all_parameters()]
        with main_block.program._op_role_guard("optimize"):
            self._append_lookahead_ops(main_block, startup, params)
        return result

    def _append_lookahead_ops(self, main_block, startup, params):
        from . import layers
        # slow copies live alongside the fast params (ref: <name>@SLOW),
        # initialized to the fast values by the startup program
        for name in params:
            fast = main_block.var(name)
            main_block.create_var(name=name + "@SLOW", shape=fast.shape,
                                  dtype=fast.dtype, persistable=True)
            sb = startup.global_block()
            sv = sb.create_var(name=name + "@SLOW", shape=fast.shape,
                               dtype=fast.dtype, persistable=True)
            if not sb.has_var(name):
                # params restored via load_persistables instead of init
                # ops: declare the var so the copy below is well-formed
                # (its value must be in the scope before startup runs)
                sb.create_var(name=name, shape=fast.shape,
                              dtype=fast.dtype, persistable=True)
            sb.append_op("assign", inputs={"X": [name]},
                         outputs={"Out": [sv.name]}, attrs={})

        # int32 counter: a float32 step would freeze at 2^24 and silently
        # stop (or jam on) the sync (ref uses an int32 lookahead_step too)
        step = layers.create_global_var(name="lookahead_step", shape=[1],
                                        value=0, dtype="int32",
                                        persistable=True)
        layers.increment(step, value=1, in_place=True)
        # mask = 1.0 every k-th step else 0.0
        mod = layers.elementwise_mod(step, layers.fill_constant(
            shape=[1], dtype="int32", value=self.k))
        mask = layers.cast(layers.equal(mod, layers.fill_constant(
            shape=[1], dtype="int32", value=0)), "float32")
        for name in params:
            fast = main_block.var(name)
            slow = main_block.var(name + "@SLOW")
            blend = slow + self.alpha * (fast - slow)
            new_slow = mask * blend + (1.0 - mask) * slow
            new_fast = mask * new_slow + (1.0 - mask) * fast
            layers.assign(new_slow, slow)
            layers.assign(new_fast, fast)
