"""paddle_tpu — a TPU-native deep-learning framework with PaddlePaddle
Fluid's capabilities (reference: jhjiangcs/Paddle, see SURVEY.md).

Architecture: a Program/Block/Op IR built by a fluid-style layer DSL;
program-level autodiff (grad-op synthesis); an Executor that lowers whole
blocks into single XLA computations; data/model parallelism via
jax.sharding meshes (GSPMD) instead of NCCL SSA graphs; Pallas kernels for
ops XLA can't fuse (see paddle_tpu.pallas).
"""

from . import ops  # registers all op lowerings
from . import amp, initializer, layers, regularizer  # noqa
from .clip import (GradientClipByGlobalNorm, GradientClipByNorm,  # noqa
                   GradientClipByValue)
from .compiler import BuildStrategy, CompiledProgram, ExecutionStrategy  # noqa
from .framework import (Program, Variable, append_backward,  # noqa
                        default_main_program, default_startup_program,
                        global_scope, gradients, name_scope, program_guard,
                        scope_guard,
                        Scope)
from .framework.executor import Executor  # noqa
from . import optimizer  # noqa
from . import evaluator, metrics, nets  # noqa
from . import contrib  # noqa
from . import incubate  # noqa
from . import average, checkpoint, debugger, install_check, net_drawer  # noqa
from . import flags  # noqa  (FLAGS_* env bootstrap runs at import)
from .flags import get_flags, set_flags  # noqa
from .average import WeightedAverage  # noqa
from . import device_worker, trainer_desc, trainer_factory  # noqa
from . import dygraph  # noqa
from . import io  # noqa
from . import memory  # noqa
from . import native  # noqa
from . import monitor  # noqa  (metrics registry + step tracer)
from . import hbm  # noqa  (runtime HBM accountant + OOM forensics)
from . import resilience  # noqa  (fault injection, retries, preemption)
from . import analysis  # noqa  (program verifier: static checks at optimize time)
from . import serving  # noqa  (multi-tenant continuous-batching server)
from . import profiler  # noqa
from . import data  # noqa
from .data import DataFeeder, DataLoader, PyReader  # noqa
from .data_feed_desc import DataFeedDesc  # noqa
from .async_executor import AsyncExecutor  # noqa
from .data.slot_dataset import DatasetFactory  # noqa
from .io import (load_inference_model, load_params, load_persistables,  # noqa
                 load_vars, save_inference_model, save_params,
                 save_persistables, save_vars)
from .param_attr import ParamAttr, WeightNormParamAttr  # noqa


class CPUPlace:
    """ref platform/place.h:37 CPUPlace."""
    def __repr__(self):
        return "CPUPlace"


class TPUPlace:
    """The TPU analog of CUDAPlace (ref platform/place.h:26): device ordinal
    within jax.devices()."""

    def __init__(self, device_id=0):
        self.device_id = device_id

    def __repr__(self):
        return f"TPUPlace({self.device_id})"


# Fluid API compat alias: CUDAPlace(n) maps to the n-th accelerator.
CUDAPlace = TPUPlace


def device_count():
    import jax
    return len(jax.devices())


def is_compiled_with_cuda():
    return False


def is_compiled_with_tpu():
    import jax
    from .device import is_tpu
    return any(is_tpu(d) for d in jax.devices())


__version__ = "0.3.0"
