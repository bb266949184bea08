"""Op lowering registry population — importing this package registers every
op's JAX lowering (the TPU stand-in for the reference's static
REGISTER_OPERATOR initializers)."""

from . import (attention_ops, control_flow_ops, detection_ops,  # noqa
               math_ops, metrics_ops, misc_ops, nn_ops, optimizer_ops,
               quant_ops, reduce_ops, rnn_ops, sequence_ops,
               structured_ops, tensor_ops)
from . import fused_ops  # noqa  (analysis.fusion rewrite targets)
from . import moe_ops  # noqa
from . import hc_ops  # noqa
from . import kda_ops  # noqa
from . import ssd_ops  # noqa
from . import compat_ops  # noqa  (must come last: aliases existing ops)
from ..framework.registry import registered_ops  # noqa
