"""Device milliseconds per traced step under ``fused_conv1x1_bn`` and its
grad: the Mosaic kernel, its second run inside the grad, and the copies and
layout changes lowered with them."""

from .. import op_scopes


def read(inputs):
    return op_scopes.train_ms_of_ops(inputs, ("fused_conv1x1_bn",))
