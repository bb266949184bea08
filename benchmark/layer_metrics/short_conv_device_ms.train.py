"""Device milliseconds per traced step under the program op ``short_conv``
and its grad op, whatever the role (``pt.fwd/short_conv``,
``pt.bwd/short_conv_grad`` and, under recomputation, ``pt.rc/short_conv``):
the gates and the causal depthwise convolution of a gated short-convolution
operator, forward and backward.  The operator's two projections are ``mul``
ops and not in here.  Nothing to read where the trace holds no such op (a
program without the op, or a commit before it)."""

from .. import op_scopes


def read(inputs):
    return op_scopes.train_ms_of_ops(inputs, ("short_conv",)) or None
