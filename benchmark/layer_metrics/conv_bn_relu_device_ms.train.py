"""Device milliseconds per traced step of the work of ResNet-50's residual
blocks, forward and backward, by the program ops that do it: ``conv2d``,
``batch_norm``, ``batch_norm_explicit`` (what the backward of a
batch-statistics BN lowers through), ``relu``, ``elementwise_add`` (the
residual add: XLA fuses it with the BN before it and the ReLU after it, and a
fusion carries the name of its root, ``relu`` in one compiled step and
``elementwise_add`` in another) and, where the program's default
``conv_bn_relu`` rewrite is on, ``fused_conv1x1_bn``, each with its ``_grad``
op.  A metric of the work, not of one lowering of it: it reads a number with
the rewrite and without it, and the two are of the same work."""

from .. import op_scopes

OPS = ("conv2d", "batch_norm", "batch_norm_explicit", "relu",
       "elementwise_add", "fused_conv1x1_bn")


def read(inputs):
    return op_scopes.train_ms_of_ops(inputs, OPS)
