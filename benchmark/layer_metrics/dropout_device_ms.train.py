"""Device milliseconds per traced step under ``dropout`` and
``dropout_grad`` (which regenerates the mask): where the
``rng-bit-generator`` time of the data-parallel cell belongs.  The dropout
folded into ``fused_dense_act`` is read with that op, not here."""

from .. import op_scopes


def read(inputs):
    return op_scopes.train_ms_of_ops(inputs, ("dropout",))
