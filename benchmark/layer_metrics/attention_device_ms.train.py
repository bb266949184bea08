"""Device milliseconds per traced step of the attention core, forward and
backward: ``flash_attention`` where the program holds the fused op, else the
ops ``models/transformer.py`` writes it with, the batched ``matmul`` (QK^T
and PV; its dense layers are ``mul`` and ``fused_dense_act``) and
``softmax``.  The dropout on the attention weights is read with the other
dropouts (``dropout_device_ms.train``)."""

from .. import op_scopes


def read(inputs):
    return op_scopes.train_ms_of_ops(
        inputs, ("flash_attention", "matmul", "softmax"))
