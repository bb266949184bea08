"""Device milliseconds per traced step under the program op ``kda_gate`` and
its grad op, whatever the role (``pt.fwd/kda_gate``, ``pt.bwd/kda_gate_grad``
and, under recomputation, ``pt.rc/kda_gate``): the decay's gate of a KDA
layer in either of its forms (``-exp(A_log) softplus(.)``, or the bounded
``lower_bound sigmoid(exp(A_log) .)``) and beta's sigmoid, float32 over
``[tokens, heads, 128]``, without the projection that feeds it.  Read by
program op: whatever implements it is under the same name.  Nothing to read
where the trace holds no such op (a program without the layer, or a commit
before it)."""

from .. import op_scopes


def read(inputs):
    return op_scopes.train_ms_of_ops(inputs, ("kda_gate",)) or None
