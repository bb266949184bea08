"""Device milliseconds per traced step under the ``pt.bwd/*`` scopes: the
grad ops, the forward work their generic vjp lowers again included
(``benchmark/op_scopes.py``; each instant counted once, mean over the
chips)."""

from .. import op_scopes


def read(inputs):
    return op_scopes.train_ms_of_role(inputs, "bwd")
