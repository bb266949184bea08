"""Device milliseconds per traced step under the ``pt.fwd/*`` scopes: the
forward ops of the optimised program (``benchmark/op_scopes.py``; each
instant counted once, mean over the chips)."""

from .. import op_scopes


def read(inputs):
    return op_scopes.train_ms_of_role(inputs, "fwd")
