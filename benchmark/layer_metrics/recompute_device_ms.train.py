"""Device milliseconds per traced step under the ``pt.rc/*`` scopes: the
forward ops ``framework/recompute.py:apply_recompute`` emitted a second time
behind the checkpoints (and the barriers that feed them), which the executor
names by a role of their own since PR 36 — until then they read ``pt.fwd/*``
and ``fwd_device_ms.train`` held both forwards.  The program's own
recomputation: time, not work.  0.0 where the program recomputes nothing, and
on a tree from before the role (``benchmark/op_scopes.py``; each instant
counted once, mean over the chips)."""

from .. import op_scopes


def read(inputs):
    return op_scopes.train_ms_of_role(inputs, "rc")
