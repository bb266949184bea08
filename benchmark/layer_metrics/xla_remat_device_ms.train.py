"""Device milliseconds per traced step of the instructions XLA rematerialised
to make the step fit: the device events whose instruction name ends in
``.remat`` (``benchmark/remat_scopes.py``; ``op_scopes``' loader, window and
self-time rule).  Whatever role their scope has — a clone keeps its
original's ``op_name``, so this time is part of ``fwd_device_ms.train``
mostly.  A lower bound: a rematerialised instruction inside a fusion runs
under the fusion's root's name.  0.0 where the compiler rematerialised
nothing that the trace shows."""

from .. import remat_scopes


def read(inputs):
    by = remat_scopes.seconds_by_op(inputs)
    steps = inputs["counters"].get("steps_traced")
    if by is None or not steps:
        return None
    return sum(by.values()) / steps * 1e3
