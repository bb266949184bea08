"""Device milliseconds per traced step under the program op ``kda_scan`` and
its grad op, whatever the role (``pt.fwd/kda_scan``, ``pt.bwd/kda_scan_grad``
and, under recomputation, ``pt.rc/kda_scan``): the chunked gated delta rule
itself, forward and backward, without the projections, convolutions and gates
round it.  Read by program op: whatever implements the op (jnp that XLA
fuses round one ``lax.scan``, or a kernel) is under the same name.  Nothing
to read where the trace holds no such op."""

from .. import op_scopes


def read(inputs):
    return op_scopes.train_ms_of_ops(inputs, ("kda_scan",)) or None
