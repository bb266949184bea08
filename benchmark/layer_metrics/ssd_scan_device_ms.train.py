"""Device milliseconds per traced step under the program op ``ssd_scan`` and
its grad op, whatever the role (``pt.fwd/ssd_scan``, ``pt.bwd/ssd_scan_grad``
and, under recomputation, ``pt.rc/ssd_scan``): the chunked state-space scan
itself, forward and backward, without the projections, the convolution and
the gated norm round it.  Read by program op: whatever implements the op (jnp
that XLA fuses round one ``lax.scan``, or a kernel later) is under the same
name.  Nothing to read where the trace holds no such op."""

from .. import op_scopes


def read(inputs):
    return op_scopes.train_ms_of_ops(inputs, ("ssd_scan",)) or None
