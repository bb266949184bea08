"""Device milliseconds per traced step under the program ops ``hc_pre`` and
``hc_post`` and their grad ops, whatever the role (``pt.fwd/hc_pre``,
``pt.bwd/hc_post_grad`` and, under recomputation, ``pt.rc/hc_pre``): what a
residual stream several streams wide costs round the sublayers, the
coefficients' projection, Sinkhorn-Knopp and the two mixes of the streams,
forward and backward.  The sublayers themselves, their norms and the sums
that add a stream's two gradients are other ops and not in here.  Nothing to
read where the trace holds no such op (a configuration with a plain residual,
or a commit before the ops)."""

from .. import op_scopes


def read(inputs):
    return op_scopes.train_ms_of_ops(inputs, ("hc_pre", "hc_post")) or None
