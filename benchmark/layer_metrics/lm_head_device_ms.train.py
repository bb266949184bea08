"""Device milliseconds per traced step under ``fused_lm_head_ce`` and its
grad: the chunked head projection + cross-entropy, the ``while`` of the
device trace."""

from .. import op_scopes


def read(inputs):
    return op_scopes.train_ms_of_ops(inputs, ("fused_lm_head_ce",))
