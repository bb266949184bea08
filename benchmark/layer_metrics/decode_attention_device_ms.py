"""Device milliseconds per decode iteration ended in the traced window under
``pt.decode/attention``: scores, mask, softmax and the weighted sum over the
gathered context."""

from .. import op_scopes


def read(inputs):
    return op_scopes.decode_ms_of_part(inputs, "attention")
