"""Device milliseconds per decode iteration ended in the traced window under
``pt.decode/kv_gather``: every slot's page list gathered into a contiguous
context, all layers."""

from .. import op_scopes


def read(inputs):
    return op_scopes.decode_ms_of_part(inputs, "kv_gather")
