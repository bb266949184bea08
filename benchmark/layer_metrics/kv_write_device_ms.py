"""Device milliseconds per decode iteration ended in the traced window under
``pt.decode/kv_write``: this token's K and V written into the donated page
pools, all layers."""

from .. import op_scopes


def read(inputs):
    return op_scopes.decode_ms_of_part(inputs, "kv_write")
