"""Device milliseconds per traced step under the ``router`` scope of
``moe_ffn`` and its grad op: the float32 ``[S, d] x [d, E]`` product at full
precision, the scores, the top-k and the expert counts, forward, and in the
backward the same again for its vjp and the two transposed products.  The
part a router placed before attention moved: where it has an input of its own
(``RouterX``), its cotangent leaves the op apart from the experts'.  Nothing
to read where the trace holds no such scope."""

from .. import part_scopes


def read(inputs):
    parts = part_scopes.moe_seconds(inputs)
    steps = inputs["counters"].get("steps_traced")
    if not parts or not steps or not parts.get("router"):
        return None
    return parts["router"] / steps * 1e3
