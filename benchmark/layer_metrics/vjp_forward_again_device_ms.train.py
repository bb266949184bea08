"""Device milliseconds per traced step of forward work that the generic vjp
lowered again inside a grad op: under a ``pt.bwd/*`` scope, inside ``jvp(``
and outside ``transpose(`` (``op_scopes.reduce_scopes``'s ``forward_again``;
a part of ``bwd_device_ms.train``).  What a grad op of its own over saved
residuals removes (``moe_ffn_grad`` PR 27, ``flash_attention_grad`` PR 33);
0.0 where XLA's dead-code elimination or such a grad op left none."""

from .. import op_scopes


def read(inputs):
    red = op_scopes.of_run(inputs)
    steps = inputs["counters"].get("steps_traced")
    if red is None or not steps:
        return None
    return sum(red["forward_again"].values()) / steps * 1e3
