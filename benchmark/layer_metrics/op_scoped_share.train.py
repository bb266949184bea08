"""Share of the traced window's device time that runs under any ``pt.``
scope of the program (``benchmark/op_scopes.py``): what is left is what XLA
added by itself (asynchronous copies' waits, loop plumbing) or what JAX
hoisted out of a scope.  Nothing to read where no operation carries a scope:
a program from before PR 24, or an executable that a compile cache kept from
one."""

from .. import op_scopes


def read(inputs):
    red = op_scopes.of_run(inputs)
    if red is None or not red["busy_s"]:
        return None
    return 100.0 * sum(red["scoped"].values()) / red["busy_s"]
