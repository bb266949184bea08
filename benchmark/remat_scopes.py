"""Device time of a traced window under the instructions XLA rematerialised.

Where a step does not fit, XLA's rematerialisation pass clones an instruction
next to a later use instead of keeping its result, and names the clone after
its original with ``.remat`` behind (``.remat2`` for a second clone; a
compressed copy is ``.remat_compressed`` / ``.remat_uncompressed``; the
compiler's serial number may follow: ``convolution_bitcast_fusion.5.remat2``).
The clone keeps its original's ``op_name``, so ``op_scopes`` reads it under
the program op it came from, ``pt.fwd/<op>`` mostly: this module picks the
clones out by the event's name, with ``op_scopes``' loader and self-time rule,
so its sums are parts of ``op_scopes``' sums.

**A lower bound**: a rematerialised instruction that XLA then fuses into
another fusion runs under that fusion's name, the root's, and is not seen
here.  A file of its own: the benchmark's existing files are not edited.
"""

from __future__ import annotations

import functools
import re
from typing import Dict, Optional, Tuple

from . import op_scopes, trace_reduce

#: the instruction name of a rematerialised clone, as the trace keeps it
_REMAT = re.compile(
    r"\.remat(?:_compressed|_uncompressed)?\d*(?:\.(?:clone|\d+))*$")


def is_remat(name: str) -> bool:
    return bool(_REMAT.search(name))


@functools.lru_cache(maxsize=2)
def reduce_remat(path: str, window: Tuple[int, int]) -> Dict[str, float]:
    """{"role/op type" ("" without a ``pt.`` scope): seconds} of the
    rematerialised device events of an ``.xplane.pb`` inside ``window``
    (profiler nanoseconds), mean over the chips, each instant given to the
    innermost event: a clone inside a loop's body gets its own time and not
    the loop's, and a rematerialised ``while`` its own and not its body's
    (the body's instructions are read by their own names)."""
    per_dev: Dict[str, list] = {}
    for ev in op_scopes.load_scoped_events(path):
        if ev["name"] == trace_reduce.MARK:
            continue
        a = max(ev["start_ns"], window[0])
        b = min(ev["start_ns"] + ev["dur_ns"], window[1])
        if b <= a:
            continue
        key = None
        if is_remat(ev["name"]):
            sc = op_scopes.program_scope(ev.get("scope") or "")
            key = "" if sc is None else f"{sc[0]}/{sc[1]}"
        per_dev.setdefault(ev["plane"], []).append((a, b, key))
    out: Dict[str, float] = {}
    for evs in per_dev.values():
        for key, ns in op_scopes.self_times(evs).items():
            if key is not None:
                out[key] = out.get(key, 0.0) + ns / 1e9 / len(per_dev)
    return out


def seconds_by_op(inputs: dict) -> Optional[Dict[str, float]]:
    """:func:`reduce_remat` of a harness run's traced window (the one
    ``op_scopes.of_run`` reduces); None without a trace that carries scopes,
    {} where nothing was rematerialised."""
    if op_scopes.of_run(inputs) is None:
        return None
    red, win = inputs["trace"], inputs["trace_window"]
    off = red["offset_ns"]
    return reduce_remat(red["path"], (int(win[0] * 1e9 + off),
                                      int(win[1] * 1e9 + off)))
