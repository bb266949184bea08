"""Device time of a traced window by the parts inside one program op.

``op_scopes.py`` gives each device operation to the program op whose
``pt.<role>/<op type>`` scope it carries.  A lowering may name its own parts
with nested ``jax.named_scope``s (``moe_ffn``: ``router``, ``dispatch``,
``experts``, ``combine``); they follow the op's scope in the HLO op_name,
bare in the forward (``pt.fwd/moe_ffn/experts/...``) and wrapped by the
transformation in a backward made by ``jax.vjp``
(``pt.bwd/moe_ffn_grad/transpose(jvp(experts))/...``).  This module reads
them with ``op_scopes``' own loader and self-time rule, so its sums are parts
of ``op_scopes``' sums.  A file of its own: the benchmark's existing files
are not edited.
"""

from __future__ import annotations

import functools
import re
from typing import Dict, Optional, Sequence, Tuple

from . import op_scopes, trace_reduce


def part_of(rest: str, parts: Sequence[str]) -> str:
    """The first of ``parts`` that is a component of ``rest`` (what follows
    the op's scope in an op_name), bare or inside ``jvp(``/``transpose(``;
    "" where none is."""
    m = re.search(r"(?:^|[/(])(%s)(?=[/)]|$)" % "|".join(map(re.escape, parts)),
                  rest)
    return m.group(1) if m else ""


@functools.lru_cache(maxsize=4)
def reduce_parts(path: str, window: Tuple[int, int], parts: Tuple[str, ...]
                 ) -> Dict[Tuple[str, str, str], float]:
    """{(role, op type, part): seconds} of the device events of an
    ``.xplane.pb`` inside ``window`` (profiler nanoseconds), mean over the
    chips, each instant given to the innermost event."""
    per_dev: Dict[str, list] = {}
    for ev in op_scopes.load_scoped_events(path):
        if ev["name"] == trace_reduce.MARK:
            continue
        a = max(ev["start_ns"], window[0])
        b = min(ev["start_ns"] + ev["dur_ns"], window[1])
        if b <= a:
            continue
        m = op_scopes._SCOPE.search(ev.get("scope") or "")
        key = ("", "", "") if m is None else (
            m.group(1), m.group(2), part_of(ev["scope"][m.end():], parts))
        per_dev.setdefault(ev["plane"], []).append((a, b, key))
    out: Dict[Tuple[str, str, str], float] = {}
    for evs in per_dev.values():
        for key, ns in op_scopes.self_times(evs).items():
            out[key] = out.get(key, 0.0) + ns / 1e9 / len(per_dev)
    return out


def seconds_by_part(inputs: dict, parts: Sequence[str]
                    ) -> Optional[Dict[Tuple[str, str, str], float]]:
    """{(role, op type, part): seconds} of a harness run's traced window,
    mean over the chips; None without a trace that carries scopes."""
    red = inputs.get("trace")
    win = inputs.get("trace_window")
    if op_scopes.of_run(inputs) is None:
        return None
    off = red["offset_ns"]
    return reduce_parts(red["path"], (int(win[0] * 1e9 + off),
                                      int(win[1] * 1e9 + off)), tuple(parts))


MOE_PARTS = ("router", "dispatch", "experts", "combine")


def moe_seconds(inputs: dict) -> Optional[Dict[str, float]]:
    """Seconds of the traced window under ``moe_ffn`` and its grad op, by
    part ("" = under the op but under none of its parts); None where the
    trace holds no such operation."""
    by = seconds_by_part(inputs, MOE_PARTS)
    if by is None:
        return None
    out: Dict[str, float] = {}
    for (_, op, part), s in by.items():
        if op in ("moe_ffn", "moe_ffn_grad"):
            out[part] = out.get(part, 0.0) + s
    return out or None
