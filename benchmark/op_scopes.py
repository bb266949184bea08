"""Device time of a traced window by the program's own names.

The executor lowers every op of a block under ``jax.named_scope("pt.<role>/
<op type>")`` and the decode step its parts under ``pt.decode/<part>``
(PR 24), so each HLO instruction's ``op_name`` starts with the program op it
came from.  On a v5e the profiler keeps that ``op_name`` as the stat ``tf_op``
of the operation's *event metadata* in the device plane (``jit(step)/pt.bwd/
mul_grad/transpose(jvp())/dot_general:``).  ``jax.profiler.ProfileData``
shows an event's own stats only, not its metadata's, so this module reads the
``.xplane.pb`` file's protobuf wire format itself (a few fields of XSpace,
below); ``trace_reduce.py`` and its ProfileData path are left as they are.

A fusion carries the ``op_name`` of its root instruction, so it belongs to
the scope of its root.  Ops of a sub-block nest under their parent op
(``pt.fwd/fused_lm_head_ce/while/body/...``): the first ``pt.`` scope of the
name, the outermost, owns the time.  Under a ``bwd`` scope, what sits inside
``jvp(`` and not inside ``transpose(`` is forward work that the generic vjp
lowered again.

Time is attributed without counting twice: on one chip the ``XLA Ops`` line
is sequential except that a ``while`` (or call) event spans the events of its
body, so each instant belongs to the innermost event that covers it
(:func:`self_times`).  The sums then add up to ``trace_reduce``'s busy time
of the same window, and are means over the chips like it.
"""

from __future__ import annotations

import functools
import re
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from . import trace_reduce

#: ``pt.<role>/<type>`` anywhere in an op_name; the first match is outermost
_SCOPE = re.compile(r"(?:^|/)pt\.([a-z]+)/([A-Za-z0-9_.]+)")
#: the stat of an operation's event metadata that holds the HLO op_name
SCOPE_STAT = "tf_op"


# -- the xplane file, as far as it is needed --------------------------------------
#
# XSpace{planes=1}  XPlane{name=2, lines=3, event_metadata=4, stat_metadata=5}
# (both maps: entry{key=1, value=2})  XLine{name=2, timestamp_ns=3, events=4}
# XEvent{metadata_id=1, offset_ps=2, duration_ps=3}
# XEventMetadata{id=1, name=2, display_name=4, stats=5}
# XStat{metadata_id=1, str_value=5, ref_value=7}  XStatMetadata{id=1, name=2}
# (tsl/profiler/protobuf/xplane.proto)

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        c = buf[i]
        i += 1
        out |= (c & 0x7F) << shift
        if c < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int for a varint, the bytes
    for a length-delimited or fixed field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire == 1:
            val, i = buf[i:i + 8], i + 8
        elif wire == 5:
            val, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"xplane: wire type {wire} is not expected")
        yield key >> 3, val


def _map_entries(entries: Sequence[bytes]) -> Iterator[Tuple[int, bytes]]:
    for e in entries:
        d = dict(_fields(e))
        if 2 in d:
            yield d.get(1, 0), d[2]


def _plane_events(plane: bytes) -> Iterator[dict]:
    parts: Dict[int, List[bytes]] = {}
    for f, v in _fields(plane):
        parts.setdefault(f, []).append(v)
    pname = parts[2][0].decode() if 2 in parts else ""
    if not trace_reduce.is_device_plane(pname):
        return
    stat_names = {}
    for sid, body in _map_entries(parts.get(5, [])):
        stat_names[sid] = dict(_fields(body)).get(2, b"").decode()
    meta = {}
    for mid, body in _map_entries(parts.get(4, [])):
        name = display = scope = ""
        for f, v in _fields(body):
            if f == 2:
                name = v.decode(errors="replace")
            elif f == 4:
                display = v.decode(errors="replace")
            elif f == 5:
                st = dict(_fields(v))
                if stat_names.get(st.get(1)) == SCOPE_STAT:
                    if 5 in st:
                        scope = st[5].decode(errors="replace")
                    elif 7 in st:                 # a reference to a name
                        scope = stat_names.get(st[7], "")
        meta[mid] = (display or trace_reduce.op_class(name), scope)
    for line in parts.get(3, []):
        lname, t_line, events = "", 0, []
        for f, v in _fields(line):
            if f == 2:
                lname = v.decode()
            elif f == 3:
                t_line = v
            elif f == 4:
                events.append(v)
        if lname not in trace_reduce.OP_LINES:
            continue
        for ev in events:
            d = dict(_fields(ev))
            name, scope = meta.get(d.get(1, 0), ("", ""))
            yield {"plane": pname, "line": lname, "name": name,
                   "start_ns": int(t_line) + int(d.get(2, 0)) // 1000,
                   "dur_ns": int(d.get(3, 0)) // 1000, "scope": scope}


@functools.lru_cache(maxsize=2)
def load_scoped_events(path: str) -> Tuple[dict, ...]:
    """The ``XLA Ops`` events of every device plane of an ``.xplane.pb``, as
    ``trace_reduce``'s dicts plus ``scope`` (the HLO op_name, "" where the
    operation has none).  Read once per file."""
    with open(path, "rb") as f:
        data = f.read()
    out: List[dict] = []
    for f_no, plane in _fields(data):
        if f_no == 1:
            out.extend(_plane_events(plane))
    return tuple(out)


# -- the reduction ------------------------------------------------------------------

def program_scope(op_name: str) -> Optional[Tuple[str, str, bool]]:
    """``(role, op type, forward_again)`` of an HLO op_name, None without a
    ``pt.`` scope.  ``forward_again``: under a ``bwd`` scope, inside ``jvp(``
    and outside ``transpose(`` — forward work lowered again by the generic
    vjp."""
    m = _SCOPE.search(op_name or "")
    if m is None:
        return None
    rest = op_name[m.end():]
    again = m.group(1) == "bwd" and "jvp(" in rest \
        and "transpose(" not in rest
    return m.group(1), m.group(2), again


def self_times(events: Sequence[Tuple[int, int, object]]) -> Dict[object, int]:
    """Nanoseconds per key of ``(start, end, key)`` events of one chip, each
    instant given to the innermost (latest started) event that covers it, so
    that a ``while`` is not counted again with its body."""
    out: Dict[object, int] = {}
    stack: List[Tuple[int, object]] = []
    cur = 0
    for s, e, k in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, kk = stack.pop()
            if end > cur:
                out[kk] = out.get(kk, 0) + end - cur
                cur = end
        if stack and s > cur:
            out[stack[-1][1]] = out.get(stack[-1][1], 0) + s - cur
        cur = max(cur, s) if stack else s
        stack.append((e, k))
    while stack:
        end, kk = stack.pop()
        if end > cur:
            out[kk] = out.get(kk, 0) + end - cur
            cur = end
    return out


def reduce_scopes(events: Sequence[dict],
                  window: Optional[Tuple[int, int]] = None) -> dict:
    """Seconds of the events inside ``window`` (profiler nanoseconds; default
    all of them), mean over the chips: ``scoped`` {"role/type": s},
    ``forward_again`` {"bwd/type": s} (a part of ``scoped``), ``unscoped``
    {XLA operation class: s}, and ``busy_s``, their total."""
    per_dev: Dict[str, List[Tuple[int, int, object]]] = {}
    for ev in events:
        if ev["name"] == trace_reduce.MARK or \
                not trace_reduce.is_device_plane(ev["plane"]):
            continue
        a, b = ev["start_ns"], ev["start_ns"] + ev["dur_ns"]
        if window is not None:
            a, b = max(a, int(window[0])), min(b, int(window[1]))
        if b <= a:
            continue
        sc = program_scope(ev.get("scope", ""))
        key = ("", trace_reduce.op_class(ev["name"]), False) \
            if sc is None else sc
        per_dev.setdefault(ev["plane"], []).append((a, b, key))
    red = {"n_devices": len(per_dev), "busy_s": 0.0, "scoped": {},
           "forward_again": {}, "unscoped": {}}
    n = len(per_dev)
    for evs in per_dev.values():
        for (role, typ, again), ns in self_times(evs).items():
            s = ns / 1e9 / n
            red["busy_s"] += s
            if not role:
                red["unscoped"][typ] = red["unscoped"].get(typ, 0.0) + s
                continue
            name = f"{role}/{typ}"
            red["scoped"][name] = red["scoped"].get(name, 0.0) + s
            if again:
                red["forward_again"][name] = \
                    red["forward_again"].get(name, 0.0) + s
    return red


# -- for the readers ------------------------------------------------------------------

@functools.lru_cache(maxsize=2)
def _reduced(path: str, window: Tuple[int, int]) -> dict:
    return reduce_scopes(load_scoped_events(path), window)


def of_run(inputs: dict) -> Optional[dict]:
    """:func:`reduce_scopes` of a harness run's trace over the window between
    the harness's two marks (made once per run, whichever reader asks
    first); None without a trace file or where the program put no scope on
    it (a commit before PR 24, or an executable that a compile cache kept
    from one)."""
    red = inputs.get("trace")
    win = inputs.get("trace_window")
    if not red or not red.get("path") or red.get("offset_ns") is None \
            or not win or win[0] is None or win[1] is None:
        return None
    off = red["offset_ns"]
    out = _reduced(red["path"],
                   (int(win[0] * 1e9 + off), int(win[1] * 1e9 + off)))
    return out if out["scoped"] else None


def device_ms(inputs: dict, units: Optional[float], pick) -> Optional[float]:
    """Milliseconds of device time per unit of work (a step, an iteration)
    under the scopes ``pick(role, op type)`` accepts."""
    red = of_run(inputs)
    if red is None or not units:
        return None
    total = sum(s for name, s in red["scoped"].items()
                if pick(*name.split("/", 1)))
    return total / units * 1e3


def train_ms_of_role(inputs: dict, role: str) -> Optional[float]:
    """Per traced training step, under ``pt.<role>/*``."""
    return device_ms(inputs, inputs["counters"].get("steps_traced"),
                     lambda r, op: r == role)


def train_ms_of_ops(inputs: dict, ops: Sequence[str]) -> Optional[float]:
    """Per traced training step, under the op types ``ops`` and their
    ``_grad`` ops, whatever the role."""
    return device_ms(
        inputs, inputs["counters"].get("steps_traced"),
        lambda r, op: op in ops or
        (op.endswith("_grad") and op[:-len("_grad")] in ops))


def decode_ms_of_part(inputs: dict, part: str) -> Optional[float]:
    """Per decode iteration ended in the traced window, under
    ``pt.decode/<part>``."""
    from .reading import in_trace_window
    return device_ms(inputs, in_trace_window(inputs, "serving.decode_iter"),
                     lambda r, p: r == "decode" and p == part)
