"""Reduction of a JAX profiler trace to the numbers the benchmark reports.

Two steps, so that the arithmetic can be checked on a small recorded trace
(``benchmark/fixtures/*.json``) without a chip:

1. :func:`load_xplane` reads an ``.xplane.pb`` with nothing but
   ``jax.profiler.ProfileData`` and keeps, as plain dicts, the operation
   events of every device plane and the benchmark's own host marks.
2. :func:`reduce_events` takes those dicts and a window and computes, per
   device: the union of the intervals in which an operation ran (busy), the
   idle gaps, seconds per operation name, and the time a collective ran while
   no compute operation did.

Times in events are nanoseconds on the profiler's clock.  The host's
``perf_counter`` clock is tied to it by a mark the harness writes inside the
trace (``bench_mark`` with its ``t_perf``), see :func:`clock_offset_ns`.
"""

from __future__ import annotations

import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

#: lines of a device plane that hold one event per executed operation
OP_LINES = ("XLA Ops",)
#: lines of a device plane that never hold operations
NOT_OP_LINES = ("Steps", "XLA Modules", "XLA TraceMe", "Framework Ops",
                "Framework Name Scope", "Source code", "Launch Stats",
                "Async XLA Ops", "Scalar Unit", "TC Overlay")
MARK = "bench_mark"

_COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|collective-broadcast|ragged-all-to-all)")
_SUFFIX = re.compile(r"[.:_-]?\d+$")

Interval = Tuple[int, int]


def op_class(name: str) -> str:
    """``%fusion.123`` -> ``fusion``: the operation's name without the
    compiler's serial number, so that events of one kind sum together."""
    n = name.strip().lstrip("%")
    n = n.split(" ", 1)[0].split("=", 1)[0]
    prev = None
    while prev != n:
        prev = n
        n = _SUFFIX.sub("", n)
    return n or name


def is_collective(name: str) -> bool:
    return bool(_COLLECTIVE.match(name.strip().lstrip("%")))


_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")


def is_device_plane(name: str) -> bool:
    """``/device:TPU:0`` and its like; not the host, not ``/device:CUSTOM``."""
    return bool(_DEVICE_PLANE.match(name))


def load_xplane(path: str) -> List[dict]:
    """Operation events of the device planes and the ``bench_mark`` events of
    the host, as ``{"plane", "line", "name", "start_ns", "dur_ns"[, "t_perf"]}``
    dicts."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    out: List[dict] = []
    for plane in data.planes:
        pname = plane.name
        if is_device_plane(pname):
            lines = list(plane.lines)
            wanted = [ln for ln in lines if ln.name in OP_LINES] or \
                [ln for ln in lines if ln.name not in NOT_OP_LINES]
            for ln in wanted:
                for ev in ln.events:
                    out.append({"plane": pname, "line": ln.name,
                                "name": ev.name,
                                "start_ns": int(ev.start_ns),
                                "dur_ns": int(ev.duration_ns)})
        elif pname.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name == MARK:
                        stats = dict(ev.stats)
                        out.append({"plane": pname, "line": ln.name,
                                    "name": ev.name,
                                    "start_ns": int(ev.start_ns),
                                    "dur_ns": int(ev.duration_ns),
                                    "t_perf": float(stats.get("t_perf", 0.0)),
                                    "tag": str(stats.get("tag", ""))})
    return out


def clock_offset_ns(events: Iterable[dict]) -> Optional[float]:
    """Profiler nanoseconds minus ``perf_counter`` nanoseconds, from the
    first ``bench_mark`` (None when the trace holds no mark)."""
    for ev in events:
        if ev["name"] == MARK and ev.get("t_perf"):
            return ev["start_ns"] - ev["t_perf"] * 1e9
    return None


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Sorted, disjoint union of half-open intervals."""
    out: List[Interval] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def total(intervals: Sequence[Interval]) -> int:
    return sum(b - a for a, b in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of the disjoint sorted ``a`` not covered by the disjoint sorted
    ``b``."""
    out: List[Interval] = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def clip(intervals: Sequence[Interval], window: Interval) -> List[Interval]:
    w0, w1 = window
    return [(max(a, w0), min(b, w1)) for a, b in intervals
            if min(b, w1) > max(a, w0)]


def reduce_events(events: Sequence[dict], window: Optional[Interval] = None
                  ) -> dict:
    """Per-device and averaged busy/idle/collective numbers of the operation
    events inside ``window`` (profiler nanoseconds; default: first operation
    start to last operation end).

    Returned: ``window_s``, ``busy_s`` (mean over devices), ``idle_share``,
    ``n_devices``, ``ops`` ({class: seconds, mean over devices}),
    ``collective_s`` / ``collective_exposed_s`` (worst device), ``devices``
    ({plane: {busy_s, collective_s, collective_exposed_s, gaps}}), where
    ``gaps`` are the idle intervals of that device inside the window."""
    dev: Dict[str, List[dict]] = {}
    for ev in events:
        if ev["name"] != MARK and is_device_plane(ev["plane"]):
            dev.setdefault(ev["plane"], []).append(ev)
    if not dev:
        return {"window_s": 0.0, "busy_s": 0.0, "idle_share": None,
                "n_devices": 0, "ops": {}, "collective_s": 0.0,
                "collective_exposed_s": 0.0, "devices": {}}
    if window is None:
        window = (min(e["start_ns"] for es in dev.values() for e in es),
                  max(e["start_ns"] + e["dur_ns"]
                      for es in dev.values() for e in es))
    w0, w1 = int(window[0]), int(window[1])
    span_ns = max(w1 - w0, 1)
    ops: Dict[str, float] = {}
    per_dev = {}
    for plane, evs in sorted(dev.items()):
        all_iv, coll_iv, comp_iv = [], [], []
        for e in evs:
            iv = clip([(e["start_ns"], e["start_ns"] + e["dur_ns"])],
                      (w0, w1))
            if not iv:
                continue
            all_iv += iv
            (coll_iv if is_collective(e["name"]) else comp_iv).extend(iv)
            cls = op_class(e["name"])
            ops[cls] = ops.get(cls, 0.0) + total(iv) / 1e9
        busy = union(all_iv)
        coll = union(coll_iv)
        exposed = subtract(coll, union(comp_iv))
        per_dev[plane] = {
            "busy_s": total(busy) / 1e9,
            "collective_s": total(coll) / 1e9,
            "collective_exposed_s": total(exposed) / 1e9,
            "gaps": subtract([(w0, w1)], busy),
        }
    n = len(per_dev)
    busy_s = sum(d["busy_s"] for d in per_dev.values()) / n
    return {
        "window_s": span_ns / 1e9,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / (span_ns / 1e9),
        "n_devices": n,
        "ops": {k: v / n for k, v in ops.items()},
        "collective_s": max(d["collective_s"] for d in per_dev.values()),
        "collective_exposed_s": max(d["collective_exposed_s"]
                                    for d in per_dev.values()),
        "devices": per_dev,
    }


def attribute_gaps(gaps: Sequence[Interval],
                   host_spans: Sequence[Tuple[str, int, int]],
                   other: str = "between_spans") -> Dict[str, float]:
    """Seconds of idle gaps by what the host was doing: each gap is split
    among the host spans ``(name, start_ns, end_ns)`` that overlap it (the
    innermost, i.e. shortest, span wins where several do) and the rest goes
    to ``other``."""
    out: Dict[str, float] = {}
    spans = sorted(host_spans, key=lambda s: s[2] - s[1])
    for g0, g1 in gaps:
        left = [(g0, g1)]
        for name, s0, s1 in spans:
            if s1 <= g0 or s0 >= g1 or not left:
                continue
            cover = clip(left, (s0, s1))
            if cover:
                out[name] = out.get(name, 0.0) + total(cover) / 1e9
                left = subtract(left, union(cover))
        if left:
            out[other] = out.get(other, 0.0) + total(left) / 1e9
    return out


def top(d: Dict[str, float], n: int = 10) -> List[list]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
