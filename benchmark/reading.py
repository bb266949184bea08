"""Small helpers the per-layer readers share (spans are ``(name, t0, t1,
args)`` on the perf_counter clock, seconds)."""

from __future__ import annotations

from typing import List, Optional, Tuple

from .stats import percentile


def named(inputs: dict, name: str) -> List[tuple]:
    return [s for s in inputs["spans"] if s[0] == name]


def p50_ms(inputs: dict, name: str) -> Optional[float]:
    d = [(s[2] - s[1]) * 1e3 for s in named(inputs, name)]
    return percentile(d, 50) if d else None


def in_trace_window(inputs: dict, name: str) -> Optional[int]:
    """How many spans ``name`` ended inside the profiler's window."""
    w: Optional[Tuple[float, float]] = inputs.get("trace_window")
    if not w or w[0] is None or w[1] is None:
        return None
    return sum(1 for s in named(inputs, name) if w[0] <= s[2] <= w[1])


def busy_per(inputs: dict, units: Optional[float]) -> Optional[float]:
    """Device-busy milliseconds of the traced window per unit of work."""
    red = inputs.get("trace")
    if not red or not red.get("n_devices") or not units:
        return None
    return red["busy_s"] / units * 1e3


def idle_share(inputs: dict) -> Optional[float]:
    red = inputs.get("trace")
    if not red or not red.get("n_devices"):
        return None
    return 100.0 * red["idle_share"]
