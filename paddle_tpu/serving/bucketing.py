"""Bucketized shape cache: pad arbitrary request shapes onto a small set
of compile buckets so serving compile cost is bounded by the bucket count,
not the number of distinct request shapes (the TVM-style AOT shape-bucket
design — PAPERS.md arxiv 1802.04799).

A bucket is a sequence length; every feed of a request is padded along its
leading (per-example sequence) axis up to the bucket, and the batch is
padded to a FIXED per-bucket width — so each bucket lowers to exactly one
XLA executable, persisted across restarts by the persistent compile cache
(``device.place_compile_cache``).  Fluid programs bake the sequence length
into op attrs (position-table slices, causal-mask ranges), so the server
materializes one program per bucket through a ``program_factory`` and runs
each through ``compiler.optimize`` — the verifier / cost / memory stamps
ride along on every bucket program.
"""

from __future__ import annotations

import threading
from typing import Callable, Dict, Optional, Sequence, Tuple

import numpy as np

from .. import monitor as _monitor

BUCKET_WIDTH_GAUGE = _monitor.REGISTRY.gauge(
    "paddle_tpu_serving_bucket_width",
    "admitted batch width per compile bucket (lowered below "
    "FLAGS_serving_max_batch when the static HBM plan at full width "
    "exceeds FLAGS_memory_budget_mb)", ("bucket",))
PAD_TOKENS_CTR = _monitor.REGISTRY.counter(
    "paddle_tpu_serving_padding_rows_total",
    "dummy batch rows dispatched to keep bucket shapes fixed (the "
    "occupancy complement: rows = batches*width - real requests)")


def parse_buckets(spec: str, max_len: int = 512) -> Tuple[int, ...]:
    """``FLAGS_serving_shape_buckets`` grammar: ``"16,32,64"`` explicit,
    ``"pow2:LO:HI"`` powers of two from LO to HI inclusive, ``""`` =
    powers of two from 8 up to ``max_len``."""
    spec = (spec or "").strip()
    if not spec:
        buckets, b = [], 8
        while b < max_len:
            buckets.append(b)
            b *= 2
        buckets.append(max_len)
        return tuple(sorted(set(buckets)))
    if spec.startswith("pow2:"):
        try:
            _, lo, hi = spec.split(":")
            lo, hi = int(lo), int(hi)
        except ValueError:
            raise ValueError(
                f"bad bucket spec {spec!r}: expected 'pow2:LO:HI'")
        if lo <= 0 or hi < lo:
            raise ValueError(f"bad bucket spec {spec!r}: need 0 < LO <= HI")
        buckets, b = [], lo
        while b < hi:
            buckets.append(b)
            b *= 2
        buckets.append(hi)
        return tuple(sorted(set(buckets)))
    try:
        buckets = tuple(sorted({int(tok) for tok in spec.split(",") if tok}))
    except ValueError:
        raise ValueError(
            f"bad bucket spec {spec!r}: expected comma-separated ints or "
            "'pow2:LO:HI'")
    if not buckets or any(b <= 0 for b in buckets):
        raise ValueError(f"bad bucket spec {spec!r}: buckets must be > 0")
    return buckets


def bucket_for(seq_len: int, buckets: Sequence[int]) -> Optional[int]:
    """Smallest bucket that fits ``seq_len``; None when it exceeds the
    largest bucket (the request is rejected at admission, not truncated)."""
    for b in buckets:
        if seq_len <= b:
            return b
    return None


def pad_to_bucket(arr: np.ndarray, bucket: int) -> np.ndarray:
    """Pad one per-example feed along its leading axis up to ``bucket``
    with zeros (0 is the [PAD] id convention throughout this repo).
    Scalars and feeds already at the bucket pass through."""
    a = np.asarray(arr)
    if a.ndim == 0 or a.shape[0] == bucket:
        return a
    if a.shape[0] > bucket:
        raise ValueError(
            f"feed of length {a.shape[0]} exceeds bucket {bucket}")
    pad = [(0, bucket - a.shape[0])] + [(0, 0)] * (a.ndim - 1)
    return np.pad(a, pad)


class BucketPlan:
    """Per-bucket execution plan: the bucket program (built once through
    ``program_factory`` and wrapped in a CompiledProgram so dispatch goes
    through ``compiler.optimize`` — verifier/cost/memory stamps ride
    along) plus the admitted batch width.

    Width admission control (PR-7 static HBM plan): when
    ``FLAGS_memory_budget_mb`` is set, the width starts at
    ``FLAGS_serving_max_batch`` and halves until the bucket program's
    static peak fits the budget — an over-budget bucket serves narrower
    batches instead of OOMing the chip."""

    def __init__(self, buckets: Sequence[int],
                 program_factory: Callable[[int], tuple],
                 max_batch: int, memory_budget_mb: int = 0):
        self.buckets = tuple(sorted(buckets))
        self._factory = program_factory
        self._max_batch = max(1, int(max_batch))
        self._budget = int(memory_budget_mb)
        self._plans: Dict[int, tuple] = {}  # guarded-by: _mu
        self._mu = threading.Lock()

    def plan(self, bucket: int):
        """(compiled_program, feed_names, fetch_names, width) for one
        bucket — built on first use, memoized after."""
        with self._mu:
            entry = self._plans.get(bucket)
        if entry is not None:
            return entry
        from ..compiler import CompiledProgram
        program, feed_names, fetch_names = self._factory(bucket)
        feed_names = [getattr(f, "name", f) for f in feed_names]
        fetch_names = [getattr(f, "name", f) for f in fetch_names]
        width = self._admit_width(program, fetch_names)
        entry = (CompiledProgram(program), list(feed_names),
                 list(fetch_names), width)
        BUCKET_WIDTH_GAUGE.set(width, bucket=str(bucket))
        with self._mu:
            # first build wins — a concurrent builder's duplicate is
            # dropped so every caller dispatches the same CompiledProgram
            # (and hence the same compiled block)
            entry = self._plans.setdefault(bucket, entry)
        return entry

    def _admit_width(self, program, fetch_names) -> int:
        width = self._max_batch
        if self._budget <= 0:
            return width
        from ..analysis.memory import plan_memory
        budget_bytes = self._budget * (1 << 20)
        while width > 1:
            try:
                plan = plan_memory(program, tuple(fetch_names),
                                   batch_size=width)
            except Exception:
                return width        # planning must never block serving
            if plan.peak_bytes <= budget_bytes:
                return width
            width //= 2
        return width

    def shrink_widths(self) -> Dict[int, int]:
        """Halve the admitted width of every ALREADY-BUILT bucket (floor
        1) — the fleet autoscaler's degradation-ladder rung for a replica
        reporting OOM-risk headroom.  The scheduler re-reads the admitted
        width from the memoized entry on every dispatch, so the shrink
        takes effect on the next batch (one fresh XLA compile per shrunk
        bucket — an acceptable one-time cost against an imminent OOM).
        Cold buckets are untouched: they will admit at their planned
        width when first built.  Returns {bucket: new_width}."""
        out: Dict[int, int] = {}
        with self._mu:
            for bucket, entry in list(self._plans.items()):
                compiled, feeds, fetches, width = entry
                new = max(1, int(width) // 2)
                if new != width:
                    self._plans[bucket] = (compiled, feeds, fetches, new)
                out[bucket] = new
        for bucket, w in out.items():
            BUCKET_WIDTH_GAUGE.set(w, bucket=str(bucket))
        return out

    def width_of(self, bucket: int) -> Optional[int]:
        """Admitted width of an ALREADY-BUILT bucket plan; None for a
        cold bucket (statusz must never trigger a build/compile)."""
        with self._mu:
            entry = self._plans.get(int(bucket))
        return entry[3] if entry is not None else None

    def static_peak_of(self, bucket: int) -> Optional[int]:
        """Static HBM peak (bytes) of an ALREADY-BUILT bucket program at
        its admitted width — the /statusz memory section's per-bucket
        plan.  Fingerprint-cached (plan_memory), so a statusz scrape
        never re-plans; None for cold buckets or on planner failure."""
        with self._mu:
            entry = self._plans.get(int(bucket))
        if entry is None:
            return None
        compiled, _feeds, fetch_names, width = entry
        try:
            from ..analysis.memory import plan_memory
            return int(plan_memory(compiled.program, tuple(fetch_names),
                                   batch_size=width).peak_bytes)
        except Exception:
            return None

    def bucket_for(self, seq_len: int) -> Optional[int]:
        return bucket_for(seq_len, self.buckets)
