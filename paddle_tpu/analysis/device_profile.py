"""Measured device-time attribution from captured profiler windows —
the xprof half of the observability stack, closing the loop PR 8 opened.

``profiler.SamplingProfiler`` captures real ``jax.profiler`` windows
(``<window>/plugins/profile/<run>/*.trace.json.gz`` + ``*.xplane.pb``,
step-annotated via ``StepTraceAnnotation("paddle_tpu.step")``), but until
this module nothing in the repo ever parsed them: MFU was analytic-only
(``paddle_tpu_step_mfu`` divides model flops by the dispatch interval),
with no measured breakdown of compute vs memory vs idle.  This module
turns a captured window into *attribution*:

- **Trace parser** (:func:`parse_trace`): the chrome-trace JSON the
  profiler writes per window, with process/thread metadata resolved.
  Device lanes are the ``/device:*`` processes on real TPU captures and
  the XLA runtime execution threads (``tf_XLATfrtCpuClient*``) on the
  CPU smoke — host python frames and compile threads never count as
  device time.

- **XPlane wire reader** (:func:`read_xplane`): a dependency-free
  protobuf *wire-format* parser for ``*.xplane.pb`` (XSpace → XPlane →
  XLine → XEvent durations + event-metadata names) — no TensorFlow or
  generated proto import, because the container has neither.  Used for
  kernel durations on device planes and cross-checking the JSON trace.

- **Step join** (:func:`step_intervals`): ``paddle_tpu.step`` spans
  carry the executor's process-global step id (``args.step_num``) — the
  SAME id stamped on the host ``executor.dispatch`` span and the
  sampling-window manifest — so device kernels attribute to framework
  steps by interval containment on the shared trace clock.

- **Op-class attribution** (:func:`classify_kernel`): HLO/fusion kernel
  names map back to the PR-8 cost-model op classes
  (matmul/conv/attention/embedding/collective/infeed/elementwise), per
  arxiv 2104.05755's observation that a few op classes dominate device
  time.  Per-step measured device time, per-class shares, idle/gap
  fraction, and **measured MFU** — analytic flops/step over measured
  device-busy time × chip peak — published as
  ``paddle_tpu_step_mfu_measured`` next to the analytic gauge.

- **Objective oracle** (:func:`summarize_and_publish`): the post-close
  hook in ``SamplingProfiler`` calls this to persist
  ``<window>/summary.json`` — per-class measured shares, the
  measured-vs-analytic divergence table, and per-kernel
  wasted-roofline-headroom ranking the autotune search (TVM-style,
  arxiv 1802.04799) consumes as its measurement objective.  The hook
  path NEVER raises: malformed/truncated captures warn and skip.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
import time
import warnings
from typing import Any, Dict, List, Optional, Tuple

from .. import monitor as _monitor

__all__ = [
    "classify_kernel", "parse_trace", "step_intervals", "device_lanes",
    "read_xplane", "xplane_kernel_ms", "attribute", "summarize_window",
    "write_summary", "summarize_and_publish", "latest_profile_run",
    "MEASURED_CLASSES",
]

#: measured device-time classes, the attribution buckets kernels map to
MEASURED_CLASSES = ("matmul", "conv", "attention", "embedding",
                    "collective", "infeed", "elementwise", "other")

MFU_MEASURED_GAUGE = _monitor.REGISTRY.gauge(
    "paddle_tpu_step_mfu_measured",
    "measured model-flops utilization in [0,1]: analytic flops/step "
    "over MEASURED per-step device-busy time x chip peak, from the last "
    "parsed profiler window — the companion of the analytic "
    "paddle_tpu_step_mfu gauge (divergence = dispatch-interval slack "
    "the analytic estimate cannot see)")
IDLE_FRAC_GAUGE = _monitor.REGISTRY.gauge(
    "paddle_tpu_step_device_idle_frac",
    "measured idle/gap fraction of the step span (device lanes quiet) "
    "from the last parsed profiler window")
DEVICE_SHARE_GAUGE = _monitor.REGISTRY.gauge(
    "paddle_tpu_step_device_time_share",
    "measured device-time share by op class from the last parsed "
    "profiler window — the MEASURED counterpart of the analytic "
    "paddle_tpu_step_flops_share", ("op_class",))
_SUMMARY_CTR = _monitor.REGISTRY.counter(
    "paddle_tpu_profile_summaries_total",
    "post-close window summaries by outcome (ok / empty / error)",
    ("outcome",))

#: wall time of the last successful publish — monitor.metrics_digest
#: freshness-gates the ``mfu_m`` digest key on this (same discipline as
#: the hbm/comms planes: a rank that stopped capturing windows must not
#: report its last measured MFU forever)
last_publish_wall = 0.0


# ---------------------------------------------------------------------------
# kernel-name -> op-class attribution
# ---------------------------------------------------------------------------

#: ordered (regex, class) rules: FIRST match wins, so collectives beat
#: the embedded 'scatter' in 'reduce-scatter' and fused attention beats
#: the 'dot' inside its fusion name
_KERNEL_RULES: Tuple[Tuple[re.Pattern, str], ...] = tuple(
    (re.compile(p, re.IGNORECASE), c) for p, c in (
        (r"all-?reduce|all-?gather|reduce-?scatter|all-?to-?all|"
         r"collective-?permute|psum|ppermute|cross-replica", "collective"),
        (r"infeed|outfeed|host-?transfer|copy-start|copy-done|"
         r"send\b|send-done|recv\b|recv-done", "infeed"),
        (r"attention|flash|mha\b", "attention"),
        (r"conv", "conv"),
        (r"\bdot\b|dot[._]|[^a-z]dot$|gemm|matmul|einsum|cublas|mxu",
         "matmul"),
        (r"gather|scatter|dynamic-?slice|dynamic-?update-?slice|"
         r"embedding|one-?hot|take\b", "embedding"),
        (r"fusion|loop|elementwise|add|sub[^s]|mult|div|exp|log|tanh|"
         r"sigmoid|gelu|relu|erf|rsqrt|sqrt|pow|max|min|select|compare|"
         r"broadcast|reduce|transpose|reshape|convert|bitcast|concat|"
         r"slice|pad|iota|rng|sort|tuple|copy|clamp|negate|and|or|xor",
         "elementwise"),
    ))


def classify_kernel(name: str) -> str:
    """Map one HLO/fusion/thunk kernel name to a measured op class (the
    PR-8 cost-model classes, measured flavor).  Unrecognized -> 'other'."""
    n = str(name)
    # custom-call / pallas kernels keep their payload name ("%fusion.3",
    # "custom-call.7 @flash_attention" ...) — strip HLO sigils so the
    # rules see the meat
    n = n.lstrip("%").strip()
    for rx, cls in _KERNEL_RULES:
        if rx.search(n):
            return cls
    return "other"


#: non-kernel infrastructure spans on device/runtime lanes — scheduler
#: bookkeeping and blocking waits, never device work
_INFRA_RX = re.compile(
    r"ThreadpoolListener|ThunkExecutor|ExecuteThunks|wait for completion|"
    r"^\$|^process_|^thread_|^paddle_tpu\.step$|^PjitFunction|"
    r"^ThreadRun|XlaModule|^Steps?$", re.IGNORECASE)


# ---------------------------------------------------------------------------
# chrome-trace (trace.json.gz) parsing
# ---------------------------------------------------------------------------

def parse_trace(path: str) -> Optional[Dict[str, Any]]:
    """Load one chrome-trace JSON (optionally gzipped) into
    ``{"events": [...], "processes": {pid: name},
    "threads": {(pid, tid): name}}``.  Malformed or truncated files
    warn and return None — the post-close hook path must never raise."""
    try:
        if path.endswith(".gz"):
            with gzip.open(path, "rt", encoding="utf-8",
                           errors="replace") as f:
                data = json.load(f)
        else:
            with open(path, encoding="utf-8", errors="replace") as f:
                data = json.load(f)
    except (OSError, EOFError, ValueError) as e:
        warnings.warn(f"device_profile: unreadable trace {path!r}: {e!r}")
        return None
    if not isinstance(data, dict):
        warnings.warn(f"device_profile: trace {path!r} is not an object")
        return None
    events = data.get("traceEvents")
    if not isinstance(events, list):
        warnings.warn(f"device_profile: trace {path!r} has no traceEvents")
        return None
    processes: Dict[int, str] = {}
    threads: Dict[Tuple[int, int], str] = {}
    spans: List[Dict[str, Any]] = []
    for ev in events:
        if not isinstance(ev, dict):
            continue
        ph = ev.get("ph")
        if ph == "M":
            args = ev.get("args") or {}
            if ev.get("name") == "process_name":
                processes[ev.get("pid")] = str(args.get("name", ""))
            elif ev.get("name") == "thread_name":
                threads[(ev.get("pid"), ev.get("tid"))] = \
                    str(args.get("name", ""))
        elif ph == "X":
            try:
                ts = float(ev.get("ts", 0.0))
                dur = float(ev.get("dur", 0.0))
            except (TypeError, ValueError):
                continue
            spans.append({"name": str(ev.get("name", "")),
                          "pid": ev.get("pid"), "tid": ev.get("tid"),
                          "ts": ts, "dur": dur,
                          "args": ev.get("args") or {}})
    return {"events": spans, "processes": processes, "threads": threads}


def step_intervals(trace: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Framework-step intervals from the ``paddle_tpu.step``
    StepTraceAnnotation spans (``args.step_num`` is the executor's
    process-global step id).  Duplicate annotations for one id (nested
    re-entry) collapse to the widest span.  Sorted by start time."""
    by_id: Dict[int, Tuple[float, float]] = {}
    for ev in trace["events"]:
        if ev["name"] != "paddle_tpu.step":
            continue
        try:
            step = int(ev["args"].get("step_num"))
        except (TypeError, ValueError):
            continue
        t0, t1 = ev["ts"], ev["ts"] + ev["dur"]
        if step in by_id:
            o0, o1 = by_id[step]
            by_id[step] = (min(t0, o0), max(t1, o1))
        else:
            by_id[step] = (t0, t1)
    return [{"step": s, "ts": t0, "dur": t1 - t0}
            for s, (t0, t1) in sorted(by_id.items(),
                                      key=lambda kv: kv[1][0])]


def device_lanes(trace: Dict[str, Any]) -> List[Tuple[int, int]]:
    """(pid, tid) lanes that carry device/kernel execution events: any
    thread of a ``/device:*`` process (real TPU capture), else the XLA
    runtime execution threads of the host process (CPU smoke —
    ``tf_XLATfrtCpuClient*``; the llvm-codegen threads are COMPILE time
    and never count)."""
    dev_pids = {pid for pid, name in trace["processes"].items()
                if str(name).startswith("/device:")}
    lanes = {(ev["pid"], ev["tid"]) for ev in trace["events"]
             if ev["pid"] in dev_pids}
    if lanes:
        return sorted(lanes)
    for (pid, tid), tname in trace["threads"].items():
        n = str(tname)
        if n.startswith("tf_XLA") and "codegen" not in n.lower():
            lanes.add((pid, tid))
    return sorted(lanes)


def _union_ms(intervals: List[Tuple[float, float]]) -> float:
    """Total length (ms) of the union of [t0, t1) microsecond intervals
    — overlapping kernels on parallel lanes count once (wall busy time,
    the roofline's denominator), not summed."""
    if not intervals:
        return 0.0
    intervals = sorted(intervals)
    total = 0.0
    cur0, cur1 = intervals[0]
    for t0, t1 in intervals[1:]:
        if t0 > cur1:
            total += cur1 - cur0
            cur0, cur1 = t0, t1
        else:
            cur1 = max(cur1, t1)
    total += cur1 - cur0
    return total / 1e3


def attribute(trace: Dict[str, Any]) -> Dict[str, Any]:
    """Attribute device-lane kernel time to framework steps and op
    classes.  Returns per-step rows (measured device ms, idle fraction,
    per-class ms), window-level per-class totals/shares, and the
    per-kernel aggregation the divergence table ranks."""
    steps = step_intervals(trace)
    lanes = set(device_lanes(trace))
    kernels = [ev for ev in trace["events"]
               if (ev["pid"], ev["tid"]) in lanes and ev["dur"] > 0
               and not _INFRA_RX.search(ev["name"])]

    per_kernel: Dict[str, Dict[str, Any]] = {}
    per_class_ms: Dict[str, float] = {}
    step_rows: List[Dict[str, Any]] = []
    unattributed_ms = 0.0

    def _step_of(ev):
        mid = ev["ts"] + ev["dur"] / 2.0
        for s in steps:
            if s["ts"] <= mid < s["ts"] + s["dur"]:
                return s["step"]
        return None

    by_step: Dict[Optional[int], List[dict]] = {}
    for ev in kernels:
        cls = classify_kernel(ev["name"])
        ms = ev["dur"] / 1e3
        k = per_kernel.setdefault(
            ev["name"], {"name": ev["name"], "op_class": cls,
                         "ms": 0.0, "count": 0})
        k["ms"] += ms
        k["count"] += 1
        per_class_ms[cls] = per_class_ms.get(cls, 0.0) + ms
        sid = _step_of(ev)
        by_step.setdefault(sid, []).append(ev)
        if sid is None:
            unattributed_ms += ms

    for s in steps:
        evs = by_step.get(s["step"], [])
        busy = _union_ms([(max(e["ts"], s["ts"]),
                           min(e["ts"] + e["dur"], s["ts"] + s["dur"]))
                          for e in evs])
        span_ms = s["dur"] / 1e3
        cls_ms: Dict[str, float] = {}
        for e in evs:
            c = classify_kernel(e["name"])
            cls_ms[c] = cls_ms.get(c, 0.0) + e["dur"] / 1e3
        step_rows.append({
            "step": s["step"],
            "span_ms": round(span_ms, 6),
            "device_ms": round(busy, 6),
            "idle_frac": round(1.0 - busy / span_ms, 6)
            if span_ms > 0 else None,
            "per_class_ms": {c: round(v, 6)
                             for c, v in sorted(cls_ms.items())}})

    total_ms = sum(per_class_ms.values())
    share = {c: v / total_ms for c, v in per_class_ms.items()} \
        if total_ms > 0 else {}
    spans = [r["span_ms"] for r in step_rows if r["span_ms"] > 0]
    busy_in_steps = [r["device_ms"] for r in step_rows]
    idle = (1.0 - sum(busy_in_steps) / sum(spans)) if spans else None
    return {
        "steps": step_rows,
        "n_steps": len(step_rows),
        "per_class_ms": {c: round(v, 6)
                         for c, v in sorted(per_class_ms.items())},
        "per_class_share": {c: round(v, 6)
                            for c, v in sorted(share.items())},
        "device_ms_total": round(total_ms, 6),
        "device_busy_ms": round(_union_ms(
            [(e["ts"], e["ts"] + e["dur"]) for e in kernels]), 6),
        "unattributed_ms": round(unattributed_ms, 6),
        "idle_frac": round(idle, 6) if idle is not None else None,
        "kernels": sorted(
            ({**k, "ms": round(k["ms"], 6)} for k in per_kernel.values()),
            key=lambda k: -k["ms"]),
    }


# ---------------------------------------------------------------------------
# xplane.pb: dependency-free protobuf wire-format reader
# ---------------------------------------------------------------------------
# XSpace{1: planes} / XPlane{2: name, 3: lines, 4: event_metadata map
# {1: key, 2: XEventMetadata{1: id, 2: name}}} / XLine{1: id, 2: name,
# 3: timestamp_ns, 4: events} / XEvent{1: metadata_id, 2: offset_ps,
# 3: duration_ps}.  Verified against real jax.profiler captures; no
# TensorFlow import — the wire format is stable, generated protos are
# a dependency the container does not carry.

def _varint(b: bytes, i: int) -> Tuple[int, int]:
    r = s = 0
    while True:
        if i >= len(b):
            raise ValueError("truncated varint")
        x = b[i]
        i += 1
        r |= (x & 0x7F) << s
        if not x & 0x80:
            return r, i
        s += 7
        if s > 70:
            raise ValueError("varint overflow")


def _fields(b: bytes):
    """Yield (field_no, wire_type, value) over one message's bytes."""
    i, n = 0, len(b)
    while i < n:
        tag, i = _varint(b, i)
        fn, wt = tag >> 3, tag & 7
        if wt == 0:
            v, i = _varint(b, i)
        elif wt == 2:
            ln, i = _varint(b, i)
            if i + ln > n:
                raise ValueError("truncated length-delimited field")
            v = b[i:i + ln]
            i += ln
        elif wt == 5:
            v, i = b[i:i + 4], i + 4
        elif wt == 1:
            v, i = b[i:i + 8], i + 8
        else:
            raise ValueError(f"unsupported wire type {wt}")
        if i > n:
            raise ValueError("truncated field")
        yield fn, wt, v


def read_xplane(path: str) -> Optional[List[Dict[str, Any]]]:
    """Parse an ``*.xplane.pb`` XSpace into
    ``[{"name", "lines": [{"name", "timestamp_ns", "events":
    [{"name", "offset_ps", "duration_ps"}]}]}]``.  Malformed/truncated
    input warns and returns None (post-close-hook discipline)."""
    try:
        with open(path, "rb") as f:
            data = f.read()
        planes = []
        for fn, wt, v in _fields(data):
            if fn != 1 or wt != 2:
                continue
            name, lines, emeta = "", [], {}
            for f2, w2, v2 in _fields(v):
                if f2 == 2 and w2 == 2:
                    name = v2.decode("utf-8", "replace")
                elif f2 == 3 and w2 == 2:
                    lines.append(v2)
                elif f2 == 4 and w2 == 2:
                    key = mname = mid = None
                    for f3, w3, v3 in _fields(v2):
                        if f3 == 1 and w3 == 0:
                            key = v3
                        elif f3 == 2 and w3 == 2:
                            for f4, w4, v4 in _fields(v3):
                                if f4 == 1 and w4 == 0:
                                    mid = v4
                                elif f4 == 2 and w4 == 2:
                                    mname = v4.decode("utf-8", "replace")
                    k = key if key is not None else mid
                    if k is not None and mname is not None:
                        emeta[k] = mname
            out_lines = []
            for ln in lines:
                lname, ts_ns, evs = "", 0, []
                for f3, w3, v3 in _fields(ln):
                    if f3 == 2 and w3 == 2:
                        lname = v3.decode("utf-8", "replace")
                    elif f3 == 3 and w3 == 0:
                        ts_ns = v3
                    elif f3 == 4 and w3 == 2:
                        mid = off = dur = 0
                        for f4, w4, v4 in _fields(v3):
                            if w4 != 0:
                                continue
                            if f4 == 1:
                                mid = v4
                            elif f4 == 2:
                                off = v4
                            elif f4 == 3:
                                dur = v4
                        evs.append({"name": emeta.get(mid, f"#{mid}"),
                                    "offset_ps": off, "duration_ps": dur})
                out_lines.append({"name": lname, "timestamp_ns": ts_ns,
                                  "events": evs})
            planes.append({"name": name, "lines": out_lines})
        return planes
    except (OSError, ValueError, IndexError) as e:
        warnings.warn(f"device_profile: unreadable xplane {path!r}: {e!r}")
        return None


def xplane_kernel_ms(path: str) -> Optional[Dict[str, float]]:
    """Per-kernel total durations (ms) from the DEVICE planes of one
    xplane.pb (``/device:*``; infrastructure spans filtered the same way
    as the JSON-trace path).  None when no device plane exists or the
    file is malformed — the trace.json.gz attribution then stands
    alone."""
    planes = read_xplane(path)
    if planes is None:
        return None

    def _lane_events(device_only):
        for plane in planes:
            pname = str(plane["name"])
            if device_only:
                if not pname.startswith("/device:"):
                    continue
                for line in plane["lines"]:
                    yield from line["events"]
            else:
                # CPU capture: the XLA client execution lines of the
                # host plane (codegen lines are compile time)
                for line in plane["lines"]:
                    lname = str(line["name"])
                    if lname.startswith("tf_XLA") and \
                            "codegen" not in lname.lower():
                        yield from line["events"]

    out: Dict[str, float] = {}
    for device_only in (True, False):
        for ev in _lane_events(device_only):
            if _INFRA_RX.search(ev["name"]):
                continue
            out[ev["name"]] = out.get(ev["name"], 0.0) + \
                ev["duration_ps"] / 1e9
        if out:
            break
    return {k: round(v, 6) for k, v in out.items()} if out else None


# ---------------------------------------------------------------------------
# window summary: the persisted objective oracle
# ---------------------------------------------------------------------------

#: analytic cost-model classes folded into the measured buckets for the
#: divergence table (norm/softmax/reduce/optimizer are VPU work a fused
#: device kernel bills as elementwise time)
_ANALYTIC_TO_MEASURED = {
    "matmul": "matmul", "conv": "conv", "attention": "attention",
    "embedding": "embedding",
}


def latest_profile_run(window_dir: str) -> Optional[str]:
    """Newest ``plugins/profile/<run>/`` under a capture window (a
    re-used window dir holds one run per capture; run names are
    timestamps, so lexical order is capture order)."""
    runs = sorted(glob.glob(os.path.join(
        window_dir, "plugins", "profile", "*")))
    runs = [r for r in runs if os.path.isdir(r)]
    return runs[-1] if runs else None


def summarize_window(window_dir: str,
                     flops_per_step: Optional[float] = None,
                     peak_flops: Optional[float] = None,
                     analytic_share: Optional[Dict[str, float]] = None,
                     ) -> Optional[Dict[str, Any]]:
    """Parse one captured window into the summary dict (the schema
    ``<window>/summary.json`` persists).  ``flops_per_step`` /
    ``peak_flops`` enable measured MFU; ``analytic_share`` (the
    ``paddle_tpu_step_flops_share`` per-class flop shares) enables the
    measured-vs-analytic divergence table and the per-kernel
    wasted-roofline-headroom ranking.  Warns and returns None when the
    window holds no parseable capture — never raises."""
    run = latest_profile_run(window_dir)
    if run is None:
        warnings.warn(
            f"device_profile: no plugins/profile run under {window_dir!r}")
        return None
    traces = sorted(glob.glob(os.path.join(run, "*.trace.json.gz"))) + \
        sorted(glob.glob(os.path.join(run, "*.trace.json")))
    trace = None
    trace_path = None
    for cand in traces:
        trace = parse_trace(cand)
        if trace is not None:
            trace_path = cand
            break
    if trace is None:
        warnings.warn(
            f"device_profile: no parseable trace under {run!r}")
        return None
    summary: Dict[str, Any] = {
        "window": window_dir,
        "profile_run": run,
        "trace": os.path.basename(trace_path),
        **attribute(trace),
    }
    for xp in sorted(glob.glob(os.path.join(run, "*.xplane.pb"))):
        km = xplane_kernel_ms(xp)
        if km:
            summary["xplane_kernel_ms"] = km
            summary["xplane"] = os.path.basename(xp)
            break

    # measured MFU: analytic flops/step over measured device-busy time
    # per step x peak.  Steps with zero measured device time drop out
    # (a window tail can clip a step's kernels).  The join is by the
    # step's HOST span, and dispatch is asynchronous: where every kernel
    # of the window ran after its step's span had closed (a loaded host;
    # basis "window") the window's own busy time over its steps is what
    # there is to divide by.
    busy = [r["device_ms"] for r in summary["steps"]
            if r["device_ms"] > 0]
    mean_busy_ms, basis = None, None
    if busy:
        mean_busy_ms, basis = sum(busy) / len(busy), "steps"
    elif summary["steps"] and summary["device_busy_ms"] > 0:
        mean_busy_ms = summary["device_busy_ms"] / len(summary["steps"])
        basis = "window"
    mfu_measured = None
    if mean_busy_ms and flops_per_step and peak_flops:
        mfu_measured = flops_per_step / (mean_busy_ms / 1e3) / peak_flops
    spans = [r["span_ms"] for r in summary["steps"] if r["span_ms"] > 0]
    mfu_analytic = None
    if spans and flops_per_step and peak_flops:
        mfu_analytic = flops_per_step / (sum(spans) / len(spans) / 1e3) \
            / peak_flops
    summary["measured"] = {
        "flops_per_step": flops_per_step,
        "peak_flops": peak_flops,
        "mfu_measured": round(mfu_measured, 6)
        if mfu_measured is not None else None,
        "mfu_basis": basis if mfu_measured is not None else None,
        "mfu_analytic_over_span": round(mfu_analytic, 6)
        if mfu_analytic is not None else None,
    }

    if analytic_share:
        summary["divergence"] = _divergence(
            summary, analytic_share, flops_per_step, peak_flops)
    return summary


def _divergence(summary: Dict[str, Any],
                analytic_share: Dict[str, float],
                flops_per_step: Optional[float],
                peak_flops: Optional[float]) -> Dict[str, Any]:
    """Measured-vs-analytic attribution: per-class time share against
    flop share (a class burning far more time than its flop share is
    memory/latency-bound — the fusion arc's candidate list), and the
    per-kernel wasted-roofline-headroom ranking (measured ms minus the
    roofline-minimum ms for the flops the class attributes to it) — the
    autotune search's objective, largest headroom first."""
    folded: Dict[str, float] = {}
    for cls, share in analytic_share.items():
        m = _ANALYTIC_TO_MEASURED.get(cls, "elementwise")
        folded[m] = folded.get(m, 0.0) + float(share)
    measured_share = summary.get("per_class_share", {})
    classes = sorted(set(folded) | set(measured_share))
    table = [{
        "op_class": c,
        "measured_time_share": round(measured_share.get(c, 0.0), 6),
        "analytic_flop_share": round(folded.get(c, 0.0), 6),
        "time_over_flop_ratio": round(
            measured_share.get(c, 0.0) / folded[c], 4)
        if folded.get(c, 0.0) > 0 else None,
    } for c in classes]

    ranking: List[Dict[str, Any]] = []
    n_steps = max(summary.get("n_steps") or 0, 1)
    per_class_ms = summary.get("per_class_ms", {})
    if flops_per_step and peak_flops:
        for k in summary.get("kernels", []):
            cls_ms = per_class_ms.get(k["op_class"], 0.0)
            # window-total class flops (per-step x steps): kernel ms
            # totals span the whole window, so the proportional split
            # below needs both sides on the same window-total basis
            cls_flops = flops_per_step * n_steps * \
                folded.get(k["op_class"], 0.0)
            # class flops attribute to kernels proportionally by time —
            # honest without per-kernel flop counts, and exact when a
            # class is one kernel
            est_flops = cls_flops * (k["ms"] / cls_ms) if cls_ms > 0 \
                else 0.0
            ms_per_step = k["ms"] / n_steps
            ideal_ms = est_flops / n_steps / peak_flops * 1e3
            ranking.append({
                "kernel": k["name"], "op_class": k["op_class"],
                "ms_per_step": round(ms_per_step, 6),
                "est_flops_per_step": round(est_flops / n_steps, 3),
                "roofline_min_ms": round(ideal_ms, 6),
                "wasted_ms": round(ms_per_step - ideal_ms, 6),
            })
        ranking.sort(key=lambda r: -r["wasted_ms"])
    return {"per_class": table, "wasted_headroom": ranking}


def write_summary(window_dir: str, summary: Dict[str, Any]) -> str:
    """Persist ``<window>/summary.json`` atomically (same tmp+replace
    discipline as the manifest — a concurrent reader never sees a torn
    file)."""
    path = os.path.join(window_dir, "summary.json")
    tmp = path + f".tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(summary, f, indent=1, sort_keys=False)
    os.replace(tmp, path)
    return path


def _live_analytic() -> Tuple[Optional[float], Optional[float],
                              Dict[str, float]]:
    """(flops/step, peak flops, per-class flop share) from the live
    gauges the executor publishes at compile time — the denominators
    the post-close hook joins to the freshly captured window."""
    flops = peak = None
    fam = _monitor.REGISTRY.get("paddle_tpu_analytic_step_flops")
    if fam is not None:
        v = fam.value()
        if v:
            flops = float(v)
    try:
        from .cost import device_peak_flops
        peak = float(device_peak_flops())
    except Exception:
        peak = None
    share: Dict[str, float] = {}
    sfam = _monitor.REGISTRY.get("paddle_tpu_step_flops_share")
    if sfam is not None:
        for labels, cell in sfam.series():
            c = labels.get("op_class")
            if c:
                share[c] = float(cell.get())
    return flops, peak, share


def summarize_and_publish(window_dir: str) -> Optional[str]:
    """The SamplingProfiler post-close hook: parse the just-closed
    window, persist ``summary.json`` (the autotune search's objective
    oracle), and publish the measured gauges —
    ``paddle_tpu_step_mfu_measured``, idle fraction, per-class measured
    device-time shares (the ``mfu_m`` gang-digest key reads the first).
    Returns the summary path, or None (warn + skip) on any failure —
    this path must NEVER fail the training step."""
    global last_publish_wall
    try:
        flops, peak, share = _live_analytic()
        summary = summarize_window(window_dir, flops_per_step=flops,
                                   peak_flops=peak,
                                   analytic_share=share or None)
        if summary is None:
            _SUMMARY_CTR.inc(1, outcome="empty")
            return None
        path = write_summary(window_dir, summary)
        mfu = summary["measured"]["mfu_measured"]
        if mfu is not None:
            MFU_MEASURED_GAUGE.set(float(mfu))
        if summary["idle_frac"] is not None:
            IDLE_FRAC_GAUGE.set(float(summary["idle_frac"]))
        # stale classes zero out: the gauge reflects THIS window only
        for labels, cell in DEVICE_SHARE_GAUGE.series():
            cell.set(0.0)
        for c, v in summary["per_class_share"].items():
            DEVICE_SHARE_GAUGE.set(float(v), op_class=c)
        last_publish_wall = time.time()
        _SUMMARY_CTR.inc(1, outcome="ok")
        if _monitor.TRACER.enabled:
            _monitor.TRACER.instant(
                "profile.window_summary", "profile",
                {"window": window_dir, "mfu_measured": mfu,
                 "idle_frac": summary["idle_frac"],
                 "n_steps": summary["n_steps"]})
        return path
    except Exception as e:       # never fail the step/close path
        _SUMMARY_CTR.inc(1, outcome="error")
        warnings.warn(
            f"device_profile: window summary failed for "
            f"{window_dir!r}: {e!r}")
        return None
