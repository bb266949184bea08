"""Unified runtime telemetry: metrics registry + step tracer.

The async step pipeline (PR 1) made the interesting time invisible — host
work, feed staging, throttle waits, compile stalls, and fetch
materializations all overlap device compute, so no single tool shows where
a slow step went.  This module is the ledger the ROADMAP's "as fast as the
hardware allows" goal needs before the next optimisation:

- **Metrics registry** (``REGISTRY``): counters, gauges, and fixed-bucket
  histograms with labels, exportable as JSON and Prometheus text format.
  Cheap enough to stay on by default: one lock + float add per bump, no
  allocation on the hot path (label series are resolved once and bound).
  The executor's dispatch counters (``Executor.dispatch_stats()``) are
  BACKED by this registry, so the per-executor view, the profiler-level
  aggregate, and the exporters are one source of truth by construction.

- **Step tracer** (``TRACER``): structured spans for the whole async
  pipeline — dataloader staging, int64 feed checks, XLA trace+compile,
  dispatch, in-flight throttle waits, fetch/``FetchHandle``
  materialization, and host-launched collectives — buffered in a bounded
  ring and exported as chrome://tracing JSON.  ``profiler.chrome_trace``
  merges these spans with the classic ``RecordEvent`` profiler events, so
  ``tools/timeline.py`` renders one stacked multi-rank timeline.

Gating: ``FLAGS_telemetry`` (default on) enables span recording;
``FLAGS_telemetry_export_path`` exports metrics + trace at process exit;
metrics counters are always live (they are the dispatch-stats storage).

The reference stack ships a profiler + timeline pipeline as a first-class
subsystem (``platform/profiler.h``, ``tools/timeline.py``; SURVEY §5.1) —
this is its registry-backed, async-pipeline-aware rebuild.
"""

from __future__ import annotations

import collections
import contextlib
import json
import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "REGISTRY",
    "StepTracer", "TRACER", "span", "export", "telemetry_snapshot",
    "counter_totals", "metrics_digest", "capped_digest",
    "DIGEST_MAX_BYTES", "retire_tenant_series",
]

# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

#: default microsecond buckets: host-side events span ~50 us (a dict probe
#: plus dispatch) to seconds (a cold XLA compile)
DEFAULT_BUCKETS_US = (50.0, 100.0, 250.0, 500.0, 1000.0, 2500.0, 5000.0,
                      10000.0, 25000.0, 50000.0, 100000.0, 250000.0,
                      500000.0, 1e6, 5e6, 30e6)


class _Cell:
    """One labeled series of a counter/gauge: a lock + a float.

    Bound cells (via ``.labels()``) are the hot-path interface: the label
    tuple is resolved ONCE, after which a bump is a lock acquire + add —
    the same cost as the pre-registry dispatch counters."""

    __slots__ = ("_mu", "_v")

    def __init__(self):
        self._mu = threading.Lock()
        self._v = 0  # guarded-by: _mu

    def inc(self, n=1):
        with self._mu:
            self._v += n

    def set(self, v):
        with self._mu:
            self._v = v

    def get(self):
        with self._mu:
            return self._v

    def reset(self):
        with self._mu:
            self._v = 0


class _HistCell:
    """One labeled series of a fixed-bucket histogram."""

    __slots__ = ("_mu", "buckets", "counts", "sum", "count")

    def __init__(self, buckets: Tuple[float, ...]):
        self._mu = threading.Lock()
        self.buckets = buckets
        self.counts = [0] * (len(buckets) + 1)  # guarded-by: _mu  (+Inf bucket at the end)
        self.sum = 0.0  # guarded-by: _mu
        self.count = 0  # guarded-by: _mu

    def observe(self, v: float):
        with self._mu:
            i = 0
            for i, b in enumerate(self.buckets):       # noqa: B007
                if v <= b:
                    break
            else:
                i = len(self.buckets)
            self.counts[i] += 1
            self.sum += v
            self.count += 1

    def snapshot(self):
        with self._mu:
            return list(self.counts), self.sum, self.count

    def reset(self):
        with self._mu:
            self.counts = [0] * (len(self.buckets) + 1)
            self.sum = 0.0
            self.count = 0


class _Metric:
    """Base: a named family of labeled series."""

    kind = "untyped"

    def __init__(self, name: str, help: str = "",
                 labelnames: Sequence[str] = ()):
        self.name = name
        self.help = help
        self.labelnames = tuple(labelnames)
        # re-entrant: a collection that starts while this thread holds the
        # lock (between two bytecodes of ``series``) may run an executor's
        # finalizer, whose ``retire`` folds series of this very family
        self._mu = threading.RLock()
        self._series: Dict[Tuple[str, ...], Any] = {}  # guarded-by: _mu

    def _new_cell(self):
        return _Cell()

    def labels(self, **kv):
        """Resolve (and memoize) the cell for a label-value combination.
        Hot paths call this once and keep the bound cell."""
        if set(kv) != set(self.labelnames):
            raise ValueError(
                f"metric {self.name!r} expects labels {self.labelnames}, "
                f"got {tuple(kv)}")
        key = tuple(str(kv[n]) for n in self.labelnames)
        with self._mu:
            cell = self._series.get(key)
            if cell is None:
                cell = self._series[key] = self._new_cell()
            return cell

    def _default_cell(self):
        return self.labels()

    # convenience: unlabeled metrics act on their single default series
    def reset(self):
        with self._mu:
            cells = list(self._series.values())
        for c in cells:
            c.reset()

    def series(self) -> List[Tuple[Dict[str, str], Any]]:
        with self._mu:
            items = list(self._series.items())
        return [(dict(zip(self.labelnames, key)), cell)
                for key, cell in items]

    def fold(self, src: Dict[str, str], dst: Optional[Dict[str, str]]):
        """Retire the ``src`` label series: merge its value into ``dst``
        (created on demand) and drop ``src``.  Bounds per-instance label
        growth — a fresh-executor-per-request or loader-per-epoch loop
        must not grow the registry forever — while preserving
        process-lifetime totals (``counter_totals()`` still sums the
        retired aggregate).  ``dst=None`` just drops the series (gauges:
        a dead instance's last value is meaningless)."""
        skey = tuple(str(src[n]) for n in self.labelnames)
        with self._mu:
            cell = self._series.pop(skey, None)
        if cell is None or dst is None:
            return
        dcell = self.labels(**dst)
        if isinstance(cell, _HistCell):
            counts, s, c = cell.snapshot()
            with dcell._mu:
                for i, n in enumerate(counts):
                    dcell.counts[i] += n
                dcell.sum += s
                dcell.count += c
        else:
            dcell.inc(cell.get())


class Counter(_Metric):
    kind = "counter"

    def inc(self, n=1, **labels):
        (self.labels(**labels) if labels or self.labelnames
         else self._default_cell()).inc(n)

    def value(self, **labels) -> float:
        """The series' count; given some of the label names only, the sum
        over the series that match them (a reader written before a label
        was added still reads its total)."""
        if labels and set(labels) < set(self.labelnames):
            want = {k: str(v) for k, v in labels.items()}
            return sum(cell.get() for kv, cell in self.series()
                       if all(kv[k] == v for k, v in want.items()))
        return (self.labels(**labels) if labels or self.labelnames
                else self._default_cell()).get()


class Gauge(_Metric):
    kind = "gauge"

    def set(self, v, **labels):
        (self.labels(**labels) if labels or self.labelnames
         else self._default_cell()).set(v)

    def inc(self, n=1, **labels):
        (self.labels(**labels) if labels or self.labelnames
         else self._default_cell()).inc(n)

    def value(self, **labels) -> float:
        return (self.labels(**labels) if labels or self.labelnames
                else self._default_cell()).get()


class Histogram(_Metric):
    kind = "histogram"

    def __init__(self, name, help="", labelnames=(),
                 buckets: Sequence[float] = DEFAULT_BUCKETS_US):
        super().__init__(name, help, labelnames)
        self.buckets = tuple(sorted(float(b) for b in buckets))

    def _new_cell(self):
        return _HistCell(self.buckets)

    def observe(self, v: float, **labels):
        (self.labels(**labels) if labels or self.labelnames
         else self._default_cell()).observe(v)


class MetricsRegistry:
    """Get-or-create metric families; collect/export them all."""

    def __init__(self):
        self._mu = threading.Lock()
        self._metrics: "collections.OrderedDict[str, _Metric]" = \
            collections.OrderedDict()  # guarded-by: _mu

    def _get_or_create(self, cls, name, help, labelnames, **kw):
        with self._mu:
            m = self._metrics.get(name)
            if m is None:
                m = self._metrics[name] = cls(name, help, labelnames, **kw)
                return m
        if not isinstance(m, cls) or tuple(labelnames) != m.labelnames:
            raise ValueError(
                f"metric {name!r} already registered as {m.kind} with "
                f"labels {m.labelnames}")
        if "buckets" in kw and tuple(
                sorted(float(b) for b in kw["buckets"])) != m.buckets:
            # a silent bucket mismatch would bin the second caller's
            # observations into limits it never asked for
            raise ValueError(
                f"histogram {name!r} already registered with buckets "
                f"{m.buckets}")
        return m

    def counter(self, name, help="", labelnames=()) -> Counter:
        return self._get_or_create(Counter, name, help, labelnames)

    def gauge(self, name, help="", labelnames=()) -> Gauge:
        return self._get_or_create(Gauge, name, help, labelnames)

    def histogram(self, name, help="", labelnames=(),
                  buckets: Sequence[float] = DEFAULT_BUCKETS_US) -> Histogram:
        return self._get_or_create(Histogram, name, help, labelnames,
                                   buckets=buckets)

    def get(self, name) -> Optional[_Metric]:
        with self._mu:
            return self._metrics.get(name)

    def collect(self) -> List[Dict[str, Any]]:
        """Snapshot every metric family as a JSON-able dict."""
        with self._mu:
            metrics = list(self._metrics.values())
        out = []
        for m in metrics:
            series = []
            for labels, cell in m.series():
                if isinstance(cell, _HistCell):
                    counts, s, c = cell.snapshot()
                    series.append({"labels": labels,
                                   "buckets": list(m.buckets),
                                   "counts": counts, "sum": s, "count": c})
                else:
                    series.append({"labels": labels, "value": cell.get()})
            out.append({"name": m.name, "type": m.kind, "help": m.help,
                        "series": series})
        return out

    def to_json(self, indent=None) -> str:
        return json.dumps({"metrics": self.collect()}, indent=indent)

    def to_prometheus(self) -> str:
        """Prometheus text exposition format (version 0.0.4)."""
        lines = []
        for m in self.collect():
            if m["help"]:
                lines.append(f"# HELP {m['name']} "
                             f"{_escape_help(m['help'])}")
            lines.append(f"# TYPE {m['name']} {m['type']}")
            for s in m["series"]:
                lbl = _fmt_labels(s["labels"])
                if m["type"] == "histogram":
                    cum = 0
                    for b, c in zip(s["buckets"], s["counts"]):
                        cum += c
                        lines.append(
                            f"{m['name']}_bucket"
                            f"{_fmt_labels(s['labels'], le=_fmt_float(b))} "
                            f"{cum}")
                    cum += s["counts"][-1]
                    lines.append(f"{m['name']}_bucket"
                                 f"{_fmt_labels(s['labels'], le='+Inf')} "
                                 f"{cum}")
                    lines.append(f"{m['name']}_sum{lbl} "
                                 f"{_fmt_float(s['sum'])}")
                    lines.append(f"{m['name']}_count{lbl} {s['count']}")
                else:
                    lines.append(f"{m['name']}{lbl} "
                                 f"{_fmt_float(s['value'])}")
        return "\n".join(lines) + "\n"

    def reset(self):
        """Zero every series (testing/bench isolation; keeps families)."""
        with self._mu:
            metrics = list(self._metrics.values())
        for m in metrics:
            m.reset()


def _fmt_float(v) -> str:
    if isinstance(v, str):
        return v
    if float(v) == int(v) and abs(v) < 1e15:
        return str(int(v))
    return repr(float(v))


def _escape_help(s: str) -> str:
    return s.replace("\\", r"\\").replace("\n", r"\n")


def _escape_label(s: str) -> str:
    return s.replace("\\", r"\\").replace('"', r'\"').replace("\n", r"\n")


def _fmt_labels(labels: Dict[str, str], **extra) -> str:
    items = list(labels.items()) + list(extra.items())
    if not items:
        return ""
    body = ",".join(f'{k}="{_escape_label(str(v))}"' for k, v in items)
    return "{" + body + "}"


#: the process-wide default registry — the executor's dispatch counters,
#: the dataloader gauges, and the compile/collective telemetry all live
#: here, so one export covers the whole runtime
REGISTRY = MetricsRegistry()


# ---------------------------------------------------------------------------
# gang liveness plane (distributed/coordinator.py).  Declared HERE rather
# than in the coordinator module because both sides of the socket bump the
# same families — the coordinator server (hosted by the launcher or a
# rank-0 side thread) and every rank's GangClient — and the launcher
# process imports monitor anyway for its export path.
# ---------------------------------------------------------------------------

GANG_HB_CTR = REGISTRY.counter(
    "paddle_tpu_gang_heartbeats_total",
    "gang heartbeats, by role ('client' = a rank's GangClient sent one, "
    "'coordinator' = the coordinator served one)", ("role",))
GANG_DEATH_CTR = REGISTRY.counter(
    "paddle_tpu_gang_rank_deaths_total",
    "ranks declared dead by the coordinator's liveness scan (missed "
    "FLAGS_gang_heartbeat_timeout_s of heartbeats)")
GANG_REJOIN_CTR = REGISTRY.counter(
    "paddle_tpu_gang_rejoins_total",
    "previously-dead ranks re-admitted to the gang (the elastic "
    "--max_restarts respawn path)")
GANG_DEGRADED_GAUGE = REGISTRY.gauge(
    "paddle_tpu_gang_degraded",
    "1 while at least one rank of the gang is dead (coordinator-side "
    "view; survivors should be draining/parked, not training)")
GANG_FP_CTR = REGISTRY.counter(
    "paddle_tpu_gang_fingerprint_mismatch_total",
    "cross-rank collective-fingerprint mismatches detected (heartbeat "
    "exchange or step-barrier refusal) — each one is a divergence that "
    "would otherwise hang inside a collective")

# -- gang metrics digests (this PR): every rank's heartbeat carries a
# compact, byte-capped digest of its runtime metrics (step-time estimate, MFU,
# queue occupancy, in-flight depth); the coordinator folds the digests
# into the gang-level skew/straggler series below and per-rank series a
# `tools/gangtop.py` table renders live.  Declared here for the same
# reason as the families above: both socket ends touch them.

#: serialized digest size cap: a gang control frame stays tiny by
#: contract — the client drops keys to fit, and the coordinator CAPS
#: anything still over with the same priority-ordered dropping
#: (counted; a compat guard against a future client stuffing the
#: liveness plane)
DIGEST_MAX_BYTES = 512

GANG_RANK_STEP_MS = REGISTRY.gauge(
    "paddle_tpu_gang_rank_step_ms",
    "per-rank step-time estimate (ms) from the heartbeat digest", ("rank",))
GANG_RANK_MFU = REGISTRY.gauge(
    "paddle_tpu_gang_rank_mfu",
    "per-rank live MFU from the heartbeat digest", ("rank",))
GANG_RANK_QUEUE = REGISTRY.gauge(
    "paddle_tpu_gang_rank_queue_depth",
    "per-rank dataloader prefetch-queue depth from the heartbeat "
    "digest", ("rank",))
GANG_RANK_INFLIGHT = REGISTRY.gauge(
    "paddle_tpu_gang_rank_inflight",
    "per-rank executor in-flight step depth from the heartbeat digest",
    ("rank",))
GANG_RANK_SRVQ = REGISTRY.gauge(
    "paddle_tpu_gang_rank_serving_queue_depth",
    "per-rank serving queue depth (queued + in-flight requests across "
    "tenants) from the heartbeat digest — the primary least-loaded "
    "routing signal for a serving fleet", ("rank",))
GANG_RANK_OCC = REGISTRY.gauge(
    "paddle_tpu_gang_rank_batch_occupancy",
    "per-rank most-recent dispatched-batch occupancy (real requests per "
    "batch) from the heartbeat digest", ("rank",))
GANG_RANK_FREE_SLOTS = REGISTRY.gauge(
    "paddle_tpu_gang_rank_free_decode_slots",
    "per-rank free KV decode slots from the heartbeat digest (0 = the "
    "replica's decode batch is full)", ("rank",))
GANG_RANK_TPS = REGISTRY.gauge(
    "paddle_tpu_gang_rank_tokens_per_s",
    "per-rank decode throughput (generated tokens/s, windowed) from the "
    "heartbeat digest", ("rank",))
GANG_RANK_GNORM = REGISTRY.gauge(
    "paddle_tpu_gang_rank_grad_norm",
    "per-rank global gradient L2 norm from the heartbeat digest "
    "(numerics plane 'gnorm' key) — a rank whose norm diverges from "
    "its peers is de-synced or about to blow up", ("rank",))
GANG_RANK_NANF = REGISTRY.gauge(
    "paddle_tpu_gang_rank_nonfinite",
    "per-rank cumulative non-finite element count from the heartbeat "
    "digest (numerics plane 'nanf' key) — nonzero on exactly one rank "
    "fingers the chip/input producing the NaNs", ("rank",))
GANG_RANK_COMM_MS = REGISTRY.gauge(
    "paddle_tpu_gang_rank_comm_ms",
    "per-rank measured comm time per collective step (ms, wait + wire) "
    "from the heartbeat digest (comms plane 'comm_ms' key)", ("rank",))
GANG_RANK_COMM_WAIT = REGISTRY.gauge(
    "paddle_tpu_gang_rank_comm_wait_ms",
    "per-rank straggler-wait part of the comm time (ms) from the "
    "heartbeat digest ('comm_wait') — the coordinator subtracts it "
    "from step_ms when picking the straggler, so a rank stalled on a "
    "slow peer never reads as the slow one", ("rank",))
GANG_RANK_COMM_BW = REGISTRY.gauge(
    "paddle_tpu_gang_rank_comm_bw",
    "per-rank measured collective bus bandwidth over link peak in "
    "[0,1] from the heartbeat digest ('comm_bw') — the network MFU "
    "column gangtop renders as BW%", ("rank",))
GANG_RANK_HBM = REGISTRY.gauge(
    "paddle_tpu_gang_rank_hbm_bytes",
    "per-rank measured live HBM bytes plus the dispatched block's "
    "compiled temporaries from the heartbeat digest (hbm plane 'hbm' "
    "key) — the fleet-wide residency view gangtop renders as the HBM "
    "column", ("rank",))
GANG_RANK_HDRM = REGISTRY.gauge(
    "paddle_tpu_gang_rank_hbm_headroom_bytes",
    "per-rank measured HBM headroom (budget - live - the dispatched "
    "block's compiled temporaries) from the heartbeat digest ('hdrm'; "
    "present only while the rank knows a budget) — the "
    "admission signal the GSPMD sharding chooser and an autoscaler "
    "read, and the gangtop HDRM%/OOM-RISK input", ("rank",))
GANG_DIGEST_CTR = REGISTRY.counter(
    "paddle_tpu_gang_digests_total",
    "heartbeat metrics digests accepted by the coordinator, per rank",
    ("rank",))
GANG_DIGEST_OVERSIZE_CTR = REGISTRY.counter(
    "paddle_tpu_gang_digest_oversize_total",
    "heartbeat digests that exceeded DIGEST_MAX_BYTES serialized and "
    "were CAPPED server-side with the same priority-ordered key "
    "dropping the client applies (the surviving keys still feed the "
    "per-rank gauges; the beat itself is always accepted — liveness "
    "never rides on digest validity)")
GANG_STEP_SKEW_GAUGE = REGISTRY.gauge(
    "paddle_tpu_gang_step_skew",
    "max-min current training step across LIVE ranks (degraded-aware: "
    "dead and departed ranks are excluded) — sustained growth names a "
    "straggler or a wedged rank")
GANG_STEP_TIME_SKEW_GAUGE = REGISTRY.gauge(
    "paddle_tpu_gang_step_time_skew_ms",
    "max-min per-rank step-time estimate (ms) across live ranks with "
    "digests — the throughput form of the step skew")
GANG_STRAGGLER_GAUGE = REGISTRY.gauge(
    "paddle_tpu_gang_straggler_rank",
    "rank id with the slowest step-time estimate among live ranks (-1 when "
    "no digests have arrived) — the rank gangtop flags")
GANG_STRAGGLER_MS_GAUGE = REGISTRY.gauge(
    "paddle_tpu_gang_straggler_step_ms",
    "the straggler rank's step-time estimate (ms)")

# -- serving fleet + coordinator HA (this PR): the router's reroute
# ledger, the per-replica placement-state gauge, the failover latency
# surface, and the epoch-fencing counters.  Declared here because both
# the router process and the coordinator processes touch them (the
# same one-home rule as the gang families above).
FLEET_REROUTE_CTR = REGISTRY.counter(
    "paddle_tpu_fleet_reroutes_total",
    "requests the FleetRouter moved off their placed replica, by reason "
    "(drain = the replica refused admission while draining; dead = the "
    "forward hit a transport error; circuit = the replica's breaker was "
    "open at placement; error = the replica failed the request "
    "non-transiently) — the chaos-drill ledger: completed requests = "
    "first-try successes + exactly these", ("reason",))
FLEET_REPLICA_STATE = REGISTRY.gauge(
    "paddle_tpu_fleet_replica_state",
    "router's placement view of each replica: 0=up 1=draining 2=dead "
    "3=stale (load digest older than FLAGS_fleet_digest_ttl_s — held "
    "out of least-loaded placement until it proves liveness again)",
    ("replica",))
FLEET_FAILOVER_HIST = REGISTRY.histogram(
    "paddle_tpu_fleet_failover_ms",
    "wall ms from a forward/coordinator failure to the request landing "
    "on a healthy target (router reroutes and gang-client coordinator "
    "failovers both observe here) — the p99 the chaos gate bounds",
    buckets=(1.0, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0, 500.0, 1000.0,
             2500.0, 5000.0, 15000.0, 60000.0))
COORD_EPOCH_GAUGE = REGISTRY.gauge(
    "paddle_tpu_coordinator_epoch",
    "this coordinator's leadership epoch (bumped by each standby "
    "promotion; the fencing token a zombie primary's manifest writes "
    "are refused against)")
COORD_FENCED_CTR = REGISTRY.counter(
    "paddle_tpu_coordinator_fenced_total",
    "operations refused by epoch fencing, by path (frame = a request "
    "carried a newer epoch than this coordinator's — it is a zombie; "
    "manifest = a mirror write observed a newer epoch in the EPOCH "
    "file and was dropped)", ("path",))
COORD_FAILOVER_CTR = REGISTRY.counter(
    "paddle_tpu_coordinator_failovers_total",
    "standby-to-primary promotions performed by this process")

# -- fleet autoscaler (this PR): the closed-loop controller's decision
# ledger.  Every target change is exactly one count here (spawn retries
# after a failed launch do NOT recount — the chaos drill asserts the
# ledger is oscillation-free), so dir=up{reason=burn_queue} after a load
# spike reads exactly 1.
FLEET_SCALE_CTR = REGISTRY.counter(
    "paddle_tpu_fleet_scale_total",
    "autoscaler scale decisions, by direction and reason (up/burn_queue "
    "= sustained SLO burn + queue pressure raised the target; up/death "
    "= a dead replica is being replaced to restore the target; "
    "up/oom = a replica that kept breaching headroom after its bucket "
    "shrink is being respawned fresh; down/idle = sustained idle "
    "drained-and-retired one) — counted once per decision, never per "
    "spawn attempt", ("dir", "reason"))
FLEET_TARGET_GAUGE = REGISTRY.gauge(
    "paddle_tpu_fleet_target_replicas",
    "the autoscaler's current target fleet size (clamped to "
    "[FLAGS_fleet_min_replicas, FLAGS_fleet_max_replicas])")
FLEET_SIZE_GAUGE = REGISTRY.gauge(
    "paddle_tpu_fleet_live_replicas",
    "replicas the router currently counts as placeable (up or stale — "
    "draining and dead replicas are out); TGT vs SIZE is the gangtop "
    "footer")
FLEET_SHED_GAUGE = REGISTRY.gauge(
    "paddle_tpu_fleet_shedding",
    "1 while the autoscaler has engaged fleet-wide admission shedding "
    "(SLO breach sustained past FLAGS_fleet_shed_after_ticks with a "
    "spawn in flight or the fleet at max), else 0")
FLEET_SHRINK_CTR = REGISTRY.counter(
    "paddle_tpu_fleet_width_shrinks_total",
    "bucket-width shrink control ops the autoscaler sent to replicas "
    "reporting HBM headroom under FLAGS_fleet_oom_headroom_frac (the "
    "degradation ladder's first rung; the replica is named in the "
    "autoscaler.shrink trace instant)")


def metrics_digest() -> Dict[str, Any]:
    """Compact snapshot of THIS rank's runtime health for the gang
    heartbeat: step-time estimate + live MFU (the newest live executor's
    ``paddle_tpu_step_device_ms``/``paddle_tpu_step_mfu`` series),
    dataloader queue depth, executor in-flight depth, and total steps
    dispatched.  Reads a handful of specific families — never a full
    registry collect — so the heartbeat thread stays cheap."""
    digest: Dict[str, Any] = {}

    def newest_executor_series(name):
        fam = REGISTRY.get(name)
        if fam is None:
            return None
        best, best_serial = None, -1
        for labels, cell in fam.series():
            try:
                serial = int(labels.get("executor", -1))
            except (TypeError, ValueError):
                continue                  # the "retired" fold series
            if serial > best_serial:
                best_serial, best = serial, cell.get()
        return best

    ms = newest_executor_series("paddle_tpu_step_device_ms")
    if ms is not None:
        digest["step_ms"] = round(float(ms), 3)
    mfu = newest_executor_series("paddle_tpu_step_mfu")
    if mfu is not None:
        digest["mfu"] = round(float(mfu), 5)
    # measured MFU (this PR): analytic flops over MEASURED device-busy
    # time from the last parsed profiler window — presence-gated on the
    # window summary having published RECENTLY (same frozen-value
    # discipline as the comms/hbm keys: a rank that stopped capturing
    # windows must not report its last measured MFU forever).
    if _measured_mfu_fresh():
        fam = REGISTRY.get("paddle_tpu_step_mfu_measured")
        if fam is not None:
            v = fam.value()
            if v:
                digest["mfu_m"] = round(float(v), 5)
    qd = REGISTRY.get("paddle_tpu_dataloader_queue_depth")
    if qd is not None:
        vals = [cell.get() for labels, cell in qd.series()
                if labels.get("pipeline") != "retired"]
        if vals:
            digest["queue"] = float(sum(vals))
    steps_fam = REGISTRY.get("paddle_tpu_executor_steps_dispatched")
    if steps_fam is not None:
        total = sum(cell.get() for _, cell in steps_fam.series())
        if total:
            digest["steps"] = int(total)
    try:
        from .framework.executor import _EXECUTORS
        digest["inflight"] = int(sum(
            len(e._inflight) for e in list(_EXECUTORS)))
    except Exception:
        pass
    # serving load (this PR): the per-replica signals the fleet
    # router/autoscaler consumes — queue depth across tenants, the last
    # dispatched batch's occupancy, free decode slots, and decode
    # tokens/s.  Presence-gated on the series existing AND on the
    # scheduler loops having proven liveness within
    # FLAGS_fleet_digest_ttl_s (the aging discipline every other plane
    # already has): a wedged scheduler's last-known-good load digest
    # would otherwise read as an attractively idle replica to a
    # least-loaded router forever — exactly the replica that must drop
    # out of placement.
    if _serving_digest_fresh():
        sq = REGISTRY.get("paddle_tpu_serving_queue_depth")
        if sq is not None:
            vals = [cell.get() for labels, cell in sq.series()
                    if labels.get("tenant") != "retired"]
            if vals:
                digest["srv_q"] = float(sum(vals))
        for key, fam_name in (
                ("occ", "paddle_tpu_serving_last_batch_occupancy"),
                ("slots", "paddle_tpu_serving_free_decode_slots"),
                ("tps", "paddle_tpu_serving_tokens_per_s")):
            fam = REGISTRY.get(fam_name)
            if fam is not None:
                cells = [cell.get() for _, cell in fam.series()]
                if cells:
                    digest[key] = round(float(cells[-1]), 3)
    # numerics plane (this PR): global grad norm + cumulative non-finite
    # count, presence-gated on the numerics engine having published —
    # the fleet-wide "which rank is producing NaNs" signal.  nanf rides
    # whenever gnorm does (a healthy 0 is the signal's baseline).
    gn = REGISTRY.get("paddle_tpu_numerics_global_grad_norm")
    if gn is not None:
        cells = [cell.get() for _, cell in gn.series()]
        if cells:
            digest["gnorm"] = round(float(cells[-1]), 4)
            nf = REGISTRY.get("paddle_tpu_numerics_nonfinite_total")
            if nf is not None:
                digest["nanf"] = int(sum(
                    cell.get() for _, cell in nf.series()))
    # comms plane (this PR): measured comm time per collective step,
    # its straggler-wait part, and the bus-bandwidth gauge — presence-
    # gated on the comms monitor having published RECENTLY, so a rank
    # that never dispatches collectives carries none of them and a rank
    # that STOPPED dispatching them ages out instead of haunting the
    # net-of-wait straggler math with frozen medians (a stale comm_wait
    # would excuse a genuinely slow rank forever).  comm_wait rides
    # whenever comm_ms does (a measured 0 is the signal's baseline).
    # hbm plane: measured live bytes (with the dispatched block's compiled
    # temporaries: hbm + hdrm is the budget) + headroom — presence-
    # gated on the accountant having published RECENTLY (same frozen-
    # value discipline as the comms keys: a rank that stopped sampling
    # must not read as holding its last-known residency forever).
    # hdrm rides only when the rank knows a budget — a budget-less
    # rank's headroom is undefined, not zero.
    if _hbm_digest_fresh():
        mod = sys.modules.get("paddle_tpu.hbm")
        sample = getattr(mod.ACCOUNTANT, "last_sample", None) \
            if mod is not None else None
        if sample is not None:
            live, headroom = sample
            digest["hbm"] = int(live)
            if headroom is not None:
                digest["hdrm"] = int(headroom)
    cm = REGISTRY.get("paddle_tpu_comm_step_ms")
    if cm is not None and _comm_digest_fresh():
        cells = [cell.get() for _, cell in cm.series()]
        if cells:
            digest["comm_ms"] = round(float(cells[-1]), 3)
            cw = REGISTRY.get("paddle_tpu_comm_wait_ms")
            if cw is not None:
                wcells = [cell.get() for _, cell in cw.series()]
                if wcells:
                    digest["comm_wait"] = round(float(wcells[-1]), 3)
            bw = REGISTRY.get("paddle_tpu_collective_bus_bw")
            if bw is not None:
                bcells = [cell.get() for _, cell in bw.series()]
                if bcells:
                    digest["comm_bw"] = round(float(bcells[-1]), 5)
    return digest


#: how long the comm_* digest keys outlive the comms monitor's last
#: gauge publish.  Generous on purpose — a giant-model step can take a
#: minute — and degradation is safe: once the keys drop, straggler
#: selection falls back to raw step_ms (the pre-comms behavior).
_COMM_DIGEST_TTL_S = 120.0


def _comm_digest_fresh() -> bool:
    mod = sys.modules.get("paddle_tpu.analysis.comms")
    if mod is None:
        return False                # plane never loaded: nothing to carry
    last = getattr(mod.MONITOR, "last_publish_wall", 0.0)
    return bool(last) and time.time() - last <= _COMM_DIGEST_TTL_S


def _hbm_digest_fresh() -> bool:
    mod = sys.modules.get("paddle_tpu.hbm")
    if mod is None:
        return False                # plane never loaded: nothing to carry
    last = getattr(mod.ACCOUNTANT, "last_publish_wall", 0.0)
    return bool(last) and time.time() - last <= _COMM_DIGEST_TTL_S


#: mfu_m freshness window — much longer than the comms/hbm TTL because
#: profiler windows are SPARSE by design (every_n steps apart, or only
#: on regression/anomaly triggers); a measurement from the last few
#: minutes is still the rank's best measured truth
_MFU_MEASURED_TTL_S = 600.0


def _measured_mfu_fresh() -> bool:
    mod = sys.modules.get("paddle_tpu.analysis.device_profile")
    if mod is None:
        return False                # plane never loaded: nothing to carry
    last = getattr(mod, "last_publish_wall", 0.0)
    return bool(last) and time.time() - last <= _MFU_MEASURED_TTL_S


def _serving_digest_fresh() -> bool:
    """The srv_q/occ/slots/tps keys ride only while a serving scheduler
    loop (batcher dispatch or decode iteration) has woken within
    FLAGS_fleet_digest_ttl_s.  Liveness, not traffic: an IDLE healthy
    replica keeps beating (its loops wake on the coalescing timeout)
    and stays the most attractive placement, while a scheduler wedged
    inside a dispatch stops touching the wall and ages out."""
    mod = sys.modules.get("paddle_tpu.serving.scheduler")
    if mod is None:
        return False                # plane never loaded: nothing to carry
    last = getattr(mod, "last_alive_wall", 0.0)
    try:
        from .flags import get_flags
        ttl = float(get_flags("FLAGS_fleet_digest_ttl_s")
                    ["FLAGS_fleet_digest_ttl_s"])
    except Exception:
        ttl = 10.0
    return bool(last) and time.time() - last <= ttl


#: digest keys the gang skew/straggler plane reads, most important
#: first — capped_digest sheds from the BOTTOM of this list, and sheds
#: keys not on it before any that are.  comm_wait rides right behind
#: step_ms: the two TOGETHER are the straggler input (the coordinator
#: picks the straggler net of comm wait, so shedding comm_wait while
#: keeping step_ms would mis-blame the waiting rank).  nanf/gnorm rank
#: next: a NaN'ing rank must stay identifiable fleet-wide even under
#: the byte cap, and hbm/hdrm right after — a rank about to OOM must
#: stay identifiable too.  hbm BEFORE hdrm: gangtop's HDRM%/OOM-RISK
#: need BOTH keys (budget = hbm + hdrm), so if the cap cuts between
#: them the surviving key must be the one that renders alone (the HBM
#: residency column) — a lone hdrm would render nothing.
_DIGEST_PRIORITY = ("step_ms", "comm_wait", "nanf", "gnorm", "hbm",
                    "hdrm", "mfu", "mfu_m", "comm_ms", "comm_bw",
                    "srv_q", "queue", "inflight", "occ", "slots", "tps",
                    "steps")


def capped_digest(digest: Dict[str, Any],
                  max_bytes: int = DIGEST_MAX_BYTES) -> Dict[str, Any]:
    """Enforce the serialized digest byte cap by dropping keys until
    the JSON fits: unknown extras first (reverse-sorted, so the order
    is deterministic), then known keys from least to most important —
    ``step_ms``, the input the whole straggler plane runs on, is the
    LAST to go.  Both socket ends use it: the client caps before
    sending, and the coordinator re-applies it to anything still over
    (counted in ``paddle_tpu_gang_digest_oversize_total``) instead of
    refusing the digest."""
    d = dict(digest)
    while d and len(json.dumps(d, sort_keys=True)) > max_bytes:
        extras = sorted((k for k in d if k not in _DIGEST_PRIORITY),
                        reverse=True)
        if extras:
            d.pop(extras[0])
        else:
            d.pop(next(k for k in reversed(_DIGEST_PRIORITY) if k in d))
    return d


# -- serving tenant plane (paddle_tpu.serving): per-tenant label series
# of the request server.  Declared here (like the gang families above)
# because the server, the scheduler thread, and the retirement helper
# below all touch them, and `retire_tenant_series` must see the exact
# family objects to fold.  Tenant churn retires through
# `retire_tenant_series`, so a revolving tenant population cannot grow
# the registry unbounded while `counter_totals()` stays exact.

SERVING_REQ_CTR = REGISTRY.counter(
    "paddle_tpu_serving_requests_total",
    "requests ADMITTED into the serving queue, per tenant", ("tenant",))
SERVING_DONE_CTR = REGISTRY.counter(
    "paddle_tpu_serving_completed_total",
    "requests completed (future resolved with a result), per tenant",
    ("tenant",))
SERVING_FAIL_CTR = REGISTRY.counter(
    "paddle_tpu_serving_failed_total",
    "requests failed (future resolved with an error), per tenant",
    ("tenant",))
SERVING_REJECT_CTR = REGISTRY.counter(
    "paddle_tpu_serving_rejected_total",
    "requests refused at admission, per tenant and reason "
    "(quota / draining / too_long)", ("tenant", "reason"))
SERVING_QUEUE_GAUGE = REGISTRY.gauge(
    "paddle_tpu_serving_queue_depth",
    "requests currently queued + in flight, per tenant", ("tenant",))
SERVING_LAT_HIST = REGISTRY.histogram(
    "paddle_tpu_serving_latency_ms",
    "end-to-end request latency (submit -> future resolved), ms, per "
    "tenant", ("tenant",),
    buckets=(1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0, 500.0,
             1000.0, 2500.0, 5000.0, 10000.0, 30000.0, 120000.0))

# -- request-path tracing + SLO plane (this PR): the serving pipeline's
# per-phase latency decomposition and the per-tenant burn-rate gauges.
# Declared here (like the families above) so retire_tenant_series can
# fold tenant churn and metrics_digest can read the load gauges.

SERVING_PHASE_HIST = REGISTRY.histogram(
    "paddle_tpu_serving_phase_ms",
    "per-request phase latency (ms) of the serving pipeline by phase "
    "(admit / queue_wait / batch_wait / dispatch / decode / "
    "materialize), tenant and bucket (bucket='decode' for the KV decode "
    "loop) — phases partition submit->resolve, so their sum is the "
    "request's end-to-end latency and p99 decomposes by phase",
    ("phase", "tenant", "bucket"),
    buckets=(0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0,
             500.0, 1000.0, 2500.0, 5000.0, 10000.0, 30000.0))
SERVING_LAST_OCC_GAUGE = REGISTRY.gauge(
    "paddle_tpu_serving_last_batch_occupancy",
    "occupancy (real requests) of the most recently dispatched serving "
    "batch / decode iteration — the instantaneous load form of the "
    "paddle_tpu_serving_batch_occupancy histogram, carried in the gang "
    "heartbeat digest as 'occ'")
SERVING_FREE_SLOTS_GAUGE = REGISTRY.gauge(
    "paddle_tpu_serving_free_decode_slots",
    "KV decode slots currently unoccupied (digest key 'slots'; 0 = the "
    "decode batch is full and new requests queue)")
SERVING_TPS_GAUGE = REGISTRY.gauge(
    "paddle_tpu_serving_tokens_per_s",
    "decode throughput: generated tokens per second over a short "
    "trailing window (digest key 'tps')")
SERVING_TOKENS_CTR = REGISTRY.counter(
    "paddle_tpu_serving_generated_tokens_total",
    "tokens generated by the decode loop (prefill consumption excluded)")

SLO_BURN_GAUGE = REGISTRY.gauge(
    "paddle_tpu_slo_burn_rate",
    "per-tenant SLO error-budget burn rate, by window ('fast' / "
    "'slow'): (bad-event fraction in the window) / (1 - objective) — "
    "1.0 means the budget is consumed exactly at the rate the SLO "
    "allows, a sustained burn above the threshold on BOTH windows is a "
    "breach", ("tenant", "window"))
SLO_BREACHED_GAUGE = REGISTRY.gauge(
    "paddle_tpu_slo_breached",
    "1 while the tenant's SLO is in breach (multi-window burn rate over "
    "threshold; clears with hysteresis at threshold/2 on the fast "
    "window)", ("tenant",))
SLO_BREACH_CTR = REGISTRY.counter(
    "paddle_tpu_slo_breach_total",
    "SLO breach EVENTS per tenant (each breach->recovery cycle counts "
    "once; the instant is also recorded in the trace ring as "
    "'slo.breach')", ("tenant",))

# -- per-tenant KV-page plane (this PR): which tenant's decode requests
# own the paged-KV pool.  Declared here so retire_tenant_series folds
# tenant churn (PR-2 semantics: counter totals exact, gauges dropped).

SERVING_KV_TENANT_PAGES = REGISTRY.gauge(
    "paddle_tpu_serving_kv_tenant_pages",
    "KV-cache pages currently owned by the tenant's in-flight decode "
    "requests — the per-tenant occupancy slice of "
    "paddle_tpu_serving_kv_pages_in_use", ("tenant",))
SERVING_KV_TENANT_FRAG = REGISTRY.gauge(
    "paddle_tpu_serving_kv_tenant_frag",
    "internal fragmentation of the tenant's KV pages in [0,1]: "
    "1 - written_tokens / (pages * page_len) — reserved-but-unwritten "
    "tail capacity (worst-case admission reservations inflate it early "
    "in a request's life)", ("tenant",))
SERVING_KV_TENANT_ALLOC_CTR = REGISTRY.counter(
    "paddle_tpu_serving_kv_tenant_pages_total",
    "KV pages RESERVED for the tenant's requests at admission, "
    "cumulative (folds to tenant=\"retired\" on eviction so "
    "counter_totals() stays exact across tenant churn)", ("tenant",))


def retire_tenant_series(tenant) -> None:
    """Registry hygiene for tenant eviction (PR-2 retirement semantics):
    the tenant's counter/histogram series fold into ``tenant="retired"``
    (process totals stay exact — ``counter_totals()`` sums the retired
    aggregate) and its queue-depth gauge is dropped (a departed tenant
    has no queue)."""
    src = {"tenant": str(tenant)}
    dst = {"tenant": "retired"}
    SERVING_REQ_CTR.fold(src, dst)
    SERVING_DONE_CTR.fold(src, dst)
    SERVING_FAIL_CTR.fold(src, dst)
    SERVING_LAT_HIST.fold(src, dst)
    for labels, _cell in SERVING_REJECT_CTR.series():
        if labels.get("tenant") == str(tenant):
            SERVING_REJECT_CTR.fold(
                labels, {"tenant": "retired",
                         "reason": labels.get("reason", "")})
    for labels, _cell in SERVING_PHASE_HIST.series():
        if labels.get("tenant") == str(tenant):
            SERVING_PHASE_HIST.fold(labels, dict(labels, tenant="retired"))
    SERVING_QUEUE_GAUGE.fold(src, None)
    # KV-page plane: the cumulative reservation counter folds (totals
    # exact); the occupancy/fragmentation gauges drop — a departed
    # tenant owns no pages
    SERVING_KV_TENANT_ALLOC_CTR.fold(src, dst)
    SERVING_KV_TENANT_PAGES.fold(src, None)
    SERVING_KV_TENANT_FRAG.fold(src, None)
    # SLO series: the breach-event counter folds (totals stay exact);
    # the burn/breached gauges drop — a departed tenant has no burn
    SLO_BREACH_CTR.fold(src, dst)
    SLO_BREACHED_GAUGE.fold(src, None)
    for labels, _cell in SLO_BURN_GAUGE.series():
        if labels.get("tenant") == str(tenant):
            SLO_BURN_GAUGE.fold(labels, None)


def retire_gang_rank_series(rank) -> None:
    """Registry hygiene when a rank dies or departs: its digest counter
    folds into ``rank="retired"`` (process totals stay exact — PR 2's
    retirement semantics) and its gauge series are dropped (a dead
    rank's last step time is meaningless, and an elastic gang respawning
    ranks must not grow the registry per incarnation)."""
    src = {"rank": str(rank)}
    GANG_DIGEST_CTR.fold(src, {"rank": "retired"})
    for g in (GANG_RANK_STEP_MS, GANG_RANK_MFU, GANG_RANK_QUEUE,
              GANG_RANK_INFLIGHT, GANG_RANK_SRVQ, GANG_RANK_OCC,
              GANG_RANK_FREE_SLOTS, GANG_RANK_TPS, GANG_RANK_GNORM,
              GANG_RANK_NANF, GANG_RANK_COMM_MS, GANG_RANK_COMM_WAIT,
              GANG_RANK_COMM_BW, GANG_RANK_HBM, GANG_RANK_HDRM):
        g.fold(src, None)


# ---------------------------------------------------------------------------
# step tracer
# ---------------------------------------------------------------------------

class StepTracer:
    """Bounded ring of chrome-trace events for the async step pipeline.

    Events are stored as tuples (ph, name, cat, tid, t_start, dur, args)
    with perf_counter timestamps; chrome dicts are built only at export.
    ``enabled`` is a plain bool so hot paths can guard with one attribute
    load; recording itself is a deque append (thread-safe under the GIL,
    auto-capped so a long training run cannot grow host memory unbounded —
    the ring keeps the most recent events).
    """

    def __init__(self, max_events: int = 200_000):
        self._events: collections.deque = collections.deque(
            maxlen=max_events)  # guarded-by: _emu
        # guards the ring against export/resize racing producer-thread
        # appends (a deque append alone is GIL-atomic, but a capacity
        # swap or snapshot concurrent with appends is not)
        self._emu = threading.Lock()
        # epoch-aligned timebase: perf_counter gives monotonic durations,
        # the wall anchor lets multi-rank traces stack on one axis after
        # tools/timeline.py merges them
        self._perf0 = time.perf_counter()
        self._wall0 = time.time()
        self._tnames: Dict[int, str] = {}
        self.enabled = True

    # -- recording ----------------------------------------------------------
    def _tid(self) -> int:
        tid = threading.get_ident() & 0xffffff
        if tid not in self._tnames:
            self._tnames[tid] = threading.current_thread().name
        return tid

    def add_complete(self, name: str, cat: str, t_start: float,
                     t_end: float, args: Optional[dict] = None):
        """Record a complete span [t_start, t_end] (perf_counter seconds).
        The raw API for hot paths that already hold both timestamps."""
        if not self.enabled:
            return
        with self._emu:
            self._events.append(("X", name, cat, self._tid(), t_start,
                                 t_end - t_start, args))

    def instant(self, name: str, cat: str = "",
                args: Optional[dict] = None):
        if not self.enabled:
            return
        with self._emu:
            self._events.append(("i", name, cat, self._tid(),
                                 time.perf_counter(), 0.0, args))

    def counter(self, name: str, value: float, cat: str = ""):
        """Chrome counter track (e.g. dataloader queue depth over time).
        ``cat`` lets lane-routing consumers (tools/timeline.py re-homes
        ``cat == "memory"`` onto the per-rank hbm row) pick the track
        up; existing callers omit it."""
        if not self.enabled:
            return
        with self._emu:
            self._events.append(("C", name, cat, self._tid(),
                                 time.perf_counter(), 0.0,
                                 {"value": value}))

    @contextlib.contextmanager
    def span(self, name: str, cat: str = "", **args):
        if not self.enabled:
            yield
            return
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.add_complete(name, cat, t0, time.perf_counter(),
                              args or None)

    # -- export -------------------------------------------------------------
    def set_capacity(self, max_events: int):
        with self._emu:
            self._events = collections.deque(self._events,
                                             maxlen=int(max_events))

    def clear(self):
        with self._emu:
            self._events.clear()

    def __len__(self):
        with self._emu:
            return len(self._events)

    def _ts_us(self, t_perf: float) -> float:
        return (self._wall0 + (t_perf - self._perf0)) * 1e6

    def chrome_events(self) -> List[Dict[str, Any]]:
        """Build chrome://tracing event dicts (plus thread/process name
        metadata rows so the timeline is labeled)."""
        pid = os.getpid()
        out: List[Dict[str, Any]] = [{
            "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
            "args": {"name": f"paddle_tpu:{pid}"}}]
        for tid, tname in sorted(self._tnames.items()):
            out.append({"name": "thread_name", "ph": "M", "pid": pid,
                        "tid": tid, "args": {"name": tname}})
        for ph, name, cat, tid, t0, dur, args in list(self._events):
            ev: Dict[str, Any] = {"name": name, "ph": ph, "pid": pid,
                                  "tid": tid,
                                  "ts": round(self._ts_us(t0), 3)}
            if cat:
                ev["cat"] = cat
            if ph == "X":
                ev["dur"] = round(dur * 1e6, 3)
            if ph == "i":
                ev["s"] = "t"
            if args:
                ev["args"] = args
            out.append(ev)
        return out


TRACER = StepTracer()


def span(name: str, cat: str = "", **args):
    """``with monitor.span("executor.dispatch", "dispatch"): ...``"""
    return TRACER.span(name, cat, **args)


# ---------------------------------------------------------------------------
# snapshots + export
# ---------------------------------------------------------------------------

def telemetry_snapshot() -> Dict[str, float]:
    """Flatten the registry into {series_key: value} for easy diffing
    (two snapshots subtracted give a workload's deltas).  Histograms
    contribute ``<name>_sum`` and ``<name>_count`` per series."""
    flat: Dict[str, float] = {}
    for m in REGISTRY.collect():
        for s in m["series"]:
            key = m["name"] + _fmt_labels(s["labels"])
            if m["type"] == "histogram":
                flat[key + "_sum"] = s["sum"]
                flat[key + "_count"] = s["count"]
            else:
                flat[key] = s["value"]
    return flat


def counter_totals() -> Dict[str, float]:
    """Per-family totals summed across label series — the registry-level
    aggregate that survives executor garbage collection (the live-executor
    aggregate in ``profiler.dispatch_stats()`` drops executors when they
    die; these totals do not)."""
    out: Dict[str, float] = {}
    for m in REGISTRY.collect():
        if m["type"] == "histogram":
            out[m["name"] + "_sum"] = sum(s["sum"] for s in m["series"])
            out[m["name"] + "_count"] = sum(
                s["count"] for s in m["series"])
        else:
            out[m["name"]] = sum(s["value"] for s in m["series"])
    return out


def export(dirpath: str, trace: bool = True) -> Dict[str, str]:
    """Write ``metrics.json``, ``metrics.prom``, and (when ``trace``)
    ``trace.json`` under ``dirpath``; returns {kind: path}.  The trace file
    goes through ``profiler.chrome_trace`` so classic RecordEvent profiler
    events and tracer spans land in ONE timeline — feed per-rank files to
    ``tools/timeline.py`` to stack ranks."""
    os.makedirs(dirpath, exist_ok=True)
    paths = {}
    p = os.path.join(dirpath, "metrics.json")
    with open(p, "w") as f:
        f.write(REGISTRY.to_json(indent=1))
    paths["json"] = p
    p = os.path.join(dirpath, "metrics.prom")
    with open(p, "w") as f:
        f.write(REGISTRY.to_prometheus())
    paths["prom"] = p
    if trace:
        from . import profiler
        p = os.path.join(dirpath, "trace.json")
        profiler.chrome_trace(p)
        paths["trace"] = p
    return paths


_export_at_exit: List[str] = []


def enable_export_on_exit(dirpath: str):
    """FLAGS_telemetry_export_path hook: export once at process exit."""
    if not _export_at_exit:
        import atexit
        atexit.register(_exit_export)
    _export_at_exit[:] = [dirpath]


def disable_export_on_exit():
    """Disarm a previously-enabled at-exit export (flag set back to '')."""
    _export_at_exit[:] = []


def _exit_export():
    if _export_at_exit:
        try:
            export(_export_at_exit[0])
        except Exception:       # never let telemetry break interpreter exit
            pass


def _sync_from_flags():
    try:
        from .flags import get_flags
        fl = get_flags(["FLAGS_telemetry", "FLAGS_telemetry_max_events",
                        "FLAGS_telemetry_export_path"])
    except Exception:           # flags mid-bootstrap: side effects re-sync
        return
    TRACER.enabled = bool(fl["FLAGS_telemetry"])
    if int(fl["FLAGS_telemetry_max_events"]) != TRACER._events.maxlen:
        TRACER.set_capacity(int(fl["FLAGS_telemetry_max_events"]))
    if fl["FLAGS_telemetry_export_path"]:
        enable_export_on_exit(str(fl["FLAGS_telemetry_export_path"]))


_sync_from_flags()
