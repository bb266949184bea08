"""Runtime HBM observability plane — the live companion to the static
planner in ``analysis/memory.py``.

The PR-7 planner predicts step footprints (estimate-vs-measured
1.000–1.006 on the bench workloads) but nothing at runtime tracked live
bytes, attributed them, or explained an OOM after the fact.  This module
closes that gap with three pieces:

- :class:`HBMAccountant` — a per-step sampler fed by the executor at
  dispatch boundaries.  The training thread pays one bounded deque
  append; a daemon worker (the ``CommsMonitor`` discipline) samples the
  process's live device bytes OFF-thread, joins them against the static
  plan stamped on the dispatched program, and publishes the
  ``paddle_tpu_hbm_{live,peak,budget,headroom}_bytes`` gauges, a
  windowed peak watermark, a plan-vs-measured drift gauge, and a
  per-class attribution (params / optimizer state / activations+temps /
  in-flight lazy-fetch buffers / checkpoint-capture chunks / serving KV
  pages).  A headroom regression past
  ``FLAGS_hbm_headroom_regress_frac`` opens a profiler capture window
  (mirroring ``FLAGS_profile_sample_regress_frac``).

- **OOM forensics** (:func:`oom_forensics`) — on any
  ``RESOURCE_EXHAUSTED`` at compile or dispatch (and the ``memory.oom``
  fault-inject drill site), a watchdog-dump-style report: the static
  plan's live set at the peak op, the top-N tensors with sizes and
  lifetimes, explicit budget/plan/measured/requested arithmetic, the
  residency summary, and the serving memory census (bucket widths, KV
  page occupancy) when a server is registered.  Counted in
  ``paddle_tpu_oom_total{site}``, traced as a ``memory.oom`` instant,
  and each OOM triggers a :class:`~paddle_tpu.profiler.SamplingProfiler`
  window (``trigger:"oom"``).

- **One reader** — :func:`measure_live_bytes` is the canonical measured-
  bytes source; ``tools/hbm_smoke.py`` and the HBM tests read this module
  instead of a private measurement.

- **The compiled step's own plan** — live arrays cannot see a compiled
  step's temporaries, so the executor hands every block it compiles to
  :func:`record_compiled_plan` (always, where it compiles, at no
  compile's cost: JAX's caches serve the second lowering): the
  executable's ``memory_analysis()`` goes through :func:`record_xla_plan`
  into ``memory.hbm_plans()`` and the gauges
  ``paddle_tpu_step_hbm_plan_bytes{block, tag, part}`` /
  ``paddle_tpu_step_hbm_argument_bytes{block, tag, cls}``, and the
  accountant counts the plan's temporaries against the budget
  (``cls="step_temporaries"``; headroom = budget - live - temporaries).

Fleet-wide, the heartbeat digest carries ``hbm``/``hdrm`` keys folded
into ``paddle_tpu_gang_rank_hbm_*`` gauges, gangtop renders HBM/HDRM%
columns with an ``<-- OOM-RISK`` flag, and the measured headroom gauge is
the admission signal the GSPMD sharding-rule chooser (ROADMAP) consumes.
"""

from __future__ import annotations

import collections
import os
import re
import tempfile
import threading
import time
import weakref
from typing import Any, Dict, List, Optional

import numpy as np

from . import memory as _memory
from . import monitor as _monitor

__all__ = [
    "HBMAccountant", "ACCOUNTANT", "measure_live_bytes", "budget_bytes",
    "oom_forensics", "record_xla_plan", "record_compiled_plan",
    "compiled_plan", "argument_classes", "PLAN_PARTS", "ARGUMENT_CLASSES",
    "set_ckpt_capture_bytes", "register_kv_pool", "register_census",
    "serving_census", "OOM_RISK_HEADROOM_FRAC",
]

# ---------------------------------------------------------------------------
# metric families
# ---------------------------------------------------------------------------

HBM_LIVE_GAUGE = _monitor.REGISTRY.gauge(
    "paddle_tpu_hbm_live_bytes",
    "measured live device bytes at the most recent sampled step "
    "boundary (the runtime counterpart of the static planner's "
    "steady_bytes)")
HBM_PEAK_GAUGE = _monitor.REGISTRY.gauge(
    "paddle_tpu_hbm_peak_bytes",
    "windowed peak watermark of the live-bytes samples (max over the "
    "last FLAGS_hbm_window samples) — the number to compare against "
    "the budget when deciding if a spike was close")
HBM_BUDGET_GAUGE = _monitor.REGISTRY.gauge(
    "paddle_tpu_hbm_budget_bytes",
    "the HBM budget in force: FLAGS_memory_budget_mb when set, else "
    "the device allocator's bytes_limit where the backend exposes one "
    "(0 = no budget known; headroom is then unpublished)")
HBM_HEADROOM_GAUGE = _monitor.REGISTRY.gauge(
    "paddle_tpu_hbm_headroom_bytes",
    "budget - live - step_temporaries at the most recent sample "
    "(published only while a budget is known): what is free once the "
    "live arrays and the dispatched block's compiled temporaries region "
    "are counted — the measured admission signal the GSPMD sharding "
    "chooser and the serving width admission consume")
HBM_DRIFT_GAUGE = _monitor.REGISTRY.gauge(
    "paddle_tpu_hbm_plan_drift",
    "measured live bytes over the static plan's steady_bytes for the "
    "most recently dispatched program (1.0 = the planner models the "
    "step exactly; sustained drift means unmodeled residency — a leak, "
    "a foreign allocator, or a planner gap)")
HBM_CLASS_GAUGE = _monitor.REGISTRY.gauge(
    "paddle_tpu_hbm_class_bytes",
    "live-byte attribution by class at the most recent sample: "
    "params / opt_state (non-parameter persistables: moments, BN "
    "stats) / activations (live arrays outside the named classes: "
    "resident batches, fetch buffers, other programs' state — NOT a "
    "compiled step's temporaries, which are in no live array) / "
    "step_temporaries (the temporaries region of the dispatched "
    "block's compiled plan: what the forward keeps for the backward, "
    "gradients, casts, scratch; 0 until the block's plan is recorded) "
    "/ lazy_fetch (in-flight throttle probes) / ckpt_capture "
    "(checkpoint snapshot copies in flight) / kv_pages (serving "
    "paged-KV pools)", ("cls",))
OOM_CTR = _monitor.REGISTRY.counter(
    "paddle_tpu_oom_total",
    "RESOURCE_EXHAUSTED events that went through OOM forensics, by "
    "site ('dispatch' = a real OOM out of a dispatched/compiling step, "
    "'injected' = the memory.oom fault drill)", ("site",))
HBM_SAMPLES_CTR = _monitor.REGISTRY.counter(
    "paddle_tpu_hbm_samples_total",
    "accountant samples by outcome ('ok' published, 'dropped' shed "
    "under backlog — gauges skip a beat, nothing blocks, 'error' the "
    "sample itself failed)", ("outcome",))
_SAMPLE_OK = HBM_SAMPLES_CTR.labels(outcome="ok")
_SAMPLE_DROPPED = HBM_SAMPLES_CTR.labels(outcome="dropped")
_SAMPLE_ERROR = HBM_SAMPLES_CTR.labels(outcome="error")

#: gangtop flags a rank <-- OOM-RISK when its measured headroom fraction
#: (hdrm / budget) falls under this (mirrored in tools/gangtop.py, which
#: must not import paddle_tpu)
OOM_RISK_HEADROOM_FRAC = 0.10

_CLASSES = ("params", "opt_state", "activations", "step_temporaries",
            "lazy_fetch", "ckpt_capture", "kv_pages")
_CLASS_CELLS = {c: HBM_CLASS_GAUGE.labels(cls=c) for c in _CLASSES}


# ---------------------------------------------------------------------------
# the one measured-bytes reader
# ---------------------------------------------------------------------------

def measure_live_bytes() -> int:
    """Canonical measured live device bytes: the sum over the process's
    live jax arrays.  One reader for the accountant, the tests, and the
    forensics dump — so every 'measured' number in the system is the
    same quantity the planner's band was established against."""
    return _memory.live_bytes()


def budget_bytes() -> int:
    """The HBM budget in force: ``FLAGS_memory_budget_mb`` when set,
    else the allocator's ``bytes_limit`` where the backend exposes one
    (TPU does; CPU gives 0).  0 = no budget known."""
    from .flags import get_flags
    mb = int(get_flags("FLAGS_memory_budget_mb")["FLAGS_memory_budget_mb"])
    if mb > 0:
        return mb << 20
    stats = _memory.device_memory_stats()
    return int(stats.get("bytes_limit", 0) or 0)


# ---------------------------------------------------------------------------
# external contributors: checkpoint capture, serving KV pools, census fns
# ---------------------------------------------------------------------------

#: device bytes currently held by in-flight checkpoint-capture copies
#: (resilience.CheckpointDaemon.capture sets it, _save clears it) — a
#: capture-window live-bytes spike is attributed to ckpt_capture instead
#: of reading as a leak.  Plain float: single writer (the capturing
#: thread), torn reads impossible under the GIL.
_ckpt_capture_bytes = 0.0


def set_ckpt_capture_bytes(n: float) -> None:
    """Report the device bytes of checkpoint-snapshot copies currently
    in flight (0 when the daemon has materialized them to host)."""
    global _ckpt_capture_bytes
    _ckpt_capture_bytes = float(max(n, 0.0))
    _CLASS_CELLS["ckpt_capture"].set(_ckpt_capture_bytes)


#: live PagedKVCache pools (weak — a dead engine must not be kept alive
#: by its telemetry); the sampler attributes their device bytes to the
#: kv_pages class
_kv_pools: "weakref.WeakSet" = weakref.WeakSet()


def register_kv_pool(cache) -> None:
    """Register a serving ``PagedKVCache`` whose pool bytes the sampler
    attributes to the ``kv_pages`` class."""
    _kv_pools.add(cache)


def _kv_pool_bytes() -> int:
    total = 0
    for cache in list(_kv_pools):
        try:
            if not cache.buffers_alive():
                continue
            total += int(cache.pool_bytes())
        except Exception:
            continue
    return total


#: weak refs to serving ``statusz``-style callables — the forensics dump
#: folds their memory census (bucket widths, KV page occupancy) in when
#: a server is live at OOM time
_census_fns: List[Any] = []


def register_census(fn) -> None:
    """Register a bound method (weakly) returning a status dict; the OOM
    forensics dump includes every live registrant's snapshot."""
    try:
        ref = weakref.WeakMethod(fn)
    except TypeError:
        ref = weakref.ref(fn)
    _census_fns.append(ref)


def serving_census() -> List[dict]:
    """Snapshots from every live registered census callable (dead refs
    pruned); [] when no serving stack is up."""
    out, live = [], []
    for ref in _census_fns:
        fn = ref()
        if fn is None:
            continue
        live.append(ref)
        try:
            out.append(fn())
        except Exception:
            continue
    _census_fns[:] = live
    return out


# ---------------------------------------------------------------------------
# the accountant
# ---------------------------------------------------------------------------

class HBMAccountant:
    """Off-thread per-step HBM sampler (the CommsMonitor discipline).

    The executor hands every sampled step boundary a record (step id, a
    strong scope ref, the block's class name-sets + static-plan bytes,
    and the in-flight probe bytes); a daemon worker samples live device
    bytes, attributes them, and publishes the gauges — the training
    thread never blocks on the measurement.  The queue is bounded: under
    backlog the OLDEST record is shed (counted) — a skipped gauge beat,
    never a stalled step.
    """

    MAX_PENDING = 4

    def __init__(self):
        self._cv = threading.Condition()
        self._pending: collections.deque = collections.deque()  # guarded-by: _cv
        self._inflight = 0                                      # guarded-by: _cv
        self._thread: Optional[threading.Thread] = None         # guarded-by: _cv
        #: fast-path gates, written only by configure()
        self.enabled = True
        self.every_n = 1
        self.window = 16
        self.regress_frac = 0.0
        self._live_win: collections.deque = collections.deque(
            maxlen=16)                                          # guarded-by: _cv
        self._best_headroom: Optional[float] = None             # guarded-by: _cv
        self._headroom_obs = 0                                  # guarded-by: _cv
        self._regress_armed = True                              # guarded-by: _cv
        #: wall clock of the last gauge publish — metrics_digest drops
        #: the hbm/hdrm keys once this goes stale (the comms-plane
        #: frozen-median discipline)
        self.last_publish_wall = 0.0
        #: (live, headroom_or_None) of the last publish, for digest reads
        self.last_sample: Optional[tuple] = None

    #: samples the regression baseline ignores (warmup arrays, compile
    #: scratch) before the best-headroom watermark is trusted
    _REGRESS_WARMUP = 4

    def configure(self, enabled: bool, every_n: int, window: int,
                  regress_frac: float) -> None:
        with self._cv:
            self.every_n = max(int(every_n), 1)
            self.window = max(int(window), 1)
            if self._live_win.maxlen != self.window:
                self._live_win = collections.deque(self._live_win,
                                                   maxlen=self.window)
            self.regress_frac = max(float(regress_frac), 0.0)
            self._best_headroom = None
            self._headroom_obs = 0
            self._regress_armed = True
            # set LAST: the armed fast path must observe a fully
            # configured accountant
            self.enabled = bool(enabled)

    def _ensure_thread_locked(self):  # guarded-by-caller: _cv
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._loop, daemon=True, name="pt-hbm-accountant")
            self._thread.start()

    # -- producer side (the executor's step boundary) ------------------------
    def note_step(self, step_id: int, scope, info: Optional[dict],
                  inflight_bytes: int = 0) -> None:
        """Queue one step boundary for off-thread sampling.  ``info`` is
        the executor's per-compiled-block resolution ({params,
        opt_state} name sets + the static plan's steady/peak bytes at
        the real batch + ``step_temporaries``, the temporaries of the
        block's compiled plan once :func:`record_compiled_plan` has it),
        or None for foreign/unplanned programs."""
        with self._cv:
            self._ensure_thread_locked()
            if len(self._pending) >= self.MAX_PENDING:
                self._pending.popleft()
                _SAMPLE_DROPPED.inc()
            self._pending.append((step_id, scope, info,
                                  int(inflight_bytes)))
            self._cv.notify()

    def drain(self, timeout_s: float = 10.0) -> bool:
        """Block until every queued sample is published (tests, bench,
        smoke teardown).  Returns False on timeout."""
        deadline = time.monotonic() + timeout_s
        with self._cv:
            while self._pending or self._inflight:
                left = deadline - time.monotonic()
                if left <= 0:
                    return False
                self._cv.wait(timeout=min(left, 0.1))
        return True

    # -- worker side ---------------------------------------------------------
    def _loop(self):
        while True:
            with self._cv:
                while not self._pending:
                    self._cv.wait()
                rec = self._pending.popleft()
                self._inflight += 1
            try:
                self._sample(*rec)
                _SAMPLE_OK.inc()
            except Exception:
                _SAMPLE_ERROR.inc()   # telemetry must never kill the worker
            finally:
                # drop the record BEFORE parking on the cv: it holds a
                # strong scope ref, and a retained last-note scope would
                # keep a dead workload's arrays (and their device bytes)
                # alive until the next sample arrived
                rec = None
                with self._cv:
                    self._inflight -= 1
                    self._cv.notify_all()

    def _sample(self, step_id: int, scope, info: Optional[dict],
                inflight_bytes: int):
        live = measure_live_bytes()
        # -- attribution: named scope arrays by class, external
        # contributors, remainder = activations/temps ---------------------
        params = opt = 0
        if info is not None and scope is not None:
            for name in info.get("params", ()):
                params += _scope_nbytes(scope, name)
            for name in info.get("opt_state", ()):
                opt += _scope_nbytes(scope, name)
        kv = _kv_pool_bytes()
        ckpt = int(_ckpt_capture_bytes)
        acts = max(live - params - opt - kv - ckpt - inflight_bytes, 0)
        # in no live array: the region the dispatched block's executable
        # reserves while it runs, from its compiled plan
        temps = int((info or {}).get("step_temporaries", 0) or 0)
        _CLASS_CELLS["params"].set(float(params))
        _CLASS_CELLS["opt_state"].set(float(opt))
        _CLASS_CELLS["activations"].set(float(acts))
        _CLASS_CELLS["step_temporaries"].set(float(temps))
        _CLASS_CELLS["lazy_fetch"].set(float(inflight_bytes))
        _CLASS_CELLS["kv_pages"].set(float(kv))
        # ckpt_capture is set by its reporter (set_ckpt_capture_bytes)

        budget = budget_bytes()
        headroom = None
        if budget > 0:
            headroom = float(budget - live - temps)
            HBM_BUDGET_GAUGE.set(float(budget))
            HBM_HEADROOM_GAUGE.set(headroom)
        else:
            # budget cleared mid-run: a frozen last headroom would feed
            # a scraper a bogus admission signal — 0 budget = unknown,
            # and the headroom series drops (its help-text contract)
            HBM_BUDGET_GAUGE.set(0.0)
            HBM_HEADROOM_GAUGE.fold({}, None)
        drift = None
        plan_steady = int((info or {}).get("plan_steady", 0))
        if plan_steady > 0:
            drift = live / plan_steady
            HBM_DRIFT_GAUGE.set(drift)
        HBM_LIVE_GAUGE.set(float(live))
        with self._cv:
            self._live_win.append(float(live))
            peak = max(self._live_win)
            trigger = self._observe_headroom_locked(headroom)
        HBM_PEAK_GAUGE.set(peak)
        # the digest's hbm key is what the budget is spent on, so that
        # hbm + hdrm stays the budget (gangtop's HDRM% divides by it)
        self.last_sample = (int(live + temps), headroom)
        self.last_publish_wall = time.time()
        tracer = _monitor.TRACER
        if tracer.enabled:
            tracer.counter("hbm.live_bytes", float(live), cat="memory")
            args = {"step": int(step_id), "live": int(live),
                    "peak": int(peak), "params": int(params),
                    "opt_state": int(opt), "activations": int(acts),
                    "step_temporaries": temps,
                    "lazy_fetch": int(inflight_bytes),
                    "ckpt_capture": ckpt, "kv_pages": int(kv)}
            if headroom is not None:
                args["headroom"] = int(headroom)
            if drift is not None:
                args["drift"] = round(drift, 4)
            tracer.instant("hbm.sample", "memory", args)
        if trigger:
            if tracer.enabled:
                tracer.instant(
                    "memory.headroom_regress", "memory",
                    {"step": int(step_id), "headroom": int(headroom),
                     "best": int(self._best_headroom or 0)})
            from .profiler import SAMPLER
            SAMPLER.trigger_window(step_id, trigger="hbm_regress")

    def _observe_headroom_locked(self, headroom) -> bool:  # guarded-by-caller: _cv
        """Track the best (largest) headroom seen and decide whether the
        regression trigger fires — the FLAGS_profile_sample_regress_frac
        pattern applied to memory: a capture window opens the sample the
        measured headroom shrinks by the configured fraction under the
        best, re-arming only after it recovers half-way back."""
        if self.regress_frac <= 0 or headroom is None or headroom <= 0:
            return False
        self._headroom_obs += 1
        if self._best_headroom is None or headroom > self._best_headroom:
            self._best_headroom = float(headroom)
        if self._headroom_obs < self._REGRESS_WARMUP:
            return False
        threshold = self._best_headroom * (1.0 - self.regress_frac)
        if headroom <= threshold:
            if self._regress_armed:
                self._regress_armed = False
                return True
            return False
        if headroom >= self._best_headroom * (1.0 - self.regress_frac / 2.0):
            self._regress_armed = True    # recovered: re-arm
        return False


def _scope_nbytes(scope, name: str) -> int:
    try:
        v = scope.find_var(name)
        return per_device_nbytes(v)
    except Exception:
        return 0


def per_device_nbytes(v, sharding=None) -> int:
    """Bytes ONE device holds for an array: sharded jax Arrays (GSPMD
    params under a rule table, ZeRO-1 optimizer state) cost their shard,
    not the global shape — ``sharding.shard_shape`` is the same
    arithmetic XLA's buffer assignment uses, so a dp-sharded Adam moment
    reports 1/dp of its global bytes.  Replicated (or host/numpy) values
    keep their full nbytes.  ``sharding`` stands in for the value's own
    (an executable's input sharding for a host batch it shards on the way
    in); a ``ShapeDtypeStruct`` serves as well as an array."""
    shape = getattr(v, "shape", None)
    nbytes = getattr(v, "nbytes", None)
    if nbytes is None and shape is not None and hasattr(v, "dtype"):
        nbytes = int(np.prod(shape, dtype=np.int64)) * \
            np.dtype(v.dtype).itemsize
    nbytes = int(nbytes or 0)
    if sharding is None:
        sharding = getattr(v, "sharding", None)
    if sharding is None or not shape or not nbytes:
        return nbytes
    try:
        shard = sharding.shard_shape(tuple(shape))
    except Exception:
        return nbytes
    n, g = 1, 1
    for sd, gd in zip(shard, shape):
        n *= int(sd)
        g *= int(gd)
    return nbytes if g == 0 else int(nbytes * n // g)


#: process-wide accountant — the executor's step boundary feeds it
ACCOUNTANT = HBMAccountant()


# ---------------------------------------------------------------------------
# OOM forensics
# ---------------------------------------------------------------------------

#: XLA phrasings: "Out of memory allocating 123 bytes", "... while trying
#: to allocate 1.21G"/"allocate 99999 bytes"
_REQ_RE = re.compile(
    r"allocat(?:ing|e)\s+([0-9][0-9.]*)\s*([KMGT]i?B?|bytes|B)?",
    re.IGNORECASE)
_UNIT = {"": 1, "b": 1, "bytes": 1,
         "k": 1 << 10, "kb": 1 << 10, "kib": 1 << 10,
         "m": 1 << 20, "mb": 1 << 20, "mib": 1 << 20,
         "g": 1 << 30, "gb": 1 << 30, "gib": 1 << 30,
         "t": 1 << 40, "tb": 1 << 40, "tib": 1 << 40}


def parse_requested_bytes(msg: str) -> int:
    """Best-effort 'requested bytes' out of an XLA RESOURCE_EXHAUSTED
    message; 0 when the message carries no allocation size."""
    m = _REQ_RE.search(msg or "")
    if not m:
        return 0
    try:
        return int(float(m.group(1)) *
                   _UNIT.get((m.group(2) or "").lower(), 1))
    except (TypeError, ValueError):
        return 0


def _fmt(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024 or unit == "TiB":
            return f"{n:.2f} {unit}"
        n /= 1024
    return f"{n:.2f} TiB"


def oom_forensics(error: BaseException, scope=None, program=None,
                  fetch_names=(), batch: int = 1,
                  site: str = "dispatch", top_n: int = 10) -> str:
    """Write an OOM forensics dump (watchdog-dump style) and fire the
    observability side effects: ``paddle_tpu_oom_total{site}``, a
    ``memory.oom`` trace instant, and a profiler capture window with
    ``trigger:"oom"``.  Returns the dump file path.

    The dump's arithmetic section is self-consistent by construction —
    every derived line restates the operands it was computed from, so a
    reader (or the CI smoke) can re-add them."""
    OOM_CTR.inc(1, site=site)
    measured = 0
    try:
        measured = measure_live_bytes()
    except Exception:
        pass
    requested = parse_requested_bytes(str(error))
    budget = 0
    try:
        budget = budget_bytes()
    except Exception:
        pass
    plan = None
    if program is not None:
        try:
            from .analysis.memory import plan_memory
            plan = plan_memory(program, tuple(fetch_names),
                               batch_size=max(int(batch), 1))
        except Exception:
            plan = None

    lines = ["=== hbm oom forensics ===",
             f"site: {site}",
             f"pid: {os.getpid()}",
             f"time: {time.strftime('%Y-%m-%dT%H:%M:%S')}",
             f"error: {(str(error).splitlines() or [''])[0][:400]}",
             "",
             "--- budget arithmetic (bytes) ---",
             f"budget_bytes: {budget}",
             f"plan_peak_bytes: {plan.peak_bytes if plan else 0}",
             f"measured_bytes: {measured}",
             f"requested_bytes: {requested}",
             f"measured_plus_requested: {measured + requested}",
             f"deficit_bytes: {measured + requested - budget}",
             f"# measured ({_fmt(measured)}) + requested "
             f"({_fmt(requested)}) = {_fmt(measured + requested)} vs "
             f"budget {_fmt(budget)}",
             ""]
    if plan is not None:
        lines.append(f"--- static plan (batch={plan.batch_size}) ---")
        lines.append(
            f"peak {_fmt(plan.peak_bytes)} at op #{plan.peak_pos} "
            f"({plan.peak_op}); resident {_fmt(plan.resident_bytes)}; "
            f"steady {_fmt(plan.steady_bytes)}")
        lines.append(f"--- top {top_n} tensors live at the peak op "
                     "(name, bytes, kind, lifetime [def..last op]) ---")
        for name, nbytes, kind in plan.peak_live[:top_n]:
            iv = plan.intervals.get(name)
            life = (f"[{iv[0]}..{iv[1]}]" if iv is not None
                    else "[resident whole step]")
            lines.append(f"  {_fmt(nbytes):>12s}  {kind:<8s} {life:<24s} "
                         f"{name}")
        lines.append("")
    lines.append("--- residency summary ---")
    try:
        lines.append(_memory.summary(scope) if scope is not None
                     else _memory.summary())
    except Exception as e:      # the dump must never fail the dumper
        lines.append(f"<summary unavailable: {e}>")
    census = serving_census()
    if census:
        import json
        lines.append("")
        lines.append("--- serving memory census ---")
        for snap in census:
            try:
                lines.append(json.dumps(snap, indent=1, sort_keys=True,
                                        default=str))
            except Exception:
                lines.append(repr(snap))
    lines.append("")

    from .flags import get_flags
    d = get_flags("FLAGS_oom_dump_dir")["FLAGS_oom_dump_dir"] or \
        get_flags("FLAGS_watchdog_dump_dir")["FLAGS_watchdog_dump_dir"] \
        or tempfile.gettempdir()
    os.makedirs(d, exist_ok=True)
    path = os.path.join(
        d, f"paddle_tpu_oom_{os.getpid()}_{int(time.time() * 1e3)}.txt")
    with open(path, "w") as f:
        f.write("\n".join(lines) + "\n")
        f.flush()
        os.fsync(f.fileno())

    if _monitor.TRACER.enabled:
        _monitor.TRACER.instant(
            "memory.oom", "memory",
            {"site": site, "dump": path, "budget": budget,
             "measured": measured, "requested": requested,
             "plan_peak": plan.peak_bytes if plan else 0})
    try:
        # capture window only when the sampler has a configured home —
        # an unconfigured run must not sprout pt_profile_samples/ in the
        # cwd just because an OOM surfaced
        if get_flags("FLAGS_profile_sample_dir")[
                "FLAGS_profile_sample_dir"]:
            from .profiler import SAMPLER
            SAMPLER.trigger_window(trigger="oom")
    except Exception:
        pass
    return path


# ---------------------------------------------------------------------------
# compiled plans: every block's memory_analysis(), where it compiles
# ---------------------------------------------------------------------------

#: gauge label ``part`` -> key of a ``memory.hbm_plans()`` entry
PLAN_PARTS = {"arguments": "argument_bytes", "outputs": "output_bytes",
              "aliased": "alias_bytes", "temporaries": "temp_bytes",
              "code": "generated_code_bytes"}
ARGUMENT_CLASSES = ("params", "opt_state", "feeds", "other")

STEP_PLAN_GAUGE = _monitor.REGISTRY.gauge(
    "paddle_tpu_step_hbm_plan_bytes",
    "XLA buffer assignment (memory_analysis(), bytes on one device) of "
    "each compiled block, set when it compiles: part = arguments (the "
    "state the step takes, and one batch) / outputs / aliased (outputs "
    "that donation put in their argument's buffer) / temporaries (what "
    "the forward keeps for the backward, gradients, casts, scratch: "
    "those live at the executable's peak where the backend reports "
    "one, memory.plan_parts) / code.  block = 'train' | 'other' as "
    "paddle_tpu_compile_phase_seconds decides it; tag = the block's "
    "fetch list, cut to 64 characters, '#n' on a later compile under "
    "the same list (a re-trace compiles its block again)",
    ("block", "tag", "part"))
STEP_PLAN_AT_GAUGE = _monitor.REGISTRY.gauge(
    "paddle_tpu_step_hbm_plan_compiled_at_seconds",
    "time.perf_counter() when the compiling call of that block returned "
    "(which plan ran a window: the newest before it opened)",
    ("block", "tag"))
STEP_ARGUMENT_GAUGE = _monitor.REGISTRY.gauge(
    "paddle_tpu_step_hbm_argument_bytes",
    "the compiled block's arguments by the program's own classes, from "
    "the compiling call's avals under the executable's input shardings "
    "(bytes on one device): params / opt_state (non-parameter "
    "persistables) / feeds / other (the seed, names the block does not "
    "declare); they add up to part='arguments' within the compiler's "
    "padding", ("block", "tag", "cls"))
PLAN_HOOK_CTR = _monitor.REGISTRY.counter(
    "paddle_tpu_step_hbm_plan_records_total",
    "compiled-block plans by outcome ('ok' recorded, 'error' the hook "
    "failed: the step ran, its plan is missing)", ("outcome",))


def argument_classes(args, names, info=None, input_shardings=None) -> dict:
    """The bytes of a block's call arguments ``(feeds, ro, rw, seed)`` by
    class.  ``names`` is ``(feed_names, persist_ro, persist_rw)``;
    ``info`` the executor's ``{params, opt_state}`` name sets (None: every
    persistable is ``other``); ``input_shardings`` the executable's, laid
    out like ``args``, so that a feed the step shards on its way in counts
    its shard.  Only avals and shardings are read: donated, deleted
    arrays serve."""
    feeds, ro, rw, seed = args
    sh = input_shardings if input_shardings is not None \
        else ([None] * len(feeds), [None] * len(ro), [None] * len(rw), None)
    params = (info or {}).get("params", ())
    opt = (info or {}).get("opt_state", ())
    out = dict.fromkeys(ARGUMENT_CLASSES, 0)
    for v, s in zip(feeds, sh[0]):
        out["feeds"] += per_device_nbytes(v, s)
    for group, shs, ns in ((ro, sh[1], names[1]), (rw, sh[2], names[2])):
        for v, s, n in zip(group, shs, ns):
            cls = ("params" if n in params else
                   "opt_state" if n in opt else "other")
            out[cls] += per_device_nbytes(v, s)
    out["other"] += per_device_nbytes(seed, sh[3])
    return out


def compiled_plan(compiled, args, names, info=None):
    """``(memory_analysis, argument classes)`` of one compiled block: the
    one function the executor's hook, ``tools/joyai_step_aot.py`` and
    ``tools/record_hbm.py`` read an executable through."""
    try:
        shardings = compiled.input_shardings[0]
    except Exception:
        shardings = None
    return compiled.memory_analysis(), argument_classes(
        args, names, info, shardings)


def record_compiled_plan(jitted, args, names, info, tag: str, block: str,
                         compiled_at: float, defer: bool = False) -> dict:
    """The executor's hook, called after a compiling call has returned:
    lower and compile the block again with that call's OWN arguments —
    JAX's caches serve both (no lowering, no backend compile; a
    ShapeDtypeStruct in an array's place would miss) — and record the
    executable's plan; its temporaries go into ``info``, the record the
    accountant's samples of this block carry.  Times itself
    (``hook_ms``).  Never raises: a failure costs the plan, not the step.

    ``defer``: nothing now; the same call is left with
    ``memory.defer_hbm_plan`` for the first reader of
    ``memory.hbm_plans()`` (for a jit whose ``.compile()`` is never
    cached: ``_CompiledBlock.record_plan``).  Until then the block's
    samples carry no temporaries, and the deferred call keeps that
    compile's arguments referenced."""
    if defer:
        # the seed is the one argument nothing else keeps alive (the state
        # is the scope's or donated and gone, the batch its reader's): as
        # a host scalar it has the same aval and pins no device buffer
        args = (*args[:3], np.asarray(args[3]))
        _memory.defer_hbm_plan(id(jitted), lambda: record_compiled_plan(
            jitted, args, names, info, tag, block, compiled_at))
        return {}
    t0 = time.perf_counter()
    try:
        ma, classes = compiled_plan(jitted.lower(*args).compile(), args,
                                    names, info)
        entry = record_xla_plan(
            tag, ma, block=block, compiled_at=float(compiled_at),
            classes=classes, hook_ms=(time.perf_counter() - t0) * 1e3)
        if isinstance(info, dict):
            info["step_temporaries"] = entry["temp_bytes"]
        PLAN_HOOK_CTR.inc(1, outcome="ok")
        return entry
    except Exception:
        PLAN_HOOK_CTR.inc(1, outcome="error")
        return {}


def _drop_plan_series(tag: str, entry: dict) -> None:
    block = entry.get("block", "other")
    for part in PLAN_PARTS:
        STEP_PLAN_GAUGE.fold({"block": block, "tag": tag, "part": part},
                             None)
    for cls in ARGUMENT_CLASSES:
        STEP_ARGUMENT_GAUGE.fold({"block": block, "tag": tag, "cls": cls},
                                 None)
    STEP_PLAN_AT_GAUGE.fold({"block": block, "tag": tag}, None)


def record_xla_plan(tag: str, ma, block: str = "other",
                    compiled_at: Optional[float] = None,
                    classes: Optional[dict] = None,
                    hook_ms: Optional[float] = None) -> dict:
    """Record one compiled executable's ``memory_analysis()`` — the
    on-chip buffer assignment — into the shared plan store
    (``memory.hbm_plans()``, which the residency summary and
    tools/record_hbm.py read) and publish it as gauges.  The ONE
    ingestion point for XLA-side measured bytes.  Returns the entry,
    with the ``tag`` it was stored under."""
    facts = {"block": block,
             "compiled_at": time.perf_counter() if compiled_at is None
             else compiled_at}
    if classes is not None:
        facts["argument_classes"] = dict(classes)
    if hook_ms is not None:
        facts["hook_ms"] = float(hook_ms)
    # record_hbm_plan suffixes colliding tags (startup programs all tag
    # '<block>') and returns the FINAL tag — reading back by the passed
    # tag would hand a collision the previous executable's plan
    tag, entry, evicted = _memory.record_hbm_plan(tag, ma, **facts)
    for old_tag, old in evicted:
        _drop_plan_series(old_tag, old)
    entry = dict(entry, tag=tag)
    for part, key in PLAN_PARTS.items():
        STEP_PLAN_GAUGE.set(float(entry[key]), block=block, tag=tag,
                            part=part)
    STEP_PLAN_AT_GAUGE.set(entry["compiled_at"], block=block, tag=tag)
    for cls, n in (classes or {}).items():
        STEP_ARGUMENT_GAUGE.set(float(n), block=block, tag=tag, cls=cls)
    if _monitor.TRACER.enabled:
        _monitor.TRACER.instant("hbm.xla_plan", "memory", dict(entry))
    return entry
