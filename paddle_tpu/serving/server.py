"""Multi-tenant inference server: tenant plane + graceful drain.

Admission is per tenant: every tenant gets a request counter, a
queue-depth gauge, and a latency histogram in ``paddle_tpu.monitor``
(series retire through ``monitor.retire_tenant_series`` on eviction — a
revolving tenant population cannot grow the registry), plus an outstanding
quota (``FLAGS_serving_tenant_quota`` or per-tenant overrides) enforced at
submit.

SIGTERM handling follows the PreemptionGuard pattern: the handler only
sets an Event (taking a metric/tracer lock while interrupting the main
thread's own critical section would self-deadlock at the exact moment the
drain must run); the serve loop then stops admitting (new submits reject
with reason="draining"), finishes every in-flight request, exports
telemetry, and returns exit code 0.
"""

from __future__ import annotations

import signal
import threading
import time
import warnings
from typing import Any, Callable, Dict, Optional, Sequence

import numpy as np

from .. import monitor as _monitor
from .bucketing import BucketPlan, parse_buckets
from .scheduler import (ContinuousBatcher, DecodeScheduler, Request,
                        ServingFuture)


class TenantPlane:
    """Per-tenant admission + telemetry bookkeeping."""

    def __init__(self, default_quota: int = 0, on_evict=None):
        self._mu = threading.Lock()
        self._on_evict = on_evict    # eviction hook (e.g. slo.forget)
        self._outstanding: Dict[str, int] = {}  # guarded-by: _mu
        self._quotas: Dict[str, int] = {}  # guarded-by: _mu
        self._evicted: set = set()  # guarded-by: _mu
        # incarnation counter, bumped on evict: requests carry the
        # generation they were admitted under, so a straggler from a
        # PRE-eviction incarnation can neither decrement the re-admitted
        # tenant's quota nor re-mint the folded series
        self._gen: Dict[str, int] = {}  # guarded-by: _mu
        self._default_quota = int(default_quota)

    def generation(self, tenant: str) -> int:
        with self._mu:
            return self._gen.get(str(tenant), 0)

    def is_current(self, tenant: str, gen: Optional[int]) -> bool:
        """True when the request's admission incarnation is still live:
        tenant not evicted and (when the request carries one) its
        admission generation matches the current incarnation."""
        tenant = str(tenant)
        with self._mu:
            if tenant in self._evicted:
                return False
            return gen is None or gen == self._gen.get(tenant, 0)

    def set_quota(self, tenant: str, quota: int) -> None:
        with self._mu:
            self._quotas[str(tenant)] = int(quota)

    def try_admit(self, tenant: str) -> bool:
        """Reserve one outstanding unit; False when over quota (the
        caller counts the rejection)."""
        tenant = str(tenant)
        with self._mu:
            quota = self._quotas.get(tenant, self._default_quota)
            cur = self._outstanding.get(tenant, 0)
            if quota > 0 and cur >= quota:
                return False
            self._outstanding[tenant] = cur + 1
            depth = cur + 1
            # a fresh submit is a new incarnation: it may mint fresh
            # series again (and retire again on its own eviction)
            self._evicted.discard(tenant)
        _monitor.SERVING_REQ_CTR.inc(1, tenant=tenant)
        _monitor.SERVING_QUEUE_GAUGE.set(depth, tenant=tenant)
        return True

    def _account(self, tenant: str, gen: Optional[int]) -> tuple:
        """(label to account under, depth or None): requests of an
        EVICTED tenant — or an earlier incarnation of a re-admitted one
        (admission generation older than the current) — completing after
        the fold must land in the "retired" series, not resurrect the
        just-retired per-tenant ones or shrink the new incarnation's
        outstanding count."""
        with self._mu:
            stale = gen is not None and gen != self._gen.get(tenant, 0)
            if tenant in self._evicted or stale:
                return "retired", None
            depth = max(0, self._outstanding.get(tenant, 1) - 1)
            self._outstanding[tenant] = depth
            return tenant, depth

    def complete(self, tenant: str, latency_ms: float,
                 gen: Optional[int] = None) -> None:
        label, depth = self._account(str(tenant), gen)
        _monitor.SERVING_DONE_CTR.inc(1, tenant=label)
        _monitor.SERVING_LAT_HIST.observe(latency_ms, tenant=label)
        if depth is not None:
            _monitor.SERVING_QUEUE_GAUGE.set(depth, tenant=label)

    def fail(self, tenant: str, gen: Optional[int] = None) -> None:
        label, depth = self._account(str(tenant), gen)
        _monitor.SERVING_FAIL_CTR.inc(1, tenant=label)
        if depth is not None:
            _monitor.SERVING_QUEUE_GAUGE.set(depth, tenant=label)

    def reject(self, tenant: str, reason: str) -> None:
        tenant = str(tenant)
        with self._mu:
            if tenant in self._evicted:
                tenant = "retired"
        _monitor.SERVING_REJECT_CTR.inc(1, tenant=tenant, reason=reason)

    def snapshot(self) -> Dict[str, int]:
        """Per-tenant outstanding (queued + in-flight) counts — the
        ``/statusz`` queue-depth view."""
        with self._mu:
            return {t: n for t, n in self._outstanding.items()
                    if t not in self._evicted}

    def evict(self, tenant: str) -> None:
        """Drop the tenant and retire its registry series (PR-2 fold
        semantics: counters fold into tenant="retired", totals exact).
        In-flight requests of the tenant finish normally; their counts
        accrue to the "retired" series."""
        tenant = str(tenant)
        with self._mu:
            self._outstanding.pop(tenant, None)
            self._quotas.pop(tenant, None)
            self._evicted.add(tenant)
            self._gen[tenant] = self._gen.get(tenant, 0) + 1
        _monitor.retire_tenant_series(tenant)
        if self._on_evict is not None:
            self._on_evict(tenant)

    def outstanding(self, tenant: str) -> int:
        with self._mu:
            return self._outstanding.get(str(tenant), 0)


class _ServerBase:
    """Shared admission / drain / signal plumbing for both server modes."""

    def __init__(self, tenant_quota: Optional[int] = None,
                 max_retries: Optional[int] = None):
        from ..flags import get_flags
        from .slo import BurnRateEvaluator
        fl = get_flags(["FLAGS_serving_tenant_quota",
                        "FLAGS_serving_max_retries",
                        "FLAGS_serving_slo_shed",
                        "FLAGS_serving_slo_eval_interval_s"])
        quota = fl["FLAGS_serving_tenant_quota"] \
            if tenant_quota is None else tenant_quota
        self.tenants = TenantPlane(int(quota), on_evict=self._forget_slo)
        self._max_retries = int(fl["FLAGS_serving_max_retries"]
                                if max_retries is None else max_retries)
        self._draining = threading.Event()
        self._started = False
        self._old_handlers: Dict[int, Any] = {}
        self._sched = None       # set by the subclass
        #: per-tenant burn-rate state machine; None = SLO plane off
        self.slo = BurnRateEvaluator.from_flags()
        self._slo_shed = bool(fl["FLAGS_serving_slo_shed"])
        self._slo_interval = float(
            fl["FLAGS_serving_slo_eval_interval_s"])
        self._slo_stop = threading.Event()
        self._slo_thread: Optional[threading.Thread] = None
        self._slo_eval_warned = False
        self._http = None        # MetricsHTTPServer (enable_http)

    def _forget_slo(self, tenant: str) -> None:
        """Tenant-eviction hook: the evaluator must stop tracking the
        tenant or its next tick re-mints the SLO gauge series that
        ``retire_tenant_series`` just dropped."""
        if self.slo is not None:
            self.slo.forget(tenant)

    def _slo_eval_safe(self) -> None:
        """One evaluator tick.  The loop must outlive evaluator bugs,
        but not silently — a dead SLO plane showing breach-free gauges
        during an outage is worse than a crash, so the first failure
        warns with the error."""
        try:
            self.slo.evaluate()
        except Exception as e:
            if not self._slo_eval_warned:
                self._slo_eval_warned = True
                warnings.warn(
                    "serving SLO evaluator failed — burn/breach gauges "
                    f"are stale until it recovers: {e!r}")

    # -- admission -----------------------------------------------------------
    def _admit(self, tenant: str) -> Optional[str]:
        """None = admitted (one outstanding unit reserved); otherwise
        the rejection reason (already counted per tenant)."""
        if self._draining.is_set():
            self.tenants.reject(tenant, "draining")
            return "draining"
        if (self._slo_shed and self.slo is not None
                and self.slo.in_breach(tenant)):
            # shed-on-burn: while the tenant's SLO is in breach, new
            # work would only deepen the burn — refuse it at the door
            self.tenants.reject(tenant, "slo_shed")
            return "slo_shed"
        if not self.tenants.try_admit(tenant):
            self.tenants.reject(tenant, "quota")
            return "quota"
        return None

    def _on_complete(self, req: Request, result, latency_ms: float):
        req.future._resolve(result)
        self.tenants.complete(req.tenant, latency_ms, gen=req.admit_gen)
        # stale-generation guard mirrors TenantPlane._account: an
        # in-flight request resolving AFTER its tenant's eviction must
        # not un-forget the tenant and resurrect its retired SLO series
        if self.slo is not None \
                and self.tenants.is_current(req.tenant, req.admit_gen):
            self.slo.record(req.tenant, ok=True, latency_ms=latency_ms)

    def _on_fail(self, req: Request, err: BaseException):
        req.future._fail(err)
        self.tenants.fail(req.tenant, gen=req.admit_gen)
        if self.slo is not None \
                and self.tenants.is_current(req.tenant, req.admit_gen):
            self.slo.record(req.tenant, ok=False)

    # -- lifecycle -----------------------------------------------------------
    def start(self):
        if not self._started:
            self._sched.start()
            self._started = True
            # OOM forensics census: a RESOURCE_EXHAUSTED dump includes
            # this server's memory section (bucket widths / KV page
            # occupancy) — weak registration, the dump never keeps a
            # stopped server alive
            from .. import hbm as _hbm
            _hbm.register_census(self.statusz)
        if self.slo is not None and self._slo_thread is None:
            self._slo_stop.clear()
            self._slo_thread = threading.Thread(
                target=self._slo_loop, name="serving-slo", daemon=True)
            self._slo_thread.start()
        return self

    def _slo_loop(self) -> None:
        while not self._slo_stop.wait(self._slo_interval):
            self._slo_eval_safe()

    def drain(self, timeout_s: float = 60.0) -> bool:
        """Stop admitting and block until every in-flight request has
        resolved.  True when nothing was dropped."""
        self._draining.set()
        return self._sched.drain(timeout_s)

    def stop(self) -> None:
        self._draining.set()
        self._sched.stop()
        self._slo_stop.set()
        if self._slo_thread is not None:
            self._slo_thread.join(timeout=2.0)
            self._slo_thread = None      # start() can relaunch it
        if self.slo is not None:
            self._slo_eval_safe()          # final state for the export
        if self._http is not None:
            self._http.stop()
            self._http = None

    def queue_depth(self) -> int:
        return self._sched.queue_depth()

    # -- live scrape surface -------------------------------------------------
    def _health(self):
        draining = self._draining.is_set()
        return (not draining, "draining" if draining else "ok")

    def statusz(self) -> Dict[str, Any]:
        """Operational snapshot for ``/statusz`` (subclasses extend)."""
        return {"draining": self._draining.is_set(),
                "queue_depth": self.queue_depth(),
                "tenants": self.tenants.snapshot(),
                "slo": self.slo.state() if self.slo is not None else None}

    def enable_http(self, port: Optional[int] = None,
                    host: Optional[str] = None):
        """Start the /metrics /healthz /statusz endpoint for this
        server (idempotent).  ``port=None`` reads FLAGS_metrics_port —
        whose 0 default means DISABLED, so the call returns None rather
        than opening an unconfigured fleet-facing socket.  An explicit
        ``port=0`` argument binds an ephemeral port (read ``.port``).
        ``host=None`` reads FLAGS_metrics_host (default 0.0.0.0: the
        endpoint is fleet-facing — scrapers and balancers are
        off-box)."""
        if self._http is not None:
            return self._http
        from ..flags import get_flags
        if port is None:
            port = int(get_flags("FLAGS_metrics_port")
                       ["FLAGS_metrics_port"])
            if port <= 0:
                return None
        if host is None:
            host = str(get_flags("FLAGS_metrics_host")
                       ["FLAGS_metrics_host"])
        from .httpd import MetricsHTTPServer
        self._http = MetricsHTTPServer(
            port=int(port), host=host, health_fn=self._health,
            status_fn=self.statusz).start()
        return self._http

    # -- SIGTERM graceful drain (PreemptionGuard pattern) --------------------
    def install_signal_handlers(
            self, signals: Sequence[int] = (signal.SIGTERM,
                                            signal.SIGINT)) -> None:
        for s in signals:
            self._old_handlers[s] = signal.signal(s, self._handler)

    def _handler(self, signum, frame):
        # lock-free on purpose: only an Event.set — see module docstring
        self._draining.set()

    def serve_until_terminated(self, poll_s: float = 0.05,
                               drain_timeout_s: float = 60.0) -> int:
        """Block until SIGTERM/SIGINT, then drain and return the exit
        code (0 = zero dropped in-flight requests).  Exports telemetry
        when ``FLAGS_telemetry_export_path`` is set (at-exit hook);
        exposes the live scrape endpoint when ``FLAGS_metrics_port`` is
        set (``/healthz`` flips to 503 the moment draining starts, so a
        balancer can eject the replica before the drain finishes)."""
        self.install_signal_handlers()
        from ..flags import get_flags
        if self._http is None and int(
                get_flags("FLAGS_metrics_port")["FLAGS_metrics_port"]) > 0:
            self.enable_http()
        try:
            while not self._draining.is_set():
                time.sleep(poll_s)
            ok = self.drain(drain_timeout_s)
        finally:
            for s, h in self._old_handlers.items():
                signal.signal(s, h)
            self._old_handlers.clear()
            self.stop()
        return 0 if ok else 1


class InferenceServer(_ServerBase):
    """Bucketized continuous-batching server for request/response models.

    ``program_factory(seq_len) -> (program, feed_names, fetch_names)``
    materializes the model at one bucket length (Fluid programs bake the
    sequence length into op attrs, so each bucket is its own program —
    all sharing one scope of parameters).  Each bucket compiles ONCE
    (fixed width x bucket feed shapes through ``compiler.optimize`` with
    the verifier/cost/memory stamps riding along) and persists in the
    compile cache placed at import (``device.place_compile_cache``), so a
    server restart is warm and the
    compile count equals the bucket count — never the number of distinct
    request shapes.
    """

    def __init__(self, program_factory: Callable[[int], tuple], scope,
                 buckets=None, max_batch: Optional[int] = None,
                 max_seq: Optional[int] = None, executor=None,
                 tenant_quota: Optional[int] = None,
                 batch_wait_ms: Optional[float] = None,
                 max_retries: Optional[int] = None):
        super().__init__(tenant_quota, max_retries)
        from ..flags import get_flags
        from ..framework.executor import Executor
        fl = get_flags(["FLAGS_serving_shape_buckets",
                        "FLAGS_serving_max_batch",
                        "FLAGS_serving_batch_wait_ms",
                        "FLAGS_memory_budget_mb"])
        if buckets is None:
            buckets = parse_buckets(fl["FLAGS_serving_shape_buckets"],
                                    max_len=int(max_seq or 512))
        self.buckets = tuple(sorted(int(b) for b in buckets))
        self.scope = scope
        self.executor = executor or Executor()
        self.plan = BucketPlan(
            self.buckets, program_factory,
            int(max_batch or fl["FLAGS_serving_max_batch"]),
            memory_budget_mb=int(fl["FLAGS_memory_budget_mb"]))
        self._sched = ContinuousBatcher(
            self.executor, scope, self.plan,
            on_complete=self._on_complete, on_fail=self._on_fail,
            max_retries=self._max_retries,
            batch_wait_ms=float(fl["FLAGS_serving_batch_wait_ms"]
                                if batch_wait_ms is None else
                                batch_wait_ms))

    def warmup(self, buckets=None) -> int:
        """Compile each bucket once with a dummy full-width batch —
        after this the steady-state compile counter is flat and a
        restart hits the persistent XLA disk cache.  Returns the number
        of buckets warmed."""
        n = 0
        for b in (buckets or self.buckets):
            compiled, feed_names, fetch_names, width = self.plan.plan(b)
            feed = {}
            program = compiled.program
            block = program.global_block()
            for name in feed_names:
                var = block.var(name)
                shape = [width] + [b if d == -1 or d is None else int(d)
                                   for d in (var.shape or ())[1:]]
                # the DECLARED dtype: the compiled-block key includes the
                # feed signature, so a warmup in the wrong dtype would
                # compile a bucket no real request ever hits
                dt = np.dtype(str(var.dtype or "float32"))
                feed[name] = np.zeros(shape, dt)
            self.executor.run(compiled, feed=feed,
                              fetch_list=list(fetch_names),
                              scope=self.scope, return_numpy=True)
            n += 1
        return n

    def submit(self, tenant: str, feeds: Dict[str, Any],
               seq_len: Optional[int] = None) -> ServingFuture:
        """Queue one request (per-example feeds, NO batch dim) and return
        its future.  Rejected requests get a future already failed with
        :class:`AdmissionError` — callers never block on admission.
        ``seq_len`` overrides the TRIM length of the fetches; the bucket
        is always chosen to fit every feed (a caller-understated length
        must not smuggle an oversize array past padding)."""
        t0 = time.perf_counter()
        feeds = {k: np.asarray(v) for k, v in feeds.items()}
        longest = max((a.shape[0] for a in feeds.values() if a.ndim),
                      default=0)
        n = int(seq_len) if seq_len is not None else longest
        bucket = self.plan.bucket_for(max(n, longest))
        if bucket is None:
            self.tenants.reject(tenant, "too_long")
            f = ServingFuture()
            f._fail(AdmissionError(
                f"request length {max(n, longest)} exceeds the largest "
                f"bucket {self.buckets[-1]}"))
            return f
        reason = self._admit(tenant)
        if reason is not None:
            f = ServingFuture()
            f._fail(AdmissionError(
                f"tenant {tenant!r} rejected ({reason})"))
            return f
        req = Request(tenant, feeds=feeds, seq_len=n, bucket=bucket)
        # the admit phase starts at submit ENTRY (bucket choice + quota
        # accounting belong to it), so the phase chain partitions the
        # whole measured e2e latency
        req.t_submit = t0
        req.tm["submit"] = t0
        req.admit_gen = self.tenants.generation(tenant)
        if not self._sched.enqueue(req):
            # enqueue raced stop(): nothing will ever service the queue
            self._on_fail(req, AdmissionError("server stopped"))
        return req.future

    def compile_stats(self) -> Dict[str, int]:
        st = self.executor.dispatch_stats()
        return {"traces": int(st["traces"]),
                "compiled_blocks": int(st.get("compiled_blocks", 0)),
                "buckets": len(self.buckets)}

    def shrink_widths(self) -> Dict[int, int]:
        """Degradation-ladder actuator (fleet autoscaler ``control`` op):
        halve every built bucket's admitted batch width.  Delegates to
        the :class:`~paddle_tpu.serving.bucketing.BucketPlan`; the
        scheduler picks the new width up on its next dispatch."""
        return self.plan.shrink_widths()

    def statusz(self) -> Dict[str, Any]:
        out = super().statusz()
        out["buckets"] = {str(b): self.plan.width_of(b)
                         for b in self.buckets}
        out["compile"] = self.compile_stats()
        # memory section: the budget in force + each BUILT bucket's
        # admitted width and static HBM peak at that width (cold
        # buckets report null — statusz never triggers a build)
        from ..flags import get_flags
        out["memory"] = {
            "budget_mb": int(get_flags("FLAGS_memory_budget_mb")
                             ["FLAGS_memory_budget_mb"]),
            "per_bucket": {
                str(b): {"width": self.plan.width_of(b),
                         "static_peak_bytes": self.plan.static_peak_of(b)}
                for b in self.buckets},
        }
        occ = _monitor.REGISTRY.get("paddle_tpu_serving_batch_occupancy")
        if occ is not None:
            tot_sum = tot_n = 0.0
            for labels, cell in occ.series():
                if labels.get("mode") != "batch":
                    continue    # a coexisting decode loop's iterations
                _counts, s, c = cell.snapshot()
                tot_sum += s
                tot_n += c
            if tot_n:
                out["mean_occupancy"] = round(tot_sum / tot_n, 3)
        return out


class DecodeServer(_ServerBase):
    """Continuous-batching token-generation server (``gpt_causal``).

    Wraps a :class:`~paddle_tpu.serving.kv_cache.DecodeEngine`: requests
    carry a prompt + ``max_new_tokens``; the decode loop admits them into
    KV slots, prefills and generates through ONE compiled step, and frees
    the paged cache on completion — slot reuse across requests with the
    compile counter flat after warmup."""

    def __init__(self, engine, tenant_quota: Optional[int] = None,
                 max_retries: Optional[int] = None):
        super().__init__(tenant_quota, max_retries)
        self.engine = engine
        self._sched = DecodeScheduler(
            engine, on_complete=self._on_complete, on_fail=self._on_fail,
            max_retries=self._max_retries)

    def submit(self, tenant: str, prompt, max_new_tokens: int = 16,
               eos_id: Optional[int] = None) -> ServingFuture:
        t0 = time.perf_counter()
        prompt = np.asarray(prompt).ravel()
        if prompt.size == 0:
            self.tenants.reject(tenant, "too_long")
            f = ServingFuture()
            f._fail(AdmissionError("empty prompt"))
            return f
        if prompt.size + int(max_new_tokens) > self.engine.max_seq:
            self.tenants.reject(tenant, "too_long")
            f = ServingFuture()
            f._fail(AdmissionError(
                f"prompt {prompt.size} + max_new_tokens {max_new_tokens} "
                f"exceeds the engine context window "
                f"{self.engine.max_seq}"))
            return f
        reason = self._admit(tenant)
        if reason is not None:
            f = ServingFuture()
            f._fail(AdmissionError(
                f"tenant {tenant!r} rejected ({reason})"))
            return f
        req = Request(tenant, prompt=prompt,
                      max_new_tokens=int(max_new_tokens), eos_id=eos_id)
        req.t_submit = t0
        req.tm["submit"] = t0
        req.admit_gen = self.tenants.generation(tenant)
        if not self._sched.enqueue(req):
            self._on_fail(req, AdmissionError("server stopped"))
        return req.future

    def compile_stats(self) -> Dict[str, int]:
        return {"traces": int(self.engine.trace_count),
                "kv_pages_in_use": self.engine.cache.pages_in_use()}

    def statusz(self) -> Dict[str, Any]:
        out = super().statusz()
        free = sum(1 for s in self._sched._slots if s is None)
        out["slots"] = {"total": self.engine.max_slots, "free": free}
        out["kv_pages_in_use"] = self.engine.cache.pages_in_use()
        out["tokens_per_s"] = float(_monitor.SERVING_TPS_GAUGE.value()) \
            if _monitor.REGISTRY.get(
                "paddle_tpu_serving_tokens_per_s").series() else 0.0
        # memory section: budget + KV pool census with per-tenant page
        # occupancy and internal fragmentation (retire-on-eviction fold
        # keeps the backing gauges bounded across tenant churn)
        from ..flags import get_flags
        cache = self.engine.cache
        out["memory"] = {
            "budget_mb": int(get_flags("FLAGS_memory_budget_mb")
                             ["FLAGS_memory_budget_mb"]),
            "kv": {"page_len": int(self.engine.page_len),
                   "pages_total": int(cache.n_pages),
                   "pages_in_use": int(cache.pages_in_use()),
                   "pool_bytes": int(cache.pool_bytes()),
                   "per_tenant": self._sched.kv_census()},
        }
        return out


class AdmissionError(RuntimeError):
    """A request refused at admission (quota / draining / too long)."""
