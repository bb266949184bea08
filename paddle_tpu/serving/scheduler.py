"""Continuous-batching scheduler over the async executor.

Two execution loops, one admission contract:

- :class:`ContinuousBatcher` (stateless request/response models): an
  admission queue drained by a scheduler thread that coalesces queued
  requests into the widest same-bucket batch available (waiting at most
  ``FLAGS_serving_batch_wait_ms`` for stragglers), pads the batch to the
  bucket's fixed (width, seq) shape, and dispatches through
  ``Executor.run(..., return_numpy=False)`` — the PR-1 lazy-fetch path, so
  host batch assembly of request *i+1* overlaps device execution of *i*
  and ``FLAGS_executor_max_inflight_steps`` bounds run-ahead.  A separate
  completion thread materializes fetch handles, slices each request's rows
  back out (padding trimmed), and resolves futures.

- :class:`DecodeScheduler` (``gpt_causal`` token generation): drives the
  :class:`~paddle_tpu.serving.kv_cache.DecodeEngine` — each iteration runs
  ONE compiled step over the fixed slot batch; requests join a free slot
  (prefill consumes prompt tokens one per iteration through the same
  step), leave on EOS/max-tokens (pages freed), and the batch composition
  changes every iteration with zero recompiles.

Dispatch faults that are transient (``FLAGS_fault_inject`` fires,
infra errors tagged via ``resilience.mark_transient``) are ABSORBED: the
batch re-dispatches up to ``FLAGS_serving_max_retries`` times before the
batch's requests fail — counted in
``paddle_tpu_serving_faults_absorbed_total``.
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from .. import monitor as _monitor
from ..framework.executor import last_step_id
from .bucketing import PAD_TOKENS_CTR

OCCUPANCY_HIST = _monitor.REGISTRY.histogram(
    "paddle_tpu_serving_batch_occupancy",
    "real requests per dispatched batch/decode iteration (mean > 1 == "
    "continuous batching is actually coalescing), by mode: 'batch' for "
    "the coalescing batcher, 'decode' for the KV decode loop — a "
    "process running both must not blend them in per-server views",
    labelnames=("mode",),
    buckets=(1.0, 2.0, 3.0, 4.0, 6.0, 8.0, 12.0, 16.0, 24.0, 32.0, 64.0))
FAULTS_ABSORBED_CTR = _monitor.REGISTRY.counter(
    "paddle_tpu_serving_faults_absorbed_total",
    "transient dispatch faults absorbed by a batch re-dispatch "
    "(requests completed anyway)")

#: wall clock of the most recent scheduler-loop wake (batcher dispatch
#: or decode iteration) — the liveness proof behind the srv_q/occ/
#: slots/tps digest keys' FLAGS_fleet_digest_ttl_s aging
#: (monitor._serving_digest_fresh).  Liveness, not traffic: the idle
#: loops wake on their bounded waits and keep touching this, while a
#: scheduler wedged inside a dispatch stops — and its replica ages out
#: of router placement.  Benign-race float: single word, newest wins.
last_alive_wall = 0.0


def _touch_alive() -> None:
    global last_alive_wall
    last_alive_wall = time.time()

#: per-process request trace ids: every admitted request gets one, and
#: every phase span of its lifetime carries it — `trace` in the span
#: args groups the chain admission->materialize in the exported ring
_TRACE_IDS = itertools.count(1)


def _emit_request_trace(req: "Request", phases, e2e_ms: float,
                        bucket=None, extra=None) -> None:
    """Emit the request's phase spans (each tagged with its trace id,
    tenant, and bucket) into the tracer ring and the per-phase latency
    histograms.  ``phases`` is an ordered list of (name, t0, t1)
    perf_counter boundaries that PARTITION submit->resolve, so the
    per-phase sum reconstructs the measured end-to-end latency (the
    serving_smoke 10% gate).  ``extra`` maps phase name -> extra span
    args (the dispatch phase carries the process-global step id, batch
    width/occupancy, and the padding overhead)."""
    bucket = str(req.bucket if bucket is None else bucket)
    tenant = str(req.tenant)
    tracer = _monitor.TRACER
    for name, t0, t1 in phases:
        if t0 is None or t1 is None or t1 < t0:
            continue
        _monitor.SERVING_PHASE_HIST.observe(
            (t1 - t0) * 1e3, phase=name, tenant=tenant, bucket=bucket)
        if tracer.enabled:
            args = {"trace": req.trace_id, "tenant": tenant,
                    "bucket": bucket}
            if name == "materialize":
                # the request's measured e2e rides the LAST span of the
                # chain, so an offline reader can check the phase sum
                # against it without any out-of-band ledger
                args["e2e_ms"] = round(e2e_ms, 3)
            if extra and name in extra:
                args.update(extra[name])
            tracer.add_complete("serving." + name, "serving", t0, t1,
                                args)


class ServingFuture:
    """Resolution handle for one request (threading.Event based)."""

    def __init__(self):
        self._ev = threading.Event()
        self._result = None
        self._error: Optional[BaseException] = None

    def _resolve(self, result) -> None:
        self._result = result
        self._ev.set()

    def _fail(self, err: BaseException) -> None:
        self._error = err
        self._ev.set()

    def done(self) -> bool:
        return self._ev.is_set()

    def result(self, timeout: Optional[float] = None):
        if not self._ev.wait(timeout):
            raise TimeoutError("serving request still in flight")
        if self._error is not None:
            raise self._error
        return self._result


class Request:
    """One admitted request: per-example feeds (no batch dim) + future."""

    __slots__ = ("tenant", "feeds", "seq_len", "bucket", "future",
                 "t_submit", "prompt", "max_new_tokens", "eos_id",
                 "admit_gen", "trace_id", "tm")

    def __init__(self, tenant: str, feeds: Optional[Dict[str, Any]] = None,
                 seq_len: int = 0, bucket: int = 0,
                 prompt=None, max_new_tokens: int = 0,
                 eos_id: Optional[int] = None):
        self.tenant = tenant
        self.feeds = feeds
        self.seq_len = seq_len
        self.bucket = bucket
        self.future = ServingFuture()
        self.t_submit = time.perf_counter()
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.eos_id = eos_id
        self.admit_gen = 0   # tenant incarnation at admission (server)
        self.trace_id = next(_TRACE_IDS)
        # phase boundary marks (perf_counter): written strictly along
        # the request's pipeline handoffs (submit thread -> scheduler
        # thread -> completion thread), each handoff through a lock, so
        # readers always see the marks of the phases that finished
        self.tm: Dict[str, float] = {"submit": self.t_submit}


class ContinuousBatcher:
    """Bucket-coalescing scheduler + completion pipeline (batch mode)."""

    def __init__(self, executor, scope, bucket_plan, on_complete,
                 on_fail, max_retries: int = 1, batch_wait_ms: float = 0.0):
        self._exe = executor
        self._scope = scope
        self._plan = bucket_plan
        self._on_complete = on_complete      # (request, result, latency_ms)
        self._on_fail = on_fail              # (request, exception)
        self._max_retries = int(max_retries)
        self._wait_s = max(0.0, float(batch_wait_ms)) / 1e3
        self._cv = threading.Condition()
        self._queue: collections.deque = collections.deque()  # guarded-by: _cv
        self._pending = 0          # admitted, not yet resolved  # guarded-by: _cv
        self._stop = False         # guarded-by: _cv
        self._done_cv = threading.Condition()
        self._done_q: collections.deque = \
            collections.deque()    # guarded-by: _done_cv
        self._done_stop = False    # guarded-by: _done_cv
        self._threads: List[threading.Thread] = []

    # -- lifecycle -----------------------------------------------------------
    def start(self) -> None:
        for name, fn in (("serving-scheduler", self._schedule_loop),
                         ("serving-completion", self._complete_loop)):
            t = threading.Thread(target=fn, name=name, daemon=True)
            t.start()
            self._threads.append(t)

    def stop(self) -> None:
        """Stop accepting work; both threads exit after finishing what is
        already queued/in flight (the scheduler drains the queue, then
        its exit releases the completion thread — never the reverse, so
        a dispatched batch's futures always resolve)."""
        with self._cv:
            self._stop = True
            self._cv.notify_all()

    def enqueue(self, req: Request) -> bool:
        """False when the scheduler has been stopped — nothing would ever
        pop the queue, so the caller must fail the request instead of
        stranding its future (enqueue racing stop())."""
        with self._cv:
            if self._stop:
                return False
            req.tm["enq"] = time.perf_counter()
            self._queue.append(req)
            self._pending += 1
            self._cv.notify()
        return True

    def queue_depth(self) -> int:
        with self._cv:
            return len(self._queue)

    def drain(self, timeout_s: float = 60.0) -> bool:
        """Block until every admitted request has resolved (completed or
        failed) — the SIGTERM graceful-drain barrier.  False on timeout."""
        deadline = time.monotonic() + timeout_s
        with self._cv:
            while self._pending > 0:
                left = deadline - time.monotonic()
                if left <= 0:
                    return False
                self._cv.wait(min(left, 0.1))
        return True

    # -- scheduler thread ----------------------------------------------------
    def _take_batch(self) -> Optional[List[Request]]:
        """Pop the widest same-bucket batch available, coalescing-wait up
        to the window for stragglers; None on stop with an empty queue."""
        with self._cv:
            while not self._queue and not self._stop:
                _touch_alive()
                self._cv.wait(0.1)
            _touch_alive()
            if not self._queue:
                return None
            bucket = self._queue[0].bucket
        # resolve the bucket plan OUTSIDE the queue lock: a cold bucket
        # builds a program + HBM plan here, and submitters must not
        # block behind it.  Only this scheduler thread pops, so the
        # peeked head cannot be stolen meanwhile.  A factory/build error
        # fails that bucket's queued requests — not this thread (a dead
        # scheduler would strand every later future forever).
        try:
            width = self._plan.plan(bucket)[3]
        except Exception as e:
            with self._cv:
                bad = self._pop_bucket_locked(bucket, len(self._queue))
            self._fail_batch(bad, e)
            return []
        with self._cv:
            deadline = time.monotonic() + self._wait_s
            batch = self._pop_bucket_locked(bucket, width)
            while len(batch) < width and not self._stop:
                left = deadline - time.monotonic()
                if left <= 0:
                    break
                self._cv.wait(left)
                batch.extend(
                    self._pop_bucket_locked(bucket, width - len(batch)))
            return batch

    def _pop_bucket_locked(self, bucket: int, n: int) -> List[Request]:
        # guarded-by-caller: _cv
        out: List[Request] = []
        if n <= 0:
            return out
        now = time.perf_counter()
        keep: collections.deque = collections.deque()
        while self._queue:
            r = self._queue.popleft()
            if r.bucket == bucket and len(out) < n:
                r.tm["pop"] = now        # queue_wait ends here
                out.append(r)
            else:
                keep.append(r)
        self._queue.extend(keep)
        return out

    def _schedule_loop(self) -> None:
        try:
            self._schedule_loop_inner()
        finally:
            # the completion thread exits only AFTER this thread: a
            # stop() racing an in-flight batch must let the completion
            # side drain everything the scheduler ever appended, or the
            # batch's futures would strand un-resolved
            with self._done_cv:
                self._done_stop = True
                self._done_cv.notify_all()

    def _schedule_loop_inner(self) -> None:
        while True:
            batch = self._take_batch()
            if batch is None:
                return
            if not batch:
                continue             # bucket-plan failure already handled
            bucket = batch[0].bucket
            try:
                compiled, feed_names, fetch_names, width = \
                    self._plan.plan(bucket)
                feed = self._assemble(batch, bucket, feed_names, width,
                                      compiled.program)
            except Exception as e:
                # a malformed request (missing feed key, oversize or
                # ragged array) must fail ITS batch, never kill this
                # thread — a dead scheduler would strand every later
                # request's future forever
                self._fail_batch(batch, e)
                continue
            PAD_TOKENS_CTR.inc(width - len(batch))
            t_d0 = time.perf_counter()
            handles = self._dispatch(compiled, feed, fetch_names, batch)
            t_d1 = time.perf_counter()
            OCCUPANCY_HIST.observe(float(len(batch)), mode="batch")
            _monitor.SERVING_LAST_OCC_GAUGE.set(float(len(batch)))
            if handles is None:
                continue                     # batch failed; futures done
            # correlation hint: the step id the executor just stamped on
            # its executor.dispatch span + StepTraceAnnotation — this
            # scheduler thread dispatched it, so reading it here (before
            # any other run() of ours) names OUR step
            meta = {"t_d0": t_d0, "t_d1": t_d1, "step": last_step_id(),
                    "width": width, "occupancy": len(batch)}
            with self._done_cv:
                self._done_q.append((batch, handles, bucket, meta))
                self._done_cv.notify()

    @staticmethod
    def _assemble(batch, bucket, feed_names, width, program):
        """Padded fixed-shape batch feed from the requests' per-example
        arrays (raises on malformed requests — caller fails the batch).
        The BUCKET PROGRAM's declared var shapes say which feeds carry
        the sequence axis: only feeds declared at the bucket length are
        padded; fixed-length feeds (a static feature vector) stack as-is
        and a mismatch fails the batch loudly instead of smuggling a
        wrong shape into a fresh compile."""
        from .bucketing import pad_to_bucket
        block = program.global_block()
        feed = {}
        for name in feed_names:
            declared = tuple(block.var(name).shape or ()) \
                if block.has_var(name) else ()
            is_seq = len(declared) > 1 and declared[1] == bucket
            rows = [pad_to_bucket(r.feeds[name], bucket) if is_seq
                    else np.asarray(r.feeds[name]) for r in batch]
            a = np.stack(rows)
            if len(batch) < width:           # fixed-shape dummy rows
                a = np.pad(a, [(0, width - len(batch))] +
                           [(0, 0)] * (a.ndim - 1))
            feed[name] = a
        return feed

    def _dispatch(self, compiled, feed, fetch_names, batch):
        """Run the batch; transient faults re-dispatch up to the retry
        budget (injected-fault absorption), anything else — or an
        exhausted budget — fails the batch's futures."""
        from .. import resilience as _resil
        attempt = 0
        while True:
            try:
                # watchdog-watched: a dispatch hung past
                # FLAGS_watchdog_timeout_s dumps all stacks and raises
                # HungStepError here — non-transient, so it falls through
                # to _fail_batch instead of silently stalling the queue
                with _resil.WATCHDOG.watch("serving.batch_dispatch"):
                    _resil.maybe_inject("serving.batch_dispatch")
                    return self._exe.run(
                        compiled, feed=feed, fetch_list=list(fetch_names),
                        scope=self._scope, return_numpy=False)
            except Exception as e:
                if _resil.is_transient(e) and attempt < self._max_retries:
                    attempt += 1
                    FAULTS_ABSORBED_CTR.inc()
                    if _monitor.TRACER.enabled:
                        _monitor.TRACER.instant(
                            "serving.fault_absorbed", "serving",
                            {"attempt": attempt, "error": repr(e)[:120]})
                    continue
                self._fail_batch(batch, e)
                return None

    def _fail_batch(self, batch, err) -> None:
        for r in batch:
            self._on_fail(r, err)
        with self._cv:
            self._pending -= len(batch)
            self._cv.notify_all()

    # -- completion thread ---------------------------------------------------
    def _complete_loop(self) -> None:
        while True:
            with self._done_cv:
                while not self._done_q:
                    if self._done_stop:
                        return
                    self._done_cv.wait(0.1)
                batch, handles, bucket, meta = self._done_q.popleft()
            try:
                # materialize AND slice before resolving anything: a
                # failure here (async device error, unexpected fetch
                # rank) fails the whole batch's futures instead of
                # killing this thread with some futures half-resolved
                outs = [np.asarray(h) for h in handles]
                results = []
                for i, r in enumerate(batch):
                    result = []
                    for a in outs:
                        row = a[i]
                        if (row.ndim >= 1 and row.shape[0] == bucket
                                and r.seq_len != bucket):
                            row = row[:r.seq_len]  # trim bucket padding
                        result.append(row)
                    results.append(result)
            except Exception as e:
                self._fail_batch(batch, e)
                continue
            now = time.perf_counter()
            pad = meta["width"] - meta["occupancy"]
            dispatch_args = {
                "step": meta["step"], "width": meta["width"],
                "occupancy": meta["occupancy"], "pad_rows": pad,
                "pad_frac": round(pad / float(meta["width"]), 4)}
            for r, result in zip(batch, results):
                e2e_ms = (now - r.t_submit) * 1e3
                _emit_request_trace(r, (
                    ("admit", r.tm.get("submit"), r.tm.get("enq")),
                    ("queue_wait", r.tm.get("enq"), r.tm.get("pop")),
                    ("batch_wait", r.tm.get("pop"), meta["t_d0"]),
                    ("dispatch", meta["t_d0"], meta["t_d1"]),
                    ("materialize", meta["t_d1"], now),
                ), e2e_ms, extra={"dispatch": dispatch_args})
                self._on_complete(r, result, e2e_ms)
            with self._cv:
                self._pending -= len(batch)
                self._cv.notify_all()


class _SlotState:
    __slots__ = ("req", "tokens", "pos", "generated", "iters", "token_t")

    def __init__(self, req: Request):
        self.req = req
        self.tokens: List[int] = [int(t) for t in np.asarray(
            req.prompt).ravel()]
        self.pos = 0
        self.generated: List[int] = []
        self.token_t: List[float] = []   # perf_counter of each of them
        self.iters = 0          # decode iterations this request rode


class DecodeScheduler:
    """Continuous-batching loop over the paged-KV decode engine.

    One thread, one compiled step: every iteration admits queued requests
    into free slots, feeds each active slot its current token (prompt
    token during prefill, previous argmax during generation), and retires
    slots whose request hit EOS / max_new_tokens — freeing their pages
    for the next request with the compile counter flat."""

    def __init__(self, engine, on_complete, on_fail,
                 max_retries: int = 1):
        self._engine = engine
        self._on_complete = on_complete
        self._on_fail = on_fail
        self._max_retries = int(max_retries)
        self._cv = threading.Condition()
        self._queue: collections.deque = collections.deque()  # guarded-by: _cv
        self._pending = 0   # guarded-by: _cv
        self._stop = False  # guarded-by: _cv
        self._slots: List[Optional[_SlotState]] = \
            [None] * engine.max_slots
        self._thread: Optional[threading.Thread] = None
        self._iter = 0                 # decode-loop iterations (loop thread only)
        #: trailing (t, n_generated) window for the tokens/s gauge —
        #: touched only by the decode thread
        self._tok_win: collections.deque = collections.deque()
        #: per-tenant KV-page ownership — admits happen in _admit_locked
        #: and releases in _retire/_run_step, all on the decode thread,
        #: so no lock; statusz readers go through kv_census() which
        #: snapshots the slot list
        self._tenant_pages: Dict[str, int] = {}

    def start(self) -> None:
        self._thread = threading.Thread(
            target=self._loop, name="serving-decode", daemon=True)
        self._thread.start()

    def stop(self) -> None:
        with self._cv:
            self._stop = True
            self._cv.notify_all()

    def enqueue(self, req: Request) -> bool:
        """False when the decode loop has been stopped (see
        :meth:`ContinuousBatcher.enqueue`)."""
        with self._cv:
            if self._stop:
                return False
            req.tm["enq"] = time.perf_counter()
            self._queue.append(req)
            self._pending += 1
            self._cv.notify()
        return True

    def queue_depth(self) -> int:
        with self._cv:
            return len(self._queue)

    def drain(self, timeout_s: float = 60.0) -> bool:
        deadline = time.monotonic() + timeout_s
        with self._cv:
            while self._pending > 0:
                left = deadline - time.monotonic()
                if left <= 0:
                    return False
                self._cv.wait(min(left, 0.1))
        return True

    # -- decode loop ---------------------------------------------------------
    def _admit_locked(self) -> None:
        # guarded-by-caller: _cv
        for s, state in enumerate(self._slots):
            if state is not None or not self._queue:
                continue
            req = self._queue[0]
            # reserve the request's WORST-CASE pages now: admission is
            # the only safe wait point (completions run on this same
            # thread, so a mid-flight page stall could never resolve)
            need = -(-(int(np.asarray(req.prompt).size)
                       + req.max_new_tokens) // self._engine.page_len)
            if not self._engine.reserve_slot(s, max(1, need)):
                break               # pool exhausted: wait for completions
            self._queue.popleft()
            req.tm["slot"] = time.perf_counter()   # queue_wait ends
            self._slots[s] = _SlotState(req)
            self._kv_account(req.tenant,
                             len(self._engine.cache.pages_of(s)),
                             reserved=True)

    def _loop(self) -> None:
        eng = self._engine
        S = eng.max_slots
        while True:
            _touch_alive()
            with self._cv:
                self._admit_locked()
                active_slots = [s for s in range(S)
                                if self._slots[s] is not None]
                if not active_slots:
                    if self._stop:
                        return
                    self._cv.wait(0.1)
                    continue
            ids = np.zeros(S, np.int32)
            pos = np.zeros(S, np.int32)
            active = np.zeros(S, bool)
            stepped = []
            for s in active_slots:
                st = self._slots[s]
                # the page covering this position must exist BEFORE the
                # step writes into it; an exhausted pool parks the slot
                # for this iteration (completions will free pages)
                if not eng.ensure_page(s, st.pos):
                    continue
                ids[s] = st.tokens[st.pos]
                pos[s] = st.pos
                active[s] = True
                stepped.append(s)
            if not stepped:
                time.sleep(0.001)
                continue
            _monitor.SERVING_FREE_SLOTS_GAUGE.set(
                float(S - len(active_slots)))
            self._iter += 1
            t_i0 = time.perf_counter()
            logits = self._run_step(ids, pos, active, stepped)
            t_i1 = time.perf_counter()
            if _monitor.TRACER.enabled:
                _monitor.TRACER.add_complete(
                    "serving.decode_iter", "serving", t_i0, t_i1,
                    {"iter": self._iter, "occupancy": len(stepped)})
                self._emit_step_phases(t_i0, t_i1)
            if logits is None:
                continue
            self._logits_sentinel(logits, stepped)
            OCCUPANCY_HIST.observe(float(len(stepped)), mode="decode")
            _monitor.SERVING_LAST_OCC_GAUGE.set(float(len(stepped)))
            now = time.perf_counter()
            n_gen = 0
            for s in stepped:
                st = self._slots[s]
                st.pos += 1
                st.iters += 1
                if st.pos < len(st.tokens):
                    continue                   # prefill: next prompt token
                nxt = int(np.argmax(logits[s]))
                st.tokens.append(nxt)
                st.generated.append(nxt)
                st.token_t.append(time.perf_counter())
                n_gen += 1
                done = (len(st.generated) >= st.req.max_new_tokens
                        or (st.req.eos_id is not None
                            and nxt == st.req.eos_id)
                        or st.pos + 1 >= eng.max_seq)
                if done:
                    self._retire(s, st, now)
            self._update_token_rate(now, n_gen)
            if _monitor.TRACER.enabled:
                # argmax, retirements and their callbacks: the host work
                # that follows the step before the next one can start
                _monitor.TRACER.add_complete(
                    "serving.decode_iter.sample", "serving", t_i1,
                    time.perf_counter(), {"iter": self._iter})

    def _emit_step_phases(self, t_i0: float, t_i1: float) -> None:
        """The engine's marks of the step just run (``DecodeEngine.
        phase_marks``) as child spans of the iteration span: ``dispatch``
        (the jitted call until it returns), ``device_wait`` (until the
        logits are ready), ``logits_to_host`` (the copy)."""
        marks = getattr(self._engine, "phase_marks", None)
        if not marks or not t_i0 <= marks[0] <= marks[3] <= t_i1:
            return                  # no marks, or an earlier iteration's
        args = {"iter": self._iter}
        for name, a, b in zip(("dispatch", "device_wait", "logits_to_host"),
                              marks, marks[1:]):
            _monitor.TRACER.add_complete(
                "serving.decode_step." + name, "serving", a, b, args)

    def _run_step(self, ids, pos, active, stepped):
        from .. import resilience as _resil
        attempt = 0
        while True:
            try:
                # watchdog-watched like the batcher's dispatch: a hung
                # decode iteration dumps stacks and fails its requests
                with _resil.WATCHDOG.watch("serving.decode_step"):
                    _resil.maybe_inject("serving.decode_step")
                    return self._engine.run_iteration(ids, pos, active)
            except Exception as e:
                # retry only while the donated pools survived the
                # failure: a fault from INSIDE the jitted step consumed
                # the k/v buffers, and re-invoking with deleted arrays
                # would just fail differently — fail the requests and
                # rebuild the pools instead
                alive = self._engine.cache.buffers_alive()
                if (alive and _resil.is_transient(e)
                        and attempt < self._max_retries):
                    attempt += 1
                    FAULTS_ABSORBED_CTR.inc()
                    continue
                # every active slot's cached prefix rides those pools —
                # all of them are lost, not just this iteration's set
                failed = [s for s in range(len(self._slots))
                          if self._slots[s] is not None] \
                    if not alive else list(stepped)
                for s in failed:
                    st = self._slots[s]
                    self._kv_account(
                        st.req.tenant,
                        -len(self._engine.cache.pages_of(s)))
                    self._engine.release_slot(s)
                    self._slots[s] = None
                    self._on_fail(st.req, e)
                if not alive:
                    self._engine.cache.reinit_pools()
                with self._cv:
                    self._pending -= len(failed)
                    self._cv.notify_all()
                return None

    def _logits_sentinel(self, logits, stepped) -> None:
        """Decode-path numerics sentinel (behind ``FLAGS_numerics``): a
        non-finite logit means the model/KV state is poisoned and every
        argmax downstream of it is garbage — count it per class
        ('logits') and emit ONE anomaly record per episode.  The logits
        are already host-side at argmax time, so the scan costs one
        vectorized pass, no device sync."""
        try:
            from ..analysis import numerics as _numerics
            if _numerics.mode() == "off":
                return
            sub = logits[stepped] if len(stepped) < logits.shape[0] \
                else logits
            bad = int(sub.size - np.count_nonzero(np.isfinite(sub)))
            _numerics.note_nonfinite(
                "logits", bad, step=self._iter,
                detail={"slots": list(map(int, stepped))} if bad
                else None)
        except Exception:
            pass            # the sentinel must never fail a decode step

    def _kv_account(self, tenant, delta: int, reserved: bool = False) -> None:
        """Per-tenant KV-page bookkeeping (decode thread only): the
        occupancy gauge tracks pages currently owned by the tenant's
        requests, and each admission's reservation bumps the cumulative
        counter — both fold on tenant eviction through
        ``monitor.retire_tenant_series`` (PR-2 semantics), so a
        revolving tenant population cannot grow the registry while
        ``counter_totals()`` stays exact."""
        tenant = str(tenant)
        total = max(self._tenant_pages.get(tenant, 0) + int(delta), 0)
        self._tenant_pages[tenant] = total
        _monitor.SERVING_KV_TENANT_PAGES.set(float(total), tenant=tenant)
        if total == 0:
            # no pages -> no fragmentation: the frag gauge is otherwise
            # written only by kv_census() scrapes and would freeze at
            # the last in-flight value after the tenant's requests retire
            _monitor.SERVING_KV_TENANT_FRAG.set(0.0, tenant=tenant)
        if reserved and delta > 0:
            _monitor.SERVING_KV_TENANT_ALLOC_CTR.inc(int(delta),
                                                     tenant=tenant)

    def kv_census(self) -> Dict[str, dict]:
        """Per-tenant KV-page occupancy + internal fragmentation (the
        /statusz memory section): for every in-flight request, pages
        owned vs positions actually written — ``frag = 1 - written /
        (pages * page_len)`` is the reserved-but-unwritten tail (worst-
        case admission reservations inflate it early in a request's
        life).  Also refreshes the per-tenant fragmentation gauge.
        Reads a snapshot of the slot list, so a concurrent decode
        iteration costs at most a stale row, never a crash."""
        page_len = int(self._engine.page_len)
        census: Dict[str, dict] = {}
        for s, st in enumerate(list(self._slots)):
            if st is None:
                continue
            t = str(st.req.tenant)
            row = census.setdefault(
                t, {"pages": 0, "written_tokens": 0, "requests": 0})
            row["pages"] += len(self._engine.cache.pages_of(s))
            row["written_tokens"] += int(st.pos)
            row["requests"] += 1
        for t, row in census.items():
            cap = row["pages"] * page_len
            row["frag"] = round(1.0 - row["written_tokens"] / cap,
                                4) if cap else 0.0
            _monitor.SERVING_KV_TENANT_FRAG.set(row["frag"], tenant=t)
        return census

    def _update_token_rate(self, now: float, n_gen: int,
                           window_s: float = 5.0) -> None:
        """Windowed generated-tokens/s into the gauge the heartbeat
        digest ships as ``tps`` (decode thread only — no lock)."""
        if n_gen:
            _monitor.SERVING_TOKENS_CTR.inc(n_gen)
        win = self._tok_win
        win.append((now, n_gen))
        while win and now - win[0][0] > window_s:
            win.popleft()
        # a lone sample after an idle gap carries no rate information:
        # floor its span at 1 s so the first token back doesn't publish
        # a phantom 1000 tok/s spike into the routing digest
        span = max(now - win[0][0], 1.0 if len(win) == 1 else 1e-3)
        _monitor.SERVING_TPS_GAUGE.set(
            round(sum(n for _, n in win) / span, 3))

    @staticmethod
    def _decode_span_args(st) -> dict:
        """What the request's ``serving.decode`` span carries, written once
        at completion: every generated token's time as milliseconds from
        submission (``token_ms``; the first is ``ttft_ms``)."""
        t_sub = st.req.t_submit
        token_ms = [round((t - t_sub) * 1e3, 3) for t in st.token_t]
        args = {"iters": st.iters, "generated": len(st.generated),
                "token_ms": token_ms}
        if token_ms:
            args["ttft_ms"] = token_ms[0]
        return args

    def _retire(self, s, st, now) -> None:
        self._kv_account(st.req.tenant,
                         -len(self._engine.cache.pages_of(s)))
        self._engine.release_slot(s)
        self._slots[s] = None
        out = np.asarray(st.generated, np.int32)
        done_t = time.perf_counter()
        e2e_ms = (done_t - st.req.t_submit) * 1e3
        tm = st.req.tm
        _emit_request_trace(st.req, (
            ("admit", tm.get("submit"), tm.get("enq")),
            ("queue_wait", tm.get("enq"), tm.get("slot")),
            ("decode", tm.get("slot"), now),
            ("materialize", now, done_t),
        ), e2e_ms, bucket="decode",
            extra={"decode": self._decode_span_args(st)}
            if _monitor.TRACER.enabled else None)
        _monitor.SERVING_FREE_SLOTS_GAUGE.set(float(sum(
            1 for x in self._slots if x is None)))
        self._on_complete(st.req, out, e2e_ms)
        with self._cv:
            self._pending -= 1
            self._cv.notify_all()
