"""Numerics observability plane: in-graph tensor-health statistics,
anomaly detection with auto-capture, and checkpoint quarantine.

PRs 8 and 11 built the *time*-domain observability plane (device
attribution, MFU, request tracing); nothing in the runtime observed
*values* — a NaN'd loss, an exploding grad norm, or a bf16 overflow was
invisible until a user eyeballed printed losses.  This module is the
value-domain counterpart:

- **In-graph stats** (:func:`build_step_stats`): behind ``FLAGS_numerics``
  (``off`` | ``sentinel`` | ``full``) the lowered step computes per-step
  tensor-health statistics INSIDE the jitted program — NaN/Inf trips
  for gradients and weight state plus the global grad norm at one
  reduction per tensor (``sentinel``), adding per-variable grad L2
  norms and absmax, element-exact finite masks, weight-update ratios
  (‖Δw‖/‖w‖), activation coverage and log2 dynamic-range histograms
  (``full``) — folded into ONE small packed f32 vector output per step.  The stats ride the PR-1
  lazy-fetch path: the training thread never syncs on them.

- **Anomaly engine** (:class:`NumericsEngine`, the process ``ENGINE``):
  materializes stats frames only once their arrays are ready (or a
  bounded backlog forces it — counted, never silent), runs NaN/Inf
  sentinel trips and windowed-median grad-norm spike detection with
  hysteresis, fires ``numerics.anomaly`` trace instants, opens a PR-9
  style profiler window (``trigger: "anomaly"`` in the manifest), and
  QUARANTINES the checkpoint plane: once a step is poisoned, the
  :class:`~paddle_tpu.resilience.CheckpointDaemon` holds commit so the
  gang manifest never advances past the last healthy step.

- **Surfaces**: per-variable gauges
  ``paddle_tpu_numerics_{grad_norm,update_ratio,absmax}`` with a bounded
  top-K registry series set (churn folds out, PR-2 retirement
  semantics), ``paddle_tpu_numerics_nonfinite_total{var_class}``
  counters, and the ``gnorm``/``nanf`` heartbeat-digest keys the gang
  coordinator folds into per-rank gauges and ``tools/gangtop.py``
  columns — a single rank producing NaNs is identifiable fleet-wide in
  one screen.

The dynamic-range histograms are the enabling signal for the ROADMAP's
quantized-collectives arc (EQuARX-style blockwise int8 needs per-tensor
dynamic range to pick scales; :func:`loss_fingerprint`'s trajectory sha1
is the matching loss-parity gate).
"""

from __future__ import annotations

import collections
import hashlib
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from .. import monitor as _monitor

__all__ = [
    "MODES", "mode", "configure", "build_step_stats", "StatsLayout",
    "NumericsFrame", "NumericsEngine", "ENGINE", "record_anomaly",
    "note_nonfinite", "poisoned_since", "is_poisoned", "clear_quarantine",
    "plan_numerics", "loss_fingerprint",
]

MODES = ("off", "sentinel", "full")

#: per-variable sections traced in full mode are bounded: the largest
#: tensors dominate both numerics risk and cost, the tail folds into one
#: aggregate "other" section
MAX_TRACED_VARS = 32

#: log2 dynamic-range histogram bins: floor(log2|x|) clipped to
#: [_HIST_LO, _HIST_HI] — bf16's normal range is ~[-126, 127] but the
#: actionable band for int8 scale picking is this window
_HIST_LO, _HIST_HI = -20, 11
HIST_BINS = _HIST_HI - _HIST_LO + 1

# ---------------------------------------------------------------------------
# metric families (declared at import so digest presence-gating works the
# moment the engine publishes its first frame)
# ---------------------------------------------------------------------------

NUM_GNORM_GAUGE = _monitor.REGISTRY.gauge(
    "paddle_tpu_numerics_grad_norm",
    "per-variable gradient L2 norm of the most recent processed step "
    "(top-K by norm; churn folds out so the registry stays bounded)",
    ("var",))
NUM_UPDATE_GAUGE = _monitor.REGISTRY.gauge(
    "paddle_tpu_numerics_update_ratio",
    "per-variable weight-update ratio ‖Δw‖/‖w‖ of the most recent "
    "processed step (top-K by ratio) — the classic LR-sanity signal "
    "(healthy training sits around 1e-3)", ("var",))
NUM_ABSMAX_GAUGE = _monitor.REGISTRY.gauge(
    "paddle_tpu_numerics_absmax",
    "per-variable gradient absmax of the most recent processed step "
    "(top-K; the bf16/int8 overflow headroom signal)", ("var",))
NUM_GLOBAL_GNORM_GAUGE = _monitor.REGISTRY.gauge(
    "paddle_tpu_numerics_global_grad_norm",
    "global gradient L2 norm (sqrt of the sum over EVERY grad var, "
    "traced or not) of the most recent processed step — the heartbeat "
    "digest's 'gnorm' key")
NUM_RANGE_GAUGE = _monitor.REGISTRY.gauge(
    "paddle_tpu_numerics_dynamic_range_bits",
    "occupied log2 dynamic range (highest - lowest populated exponent "
    "bin) of the most recent step's histogram, by class — the signal a "
    "blockwise-int8 quantization policy reads for scale headroom",
    ("var_class",))
NONFINITE_CTR = _monitor.REGISTRY.counter(
    "paddle_tpu_numerics_nonfinite_total",
    "non-finite (NaN/Inf) observations by variable class (grad / act / "
    "weight / logits): ELEMENT counts in full mode and the serving "
    "logits sentinel, poisoned-TENSOR counts in sentinel mode — the "
    "heartbeat digest's 'nanf' key", ("var_class",))
ANOMALY_CTR = _monitor.REGISTRY.counter(
    "paddle_tpu_numerics_anomalies_total",
    "numerics anomaly records by kind (nonfinite / grad_spike / "
    "nonfinite_logits / loss_scale_* / step_skipped)", ("kind",))
FORCED_SYNC_CTR = _monitor.REGISTRY.counter(
    "paddle_tpu_numerics_forced_syncs_total",
    "stats frames materialized by the backlog bound instead of the "
    "ready-poll — nonzero means the lazy path fell behind and the "
    "training thread paid a host sync")
QUARANTINE_CTR = _monitor.REGISTRY.counter(
    "paddle_tpu_checkpoint_quarantine_holds_total",
    "checkpoint captures held back because the numerics engine has the "
    "step quarantined (poisoned state must not advance the manifest)")


# ---------------------------------------------------------------------------
# configuration (mirrors FLAGS_numerics*; set_flags side-effects call
# configure(), the executor reads the module-level mode per dispatch)
# ---------------------------------------------------------------------------

_CONFIG = {
    "mode": "off",
    "spike_factor": 10.0,
    "window": 16,
    "topk": 8,
    "quarantine": True,
}


def mode() -> str:
    """The active ``FLAGS_numerics`` mode (one attribute read — the
    executor's per-dispatch fast path keys its plans on this)."""
    return _CONFIG["mode"]


def configure(mode: str, spike_factor: Optional[float] = None,
              window: Optional[int] = None, topk: Optional[int] = None,
              quarantine: Optional[bool] = None) -> None:
    if mode not in MODES:
        raise ValueError(
            f"FLAGS_numerics must be one of {MODES}, got {mode!r}")
    _CONFIG["mode"] = mode
    if spike_factor is not None:
        _CONFIG["spike_factor"] = float(spike_factor)
    if window is not None:
        _CONFIG["window"] = max(int(window), 4)
    if topk is not None:
        _CONFIG["topk"] = max(int(topk), 1)
    if quarantine is not None:
        _CONFIG["quarantine"] = bool(quarantine)


# ---------------------------------------------------------------------------
# compiler stat-capture slot: the post-fusion variable census
# ---------------------------------------------------------------------------

_plan_cache: Dict[Any, Dict[str, Any]] = {}
_plan_lock = threading.Lock()


def plan_numerics(program, fetch_names=()) -> Dict[str, Any]:
    """Static numerics-capture plan over the (post-fusion) program: the
    float intermediate activations the in-graph stats builder may trace
    in ``full`` mode.  Runs in ``compiler.optimize``'s pass slot AFTER
    fusion so fused programs census the variables the rewritten program
    actually produces, and is stamped into
    ``program._attrs["numerics"]`` (clone carries it onto the optimized
    program).  Fingerprint-cached; advisory — the trace-time builder
    intersects it with the live value environment, and grads/weights
    always trace regardless."""
    key = (program.fingerprint(), tuple(fetch_names))
    with _plan_lock:
        plan = _plan_cache.get(key)
        if plan is not None:
            return plan
    block = program.global_block()
    acts = []
    written = set()
    for op in block.ops:
        if op.type in ("feed", "fetch"):
            continue
        for n in op.output_arg_names():
            if not n or n in written:
                continue
            written.add(n)
            if not block.has_var(n):
                continue
            v = block.var(n)
            dt = str(getattr(v, "dtype", "") or "")
            if "float" not in dt and "bf16" not in dt:
                continue
            if not n.endswith("@GRAD") and not v.persistable:
                acts.append(n)
    # activations only: grads and weight state always trace from the
    # live value environment (missing one is exactly the blind spot to
    # avoid), so a census of them would be dead data
    plan = {"acts": sorted(acts)}
    with _plan_lock:
        if len(_plan_cache) > 256:
            _plan_cache.clear()
        _plan_cache.setdefault(key, plan)
    return plan


# ---------------------------------------------------------------------------
# trace-time stats builder
# ---------------------------------------------------------------------------

class StatsLayout:
    """Host-side description of one packed stats vector.

    Header (both modes): ``[nonfinite_grad, nonfinite_act,
    nonfinite_weight, global_gnorm_sq, grad_absmax, act_absmax]``.
    The weight-state scan matters even with the grad scan present: a
    NaN'd weight can hide from the backward entirely (``relu_grad``
    masks on ``x > 0``, and ``NaN > 0`` is False — the gradient comes
    back a clean 0) while the persisted state is poisoned forever.
    ``full`` appends, in order: 3 floats per traced grad (``gnorm_sq,
    absmax, nonfinite``), 2 per traced weight (``wnorm_sq, dnorm_sq``),
    then the grad and act log2 dynamic-range histograms
    (:data:`HIST_BINS` bins each)."""

    HEADER = 6

    def __init__(self, mode: str, grads: Tuple[str, ...] = (),
                 weights: Tuple[str, ...] = ()):
        self.mode = mode
        self.grads = tuple(grads)
        self.weights = tuple(weights)

    @property
    def size(self) -> int:
        if self.mode != "full":
            return self.HEADER
        return (self.HEADER + 3 * len(self.grads)
                + 2 * len(self.weights) + 2 * HIST_BINS)


def _is_float(v) -> bool:
    import jax.numpy as jnp
    dt = getattr(v, "dtype", None)
    if dt is None:
        return False
    try:
        return bool(jnp.issubdtype(dt, jnp.floating))
    except TypeError:
        return False


def _static_size(v) -> int:
    shape = getattr(v, "shape", None) or ()
    n = 1
    for d in shape:
        n *= int(d) if d else 1
    return n


def _exp_hist(parts):
    """Aggregate log2 dynamic-range histogram over a list of arrays:
    bin = clip(floor(log2|x|), lo, hi) over the finite nonzero
    elements.  One scatter-add per tensor — full-mode cost, by design."""
    import jax.numpy as jnp
    hist = jnp.zeros((HIST_BINS,), jnp.float32)
    for x in parts:
        ax = jnp.abs(jnp.ravel(x).astype(jnp.float32))
        ok = jnp.isfinite(ax) & (ax > 0)
        e = jnp.clip(jnp.floor(jnp.log2(jnp.where(ok, ax, 1.0))),
                     _HIST_LO, _HIST_HI)
        idx = (e - _HIST_LO).astype(jnp.int32)
        hist = hist.at[idx].add(jnp.where(ok, 1.0, 0.0))
    return hist


def build_step_stats(values: Dict[str, Any], written,
                     feed_names, persist_rw, rw_in, rw_out,
                     mode: str, spec: Optional[Dict[str, Any]] = None,
                     force: bool = False):
    """Trace-time: fold the block's tensor-health statistics into one
    packed f32 vector (returns ``(layout, packed)``, or ``(None, None)``
    when the block has nothing to observe — e.g. a startup program —
    and ``force`` is off; forcing returns an all-zero header so callers
    that need a fixed output arity, like the executor, always get one).

    ``sentinel`` observes GRADIENTS only (NaN/Inf counts, global norm,
    absmax) — NaN'd forward math poisons the backward within the same
    step, so a grad sentinel catches it at a fraction of the cost of
    scanning every activation.  ``full`` adds per-variable sections,
    weight-update ratios and activation absmax/dynamic-range
    histograms.

    Called from inside the lowered ``step()`` while tracing, so every
    operation here becomes part of the jitted program; the packed vector
    is ONE small extra output that rides the async dispatch.  ``spec``
    is the compiler's post-fusion census (advisory: intersected with the
    live value environment so a partially-fed program never KeyErrors).
    """
    import jax.numpy as jnp
    f32 = jnp.float32
    feed_set = set(feed_names)

    def _live_float(n):
        v = values.get(n)
        return v if v is not None and _is_float(v) \
            and getattr(v, "ndim", None) is not None else None

    grad_names = sorted(n for n in written
                        if n.endswith("@GRAD")
                        and _live_float(n) is not None)
    act_names = []
    if mode == "full":
        act_names = sorted(
            n for n in written
            if not n.endswith("@GRAD") and n not in feed_set
            and n not in persist_rw and _live_float(n) is not None
            and getattr(values[n], "ndim", 0) >= 1)
        if spec:
            # the compiler's census restricts activations (a fused
            # program's internal temporaries the census dropped stay
            # untraced); grads and weight state always trace — missing
            # one is exactly the blind spot to avoid
            allowed = set(spec.get("acts", ()))
            if allowed:
                act_names = [n for n in act_names if n in allowed]
    # weight pairs: rw persistables whose incoming value has the same
    # shape as the outgoing one (write-only rw gets dummy scalar zeros)
    weight_pairs = []
    if mode == "full":
        for n, old, new in zip(persist_rw, rw_in, rw_out):
            if (_is_float(new) and hasattr(old, "shape")
                    and getattr(old, "shape", None)
                    == getattr(new, "shape", None)
                    and _is_float(old) and (n + "@GRAD") in values):
                weight_pairs.append((n, old, new))
    state_vals = [v for v in rw_out if _is_float(v)
                  and getattr(v, "ndim", None) is not None]
    if not grad_names and not act_names and not weight_pairs \
            and not state_vals and not force:
        return None, None

    grad_vals = [values[n] for n in grad_names]
    act_vals = [values[n] for n in act_names]

    def _nonfinite(parts):
        t = jnp.zeros((), f32)
        for x in parts:
            t = t + jnp.sum(
                (~jnp.isfinite(x.astype(f32))).astype(f32))
        return t

    def _absmax(parts):
        if not parts:
            return jnp.zeros((), f32)
        return jnp.max(jnp.stack(
            [jnp.max(jnp.abs(x.astype(f32))) if x.size else
             jnp.zeros((), f32) for x in parts]))

    gnorm_sqs = [jnp.sum(jnp.square(g.astype(f32))) for g in grad_vals]
    if mode != "full":
        # sentinel tier: ONE reduction per tensor, total.  Finiteness is
        # derived from the reduction scalars (NaN/Inf propagate through
        # a sum), so the nonfinite_* header slots count poisoned TENSORS
        # here, not elements — the engine only needs > 0 to trip, and
        # the elementwise scans + absmax passes are exactly what pushed
        # the overhead past the 5% budget on small steps.
        def _tensor_trips(scalars):
            t = jnp.zeros((), f32)
            for s in scalars:
                t = t + (~jnp.isfinite(s)).astype(f32)
            return t

        state_sums = [jnp.sum(v.astype(f32)) for v in state_vals]
        header = [
            _tensor_trips(gnorm_sqs),
            jnp.zeros((), f32),
            _tensor_trips(state_sums),
            (sum(gnorm_sqs[1:], gnorm_sqs[0]) if gnorm_sqs
             else jnp.zeros((), f32)),
            jnp.zeros((), f32),
            jnp.zeros((), f32),
        ]
        return StatsLayout("sentinel"), jnp.stack(header)
    header = [
        _nonfinite(grad_vals),
        _nonfinite(act_vals),
        _nonfinite(state_vals),
        (sum(gnorm_sqs[1:], gnorm_sqs[0]) if gnorm_sqs
         else jnp.zeros((), f32)),
        _absmax(grad_vals),
        _absmax(act_vals),
    ]

    # full: per-variable sections for the largest tensors (bounded),
    # deterministic order (size desc, name asc) so retraces agree
    order = sorted(range(len(grad_names)),
                   key=lambda i: (-_static_size(grad_vals[i]),
                                  grad_names[i]))[:MAX_TRACED_VARS]
    traced_g = [grad_names[i] for i in order]
    per_var = []
    for i in order:
        g = grad_vals[i].astype(f32)
        per_var += [gnorm_sqs[i], jnp.max(jnp.abs(g)) if g.size else
                    jnp.zeros((), f32),
                    jnp.sum((~jnp.isfinite(g)).astype(f32))]
    worder = sorted(range(len(weight_pairs)),
                    key=lambda i: (-_static_size(weight_pairs[i][2]),
                                   weight_pairs[i][0]))[:MAX_TRACED_VARS]
    traced_w = [weight_pairs[i][0] for i in worder]
    for i in worder:
        _, old, new = weight_pairs[i]
        nf = new.astype(f32)
        per_var += [jnp.sum(jnp.square(nf)),
                    jnp.sum(jnp.square(nf - old.astype(f32)))]
    layout = StatsLayout("full", tuple(traced_g), tuple(traced_w))
    packed = jnp.concatenate([
        jnp.stack(header + per_var) if per_var else jnp.stack(header),
        _exp_hist(grad_vals), _exp_hist(act_vals)])
    return layout, packed


# ---------------------------------------------------------------------------
# host-side frame
# ---------------------------------------------------------------------------

class NumericsFrame:
    """One step's unpacked tensor-health statistics."""

    __slots__ = ("step", "nonfinite_grad", "nonfinite_act",
                 "nonfinite_weight", "global_gnorm",
                 "grad_absmax", "act_absmax", "grads", "weights",
                 "grad_hist", "act_hist")

    def __init__(self, step: int, vec: np.ndarray, layout: StatsLayout):
        if vec.ndim == 2:
            # collective shard_map mode stacks per-rank stats: counts
            # and hists SUM, absmax MAXes, norms average (grads are
            # replicated post-allreduce, activations are per-shard)
            v = vec.astype(np.float64)
            vec = np.where(
                np.isfinite(v).all(0), v.mean(0), np.float64("nan"))
            h = StatsLayout.HEADER
            for i in (0, 1, 2):
                vec[i] = v[:, i].sum()
            vec[4] = v[:, 4].max()
            vec[5] = v[:, 5].max()
            if layout.mode == "full":
                vec[-2 * HIST_BINS:] = v[:, -2 * HIST_BINS:].sum(0)
                for i in range(len(layout.grads)):
                    vec[h + 3 * i + 1] = v[:, h + 3 * i + 1].max()
                    vec[h + 3 * i + 2] = v[:, h + 3 * i + 2].sum()
        vec = np.asarray(vec, np.float64)
        self.step = int(step)
        self.nonfinite_grad = float(np.nan_to_num(vec[0], nan=1.0))
        self.nonfinite_act = float(np.nan_to_num(vec[1], nan=1.0))
        self.nonfinite_weight = float(np.nan_to_num(vec[2], nan=1.0))
        gsq = float(vec[3])
        self.global_gnorm = (float(np.sqrt(gsq)) if np.isfinite(gsq)
                             and gsq >= 0 else float("nan"))
        self.grad_absmax = float(vec[4])
        self.act_absmax = float(vec[5])
        self.grads: Dict[str, Dict[str, float]] = {}
        self.weights: Dict[str, Dict[str, float]] = {}
        self.grad_hist = self.act_hist = None
        if layout.mode == "full":
            off = StatsLayout.HEADER
            for n in layout.grads:
                sq, amax, nf = vec[off:off + 3]
                off += 3
                self.grads[n] = {
                    "norm": (float(np.sqrt(sq)) if np.isfinite(sq)
                             and sq >= 0 else float("nan")),
                    "absmax": float(amax), "nonfinite": float(nf)}
            for n in layout.weights:
                wsq, dsq = vec[off:off + 2]
                off += 2
                ratio = (float(np.sqrt(dsq / wsq))
                         if wsq > 0 and np.isfinite(wsq)
                         and np.isfinite(dsq) else 0.0)
                self.weights[n] = {
                    "wnorm": float(np.sqrt(max(wsq, 0.0))),
                    "update_ratio": ratio}
            self.grad_hist = vec[off:off + HIST_BINS]
            self.act_hist = vec[off + HIST_BINS:off + 2 * HIST_BINS]

    @property
    def nonfinite(self) -> float:
        return (self.nonfinite_grad + self.nonfinite_act
                + self.nonfinite_weight)

    @staticmethod
    def range_bits(hist) -> int:
        """Occupied log2 dynamic range of a histogram (0 = empty)."""
        nz = np.nonzero(np.asarray(hist) > 0)[0]
        return int(nz[-1] - nz[0] + 1) if nz.size else 0


# ---------------------------------------------------------------------------
# anomaly records (shared format: the engine, amp loss-scale events and
# the serving logits sentinel all emit these)
# ---------------------------------------------------------------------------

def record_anomaly(kind: str, step: Optional[int] = None,
                   var: Optional[str] = None,
                   value: Optional[float] = None,
                   detail: Optional[Dict[str, Any]] = None,
                   instant: str = "numerics.anomaly",
                   capture: bool = False,
                   quarantine: bool = False) -> Dict[str, Any]:
    """Append one anomaly record (the ONE record format every numerics
    event uses — engine trips, amp loss-scale events, serving logits
    sentinels): bumps ``paddle_tpu_numerics_anomalies_total{kind}``,
    emits the trace instant, optionally opens a profiler capture window
    (``trigger: "anomaly"`` in its manifest) and/or quarantines the
    checkpoint plane.  Returns the record."""
    rec: Dict[str, Any] = {"kind": kind, "t": time.time()}
    if step is not None:
        rec["step"] = int(step)
    if var is not None:
        rec["var"] = str(var)
    if value is not None:
        try:
            rec["value"] = float(value)
        except (TypeError, ValueError):
            rec["value"] = repr(value)
    if detail:
        rec.update(detail)
    ANOMALY_CTR.inc(1, kind=kind)
    if _monitor.TRACER.enabled:
        _monitor.TRACER.instant(instant, "numerics", dict(rec))
    ENGINE._note_record(rec, capture=capture, quarantine=quarantine)
    return rec


def note_nonfinite(var_class: str, n: int, step: Optional[int] = None,
                   detail: Optional[Dict[str, Any]] = None) -> None:
    """Out-of-graph sentinel entry point (the serving decode loop counts
    non-finite logits here): bumps the class counter and emits one
    anomaly record per episode (latched until a clean ``n == 0``
    observation un-latches the class)."""
    NONFINITE_CTR.inc(int(n), var_class=var_class)
    if int(n) > 0:
        ENGINE._class_trip(var_class, int(n), step=step, detail=detail)
    else:
        with ENGINE._mu:
            ENGINE._class_tripped.discard(var_class)


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

class NumericsEngine:
    """Consumes in-flight stats frames and turns them into anomaly
    records, gauges and quarantine state.  All entry points are cheap
    and lock-guarded; frame materialization happens only for arrays
    that report ready (``jax.Array.is_ready``) or once the bounded
    backlog forces it (counted in
    ``paddle_tpu_numerics_forced_syncs_total``)."""

    MAX_BACKLOG = 8
    MAX_RECORDS = 256

    def __init__(self):
        self._mu = threading.Lock()
        self._pending: collections.deque = collections.deque()  # guarded-by: _mu
        self._windows: Dict[str, collections.deque] = {}  # guarded-by: _mu
        self._armed: Dict[str, bool] = {}  # guarded-by: _mu
        self._published: set = set()       # guarded-by: _mu
        self._published_w: set = set()     # guarded-by: _mu
        self._class_tripped: set = set()   # guarded-by: _mu
        self._poisoned_since: Optional[int] = None  # guarded-by: _mu
        self._nf_cells = {
            c: NONFINITE_CTR.labels(var_class=c)
            for c in ("grad", "act", "weight")}
        self.anomalies: collections.deque = collections.deque(
            maxlen=self.MAX_RECORDS)
        self.frames_processed = 0
        self.last_frame: Optional[NumericsFrame] = None

    # -- executor side -------------------------------------------------------
    def note_step(self, step_id: int, stats, layout: StatsLayout) -> None:
        """Register one dispatched step's in-flight stats array (the
        training thread; no sync — the array is still computing)."""
        with self._mu:
            self._pending.append((int(step_id), stats, layout))
        self.poll()

    def poll(self, force: bool = False) -> int:
        """Process ready frames.  ``force=True`` materializes EVERYTHING
        pending (a host sync — the checkpoint-quarantine gate and tests
        use it; never the steady-state dispatch path).  Returns the
        number of frames processed."""
        done = 0
        while True:
            with self._mu:
                if not self._pending:
                    return done
                step_id, stats, layout = self._pending[0]
                overflow = len(self._pending) > self.MAX_BACKLOG
                if not force and not overflow:
                    ready = getattr(stats, "is_ready", None)
                    try:
                        if ready is not None and not ready():
                            return done
                    except Exception:
                        pass
                self._pending.popleft()
            if overflow and not force:
                FORCED_SYNC_CTR.inc()
            try:
                frame = NumericsFrame(step_id, np.asarray(stats), layout)
            except Exception:
                continue         # a deleted/poisoned buffer never wedges us
            self._process(frame)
            done += 1

    # -- frame processing ----------------------------------------------------
    def _process(self, frame: NumericsFrame) -> None:
        self.frames_processed += 1
        self.last_frame = frame
        if np.isfinite(frame.global_gnorm):
            NUM_GLOBAL_GNORM_GAUGE.set(round(frame.global_gnorm, 6))
        self._nf_cells["grad"].inc(int(frame.nonfinite_grad))
        if frame.nonfinite_act:
            self._nf_cells["act"].inc(int(frame.nonfinite_act))
        if frame.nonfinite_weight:
            self._nf_cells["weight"].inc(int(frame.nonfinite_weight))
        if frame.grad_hist is not None:
            NUM_RANGE_GAUGE.set(frame.range_bits(frame.grad_hist),
                                var_class="grad")
            NUM_RANGE_GAUGE.set(frame.range_bits(frame.act_hist),
                                var_class="act")
        # -- NaN/Inf sentinel (latched per episode) ----------------------
        bad = frame.nonfinite > 0 or not np.isfinite(frame.global_gnorm)
        if bad:
            cls = ("weight" if frame.nonfinite_weight
                   else "grad" if frame.nonfinite_grad
                   or not np.isfinite(frame.global_gnorm) else "act")
            self._class_trip(
                cls, int(frame.nonfinite), step=frame.step,
                # absmax only exists in full mode — a hardwired 0.0 on
                # a sentinel record would read as "values are tiny"
                detail=({"grad_absmax": frame.grad_absmax,
                         "act_absmax": frame.act_absmax}
                        if frame.grad_hist is not None else None),
                in_graph=True)
        else:
            with self._mu:
                self._class_tripped -= {"grad", "act", "weight"}
        # -- per-var gauges + spike detection (full mode) ----------------
        if frame.grads:
            self._publish_vars(frame)
            self._detect_spikes(frame)

    def _publish_vars(self, frame: NumericsFrame) -> None:
        k = _CONFIG["topk"]
        top = sorted(frame.grads,
                     key=lambda n: -np.nan_to_num(
                         frame.grads[n]["norm"], nan=np.inf))[:k]
        wtop = sorted(frame.weights,
                      key=lambda n: -frame.weights[n]["update_ratio"])[:k]
        with self._mu:
            stale = self._published - set(top)
            stale_w = self._published_w - set(wtop)
            self._published = set(top)
            self._published_w = set(wtop)
        # PR-2 retirement semantics for gauges: churned-out vars DROP
        # (a stale per-var norm would read as live signal)
        for n in stale:
            NUM_GNORM_GAUGE.fold({"var": n}, None)
            NUM_ABSMAX_GAUGE.fold({"var": n}, None)
        for n in stale_w:
            NUM_UPDATE_GAUGE.fold({"var": n}, None)
        for n in top:
            g = frame.grads[n]
            NUM_GNORM_GAUGE.set(round(np.nan_to_num(
                g["norm"], nan=-1.0), 6), var=n)
            NUM_ABSMAX_GAUGE.set(round(np.nan_to_num(
                g["absmax"], nan=-1.0), 6), var=n)
        for n in wtop:
            NUM_UPDATE_GAUGE.set(
                round(frame.weights[n]["update_ratio"], 8), var=n)

    def _detect_spikes(self, frame: NumericsFrame) -> None:
        factor = _CONFIG["spike_factor"]
        wlen = _CONFIG["window"]
        for n, g in frame.grads.items():
            v = g["norm"]
            if not np.isfinite(v):
                continue             # the sentinel already tripped
            with self._mu:
                win = self._windows.get(n)
                if win is None or win.maxlen != wlen:
                    win = self._windows[n] = collections.deque(
                        list(win or ()), maxlen=wlen)
                    if len(self._windows) > 4 * MAX_TRACED_VARS:
                        # var churn across programs must not grow the
                        # window table forever
                        for dead in list(self._windows)[
                                :len(self._windows) // 2]:
                            if dead not in frame.grads:
                                del self._windows[dead]
                                self._armed.pop(dead, None)
                med = (sorted(win)[len(win) // 2] if win else None)
                armed = self._armed.get(n, True)
                fire = recover = False
                if med is not None and med > 0 and len(win) >= 4:
                    if v > factor * med:
                        if armed:
                            fire = True
                            self._armed[n] = False
                        # a spiking norm must not drag the median up to
                        # its own level and self-legitimize — freeze the
                        # window while tripped
                    else:
                        win.append(v)
                        if not armed and v <= (factor / 2.0) * med:
                            recover = self._armed[n] = True
                else:
                    win.append(v)
                med_out = med
            if fire:
                record_anomaly(
                    "grad_spike", step=frame.step, var=n, value=v,
                    detail={"median": round(float(med_out), 6),
                            "factor": factor}, capture=True)
            elif recover and _monitor.TRACER.enabled:
                _monitor.TRACER.instant(
                    "numerics.recovered", "numerics",
                    {"var": n, "step": frame.step, "value": v})

    # -- anomaly plumbing ----------------------------------------------------
    def _class_trip(self, var_class: str, n: int,
                    step: Optional[int] = None,
                    detail: Optional[Dict[str, Any]] = None,
                    in_graph: bool = False) -> None:
        with self._mu:
            first = var_class not in self._class_tripped
            self._class_tripped.add(var_class)
        if first:
            record_anomaly(
                "nonfinite" if in_graph else f"nonfinite_{var_class}",
                step=step, var=var_class, value=n, detail=detail,
                capture=True,
                quarantine=in_graph
                and var_class in ("grad", "act", "weight"))

    def _note_record(self, rec: Dict[str, Any], capture: bool,
                     quarantine: bool) -> None:
        self.anomalies.append(rec)
        if quarantine and _CONFIG["quarantine"]:
            with self._mu:
                if self._poisoned_since is None:
                    self._poisoned_since = int(rec.get("step", 0) or 0)
                    poisoned = self._poisoned_since
                else:
                    poisoned = None
            if poisoned is not None and _monitor.TRACER.enabled:
                _monitor.TRACER.instant(
                    "numerics.quarantine", "numerics",
                    {"since_step": poisoned, "kind": rec.get("kind")})
        if capture:
            try:
                from ..profiler import SAMPLER
                SAMPLER.trigger_window(rec.get("step"), trigger="anomaly")
            except Exception:
                pass             # capture is best-effort, never the step

    # -- quarantine ----------------------------------------------------------
    def poisoned_since(self) -> Optional[int]:
        with self._mu:
            return self._poisoned_since

    def clear_quarantine(self) -> None:
        """Operator action: the poisoned state was rolled back (e.g.
        resume_or_init restored the last healthy manifest step) — the
        checkpoint plane may commit again."""
        with self._mu:
            self._poisoned_since = None
            self._class_tripped.clear()

    def reset(self) -> None:
        """Full state reset (tests / bench isolation)."""
        with self._mu:
            self._pending.clear()
            self._windows.clear()
            self._armed.clear()
            self._published.clear()
            self._published_w.clear()
            self._class_tripped.clear()
            self._poisoned_since = None
        self.anomalies.clear()
        self.frames_processed = 0
        self.last_frame = None


ENGINE = NumericsEngine()


def poisoned_since() -> Optional[int]:
    return ENGINE.poisoned_since()


def is_poisoned() -> bool:
    return ENGINE.poisoned_since() is not None


def clear_quarantine() -> None:
    ENGINE.clear_quarantine()


# ---------------------------------------------------------------------------
# loss-trajectory fingerprint (numerics_smoke.py's loss-parity gate)
# ---------------------------------------------------------------------------

def loss_fingerprint(losses, decimals: int = 5) -> str:
    """sha1 over the rounded loss trajectory — the loss-parity gate the
    quantized-collectives arc compares across codec configurations (and
    numerics_smoke.py compares across FLAGS_numerics modes: stats outputs
    must never perturb the training math)."""
    a = np.round(np.asarray(list(losses), np.float64), decimals)
    return hashlib.sha1(a.tobytes()).hexdigest()
