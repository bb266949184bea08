"""Analytic per-op flops/bytes cost model over the dependency-ordered
``framework.ir`` Graph — the device-time attribution half of the
observability stack.

``benchmark/flops.py`` computes MFU offline from hand-written per-model
FLOP formulas; this module generalizes that accounting to ANY program:
each op gets an analytic flop count and a logical byte-traffic estimate
from its inferred shapes (TPP, arxiv 2104.05755, frames exactly this
flops/bytes efficiency ledger per primitive; TVM, arxiv 1802.04799, uses
the same per-op cost shape to drive schedule selection — the upcoming
fusion pass picks candidates from these numbers).  The model is the
denominator source for the executor's live ``paddle_tpu_step_mfu`` gauge
and the roofline attribution (``per_class`` flop shares) the fusion arc
will rank rewrite candidates by.

Accounting rules:

- **matmul family** (``mul``/``matmul``/``matmul_v2``): 2·M·K·N over the
  batch-resolved shapes (transpose attrs honored);
- **conv2d**: 2·C_in·kh·kw per output element (the same 2·MAC rule
  ``benchmark/flops.py`` applies to ResNet);
- **grad ops** inherit their forward op's formula ×2 (a matmul backward
  is two matmuls of the forward's size; conv backward likewise — the
  standard fwd:bwd 1:2 flop ratio the benchmark's ×3 total encodes);
- **normalization/softmax/activation/elementwise**: a small per-element
  factor (the VPU work is real but MXU-irrelevant; it matters for the
  bytes-bound ops the roofline flags);
- **lookup/gather family**: zero flops, bytes = gathered rows (pure
  HBM traffic — exactly the ops the roofline calls memory-bound);
- **bytes** per op = input bytes read + output bytes written at the
  resolved batch (symbolic dims resolve through ``batch_size``, same as
  the memory planner).

Results are cached on the program fingerprint (the memory planner's key
discipline) and stamped into ``program._attrs["verify"]["cost"]`` by the
verifier, so steady-state dispatch never re-plans and the executor reads
flops-per-step with one dict probe.  ``compiled.cost_analysis()`` — the
XLA-reported flop count — is the cross-check: ``FLAGS_cost_crosscheck``
makes the executor compare the two at compile time and count divergence
(``paddle_tpu_cost_crosscheck_total{verdict}``), so the analytic model
can never silently drift from what the compiler actually emits.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from .. import monitor as _monitor
from ..device import is_tpu
from ..framework.core import Block, Program

__all__ = ["CostPlan", "plan_cost", "clear_cache", "device_peak_flops",
           "xla_cost_breakdown", "xla_cost_totals"]

_PLAN_CTR = _monitor.REGISTRY.counter(
    "paddle_tpu_cost_plans_total",
    "plan_cost calls by fingerprint-cache outcome", ("cache",))
_PLAN_HIT = _PLAN_CTR.labels(cache="hit")
_PLAN_MISS = _PLAN_CTR.labels(cache="miss")

#: op type -> roofline class.  Grad ops inherit their forward's class;
#: anything unlisted is "other".
_CLASS_OF = {
    "conv2d": "conv", "depthwise_conv2d": "conv", "conv2d_transpose": "conv",
    "mul": "matmul", "matmul": "matmul", "matmul_v2": "matmul",
    "lookup_table": "embedding", "lookup_table_v2": "embedding",
    "gather": "embedding", "gather_nd": "embedding",
    "scatter": "embedding", "scatter_nd_add": "embedding",
    "batch_norm": "norm", "layer_norm": "norm", "group_norm": "norm",
    "softmax": "softmax", "softmax_with_cross_entropy": "softmax",
    "cross_entropy": "softmax", "cross_entropy2": "softmax",
    "reduce_sum": "reduce", "reduce_mean": "reduce", "reduce_max": "reduce",
    "mean": "reduce", "sum": "reduce",
    "adam": "optimizer", "momentum": "optimizer", "sgd": "optimizer",
    "adagrad": "optimizer", "lamb": "optimizer", "rmsprop": "optimizer",
    "flash_attention": "attention", "fused_attention": "attention",
    # analysis.fusion rewrite targets keep their source chain's class so
    # the roofline shares (and the live MFU numerator) survive fusion
    "fused_dense_act": "matmul", "fused_embedding_layer_norm": "embedding",
}

#: per-element flop factors for the cheap (VPU) classes; everything not
#: matched by a structural formula below falls back to one of these
_ELEM_FLOPS = {
    "softmax": 5.0, "softmax_with_cross_entropy": 7.0,
    "cross_entropy": 3.0, "cross_entropy2": 3.0,
    "layer_norm": 8.0, "batch_norm": 4.0, "group_norm": 8.0,
    "gelu": 9.0, "tanh": 6.0, "sigmoid": 4.0, "erf": 6.0,
    "exp": 2.0, "log": 2.0, "sqrt": 2.0, "rsqrt": 2.0, "pow": 3.0,
    "dropout": 2.0, "adam": 10.0, "lamb": 14.0, "momentum": 4.0,
    "fused_embedding_layer_norm": 8.0,
}

_ITEMSIZE = {"bfloat16": 2, "float16": 2, "bool": 1}


def _itemsize(dtype) -> int:
    d = str(dtype or "float32")
    if d in _ITEMSIZE:
        return _ITEMSIZE[d]
    try:
        return int(np.dtype(d).itemsize)
    except TypeError:
        return 4


def _shape(block: Block, name, batch_size: int) -> Optional[Tuple[int, ...]]:
    if not name or not block.has_var(name):
        return None
    v = block.var(name)
    if v.shape is None:
        return None
    return tuple(batch_size if d in (-1, None) else int(d)
                 for d in v.shape)


def _numel(shape) -> int:
    if shape is None:
        return 0
    n = 1
    for d in shape:
        n *= max(int(d), 1)
    return n


def _var_bytes(block: Block, name, batch_size: int) -> int:
    s = _shape(block, name, batch_size)
    if s is None:
        return 0
    v = block.var(name)
    return max(_numel(s), 1) * _itemsize(v.dtype)


#: published peak dense bf16 FLOP/s per chip (Google Cloud TPU docs),
#: keyed by the ``device_kind`` JAX reports.  "TPU v5 lite" is what a v5e
#: reads (confirmed on the chip, CHANGES.md PR 21); a bare "TPU v5" is a
#: v5p.  A kind that is not here is an error, never a default.
TPU_PEAK_FLOPS = {"TPU v4": 275e12, "TPU v5 lite": 197e12,
                  "TPU v5e": 197e12, "TPU v5": 459e12, "TPU v5p": 459e12}


def tpu_table_lookup(table, device, what):
    """``table[device.device_kind]``, or a ``ValueError`` naming the kind
    (shared with ``comms.device_link_bandwidth``)."""
    kind = device.device_kind
    if kind not in table:
        raise ValueError(
            f"no {what} known for TPU device_kind {kind!r} (known: "
            f"{sorted(table)}); add its published figure to the table "
            "rather than assuming another chip's")
    return table[kind]


def device_peak_flops(device=None) -> float:
    """Peak dense bf16 FLOP/s of one chip — the MFU denominator shared by
    every offline MFU line and the executor's live gauge (the two
    accountings must divide by the SAME peak or comparing them
    is meaningless).  A TPU ``device_kind`` missing from
    :data:`TPU_PEAK_FLOPS` raises.  CPU backends get a nominal 1e12
    planning constant (the partitioner's ranking needs a finite number;
    a CPU run has no MFU)."""
    if device is None:
        import jax
        device = jax.devices()[0]
    if not is_tpu(device):
        return 1e12
    return tpu_table_lookup(TPU_PEAK_FLOPS, device, "peak FLOP/s")


@dataclass
class CostPlan:
    """Analytic per-step flops/bytes model of one program."""

    #: total analytic flops per step (forward + backward + optimizer)
    flops: int = 0
    #: total logical bytes accessed per step (inputs read + outputs
    #: written, not deduplicated across ops — an upper bound on traffic)
    bytes: int = 0
    #: per-op attribution in dependency order:
    #: (pos, op_type, op_class, flops, bytes)
    per_op: List[tuple] = field(default_factory=list)
    #: op_class -> total flops (the roofline share the fusion arc ranks
    #: candidates by; ``share()`` normalizes)
    per_class: Dict[str, int] = field(default_factory=dict)
    #: op_class -> total bytes
    per_class_bytes: Dict[str, int] = field(default_factory=dict)
    batch_size: int = 1

    def share(self) -> Dict[str, float]:
        """Per-class flop share in [0, 1] (empty program: {})."""
        total = float(self.flops) or 1.0
        return {c: f / total for c, f in self.per_class.items()}

    def intensity(self) -> float:
        """Arithmetic intensity (flops per logical byte accessed)."""
        return self.flops / self.bytes if self.bytes else 0.0

    def top_ops(self, k: int = 10) -> List[tuple]:
        return sorted(self.per_op, key=lambda r: -r[3])[:k]

    def report(self, k: int = 10) -> str:
        lines = [
            f"analytic cost (batch={self.batch_size}): "
            f"{self.flops / 1e9:.3f} GFLOP, "
            f"{self.bytes / 1e6:.1f} MB accessed, "
            f"intensity {self.intensity():.1f} flop/B"]
        share = self.share()
        if share:
            lines.append("flop share: " + ", ".join(
                f"{c}={s * 100:.1f}%" for c, s in
                sorted(share.items(), key=lambda kv: -kv[1])))
        for pos, typ, cls, fl, by in self.top_ops(k):
            lines.append(f"  #{pos:<4} {typ:<28} [{cls}] "
                         f"{fl / 1e6:10.2f} MFLOP  {by / 1e6:8.2f} MB")
        return "\n".join(lines)


def _slot(op, name):
    """Input slot resolution that also sees a GRAD op's forwarded
    forward-inputs (``make_grad_ops`` re-feeds them under ``X$<slot>`` —
    the same convention the verifier's int64 classifier follows)."""
    return op.input("X$" + name) or op.input(name)


def _matmul_flops(block, op, batch_size) -> Optional[int]:
    """2·M·K·N for the mul/matmul family; None when shapes are unknown."""
    xs = _slot(op, "X")
    ys = _slot(op, "Y")
    if not xs or not ys:
        return None
    x = _shape(block, xs[0], batch_size)
    y = _shape(block, ys[0], batch_size)
    if not x or not y:
        return None
    if op.type == "mul":
        # mul flattens X to 2-D at num_col_dims: [prod(lead), K] @ [K, N]
        ncd = int(op.attrs.get("x_num_col_dims", 1))
        m = _numel(x[:ncd])
        k = _numel(x[ncd:])
        n = _numel(y[1:]) if len(y) > 1 else 1
        return 2 * m * k * n
    tx = bool(op.attrs.get("transpose_X") or op.attrs.get("trans_x"))
    ty = bool(op.attrs.get("transpose_Y") or op.attrs.get("trans_y"))
    if len(x) == 1:                       # vector promotes to [1, K]
        x = (1,) + x
    if len(y) == 1:                       # vector promotes to [K, 1]
        y = y + (1,)
    xm, xk = (x[-1], x[-2]) if tx else (x[-2], x[-1])
    yn = y[-2] if ty else y[-1]
    lead = _numel(x[:-2]) if len(x) > 2 else \
        (_numel(y[:-2]) if len(y) > 2 else 1)
    return 2 * lead * xm * xk * yn


def _conv_flops(block, op, batch_size) -> Optional[int]:
    f = _slot(op, "Filter")
    # a conv grad has no "Output" slot; the output GRADIENT it consumes
    # has the forward output's shape, which is all the formula needs
    o = op.output("Output") or op.input("OG$Output") or \
        op.input("Output@GRAD")
    if not f or not o:
        return None
    w = _shape(block, f[0], batch_size)
    out = _shape(block, o[0], batch_size)
    if not w or not out or len(w) < 4 or len(out) < 4:
        return None
    # out [N, C_out, H, W]; filter [C_out, C_in/groups, kh, kw]
    return 2 * _numel(out) * w[1] * w[2] * w[3]


def _fused_dense_flops(block, op, batch_size) -> Optional[int]:
    """fused_dense_act: 2·M·K·N over the flattened x (mul semantics at
    ``x_num_col_dims``; -1 = matmul over the trailing dim)."""
    xs = _slot(op, "X")
    ws = _slot(op, "W")
    if not xs or not ws:
        return None
    x = _shape(block, xs[0], batch_size)
    w = _shape(block, ws[0], batch_size)
    if not x or not w:
        return None
    ncd = int(op.attrs.get("x_num_col_dims", 1))
    if ncd < 0:
        ncd = len(x) - 1
    m = _numel(x[:ncd])
    k = _numel(x[ncd:])
    n = _numel(w[1:]) if len(w) > 1 else 1
    return 2 * m * k * n


def _op_cost(block: Block, op, batch_size: int) -> Tuple[int, int, str]:
    """(flops, bytes, op_class) of one op at the resolved batch."""
    typ = op.type
    is_grad = typ.endswith("_grad")
    fwd = typ[: -len("_grad")] if is_grad else typ
    grad_mult = 2 if is_grad else 1

    in_bytes = sum(_var_bytes(block, n, batch_size)
                   for n in op.input_arg_names())
    out_bytes = sum(_var_bytes(block, n, batch_size)
                    for n in op.output_arg_names())
    bytes_ = in_bytes + out_bytes
    cls = _CLASS_OF.get(fwd, "other")

    flops = None
    if fwd in ("mul", "matmul", "matmul_v2"):
        flops = _matmul_flops(block, op, batch_size)
    elif fwd in ("conv2d", "depthwise_conv2d", "conv2d_transpose"):
        flops = _conv_flops(block, op, batch_size)
    elif fwd == "fused_dense_act":
        flops = _fused_dense_flops(block, op, batch_size)
    elif fwd in ("lookup_table", "lookup_table_v2", "gather", "gather_nd",
                 "scatter", "scatter_nd_add"):
        flops = 0
    if flops is None:
        # per-element fallback on the dominant output (grad ops read the
        # forward's output names through the same var set, so the element
        # count is comparable)
        elems = max((_numel(_shape(block, n, batch_size))
                     for n in op.output_arg_names() if n), default=0)
        if not elems:
            elems = max((_numel(_shape(block, n, batch_size))
                         for n in op.input_arg_names() if n), default=0)
        flops = int(elems * _ELEM_FLOPS.get(fwd, 1.0))
    return int(flops) * grad_mult, int(bytes_), cls


# (program fingerprint, fetch tuple, batch) -> CostPlan; bounded FIFO —
# same discipline as the verifier and memory-planner caches
_CACHE: Dict[tuple, CostPlan] = {}  # guarded-by: _CACHE_LOCK
_CACHE_CAP = 128
_CACHE_LOCK = threading.Lock()


def clear_cache() -> None:
    with _CACHE_LOCK:
        _CACHE.clear()


def plan_cost(program: Program, fetch_names=(),
              batch_size: int = 1) -> CostPlan:
    """Analytic flops/bytes plan for one program (see module docstring).
    Cached on (program fingerprint, fetch tuple, batch_size); symbolic
    (-1/None) dims resolve through ``batch_size``."""
    fetch_names = tuple(
        f.name if hasattr(f, "name") else f for f in (fetch_names or ()))
    key = (program.fingerprint(), fetch_names, int(batch_size))
    with _CACHE_LOCK:
        cached = _CACHE.get(key)
    if cached is not None:
        _PLAN_HIT.inc()
        return cached
    _PLAN_MISS.inc()
    with _monitor.TRACER.span("cost.plan", "compile",
                              fetches=len(fetch_names)):
        plan = _plan(program, int(batch_size))
    with _CACHE_LOCK:
        if key not in _CACHE:
            if len(_CACHE) >= _CACHE_CAP:
                _CACHE.pop(next(iter(_CACHE)))
            _CACHE[key] = plan
        plan = _CACHE[key]
    return plan


def _plan(program: Program, batch_size: int) -> CostPlan:
    from ..framework import ir
    from ..framework.core import Block as _Block
    block = program.global_block()
    graph = ir.Graph(program)
    order = graph.topology_sort()

    plan = CostPlan(batch_size=batch_size)
    per_class: Dict[str, int] = {}
    per_class_bytes: Dict[str, int] = {}

    def add(pos, blk, op):
        if op.type in ("feed", "fetch"):
            return
        fl, by, cls = _op_cost(blk, op, batch_size)
        plan.flops += fl
        plan.bytes += by
        per_class[cls] = per_class.get(cls, 0) + fl
        per_class_bytes[cls] = per_class_bytes.get(cls, 0) + by
        plan.per_op.append((pos, op.type, cls, fl, by))
        # sub-block bodies (while/cond) count ONCE — a static model
        # cannot know the trip count; the per-iteration cost is the
        # honest per-step lower bound (same convention as the planner)
        for v in op.attrs.values():
            if isinstance(v, _Block):
                for sop in v.ops:
                    add(pos, v, sop)

    for i, node in enumerate(order):
        add(i, block, node.op)
    plan.per_class = per_class
    plan.per_class_bytes = per_class_bytes
    return plan


def xla_cost_totals(cost_analysis) -> Tuple[float, float]:
    """(flops, bytes accessed) out of a ``Compiled.cost_analysis()``
    result, which jax returns as a dict or a one-element list of dicts
    depending on version.  Missing keys read as 0."""
    ca = cost_analysis
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    if not isinstance(ca, dict):
        return 0.0, 0.0
    return float(ca.get("flops", 0.0) or 0.0), \
        float(ca.get("bytes accessed", 0.0) or 0.0)


def xla_cost_breakdown(cost_analysis) -> Dict[str, object]:
    """The FULL utilization breakdown of a ``cost_analysis()`` result —
    not just the totals: transcendentals (XLA bills RNG/gelu erf here,
    a common totals-divergence cause) and the per-operand ``bytes
    accessedN{}``/``utilizationN{}`` keys, parsed into nested dicts the
    crosscheck attaches to its tracer record and divergence warning."""
    ca = cost_analysis
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    if not isinstance(ca, dict):
        return {}
    out: Dict[str, object] = {
        "flops": float(ca.get("flops", 0.0) or 0.0),
        "transcendentals": float(ca.get("transcendentals", 0.0) or 0.0),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0) or 0.0),
    }
    operand_bytes: Dict[str, float] = {}
    operand_util: Dict[str, float] = {}
    for k, v in ca.items():
        try:
            fv = float(v)
        except (TypeError, ValueError):
            continue
        tag = k.replace("{}", "").strip()
        if k.startswith("bytes accessed") and k != "bytes accessed":
            operand_bytes[tag[len("bytes accessed"):] or "out"] = fv
        elif k.startswith("utilization"):
            operand_util[tag[len("utilization"):] or "out"] = fv
    if operand_bytes:
        out["operand_bytes"] = operand_bytes
    if operand_util:
        out["operand_utilization"] = operand_util
    return out
