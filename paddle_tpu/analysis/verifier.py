"""Program verifier: static checks over the ``framework.ir`` Graph.

The reference validates ProgramDesc graphs ad hoc at kernel launch
(``framework/operator.cc`` enforce macros firing mid-run); this verifier
moves that whole defect class to ``compiler.optimize`` time, where a bad
program costs one diagnostic instead of a dispatch-time crash — or, for
the cross-rank ordering defects, a silent multi-process hang.

Checks (one ``verifier.*`` counter series per check in the telemetry
registry; see README "Static analysis" for the table):

==================  =========  ==============================================
check               severity   flags
==================  =========  ==============================================
def_before_use      error      op input var not declared anywhere in the
                               block (would KeyError mid-trace)
uninitialized_read  warning    declared non-persistable, non-data var read
                               before any op writes it (must be fed or
                               pre-seeded in the scope at run time)
dangling_fetch      error      fetch target never produced: not a block
                               var, or declared but neither written nor
                               persistable
dangling_feed       warning    declared data var consumed by no op in any
                               block (its fed value is dropped)
shape_consistency   warning    a var's recorded shape/dtype disagrees with
                               re-running build-time inference over the
                               block (a mutation bypassed ``append_op``)
dead_op             warning    op unreachable from the fetch + persistable
                               + side-effect liveness roots (the
                               ``dead_op_eliminate`` pass removes these)
use_after_donate    warning    fetch target is a read-write persistable:
                               the executor donates rw buffers to the next
                               step and must defensively copy the fetch out
                               of the donated buffer every step
int64_feed          (none)     classification, not a diagnostic: its
                               counter tracks feeds that KEPT the runtime
                               wrap check (verifier-dynamic)
collective_order    error/     collective ops not totally ordered by data
                    warning    dependencies: error when an unordered pair
                               has the SAME signature (cross-rank pairing
                               is ambiguous — the documented ``.numpy()``
                               ordering deadlock class), warning otherwise
memory_budget       warning    the static HBM peak-memory estimate
                               (analysis.memory, batch=1 lower bound)
                               exceeds FLAGS_memory_budget_mb
==================  =========  ==============================================

The graph-walking checks are INTERPROCEDURAL: ``while``/``cond`` bodies
verify recursively in their enclosing scope context (outer defs visible,
inner defs scoped, loop-carried body writes never read as
uninitialized), sub-block collectives fold into the fingerprint stamped
with their block path, and dead body compute is flagged/pruned without
touching live loop-carried vars.

``verify_program`` is cached on the source-program fingerprint
(``Program.fingerprint()`` — the PR-4 dispatch-plan key), so a program is
verified once per mutation and steady-state dispatch never re-enters the
verifier.  Results additionally stamp ``program._attrs["verify"]`` (which
rides ``Program.clone``) with the machine-readable artifacts other layers
consume: the int64 feed classification (the executor keeps its runtime
feed-wrap check only for feeds marked dynamic) and the collective
fingerprint (ranks can compare it out of band before entering a gang).
"""

from __future__ import annotations

import hashlib
import threading
from dataclasses import dataclass, field
from typing import Dict, FrozenSet, List, Optional, Tuple

from .. import monitor as _monitor
from ..framework.core import Block, Program

__all__ = [
    "CHECKS", "Diagnostic", "ProgramVerificationError", "VerifyResult",
    "clear_cache", "collective_fingerprint", "dynamic_int64_feeds",
    "verify_or_raise", "verify_program",
]

#: every check name, in report order (one counter series per entry)
CHECKS = (
    "def_before_use", "uninitialized_read", "dangling_fetch",
    "dangling_feed", "shape_consistency", "dead_op", "use_after_donate",
    "int64_feed", "collective_order", "memory_budget",
    "spec_conflict", "shard_divisibility", "mesh_axis_overuse",
)

_FINDINGS = _monitor.REGISTRY.counter(
    "paddle_tpu_verifier_findings_total",
    "program-verifier findings by check", ("check",))
#: bound once per check: a verify pass bumps these, never resolves labels
_FINDING_CELLS = {c: _FINDINGS.labels(check=c) for c in CHECKS}
_RUNS = _monitor.REGISTRY.counter(
    "paddle_tpu_verifier_runs_total",
    "verify_program calls by fingerprint-cache outcome", ("cache",))
_RUNS_HIT = _RUNS.labels(cache="hit")
_RUNS_MISS = _RUNS.labels(cache="miss")

#: int64 feeds whose every consumer bounds VALID values below this are
#: static-safe: with the bound under 2**31, every valid index fits int32,
#: so the int64->int32 feed conversion can only alter values that were
#: already out of range — and those the consumer already mishandles
#: identically with or without the wrap (XLA gather clamps out-of-bounds
#: ids silently; the runtime wrap check never diagnosed table-bounds
#: violations inside the int32 range either).  The wrap check therefore
#: adds no protection for these feeds that the bound itself doesn't.
_INT32_BOUND = 2 ** 31

#: collective ops whose cross-rank launch order must match on every rank
#: (init/sync shims are host no-ops and carry no ordering constraint)
_COLLECTIVE_OPS = frozenset({
    "c_allreduce_sum", "c_allreduce_max", "c_allreduce_min",
    "c_allreduce_prod", "c_broadcast", "c_allgather", "c_reducescatter",
    "c_split",
})


class ProgramVerificationError(RuntimeError):
    """Raised by :func:`verify_or_raise` when any error-severity
    diagnostic is present.  ``.result`` carries the full
    :class:`VerifyResult`."""

    def __init__(self, msg: str, result: "VerifyResult"):
        super().__init__(msg)
        self.result = result


@dataclass(frozen=True)
class Diagnostic:
    """One structured finding: which check, how bad, where, and what to do
    about it (ref platform/enforce.h — the reference enriches launch-time
    errors with op context; here the context is attached pre-launch)."""

    check: str                 # one of CHECKS
    severity: str              # "error" | "warning"
    message: str
    op_type: Optional[str] = None
    op_index: Optional[int] = None   # program-order index in its block
    var: Optional[str] = None
    fix_hint: Optional[str] = None
    #: block path for sub-block findings ("0" is the top block; a loop
    #: body reads e.g. "0/while@5/1": the while op at block-0 index 5,
    #: sub-block 1).  None means block 0 (back-compat).
    block: Optional[str] = None


@dataclass
class VerifyResult:
    diagnostics: List[Diagnostic] = field(default_factory=list)
    #: int64/uint64 data feeds that still need the runtime wrap check
    int64_dynamic: FrozenSet[str] = frozenset()
    #: int64/uint64 data feeds proven bounded by every consumer
    int64_static: FrozenSet[str] = frozenset()
    #: sha1 over the dependency-ordered, block-path-stamped collective
    #: sequence + fetch list (None when no block launches a collective)
    collective_fingerprint: Optional[str] = None
    dead_ops: Tuple[int, ...] = ()   # block-0 indices of dead ops
    #: {sub-block idx: (op indices...)} of dead body compute
    dead_subblock_ops: Dict[int, tuple] = field(default_factory=dict)
    #: static HBM plan (analysis.memory.MemoryPlan; None if planning
    #: failed — the plan must never block verification)
    memory_plan: Optional[object] = None
    #: analytic flops/bytes plan (analysis.cost.CostPlan; None if
    #: planning failed — same never-blocks contract as the memory plan)
    cost_plan: Optional[object] = None
    #: static comms plan (analysis.comms.CommsPlan; None for programs
    #: that launch no collectives or when planning failed).  Its
    #: fingerprint folds into ``collective_fingerprint``, so ranks whose
    #: COMMS PLANS diverge (payload bytes, nranks) refuse at the gang
    #: barrier exactly like divergent collective sequences.
    comms_plan: Optional[object] = None
    #: static GSPMD sharding plan (analysis.sharding.ShardingPlan; None
    #: for unpartitioned programs or when planning failed).  UNLIKE the
    #: planners above this one contributes blocking diagnostics
    #: (spec_conflict / mesh_axis_overuse errors refuse a bad rule table
    #: at optimize time with zero dispatches), and its ``#resh=`` token
    #: folds into ``collective_fingerprint`` so divergent reshard plans
    #: refuse at the step barrier even under IDENTICAL rule-table names.
    sharding_plan: Optional[object] = None

    def errors(self) -> List[Diagnostic]:
        return [d for d in self.diagnostics if d.severity == "error"]

    def warnings(self) -> List[Diagnostic]:
        return [d for d in self.diagnostics if d.severity == "warning"]

    @property
    def ok(self) -> bool:
        return not self.errors()

    def by_check(self, check: str) -> List[Diagnostic]:
        return [d for d in self.diagnostics if d.check == check]


# ---------------------------------------------------------------------------
# fingerprint cache
# ---------------------------------------------------------------------------

#: (program fingerprint, fetch TUPLE) -> VerifyResult.  The fetch list is
#: keyed in ORDER, not as a set: the collective fingerprint hashes the
#: materialization order, so a reordered fetch list is a different verify.
#: Bounded FIFO: every program MUTATION mints a new fingerprint, so an
#: unbounded dict would grow per version in a build-mutate-verify loop.
#: Guarded: concurrent first compiles of different programs verify in
#: parallel, and an unguarded evict could pop a key another thread just
#: took from next(iter(...)).
_CACHE: Dict[tuple, VerifyResult] = {}  # guarded-by: _CACHE_LOCK
_CACHE_CAP = 256
_CACHE_LOCK = threading.Lock()


def clear_cache() -> None:
    with _CACHE_LOCK:
        _CACHE.clear()


# ---------------------------------------------------------------------------
# individual checks (each takes the block-0 graph + context, appends diags)
# ---------------------------------------------------------------------------

def _is_data(v) -> bool:
    return bool(getattr(v, "is_data", False))


def sub_blocks_of(op) -> List[Tuple[str, Block]]:
    """The Block-valued attrs of one op, sorted by attr name (while/cond
    bodies and any future multi-block control flow)."""
    return sorted(((k, v) for k, v in op.attrs.items()
                   if isinstance(v, Block)), key=lambda kv: kv[0])


def _check_def_before_use(program: Program, diags: List[Diagnostic]):
    """Interprocedural program-order def-before-use: block 0 first, then
    every ``while``/``cond`` sub-block recursively IN ITS ENCLOSING SCOPE
    CONTEXT — outer defs written before the control-flow op are visible
    inside the body, inner defs stay scoped to it.  Feed/fetch shim ops
    participate as writers only (the executor skips them at trace time).

    Loop-body semantics: a body read of a var some body op writes LATER
    is a loop-carried use (iteration *n* reads iteration *n-1*'s write,
    and the carry's initial value comes from the parent scope), so only
    block-0 order violations earn ``uninitialized_read`` — sub-blocks
    suppress it for names written anywhere in the same body."""

    def walk(block: Block, written: set, path: str):
        local = set(written)
        body_writes = {n for op in block.ops
                       for n in op.output_arg_names() if n}
        for idx, op in enumerate(block.ops):
            if op.type not in ("feed", "fetch"):
                for slot, names in op.inputs.items():
                    # OG$ (output-grad) slots may legally be absent: an
                    # output unused downstream has no grad, and the
                    # lowering reads them with .get(), treating None as
                    # zero
                    if slot.startswith("OG$"):
                        continue
                    for name in names:
                        if not name or name in local:
                            continue
                        if not block.has_var(name):
                            diags.append(Diagnostic(
                                "def_before_use", "error",
                                f"op input var {name!r} is not declared "
                                "in the block (or an enclosing block) "
                                "and no preceding op produces it",
                                op_type=op.type, op_index=idx, var=name,
                                block=path,
                                fix_hint="declare the var "
                                         "(block.create_var / "
                                         "layers.data) or fix the "
                                         "producing op's output name"))
                            continue
                        v = block.var(name)
                        if v.persistable or _is_data(v) or \
                                v.initializer is not None:
                            continue
                        if block.idx != 0 and name in body_writes:
                            continue       # loop-carried body write
                        diags.append(Diagnostic(
                            "uninitialized_read", "warning",
                            f"var {name!r} is read before any op writes "
                            "it and is neither persistable nor a "
                            "declared data var — it must be fed (or "
                            "pre-seeded in the scope) at every run",
                            op_type=op.type, op_index=idx, var=name,
                            block=path,
                            fix_hint="declare it via layers.data if it "
                                     "is fed, or mark it persistable if "
                                     "it lives in the scope"))
            # recurse into sub-block bodies with the defs visible HERE
            # (outer writes up to and including earlier ops); the body's
            # own writes never leak back out — the enclosing op's
            # declared outputs carry them
            for _, sub in sub_blocks_of(op):
                walk(sub, local, f"{path}/{op.type}@{idx}/{sub.idx}")
            for name in op.output_arg_names():
                if name:
                    local.add(name)

    walk(program.global_block(), set(), "0")


def _check_feed_fetch(program: Program, fetch_names, diags):
    block = program.global_block()
    written = {n for op in block.ops
               for n in op.output_arg_names() if n}
    for name in fetch_names:
        if name in written:
            continue
        if not block.has_var(name):
            diags.append(Diagnostic(
                "dangling_fetch", "error",
                f"fetch target {name!r} is not a var of the program",
                var=name,
                fix_hint="fetch an existing var (typo?) or rebuild the "
                         "program that defines it"))
        elif not block.var(name).persistable and \
                not _is_data(block.var(name)):
            # data vars are legal passthrough fetches: the lowered step
            # materializes fetches from the value environment, which
            # includes the feeds (dangling_feed below blesses exactly
            # this echo/debug pattern)
            diags.append(Diagnostic(
                "dangling_fetch", "error",
                f"fetch target {name!r} is declared but no op produces it "
                "and it is not persistable — materialization would fail "
                "at dispatch",
                var=name,
                fix_hint="fetch the op output you meant, or mark the var "
                         "persistable if its value lives in the scope"))
    consumed = {n for b in program.blocks for op in b.ops
                for n in op.input_arg_names() if n}
    for name, v in block.vars.items():
        if _is_data(v) and name not in consumed and name not in fetch_names:
            diags.append(Diagnostic(
                "dangling_feed", "warning",
                f"data var {name!r} is consumed by no op in any block — "
                "its fed value is dropped every step",
                var=name,
                fix_hint="remove the layers.data declaration (and the "
                         "feed) or wire it into the model"))


def _check_shape_consistency(program: Program, diags):
    """Re-run build-time inference over a clone of block 0 and diff the
    recorded Variable shape/dtype metadata.  Catches mutations that
    bypassed ``append_op`` (whose inline InferShape keeps metadata live —
    the invariant ``tests/test_shape_inference.py`` pins).  Only concrete
    dims are compared: -1/None stay symbolic on both sides."""
    from ..framework import registry
    try:
        clone = program.clone()
    except Exception:
        return
    src = program.global_block()
    blk = clone.global_block()
    for idx, op in enumerate(blk.ops):
        if op.type in ("feed", "fetch"):
            continue
        try:
            registry.infer_op(op, blk)
        except Exception:
            continue             # not re-inferable out of build context
        for name in op.output_arg_names():
            if not name or name not in blk.vars or name not in src.vars:
                continue
            iv, sv = blk.vars[name], src.vars[name]
            ishape, sshape = iv.shape, sv.shape
            if ishape is not None and sshape is not None:
                if len(ishape) != len(sshape) or any(
                        a != b for a, b in zip(ishape, sshape)
                        if a not in (-1, None) and b not in (-1, None)):
                    diags.append(Diagnostic(
                        "shape_consistency", "warning",
                        f"var {name!r} records shape {list(sshape)} but "
                        f"inference over op {op.type!r} derives "
                        f"{list(ishape)}",
                        op_type=op.type, op_index=idx, var=name,
                        fix_hint="the shape was mutated after build; "
                                 "rebuild the op (append_op re-infers) "
                                 "instead of patching Variable.shape"))
            if iv.dtype and sv.dtype and iv.dtype != sv.dtype:
                diags.append(Diagnostic(
                    "shape_consistency", "warning",
                    f"var {name!r} records dtype {sv.dtype!r} but "
                    f"inference over op {op.type!r} derives {iv.dtype!r}",
                    op_type=op.type, op_index=idx, var=name,
                    fix_hint="rebuild the op instead of patching "
                             "Variable.dtype"))


def _check_dead_ops(graph, fetch_names, diags):
    from ..framework import ir
    dead = ir.dead_op_analysis(graph, protected=frozenset(fetch_names))
    dead_ids = {n.id for n in dead}
    indices = tuple(i for i, n in enumerate(graph.op_nodes)
                    if n.id in dead_ids)
    for i in indices:
        op = graph.op_nodes[i]
        # auto-generated backward leftovers (grads of non-parameter
        # inputs append_backward materializes and nothing consumes) are
        # framework-made, not a user defect: the dead_op_eliminate pass
        # still removes them, but only user-authored dead FORWARD compute
        # earns a diagnostic
        if op.name.endswith("_grad") or \
                op.op.attrs.get("op_role") == "backward":
            continue
        diags.append(Diagnostic(
            "dead_op", "warning",
            f"op {op.name!r} reaches no fetch target, persistable write, "
            "or side-effecting op — its outputs are computed and dropped",
            op_type=op.name, op_index=i,
            fix_hint="fetch its output if you need it; the "
                     "dead_op_eliminate pass removes it otherwise"))
    # sub-block bodies: dead body compute re-runs EVERY iteration — the
    # liveness keeps carried vars (their writers root through the
    # enclosing op's var lists) and flags only compute no carry, fetch,
    # or persistable observes
    sub_dead = ir.dead_subblock_op_analysis(
        graph.program, protected=frozenset(fetch_names))
    for blk_idx, sub_indices in sub_dead.items():
        block = graph.program.blocks[blk_idx]
        for i in sub_indices:
            op = block.ops[i]
            if op.type.endswith("_grad") or \
                    op.attrs.get("op_role") == "backward":
                continue
            diags.append(Diagnostic(
                "dead_op", "warning",
                f"op {op.type!r} inside sub-block {blk_idx} reaches no "
                "loop-carried var, fetch target, persistable write, or "
                "side-effecting op — it recomputes a dropped value EVERY "
                "iteration",
                op_type=op.type, op_index=i, block=str(blk_idx),
                fix_hint="carry or fetch its output if you need it; the "
                         "dead_op_eliminate pass prunes it otherwise"))
    return indices, sub_dead


def _rw_persistables(program: Program) -> set:
    block = program.global_block()
    written = set()
    for b in program.blocks:
        for op in b.ops:
            written.update(n for n in op.output_arg_names() if n)
    return {n for n in written
            if block.has_var(n) and block.var(n).persistable}


def _check_use_after_donate(program: Program, fetch_names, diags):
    rw = _rw_persistables(program)
    for name in fetch_names:
        if name in rw:
            diags.append(Diagnostic(
                "use_after_donate", "warning",
                f"fetch target {name!r} is a read-write persistable: the "
                "executor donates rw buffers to the next step, so every "
                "step must defensively copy this fetch out of the donated "
                "buffer",
                var=name,
                fix_hint="fetch a non-persistable snapshot (e.g. "
                         "layers.assign the value) or read it from the "
                         "scope at a step boundary instead"))


#: value-preserving ops the int64 classification propagates THROUGH: the
#: output carries the same fed values (reshaped/selected/concatenated),
#: so safety is decided by the OUTPUT's consumers.  concat is included
#: because the fed values survive verbatim into the merged var — a
#: bounded downstream index consumer bounds them exactly as it bounds a
#: direct feed.
_INT64_PASS_OPS = frozenset({
    "reshape", "reshape2", "squeeze", "squeeze2", "unsqueeze",
    "unsqueeze2", "flatten", "flatten2", "slice", "strided_slice",
    "split", "concat", "assign", "transpose", "transpose2",
})


def _classify_int64_feeds(program: Program, fetch_names=()):
    """Static feed-wrap classification v2: an int64/uint64 data feed
    whose every (transitively reached) consumer bounds its VALID values
    below 2**31 is ``static``: every in-range id fits int32, so the
    feed conversion only alters ids that were already invalid — and the
    consumer treats those identically with or without the wrap (see the
    _INT32_BOUND note; XLA gather clamps silently either way).

    v2 over the PR-5 classifier:

    - **bounded index consumers** now include the gather/scatter family
      (``gather``/``gather_nd``/``scatter``/``scatter_nd_add``) — the
      indexed operand's static dims are the bound, exactly as the
      embedding row count bounds ``lookup_table`` ids;
    - **dataflow propagation** through value-preserving chains
      (:data:`_INT64_PASS_OPS`: reshape/squeeze/flatten/slice/split/
      concat/transpose/assign) and integer-to-integer ``cast``: the
      chain's OUTPUT consumers decide, so ``reshape(ids) -> gather``
      classifies like a direct gather;
    - grad-op inheritance preserved: a grad op replays the forward's
      reads of the SAME fed values (``X$<slot>``), so it classifies
      exactly as its forward op.

    Everything else stays ``dynamic`` and keeps the executor's
    first-batch runtime min/max check."""
    block = program.global_block()
    feeds = [v for v in block.vars.values()
             if _is_data(v) and v.dtype in ("int64", "uint64")]
    if not feeds:
        return frozenset(), frozenset()

    def _shape(name, blk):
        if not blk.has_var(name):
            return None
        return blk.var(name).shape

    def _dim_bounded(name, blk, axis=None):
        """True when the indexed extent of var ``name`` is statically
        known and addressable by int32: the consumer clamps/ignores
        anything outside it, wrapped or not."""
        shape = _shape(name, blk)
        if not shape:
            return False
        if axis is None:
            dims = shape
        else:
            # normalize negative axes — a raw shape[-1:0] slice would
            # be EMPTY and all(...) vacuously true (unbounded extents
            # would classify static)
            axis = axis % len(shape) if -len(shape) <= axis < len(shape) \
                else None
            if axis is None:
                return False
            dims = shape[axis:axis + 1]
        return bool(dims) and all(
            d is not None and 0 < d < _INT32_BOUND for d in dims)

    def consumer_verdict(op, blk, name) -> str:
        """'safe' (bounded index consumer) | 'pass' (value-preserving,
        judge the outputs' consumers) | 'ignore' (harmless read that
        neither bounds nor propagates the values — a pass-through op's
        grad reads shape metadata only) | 'unsafe'."""
        typ = op.type
        is_grad = typ.endswith("_grad")
        if is_grad:
            # a grad op replays the forward's reads of the SAME fed
            # values (make_grad_ops forwards them under "X$<slot>"), so
            # it is exactly as safe as its forward op
            typ = typ[: -len("_grad")]

            def slot(s, _op=op):
                return _op.input("X$" + s) or _op.input(s)
        else:
            def slot(s, _op=op):
                return _op.input(s)
        if typ in ("lookup_table", "lookup_table_v2",
                   "fused_embedding_layer_norm") and \
                name in slot("Ids"):
            # the fused embedding+LN op (analysis.fusion) gathers rows
            # exactly like lookup_table: the table's row count bounds
            # valid ids, so fusion must not demote a static feed
            w = slot("W")
            return "safe" if w and _dim_bounded(w[0], blk, axis=0) \
                else "unsafe"
        if typ in ("one_hot", "one_hot_v2") and name in slot("X"):
            depth = op.attrs.get("depth")
            return "safe" if depth and int(depth) < _INT32_BOUND \
                else "unsafe"
        if typ == "gather" and name in slot("Index"):
            x = slot("X")
            axis = int(op.attrs.get("axis", 0))
            return "safe" if x and _dim_bounded(x[0], blk, axis=axis) \
                else "unsafe"
        if typ == "gather_nd" and name in slot("Index"):
            # the trailing index dim addresses the leading dims of X:
            # every statically-known dim under int32 bounds the tuple
            x = slot("X")
            return "safe" if x and _dim_bounded(x[0], blk) else "unsafe"
        if typ == "scatter" and name in slot("Ids"):
            x = slot("X")
            return "safe" if x and _dim_bounded(x[0], blk, axis=0) \
                else "unsafe"
        if typ == "scatter_nd_add" and name in slot("Index"):
            x = slot("X")
            return "safe" if x and _dim_bounded(x[0], blk) else "unsafe"
        if typ == "cast" and name in slot("X"):
            # int->int cast preserves in-range values; a float target
            # means the VALUES are data and a wrap would corrupt them
            outs = op.output_arg_names()
            out_dt = (blk.var(outs[0]).dtype
                      if outs and outs[0] and blk.has_var(outs[0])
                      else None)
            if not (out_dt and "int" in str(out_dt)):
                return "unsafe"
            return "ignore" if is_grad else "pass"
        if typ in _INT64_PASS_OPS:
            # the GRAD of a value-preserving op reads the fed values for
            # shape metadata only (reshape_grad reshapes the cotangent,
            # concat_grad splits it) — its outputs are float gradients,
            # not the fed values, so there is nothing to propagate to;
            # but neither does it BOUND the values, so it must not make
            # a chain static by itself ('ignore', not 'safe')
            return "ignore" if is_grad else "pass"
        return "unsafe"

    # consumer index over EVERY block (loop/cond bodies consume feeds
    # too — sub-block consumers classify exactly like top-level ones)
    consumers: Dict[str, list] = {}
    for b in program.blocks:
        for op in b.ops:
            if op.type in ("feed", "fetch"):
                continue
            for name in op.input_arg_names():
                if name:
                    consumers.setdefault(name, []).append((op, b))

    fetched = frozenset(fetch_names)

    def feed_static(feed_name: str) -> bool:
        # static requires a BOUNDED terminal consumer, not merely any
        # consumer: a chain of pure pass-through ops (reshape -> fetch)
        # re-exposes the raw values with nothing to clamp them, so it
        # must keep the runtime wrap check exactly as v1 did.  The same
        # exposure applies to ANY fetched name in the pass-through
        # closure (including the feed itself): the fetch materializes
        # the post-wrap device values even when a bounded SIBLING
        # consumer exists, so a fetched alias forces dynamic.
        seen = {feed_name}
        frontier = [feed_name]
        any_bounded = False
        while frontier:
            name = frontier.pop()
            if name in fetched:
                return False
            for op, blk in consumers.get(name, ()):
                verdict = consumer_verdict(op, blk, name)
                if verdict == "unsafe":
                    return False
                if verdict == "safe":
                    any_bounded = True
                if verdict == "pass":
                    for out in op.output_arg_names():
                        if out and out not in seen:
                            seen.add(out)
                            frontier.append(out)
        return any_bounded

    static, dynamic = set(), set()
    for v in feeds:
        (static if feed_static(v.name) else dynamic).add(v.name)
    return frozenset(static), frozenset(dynamic)


def _collective_signature(op_node, block: Block):
    op = op_node.op
    x = op.input("X")
    shape = dtype = None
    if x and block.has_var(x[0]):
        v = block.var(x[0])
        shape, dtype = v.shape, v.dtype
    return (op.type, op.attrs.get("ring_id", 0), dtype,
            tuple(shape) if shape else None)


def _check_collective_order(program: Program, graph, fetch_names, diags):
    """Dependency-order the collective ops of the WHOLE program, block 0
    and every ``while``/``cond`` sub-block recursively.  Pairs with no
    path between them can launch in different orders on different ranks
    (the compiler is free to schedule independent collectives for
    latency); when an unordered pair has the SAME signature the
    cross-rank pairing itself is ambiguous — the static form of the
    documented cross-rank ``.numpy()`` materialization deadlock — and
    the check applies per block: two identical unordered allreduces
    INSIDE a loop body mispair exactly like top-level ones.

    Returns the fingerprint of the dependency-ordered collective
    sequence, which every rank of a gang compares over the coordinator
    heartbeat and at ``step_barrier``.  Sub-block collectives fold in at
    their enclosing op's position, stamped with the block path
    (``0/while@5/1``): a loop-body collective is part of the rank's
    launch sequence even though the top-level graph never sees it, so a
    rank whose peer runs a different body refuses before the hang."""
    from ..framework import ir
    entries: List[tuple] = []   # (block path, signature), execution order

    def gather(block_graph, path: str):
        block = program.blocks[block_graph.block_idx]
        nodes = [n for n in block_graph.op_nodes
                 if n.name in _COLLECTIVE_OPS]
        if nodes:
            # forward-reachable op-id sets, by BFS from each collective
            reach: Dict[int, set] = {}
            for n in nodes:
                seen = set()
                stack = [n]
                while stack:
                    cur = stack.pop()
                    for v in cur.outputs:
                        for consumer in v.outputs:
                            if consumer.id not in seen:
                                seen.add(consumer.id)
                                stack.append(consumer)
                reach[n.id] = seen
            unordered, ambiguous = [], []
            for i in range(len(nodes)):
                for j in range(i + 1, len(nodes)):
                    a, b = nodes[i], nodes[j]
                    if b.id in reach[a.id] or a.id in reach[b.id]:
                        continue
                    sig_a = _collective_signature(a, block)
                    sig_b = _collective_signature(b, block)
                    (ambiguous if sig_a == sig_b else unordered).append(
                        (a.name, b.name, sig_a))
            where = "" if path == "0" else f" in sub-block {path!r}"
            if ambiguous:
                a, b, sig = ambiguous[0]
                diags.append(Diagnostic(
                    "collective_order", "error",
                    f"{len(ambiguous)} pair(s) of collective ops share "
                    f"a signature {sig!r} but have no dependency path "
                    f"between them{where} (first pair: {a!r}/{b!r}) — "
                    "ranks can launch them in different orders and "
                    "mispair, deadlocking the gang",
                    op_type=a, block=path,
                    fix_hint="chain them (feed one's output into the "
                             "other's input chain) or give each a "
                             "distinct ring_id"))
            elif unordered:
                diags.append(Diagnostic(
                    "collective_order", "warning",
                    f"{len(unordered)} pair(s) of collective ops have "
                    f"no dependency path between them{where}; their "
                    "launch order is compiler-chosen — verify the "
                    "collective fingerprint matches across ranks before "
                    "entering the gang",
                    op_type=unordered[0][0], block=path,
                    fix_hint="compare program._attrs['verify']"
                             "['collective_fingerprint'] across ranks"))
        # dependency order with a stable program-order tie-break
        # (topology_sort is deterministic for a fixed program); fold
        # sub-block collectives at the enclosing op's position
        order = {n.id: i for i, n in enumerate(
            block_graph.topology_sort())}
        pos = {id(op): i for i, op in enumerate(block.ops)}
        for n in sorted(block_graph.op_nodes,
                        key=lambda n: (order.get(n.id, 0), n.id)):
            if n.name in _COLLECTIVE_OPS:
                entries.append((path, _collective_signature(n, block)))
            subs = sub_blocks_of(n.op)
            if subs:
                idx = pos.get(id(n.op), order.get(n.id, 0))
                for _, sub in subs:
                    gather(ir.Graph(program, sub.idx),
                           f"{path}/{n.name}@{idx}/{sub.idx}")

    gather(graph, "0")
    if not entries and not program._attrs.get("collective"):
        return None
    h = hashlib.sha1()
    for path, sig in entries:
        h.update(repr((path, sig)).encode())
    h.update(repr(tuple(fetch_names)).encode())
    return h.hexdigest()


def _check_memory(program: Program, fetch_names, diags):
    """Static HBM plan (analysis.memory): batch=1 per-example lower
    bound, cached on the fingerprint alongside this verify result.  A
    ``memory_budget`` warning fires when FLAGS_memory_budget_mb is set
    and even the lower bound exceeds it.  Planning failures never block
    verification."""
    from . import memory as _memory
    try:
        plan = _memory.plan_memory(program, fetch_names, batch_size=1)
    except Exception:
        return None
    from ..flags import get_flags
    try:
        budget_mb = int(get_flags("FLAGS_memory_budget_mb")
                        ["FLAGS_memory_budget_mb"])
    except Exception:
        budget_mb = 0
    if budget_mb > 0 and plan.peak_bytes > budget_mb << 20:
        top = ", ".join(f"{t} #{p}" for p, t, _, _ in plan.top_ops(3))
        diags.append(Diagnostic(
            "memory_budget", "warning",
            f"static peak-memory estimate {plan.peak_bytes >> 20} MiB "
            f"(batch=1 lower bound) exceeds FLAGS_memory_budget_mb="
            f"{budget_mb}; heaviest ops: {top}",
            op_type=plan.peak_op, op_index=plan.peak_pos,
            fix_hint="shrink the model/batch, enable sharding, or raise "
                     "the budget; see analysis.memory.plan_memory("
                     "...).report() for the full attribution table"))
    return plan


def _check_cost(program: Program, fetch_names):
    """Analytic per-op flops/bytes plan (analysis.cost): batch=1
    per-example baseline, cached on the fingerprint alongside this
    verify result.  Purely informational — it stamps the attribution the
    executor's live MFU gauge and the fusion arc read; planning failures
    never block verification."""
    from . import cost as _cost
    try:
        return _cost.plan_cost(program, fetch_names, batch_size=1)
    except Exception:
        return None


def _check_comms(program: Program, fetch_names):
    """Static comms plan (analysis.comms): per-collective payload bytes,
    algorithm-bandwidth wire traffic, and the analytic comm-vs-compute
    bound at batch=1.  Same contract as the memory/cost planners:
    informational, fingerprint-cached, never blocks verification."""
    from . import comms as _comms
    try:
        return _comms.plan_comms(program, fetch_names, batch_size=1)
    except Exception:
        return None


def _comms_attrs(plan):
    from . import comms as _comms
    try:
        return _comms.stamp_attrs(plan)
    except Exception:
        return None


def _check_sharding(program: Program, fetch_names, diags):
    """Static GSPMD sharding plan (analysis.sharding): PartitionSpec
    propagation + per-edge reshard pricing over the partition stamp.
    Unlike the memory/cost/comms planners this check CAN block
    verification — its spec_conflict / mesh_axis_overuse errors are
    exactly the optimize-time rule-table refusal — but a planner CRASH
    still never blocks (same contract as the others)."""
    from . import sharding as _sharding
    try:
        plan = _sharding.plan_sharding(program, fetch_names,
                                       batch_size=1)
    except Exception:
        return None
    if plan is not None:
        diags.extend(plan.diagnostics)
    return plan


def _sharding_attrs(plan):
    from . import sharding as _sharding
    try:
        return _sharding.stamp_attrs(plan)
    except Exception:
        return None


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

def _partition_token(program: Program) -> Optional[str]:
    """GSPMD partition fingerprint of ``program``'s partition stamp
    (``with_gspmd``'s ``_attrs["partition"]``), or None when the program
    is unpartitioned."""
    stamp = program._attrs.get("partition")
    if not stamp:
        return None
    try:
        from ..parallel.partitioner import partition_fingerprint
        return partition_fingerprint(stamp)
    except Exception:
        return None


def _verify_cached(program: Program, fetch_names) -> \
        Tuple[VerifyResult, bool]:
    """(result, fresh): ``fresh`` is True for exactly ONE caller per
    cache key — the thread whose result entered the cache — so warning
    emission can be deduped without re-deriving the key outside."""
    fetch_names = tuple(
        f.name if hasattr(f, "name") else f for f in (fetch_names or ()))
    # keyed on the fetch TUPLE: order matters — the collective
    # fingerprint hashes the materialization (fetch) order, so a
    # reordered fetch list must re-verify, not hit a stale result.
    # The GSPMD partition stamp joins the key: it lives in _attrs (not
    # the structural fingerprint), and a re-partitioned program must
    # re-derive its folded fingerprint, not hit the old table's.
    ptok = _partition_token(program)
    key = (program.fingerprint(), fetch_names, ptok)
    with _CACHE_LOCK:
        cached = _CACHE.get(key)
    if cached is not None:
        _RUNS_HIT.inc()
        return cached, False
    _RUNS_MISS.inc()
    with _monitor.TRACER.span("verifier.verify", "compile",
                              fetches=len(fetch_names)):
        from ..framework import ir
        result = VerifyResult()
        diags = result.diagnostics
        # one read-only Graph shared by the graph-walking checks
        graph = ir.Graph(program)
        _check_def_before_use(program, diags)
        _check_feed_fetch(program, fetch_names, diags)
        try:
            _check_shape_consistency(program, diags)
        except Exception:            # re-inference must never block verify
            pass
        result.dead_ops, result.dead_subblock_ops = \
            _check_dead_ops(graph, fetch_names, diags)
        _check_use_after_donate(program, fetch_names, diags)
        result.int64_static, result.int64_dynamic = \
            _classify_int64_feeds(program, fetch_names)
        result.collective_fingerprint = _check_collective_order(
            program, graph, fetch_names, diags)
        result.memory_plan = _check_memory(program, fetch_names, diags)
        result.cost_plan = _check_cost(program, fetch_names)
        result.comms_plan = _check_comms(program, fetch_names)
        result.sharding_plan = _check_sharding(program, fetch_names,
                                               diags)
        if result.comms_plan is not None and \
                result.collective_fingerprint is not None:
            # fold the comms plan (nranks + ordered per-collective
            # payload bytes) into the cross-rank fingerprint: the gang
            # compares ONE token over the heartbeat/step-barrier, and a
            # divergent comms plan must refuse exactly like a divergent
            # collective sequence.  Every rank derives it through this
            # same function, so matching programs keep matching.
            result.collective_fingerprint = hashlib.sha1(
                (result.collective_fingerprint + "|"
                 + result.comms_plan.fingerprint).encode()).hexdigest()
        if ptok:
            # fold the GSPMD partition stamp (mesh shape + per-param
            # PartitionSpecs) the same way: ranks that chose divergent
            # rule tables refuse at the step barrier instead of
            # deadlocking inside mismatched collectives.  Base may be
            # None — a pjit-partitioned program has no explicit
            # collective ops.  The "#rules=<table>" suffix survives the
            # hash so the coordinator's mismatch detail, which prints
            # both raw fingerprints, NAMES both tables.
            # the "#resh=<edges>x<sha8>" token joins the fold: two ranks
            # running the SAME rule table over structurally divergent
            # programs (different models, different zero stage) carry
            # different reshard plans — the barrier refusal names both
            # plans instead of deadlocking inside mismatched implicit
            # collectives.  It precedes "#rules=" so the rules suffix
            # stays the FINAL token (coordinator's _gspmd_rules_of
            # parses split("#rules=")[1] verbatim).
            resh = ""
            if result.sharding_plan is not None:
                resh = "#resh=" + result.sharding_plan.resh_token
            base = result.collective_fingerprint or ""
            digest = hashlib.sha1(
                (base + "|" + ptok + resh).encode()).hexdigest()
            result.collective_fingerprint = \
                digest + resh + ptok[ptok.index("#"):]
    for d in diags:
        _FINDING_CELLS[d.check].inc()
    # int64_feed "findings" are classifications, not diagnostics: the
    # counter tracks how many feeds KEPT the runtime wrap check
    if result.int64_dynamic:
        _FINDING_CELLS["int64_feed"].inc(len(result.int64_dynamic))
    plan = result.memory_plan
    program._attrs["verify"] = {
        "int64_dynamic": sorted(result.int64_dynamic),
        "int64_static": sorted(result.int64_static),
        "collective_fingerprint": result.collective_fingerprint,
        # static HBM model (batch=1 lower bound): the numbers other
        # layers read without re-planning — tools/analyze.py, the OOM
        # report, the GSPMD/fusion arc's placement heuristics
        "memory": None if plan is None else {
            "peak_bytes": plan.peak_bytes,
            "resident_bytes": plan.resident_bytes,
            "steady_bytes": plan.steady_bytes,
            "peak_op": plan.peak_op,
            "top_ops": [(p, t, b) for p, t, b, _ in plan.top_ops(5)],
        },
        # analytic flops/bytes model (batch=1 baseline): the per-step
        # numbers the executor's live MFU gauge scales by the real
        # batch, and the per-class roofline share the fusion arc ranks
        # rewrite candidates by
        "cost": None if result.cost_plan is None else {
            "flops": result.cost_plan.flops,
            "bytes": result.cost_plan.bytes,
            "per_class": dict(result.cost_plan.per_class),
            "intensity": result.cost_plan.intensity(),
        },
        # static comms model (batch=1 baseline): per-collective payload/
        # wire bytes, the analytic comm-time estimate at link peak, and
        # the comm-vs-compute bound verdict — what the executor's
        # collective launch telemetry, tools/comms_smoke.py, and the
        # quantized-collectives gate read without re-planning
        "comms": _comms_attrs(result.comms_plan),
        # static GSPMD sharding model: propagated specs + priced reshard
        # edges + the #resh= parity token — what tools/analyze.py
        # --sharding, the gspmd/sharding smokes, and choose_rules
        # auditing read without re-planning
        "sharding": _sharding_attrs(result.sharding_plan),
    }
    with _CACHE_LOCK:
        fresh = key not in _CACHE
        if fresh:
            if len(_CACHE) >= _CACHE_CAP:   # FIFO bound, see _CACHE note
                _CACHE.pop(next(iter(_CACHE)))
            _CACHE[key] = result
        result = _CACHE[key]   # concurrent misses converge on one object
    return result, fresh


def verify_program(program: Program, fetch_names=()) -> VerifyResult:
    """Run every check; cached on (program fingerprint, fetch tuple).

    Also stamps ``program._attrs["verify"]`` with the machine-readable
    artifacts (int64 classification, collective fingerprint) — the attrs
    ride ``Program.clone``, so the optimized program the executor caches
    in its dispatch plan carries them too."""
    return _verify_cached(program, fetch_names)[0]


def verify_or_raise(program: Program, fetch_names=()) -> VerifyResult:
    """``verify_program`` + enforcement: error-severity findings raise
    :class:`ProgramVerificationError` (with the full debugger-formatted
    report), warning-severity findings emit one ``warnings.warn`` per
    fresh verify (the fingerprint cache dedupes steady-state repeats,
    and ``_verify_cached`` marks exactly one caller fresh per key)."""
    result, fresh = _verify_cached(program, fetch_names)
    from .. import debugger
    if not result.ok:
        raise ProgramVerificationError(
            "program verification failed:\n"
            + debugger.format_diagnostics(result.diagnostics), result)
    if fresh and result.warnings():
        import warnings
        warnings.warn(
            "program verifier warnings:\n"
            + debugger.format_diagnostics(result.warnings()),
            stacklevel=2)
    return result


def dynamic_int64_feeds(program: Program) -> Optional[FrozenSet[str]]:
    """The int64/uint64 feed names still needing the runtime wrap check,
    or None when the program was never verified (caller falls back to
    checking every int64 feed — the legacy behavior)."""
    va = program._attrs.get("verify")
    if va is None or va.get("int64_dynamic") is None:
        return None
    return frozenset(va["int64_dynamic"])


def collective_fingerprint(program: Program) -> Optional[str]:
    va = program._attrs.get("verify")
    if va is not None and va.get("collective_fingerprint"):
        return va["collective_fingerprint"]
    result = verify_program(program)
    return result.collective_fingerprint
