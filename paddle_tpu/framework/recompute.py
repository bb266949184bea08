"""Activation rematerialization as a program transform (TPU-native; the
2019 reference stores every forward activation — SURVEY §5.7 notes its only
memory levers were eager deletion and reuse passes.  Modern large-model
training on TPU needs recompute to fit, so it is first-class here).

``apply_recompute(program, checkpoints)`` rewrites a program AFTER
``append_backward``:

1. the forward ops between consecutive checkpoint vars form segments;
2. each segment is re-emitted after the loss-grad seed with every
   intermediate renamed ``v@RECOMPUTE``, reading segment inputs through an
   ``optimization_barrier`` (the CSE fence — without it XLA merges the
   recomputation back into the stored original and no memory is saved);
3. backward ops are rewired to consume the ``@RECOMPUTE`` values.

Under XLA's liveness this makes segment intermediates die at the end of the
forward pass and re-materialize during backward — the effect of
``jax.checkpoint``, expressed in the Program IR.

Every op the transform emits, clone or barrier, carries the attr
``RECOMPUTED_ATTR`` — not a value of ``op_role``, whose meanings
(``clone(for_test)``'s pruning, the verifier's and the fusion pass's
``== "backward"``) stay as they are — and the executor lowers a marked op
under ``pt.rc/<op type>`` (``executor.op_scope``), so that on a device trace
the second forward has a name of its own beside ``pt.fwd/*``.
``paddle_tpu_recompute_ops_total{op}`` counts the clones by op type, once per
transform.

RNG-stateful ops are NOT recomputed UNLESS their draw is replay-safe:
tagged dropout (a nonzero ``seed`` attr) derives its bits purely from
(per-step key, tag), so re-evaluating it reproduces the identical mask and
it recomputes like any pure op.  Counter-stream RNG ops (untagged dropout,
random_crop, …) would re-draw differently, so their outputs stay stored
and feed the recomputed chain through barriers.
"""

from __future__ import annotations

import collections
from typing import Dict, List, Sequence

from .. import monitor as _monitor
from . import registry
from .core import Operator, Program

RECOMPUTE_SUFFIX = "@RECOMPUTE"
BARRIER_SUFFIX = "@RBAR"
#: attr of every op ``apply_recompute`` emits (clones and their barriers)
RECOMPUTED_ATTR = "recomputed"

RECOMPUTE_OPS_CTR = _monitor.REGISTRY.counter(
    "paddle_tpu_recompute_ops_total",
    "forward ops apply_recompute emitted a second time (the clones whose "
    "outputs are the @RECOMPUTE values; barriers not counted), by op type — "
    "counted where the segments are made, once per transform of a program, "
    "nothing per step: what a trace's pt.rc/<op> scopes can be checked "
    "against", ("op",))


def _is_rng_op(op: Operator) -> bool:
    if op.type == "dropout" and op.attrs.get("seed", 0):
        return False     # tagged dropout replays bit-identically — pure
    info = registry._REGISTRY.get(op.type)
    return bool(info and info.stateful_rng)


def apply_recompute(program: Program, checkpoints: Sequence[str],
                    after_gradient: bool = False) -> Program:
    """Rewrite IN PLACE; returns the program.  ``checkpoints`` are forward
    var names (segment boundaries) that stay stored.

    ``after_gradient``: a segment between two checkpoints is emitted where
    the backward has made the gradient of the checkpoint that ENDS it, and
    its input passes the barrier together with that gradient (one
    ``optimization_barrier`` over the pair, whose second result the backward
    goes on from), so that the compiler cannot run a segment again before
    the backward has reached it.  Without it nothing but the scheduler's own
    choice keeps the recomputed segments from running side by side at the
    start of the backward (Solar-Open2's step held four blocks' recomputed
    expert buffers at once: PERF.md section 6, PR 49).  A segment may then
    read stored values and its own alone: a program in which a later segment
    reads what an earlier one makes again is refused (``ValueError``)."""
    block = program.global_block()
    ckpt = set(checkpoints)
    loss_seed = None
    for i, op in enumerate(block.ops):
        if op.type == "fill_constant" and any(
                n.endswith("@GRAD") for n in op.output_arg_names()):
            loss_seed = i
            break
    if loss_seed is None:
        raise ValueError("apply_recompute needs a program with backward "
                         "ops (call minimize()/append_backward first)")

    fwd_ops = block.ops[:loss_seed]
    bwd_ops = block.ops[loss_seed:]

    # vars the backward actually reads from the forward
    bwd_reads = set()
    for op in bwd_ops:
        bwd_reads.update(op.input_arg_names())

    # choose ops to recompute: forward ops after the FIRST checkpoint,
    # excluding RNG ops (their outputs stay stored — re-drawing a dropout
    # mask would silently change gradients)
    rename: Dict[str, str] = {}
    recompute_ops: List[Operator] = []
    barriered: Dict[str, str] = {}

    def barrier_name(v):
        # parameters/persistables can't be CSE'd with anything (they're
        # jit arguments) — fencing them is pure graph bloat
        var = block.vars.get(v)
        if var is not None and var.persistable:
            return v
        if v not in barriered:
            barriered[v] = v + BARRIER_SUFFIX
        return barriered[v]

    seen_ckpt = False
    reached: List[str] = []          # the checkpoints in forward order
    segment_of: List[int] = []       # per clone: checkpoints before it
    for op in fwd_ops:
        outs = op.output_arg_names()
        before = len(reached)
        reached.extend(o for o in outs if o in ckpt)
        if not seen_ckpt:
            if ckpt & set(outs):
                seen_ckpt = True
            continue
        if _is_rng_op(op) or op.type in ("feed",):
            continue
        needed = any(o in bwd_reads and o not in ckpt for o in outs)
        feeds_chain = any(o in rename for o in op.input_arg_names())
        if not needed and not feeds_chain:
            continue
        # clone with renamed inputs/outputs; every stored value entering
        # the chain passes through a CSE fence
        clone = Operator(block, op.type,
                         attrs=dict(op.attrs, **{RECOMPUTED_ATTR: True}))
        clone.inputs = {
            slot: [rename.get(n, barrier_name(n) if n else n)
                   for n in names]
            for slot, names in op.inputs.items()}
        clone.outputs = {}
        for slot, names in op.outputs.items():
            new = []
            for n in names:
                if not n:
                    new.append(n)
                elif n in ckpt:
                    # checkpoints stay stored: the clone's copy is a dead
                    # value XLA removes; chain reads hit the barrier'd
                    # original (the segment boundary)
                    new.append(n + RECOMPUTE_SUFFIX + "@DEAD")
                else:
                    rename[n] = n + RECOMPUTE_SUFFIX
                    new.append(rename[n])
            clone.outputs[slot] = new
        recompute_ops.append(clone)
        segment_of.append(before)

    if not recompute_ops:
        return program

    # materialize barrier ops + vars
    barrier_ops: List[Operator] = []
    for src, dst in barriered.items():
        if not block.has_var(dst):
            v = block.var(src) if block.has_var(src) else None
            block.create_var(name=dst, shape=v.shape if v else None,
                             dtype=v.dtype if v else "float32")
        b = Operator(block, "optimization_barrier",
                     inputs={"X": [src]}, outputs={"Out": [dst]},
                     attrs={RECOMPUTED_ATTR: True})
        barrier_ops.append(b)
    for clone in recompute_ops:
        for names in clone.outputs.values():
            for dst in names:
                if dst and not block.has_var(dst):
                    src = dst.split(RECOMPUTE_SUFFIX)[0]
                    v = block.var(src) if block.has_var(src) else None
                    block.create_var(name=dst,
                                     shape=v.shape if v else None,
                                     dtype=v.dtype if v else "float32")

    # rewire backward reads onto the recomputed values
    for op in bwd_ops:
        for slot, names in op.inputs.items():
            op.inputs[slot] = [rename.get(n, n) for n in names]

    if after_gradient:
        block.ops = fwd_ops + _after_gradients(
            block, bwd_ops, barrier_ops, recompute_ops, segment_of, reached)
    else:
        # XLA schedules by dataflow, not by this position: nothing holds a
        # recomputed chain back to the grads that consume it (JoyAI's step
        # fits so; Solar-Open2's ran four blocks again side by side and was
        # refused at 19.19 GiB, which is what after_gradient is for)
        block.ops = fwd_ops + [bwd_ops[0]] + barrier_ops + \
            recompute_ops + bwd_ops[1:]
    program._bump_version()
    for typ, n in collections.Counter(
            c.type for c in recompute_ops).items():
        RECOMPUTE_OPS_CTR.inc(n, op=typ)
    return program


def _after_gradients(block, bwd_ops, barrier_ops, clones, segment_of,
                     reached):
    """The backward's op list with each segment behind the gradient of the
    checkpoint that ends it: the segment that starts at ``reached[i - 1]``
    and ends at ``reached[i]`` (``segment_of`` == i) goes behind the last op
    that writes ``reached[i]@GRAD``; the barrier of its input takes that
    gradient as a second operand, and the ops behind read the barrier's
    copy of it.  What has no such gradient (the ops behind the last
    checkpoint, a stored value that is no checkpoint) stays at the front."""
    from .core import grad_var_name
    by_src = {b.inputs["X"][0]: b for b in barrier_ops}
    last_write = {}
    for i, op in enumerate(bwd_ops):
        for n in op.output_arg_names():
            last_write[n] = i
    behind = collections.defaultdict(list)        # bwd index -> ops
    front = []
    tied = set()
    for seg in sorted(set(segment_of)):
        ops = [c for c, s in zip(clones, segment_of) if s == seg]
        grad = grad_var_name(reached[seg]) if seg < len(reached) else None
        src = reached[seg - 1] if seg >= 1 else None
        if grad not in last_write or src not in by_src:
            front.extend(ops)
            continue
        at = last_write[grad]
        fenced = grad + BARRIER_SUFFIX
        v = block.var(grad) if block.has_var(grad) else None
        block.create_var(name=fenced, shape=v.shape if v else None,
                         dtype=v.dtype if v else "float32")
        barrier = by_src[src]
        barrier.inputs["X"].append(grad)
        barrier.outputs["Out"].append(fenced)
        tied.add(src)
        for op in bwd_ops[at + 1:]:
            for slot, names in op.inputs.items():
                op.inputs[slot] = [fenced if n == grad else n for n in names]
        behind[at].extend([barrier] + ops)
    out = [bwd_ops[0]] + [b for b in barrier_ops
                          if b.inputs["X"][0] not in tied] + front
    for i, op in enumerate(bwd_ops[1:], 1):
        out.append(op)
        out.extend(behind.get(i, ()))
    out += behind.get(0, [])
    # the segments now stand in the backward's order: one that reads what
    # an EARLIER segment makes again (a term summed over the blocks, as
    # JoyAI's step has) would read it before it is made
    made = set()
    for op in out:
        late = [n for n in op.input_arg_names()
                if n.endswith(RECOMPUTE_SUFFIX) and n not in made]
        if late:
            raise ValueError(
                f"after_gradient: {op.type} reads {late[0]}, which an "
                f"earlier segment makes again and the backward reaches "
                f"later; make it a checkpoint or leave after_gradient out")
        made.update(op.output_arg_names())
    return out
