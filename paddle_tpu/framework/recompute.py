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


def apply_recompute(program: Program,
                    checkpoints: Sequence[str]) -> Program:
    """Rewrite IN PLACE; returns the program.  ``checkpoints`` are forward
    var names (segment boundaries) that stay stored."""
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
    for op in fwd_ops:
        outs = op.output_arg_names()
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

    # op-list position is cosmetic — XLA schedules by dataflow and sinks
    # each recomputed chain next to the grads consuming it
    block.ops = fwd_ops + [bwd_ops[0]] + barrier_ops + \
        recompute_ops + bwd_ops[1:]
    program._bump_version()
    for typ, n in collections.Counter(
            c.type for c in recompute_ops).items():
        RECOMPUTE_OPS_CTR.inc(n, op=typ)
    return program
