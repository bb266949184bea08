"""Op registry: per-op-type JAX lowering + shape inference + grad synthesis.

TPU-native replacement for the reference's static kernel registration
(``paddle/fluid/framework/op_registry.h:199-243``, ``op_info.h``,
``grad_op_desc_maker.h``).  Where the reference registers per-device
C++/CUDA kernels keyed by ``OpKernelType``, we register a single *lowering*
function per op type that emits JAX ops while the surrounding Block is traced
into one XLA computation.  Shape inference (ref ``shape_inference.h``) is the
lowering itself run abstractly via ``jax.eval_shape`` — one source of truth.

Gradients: an op without a grad maker gets a synthesized ``<type>_grad`` op
desc (ref ``GradOpDescMakerBase``) lowered as ``jax.vjp`` of the forward
lowering.  XLA's CSE merges the vjp's recomputed forward with the original
forward ops, so that path costs nothing after compilation, except where the
lowering holds a Pallas call: XLA merges no two Mosaic calls, so the kernel
runs twice a step, and such an op has a hand-written grad maker over saved
residuals (``moe_ffn``, ``flash_attention``; like dropout's saved mask).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .core import Block, Operator, Variable, grad_var_name


class OpInfo:
    def __init__(self, type: str, lower: Callable, infer: Optional[Callable],
                 grad_maker: Optional[Callable], no_grad: bool,
                 stateful_rng: bool, raw: bool = False):
        self.type = type
        self.lower = lower
        self.infer = infer
        self.grad_maker = grad_maker    # None -> generic vjp grad
        self.no_grad = no_grad
        self.stateful_rng = stateful_rng
        # raw ops get (ctx, block, op, state) — needed by control flow which
        # must trace sub-blocks (ref while_op.cc executing a sub-block)
        self.raw = raw


_REGISTRY: Dict[str, OpInfo] = {}


def register_op(type: str, lower: Callable = None, *, infer: Callable = None,
                grad_maker: Callable = None, no_grad: bool = False,
                stateful_rng: bool = False, raw: bool = False):
    """Register an op lowering.  Usable as decorator or call.

    lower(ctx, ins, attrs) -> outs, where ins/outs are {slot: [jax arrays]}.
    Raw ops instead get lower(ctx, block, op, state).  One type has one
    lowering: a second registration by another function raises (import
    order would otherwise pick which one a program runs).
    """
    def deco(fn):
        held = _REGISTRY.get(type)
        if held is not None and held.lower is not fn:
            raise ValueError(
                f"op {type!r} is already registered by "
                f"{held.lower.__module__}.{held.lower.__qualname__}; "
                f"{fn.__module__}.{fn.__qualname__} may not replace it")
        _REGISTRY[type] = OpInfo(type, fn, infer, grad_maker, no_grad,
                                 stateful_rng, raw)
        return fn
    if lower is not None:
        return deco(lower)
    return deco


def get_op_info(type: str) -> OpInfo:
    if type not in _REGISTRY:
        raise NotImplementedError(f"op {type!r} has no registered lowering")
    return _REGISTRY[type]


def has_op(type: str) -> bool:
    return type in _REGISTRY


def registered_ops() -> List[str]:
    return sorted(_REGISTRY)


# ---------------------------------------------------------------------------
# build-time shape/dtype inference (ref framework/operator.cc:913 InferShape)
# ---------------------------------------------------------------------------

_NO_INFER = {"feed", "fetch", "while", "conditional_block"}


class _AbstractCtx:
    """LowerCtx stand-in for abstract evaluation."""
    is_abstract = True

    def rng(self):
        return jax.random.key(0)

    def rng_tagged(self, tag):
        return jax.random.key(0)

    @property
    def mesh(self):
        return None


def infer_op(op: Operator, block: Block) -> None:
    """Populate output Variable shape/dtype by abstractly running the lowering."""
    if op.type not in _REGISTRY:
        return
    info = _REGISTRY[op.type]
    if info.infer is not None:
        info.infer(op, block)
        return
    if op.type in _NO_INFER or info.raw:
        # raw (sub-block) ops can't go through eval_shape; they either carry
        # an explicit infer above or are skipped
        return
    # symbolic batch dim: -1 is replaced by a sentinel for abstract eval and
    # mapped back afterwards (the reference's InferShape threads -1 natively).
    # The sentinel is a large prime so an accidental collision with a real
    # layer dim is vanishingly unlikely; the reverse map only runs when some
    # input actually had a -1.
    SENTINEL = 9973
    had_symbolic = False
    try:
        structs = {}
        for slot, names in op.inputs.items():
            arrs = []
            for n in names:
                if not n:
                    arrs.append(None)
                    continue
                v = block.var(n)
                if v.shape is None:
                    return  # can't infer yet
                if -1 in v.shape:
                    had_symbolic = True
                shape = tuple(SENTINEL if d == -1 else d for d in v.shape)
                arrs.append(jax.ShapeDtypeStruct(shape, jnp.dtype(v.dtype)))
            structs[slot] = arrs

        def f(ins):
            return info.lower(_AbstractCtx(), ins, op.attrs)

        outs = jax.eval_shape(f, structs)
        for slot, names in op.outputs.items():
            shaped = outs.get(slot, [])
            for n, s in zip(names, shaped):
                if s is None:
                    continue
                v = block.var(n)
                v.shape = tuple(-1 if (had_symbolic and d == SENTINEL) else d
                                for d in s.shape)
                v.dtype = np.dtype(s.dtype).name
    except Exception:
        # inference is best-effort at build time; executor re-checks at lower
        # time with concrete shapes.
        pass


# ---------------------------------------------------------------------------
# generic vjp-based gradient (stands in for GradOpDescMaker per op)
# ---------------------------------------------------------------------------

GENERIC_GRAD_TYPE_SUFFIX = "_grad"


def make_grad_ops(op: Operator, block: Block,
                  no_grad_set: set) -> List[Dict[str, Any]]:
    """Produce grad op descs for ``op`` (ref core.get_grad_op_desc,
    pybind.cc:726 → backward.py:431).

    Returns a list of dicts {type, inputs, outputs, attrs}.  Grad var names
    follow the reference convention ``<name>@GRAD``.
    """
    info = get_op_info(op.type)
    if info.no_grad:
        return []
    if info.grad_maker is not None:
        return info.grad_maker(op, block, no_grad_set)

    # generic: one grad op consuming fwd inputs + output-grads,
    # producing input-grads via jax.vjp of the forward lowering.
    g_inputs: Dict[str, List[str]] = {}
    for slot, names in op.inputs.items():
        g_inputs["X$" + slot] = list(names)
    for slot, names in op.outputs.items():
        g_inputs["OG$" + slot] = [grad_var_name(n) for n in names]
    g_outputs: Dict[str, List[str]] = {}
    for slot, names in op.inputs.items():
        outs = []
        for n in names:
            v = block.var(n) if block.has_var(n) else None
            if n in no_grad_set or (v is not None and v.stop_gradient):
                outs.append("")          # empty = not needed (ref kEmptyVarName)
            else:
                outs.append(grad_var_name(n))
        g_outputs["IG$" + slot] = outs
    attrs = dict(op.attrs)
    attrs["__fwd_type__"] = op.type
    return [{"type": op.type + GENERIC_GRAD_TYPE_SUFFIX,
             "inputs": g_inputs, "outputs": g_outputs, "attrs": attrs,
             "__generic__": True}]


def generic_grad_lower(ctx, ins: Dict[str, List], attrs: Dict[str, Any]):
    """Lowering for synthesized ``*_grad`` ops: jax.vjp of forward lowering."""
    fwd_type = attrs["__fwd_type__"]
    info = get_op_info(fwd_type)
    fwd_attrs = {k: v for k, v in attrs.items() if k != "__fwd_type__"}

    in_slots = sorted(s[2:] for s in ins if s.startswith("X$"))
    og_slots = sorted(s[3:] for s in ins if s.startswith("OG$"))

    flat_in, spec = [], []
    for slot in in_slots:
        arrs = ins["X$" + slot]
        spec.append((slot, len(arrs)))
        flat_in.extend(arrs)

    def fwd(*flat):
        d, i = {}, 0
        for slot, n in spec:
            d[slot] = list(flat[i:i + n])
            i += n
        if getattr(ctx, "amp", False):
            # cast INSIDE the vjp so master-weight grads come back f32
            # while the recomputed forward hits the MXU in bf16
            from .. import amp as _amp
            d = _amp.cast_ins(fwd_type, d)
        outs = info.lower(ctx, d, fwd_attrs)
        flat_out = []
        for slot in og_slots:
            flat_out.extend(outs.get(slot, []))
        return tuple(flat_out)

    primals_out, vjp = jax.vjp(fwd, *flat_in)
    cotangents = []
    i = 0
    for slot in og_slots:
        n = len(ins["OG$" + slot])
        for j in range(n):
            og = ins["OG$" + slot][j]
            if og is None:   # unused output: zero cotangent
                og = jnp.zeros(primals_out[i + j].shape,
                               primals_out[i + j].dtype)
            cotangents.append(og.astype(primals_out[i + j].dtype))
        i += n
    in_grads = vjp(tuple(cotangents))

    outs, i = {}, 0
    for slot, n in spec:
        outs["IG$" + slot] = list(in_grads[i:i + n])
        i += n
    return outs
