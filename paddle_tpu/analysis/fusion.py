"""Cost-guided, training-safe graph fusion over the ``framework.ir`` Graph.

The reference repo's fusion passes (``ir/conv_bn_fuse_pass.cc``,
``ir/fc_fuse_pass.cc``, ...) fire unconditionally on any structural
match; TVM (arxiv 1802.04799) showed cost-driven candidate selection
beats fixed rewrite rules, and Tensor Processing Primitives
(arxiv 2104.05755) motivates a few fused op shapes over many small
ones.  This pass combines the three ideas into the PR-5
pass-before-lowering slot:

1. **Match** candidate subgraphs with the existing
   ``PDPattern``/``GraphPatternDetector`` machinery:

   ======================  =================================  ==========
   pattern                 subgraph                           fused op
   ======================  =================================  ==========
   dense_epilogue          mul/matmul + bias add +            fused_dense_act
                           gelu/relu [+ tagged dropout]
   embedding_layer_norm    lookup_table [+ adds] +            fused_embedding_
                           layer_norm                         layer_norm
   ======================  =================================  ==========

2. **Prove each match legal for training** with a static analysis —
   every internal var must be single-consumer, non-fetched,
   non-persistable, not referenced by a control-flow sub-block
   (the dead-op liveness preconditions), and alias/donation-safe per
   the memory planner's inplace-pair interval model; in a program
   containing grad ops, the forward rewrite must come with a complete
   matching grad-op rewrite (the backward chain is located, checked
   single-consumer, and replaced by the fused op's generic-vjp grad) or
   the candidate is REJECTED.  Rejections carry the failing rule and
   are reported through ``debugger.format_diagnostics``.

3. **Rank survivors by the PR-8 cost model's per-class roofline
   shares** (``analysis.cost.CostPlan.share``): a candidate whose op
   class is below ``FLAGS_fusion_rank_threshold`` of the step's
   flop+byte budget is not worth a rewrite ("ranked_out").

The pass is static: legality and rank decide, nothing is measured.

Safety rails: the verifier runs before and after the pass, the
collective fingerprint must be UNCHANGED by fusion (fusion never
touches collectives — a changed fingerprint rolls the rewrite back),
``_attrs["verify"]`` rides the rewritten program, and every decision is
counted in ``paddle_tpu_fusion_candidates_total{pattern,verdict}``.
``FLAGS_graph_fusion`` (default on) is the master gate; the executor
and ``compiler.optimize`` key their caches on :func:`config_token`, so
flipping any fusion flag invalidates stale plans.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from .. import monitor as _monitor
from ..framework.core import Block, Program
from ..framework.recompute import RECOMPUTED_ATTR

__all__ = [
    "FusionDecision", "FusionReport", "analyze_program", "clear_cache",
    "config_token", "fuse_program",
]

_CAND_CTR = _monitor.REGISTRY.counter(
    "paddle_tpu_fusion_candidates_total",
    "graph-fusion candidate decisions by pattern and verdict "
    "(applied / rejected / ranked_out / overlapped / verify_failed)",
    ("pattern", "verdict"))

#: collective op prefixes fusion must never touch (the fingerprint
#: invariance check backstops this structurally)
_COLLECTIVE_PREFIX = "c_"

#: activations the dense epilogue folds
_DENSE_ACTS = ("gelu", "relu")


def config_token() -> tuple:
    """The fusion configuration visible to cache keys: executor dispatch
    plans and ``compiler.optimize`` results keyed on this token are
    invalidated by any fusion-flag change."""
    from ..flags import get_flags
    fl = get_flags(["FLAGS_graph_fusion", "FLAGS_fusion_rank_threshold"])
    return (bool(fl["FLAGS_graph_fusion"]),
            float(fl["FLAGS_fusion_rank_threshold"]))


@dataclass
class FusionDecision:
    """One candidate's fate, machine-readable for tools/analyze.py and
    the bench fusion line."""

    pattern: str
    anchor: str                 # the chain's output var (display name)
    verdict: str                # applied|rejected|ranked_out|...
    rule: Optional[str] = None  # failing legality rule for 'rejected'
    rank: float = 0.0           # per-class roofline share in [0, 1]

    def as_dict(self) -> dict:
        out = {"pattern": self.pattern, "anchor": self.anchor,
               "verdict": self.verdict, "rank": round(self.rank, 4)}
        if self.rule:
            out["rule"] = self.rule
        return out


@dataclass
class FusionReport:
    decisions: List[FusionDecision] = field(default_factory=list)
    applied: int = 0
    collective_fingerprint_ok: bool = True

    def by_verdict(self, verdict: str) -> List[FusionDecision]:
        return [d for d in self.decisions if d.verdict == verdict]

    def as_dict(self) -> dict:
        return {"applied": self.applied,
                "collective_fingerprint_ok":
                    self.collective_fingerprint_ok,
                "candidates": [d.as_dict() for d in self.decisions]}


# ---------------------------------------------------------------------------
# candidate model
# ---------------------------------------------------------------------------

class _Candidate:
    """One matched subgraph plus everything needed to judge and apply it.

    ``fwd_ops``/``grad_ops`` are the op Nodes the rewrite removes;
    ``internal`` the var Nodes that disappear (their consumers must all
    be inside the candidate); ``build(graph)`` applies the forward AND
    grad rewrite."""

    def __init__(self, pattern: str, op_class: str, anchor: str):
        self.pattern = pattern
        self.op_class = op_class
        self.anchor = anchor
        self.fwd_ops: List = []
        self.grad_ops: List = []
        self.internal: List = []
        self.dead_outputs: List = []    # side-output var nodes that die
        self.reject_rule: Optional[str] = None   # structural pre-reject
        self.build = None               # set by the matcher when legal

    def all_ops(self) -> List:
        return self.fwd_ops + self.grad_ops


def _has_grad_ops(program: Program) -> bool:
    return any(op.type.endswith("_grad")
               for op in program.global_block().ops)


def _node_by_name(op_node, name):
    return next((v for v in op_node.inputs if v.name == name), None)


def _fwd_consumers(var_node):
    """A var's FORWARD consumers: grad ops re-read forward intermediates
    (``X$<slot>`` replay inputs), so a match's exclusive-consumer checks
    must not count them — legality separately proves every grad-side
    consumer belongs to the candidate's own grad chain."""
    return [c for c in var_node.outputs
            if not c.name.endswith("_grad")]


def _out_node_by_name(op_node, name):
    return next((v for v in op_node.outputs if v.name == name), None)


def _grad_consumer(graph, grad_name: str, type_: str, slot: str):
    """The op node of ``type_`` whose ``slot`` input is ``grad_name`` —
    how the backward chain is walked (grad var names are plain
    ``<var>@GRAND`` only for single-consumer vars, which legality
    requires anyway)."""
    for n in graph.op_nodes:
        if n.name != type_:
            continue
        names = n.op.input(slot)
        if names and names[0] == grad_name:
            return n
    return None


# ---------------------------------------------------------------------------
# pattern matchers
# ---------------------------------------------------------------------------

def _match_dense_epilogue(graph, program, fetch_names) -> List[_Candidate]:
    """mul/matmul + elementwise_add(bias) + gelu/relu [+ tagged dropout]
    → ``fused_dense_act``."""
    from ..framework import ir

    cands = []
    for mm_type in ("mul", "matmul"):
        pat = ir.PDPattern()
        mm = pat.new_op(mm_type)
        mm_out = pat.new_var("mm_out").as_intermediate()
        add = pat.new_op("elementwise_add")
        bias = pat.new_var("bias", persistable=True)
        add_out = pat.new_var("add_out").as_intermediate()
        pat.link(mm, mm_out)
        pat.link(mm_out, add)
        pat.link(bias, add)
        pat.link(add, add_out)
        for m in ir.GraphPatternDetector(pat)(graph):
            mm_n, add_n = m[mm], m[add]
            mm_out_n, add_out_n, bias_n = m[mm_out], m[add_out], m[bias]
            # the detector links by edges only: confirm the bias var is
            # the add's Y slot (not its X), and the mm output its X
            if not add_n.op.input("Y") or \
                    add_n.op.input("Y")[0] != bias_n.name or \
                    add_n.op.input("X")[0] != mm_out_n.name:
                continue
            add_fwd = _fwd_consumers(add_out_n)
            if len(add_fwd) != 1 or add_fwd[0].name not in _DENSE_ACTS:
                continue
            act_n = add_fwd[0]
            act_out = act_n.outputs[0]
            cand = _Candidate("dense_epilogue", "matmul",
                              anchor=act_out.name)
            cand.fwd_ops = [mm_n, add_n, act_n]
            cand.internal = [mm_out_n, add_out_n]
            x_node = _node_by_name(mm_n, mm_n.op.input("X")[0])
            w_node = _node_by_name(mm_n, mm_n.op.input("Y")[0])
            if x_node is None or w_node is None or \
                    not w_node.persistable:
                cand.reject_rule = "kernel_unsupported"
                cands.append(cand)
                continue
            ma = mm_n.op.attrs
            if mm_type == "matmul" and (
                    ma.get("transpose_X") or ma.get("transpose_Y") or
                    ma.get("alpha", 1.0) != 1.0):
                cand.reject_rule = "kernel_unsupported"
                cands.append(cand)
                continue
            if mm_type == "mul" and \
                    int(ma.get("y_num_col_dims", 1)) != 1:
                # the fused lowering reshapes W at y_num_col_dims=1
                cand.reject_rule = "kernel_unsupported"
                cands.append(cand)
                continue
            bshape = getattr(getattr(bias_n, "var", None), "shape", None)
            wshape = getattr(getattr(w_node, "var", None), "shape", None)
            if not bshape or len(bshape) != 1 or \
                    not wshape or len(wshape) != 2:
                # the fused lowering is the 2-D-weight [K, N] form with
                # a per-feature bias; anything else is a different op
                cand.reject_rule = "kernel_unsupported"
                cands.append(cand)
                continue
            # the fused lowering broadcasts the bias over the LAST
            # (feature) dim of the 2-D flattened matmul: the add's axis
            # must resolve to the output's last dim and the bias length
            # must be the matmul's N, or the composition is not the
            # same computation
            out_rank = (int(ma.get("x_num_col_dims", 1)) + 1
                        if mm_type == "mul"
                        else len(getattr(getattr(x_node, "var", None),
                                         "shape", None) or ()) or None)
            axis = int(add_n.op.attrs.get("axis", -1))
            if out_rank is None or (axis != -1 and axis != out_rank - 1):
                cand.reject_rule = "kernel_unsupported"
                cands.append(cand)
                continue
            if wshape and bshape[0] not in (-1, None) and \
                    wshape[-1] not in (-1, None) and \
                    bshape[0] != wshape[-1]:
                cand.reject_rule = "kernel_unsupported"
                cands.append(cand)
                continue
            out_node = act_out
            drop_n = None
            # optional exclusive TAGGED dropout tail: the tag makes the
            # fused op regenerate the identical mask (rng is a pure
            # function of step seed + tag), keeping fused-vs-unfused
            # loss parity exact; an untagged dropout stores its mask
            # and cannot be replayed — stays unfused
            act_fwd = _fwd_consumers(act_out)
            if len(act_fwd) == 1 and act_fwd[0].is_op("dropout") and \
                    act_out.name not in fetch_names:
                dn = act_fwd[0]
                if dn.op.attrs.get("seed", 0):
                    drop_n = dn
                    cand.fwd_ops.append(drop_n)
                    cand.internal.append(act_out)
                    out_node = next(
                        (v for v in drop_n.outputs
                         if v.name in drop_n.op.output("Out")), None)
                    mask = next(
                        (v for v in drop_n.outputs
                         if v.name in drop_n.op.output("Mask")), None)
                    if out_node is None:
                        cand.reject_rule = "kernel_unsupported"
                        cands.append(cand)
                        continue
                    if mask is not None:
                        cand.dead_outputs.append(mask)
            cand.anchor = out_node.name
            fused_attrs = {
                "x_num_col_dims": int(ma.get("x_num_col_dims", 1))
                if mm_type == "mul" else -1,
                "bias_axis": int(add_n.op.attrs.get("axis", -1)),
                "act": act_n.name,
                "approximate": bool(
                    act_n.op.attrs.get("approximate", False)),
                "dropout_prob": float(
                    drop_n.op.attrs.get("dropout_prob", 0.0))
                if drop_n is not None else 0.0,
                "seed": int(drop_n.op.attrs.get("seed", 0))
                if drop_n is not None else 0,
                "is_test": bool(drop_n.op.attrs.get("is_test", False))
                if drop_n is not None else False,
                "dropout_implementation":
                    str(drop_n.op.attrs.get("dropout_implementation",
                                            "downgrade_in_infer"))
                if drop_n is not None else "downgrade_in_infer",
            }
            grad_chain = _dense_grad_chain(graph, mm_type, out_node,
                                           drop_n, act_n)
            _finish_candidate(
                graph, program, cand,
                fused_type="fused_dense_act",
                fused_ins={"X": [x_node], "W": [w_node],
                           "Bias": [bias_n]},
                fused_outs={"Out": [out_node]},
                fused_attrs=fused_attrs,
                out_node=out_node, grad_chain=grad_chain,
                grad_ig={"X": (mm_type + "_grad", "IG$X"),
                         "W": (mm_type + "_grad", "IG$Y"),
                         "Bias": ("elementwise_add_grad", "IG$Y")})
            cands.append(cand)
    return cands


def _dense_grad_chain(graph, mm_type, out_node, drop_n, act_n):
    chain = []
    g = out_node.name + "@GRAD"
    if drop_n is not None:
        dg = _grad_consumer(graph, g, "dropout_grad", "OutGrad")
        if dg is None or dg.op.input("Mask"):
            return None         # untagged dropout replays via its mask
        chain.append(dg)
        xg = dg.op.output("XGrad")
        if not xg or not xg[0]:
            return None
        g = xg[0]
    ag_t = act_n.name + "_grad"
    actg = _grad_consumer(graph, g, ag_t, "OG$Out")
    if actg is None or actg.op.attrs.get("__fwd_type__") != act_n.name:
        return None
    chain.append(actg)
    igx = actg.op.output("IG$X")
    if not igx or not igx[0]:
        return None
    addg = _grad_consumer(graph, igx[0], "elementwise_add_grad",
                          "OG$Out")
    if addg is None or \
            addg.op.attrs.get("__fwd_type__") != "elementwise_add":
        return None
    chain.append(addg)
    igx = addg.op.output("IG$X")
    if not igx or not igx[0]:
        return None
    mmg = _grad_consumer(graph, igx[0], mm_type + "_grad", "OG$Out")
    if mmg is None or mmg.op.attrs.get("__fwd_type__") != mm_type:
        return None
    chain.append(mmg)
    return chain


def _match_embedding_layer_norm(graph, program,
                                fetch_names) -> List[_Candidate]:
    """lookup_table [+ elementwise_adds] + layer_norm →
    ``fused_embedding_layer_norm``.

    The BERT-shaped chain is ``emb + pos [+ sent] -> layer_norm``; the
    fused op gathers the rows, applies the adds, and normalizes in one
    op.  The chain side must be each add's X slot with default axis, and
    every collapsed intermediate is legality-checked like any other
    internal var."""
    from ..framework import ir

    cands = []
    for ln_n in graph.ops_of_type("layer_norm"):
        x_in = ir._input_node(ln_n, "X")
        if x_in is None:
            continue
        # walk the producer chain: up to 2 adds over the lookup output
        chain_ops: List = []          # adds, outermost first
        addends: List = []            # external addend var nodes
        internal: List = []
        cur = x_in
        lt_n = None
        for _ in range(3):
            if not cur.inputs:
                break
            p = cur.inputs[0]
            if p.is_op(("lookup_table", "lookup_table_v2")):
                lt_n = p
                internal.append(cur)
                break
            if p.is_op("elementwise_add") and \
                    int(p.op.attrs.get("axis", -1)) == -1:
                xn = _node_by_name(p, p.op.input("X")[0])
                yn = _node_by_name(p, p.op.input("Y")[0])
                if xn is None or yn is None:
                    break
                chain_ops.append(p)
                addends.append(yn)
                internal.append(cur)
                cur = xn
                continue
            break
        if lt_n is None:
            continue
        chain_ops.reverse()
        addends.reverse()
        cand = _Candidate("embedding_layer_norm", "embedding",
                          anchor="")
        y_node = next((v for v in ln_n.outputs
                       if v.name in ln_n.op.output("Y")), None)
        if y_node is None:
            continue
        cand.anchor = y_node.name
        cand.fwd_ops = [lt_n] + chain_ops + [ln_n]
        cand.internal = list(internal)
        ids_n = ir._input_node(lt_n, "Ids")
        w_node = ir._input_node(lt_n, "W")
        scale_n = ir._input_node(ln_n, "Scale")
        bias_n = ir._input_node(ln_n, "Bias")
        la = lt_n.op.attrs
        if ids_n is None or w_node is None or not w_node.persistable:
            cand.reject_rule = "kernel_unsupported"
            cands.append(cand)
            continue
        if la.get("is_sparse") or la.get("is_distributed"):
            # sparse/PS tables lower through the parameter-server path;
            # a fused dense gather would change the distribution story
            cand.reject_rule = "distributed_table"
            cands.append(cand)
            continue
        fused_attrs = {
            "padding_idx": la.get("padding_idx", -1),
            "epsilon": ln_n.op.attrs.get("epsilon", 1e-5),
            "begin_norm_axis": ln_n.op.attrs.get("begin_norm_axis", 1),
        }
        ins = {"Ids": [ids_n], "W": [w_node], "Addends": list(addends)}
        if scale_n is not None:
            ins["Scale"] = [scale_n]
        if bias_n is not None:
            ins["Bias"] = [bias_n]
        outs = {"Out": [y_node]}
        for slot in ("Mean", "Variance"):
            names = ln_n.op.output(slot)
            node = next((v for v in ln_n.outputs
                         if names and v.name in names), None)
            if node is not None:
                outs[slot] = [node]
        grad = _embedding_ln_grad_chain(graph, y_node, ln_n, chain_ops,
                                        lt_n)
        grad_ig = {"W": (lt_n.name + "_grad", "IG$W")}
        if scale_n is not None:
            grad_ig["Scale"] = ("layer_norm_grad", "IG$Scale")
        if bias_n is not None:
            grad_ig["Bias"] = ("layer_norm_grad", "IG$Bias")
        _finish_candidate(
            graph, program, cand,
            fused_type="fused_embedding_layer_norm",
            fused_ins=ins, fused_outs=outs, fused_attrs=fused_attrs,
            out_node=y_node, grad_chain=grad, grad_ig=grad_ig,
            addend_grads=grad[1] if grad else None)
        cands.append(cand)
    return cands


def _embedding_ln_grad_chain(graph, y_node, ln_n, chain_ops, lt_n):
    """(chain grad ops, per-addend grad names) for the embedding+LN
    match, or None.  The add grads' IG$Y outputs carry the external
    addends' gradients, which the fused grad op must keep producing."""
    lt_grad = lt_n.name + "_grad"
    lng = _grad_consumer(graph, y_node.name + "@GRAD",
                         "layer_norm_grad", "OG$Y")
    if lng is None or \
            lng.op.attrs.get("__fwd_type__") != "layer_norm":
        return None
    chain = [lng]
    igx = lng.op.output("IG$X")
    if not igx or not igx[0]:
        return None
    g = igx[0]
    addend_gnames = []
    for add_n in reversed(chain_ops):
        ag = _grad_consumer(graph, g, "elementwise_add_grad", "OG$Out")
        if ag is None or \
                ag.op.attrs.get("__fwd_type__") != "elementwise_add":
            return None
        chain.append(ag)
        igy = ag.op.output("IG$Y")
        addend_gnames.append(igy[0] if igy else "")
        igx = ag.op.output("IG$X")
        if not igx or not igx[0]:
            return None
        g = igx[0]
    ltg = _grad_consumer(graph, g, lt_grad, "OG$Out")
    if ltg is None or \
            ltg.op.attrs.get("__fwd_type__") != lt_n.name:
        return None
    chain.append(ltg)
    addend_gnames.reverse()
    return chain, addend_gnames


# ---------------------------------------------------------------------------
# shared candidate finishing: grads, build closure
# ---------------------------------------------------------------------------

def _finish_candidate(graph, program, cand, *, fused_type, fused_ins,
                      fused_outs, fused_attrs, out_node, grad_chain,
                      grad_ig, addend_grads=None):
    """Attach the grad chain and the build() closure to a
    structurally-matched candidate.  ``grad_ig`` maps fused input slot
    -> (original grad op type, its IG slot) for recovering the external
    gradient names the fused grad op must keep producing."""
    if cand.reject_rule:
        return
    has_grads = _has_grad_ops(program)
    chain = grad_chain
    if isinstance(chain, tuple):
        chain = chain[0]
    if has_grads and not chain:
        cand.reject_rule = "missing_grad_rewrite"
        return
    cand.grad_ops = list(chain or ())
    if addend_grads and chain:
        # every REAL addend gradient must resolve to an output node on
        # one of the add grad ops being removed — an unresolvable name
        # would leave the fused grad op's output outside the graph's
        # dependency edges (topology could order its consumers first)
        adds = [n for n in cand.grad_ops
                if n.name == "elementwise_add_grad"]
        for gname in addend_grads:
            if gname and not any(
                    _out_node_by_name(gop, gname) is not None
                    for gop in adds):
                cand.reject_rule = "missing_grad_rewrite"
                return

    # grad-side internal vars: every @GRAD produced by one chain op and
    # consumed by the next — they vanish with the chain
    grad_internal = []
    removed = {n.id for n in cand.grad_ops}
    for gop in cand.grad_ops:
        for v in gop.outputs:
            if all(c.id in removed for c in v.outputs) and v.outputs:
                grad_internal.append(v)
    cand.grad_internal = grad_internal

    # an op fused from what apply_recompute emitted is itself recomputed
    # work (the executor names it ``pt.rc/...`` by this mark, as it names a
    # fused grad op ``pt.bwd/...`` by the role below); a clone reads stored
    # values through barriers only, so no chain holds both kinds
    marks = {bool(n.op.attrs.get(RECOMPUTED_ATTR)) for n in cand.fwd_ops}
    assert len(marks) == 1, \
        f"{fused_type}: fused from recomputed and first-run ops"
    mark = {RECOMPUTED_ATTR: True} if marks.pop() else {}

    def build(g):
        fused_node = g.create_op_node(fused_type, inputs=fused_ins,
                                      outputs=fused_outs,
                                      attrs=dict(fused_attrs, **mark))
        doomed = list(cand.fwd_ops) + list(cand.internal) + \
            list(cand.dead_outputs)
        if cand.grad_ops:
            # synthesize the fused op's generic-vjp grad desc (the
            # make_grad_ops X$/OG$/IG$ convention) wired to the ORIGINAL
            # external grad names, so downstream accumulation/optimizer
            # ops are untouched
            g_ins = {}
            for slot, nodes in fused_ins.items():
                g_ins["X$" + slot] = list(nodes)
            og_name = out_node.name + "@GRAD"
            og_node = None
            for gop in cand.grad_ops:
                og_node = _node_by_name(gop, og_name)
                if og_node is not None:
                    break
            g_ins["OG$Out"] = [og_node]
            g_outs = {}
            by_type = {}
            for gop in cand.grad_ops:
                by_type.setdefault(gop.name, gop)
            for slot, (gtype, ig_slot) in grad_ig.items():
                gop = by_type.get(gtype)
                if gop is None:
                    continue
                names = gop.op.output(ig_slot)
                if not names or not names[0]:
                    continue
                node = _out_node_by_name(gop, names[0])
                if node is not None:
                    g_outs["IG$" + slot] = [node]
            addend_nodes = []
            if addend_grads:
                adds = [n for n in cand.grad_ops
                        if n.name == "elementwise_add_grad"]
                for gname in addend_grads:
                    node = None
                    for gop in adds:
                        node = _out_node_by_name(gop, gname)
                        if node is not None:
                            break
                    addend_nodes.append(node)
                real = [n for n in addend_nodes if n is not None]
                if real:
                    g_outs["IG$Addends"] = real
            g_attrs = dict(fused_attrs)
            g_attrs["__fwd_type__"] = fused_type
            # a grad op like the ones it replaces (backward.py tags those
            # through _op_role_guard): clone(for_test) prunes by this role
            # and the executor names the op's scope ``pt.bwd/...`` by it
            g_attrs["op_role"] = "backward"
            gnode = g.create_op_node(fused_type + "_grad", inputs=g_ins,
                                     outputs=g_outs, attrs=g_attrs)
            if addend_grads and any(n is None for n in addend_nodes):
                # POSITIONAL alignment with the generic-grad convention:
                # generic_grad_lower returns one gradient per addend in
                # slot order, and the executor zips them against the
                # output NAME list — a stop-gradient addend must keep
                # its '' placeholder or a surviving addend would receive
                # its neighbor's gradient.  Graph edges track only the
                # real nodes (created above); the name list is restored
                # here with the placeholders.
                gnode.op.outputs["IG$Addends"] = [
                    (g or "") for g in addend_grads]
            doomed += list(cand.grad_ops) + list(cand.grad_internal)
        g.safe_remove_nodes(doomed)
        return fused_node

    cand.build = build


# ---------------------------------------------------------------------------
# legality
# ---------------------------------------------------------------------------

#: rules worth a user-facing warning (structural kernel limits are not —
#: a transposed matmul not matching the fused dense op is expected, not a
#: bug)
_WARN_RULES = frozenset({
    "fetched_internal", "multi_consumer", "persistable_internal",
    "subblock_ref", "missing_grad_rewrite", "alias_hazard",
})


def _legality(cand: _Candidate, graph, program, fetch_names,
              alias_pairs) -> Optional[str]:
    """None when the candidate is provably training-safe, else the
    failing rule name."""
    if cand.reject_rule:
        return cand.reject_rule
    fetched = set(fetch_names)
    member_ids = {n.id for n in cand.all_ops()}
    member_ops = {id(n.op) for n in cand.all_ops()}
    for op_n in cand.all_ops():
        if op_n.name.startswith(_COLLECTIVE_PREFIX):
            return "collective"
        if any(isinstance(v, Block)
               for v in op_n.op.attrs.values()):
            return "subblock_op"
    for v in cand.internal + getattr(cand, "grad_internal", []):
        if v.name in fetched:
            return "fetched_internal"
        if v.persistable:
            return "persistable_internal"
        if any(c.id not in member_ids for c in v.outputs):
            return "multi_consumer"
        from ..framework.ir import _referenced_outside_block0
        if _referenced_outside_block0(program, v.name):
            return "subblock_ref"
        # donation/alias interval model (memory planner semantics): an
        # internal var sharing a buffer through an inplace pair whose
        # consumer op SURVIVES the rewrite cannot disappear — the
        # surviving op would extend an interval the fused program no
        # longer expresses.  Pairs whose consumer is itself fused away
        # (e.g. the folded dropout aliasing its own input) are fine.
        for src, out, consumer_op in alias_pairs:
            if v.name in (src, out) and id(consumer_op) not in \
                    member_ops:
                return "alias_hazard"
    for v in cand.dead_outputs:
        if v.name in fetched:
            return "fetched_internal"
        if v.persistable:
            return "persistable_internal"
        if any(c.id not in member_ids for c in v.outputs):
            return "multi_consumer"
    return None


# ---------------------------------------------------------------------------
# main entry
# ---------------------------------------------------------------------------

_MATCHERS = (
    _match_dense_epilogue,
    _match_embedding_layer_norm,
)

# (program fingerprint, fetch tuple, config token, batch) -> program or
# None (None = fusion left the program untouched).  Bounded FIFO: every
# program mutation mints a new fingerprint (verifier-cache discipline).
_RESULT_CACHE: Dict[tuple, Optional[Program]] = {}  # guarded-by: _RESULT_LOCK
_RESULT_CAP = 64
_RESULT_LOCK = threading.Lock()

#: (fingerprint, token) pairs whose rejection warnings already fired
_WARNED: set = set()                    # guarded-by: _RESULT_LOCK


def clear_cache() -> None:
    with _RESULT_LOCK:
        _RESULT_CACHE.clear()
        _WARNED.clear()


def analyze_program(program: Program, fetch_names=(),
                    batch_size: int = 1) -> FusionReport:
    """Report-only mode for ``tools/analyze.py --fusion``: candidates,
    legality verdicts and cost ranks, with NO rewrite applied and no
    caching."""
    fetch_names = tuple(
        f.name if hasattr(f, "name") else f for f in (fetch_names or ()))
    _, report = _fuse(program, fetch_names, batch_size, dry_run=True)
    return report


def fuse_program(program: Program, fetch_names=(),
                 feed_shapes=None) -> Program:
    """The pass entry: returns the fused program (a new Program) when
    any candidate was applied and survived re-verification, else the
    original object.  Cached on (fingerprint, fetch tuple, config
    token, batch) so the executor's slow path re-enters at dict-probe
    cost."""
    from ..flags import get_flags
    if not get_flags("FLAGS_graph_fusion")["FLAGS_graph_fusion"]:
        return program
    fetch_names = tuple(
        f.name if hasattr(f, "name") else f for f in (fetch_names or ()))
    batch = _batch_of(feed_shapes)
    token = config_token()
    key = (program.fingerprint(), fetch_names, token, batch)
    with _RESULT_LOCK:
        if key in _RESULT_CACHE:
            cached = _RESULT_CACHE[key]
            return cached if cached is not None else program
    fused, report = _fuse(program, fetch_names, batch, dry_run=False)
    result = fused if fused is not program else None
    with _RESULT_LOCK:
        # concurrent first compiles of the same program can race here:
        # only the insert winner counts decisions and warns, so the
        # counters stay once-per-(program, config) exact
        won = key not in _RESULT_CACHE
        if won:
            if len(_RESULT_CACHE) >= _RESULT_CAP:
                _RESULT_CACHE.pop(next(iter(_RESULT_CACHE)))
            _RESULT_CACHE[key] = result
        else:
            cached = _RESULT_CACHE[key]
        warn_key = (program.fingerprint(), token)
        do_warn = won and warn_key not in _WARNED
        if do_warn:
            if len(_WARNED) >= 4 * _RESULT_CAP:
                # bounded like the result cache it shadows: a long-lived
                # service minting programs must not leak dedup keys; a
                # rare repeat warning after the reset is harmless
                _WARNED.clear()
            _WARNED.add(warn_key)
    if not won:
        return cached if cached is not None else program
    _count_decisions(report)
    if do_warn:
        _warn_rejections(report)
    return fused


def _batch_of(feed_shapes) -> int:
    if feed_shapes:
        for shape in (feed_shapes.values()
                      if isinstance(feed_shapes, dict) else feed_shapes):
            if shape:
                return max(int(shape[0]), 1)
    return 1


def _warn_rejections(report: FusionReport) -> None:
    from .verifier import Diagnostic
    diags = [
        Diagnostic("fusion_reject", "warning",
                   f"fusion candidate {d.pattern!r} at {d.anchor!r} "
                   f"rejected by legality rule {d.rule!r}",
                   var=d.anchor,
                   fix_hint="see README 'Graph fusion' legality table; "
                            "tools/analyze.py --fusion shows the full "
                            "candidate report")
        for d in report.decisions
        if d.verdict == "rejected" and d.rule in _WARN_RULES]
    if diags:
        import warnings

        from .. import debugger
        warnings.warn("graph fusion rejections:\n"
                      + debugger.format_diagnostics(diags), stacklevel=3)


def _fuse(program: Program, fetch_names, batch: int,
          dry_run: bool) -> Tuple[Program, FusionReport]:
    from ..flags import get_flags
    from ..framework import ir
    from . import cost as _cost
    from . import verifier as _verifier

    threshold = float(get_flags("FLAGS_fusion_rank_threshold")[
        "FLAGS_fusion_rank_threshold"])

    report = FusionReport()
    with _monitor.TRACER.span("fusion.plan", "compile",
                              fetches=len(fetch_names)):
        graph = ir.Graph(program)
        candidates: List[_Candidate] = []
        for matcher in _MATCHERS:
            candidates.extend(matcher(graph, program, fetch_names))
        if not candidates:
            return program, report

        # verify BEFORE the pass: fusion never applies to a broken
        # program, and the pre-fingerprint anchors the invariance check
        pre = _verifier.verify_program(program, fetch_names)
        if not pre.ok:
            return program, report
        pre_fp = pre.collective_fingerprint

        plan = _cost.plan_cost(program, fetch_names, batch_size=batch)
        fshare = plan.share()
        btotal = float(plan.bytes) or 1.0
        bshare = {c: b / btotal
                  for c, b in plan.per_class_bytes.items()}
        alias_graph = ir.get_pass("buffer_shared_inplace_pass").apply(
            ir.Graph(program))
        # (src, out, consumer Operator): the pair plus the op that would
        # compute in place — legality compares it against candidate
        # membership (Operator objects are shared across Graph builds)
        alias_pairs = []
        for src, out in alias_graph.attrs.get("inplace_pairs", []):
            consumer = next(
                (op for op in program.global_block().ops
                 if src in op.input_arg_names()
                 and out in op.output_arg_names()), None)
            if consumer is not None:
                alias_pairs.append((src, out, consumer))

        def rank_of(c):
            return max(fshare.get(c.op_class, 0.0),
                       bshare.get(c.op_class, 0.0))

        applied: List[_Candidate] = []
        taken: set = set()
        for cand in sorted(candidates, key=rank_of, reverse=True):
            rank = rank_of(cand)
            dec = FusionDecision(cand.pattern, cand.anchor,
                                 verdict="", rank=rank)
            report.decisions.append(dec)
            rule = _legality(cand, graph, program, fetch_names,
                             alias_pairs)
            if rule is not None:
                dec.verdict, dec.rule = "rejected", rule
                continue
            if any(n.id in taken for n in cand.all_ops()):
                dec.verdict = "overlapped"
                continue
            if rank < threshold:
                dec.verdict = "ranked_out"
                continue
            dec.verdict = "applied"
            taken.update(n.id for n in cand.all_ops())
            applied.append(cand)

        if dry_run or not applied:
            report.applied = len(applied) if dry_run else 0
            if not dry_run:
                program._attrs["fusion"] = report.as_dict()
            return program, report

        for cand in applied:
            cand.build(graph)
        fused = graph.to_program()
        report.applied = len(applied)

        # verify AFTER the pass: the fused program must be clean and its
        # collective fingerprint unchanged (fusion never touches
        # collectives) — anything else rolls the whole rewrite back
        post = _verifier.verify_program(fused, fetch_names)
        fp_ok = post.collective_fingerprint == pre_fp
        report.collective_fingerprint_ok = fp_ok
        if not post.ok or not fp_ok:
            for dec in report.decisions:
                if dec.verdict == "applied":
                    dec.verdict = "verify_failed"
            report.applied = 0
            import warnings
            warnings.warn(
                "graph fusion rolled back: the fused program "
                + ("failed verification" if not post.ok
                   else "changed the collective fingerprint")
                + " — running unfused", stacklevel=3)
            program._attrs["fusion"] = report.as_dict()
            return program, report
        fused._attrs["fusion"] = report.as_dict()
    return fused, report


def _count_decisions(report: FusionReport) -> None:
    """Final-verdict counting — called ONLY by ``fuse_program`` on a
    result-cache insert win, so decisions count once per
    (program, config) even under concurrent first compiles, and the
    report-only ``analyze_program`` path never skews the counters."""
    for dec in report.decisions:
        _CAND_CTR.inc(1, pattern=dec.pattern, verdict=dec.verdict)
