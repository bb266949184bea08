"""Graph IR + pass infrastructure (ref SURVEY §2.2, ``paddle/fluid/framework/ir/``).

TPU-native role: in the reference, graph passes are the *primary* optimizer —
fusion passes stitch kernels together because the runtime dispatches one CUDA
kernel per op.  Under XLA the whole block compiles as one computation and the
compiler does the fusing, so these passes are (a) program-level canonicalizers
that produce better-shaped traces (e.g. folding conv+BN at inference time
eliminates the BN params entirely), (b) the analysis substrate (liveness,
inplace pairing) that informs buffer donation, and (c) the user-extensible
rewrite framework (``Pass``/``PassRegistry``/``PassBuilder``) the reference
exposes via ``ir::Pass`` (``ir/pass.h``) and ``BuildStrategy``.

Components mirrored (reference file:line cited per class):
- ``Graph``/``Node``       ← ``ir/graph.{h,cc}``, ``ir/node.{h,cc}``
- ``topology_sort``        ← ``ir/graph_helper.cc TopologySortOperations``
- ``Pass``/``PassRegistry``← ``ir/pass.{h,cc}``
- ``PassBuilder``          ← ``ir/pass_builder.{h,cc}``
- ``PDNode``/``PDPattern``/``GraphPatternDetector``
                           ← ``ir/graph_pattern_detector.{h,cc}``
- fusion passes            ← ``ir/fc_fuse_pass.cc``,
                             ``ir/conv_bn_fuse_pass.cc``,
                             ``ir/fuse_elewise_add_act_pass.cc``
- ``reference_count_pass`` / ``buffer_shared_inplace_pass`` analogs
                           ← ``ir/memory_optimize_pass/``
- ``graph_viz_pass`` (DOT) ← ``ir/graph_viz_pass.cc``
- ``graph_to_program``     ← ``ir/graph_to_program_pass.cc``
"""

from __future__ import annotations

import itertools
from typing import Callable, Dict, List, Optional, Sequence

from .core import Operator, Program, Variable

# ---------------------------------------------------------------------------
# Graph / Node
# ---------------------------------------------------------------------------

_node_ids = itertools.count()


class Node:
    """Op or var node (ref ``ir/node.h`` Node::Type::kOperation/kVariable).

    Var nodes are SSA: every write to a name creates a fresh var node, so a
    pattern match never confuses a value with its later overwrite (the
    reference gets this from per-definition ``VarHandle`` versions).
    """

    def __init__(self, kind: str, name: str, op: Optional[Operator] = None,
                 var: Optional[Variable] = None):
        self.id = next(_node_ids)
        self.kind = kind                    # "op" | "var"
        self.name = name                    # op type, or var name
        self.op = op                        # Operator (op nodes)
        self.var = var                      # Variable metadata (var nodes)
        self.inputs: List[Node] = []
        self.outputs: List[Node] = []

    def is_op(self, type=None) -> bool:
        if self.kind != "op" or type is None:
            return self.kind == "op"
        if isinstance(type, (tuple, list, set, frozenset)):
            return self.name in type
        return self.name == type

    def is_var(self) -> bool:
        return self.kind == "var"

    @property
    def persistable(self) -> bool:
        return bool(self.var is not None and self.var.persistable)

    def __repr__(self):
        return f"Node#{self.id}({self.kind}:{self.name})"


class Graph:
    """Dependency graph of one block (ref ``ir/graph.h`` ir::Graph).

    Built from block 0 of a Program; ops in other blocks (control-flow
    sub-blocks) ride along opaquely through their Block-valued attrs, exactly
    as the reference keeps sub-graphs inside the op's attribute.
    """

    def __init__(self, program: Program, block_idx: int = 0):
        self.program = program
        self.block_idx = block_idx
        self.attrs: Dict[str, object] = {}
        self.op_nodes: List[Node] = []      # in original program order
        self.var_nodes: List[Node] = []
        block = program.blocks[block_idx]
        latest: Dict[str, Node] = {}        # name -> current SSA def

        def var_meta(name):
            return block.vars.get(name) or (
                block.var(name) if block.has_var(name) else None)

        for op in block.ops:
            op_node = Node("op", op.type, op=op)
            self.op_nodes.append(op_node)
            for name in op.input_arg_names():
                if not name:
                    continue
                v = latest.get(name)
                if v is None:
                    v = Node("var", name, var=var_meta(name))
                    latest[name] = v
                    self.var_nodes.append(v)
                op_node.inputs.append(v)
                v.outputs.append(op_node)
            for name in op.output_arg_names():
                if not name:
                    continue
                v = Node("var", name, var=var_meta(name))
                latest[name] = v
                self.var_nodes.append(v)
                op_node.outputs.append(v)
                v.inputs.append(op_node)

    # -- queries -------------------------------------------------------------
    def all_op_nodes(self) -> List[Node]:
        return list(self.op_nodes)

    def all_var_nodes(self) -> List[Node]:
        return list(self.var_nodes)

    def ops_of_type(self, type: str) -> List[Node]:
        return [n for n in self.op_nodes if n.name == type]

    def num_nodes(self) -> int:
        return len(self.op_nodes) + len(self.var_nodes)

    def topology_sort(self) -> List[Node]:
        """Op nodes in dependency order (ref graph_helper.cc
        TopologySortOperations).  Program order is already topological for a
        straight-line block, but passes may have appended nodes out of order."""
        indeg: Dict[int, int] = {}
        succ: Dict[int, List[Node]] = {}
        for op in self.op_nodes:
            indeg.setdefault(op.id, 0)
            for v in op.outputs:
                for consumer in v.outputs:
                    succ.setdefault(op.id, []).append(consumer)
                    indeg[consumer.id] = indeg.get(consumer.id, 0) + 1
        from collections import deque
        ready = deque(op for op in self.op_nodes if indeg[op.id] == 0)
        order: List[Node] = []
        while ready:
            op = ready.popleft()
            order.append(op)
            for consumer in succ.get(op.id, []):
                indeg[consumer.id] -= 1
                if indeg[consumer.id] == 0:
                    ready.append(consumer)
        if len(order) != len(self.op_nodes):
            raise RuntimeError("graph has a cycle; pass produced invalid IR")
        return order

    # -- mutation (ref graph.h CreateOpNode/CreateVarNode/RemoveNode) --------
    def create_op_node(self, type: str, inputs: Dict[str, List[Node]],
                       outputs: Dict[str, List[Node]],
                       attrs: Optional[dict] = None) -> Node:
        block = self.program.blocks[self.block_idx]
        op = Operator(block, type, attrs=attrs or {})
        op.inputs = {slot: [v.name for v in vs] for slot, vs in inputs.items()}
        op.outputs = {slot: [v.name for v in vs]
                      for slot, vs in outputs.items()}
        node = Node("op", type, op=op)
        for vs in inputs.values():
            for v in vs:
                node.inputs.append(v)
                v.outputs.append(node)
        for vs in outputs.values():
            for v in vs:
                node.outputs.append(v)
                v.inputs.append(node)
        self.op_nodes.append(node)
        return node

    def create_var_node(self, name: str, shape=None, dtype=None,
                        persistable: bool = False) -> Node:
        block = self.program.blocks[self.block_idx]
        var = block.create_var(name=name, shape=shape, dtype=dtype,
                               persistable=persistable)
        node = Node("var", var.name, var=var)
        self.var_nodes.append(node)
        return node

    def safe_remove_nodes(self, nodes: Sequence[Node]) -> None:
        doomed = {n.id for n in nodes}
        for n in nodes:
            if n.kind == "op":
                self.op_nodes = [o for o in self.op_nodes if o.id != n.id]
            else:
                self.var_nodes = [v for v in self.var_nodes if v.id != n.id]
        for n in itertools.chain(self.op_nodes, self.var_nodes):
            n.inputs = [i for i in n.inputs if i.id not in doomed]
            n.outputs = [o for o in n.outputs if o.id not in doomed]

    # -- export (ref ir/graph_to_program_pass.cc) ----------------------------
    def to_program(self) -> Program:
        """Rebuild a Program: block 0 from this graph (topo order), other
        blocks copied from the source so Block-valued attrs stay valid."""
        src = self.program
        out = src.clone()
        blk = out.global_block()
        # vars already cloned; add any pass-created vars
        for v in self.var_nodes:
            if v.var is not None and v.name not in blk.vars:
                blk.create_var(name=v.name, shape=v.var.shape,
                               dtype=v.var.dtype,
                               persistable=v.var.persistable)
        blk.ops = []
        for op_node in self.topology_sort():
            op = op_node.op
            attrs = {}
            for k, val in op.attrs.items():
                # remap sub-block refs into the cloned program
                from .core import Block
                attrs[k] = out.blocks[val.idx] if isinstance(val, Block) \
                    else val
            nop = Operator(blk, op.type, None, None, attrs)
            nop.inputs = {k: list(v) for k, v in op.inputs.items()}
            nop.outputs = {k: list(v) for k, v in op.outputs.items()}
            blk.ops.append(nop)
        # sub-block rewrites recorded by passes (the Graph itself models
        # only one block): dead_op_eliminate stores the per-sub-block
        # dead op indices here and materialization applies them
        sub_dead = self.attrs.get("dead_subblock_ops")
        if sub_dead:
            prune_subblock_ops(out, sub_dead)
        out._bump_version()
        return out

    def apply_to_program(self) -> Program:
        """Write the rewritten block 0 back INTO the source program object.

        For train-time passes that must run between model build and
        ``minimize()``: append_backward goes to ``loss.block.program`` but
        ``apply_gradients`` targets ``default_main_program()`` — a cloned
        program from :meth:`to_program` silently splits the two (grads in
        the clone, optimizer ops in the default → parameters never
        update).  Mutating the original keeps every later stage on one
        program."""
        rebuilt = self.to_program()
        src = self.program
        blk = src.global_block()
        new_blk = rebuilt.global_block()
        for name, v in new_blk.vars.items():
            if name not in blk.vars:
                blk.vars[name] = v
                v.block = blk
        # retarget sub-block attrs back at the source program's blocks
        from .core import Block
        ops = []
        referenced = set()
        for op in new_blk.ops:
            for k, val in op.attrs.items():
                if isinstance(val, Block):
                    op.attrs[k] = src.blocks[val.idx]
            op.block = blk
            ops.append(op)
            referenced.update(op.input_arg_names())
            referenced.update(op.output_arg_names())
        blk.ops = ops
        # drop vars the rewrite orphaned (e.g. the fused-away conv outputs)
        # — phantom unwritten non-persistables would confuse later Graph
        # builds / serialization; persistables and parameters stay (their
        # values live in the scope)
        for name in list(blk.vars):
            v = blk.vars[name]
            if name not in referenced and not v.persistable and \
                    not getattr(v, "is_parameter", False):
                del blk.vars[name]
        # sub-block rewrites (see to_program) apply to the source too
        sub_dead = self.attrs.get("dead_subblock_ops")
        if sub_dead:
            prune_subblock_ops(src, sub_dead)
        src._bump_version()
        return src


# ---------------------------------------------------------------------------
# Pass framework (ref ir/pass.h, ir/pass_builder.h)
# ---------------------------------------------------------------------------

class Pass:
    """Base pass: override ``apply_impl(graph) -> graph``.

    The ``protected`` attr (set of var names) marks values an enclosing
    executor will fetch: rewrites must not remove their defining ops (the
    reference marks fetched vars in the graph before applying passes —
    parallel_executor.cc keeps FetchOpHandles as graph roots)."""

    name = "pass"

    def __init__(self, **attrs):
        self.attrs = attrs

    def protected_vars(self) -> frozenset:
        return frozenset(self.get("protected") or ())

    def set(self, key, value):
        self.attrs[key] = value
        return self

    def get(self, key, default=None):
        return self.attrs.get(key, default)

    def apply(self, graph: Graph) -> Graph:
        out = self.apply_impl(graph)
        return graph if out is None else out

    def apply_impl(self, graph: Graph) -> Optional[Graph]:
        raise NotImplementedError


_PASS_REGISTRY: Dict[str, Callable[..., Pass]] = {}


def register_pass(name: str):
    """``REGISTER_PASS`` (ref ir/pass.h:195)."""
    def deco(cls):
        cls.name = name
        _PASS_REGISTRY[name] = cls
        return cls
    return deco


def get_pass(name: str, **attrs) -> Pass:
    if name not in _PASS_REGISTRY:
        raise KeyError(f"no pass registered under {name!r}; "
                       f"have {sorted(_PASS_REGISTRY)}")
    return _PASS_REGISTRY[name](**attrs)


def registered_passes() -> List[str]:
    return sorted(_PASS_REGISTRY)


class PassBuilder:
    """Ordered pass pipeline (ref ir/pass_builder.h PassBuilder)."""

    def __init__(self, names: Optional[Sequence[str]] = None):
        self._passes: List[Pass] = [get_pass(n) for n in (names or [])]

    def append_pass(self, name: str, **attrs) -> Pass:
        p = get_pass(name, **attrs)
        self._passes.append(p)
        return p

    def insert_pass(self, idx: int, name: str, **attrs) -> Pass:
        p = get_pass(name, **attrs)
        self._passes.insert(idx, p)
        return p

    def remove_pass(self, idx: int) -> None:
        del self._passes[idx]

    def all_passes(self) -> List[Pass]:
        return list(self._passes)

    def apply(self, graph: Graph) -> Graph:
        for p in self._passes:
            graph = p.apply(graph)
        return graph


def apply_passes(program: Program, names: Sequence[str],
                 **attrs) -> Program:
    """Convenience: Program → Graph → passes → Program."""
    graph = Graph(program)
    for n in names:
        graph = get_pass(n, **attrs).apply(graph)
    return graph.to_program()


# ---------------------------------------------------------------------------
# Pattern detector (ref ir/graph_pattern_detector.{h,cc})
# ---------------------------------------------------------------------------

class PDNode:
    """One slot of a pattern: predicate + role flags (ref PDNode)."""

    def __init__(self, pattern: "PDPattern", name: str, kind: str,
                 op_type: Optional[str] = None,
                 predicate: Optional[Callable[[Node], bool]] = None,
                 persistable: Optional[bool] = None):
        self.pattern = pattern
        self.pd_name = name
        self.kind = kind
        self.op_type = op_type
        self.predicate = predicate
        self.persistable = persistable
        self.intermediate = False

    def as_intermediate(self) -> "PDNode":
        """Matched nodes are consumed by the rewrite (removed)."""
        self.intermediate = True
        return self

    def matches(self, node: Node) -> bool:
        if node.kind != self.kind:
            return False
        if self.op_type is not None and node.name != self.op_type:
            return False
        if self.persistable is not None and node.kind == "var" and \
                node.persistable != self.persistable:
            return False
        return self.predicate is None or self.predicate(node)


class PDPattern:
    """A small graph of PDNodes with edges (ref PDPattern)."""

    def __init__(self):
        self.nodes: List[PDNode] = []
        self.edges: List[tuple] = []        # (from PDNode, to PDNode)

    def new_op(self, op_type: str, name: Optional[str] = None,
               predicate=None) -> PDNode:
        n = PDNode(self, name or op_type, "op", op_type=op_type,
                   predicate=predicate)
        self.nodes.append(n)
        return n

    def new_var(self, name: str, persistable: Optional[bool] = None,
                predicate=None) -> PDNode:
        n = PDNode(self, name, "var", predicate=predicate,
                   persistable=persistable)
        self.nodes.append(n)
        return n

    def link(self, frm: PDNode, to: PDNode) -> None:
        self.edges.append((frm, to))


class GraphPatternDetector:
    """Backtracking subgraph matcher.  The reference builds candidate sets
    per PDNode then prunes by edge consistency
    (graph_pattern_detector.cc MarkPDNodesInGraph/DetectPatterns); pattern
    sizes are tiny (<10 nodes) so plain DFS with injectivity is equivalent
    and simpler."""

    def __init__(self, pattern: PDPattern):
        self.pattern = pattern

    def __call__(self, graph: Graph) -> List[Dict[PDNode, Node]]:
        pat = self.pattern
        all_nodes = graph.all_op_nodes() + graph.all_var_nodes()
        candidates = {pd: [n for n in all_nodes if pd.matches(n)]
                      for pd in pat.nodes}
        order = sorted(pat.nodes, key=lambda pd: len(candidates[pd]))
        matches: List[Dict[PDNode, Node]] = []
        used_ids = set()                    # no overlapping rewrites

        def edges_ok(assign: Dict[PDNode, Node]) -> bool:
            for frm, to in pat.edges:
                if frm in assign and to in assign:
                    if assign[to] not in assign[frm].outputs:
                        return False
            return True

        def dfs(i: int, assign: Dict[PDNode, Node]):
            if i == len(order):
                if not any(n.id in used_ids for n in assign.values()):
                    matches.append(dict(assign))
                    used_ids.update(
                        n.id for pd, n in assign.items()
                        if pd.intermediate or pd.kind == "op")
                return
            pd = order[i]
            taken = {n.id for n in assign.values()}
            for cand in candidates[pd]:
                if cand.id in taken:
                    continue
                assign[pd] = cand
                if edges_ok(assign):
                    dfs(i + 1, assign)
                del assign[pd]

        dfs(0, {})
        return matches


# ---------------------------------------------------------------------------
# Fusion passes
# ---------------------------------------------------------------------------

@register_pass("fc_fuse_pass")
class FCFusePass(Pass):
    """mul(X,W) + elementwise_add(·,b) [+ act] → one ``fc`` op
    (ref ir/fc_fuse_pass.cc).  Under XLA the fusion itself is free; the win
    is a canonical single node for later passes (quant, viz, stats)."""

    ACTS = ("relu", "tanh", "sigmoid", "gelu")

    def apply_impl(self, graph: Graph) -> Graph:
        pat = PDPattern()
        mul = pat.new_op("mul")
        mul_out = pat.new_var("mul_out").as_intermediate()
        add = pat.new_op("elementwise_add")
        bias = pat.new_var("bias", persistable=True)
        add_out = pat.new_var("add_out")
        pat.link(mul, mul_out)
        pat.link(mul_out, add)
        pat.link(bias, add)
        pat.link(add, add_out)
        protected = self.protected_vars()
        count = 0
        for m in GraphPatternDetector(pat)(graph):
            # mul_out must feed ONLY the add (no other consumer may lose
            # it), and must not be a fetch target
            if len(m[mul_out].outputs) != 1 or \
                    m[mul_out].name in protected:
                continue
            mul_op, add_op = m[mul], m[add]
            # bind operands by SLOT, not by persistability: fc is X@W, so
            # Input must be mul's X and W its Y (which must be a weight)
            by_name = {v.name: v for v in mul_op.inputs}
            x_name = mul_op.op.input("X")[0]
            w_name = mul_op.op.input("Y")[0]
            x_node, w_node = by_name.get(x_name), by_name.get(w_name)
            if x_node is None or w_node is None or not w_node.persistable:
                continue
            out_node = m[add_out]
            act_type = ""
            doomed = [mul_op, add_op, m[mul_out]]
            # optional activation directly consuming add_out
            consumers = out_node.outputs
            if len(consumers) == 1 and consumers[0].is_op() and \
                    consumers[0].name in self.ACTS and \
                    out_node.name not in protected:
                act_op = consumers[0]
                act_type = act_op.name
                doomed += [act_op, out_node]
                out_node = act_op.outputs[0]
            graph.create_op_node(
                "fc",
                inputs={"Input": [x_node], "W": [w_node],
                        "Bias": [m[bias]]},
                outputs={"Out": [out_node]},
                attrs={"in_num_col_dims":
                       mul_op.op.attrs.get("x_num_col_dims", 1),
                       "activation_type": act_type})
            graph.safe_remove_nodes(doomed)
            count += 1
        graph.attrs["fc_fuse_count"] = count
        return graph


@register_pass("attention_fuse_pass")
class AttentionFusePass(Pass):
    """matmul(Q,Kᵀ,α) [+ mask add] → softmax → matmul(·,V)  ⇒  one
    ``flash_attention`` op.

    TPU-native pass with no reference counterpart: saved inference
    artifacts built with the dense attention recipe (ref
    dist_transformer.py scaled_dot_product_attention — materializes
    [b,h,T,T] scores) get rewritten onto the Pallas flash kernel, which
    wins from T≈1024 and is the only runnable path beyond ~8k
    (models/transformer.py attn_impl="auto" makes the same call at build
    time; this pass makes it at LOAD time for existing artifacts).
    Set ``min_seq_len`` (default 1024) to control the crossover.

    Matched shapes of the chain:
    - bidirectional self-attention (no mask add);
    - masked attention — the additive [*,*,Tq,Tk] bias rides into the
      kernel's Bias input;
    - CAUSAL decoder self-attention: when ``scope=`` is given (the
      predictor passes its loaded scope) and the bias is a persistable
      frozen causal mask (zeros on/below the diagonal, large-negative
      above), the mask is dropped and the op gets ``causal=True`` — the
      kernel then skips the masked key blocks outright (~2× at long T)
      instead of reading a [T,T] bias;
    - cross-attention (decoder→encoder): Tq and Tk differ; the kernel is
      rectangular, so the same pattern fuses with no extra handling."""

    @staticmethod
    def _is_frozen_causal_mask(arr) -> bool:
        """True for [*..,T,T] masks with ~0 on/below the diagonal and a
        large negative constant strictly above (the dist_transformer.py
        recipe freezes exactly this into decoder artifacts)."""
        import numpy as np
        if arr.ndim < 2 or arr.shape[-1] != arr.shape[-2]:
            return False
        t = arr.shape[-1]
        m = arr.reshape(-1, t, t)
        if not np.allclose(m, m[0], atol=1e-6):
            return False       # must be the same mask for every batch/head
        low = np.tril(m[0])
        up = m[0][np.triu_indices(t, k=1)]
        return (np.allclose(low, 0.0, atol=1e-6)
                and up.size > 0 and bool((up <= -1e4).all()))

    def apply_impl(self, graph: Graph) -> Graph:
        min_seq = int(self.get("min_seq_len", 1024) or 0)
        protected = self.protected_vars()
        count = 0
        for mm1 in list(graph.ops_of_type("matmul")):
            if mm1 not in graph.op_nodes:
                continue
            a = mm1.op.attrs
            if not a.get("transpose_Y") or a.get("transpose_X"):
                continue
            scores = mm1.outputs[0] if mm1.outputs else None
            if scores is None or len(scores.outputs) != 1 or \
                    scores.name in protected:
                continue
            # optional additive mask between scores and softmax
            nxt = scores.outputs[0]
            bias_node, doomed_mask = None, []
            if nxt.is_op("elementwise_add"):
                add = nxt
                m_out = add.outputs[0] if add.outputs else None
                if m_out is None or len(m_out.outputs) != 1 or \
                        m_out.name in protected:
                    continue
                by_name = {v.name: v for v in add.inputs}
                x_name = add.op.input("X")[0]
                y_name = add.op.input("Y")[0]
                if by_name.get(x_name) is not scores:
                    continue
                bias_node = by_name.get(y_name)
                doomed_mask = [add, m_out]
                nxt = m_out.outputs[0]
            if not nxt.is_op("softmax"):
                continue
            sm = nxt
            # flash_attention normalizes over the last (key) axis of
            # rank-4 [B,H,T,D] operands; a softmax over any other axis
            # must stay on the dense path (the lowering honors axis —
            # ops/nn_ops.py softmax)
            sm_axis = sm.op.attrs.get("axis", -1)
            probs = sm.outputs[0] if sm.outputs else None
            if probs is None or len(probs.outputs) != 1 or \
                    probs.name in protected:
                continue
            mm2 = probs.outputs[0]
            if not mm2.is_op("matmul"):
                continue
            a2 = mm2.op.attrs
            if a2.get("transpose_X") or a2.get("transpose_Y") or \
                    a2.get("alpha", 1.0) != 1.0:
                continue
            if mm2.op.input("X")[0] != probs.name:
                continue
            # bind Q, K, V var nodes by slot
            q_node = next((v for v in mm1.inputs
                           if v.name == mm1.op.input("X")[0]), None)
            k_node = next((v for v in mm1.inputs
                           if v.name == mm1.op.input("Y")[0]), None)
            v_node = next((v for v in mm2.inputs
                           if v.name == mm2.op.input("Y")[0]), None)
            if q_node is None or k_node is None or v_node is None:
                continue
            # crossover gate: flash wins from ~1k tokens; shorter
            # sequences keep XLA's dense attention
            shape = getattr(q_node.var, "shape", None)
            if shape is None or len(shape) != 4 or shape[-2] is None:
                continue
            if shape[-2] != -1 and shape[-2] < min_seq:
                continue
            # operand-rank + softmax-axis gates: the kernel is rank-4,
            # last-axis only
            if any(len(getattr(n.var, "shape", None) or ()) != 4
                   for n in (k_node, v_node)):
                continue
            scores_rank = len(getattr(scores.var, "shape", None) or shape)
            if sm_axis not in (-1, scores_rank - 1):
                continue
            causal = False
            if bias_node is not None:
                # the flash kernel takes [*,*,Tq,Tk]-shaped biases; the
                # [B,1,1,Tk] padding-mask form would need an explicit
                # broadcast — keep those on the dense path
                bshape = getattr(bias_node.var, "shape", None)
                if bshape is None or len(bshape) < 2 or \
                        bshape[-2] in (1, None):
                    continue
                # a frozen causal mask becomes causal=True with no Bias:
                # the kernel skips masked key blocks instead of reading
                # a [T,T] tensor of -1e9s
                scope = self.get("scope")
                if scope is not None and \
                        getattr(bias_node.var, "persistable", False) and \
                        not bias_node.inputs:
                    try:
                        val = scope.find_var(bias_node.name)
                    except Exception:
                        val = None
                    if val is not None:
                        import numpy as np
                        if self._is_frozen_causal_mask(np.asarray(val)):
                            causal = True
            inputs = {"Q": [q_node], "K": [k_node], "V": [v_node]}
            if bias_node is not None and not causal:
                inputs["Bias"] = [bias_node]
            elif causal and len(bias_node.outputs) == 1 and \
                    bias_node.name not in protected:
                # mask var fed only this add: drop the orphan node too
                doomed_mask.append(bias_node)
            out_node = mm2.outputs[0]
            # the op's second output, as layers.flash_attention makes it
            lse_node = graph.create_var_node(
                out_node.name + ".lse", shape=tuple(shape[:3]),
                dtype="float32")
            lse_node.var.stop_gradient = True
            graph.create_op_node(
                "flash_attention", inputs=inputs,
                outputs={"Out": [out_node], "Lse": [lse_node]},
                attrs={"sm_scale": float(a.get("alpha", 1.0)),
                       "causal": causal})
            graph.safe_remove_nodes(
                [mm1, scores, sm, probs, mm2] + doomed_mask)
            count += 1
        graph.attrs["attention_fuse_count"] = count
        return graph


@register_pass("fuse_elewise_add_act_pass")
class FuseElewiseAddActPass(Pass):
    """elementwise_add + activation → fused_elemwise_activation
    (ref ir/fuse_elewise_add_act_pass.cc)."""

    ACTS = ("relu", "scale", "tanh", "sigmoid", "gelu")

    def apply_impl(self, graph: Graph) -> Graph:
        protected = self.protected_vars()
        count = 0
        for add in list(graph.ops_of_type("elementwise_add")):
            if add not in graph.op_nodes:
                continue
            out = add.outputs[0] if add.outputs else None
            if out is None or len(out.outputs) != 1 or \
                    out.name in protected:
                continue
            act = out.outputs[0]
            if not act.is_op() or act.name not in self.ACTS:
                continue
            # bind by slot: elementwise broadcast is X-major
            by_name = {v.name: v for v in add.inputs}
            try:
                xs = [by_name[add.op.input("X")[0]],
                      by_name[add.op.input("Y")[0]]]
            except (KeyError, IndexError):
                continue
            extra = {}
            if act.name == "scale":
                extra = {"scale": act.op.attrs.get("scale", 1.0),
                         "bias": act.op.attrs.get("bias", 0.0),
                         "bias_after_scale":
                         act.op.attrs.get("bias_after_scale", True)}
            graph.create_op_node(
                "fused_elemwise_activation",
                inputs={"X": [xs[0]], "Y": [xs[1]]},
                outputs={"Out": [act.outputs[0]]},
                attrs={"functor_list": ["elementwise_add", act.name],
                       "axis": add.op.attrs.get("axis", -1), **extra})
            graph.safe_remove_nodes([add, act, out])
            count += 1
        graph.attrs["fuse_elewise_add_act_count"] = count
        return graph


@register_pass("repeated_fc_relu_fuse_pass")
class RepeatedFCReluFusePass(Pass):
    """Chains of fc(act=relu) → one ``fusion_repeated_fc_relu``
    (ref ir/fc_gru_fuse... family; fused op:
    fused/fusion_repeated_fc_relu_op.cc).  Runs after fc_fuse_pass, which
    produces the canonical fc nodes this pass chains."""

    def apply_impl(self, graph: Graph) -> Graph:
        protected = self.protected_vars()
        count = 0
        consumed = set()
        for fc in list(graph.ops_of_type("fc")):
            if fc not in graph.op_nodes or fc in consumed:
                continue
            if fc.op.attrs.get("activation_type") != "relu":
                continue
            # only chain HEADS: input not itself produced by a relu-fc
            x_node = next((v for v in fc.inputs
                           if v.name == fc.op.input("Input")[0]), None)
            if x_node is None:
                continue
            if x_node.inputs and x_node.inputs[0].is_op("fc") and \
                    x_node.inputs[0].op.attrs.get("activation_type") == \
                    "relu":
                continue
            chain = [fc]
            while True:
                out = chain[-1].outputs[0]
                if len(out.outputs) != 1 or out.name in protected:
                    break
                nxt = out.outputs[0]
                if not nxt.is_op("fc") or \
                        nxt.op.attrs.get("activation_type") != "relu" or \
                        nxt.op.input("Input")[0] != out.name:
                    break
                chain.append(nxt)
            if len(chain) < 2:
                continue
            ws, bs, doomed = [], [], []
            ok = True
            for i, node in enumerate(chain):
                by_name = {v.name: v for v in node.inputs}
                w = by_name.get(node.op.input("W")[0])
                b = by_name.get(node.op.input("Bias")[0]) \
                    if node.op.input("Bias") else None
                if w is None or b is None:
                    ok = False
                    break
                ws.append(w)
                bs.append(b)
                doomed.append(node)
                if i < len(chain) - 1:
                    doomed.append(node.outputs[0])
            if not ok:
                continue
            out_node = chain[-1].outputs[0]
            graph.create_op_node(
                "fusion_repeated_fc_relu",
                inputs={"X": [x_node], "W": ws, "Bias": bs},
                outputs={"Out": [out_node]}, attrs={})
            graph.safe_remove_nodes(doomed)
            consumed.update(chain)
            count += 1
        graph.attrs["repeated_fc_relu_fuse_count"] = count
        return graph


@register_pass("squared_mat_sub_fuse_pass")
class SquaredMatSubFusePass(Pass):
    """square(X·Y) − square(X)·square(Y) [→ scale] → one
    ``fusion_squared_mat_sub`` (ref ir/squared_mat_sub_fuse_pass.cc —
    the MatchMatrix/pyramid-DNN serving pattern)."""

    def apply_impl(self, graph: Graph) -> Graph:
        protected = self.protected_vars()
        count = 0
        for sub in list(graph.ops_of_type("elementwise_sub")):
            if sub not in graph.op_nodes:
                continue
            by_name = {v.name: v for v in sub.inputs}
            lhs = by_name.get(sub.op.input("X")[0])
            rhs = by_name.get(sub.op.input("Y")[0])
            if lhs is None or rhs is None or not lhs.inputs or \
                    not rhs.inputs:
                continue
            sq_xy, mm2 = lhs.inputs[0], rhs.inputs[0]
            if not sq_xy.is_op("square") or not mm2.is_op("matmul"):
                continue
            mm1_out = sq_xy.inputs[0]
            if not mm1_out.inputs or not mm1_out.inputs[0].is_op("matmul"):
                continue
            mm1 = mm1_out.inputs[0]
            a1, a2 = mm1.op.attrs, mm2.op.attrs
            if any(a.get("transpose_X") or a.get("transpose_Y") or
                   a.get("alpha", 1.0) != 1.0 for a in (a1, a2)):
                continue
            # mm2's operands must be square(x), square(y) of mm1's operands
            m1n = {v.name: v for v in mm1.inputs}
            x_node = m1n.get(mm1.op.input("X")[0])
            y_node = m1n.get(mm1.op.input("Y")[0])
            m2n = {v.name: v for v in mm2.inputs}
            sqx_v = m2n.get(mm2.op.input("X")[0])
            sqy_v = m2n.get(mm2.op.input("Y")[0])
            if None in (x_node, y_node, sqx_v, sqy_v):
                continue
            if not sqx_v.inputs or not sqx_v.inputs[0].is_op("square") or \
                    not sqy_v.inputs or not sqy_v.inputs[0].is_op("square"):
                continue
            sqx_op, sqy_op = sqx_v.inputs[0], sqy_v.inputs[0]
            if sqx_op.inputs[0] is not x_node or \
                    sqy_op.inputs[0] is not y_node:
                continue
            inter = [mm1_out, lhs, rhs, sqx_v, sqy_v]
            if any(len(v.outputs) != 1 or v.name in protected
                   for v in inter):
                continue
            out_node = sub.outputs[0]
            scalar = 1.0
            doomed_scale = []
            if len(out_node.outputs) == 1 and out_node.name not in \
                    protected and out_node.outputs[0].is_op("scale"):
                sc = out_node.outputs[0]
                if sc.op.attrs.get("bias", 0.0) == 0.0:
                    scalar = float(sc.op.attrs.get("scale", 1.0))
                    doomed_scale = [sc, out_node]
                    out_node = sc.outputs[0]
            graph.create_op_node(
                "fusion_squared_mat_sub",
                inputs={"X": [x_node], "Y": [y_node]},
                outputs={"Out": [out_node]}, attrs={"scalar": scalar})
            graph.safe_remove_nodes(
                [mm1, mm1_out, sq_xy, lhs, sqx_op, sqx_v, sqy_op, sqy_v,
                 mm2, rhs, sub] + doomed_scale)
            count += 1
        graph.attrs["squared_mat_sub_fuse_count"] = count
        return graph


@register_pass("transpose_flatten_concat_fuse_pass")
class TransposeFlattenConcatFusePass(Pass):
    """N × (transpose2 → flatten2) → concat ⇒ one
    ``fusion_transpose_flatten_concat``
    (ref ir/transpose_flatten_concat_fuse_pass.cc — the detection-head
    serving pattern)."""

    def apply_impl(self, graph: Graph) -> Graph:
        protected = self.protected_vars()
        count = 0
        for cc in list(graph.ops_of_type("concat")):
            if cc not in graph.op_nodes:
                continue
            srcs, doomed, perms = [], [cc], []
            ok = True
            for v in cc.inputs:
                if v.name in protected or len(v.outputs) != 1 or \
                        not v.inputs or not v.inputs[0].is_op(
                            ("flatten2", "flatten")):
                    ok = False
                    break
                fl = v.inputs[0]
                if fl.op.attrs.get("axis", 1) != 1:
                    ok = False
                    break
                fv = next((u for u in fl.inputs
                           if u.name == fl.op.input("X")[0]), None)
                if fv is None or len(fv.outputs) != 1 or \
                        fv.name in protected or not fv.inputs or \
                        not fv.inputs[0].is_op(("transpose2", "transpose")):
                    ok = False
                    break
                tr = fv.inputs[0]
                perms.append(tuple(tr.op.attrs.get("axis", [])))
                src = next((u for u in tr.inputs
                            if u.name == tr.op.input("X")[0]), None)
                # transpose2/flatten2 emit XShape side outputs: doom the
                # unconsumed ones with their producers (no orphans)
                extra = [o for node in (tr, fl) for o in node.outputs
                         if o is not fv and o is not v]
                if src is None or any(
                        o.outputs or o.name in protected for o in extra):
                    ok = False
                    break
                srcs.append(src)
                doomed += [fl, v, tr, fv] + extra
            if not ok or len(srcs) < 2 or len(set(perms)) != 1:
                continue
            out_node = cc.outputs[0]
            graph.create_op_node(
                "fusion_transpose_flatten_concat",
                inputs={"X": srcs}, outputs={"Out": [out_node]},
                attrs={"trans_axis": list(perms[0]),
                       "concat_axis": cc.op.attrs.get("axis", 1)})
            graph.safe_remove_nodes(doomed)
            count += 1
        graph.attrs["transpose_flatten_concat_fuse_count"] = count
        return graph


@register_pass("seqpool_concat_fuse_pass")
class SeqpoolConcatFusePass(Pass):
    """N × sequence_pool → concat ⇒ one ``fusion_seqpool_concat``
    (ref ir/seqpool_concat_fuse_pass.cc — the CTR/recall serving
    pattern)."""

    def apply_impl(self, graph: Graph) -> Graph:
        protected = self.protected_vars()
        count = 0
        for cc in list(graph.ops_of_type("concat")):
            if cc not in graph.op_nodes:
                continue
            if cc.op.attrs.get("axis", 1) not in (1, -1):
                continue
            srcs, doomed, ptypes = [], [cc], set()
            ok = True
            for v in cc.inputs:
                if v.name in protected or len(v.outputs) != 1 or \
                        not v.inputs or \
                        not v.inputs[0].is_op("sequence_pool"):
                    ok = False
                    break
                sp = v.inputs[0]
                if sp.op.input("SeqLen"):
                    ok = False     # per-branch lengths stay unfused
                    break
                ptypes.add(sp.op.attrs.get("pooltype", "AVERAGE").upper())
                src = next((u for u in sp.inputs
                            if u.name == sp.op.input("X")[0]), None)
                extra = [o for o in sp.outputs if o is not v]
                if src is None or any(
                        o.outputs or o.name in protected for o in extra):
                    ok = False   # MaxIndex consumed/fetched: stay unfused
                    break
                srcs.append(src)
                doomed += [sp, v] + extra
            if not ok or len(srcs) < 2 or len(ptypes) != 1:
                continue
            out_node = cc.outputs[0]
            graph.create_op_node(
                "fusion_seqpool_concat",
                inputs={"X": srcs}, outputs={"Out": [out_node]},
                attrs={"pooltype": next(iter(ptypes))})
            graph.safe_remove_nodes(doomed)
            count += 1
        graph.attrs["seqpool_concat_fuse_count"] = count
        return graph


def _sole_producer(var_node, op_type):
    """The op producing ``var_node`` iff it is of ``op_type`` and the var
    has no other consumer-visible role (single producer is structural)."""
    if not var_node.inputs or not var_node.inputs[0].is_op(op_type):
        return None
    return var_node.inputs[0]


def _input_node(op_node, slot, i=0):
    names = op_node.op.input(slot)
    if not names or i >= len(names):
        return None
    return next((v for v in op_node.inputs if v.name == names[i]), None)


def _output_node(op_node, slot, i=0):
    names = op_node.op.output(slot)
    if not names or i >= len(names):
        return None
    return next((v for v in op_node.outputs if v.name == names[i]), None)


def _referenced_outside_block0(program, name: str) -> bool:
    """True if any op in a control-flow sub-block (block idx > 0) touches
    ``name`` — the block-0 Graph cannot see those consumers, so params they
    share must survive block-0 rewrites."""
    for blk in program.blocks[1:]:
        for op in blk.ops:
            if name in op.input_arg_names() or \
                    name in op.output_arg_names():
                return True
    return False


def _match_fc_proj(g, protected):
    """Match the fc producing ``g``'s Input projection (the shared prefix
    of the fc+rnn fusion family).  Returns (fc, proj, x, w, bias) or
    None; fc must be act-free with in_num_col_dims=2 (keeps the
    [b, t, gates] layout) and a persistable weight."""
    proj = _input_node(g, "Input")
    if proj is None or proj.name in protected or len(proj.outputs) != 1:
        return None
    fc = _sole_producer(proj, "fc")
    if fc is None or fc.op.attrs.get("activation_type") or \
            int(fc.op.attrs.get("in_num_col_dims", 1)) != 2:
        return None
    x_node = _input_node(fc, "Input")
    w_node = _input_node(fc, "W")
    b_fc = _input_node(fc, "Bias")
    if x_node is None or w_node is None or not w_node.persistable:
        return None
    return fc, proj, x_node, w_node, b_fc


def _rnn_struct_outs(g, keep_slots, protected):
    """Split ``g``'s outputs into the structural slots to keep vs the
    internal batch buffers, which must be dead for the fuse to be legal.
    Returns (outs dict, doomed list) or None."""
    outs, doomed = {}, []
    for v in g.outputs:
        slot = next((s for s in keep_slots
                     if g.op.output(s) and v.name in g.op.output(s)), None)
        if slot is not None:
            outs[slot] = v
        elif v.outputs or v.name in protected:
            return None
        else:
            doomed.append(v)
    if set(outs) != set(keep_slots):
        return None
    return outs, doomed


class _FCRNNFuseBase(Pass):
    """fc → {gru,lstm} ⇒ {fusion_gru,fusion_lstm} (ref ir/fc_gru_fuse_pass
    .cc, ir/fc_lstm_fuse_pass.cc).  Both RNN lowerings add Bias to the x
    pre-projection — the same pre-activation the fc bias lands on — so the
    fc bias folds numerically into the gate bias (needs ``scope=``)."""

    RNN = ""
    FUSED = ""
    OUTS = ()

    def apply_impl(self, graph: Graph) -> Graph:
        import numpy as np
        scope = self.get("scope")
        protected = self.protected_vars()
        count = 0
        for g in list(graph.ops_of_type(self.RNN)):
            if g not in graph.op_nodes:
                continue
            m = _match_fc_proj(g, protected)
            if m is None:
                continue
            fc, proj, x_node, w_node, b_fc = m
            bg_node = _input_node(g, "Bias")
            if b_fc is not None and bg_node is not None and scope is None:
                continue        # numeric bias fold needs param values
            so = _rnn_struct_outs(g, self.OUTS, protected)
            if so is None:
                continue        # a live internal batch buffer blocks it
            outs, dead_outs = so
            # fused gate bias = gru/lstm bias (+ fc bias over the gate
            # prefix — peephole tail, if any, is untouched)
            bias_nodes = None
            doomed_bias = []
            if b_fc is not None and bg_node is not None:
                bg = np.asarray(scope.find_var(bg_node.name), np.float64)
                bf = np.asarray(scope.find_var(b_fc.name),
                                np.float64).reshape(-1)
                fused = bg.copy()
                fused.reshape(-1)[:bf.size] += bf
                name = outs[self.OUTS[0]].name + ".fused_gate_bias"
                node = graph.create_var_node(
                    name, shape=tuple(bg.shape), dtype="float32",
                    persistable=True)
                scope.set_var(name, fused.astype(np.float32))
                bias_nodes = [node]
                doomed_bias = [
                    n for n in (b_fc, bg_node)
                    if all(c in (fc, g) for c in n.outputs) and
                    not _referenced_outside_block0(graph.program, n.name)]
                for n in doomed_bias:   # dead params must not stay
                    scope.erase(n.name)  # device-resident in serving
            elif b_fc is not None:
                bias_nodes = [b_fc]
            elif bg_node is not None:
                bias_nodes = [bg_node]
            inputs = {"X": [x_node], "WeightX": [w_node],
                      "WeightH": [_input_node(g, "Weight")]}
            if bias_nodes:
                inputs["Bias"] = bias_nodes
            for slot in ("H0", "C0", "SeqLen"):
                n = _input_node(g, slot)
                if n is not None:
                    inputs[slot] = [n]
            graph.create_op_node(
                self.FUSED, inputs=inputs,
                outputs={s: [outs[s]] for s in self.OUTS},
                attrs=dict(g.op.attrs))
            graph.safe_remove_nodes([fc, proj, g] + doomed_bias +
                                    dead_outs)
            count += 1
        graph.attrs[self.name.replace("_pass", "") + "_count"] = count
        return graph


@register_pass("fc_gru_fuse_pass")
class FCGRUFusePass(_FCRNNFuseBase):
    RNN, FUSED, OUTS = "gru", "fusion_gru", ("Hidden",)


@register_pass("fc_lstm_fuse_pass")
class FCLSTMFusePass(_FCRNNFuseBase):
    RNN, FUSED, OUTS = "lstm", "fusion_lstm", ("Hidden", "Cell")


@register_pass("embedding_fc_lstm_fuse_pass")
class EmbeddingFCLSTMFusePass(Pass):
    """lookup_table → fc → lstm ⇒ ``fused_embedding_fc_lstm`` with a
    pre-multiplied table (ref ir/embedding_fc_lstm_fuse_pass.cc): the new
    Embeddings value is emb·W_fc + b_fc per row, so the gate projection
    becomes a single row gather.  Needs ``scope=``; runs before
    fc_lstm_fuse_pass (more specific pattern first)."""

    def apply_impl(self, graph: Graph) -> Graph:
        import numpy as np
        scope = self.get("scope")
        if scope is None:
            raise ValueError("embedding_fc_lstm_fuse_pass needs scope= "
                             "to pre-multiply the embedding table")
        protected = self.protected_vars()
        count = 0
        for g in list(graph.ops_of_type("lstm")):
            if g not in graph.op_nodes:
                continue
            m = _match_fc_proj(g, protected)
            if m is None:
                continue
            fc, proj, emb_out, w_node, b_fc = m
            if emb_out.name in protected or len(emb_out.outputs) != 1:
                continue
            lt = None
            for t in ("lookup_table", "lookup_table_v2"):
                lt = lt or _sole_producer(emb_out, t)
            if lt is None:
                continue
            pad = lt.op.attrs.get("padding_idx", -1)
            if pad not in (-1, None):
                # a padding row embeds to zeros pre-projection; the
                # pre-multiplied table would bake b_fc into it — unsound
                continue
            emb_w = _input_node(lt, "W")
            ids = _input_node(lt, "Ids")
            if emb_w is None or not emb_w.persistable:
                continue
            if any(c is not lt for c in emb_w.outputs):
                continue        # shared table: other consumers keep it
            so = _rnn_struct_outs(g, ("Hidden", "Cell"), protected)
            if so is None:
                continue
            outs, dead_outs = so
            emb = np.asarray(scope.find_var(emb_w.name), np.float64)
            w = np.asarray(scope.find_var(w_node.name), np.float64)
            table = emb @ w
            if b_fc is not None:
                table = table + np.asarray(
                    scope.find_var(b_fc.name), np.float64).reshape(1, -1)
            name = outs["Hidden"].name + ".premul_embeddings"
            tbl_node = graph.create_var_node(
                name, shape=tuple(table.shape), dtype="float32",
                persistable=True)
            scope.set_var(name, table.astype(np.float32))
            inputs = {"Ids": [ids], "Embeddings": [tbl_node],
                      "WeightH": [_input_node(g, "Weight")]}
            bg = _input_node(g, "Bias")
            if bg is not None:
                inputs["Bias"] = [bg]
            for slot in ("H0", "C0", "SeqLen"):
                n = _input_node(g, slot)
                if n is not None:
                    inputs[slot] = [n]
            graph.create_op_node(
                "fused_embedding_fc_lstm", inputs=inputs,
                outputs={"Hidden": [outs["Hidden"]],
                         "Cell": [outs["Cell"]]},
                attrs=dict(g.op.attrs))
            doomed = [lt, emb_out, fc, proj, g] + dead_outs
            for n in (emb_w, w_node, b_fc):
                # consumed params leave graph AND scope — unless a
                # control-flow sub-block the Graph can't see shares them
                if n is not None and \
                        all(c in (lt, fc) for c in n.outputs) and \
                        not _referenced_outside_block0(graph.program,
                                                       n.name):
                    doomed.append(n)
                    scope.erase(n.name)  # don't keep the dead V×D table
            graph.safe_remove_nodes(doomed)
            count += 1
        graph.attrs["embedding_fc_lstm_fuse_count"] = count
        return graph


@register_pass("conv_elementwise_add_act_fuse_pass")
class ConvEltwiseAddActFusePass(Pass):
    """conv2d → elementwise_add(per-channel bias) → act ⇒ ``conv2d_fusion``
    (ref ir/conv_elementwise_add_act_fuse_pass.cc).  Must run before
    fuse_elewise_add_act_pass, which would otherwise consume the
    add→act tail."""

    ACTS = ("relu", "sigmoid", "tanh")

    def apply_impl(self, graph: Graph) -> Graph:
        protected = self.protected_vars()
        count = 0
        for conv in list(graph.ops_of_type("conv2d")):
            if conv not in graph.op_nodes:
                continue
            conv_out = _output_node(conv, "Output")
            if conv_out is None or conv_out.name in protected or \
                    len(conv_out.outputs) != 1:
                continue
            add = conv_out.outputs[0]
            if not add.is_op("elementwise_add") or \
                    int(add.op.attrs.get("axis", -1)) != 1:
                continue
            bias = _input_node(add, "Y")
            if bias is None or not bias.persistable or \
                    bias.var is None or len(bias.var.shape or ()) != 1:
                continue
            add_out = _output_node(add, "Out")
            if add_out is None or add_out.name in protected or \
                    len(add_out.outputs) != 1:
                continue
            act = add_out.outputs[0]
            if not act.is_op() or act.name not in self.ACTS:
                continue
            out_node = act.outputs[0]
            attrs = dict(conv.op.attrs)
            attrs["activation"] = act.name
            graph.create_op_node(
                "conv2d_fusion",
                inputs={"Input": [_input_node(conv, "Input")],
                        "Filter": [_input_node(conv, "Filter")],
                        "Bias": [bias]},
                outputs={"Output": [out_node]}, attrs=attrs)
            graph.safe_remove_nodes([conv, conv_out, add, add_out, act])
            count += 1
        graph.attrs["conv_elementwise_add_act_fuse_count"] = count
        return graph


@register_pass("seqconv_eltadd_relu_fuse_pass")
class SeqConvEltAddReluFusePass(Pass):
    """sequence_conv → elementwise_add(bias) → relu ⇒
    ``fusion_seqconv_eltadd_relu`` (ref ir/seqconv_eltadd_relu_fuse_pass
    .cc — the text-CNN serving pattern)."""

    def apply_impl(self, graph: Graph) -> Graph:
        protected = self.protected_vars()
        count = 0
        for sc in list(graph.ops_of_type("sequence_conv")):
            if sc not in graph.op_nodes:
                continue
            if int(sc.op.attrs.get("contextStride", 1)) != 1:
                continue
            sc_out = _output_node(sc, "Out")
            if sc_out is None or sc_out.name in protected or \
                    len(sc_out.outputs) != 1:
                continue
            add = sc_out.outputs[0]
            if not add.is_op("elementwise_add"):
                continue
            bias = _input_node(add, "Y")
            if bias is None or not bias.persistable or \
                    bias.var is None or len(bias.var.shape or ()) != 1 or \
                    int(add.op.attrs.get("axis", -1)) != 2:
                continue        # only the 1-D per-filter feature bias
            add_out = _output_node(add, "Out")
            if add_out is None or add_out.name in protected or \
                    len(add_out.outputs) != 1:
                continue
            relu = add_out.outputs[0]
            if not relu.is_op("relu"):
                continue
            out_node = relu.outputs[0]
            graph.create_op_node(
                "fusion_seqconv_eltadd_relu",
                inputs={"X": [_input_node(sc, "X")],
                        "Filter": [_input_node(sc, "Filter")],
                        "Bias": [bias]},
                outputs={"Out": [out_node]},
                attrs={"contextLength":
                       sc.op.attrs.get("contextLength", 3),
                       "contextStart": sc.op.attrs.get("contextStart", 0)})
            graph.safe_remove_nodes([sc, sc_out, add, add_out, relu])
            count += 1
        graph.attrs["seqconv_eltadd_relu_fuse_count"] = count
        return graph


@register_pass("conv_bn_fuse_pass")
class ConvBNFusePass(Pass):
    """conv2d + batch_norm(is_test) → conv2d + folded weights
    (ref ir/conv_bn_fuse_pass.cc).  Numeric folding needs the param values:
    pass ``scope=`` when constructing.  W' = W·(γ/σ) per out-channel,
    b' = β − μ·γ/σ, emitted as an elementwise_add on the conv output (the
    reference does exactly this when conv has no bias)."""

    def apply_impl(self, graph: Graph) -> Graph:
        import numpy as np
        scope = self.get("scope")
        if scope is None:
            raise ValueError("conv_bn_fuse_pass needs scope= with param "
                             "values to fold numerically")
        count = 0
        for bn in list(graph.ops_of_type("batch_norm")):
            if bn not in graph.op_nodes:
                continue
            if not bn.op.attrs.get("is_test") and \
                    not bn.op.attrs.get("use_global_stats"):
                continue
            conv_out = next((v for v in bn.inputs
                             if v.inputs and v.inputs[0].is_op("conv2d")),
                            None)
            if conv_out is None or len(conv_out.outputs) != 1:
                continue
            conv = conv_out.inputs[0]
            w_shared = next((v for v in conv.inputs if v.persistable), None)
            if w_shared is None:
                # filter is not a plain persistable weight (e.g. a QAT
                # .quantized intermediate) — nothing to fold numerically
                continue
            if any(c is not conv for c in w_shared.outputs):
                # folding mutates the filter values in the scope — a shared
                # filter would silently corrupt its other consumers
                continue
            by_name = {v.name: v for v in bn.inputs}
            op = bn.op
            scale_n = op.input("Scale")[0]
            bias_n = op.input("Bias")[0]
            mean_n = op.input("Mean")[0]
            var_n = op.input("Variance")[0]
            w_node = next(v for v in conv.inputs if v.persistable)
            eps = op.attrs.get("epsilon", 1e-5)
            gamma = np.asarray(scope.find_var(scale_n), np.float64)
            beta = np.asarray(scope.find_var(bias_n), np.float64)
            mu = np.asarray(scope.find_var(mean_n), np.float64)
            var = np.asarray(scope.find_var(var_n), np.float64)
            w = np.asarray(scope.find_var(w_node.name), np.float64)
            factor = gamma / np.sqrt(var + eps)       # [out_c]
            scope.set_var(w_node.name,
                          (w * factor.reshape(-1, 1, 1, 1)).astype(
                              np.float32))
            fused_bias_name = bn.op.output("Y")[0] + ".conv_bn_bias"
            bias_node = graph.create_var_node(
                fused_bias_name, shape=(len(factor),), dtype="float32",
                persistable=True)
            scope.set_var(fused_bias_name,
                          (beta - mu * factor).astype(np.float32))
            y_node = next(v for v in bn.outputs
                          if v.name in op.output("Y"))
            graph.create_op_node(
                "elementwise_add",
                inputs={"X": [conv_out], "Y": [bias_node]},
                outputs={"Out": [y_node]},
                attrs={"axis": 1})
            # stat outputs (MeanOut etc.) die with the bn node
            doomed = [bn] + [v for v in bn.outputs if v is not y_node]
            doomed += [by_name[n] for n in
                       (scale_n, bias_n, mean_n, var_n)
                       if n in by_name and
                       all(c is bn for c in by_name[n].outputs)]
            graph.safe_remove_nodes(doomed)
            count += 1
        graph.attrs["conv_bn_fuse_count"] = count
        return graph


# ---------------------------------------------------------------------------
# Memory-analysis passes (ref ir/memory_optimize_pass/)
# ---------------------------------------------------------------------------

@register_pass("reference_count_pass")
class ReferenceCountPass(Pass):
    """Liveness: last-use op index per non-persistable var
    (ref reference_count_pass.cc).  Under the block-compiler XLA frees
    temporaries itself; this analysis feeds donation and debugging
    (``graph.attrs['last_use']``)."""

    def apply_impl(self, graph: Graph) -> Graph:
        order = {op.id: i for i, op in enumerate(graph.topology_sort())}
        last_use: Dict[str, int] = {}
        for v in graph.all_var_nodes():
            if v.persistable:
                continue
            uses = [order[c.id] for c in v.outputs if c.id in order]
            if uses:
                last_use[v.name] = max(uses)
        graph.attrs["last_use"] = last_use
        return graph


@register_pass("buffer_shared_inplace_pass")
class BufferSharedInplacePass(Pass):
    """Pairs (in, out) an op could compute in place because the input dies
    there (ref buffer_shared_inplace_op_pass.cc).  XLA's buffer assigner
    performs the actual aliasing; the pairs inform ``donate_argnums`` for
    feed buffers (``graph.attrs['inplace_pairs']``)."""

    INPLACE_OPS = ("relu", "scale", "reshape", "reshape2", "squeeze",
                   "squeeze2", "unsqueeze", "unsqueeze2", "flatten",
                   "flatten2", "elementwise_add", "softmax", "dropout")

    def apply_impl(self, graph: Graph) -> Graph:
        graph = get_pass("reference_count_pass").apply(graph)
        last_use = graph.attrs["last_use"]
        order = {op.id: i for i, op in enumerate(graph.topology_sort())}
        pairs = []
        for op in graph.all_op_nodes():
            if op.name not in self.INPLACE_OPS:
                continue
            for vin in op.inputs:
                if vin.persistable or vin.name not in last_use:
                    continue
                if last_use[vin.name] == order[op.id] and op.outputs:
                    pairs.append((vin.name, op.outputs[0].name))
                    break
        graph.attrs["inplace_pairs"] = pairs
        return graph


#: op types executed for their effect, not their outputs: always liveness
#: roots (ref the reference's GC whitelist in eager_deletion_pass.cc —
#: ops a liveness sweep must never collect)
SIDE_EFFECT_OPS = frozenset({
    "feed", "fetch", "listen_and_serv", "send", "recv", "print", "assert",
    "save", "load", "py_func", "gen_nccl_id",
})


def dead_op_analysis(graph: Graph, protected=frozenset()) -> List[Node]:
    """Liveness from fetch + persistable + side-effect roots: the op nodes
    whose outputs reach none of them (the verifier's ``dead_op`` check and
    the ``dead_op_eliminate`` pass share this sweep).

    Roots (deliberately conservative — a falsely-dead op silently corrupts
    results, a falsely-live op only wastes XLA's own DCE a few ns):
    - ops writing a ``protected`` (fetched) var or any persistable,
    - ops writing a var any control-flow SUB-block references (the block-0
      graph cannot see those consumers),
    - side-effecting op types (:data:`SIDE_EFFECT_OPS`, every ``c_*``
      collective, and any op carrying a Block-valued attr — its sub-block
      may write persistables),
    - ops with no outputs at all.
    Everything reaching a root through data dependencies is live; the rest
    is dead."""
    from .core import Block as _Block
    program = graph.program
    block = program.blocks[graph.block_idx]
    sub_refs = set()
    for blk in program.blocks:
        if blk.idx == graph.block_idx:
            continue
        for op in blk.ops:
            sub_refs.update(op.input_arg_names())
            sub_refs.update(op.output_arg_names())

    def persistable(name):
        return block.has_var(name) and block.var(name).persistable

    def is_root(op_node: Node) -> bool:
        op = op_node.op
        if op.type in SIDE_EFFECT_OPS or op.type.startswith("c_"):
            return True
        if any(isinstance(v, _Block) for v in op.attrs.values()):
            return True
        outs = [n for n in op.output_arg_names() if n]
        if not outs:
            return True
        return any(n in protected or n in sub_refs or persistable(n)
                   for n in outs)

    live = {n.id for n in graph.op_nodes if is_root(n)}
    stack = [n for n in graph.op_nodes if n.id in live]
    while stack:
        op_node = stack.pop()
        for v in op_node.inputs:
            for producer in v.inputs:
                if producer.id not in live:
                    live.add(producer.id)
                    stack.append(producer)
    return [n for n in graph.op_nodes if n.id not in live]


def dead_subblock_op_analysis(program: Program,
                              protected=frozenset()) -> Dict[int, tuple]:
    """Per-sub-block liveness: for every block idx > 0, the program-order
    op indices whose outputs reach none of the block's liveness roots —
    the sub-block counterpart of :func:`dead_op_analysis`, with the roots
    adjusted for loop semantics (live loop-carried vars must survive):

    - ops writing a name ANY other block references (carried vars and
      the condition appear in the enclosing ``while``/``cond`` op's
      input/output lists, so their writers are roots; so are writers of
      vars a nested body reads),
    - ops writing a ``protected`` (fetched) name or any persistable,
    - side-effecting op types, every ``c_*`` collective, ops carrying a
      nested Block attr, and ops with no outputs.

    Everything reaching a root through the block's own def-use chains is
    live; the rest is dead body compute nothing observes (its outputs
    feed no carry, no fetch, no persistable — it burns trace time and
    loop FLOPs every iteration).  Returns {block_idx: (op indices...)}
    for blocks with at least one dead op."""
    from .core import Block as _Block
    out: Dict[int, tuple] = {}
    for block in program.blocks[1:]:
        # names referenced by ANY op outside this block (enclosing
        # control-flow ops list carried vars / Condition / Out there)
        ext_refs = set()
        for other in program.blocks:
            if other.idx == block.idx:
                continue
            for op in other.ops:
                ext_refs.update(op.input_arg_names())
                ext_refs.update(op.output_arg_names())
                for v in op.attrs.values():
                    if isinstance(v, _Block) and v.idx == block.idx:
                        # the enclosing op's attr lists (carried_vars,
                        # cond_var, state_vars...) reference body names
                        # without appearing in its input/output slots
                        for av in op.attrs.values():
                            if isinstance(av, (list, tuple)):
                                ext_refs.update(
                                    x for x in av if isinstance(x, str))
                            elif isinstance(av, str):
                                ext_refs.add(av)

        def persistable(name, _b=block):
            return _b.has_var(name) and _b.var(name).persistable

        def is_root(op) -> bool:
            if op.type in SIDE_EFFECT_OPS or op.type.startswith("c_"):
                return True
            if any(isinstance(v, _Block) for v in op.attrs.values()):
                return True
            outs = [n for n in op.output_arg_names() if n]
            if not outs:
                return True
            return any(n in protected or n in ext_refs or persistable(n)
                       for n in outs)

        live = {i for i, op in enumerate(block.ops) if is_root(op)}
        # backward closure over the block's own def-use: any op writing
        # a name a live op reads is live (conservative on rewrites)
        changed = True
        while changed:
            changed = False
            needed = {n for i in live
                      for n in block.ops[i].input_arg_names() if n}
            for i, op in enumerate(block.ops):
                if i in live:
                    continue
                if needed & {n for n in op.output_arg_names() if n}:
                    live.add(i)
                    changed = True
        dead = tuple(i for i in range(len(block.ops)) if i not in live)
        if dead:
            out[block.idx] = dead
    return out


def prune_subblock_ops(program: Program,
                       dead_map: Dict[int, tuple]) -> int:
    """Drop the ops named by :func:`dead_subblock_op_analysis` from
    ``program``'s sub-blocks (in place).  Returns the removal count."""
    removed = 0
    for idx, indices in (dead_map or {}).items():
        if idx <= 0 or idx >= len(program.blocks):
            continue
        block = program.blocks[idx]
        doomed = set(indices)
        kept = [op for i, op in enumerate(block.ops) if i not in doomed]
        removed += len(block.ops) - len(kept)
        block.ops = kept
    if removed:
        program._bump_version()
    return removed


@register_pass("dead_op_eliminate")
class DeadOpEliminatePass(Pass):
    """Remove ops unreachable from the fetch/persistable/side-effect
    liveness roots (:func:`dead_op_analysis`).  Under XLA the compiler
    DCEs the lowered computation anyway — the win is never TRACING the
    dead subgraph (a dead attention head still costs its full trace +
    shape inference time) and keeping donation/liveness analyses honest.
    ``protected`` names the fetch targets, same contract as the fusion
    passes; removal count lands in
    ``graph.attrs['dead_op_eliminate_count']``.

    Sub-blocks too: dead compute inside ``while``/``cond`` bodies
    (:func:`dead_subblock_op_analysis` — live loop-carried vars always
    survive) is recorded in ``graph.attrs['dead_subblock_ops']`` and
    pruned when the graph materializes via :meth:`Graph.to_program` /
    :meth:`Graph.apply_to_program`; the count adds into
    ``dead_op_eliminate_count``."""

    def apply_impl(self, graph: Graph) -> Graph:
        dead = dead_op_analysis(graph, self.protected_vars())
        # every consumer of a dead op's output is itself dead (liveness is
        # a backward closure), so the output var nodes go with their ops
        doomed_vars = [v for n in dead for v in n.outputs]
        graph.safe_remove_nodes(list(dead) + doomed_vars)
        sub_dead = dead_subblock_op_analysis(graph.program,
                                             self.protected_vars())
        graph.attrs["dead_subblock_ops"] = sub_dead
        graph.attrs["dead_op_eliminate_count"] = \
            len(dead) + sum(len(v) for v in sub_dead.values())
        return graph


# ---------------------------------------------------------------------------
# Graph viz / round-trip passes
# ---------------------------------------------------------------------------

@register_pass("graph_viz_pass")
class GraphVizPass(Pass):
    """DOT dump (ref ir/graph_viz_pass.cc).  ``graph_viz_path`` attr writes
    to a file; the DOT text is also returned in
    ``graph.attrs['graph_viz_dot']``."""

    def apply_impl(self, graph: Graph) -> Graph:
        lines = ["digraph G {", "  rankdir=TB;"]
        for op in graph.all_op_nodes():
            lines.append(
                f'  n{op.id} [label="{op.name}" shape=box '
                f'style=filled fillcolor="#ffd39b"];')
        highlights = frozenset(self.get("highlights") or ())
        for v in graph.all_var_nodes():
            shape = "ellipse"
            fill = "#f4adad" if v.name in highlights else \
                "#c0d9ee" if not v.persistable else "#b5e7b5"
            lines.append(
                f'  n{v.id} [label="{v.name}" shape={shape} '
                f'style=filled fillcolor="{fill}"];')
        for n in graph.all_op_nodes() + graph.all_var_nodes():
            for o in n.outputs:
                lines.append(f"  n{n.id} -> n{o.id};")
        lines.append("}")
        dot = "\n".join(lines)
        graph.attrs["graph_viz_dot"] = dot
        path = self.get("graph_viz_path")
        if path:
            with open(path, "w") as f:
                f.write(dot)
        return graph


@register_pass("graph_to_program_pass")
class GraphToProgramPass(Pass):
    """Round-trip Graph → ProgramDesc (ref ir/graph_to_program_pass.cc);
    result in ``graph.attrs['program']``."""

    def apply_impl(self, graph: Graph) -> Graph:
        graph.attrs["program"] = graph.to_program()
        return graph
