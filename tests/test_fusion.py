"""Cost-guided training-safe graph fusion (``paddle_tpu.analysis.fusion``).

Covers the PR-9 contract: per-pattern match + apply, legality
near-misses (fetched intermediate, multi-consumer, missing grad
rewrite), rank-threshold gating, loss parity fused-vs-unfused on a
bert-shaped toy training program (a resnet-shaped one goes through the
pass unchanged), collective-fingerprint stability through the rewrite,
the fused lowerings against the chains they replace, and executor plan
invalidation on a fusion-flag flip.
"""

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, monitor
from paddle_tpu import optimizer as opt
from paddle_tpu.analysis import fusion, verify_program
from paddle_tpu.framework import (Program, Scope, program_guard,
                                  scope_guard)

SEED = 31


def _counter(name, **labels):
    fam = monitor.REGISTRY.get(name)
    if fam is None:
        return 0
    return sum(cell.get() for lbl, cell in fam.series()
               if all(lbl.get(k) == v for k, v in labels.items()))


@pytest.fixture(autouse=True)
def _fusion_defaults():
    pt.set_flags({"FLAGS_graph_fusion": True,
                  "FLAGS_fusion_rank_threshold": 0.02})
    fusion.clear_cache()
    yield
    pt.set_flags({"FLAGS_graph_fusion": True,
                  "FLAGS_fusion_rank_threshold": 0.02})
    fusion.clear_cache()


def _build_conv_toy():
    """conv2d(1x1)+bn+relu -> pool -> fc(softmax) -> ce loss + SGD:
    resnet-shaped, and no pattern's subject."""
    img = layers.data("image", shape=[3, 6, 6], dtype="float32")
    label = layers.data("label", shape=[1], dtype="int64")
    conv = layers.conv2d(img, num_filters=8, filter_size=1, padding=0,
                         bias_attr=False)
    bn = layers.batch_norm(conv, act="relu")
    pool = layers.pool2d(bn, global_pooling=True, pool_type="avg")
    pred = layers.fc(pool, size=10, act="softmax")
    loss = layers.mean(layers.cross_entropy(pred, label))
    opt.SGDOptimizer(learning_rate=0.1).minimize(loss)


def _build_dense_toy(train=True, side_consumer=False):
    """fc(relu) -> fc(softmax) -> ce loss [+ SGD]: the first fc's
    mul + bias add + relu is the ``dense_epilogue`` pattern's subject.
    Returns the mul's output (an internal var of the match) and the
    loss."""
    x = layers.data("x", shape=[12], dtype="float32")
    label = layers.data("label", shape=[1], dtype="int64")
    h = layers.fc(x, size=16, act="relu")
    block = pt.default_main_program().global_block()
    mm_out = block.var(next(op for op in block.ops
                            if op.type == "mul").output("Out")[0])
    if side_consumer:
        h = h + layers.relu(mm_out)   # second consumer of the mul out
    pred = layers.fc(h, size=10, act="softmax")
    loss = layers.mean(layers.cross_entropy(pred, label))
    if train:
        opt.SGDOptimizer(learning_rate=0.1).minimize(loss)
    return mm_out, loss


def _dense_feed():
    rng = np.random.RandomState(0)
    return {"x": rng.rand(4, 12).astype(np.float32),
            "label": rng.randint(0, 10, (4, 1)).astype(np.int64)}


def _conv_feed(rng=None):
    rng = rng or np.random.RandomState(0)
    return {"image": rng.rand(4, 3, 6, 6).astype(np.float32),
            "label": rng.randint(0, 10, (4, 1)).astype(np.int64)}


def _build_bert_toy():
    """emb + pos-emb add -> layer_norm -> fc(gelu) -> dropout -> fc ->
    mean-square loss + SGD: the bert-shaped chain both the
    embedding_layer_norm and dense_epilogue patterns hit."""
    src = layers.data("src", shape=[6], dtype="int64")
    pos = layers.data("pos", shape=[6], dtype="int64")
    emb = layers.embedding(src, size=[30, 8])
    pemb = layers.embedding(pos, size=[6, 8])
    x = emb + pemb
    x = layers.layer_norm(x, begin_norm_axis=2)
    h = layers.fc(x, size=16, num_flatten_dims=2, act="gelu")
    h = layers.dropout(h, dropout_prob=0.1,
                       dropout_implementation="upscale_in_train")
    out = layers.fc(h, size=8, num_flatten_dims=2)
    loss = layers.mean(out * out)
    opt.SGDOptimizer(learning_rate=0.05).minimize(loss)
    return loss


def _bert_feed(rng=None):
    rng = rng or np.random.RandomState(1)
    return {"src": rng.randint(0, 30, (3, 6)).astype(np.int64),
            "pos": np.tile(np.arange(6, dtype=np.int64), (3, 1))}


def _snapshot(scope):
    return {n: np.copy(np.asarray(scope.find_var(n)))
            for n in scope.local_var_names()}


def _run_steps(prog, loss, scope, feed, steps=3):
    exe = pt.Executor()
    out = []
    for i in range(steps):
        lv, = exe.run(prog, feed=feed, fetch_list=[loss.name],
                      scope=scope, seed=SEED + i)
        out.append(float(np.asarray(lv)))
    return out


# ---------------------------------------------------------------------------
# match + apply
# ---------------------------------------------------------------------------

def test_dense_epilogue_applied_with_tagged_dropout():
    scope = Scope()
    with scope_guard(scope), program_guard(Program(), Program()):
        x = layers.data("x", shape=[12], dtype="float32")
        h = layers.fc(x, size=16, act="gelu")
        h = layers.dropout(h, dropout_prob=0.2,
                           dropout_implementation="upscale_in_train")
        loss = layers.mean(h * h)
        opt.SGDOptimizer(learning_rate=0.1).minimize(loss)
        prog = pt.default_main_program()
        fused = fusion.fuse_program(prog, ())
        types = [op.type for op in fused.global_block().ops]
        assert "fused_dense_act" in types and \
            "fused_dense_act_grad" in types
        # the dropout (tagged) folded into the fused op
        assert "dropout" not in types and "dropout_grad" not in types
        fop = next(op for op in fused.global_block().ops
                   if op.type == "fused_dense_act")
        assert fop.attrs["seed"] != 0 and fop.attrs["act"] == "gelu"


def test_untagged_dropout_stays_outside_the_fusion():
    scope = Scope()
    with scope_guard(scope), program_guard(Program(), Program()):
        x = layers.data("x", shape=[12], dtype="float32")
        h = layers.fc(x, size=16, act="relu")
        # hand-built dropout with seed=0: mask-replay only — must NOT
        # fold (the fused op could not regenerate the same mask)
        helper_out = pt.default_main_program().global_block()
        dout = helper_out.create_var(name="drop_out", shape=h.shape,
                                     dtype="float32")
        mask = helper_out.create_var(name="drop_mask", shape=h.shape,
                                     dtype="uint8")
        helper_out.append_op(
            "dropout", inputs={"X": [h.name]},
            outputs={"Out": [dout.name], "Mask": [mask.name]},
            attrs={"dropout_prob": 0.2, "is_test": False, "seed": 0,
                   "dropout_implementation": "upscale_in_train"})
        loss = layers.mean(dout * dout)
        opt.SGDOptimizer(learning_rate=0.1).minimize(loss)
        prog = pt.default_main_program()
        fused = fusion.fuse_program(prog, ())
        types = [op.type for op in fused.global_block().ops]
        assert "fused_dense_act" in types       # mul+bias+relu fused
        assert "dropout" in types               # untagged tail survives
        fop = next(op for op in fused.global_block().ops
                   if op.type == "fused_dense_act")
        assert fop.attrs["seed"] == 0           # no dropout folded


def test_embedding_layer_norm_applied_bert_shaped():
    scope = Scope()
    with scope_guard(scope), program_guard(Program(), Program()):
        _build_bert_toy()
        prog = pt.default_main_program()
        fused = fusion.fuse_program(prog, ())
        types = [op.type for op in fused.global_block().ops]
        assert "fused_embedding_layer_norm" in types
        assert "fused_embedding_layer_norm_grad" in types
        assert "layer_norm" not in types
        rep = fused._attrs["fusion"]
        by = {c["pattern"]: c["verdict"] for c in rep["candidates"]}
        assert by.get("embedding_layer_norm") == "applied"
        assert by.get("dense_epilogue") == "applied"
        # the pos-embedding lookup (the external addend's producer)
        # survives with its grad — only the word-emb chain fused
        assert types.count("lookup_table") == 1
        assert types.count("lookup_table_grad") == 1


# ---------------------------------------------------------------------------
# legality near-misses
# ---------------------------------------------------------------------------

def test_reject_fetched_intermediate():
    scope = Scope()
    with scope_guard(scope), program_guard(Program(), Program()):
        mm_out, loss = _build_dense_toy(train=False)
        prog = pt.default_main_program()
        rep = fusion.analyze_program(prog, (mm_out.name, loss.name))
        dec = {c.pattern: c for c in rep.decisions}
        assert dec["dense_epilogue"].verdict == "rejected"
        assert dec["dense_epilogue"].rule == "fetched_internal"
        # and fuse_program leaves the program untouched
        assert fusion.fuse_program(
            prog, (mm_out.name, loss.name)) is prog


def test_reject_multi_consumer_intermediate():
    scope = Scope()
    with scope_guard(scope), program_guard(Program(), Program()):
        _build_dense_toy(train=False, side_consumer=True)
        prog = pt.default_main_program()
        rep = fusion.analyze_program(prog, ())
        dec = {c.pattern: c for c in rep.decisions}
        assert dec["dense_epilogue"].verdict == "rejected"
        assert dec["dense_epilogue"].rule == "multi_consumer"


def test_reject_missing_grad_rewrite():
    scope = Scope()
    with scope_guard(scope), program_guard(Program(), Program()):
        _build_dense_toy(train=True)
        prog = pt.default_main_program()
        blk = prog.global_block()
        # amputate the relu_grad: the program still contains grad ops,
        # so a forward rewrite without a complete grad rewrite is illegal
        blk.ops = [op for op in blk.ops if op.type != "relu_grad"]
        prog._bump_version()
        rep = fusion.analyze_program(prog, ())
        dec = {c.pattern: c for c in rep.decisions}
        assert dec["dense_epilogue"].verdict == "rejected"
        assert dec["dense_epilogue"].rule == "missing_grad_rewrite"


def test_rank_threshold_gates_rewrites():
    pt.set_flags({"FLAGS_fusion_rank_threshold": 1.1})  # nothing passes
    scope = Scope()
    with scope_guard(scope), program_guard(Program(), Program()):
        _build_dense_toy()
        prog = pt.default_main_program()
        fused = fusion.fuse_program(prog, ())
        assert fused is prog
        rep = prog._attrs["fusion"]
        verdicts = {c["verdict"] for c in rep["candidates"]
                    if c["pattern"] == "dense_epilogue"}
        assert "ranked_out" in verdicts


# ---------------------------------------------------------------------------
# fingerprint stability
# ---------------------------------------------------------------------------

def test_collective_fingerprint_unchanged_by_fusion():
    scope = Scope()
    with scope_guard(scope), program_guard(Program(), Program()):
        _mm, loss = _build_dense_toy(train=True)
        prog = pt.default_main_program()
        blk = prog.global_block()
        blk.create_var(name="allr_out", shape=loss.shape,
                       dtype="float32")
        blk.append_op("c_allreduce_sum", inputs={"X": [loss.name]},
                      outputs={"Out": ["allr_out"]},
                      attrs={"ring_id": 0})
        prog._bump_version()
        pre = verify_program(prog, (loss.name,))
        assert pre.collective_fingerprint is not None
        fused = fusion.fuse_program(prog, (loss.name,))
        assert fused is not prog
        post = verify_program(fused, (loss.name,))
        assert post.collective_fingerprint == pre.collective_fingerprint
        assert fused._attrs["fusion"]["collective_fingerprint_ok"]


# ---------------------------------------------------------------------------
# loss parity (fused vs unfused, same params, same per-step seeds)
# ---------------------------------------------------------------------------

def _parity(build, feed_fn, tol):
    scope = Scope()
    with scope_guard(scope), program_guard(Program(), Program()):
        build()
        prog = pt.default_main_program()
        loss_name = [op for op in prog.global_block().ops
                     if op.type == "mean"][-1].output("Out")[0]
        exe0 = pt.Executor()
        exe0.run(pt.default_startup_program(), scope=scope, seed=7)
        snap = _snapshot(scope)
        feed = feed_fn()

        class _L:
            name = loss_name
        losses = {}
        for fuse_on in (False, True):
            pt.set_flags({"FLAGS_graph_fusion": fuse_on})
            for n, v in snap.items():
                scope.set_var(n, np.copy(v))
            losses[fuse_on] = _run_steps(prog, _L, scope, feed)
        worst = max(abs(a - b)
                    for a, b in zip(losses[False], losses[True]))
        assert worst < tol, (losses, worst)
        # training actually progressed (the parity is not vacuous)
        assert losses[False][0] != losses[False][-1]


def test_loss_parity_resnet_shaped():
    # no pattern has a conv + BN subject: the pass hands the program back
    # and the two flag settings train the same step, to the bit
    _parity(_build_conv_toy, _conv_feed, tol=1e-12)
    with scope_guard(Scope()), program_guard(Program(), Program()):
        _build_conv_toy()
        prog = pt.default_main_program()
        assert fusion.fuse_program(prog, ()) is prog


def test_loss_parity_bert_shaped():
    # bit-exact: the dense/embedding fused lowerings compose the same
    # jnp calls and the tagged dropout replays the identical mask
    _parity(_build_bert_toy, _bert_feed, tol=1e-6)


# ---------------------------------------------------------------------------
# the fused lowerings are the chains they replace
# ---------------------------------------------------------------------------

def _chain_of(prog, fused_op):
    """The forward ops of ``prog`` that ``fused_op`` stands for, in program
    order: walked back from its output to its inputs."""
    ops = prog.global_block().ops
    producer = {n: op for op in ops if not op.type.endswith("_grad")
                for n in op.output_arg_names()}
    stop = set(fused_op.input_arg_names())
    chain, todo = [], [fused_op.output("Out")[0]]
    while todo:
        op = producer.get(todo.pop())
        if op is None or op in chain:
            continue
        chain.append(op)
        todo += [n for n in op.input_arg_names() if n not in stop]
    return sorted(chain, key=ops.index)


def _stablehlo_ops(text):
    """The lowered module as (op, element types) rows: shapes, attributes
    and the reshapes dropped (the fused dense op adds the bias and the
    activation on the matmul's 2-D result and reshapes last, where the chain
    reshapes first; nothing else may differ)."""
    import re
    rows = []
    for line in text.splitlines():
        m = re.search(r"= (\w+\.\w+|call) ", line)
        if m and m.group(1) != "stablehlo.reshape":
            rows.append((m.group(1),) + tuple(
                re.findall(r"x?(bf16|f32|i32|i64|ui8|ui32|ui64|i1)>", line)))
    return rows


@pytest.mark.parametrize("amp", [False, True], ids=["f32", "amp"])
@pytest.mark.parametrize("fused_type", ["fused_dense_act",
                                        "fused_embedding_layer_norm"])
def test_fused_lowering_is_the_chain_it_replaces(fused_type, amp):
    """What ``ops/fused_ops.py`` promises: each fused op lowers to the
    StableHLO ops of the chain the pass removed, in the chain's order and
    types (under AMP the chain's per-op casts), and gives its bits."""
    import jax

    from paddle_tpu import amp as _amp
    from paddle_tpu.framework import registry
    from paddle_tpu.framework.executor import LowerCtx

    with scope_guard(Scope()), program_guard(Program(), Program()):
        _build_bert_toy()
        prog = pt.default_main_program()
        fused_prog = fusion.fuse_program(prog, ())
    fused_op = next(op for op in fused_prog.global_block().ops
                    if op.type == fused_type)
    chain = _chain_of(prog, fused_op)
    assert len(chain) >= 3, [op.type for op in chain]
    out_name = fused_op.output("Out")[0]
    block = prog.global_block()
    rng = np.random.RandomState(3)
    vals = {}
    for n in sorted(fused_op.input_arg_names()):
        var = block.var(n)
        shape = tuple(3 if d == -1 else int(d) for d in var.shape)
        vals[n] = (rng.randint(0, 6, shape).astype(np.int64)
                   if "int" in str(var.dtype)
                   else rng.randn(*shape).astype(np.float32))

    def lower(ops):
        def run(*arrs):
            env, ctx = dict(zip(vals, arrs)), LowerCtx(SEED, amp=amp)
            for op in ops:
                ins = {s: [env[n] for n in ns]
                       for s, ns in op.inputs.items()}
                if amp:
                    ins = _amp.cast_ins(op.type, ins)
                outs = registry.get_op_info(op.type).lower(
                    ctx, ins, op.attrs)
                for s, ns in op.outputs.items():
                    env.update(zip(ns, outs.get(s, ())))
            return env[out_name]
        fn = jax.jit(run)
        return (_stablehlo_ops(fn.lower(*vals.values()).as_text()),
                np.asarray(fn(*vals.values())))

    chain_ops, chain_out = lower(chain)
    fused_ops, fused_out = lower([fused_op])
    assert fused_ops == chain_ops
    np.testing.assert_array_equal(fused_out, chain_out)


# ---------------------------------------------------------------------------
# executor plan invalidation
# ---------------------------------------------------------------------------

def test_flag_flip_invalidates_executor_plan():
    scope = Scope()
    with scope_guard(scope), program_guard(Program(), Program()):
        _mm, loss = _build_dense_toy()
        prog = pt.default_main_program()
        exe = pt.Executor()
        exe.run(pt.default_startup_program(), scope=scope, seed=7)
        feed = _dense_feed()
        exe.reset_dispatch_stats()
        exe.run(prog, feed=feed, fetch_list=[loss.name], scope=scope,
                seed=SEED)
        exe.run(prog, feed=feed, fetch_list=[loss.name], scope=scope,
                seed=SEED + 1)
        s = exe.dispatch_stats()
        assert s["traces"] == 1 and s["cache_hits"] >= 1
        # flipping the fusion gate must MISS the plan and re-lower (a
        # stale plan would keep dispatching the fused executable)
        pt.set_flags({"FLAGS_graph_fusion": False})
        exe.run(prog, feed=feed, fetch_list=[loss.name], scope=scope,
                seed=SEED + 2)
        s2 = exe.dispatch_stats()
        assert s2["traces"] == 2


def test_frozen_addend_keeps_grad_alignment():
    """A stop-gradient addend (here a fed position tensor) must keep its
    '' placeholder in the fused grad op's IG$Addends name list — the
    generic-grad convention zips gradients against names POSITIONALLY,
    so dropping the placeholder would hand a surviving addend its
    neighbor's gradient (review finding)."""
    scope = Scope()
    with scope_guard(scope), program_guard(Program(), Program()):
        src = layers.data("src", shape=[6], dtype="int64")
        posv = layers.data("posv", shape=[6, 8], dtype="float32")
        emb = layers.embedding(src, size=[30, 8])
        x = layers.layer_norm(emb + posv, begin_norm_axis=2)
        loss = layers.mean(x * x)
        opt.SGDOptimizer(learning_rate=0.05).minimize(loss)
        prog = pt.default_main_program()
        fused = fusion.fuse_program(prog, ())
        types = [op.type for op in fused.global_block().ops]
        assert "fused_embedding_layer_norm" in types
        gop = next(op for op in fused.global_block().ops
                   if op.type == "fused_embedding_layer_norm_grad")
        # the fed addend carries no gradient: placeholder preserved
        assert gop.outputs.get("IG$Addends") == [""]

        # and the fused program trains bit-identically to the unfused
        exe0 = pt.Executor()
        exe0.run(pt.default_startup_program(), scope=scope, seed=7)
        snap = _snapshot(scope)
        rng = np.random.RandomState(3)
        feed = {"src": rng.randint(0, 30, (2, 6)).astype(np.int64),
                "posv": rng.rand(2, 6, 8).astype(np.float32)}

        class _L:
            name = loss.name
        out = {}
        for fuse_on in (False, True):
            pt.set_flags({"FLAGS_graph_fusion": fuse_on})
            for n, v in snap.items():
                scope.set_var(n, np.copy(v))
            out[fuse_on] = _run_steps(prog, _L, scope, feed)
        assert out[False] == out[True]
