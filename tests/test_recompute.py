"""Activation rematerialization (framework/recompute.py; no reference
counterpart — SURVEY §5.7 notes the 2019 codebase has no recompute)."""

import collections
import hashlib
import os
import re
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.framework import Executor, name_scope
from paddle_tpu.framework.core import Program, program_guard
from paddle_tpu.framework.executor import op_scope
from paddle_tpu.framework.recompute import (RECOMPUTE_OPS_CTR,
                                            RECOMPUTED_ATTR, apply_recompute)
from paddle_tpu.framework.scope import Scope, scope_guard


def _build(n_layers=3):
    x = layers.data("x", shape=[16], dtype="float32")
    y = layers.data("y", shape=[1], dtype="float32")
    h = x
    ckpts = []
    for i in range(n_layers):
        h = layers.fc(h, size=16, act="tanh")
        ckpts.append(h)
    pred = layers.fc(h, size=1)
    loss = layers.mean(layers.square_error_cost(pred, y))
    return loss, ckpts


def _train(recompute, steps=10):
    with program_guard(Program(), Program()), scope_guard(Scope()):
        loss, ckpts = _build()
        opt = fluid.optimizer.Adam(0.01)
        if recompute:
            opt = fluid.optimizer.RecomputeOptimizer(opt)
            opt._set_checkpoints(ckpts)
        opt.minimize(loss)
        prog = fluid.default_main_program()
        exe = Executor()
        exe.run(fluid.default_startup_program(), seed=11)
        rng = np.random.RandomState(0)
        out = []
        for _ in range(steps):
            xv = rng.rand(8, 16).astype(np.float32)
            yv = xv.sum(1, keepdims=True).astype(np.float32)
            lv, = exe.run(feed={"x": xv, "y": yv}, fetch_list=[loss])
            out.append(float(lv))
        return out, prog


def _train_after_gradient(summed, steps=3):
    """``_train`` under ``after_gradient``; ``summed``: the loss also takes
    the product of a mean of each block's inside, so that a later segment
    reads what an earlier one makes again."""
    with program_guard(Program(), Program()), scope_guard(Scope()):
        x = layers.data("x", shape=[16], dtype="float32")
        y = layers.data("y", shape=[1], dtype="float32")
        h, ckpts, term = x, [x], None
        x.stop_gradient = False
        for _ in range(3):
            inside = layers.fc(h, size=16, act="tanh")
            if summed:
                m = layers.mean(inside)
                term = m if term is None else term * m
            h = layers.fc(inside, size=16)
            ckpts.append(h)
        loss = layers.mean(layers.square_error_cost(layers.fc(h, size=1), y))
        if summed:
            loss = loss + term
        opt = fluid.optimizer.RecomputeOptimizer(fluid.optimizer.Adam(0.01))
        opt._set_checkpoints(ckpts, after_gradient=True)
        opt.minimize(loss)
        exe = Executor()
        exe.run(fluid.default_startup_program(), seed=11)
        rng = np.random.RandomState(0)
        out = []
        for _ in range(steps):
            xv = rng.rand(8, 16).astype(np.float32)
            lv, = exe.run(feed={"x": xv, "y": xv.sum(1, keepdims=True)},
                          fetch_list=[loss])
            out.append(float(lv))
        return out, fluid.default_main_program()


def test_after_gradient_puts_each_segment_behind_its_gradient():
    out, prog = _train_after_gradient(False)
    assert np.all(np.isfinite(out)) and out[-1] < out[0]
    ops = prog.global_block().ops
    clones = [i for i, op in enumerate(ops)
              if op.attrs.get(RECOMPUTED_ATTR)
              and op.type != "optimization_barrier"]
    first_bwd = next(i for i, op in enumerate(ops)
                     if op.attrs.get("op_role") == "backward")
    # no longer one run of clones at the head of the backward
    assert clones and min(clones) > first_bwd + 1
    assert clones != list(range(clones[0], clones[0] + len(clones)))
    fences = [op for op in ops if op.type == "optimization_barrier"
              and len(op.inputs["X"]) == 2]
    assert fences and all(n.endswith("@GRAD")
                          for op in fences for n in op.inputs["X"][1:])


def test_after_gradient_refuses_a_segment_that_reads_an_earlier_ones():
    with pytest.raises(ValueError, match="earlier segment makes again"):
        _train_after_gradient(True)


def test_recompute_exact_parity():
    """Recompute must not change a single gradient: loss trajectories are
    bit-identical to the stored-activation run."""
    base, _ = _train(False)
    rc, prog = _train(True)
    np.testing.assert_allclose(base, rc, rtol=0, atol=0)
    types = [op.type for op in prog.global_block().ops]
    assert "optimization_barrier" in types
    assert any("@RECOMPUTE" in n for op in prog.global_block().ops
               for n in op.output_arg_names())


def test_recompute_replays_tagged_dropout():
    """Tagged dropout is replay-safe — its bits are a pure function of
    (per-step key, tag) — so recompute re-emits it instead of storing its
    output; untagged (seed=0) dropout stays stored."""
    with program_guard(Program(), Program()), scope_guard(Scope()):
        x = layers.data("x", shape=[16], dtype="float32")
        y = layers.data("y", shape=[1], dtype="float32")
        h = layers.fc(x, size=16, act="tanh")
        c1 = h
        h = layers.dropout(h, dropout_prob=0.5)        # tagged (default)
        h = layers.fc(h, size=16, act="tanh")
        pred = layers.fc(h, size=1)
        loss = layers.mean(layers.square_error_cost(pred, y))
        opt = fluid.optimizer.RecomputeOptimizer(fluid.optimizer.SGD(0.1))
        opt._set_checkpoints([c1])
        opt.minimize(loss)
        prog = fluid.default_main_program()
        recomputed = [op for op in prog.global_block().ops
                      if op.type == "dropout" and
                      any("@RECOMPUTE" in n for n in op.output_arg_names())]
        assert recomputed, "tagged dropout should re-emit in the remat chain"
        exe = Executor()
        exe.run(fluid.default_startup_program(), seed=3)
        rng = np.random.RandomState(1)
        last = None
        for _ in range(8):
            xv = rng.rand(8, 16).astype(np.float32)
            yv = xv.sum(1, keepdims=True).astype(np.float32)
            last, = exe.run(feed={"x": xv, "y": yv}, fetch_list=[loss])
        assert np.isfinite(float(last))


def test_recompute_keeps_untagged_dropout_stored():
    """seed=0 (legacy untagged) dropout draws from the counter stream, so
    re-drawing would change gradients — it must stay OUT of the chain."""
    from paddle_tpu.layer_helper import LayerHelper
    with program_guard(Program(), Program()), scope_guard(Scope()):
        x = layers.data("x", shape=[16], dtype="float32")
        y = layers.data("y", shape=[1], dtype="float32")
        h = layers.fc(x, size=16, act="tanh")
        c1 = h
        helper = LayerHelper("dropout")
        out = helper.create_variable_for_type_inference(h.dtype)
        mask = helper.create_variable_for_type_inference("uint8", True)
        helper.append_op("dropout", inputs={"X": [h]},
                         outputs={"Out": [out], "Mask": [mask]},
                         attrs={"dropout_prob": 0.5, "is_test": False,
                                "seed": 0,
                                "dropout_implementation":
                                    "downgrade_in_infer"})
        h = layers.fc(out, size=16, act="tanh")
        pred = layers.fc(h, size=1)
        loss = layers.mean(layers.square_error_cost(pred, y))
        opt = fluid.optimizer.RecomputeOptimizer(fluid.optimizer.SGD(0.1))
        opt._set_checkpoints([c1])
        opt.minimize(loss)
        prog = fluid.default_main_program()
        for op in prog.global_block().ops:
            if op.type == "dropout":
                assert not any("@RECOMPUTE" in n
                               for n in op.output_arg_names())


def test_backward_entry_point_applies_recompute():
    """The fluid-style backward()/apply_gradients flow must also remat."""
    with program_guard(Program(), Program()), scope_guard(Scope()):
        loss, ckpts = _build()
        opt = fluid.optimizer.RecomputeOptimizer(fluid.optimizer.SGD(0.1))
        opt._set_checkpoints(ckpts)
        pg = opt.backward(loss)
        opt.apply_gradients(pg)
        prog = fluid.default_main_program()
        types = [op.type for op in prog.global_block().ops]
        assert "optimization_barrier" in types
        # weights are NOT fenced (barriers only on stored activations)
        for op in prog.global_block().ops:
            if op.type == "optimization_barrier":
                src = op.input("X")[0]
                v = prog.global_block().vars.get(src)
                assert v is None or not v.persistable
        exe = Executor()
        exe.run(fluid.default_startup_program())
        xv = np.random.rand(4, 16).astype(np.float32)
        yv = xv.sum(1, keepdims=True).astype(np.float32)
        lv, = exe.run(feed={"x": xv, "y": yv}, fetch_list=[loss])
        assert np.isfinite(float(lv))


# -- the recomputed segments have a role of their own (PR 36) ------------------------

def _recomputed(op):
    return bool(op.attrs.get(RECOMPUTED_ATTR))


def _tagged_step(act="tanh", checkpoints=slice(None)):
    """``_build``'s net with its third layer under ``name_scope("tail")``,
    recomputed at ``checkpoints`` of the three layer outputs, Adam."""
    x = layers.data("x", shape=[16], dtype="float32")
    y = layers.data("y", shape=[1], dtype="float32")
    h, ckpts = x, []
    for i in range(3):
        if i == 2:
            with name_scope("tail"):
                h = layers.fc(h, size=16, act=act)
        else:
            h = layers.fc(h, size=16, act=act)
        ckpts.append(h)
    pred = layers.fc(h, size=1)
    loss = layers.mean(layers.square_error_cost(pred, y))
    opt = fluid.optimizer.RecomputeOptimizer(fluid.optimizer.Adam(0.01))
    opt._set_checkpoints(ckpts[checkpoints])
    opt.minimize(loss)
    return loss, fluid.default_main_program()


def test_clones_and_barriers_carry_the_mark_and_nothing_else_does():
    with program_guard(Program(), Program()), scope_guard(Scope()):
        _, prog = _tagged_step()
    ops = prog.global_block().ops
    emitted = [op for op in ops if op.type == "optimization_barrier" or any(
        "@RECOMPUTE" in n for n in op.output_arg_names())]
    assert emitted and all(_recomputed(op) for op in emitted)
    assert {op.type for op in emitted} >= {"optimization_barrier", "mul",
                                           "elementwise_add", "tanh"}
    rest = [op for op in ops if op not in emitted]
    assert not any(_recomputed(op) for op in rest)
    # the roles core.py prunes by and the fusion pass tests are as they were:
    # a clone has its original's (none), a grad op reads @RECOMPUTE values
    # and stays a backward op
    assert not any("op_role" in op.attrs for op in emitted)
    grads = [op for op in rest if op.attrs.get("op_role") == "backward"]
    assert any("@RECOMPUTE" in n for op in grads
               for n in op.input_arg_names())
    # clone(for_test) prunes by op_role as before: it keeps what it kept
    kept = prog.clone(for_test=True).global_block().ops
    assert sum(map(_recomputed, kept)) == len(emitted)


def test_a_marked_op_is_scoped_rc_with_its_tag_behind():
    with program_guard(Program(), Program()), scope_guard(Scope()):
        _, prog = _tagged_step()
    scopes = collections.Counter(op_scope(op)
                                 for op in prog.global_block().ops)
    # the first layer lies before the first checkpoint; the second's and the
    # third's pre-activations and the output layer are emitted again
    assert scopes["pt.rc/mul"] == 2 and scopes["pt.rc/mul/tail"] == 1
    assert scopes["pt.rc/tanh"] == 1 and scopes["pt.rc/tanh/tail"] == 1
    assert scopes["pt.rc/optimization_barrier"] >= 3
    assert scopes["pt.fwd/mul"] == 3 and scopes["pt.fwd/mul/tail"] == 1
    assert scopes["pt.bwd/mul_grad"] == 3
    assert scopes["pt.bwd/mul_grad/tail"] == 1
    assert not any(s.startswith("pt.rc/") and "_grad" in s for s in scopes)
    assert "pt.fwd/optimization_barrier" not in scopes


#: sha256 of the StableHLO text, locations stripped, of ``_tagged_step``'s
#: training step (CPU lowering) as the parent of PR 36 lowered it, when the
#: clones were scoped ``pt.fwd/*``: the role is metadata and nothing else.  A
#: PR that means to change what a recomputed step lowers to replaces it and
#: says so.
TOY_RECOMPUTED_STEP_SHA256 = (
    "1961409634e5d5fe5186f9914d07cccfd2a3d44a338b245a823ad879010d9c26")


def test_the_lowered_step_names_the_second_forward_and_is_the_parents():
    import jax.numpy as jnp
    with program_guard(Program(), Program()), scope_guard(Scope()) as _:
        loss, prog = _tagged_step()
        exe = Executor()
        exe.run(fluid.default_startup_program(), seed=11)
        rng = np.random.RandomState(0)
        xv = rng.rand(8, 16).astype(np.float32)
        feed = {"x": xv, "y": xv.sum(1, keepdims=True)}
        exe.run(feed=feed, fetch_list=[loss])
        scope = fluid.global_scope()
        cb = next(p for p in exe._plans.values()
                  if p.cb.fetch_names == (loss.name,)).cb
        args = ([jnp.asarray(feed[n]) for n in cb.feed_names],
                [scope.find_var(n) for n in cb.persist_ro],
                [scope.find_var(n) for n in cb.persist_rw], jnp.uint32(1))
        lowered = cb.jitted.lower(*args)
    named = set(re.findall(r"pt\.[a-z]+/[\w./]+", lowered.as_text(
        debug_info=True)))
    assert {"pt.rc/mul/dot_general", "pt.rc/mul/tail/dot_general",
            "pt.rc/elementwise_add/tail/add",
            "pt.rc/optimization_barrier/optimization_barrier"} <= named
    assert "pt.fwd/mul/dot_general" in named       # the first forward
    assert not any(n.startswith("pt.fwd/optimization_barrier")
                   for n in named)
    text = re.sub(r"loc\(.*?\)", "", lowered.as_text())
    assert "pt.rc" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == \
        TOY_RECOMPUTED_STEP_SHA256


def test_the_counter_counts_the_clones_by_type_once_per_transform():
    def counts():
        return {t: RECOMPUTE_OPS_CTR.value(op=t)
                for t in ("mul", "elementwise_add", "tanh", "mean",
                          "square_error_cost", "optimization_barrier")}
    with program_guard(Program(), Program()), scope_guard(Scope()):
        before = counts()
        loss, prog = _tagged_step()
        after = counts()
        clones = collections.Counter(
            op.type for op in prog.global_block().ops
            if _recomputed(op) and op.type != "optimization_barrier")
        assert {t: after[t] - before[t] for t in after} == {
            "mul": 3, "elementwise_add": 3, "tanh": 2, "mean": 1,
            "square_error_cost": 1, "optimization_barrier": 0}
        assert clones == {"mul": 3, "elementwise_add": 3, "tanh": 2,
                          "mean": 1, "square_error_cost": 1}
        # the wrapper applies the transform once to a program; a program
        # with nothing behind its checkpoint to emit again counts nothing
        opt = fluid.optimizer.RecomputeOptimizer(fluid.optimizer.SGD(0.1))
        opt._set_checkpoints([loss])
        opt._apply(prog)
        assert counts() == after
    with program_guard(Program(), Program()), scope_guard(Scope()):
        loss, _ = _build()
        fluid.optimizer.SGD(0.1).minimize(loss)
        apply_recompute(fluid.default_main_program(), [loss.name])
        assert counts() == after


def _fused(prog):
    from paddle_tpu.analysis import fusion
    return fusion.fuse_program(prog)


def test_the_fusion_pass_carries_the_mark_onto_what_it_fuses():
    """With grad ops in the program the pass fuses no recomputed chain (the
    clones have no grad chain to rewrite); in the test-mode clone, which
    keeps the emitted ops, it fuses them — and the fused op is recomputed
    work like the ops it replaces, the first layer's fused op is not."""
    with program_guard(Program(), Program()), scope_guard(Scope()):
        _, prog = _tagged_step(act="gelu", checkpoints=slice(1))
        prog = _fused(prog.clone(for_test=True))
    fused = [op for op in prog.global_block().ops
             if op.type == "fused_dense_act"]
    # (the pass hands no ``name_scope`` tag on, so the third layer's reads
    # without its ``tail``)
    assert collections.Counter(op_scope(op) for op in fused) == {
        "pt.rc/fused_dense_act": 2, "pt.fwd/fused_dense_act": 1}
    for op in fused:
        assert _recomputed(op) == any(
            "@RECOMPUTE" in n for n in op.output_arg_names())


def test_a_chain_of_both_kinds_is_refused_by_the_fusion_pass():
    with program_guard(Program(), Program()), scope_guard(Scope()):
        _, prog = _tagged_step(act="gelu", checkpoints=slice(1))
        test = prog.clone(for_test=True)
        clone_mul = next(op for op in test.global_block().ops
                         if op.type == "mul" and _recomputed(op))
        del clone_mul.attrs[RECOMPUTED_ATTR]
        with pytest.raises(AssertionError, match="recomputed and first-run"):
            _fused(test)


def test_joyais_recomputed_flash_ops_are_counted_as_they_are_lowered():
    """``paddle_tpu_recompute_ops_total{op="flash_attention"}`` after the
    JoyAI cell's program is built (toy widths: two blocks and the MTP module,
    checkpoints at the three block outputs, so the second block's and the
    module's flash ops are emitted again) against the ``pt.rc/
    flash_attention*`` scopes of its lowered step — one ``scan`` a flash
    forward on the CPU — and the first forward still reads ``pt.fwd``."""
    import jax.numpy as jnp
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "benchmark"))
    import test_joyai_cell as joyai
    from benchmark import harness
    from benchmark.models import _train
    config, traffic = joyai.toy_joyai()
    model = harness.load_module("models", "joyai_llm_flash")
    before = RECOMPUTE_OPS_CTR.value(op="flash_attention")
    m = model.build_train(config, traffic, 11, 1, False)
    counted = RECOMPUTE_OPS_CTR.value(op="flash_attention") - before
    assert counted == 2
    exe, scope = m["exe"], m["scope"]
    feed = _train.put_ring(m["ring"], 1)[0]
    exe.run(m["program"], feed=feed, fetch_list=[m["loss"]], scope=scope)
    plan = next(p for p in exe._plans.values()
                if p.cb.fetch_names == (m["loss"],))
    ops = plan.program.global_block().ops
    assert sum(op_scope(op).startswith("pt.rc/flash_attention")
               for op in ops) == counted
    cb = plan.cb
    args = ([feed[n] for n in cb.feed_names],
            [scope.find_var(n) for n in cb.persist_ro],
            [scope.find_var(n) for n in cb.persist_rw], jnp.uint32(1))
    jaxpr = cb.jitted.trace(*args).jaxpr
    scans = collections.Counter(
        s for s, p in joyai.program_scopes_test._eqn_scopes(
            getattr(jaxpr, "jaxpr", jaxpr)) if p == "scan"
        and re.fullmatch(r"pt\.[a-z]+/flash_attention(/[\w.]+)?", s))
    assert scans == {"pt.fwd/flash_attention": 2,
                     "pt.fwd/flash_attention/mtp": 1,
                     "pt.rc/flash_attention": 1,
                     "pt.rc/flash_attention/mtp": 1}
    # nothing is counted when the step runs again
    exe.run(m["program"], feed=feed, fetch_list=[m["loss"]], scope=scope)
    assert RECOMPUTE_OPS_CTR.value(op="flash_attention") - before == counted
