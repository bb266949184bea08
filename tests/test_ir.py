"""Graph IR + pass tests (ref SURVEY §2.2; test style mirrors the
reference's per-pass testers, e.g. ir/fc_fuse_pass_tester.cc which builds a
tiny program, applies the pass, and counts nodes)."""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.framework import Executor, ir
from paddle_tpu.framework.core import Program, program_guard


def _fresh():
    return program_guard(Program(), Program())


def test_graph_build_and_topo():
    with _fresh():
        x = layers.data("x", shape=[4], dtype="float32")
        y = layers.fc(x, size=3)
        g = ir.Graph(fluid.default_main_program())
        assert len(g.ops_of_type("mul")) == 1
        assert len(g.ops_of_type("elementwise_add")) == 1
        order = [n.name for n in g.topology_sort()]
        assert order.index("mul") < order.index("elementwise_add")


def test_graph_to_program_roundtrip_executes():
    with _fresh():
        x = layers.data("x", shape=[4], dtype="float32")
        out = layers.fc(x, size=3, act="relu")
        g = ir.Graph(fluid.default_main_program())
        prog2 = g.to_program()
        exe = Executor()
        exe.run(fluid.default_startup_program())
        xv = np.random.RandomState(0).rand(2, 4).astype(np.float32)
        r1, = exe.run(feed={"x": xv}, fetch_list=[out])
        r2, = exe.run(prog2, feed={"x": xv}, fetch_list=[out.name])
        np.testing.assert_allclose(r1, r2, rtol=1e-6)


def test_fc_fuse_pass_counts_and_executes():
    with _fresh():
        x = layers.data("x", shape=[4], dtype="float32")
        h = layers.fc(x, size=8, act="relu")
        out = layers.fc(h, size=3)
        g = ir.Graph(fluid.default_main_program())
        g = ir.get_pass("fc_fuse_pass").apply(g)
        assert g.attrs["fc_fuse_count"] == 2
        assert len(g.ops_of_type("fc")) == 2
        assert not g.ops_of_type("mul")
        # the act was folded into the first fc
        fcs = g.ops_of_type("fc")
        acts = sorted(n.op.attrs["activation_type"] for n in fcs)
        assert acts == ["", "relu"]
        prog2 = g.to_program()
        exe = Executor()
        exe.run(fluid.default_startup_program())
        xv = np.random.RandomState(1).rand(2, 4).astype(np.float32)
        r1, = exe.run(feed={"x": xv}, fetch_list=[out])
        r2, = exe.run(prog2, feed={"x": xv}, fetch_list=[out.name])
        np.testing.assert_allclose(r1, r2, rtol=1e-5)


def test_fc_fuse_skips_multi_consumer_intermediate():
    with _fresh():
        x = layers.data("x", shape=[4], dtype="float32")
        w = layers.create_parameter([4, 3], "float32", name="w_mc")
        b = layers.create_parameter([3], "float32", name="b_mc")
        mul_out = layers.mul(x, w)
        added = layers.elementwise_add(mul_out, b)
        # second consumer of mul_out: fusing would lose it
        extra = layers.scale(mul_out, scale=2.0)
        g = ir.Graph(fluid.default_main_program())
        g = ir.get_pass("fc_fuse_pass").apply(g)
        assert g.attrs["fc_fuse_count"] == 0


def test_fuse_elewise_add_act():
    with _fresh():
        x = layers.data("x", shape=[4], dtype="float32")
        y = layers.data("y", shape=[4], dtype="float32")
        out = layers.relu(layers.elementwise_add(x, y))
        g = ir.Graph(fluid.default_main_program())
        g = ir.get_pass("fuse_elewise_add_act_pass").apply(g)
        assert g.attrs["fuse_elewise_add_act_count"] == 1
        prog2 = g.to_program()
        exe = Executor()
        xv = np.random.randn(2, 4).astype(np.float32)
        yv = np.random.randn(2, 4).astype(np.float32)
        r2, = exe.run(prog2, feed={"x": xv, "y": yv},
                      fetch_list=[out.name])
        np.testing.assert_allclose(r2, np.maximum(xv + yv, 0), rtol=1e-6)


def test_conv_bn_fuse_numeric():
    with _fresh():
        img = layers.data("img", shape=[3, 8, 8], dtype="float32")
        conv = layers.conv2d(img, num_filters=4, filter_size=3,
                             bias_attr=False)
        out = layers.batch_norm(conv, is_test=True)
        prog = fluid.default_main_program().clone(for_test=True)
        exe = Executor()
        exe.run(fluid.default_startup_program())
        scope = fluid.global_scope()
        # make BN stats non-trivial
        bn_op = next(op for op in prog.global_block().ops
                     if op.type == "batch_norm")
        scope.set_var(bn_op.input("Mean")[0],
                      np.random.RandomState(2).rand(4).astype(np.float32))
        xv = np.random.RandomState(3).rand(2, 3, 8, 8).astype(np.float32)
        r1, = exe.run(prog, feed={"img": xv}, fetch_list=[out.name])
        g = ir.Graph(prog)
        g = ir.get_pass("conv_bn_fuse_pass", scope=scope).apply(g)
        assert g.attrs["conv_bn_fuse_count"] == 1
        assert not g.ops_of_type("batch_norm")
        prog2 = g.to_program()
        r2, = exe.run(prog2, feed={"img": xv}, fetch_list=[out.name])
        np.testing.assert_allclose(r1, r2, rtol=1e-4, atol=1e-5)


def test_memory_passes_and_viz(tmp_path):
    with _fresh():
        x = layers.data("x", shape=[4], dtype="float32")
        out = layers.fc(x, size=3, act="relu")
        g = ir.Graph(fluid.default_main_program())
        g = ir.get_pass("buffer_shared_inplace_pass").apply(g)
        assert g.attrs["last_use"], "liveness table empty"
        assert any(pair for pair in g.attrs["inplace_pairs"])
        path = str(tmp_path / "g.dot")
        g = ir.get_pass("graph_viz_pass", graph_viz_path=path).apply(g)
        dot = open(path).read()
        assert "digraph" in dot and 'label="mul" shape=box' in dot


def test_pass_builder_pipeline():
    with _fresh():
        x = layers.data("x", shape=[4], dtype="float32")
        layers.fc(x, size=3, act="relu")
        pb = ir.PassBuilder()
        pb.append_pass("fc_fuse_pass")
        pb.append_pass("graph_to_program_pass")
        g = pb.apply(ir.Graph(fluid.default_main_program()))
        prog = g.attrs["program"]
        assert any(op.type == "fc" for op in prog.global_block().ops)
    with pytest.raises(KeyError):
        ir.get_pass("no_such_pass")


def test_training_program_fusion_preserves_grads():
    """Fusion must not fire when the intermediate is consumed by backward."""
    with _fresh():
        x = layers.data("x", shape=[4], dtype="float32")
        label = layers.data("label", shape=[1], dtype="float32")
        pred = layers.fc(x, size=1)
        loss = layers.mean(layers.square_error_cost(pred, label))
        fluid.optimizer.SGD(0.1).minimize(loss)
        prog = fluid.default_main_program()
        g = ir.Graph(prog)
        g = ir.get_pass("fuse_elewise_add_act_pass").apply(g)
        prog2 = g.to_program()
        exe = Executor()
        exe.run(fluid.default_startup_program())
        rng = np.random.RandomState(0)
        last = None
        for _ in range(15):
            xv = rng.rand(8, 4).astype(np.float32)
            yv = (xv.sum(1, keepdims=True)).astype(np.float32)
            last, = exe.run(prog2, feed={"x": xv, "label": yv},
                            fetch_list=[loss.name])
        assert float(last) < 1.0, "training through passed program diverged"


def test_fuse_add_gelu_and_scale_bias_numeric():
    with _fresh():
        x = layers.data("x", shape=[4], dtype="float32")
        y = layers.data("y", shape=[4], dtype="float32")
        g1 = layers.gelu(layers.elementwise_add(x, y))
        s1 = layers.scale(layers.elementwise_add(x, y), scale=2.0, bias=1.0)
        prog = fluid.default_main_program()
        g = ir.Graph(prog)
        g = ir.get_pass("fuse_elewise_add_act_pass").apply(g)
        assert g.attrs["fuse_elewise_add_act_count"] == 2
        prog2 = g.to_program()
        exe = Executor()
        xv = np.full((2, 4), 1.0, np.float32)
        yv = np.full((2, 4), 1.0, np.float32)
        a, b = exe.run(prog2, feed={"x": xv, "y": yv},
                       fetch_list=[g1.name, s1.name])
        np.testing.assert_allclose(b, np.full((2, 4), 5.0), rtol=1e-6)
        import math
        ref = 2 * 0.5 * (1 + math.erf(2 / math.sqrt(2)))
        np.testing.assert_allclose(a, np.full((2, 4), ref), rtol=1e-5)


def test_fetched_intermediate_survives_fusion():
    from paddle_tpu.compiler import CompiledProgram
    with _fresh():
        x = layers.data("x", shape=[4], dtype="float32")
        y = layers.data("y", shape=[4], dtype="float32")
        mid = layers.elementwise_add(x, y)
        out = layers.relu(mid)
        cp = CompiledProgram(fluid.default_main_program())
        exe = Executor()
        xv = np.random.randn(2, 4).astype(np.float32)
        yv = np.random.randn(2, 4).astype(np.float32)
        m, o = exe.run(cp, feed={"x": xv, "y": yv},
                       fetch_list=[mid, out])
        np.testing.assert_allclose(m, xv + yv, rtol=1e-6)
        np.testing.assert_allclose(o, np.maximum(xv + yv, 0), rtol=1e-6)
        # without the intermediate fetched, fusion may fire; same numerics
        o2, = exe.run(cp, feed={"x": xv, "y": yv}, fetch_list=[out])
        np.testing.assert_allclose(o2, o, rtol=1e-6)


def test_fc_fuse_binds_slots_not_roles():
    with _fresh():
        # mul with PERSISTABLE X and non-persistable Y: must not fuse into
        # fc with swapped operands
        xp = layers.create_parameter([2, 4], "float32", name="xp_slot")
        y = layers.data("yy", shape=[4, 3], dtype="float32")
        b = layers.create_parameter([3], "float32", name="b_slot")
        out = layers.elementwise_add(layers.mul(xp, y), b)
        g = ir.Graph(fluid.default_main_program())
        g = ir.get_pass("fc_fuse_pass").apply(g)
        assert g.attrs["fc_fuse_count"] == 0


def test_attention_fuse_pass_rewrites_and_matches():
    """QKᵀ→softmax→PV chains rewrite to one flash_attention op at load
    time (TPU-native pass; crossover gate at min_seq_len), numerically
    identical on the CPU fallback path."""
    import numpy as np
    import paddle_tpu as pt
    from paddle_tpu import layers
    from paddle_tpu.framework import Executor, Program, program_guard
    from paddle_tpu.framework.scope import Scope, scope_guard
    from paddle_tpu.framework import ir

    B, H, T, D = 2, 2, 32, 8
    rng = np.random.RandomState(0)
    qv, kv, vv = (rng.randn(B, H, T, D).astype(np.float32) * 0.3
                  for _ in range(3))

    scope = Scope()
    with scope_guard(scope), program_guard(Program(), Program()):
        q = layers.data("q", shape=[H, T, D], dtype="float32")
        k = layers.data("k", shape=[H, T, D], dtype="float32")
        v = layers.data("v", shape=[H, T, D], dtype="float32")
        scores = layers.matmul(q, k, transpose_y=True, alpha=0.25)
        probs = layers.softmax(scores)
        out = layers.matmul(probs, v)
        marker = layers.scale(out, scale=1.0)
        prog = pt.default_main_program()

        exe = Executor()
        exe.run(pt.default_startup_program(), scope=scope)
        feed = {"q": qv, "k": kv, "v": vv}
        want, = exe.run(prog, feed=feed, fetch_list=[marker.name],
                        scope=scope)

        g = ir.Graph(prog.clone())
        g = ir.get_pass("attention_fuse_pass", min_seq_len=16).apply(g)
        assert g.attrs["attention_fuse_count"] == 1
        fused = g.to_program()
        types = [op.type for op in fused.global_block().ops]
        assert "flash_attention" in types
        assert "softmax" not in types

        got, = exe.run(fused, feed=feed, fetch_list=[marker.name],
                       scope=scope)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)

    # below the crossover the pass must leave the program alone
    with scope_guard(Scope()), program_guard(Program(), Program()):
        q = layers.data("q", shape=[H, T, D], dtype="float32")
        k = layers.data("k", shape=[H, T, D], dtype="float32")
        v = layers.data("v", shape=[H, T, D], dtype="float32")
        out = layers.matmul(layers.softmax(
            layers.matmul(q, k, transpose_y=True, alpha=0.25)), v)
        g2 = ir.Graph(pt.default_main_program())
        g2 = ir.get_pass("attention_fuse_pass", min_seq_len=1024).apply(g2)
        assert g2.attrs["attention_fuse_count"] == 0


def test_attention_fuse_pass_causal_and_cross():
    """Decoder-shaped chains: a frozen persistable causal mask flips the
    fused op to causal=True (Bias dropped — the kernel skips masked key
    blocks), and a rectangular cross-attention chain (Tq != Tk) fuses
    through the same pattern.  Parity against the dense program."""
    import numpy as np
    import paddle_tpu as pt
    from paddle_tpu import initializer
    from paddle_tpu import layers
    from paddle_tpu.framework import Executor, Program, program_guard
    from paddle_tpu.framework.scope import Scope, scope_guard
    from paddle_tpu.framework import ir

    B, H, T, TK, D = 2, 2, 32, 48, 8
    rng = np.random.RandomState(3)
    qv, kv, vv = (rng.randn(B, H, T, D).astype(np.float32) * 0.3
                  for _ in range(3))
    ek, ev = (rng.randn(B, H, TK, D).astype(np.float32) * 0.3
              for _ in range(2))
    mask_np = np.triu(np.full((T, T), -1e9, np.float32), k=1)

    scope = Scope()
    with scope_guard(scope), program_guard(Program(), Program()):
        q = layers.data("q", shape=[H, T, D], dtype="float32")
        k = layers.data("k", shape=[H, T, D], dtype="float32")
        v = layers.data("v", shape=[H, T, D], dtype="float32")
        enc_k = layers.data("enc_k", shape=[H, TK, D], dtype="float32")
        enc_v = layers.data("enc_v", shape=[H, TK, D], dtype="float32")
        mask = layers.create_parameter(
            [T, T], "float32", name="causal_mask",
            default_initializer=initializer.NumpyArrayInitializer(mask_np))
        mask.stop_gradient = True
        # causal self-attention (dist_transformer.py decoder recipe)
        scores = layers.matmul(q, k, transpose_y=True, alpha=0.25)
        probs = layers.softmax(scores + mask)
        self_out = layers.matmul(probs, v)
        # cross-attention onto the (longer) encoder sequence
        scores2 = layers.matmul(self_out, enc_k, transpose_y=True,
                                alpha=0.25)
        cross_out = layers.matmul(layers.softmax(scores2), enc_v)
        marker = layers.scale(cross_out, scale=1.0)
        prog = pt.default_main_program()

        exe = Executor()
        exe.run(pt.default_startup_program(), scope=scope)
        feed = {"q": qv, "k": kv, "v": vv, "enc_k": ek, "enc_v": ev}
        want, = exe.run(prog, feed=feed, fetch_list=[marker.name],
                        scope=scope)

        g = ir.Graph(prog.clone())
        g = ir.get_pass("attention_fuse_pass", min_seq_len=16,
                        scope=scope).apply(g)
        assert g.attrs["attention_fuse_count"] == 2
        fused = g.to_program()
        flash = [op for op in fused.global_block().ops
                 if op.type == "flash_attention"]
        assert len(flash) == 2
        causal_flags = sorted(bool(op.attrs.get("causal")) for op in flash)
        assert causal_flags == [False, True]
        for op in flash:
            if op.attrs.get("causal"):
                assert not op.input("Bias"), \
                    "causal rewrite must drop the frozen mask input"
        assert "softmax" not in [op.type for op in fused.global_block().ops]

        got, = exe.run(fused, feed=feed, fetch_list=[marker.name],
                       scope=scope)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_attention_fuse_pass_keeps_noncausal_bias_and_axis_gates():
    """A generic (non-causal) additive bias must ride into the kernel's
    Bias input unchanged, and a softmax over a non-last axis must NOT be
    rewritten (the r3 advisor's mis-fusion window)."""
    import numpy as np
    import paddle_tpu as pt
    from paddle_tpu import layers
    from paddle_tpu.framework import Executor, Program, program_guard
    from paddle_tpu.framework.scope import Scope, scope_guard
    from paddle_tpu.framework import ir

    B, H, T, D = 2, 2, 32, 8
    rng = np.random.RandomState(5)
    qv, kv, vv = (rng.randn(B, H, T, D).astype(np.float32) * 0.3
                  for _ in range(3))
    bias_np = rng.randn(B, H, T, T).astype(np.float32) * 0.1

    scope = Scope()
    with scope_guard(scope), program_guard(Program(), Program()):
        q = layers.data("q", shape=[H, T, D], dtype="float32")
        k = layers.data("k", shape=[H, T, D], dtype="float32")
        v = layers.data("v", shape=[H, T, D], dtype="float32")
        bias = layers.data("bias", shape=[H, T, T], dtype="float32")
        scores = layers.matmul(q, k, transpose_y=True, alpha=0.25)
        out = layers.matmul(layers.softmax(scores + bias), v)
        marker = layers.scale(out, scale=1.0)
        prog = pt.default_main_program()
        exe = Executor()
        feed = {"q": qv, "k": kv, "v": vv, "bias": bias_np}
        want, = exe.run(prog, feed=feed, fetch_list=[marker.name],
                        scope=scope)
        g = ir.Graph(prog.clone())
        g = ir.get_pass("attention_fuse_pass", min_seq_len=16,
                        scope=scope).apply(g)
        assert g.attrs["attention_fuse_count"] == 1
        fused = g.to_program()
        fl = [op for op in fused.global_block().ops
              if op.type == "flash_attention"]
        assert fl and fl[0].input("Bias") and not fl[0].attrs.get("causal")
        got, = exe.run(fused, feed=feed, fetch_list=[marker.name],
                       scope=scope)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    # non-last-axis softmax: no rewrite
    with scope_guard(Scope()), program_guard(Program(), Program()):
        q = layers.data("q", shape=[H, T, D], dtype="float32")
        k = layers.data("k", shape=[H, T, D], dtype="float32")
        v = layers.data("v", shape=[H, T, D], dtype="float32")
        scores = layers.matmul(q, k, transpose_y=True, alpha=0.25)
        out = layers.matmul(layers.softmax(scores, axis=2), v)
        g2 = ir.Graph(pt.default_main_program())
        g2 = ir.get_pass("attention_fuse_pass", min_seq_len=16).apply(g2)
        assert g2.attrs["attention_fuse_count"] == 0


def test_serving_fusion_passes():
    """The four serving-path canonicalization passes (ref
    ir/*_fuse_pass.cc families): pattern counts + numeric parity."""
    import numpy as np
    import paddle_tpu as pt
    from paddle_tpu import layers
    from paddle_tpu.framework import Executor, Program, program_guard, ir
    from paddle_tpu.framework.scope import Scope, scope_guard

    rng = np.random.RandomState(2)

    # -- repeated fc+relu chain ------------------------------------------
    scope = Scope()
    with scope_guard(scope), program_guard(Program(), Program()):
        x = layers.data("x", shape=[8], dtype="float32")
        h = x
        for i in range(3):
            h = layers.fc(h, size=8, act="relu")
        marker = layers.scale(h, scale=1.0)
        exe = Executor()
        exe.run(pt.default_startup_program(), scope=scope, seed=1)
        feed = {"x": rng.rand(4, 8).astype(np.float32)}
        want, = exe.run(feed=feed, fetch_list=[marker.name], scope=scope)
        g = ir.Graph(pt.default_main_program().clone())
        g = ir.get_pass("fc_fuse_pass").apply(g)
        assert g.attrs["fc_fuse_count"] == 3
        g = ir.get_pass("repeated_fc_relu_fuse_pass").apply(g)
        assert g.attrs["repeated_fc_relu_fuse_count"] == 1
        fused = g.to_program()
        types = [o.type for o in fused.global_block().ops]
        assert types.count("fusion_repeated_fc_relu") == 1
        assert "fc" not in types
        got, = exe.run(fused, feed=feed, fetch_list=[marker.name],
                       scope=scope)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)

    # -- squared mat sub -------------------------------------------------
    with scope_guard(Scope()), program_guard(Program(), Program()):
        a = layers.data("a", shape=[4], dtype="float32")
        b = layers.data("b", shape=[4, 6], dtype="float32",
                        append_batch_size=False)
        xy = layers.matmul(a, b)
        out = layers.scale(
            layers.square(xy) - layers.matmul(layers.square(a),
                                              layers.square(b)),
            scale=0.5)
        marker = layers.scale(out, scale=1.0)
        exe = Executor()
        feed = {"a": rng.rand(3, 4).astype(np.float32),
                "b": rng.rand(4, 6).astype(np.float32)}
        want, = exe.run(feed=feed, fetch_list=[marker.name],
                        scope=pt.global_scope())
        g = ir.Graph(pt.default_main_program().clone())
        g = ir.get_pass("squared_mat_sub_fuse_pass").apply(g)
        assert g.attrs["squared_mat_sub_fuse_count"] == 1
        fused = g.to_program()
        assert "fusion_squared_mat_sub" in \
            [o.type for o in fused.global_block().ops]
        got, = exe.run(fused, feed=feed, fetch_list=[marker.name],
                       scope=pt.global_scope())
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)

    # -- transpose + flatten + concat ------------------------------------
    with scope_guard(Scope()), program_guard(Program(), Program()):
        u = layers.data("u", shape=[2, 3, 4], dtype="float32")
        v = layers.data("v", shape=[2, 5, 4], dtype="float32")
        flat = [layers.flatten(layers.transpose(t, perm=[0, 2, 3, 1]))
                for t in (u, v)]
        out = layers.concat(flat, axis=1)
        marker = layers.scale(out, scale=1.0)
        exe = Executor()
        feed = {"u": rng.rand(2, 2, 3, 4).astype(np.float32),
                "v": rng.rand(2, 2, 5, 4).astype(np.float32)}
        want, = exe.run(feed=feed, fetch_list=[marker.name],
                        scope=pt.global_scope())
        g = ir.Graph(pt.default_main_program().clone())
        g = ir.get_pass("transpose_flatten_concat_fuse_pass").apply(g)
        assert g.attrs["transpose_flatten_concat_fuse_count"] == 1
        fused = g.to_program()
        assert "fusion_transpose_flatten_concat" in \
            [o.type for o in fused.global_block().ops]
        got, = exe.run(fused, feed=feed, fetch_list=[marker.name],
                       scope=pt.global_scope())
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)

    # -- seqpool + concat -------------------------------------------------
    with scope_guard(Scope()), program_guard(Program(), Program()):
        u = layers.data("u", shape=[5, 3], dtype="float32")
        v = layers.data("v", shape=[7, 3], dtype="float32")
        pooled = [layers.sequence_pool(t, pool_type="sum")
                  for t in (u, v)]
        out = layers.concat(pooled, axis=1)
        marker = layers.scale(out, scale=1.0)
        exe = Executor()
        feed = {"u": rng.rand(2, 5, 3).astype(np.float32),
                "v": rng.rand(2, 7, 3).astype(np.float32)}
        want, = exe.run(feed=feed, fetch_list=[marker.name],
                        scope=pt.global_scope())
        g = ir.Graph(pt.default_main_program().clone())
        g = ir.get_pass("seqpool_concat_fuse_pass").apply(g)
        assert g.attrs["seqpool_concat_fuse_count"] == 1
        fused = g.to_program()
        assert "fusion_seqpool_concat" in \
            [o.type for o in fused.global_block().ops]
        got, = exe.run(fused, feed=feed, fetch_list=[marker.name],
                       scope=pt.global_scope())
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)


def test_fc_gru_lstm_fuse_numeric():
    """fc→gru / fc→lstm collapse onto fusion_gru / fusion_lstm with the fc
    bias folded into the gate bias (ref ir/fc_gru_fuse_pass.cc,
    fc_lstm_fuse_pass.cc) — loss-free rewrite checked numerically."""
    from paddle_tpu.layers import compat as rnn_layers
    with _fresh():
        x = layers.data("x", shape=[5, 6], dtype="float32")
        H = 4
        proj_g = layers.fc(x, size=3 * H, num_flatten_dims=2)
        hidden_g = rnn_layers.dynamic_gru(proj_g, size=H)
        proj_l = layers.fc(x, size=4 * H, num_flatten_dims=2)
        hidden_l, _cell = rnn_layers.dynamic_lstm(
            proj_l, size=4 * H, use_peepholes=True)
        out = layers.concat([hidden_g, hidden_l], axis=2)
        prog = fluid.default_main_program().clone(for_test=True)
        exe = Executor()
        exe.run(fluid.default_startup_program(), seed=3)
        scope = fluid.global_scope()
        xv = np.random.RandomState(5).randn(2, 5, 6).astype(np.float32)
        r1, = exe.run(prog, feed={"x": xv}, fetch_list=[out.name])
        g = ir.Graph(prog)
        g = ir.get_pass("fc_fuse_pass").apply(g)
        assert g.attrs["fc_fuse_count"] == 2
        g = ir.get_pass("fc_gru_fuse_pass", scope=scope).apply(g)
        g = ir.get_pass("fc_lstm_fuse_pass", scope=scope).apply(g)
        assert g.attrs["fc_gru_fuse_count"] == 1
        assert g.attrs["fc_lstm_fuse_count"] == 1
        assert not g.ops_of_type("gru") and not g.ops_of_type("lstm")
        assert not g.ops_of_type("fc")
        r2, = exe.run(g.to_program(), feed={"x": xv},
                      fetch_list=[out.name])
        np.testing.assert_allclose(r1, r2, rtol=1e-4, atol=1e-5)


def test_embedding_fc_lstm_fuse_numeric():
    """lookup_table→fc→lstm becomes one fused_embedding_fc_lstm whose
    table is pre-multiplied emb·W+b (ref ir/embedding_fc_lstm_fuse_pass
    .cc); the row gather replaces the projection matmul exactly."""
    from paddle_tpu.layers import compat as rnn_layers
    with _fresh():
        ids = layers.data("ids", shape=[5, 1], dtype="int64")
        H = 3
        emb = layers.embedding(ids, size=[11, 6])
        proj = layers.fc(emb, size=4 * H, num_flatten_dims=2)
        hidden, _cell = rnn_layers.dynamic_lstm(
            proj, size=4 * H, use_peepholes=False)
        prog = fluid.default_main_program().clone(for_test=True)
        exe = Executor()
        exe.run(fluid.default_startup_program(), seed=9)
        scope = fluid.global_scope()
        iv = np.random.RandomState(7).randint(0, 11, (2, 5, 1)).astype(
            np.int64)
        r1, = exe.run(prog, feed={"ids": iv}, fetch_list=[hidden.name])
        g = ir.Graph(prog)
        g = ir.get_pass("fc_fuse_pass").apply(g)
        g = ir.get_pass("embedding_fc_lstm_fuse_pass", scope=scope).apply(g)
        assert g.attrs["embedding_fc_lstm_fuse_count"] == 1
        assert not g.ops_of_type("lookup_table")
        assert not g.ops_of_type("lstm") and not g.ops_of_type("fc")
        r2, = exe.run(g.to_program(), feed={"ids": iv},
                      fetch_list=[hidden.name])
        np.testing.assert_allclose(r1, r2, rtol=1e-4, atol=1e-5)


def test_conv_eltwise_add_act_fuse_numeric():
    """conv2d + channel bias + relu folds onto conv2d_fusion
    (ref ir/conv_elementwise_add_act_fuse_pass.cc)."""
    with _fresh():
        img = layers.data("img", shape=[3, 8, 8], dtype="float32")
        out = layers.conv2d(img, num_filters=4, filter_size=3, act="relu")
        prog = fluid.default_main_program().clone(for_test=True)
        exe = Executor()
        exe.run(fluid.default_startup_program(), seed=11)
        xv = np.random.RandomState(13).randn(2, 3, 8, 8).astype(np.float32)
        r1, = exe.run(prog, feed={"img": xv}, fetch_list=[out.name])
        g = ir.Graph(prog)
        g = ir.get_pass("conv_elementwise_add_act_fuse_pass").apply(g)
        assert g.attrs["conv_elementwise_add_act_fuse_count"] == 1
        assert not g.ops_of_type("conv2d")
        assert not g.ops_of_type("relu")
        r2, = exe.run(g.to_program(), feed={"img": xv},
                      fetch_list=[out.name])
        np.testing.assert_allclose(r1, r2, rtol=1e-4, atol=1e-5)


def test_seqconv_eltadd_relu_fuse_numeric():
    """sequence_conv + bias + relu folds onto fusion_seqconv_eltadd_relu
    (ref ir/seqconv_eltadd_relu_fuse_pass.cc)."""
    from paddle_tpu.layers import sequence as seq_layers
    with _fresh():
        x = layers.data("x", shape=[7, 5], dtype="float32")
        out = seq_layers.sequence_conv(x, num_filters=6, filter_size=3,
                                       act="relu")
        prog = fluid.default_main_program().clone(for_test=True)
        exe = Executor()
        exe.run(fluid.default_startup_program(), seed=17)
        xv = np.random.RandomState(19).randn(2, 7, 5).astype(np.float32)
        r1, = exe.run(prog, feed={"x": xv}, fetch_list=[out.name])
        g = ir.Graph(prog)
        g = ir.get_pass("seqconv_eltadd_relu_fuse_pass").apply(g)
        assert g.attrs["seqconv_eltadd_relu_fuse_count"] == 1
        assert not g.ops_of_type("sequence_conv")
        r2, = exe.run(g.to_program(), feed={"x": xv},
                      fetch_list=[out.name])
        np.testing.assert_allclose(r1, r2, rtol=1e-4, atol=1e-5)
