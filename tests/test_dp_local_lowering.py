"""Lowerings whose internals XLA's partitioner can only replicate run them
per batch shard under ``with_data_parallel`` (``framework.executor.per_dp_shard``):
the fused LM head's chunked scan and the dropout masks' ``rng-bit-generator``.

On the 8 virtual CPU devices of ``conftest.py``: arithmetic against the
single-device program, the compiled step's collectives, the masks'
properties, the fallbacks, and ``paddle_tpu_dp_local_lowerings_total``."""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, optimizer as opt
from paddle_tpu.framework import (Executor, Program, Scope, executor as E,
                                  program_guard, registry, scope_guard)
from paddle_tpu.models import transformer as T
from paddle_tpu.parallel import mesh as M

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))
import dp_arith_check  # noqa: E402  (tools/: the HLO's while loops)

SEQ, N_MASK, BATCH, DP = 16, 4, 8, 4


def _counts():
    c = E.DP_LOCAL_CTR
    return {k: c.value(op=k[0], engaged=k[1]) for k in list(c._series)}


def _delta(before):
    return {k: v - before.get(k, 0) for k, v in _counts().items()
            if v - before.get(k, 0)}


def _bert(dropout, chunk=None):
    cfg = T.BertConfig(vocab_size=64, d_model=16, n_layer=2, n_head=4,
                       d_inner=32, max_pos=32, dropout=dropout)
    scope, main, startup = Scope(), Program(), Program()
    with scope_guard(scope), program_guard(main, startup):
        _, _, loss = T.build_bert_pretrain(
            cfg, SEQ, fused_head=True, arange_pos=True, masked_gather=N_MASK)
        opt.SGDOptimizer(learning_rate=0.1).minimize(loss)
        if chunk:
            for op in main.global_block().ops:
                if op.type.startswith("fused_lm_head_ce"):
                    op.attrs["chunk_size"] = chunk
        exe = Executor()
        exe.run(startup, scope=scope, seed=3)
    return exe, scope, main, loss


def _feed(batch=BATCH):
    rng = np.random.RandomState(0)
    return {"src_ids": rng.randint(1, 64, (batch, SEQ)).astype(np.int32),
            "mask_pos": np.stack(
                [rng.choice(SEQ, N_MASK, replace=False) + i * SEQ
                 for i in range(batch)]).astype(np.int32),
            "lm_label": rng.randint(1, 64, (batch, N_MASK)).astype(np.int32)}


def _loss_and_grads(parallel, chunk):
    exe, scope, main, loss = _bert(0.0, chunk)
    names = [p.name for p in main.all_parameters()]
    prog = main if parallel is None else parallel(main, loss)
    out = exe.run(prog, feed=_feed(), scope=scope,
                  fetch_list=[loss.name] + [n + "@GRAD" for n in names])
    return dict(zip(["loss"] + names, map(np.asarray, out)))


def _dp(main, loss):
    return pt.CompiledProgram(main).with_data_parallel(
        loss_name=loss.name, places=DP)


def _rel(got, want):
    return np.abs(got - want).max() / max(np.abs(want).max(), 1e-12)


# (a) ------------------------------------------------------------------------

@pytest.mark.parametrize("chunk,head_tol", [
    # a chunk of one shard's tokens: the global scan's chunks ARE the shards,
    # so each bf16 partial product of dW is the same number on both sides
    (BATCH * N_MASK // DP, 1e-5),
    # the default chunk (1024) holds the whole toy batch in one product,
    # four chips make four: dW's partial sums round to bf16 in other groups,
    # as they do between the chunks of one chip
    (None, 2e-2),
])
def test_dp_step_equals_single_device(chunk, head_tol):
    before = _counts()
    single = _loss_and_grads(None, chunk)
    assert _delta(before) == {}, "no mesh: nothing asks"
    dp = _loss_and_grads(_dp, chunk)
    assert _delta(before) == {("fused_lm_head_ce", "1"): 1,
                              ("fused_lm_head_ce_grad", "1"): 1}
    assert set(dp) == set(single)
    for name, want in single.items():
        tol = head_tol if name == "mlm_out.w" else 1e-5
        assert _rel(dp[name], want) <= tol, name


# (b) ------------------------------------------------------------------------

def _step_texts(parallel, dropout=0.1):
    """(StableHLO, compiled HLO) of the training step the executor runs."""
    exe, scope, main, loss = _bert(dropout)
    got = {}
    call = E._CompiledBlock.__call__

    def record(self, feeds, ro, rw, seed):
        low = self.jitted.lower(feeds, ro, rw, seed)
        got["lowered"], got["compiled"] = low.as_text(), \
            low.compile().as_text()
        return call(self, feeds, ro, rw, seed)

    E._CompiledBlock.__call__ = record
    try:
        exe.run(parallel(main, loss), feed=_feed(), scope=scope,
                fetch_list=[loss.name])
    finally:
        E._CompiledBlock.__call__ = call
    return got["lowered"], got["compiled"]


def _collectives_in_while_bodies(hlo):
    loops = dp_arith_check.while_loops(hlo)
    return len(loops), [c for _, _, inside in loops for c in inside]


def _head_all_gathers(hlo):
    return [l.strip() for l in hlo.splitlines()
            if " all-gather(" in l and "fused_lm_head_ce" in l]


def _rng_shapes(stablehlo):
    return set(re.findall(
        r"stablehlo\.rng_bit_generator.*-> \(tensor<[^>]*>, "
        r"tensor<([0-9x]+)xui8>\)", stablehlo))


def test_dp_step_hlo_head_and_masks_are_local():
    lowered, compiled = _step_texts(_dp)
    n_while, inside = _collectives_in_while_bodies(compiled)
    assert n_while >= 2                  # the head's scan, forward + backward
    assert inside == []
    assert _head_all_gathers(compiled) == []
    # one shard's masks: [b/dp, seq, d], [b/dp, seq, d_inner], attention
    local = BATCH // DP
    shapes = _rng_shapes(lowered)
    assert shapes and all(s.startswith(f"{local}x") for s in shapes), shapes


def test_every_site_of_the_dp_step_engages():
    """The counter over one trace of the toy BERT step with dropout on: the
    head and its grad once each, every dropout and fused_dense_act site and
    its grad, none fallen back."""
    exe, scope, main, loss = _bert(0.1)
    before = _counts()
    exe.run(_dp(main, loss), feed=_feed(), scope=scope,
            fetch_list=[loss.name])
    delta = _delta(before)
    assert not [k for k in delta if k[1] == "0"], delta
    assert delta[("fused_lm_head_ce", "1")] == 1
    assert delta[("fused_lm_head_ce_grad", "1")] == 1
    for op in ("dropout", "fused_dense_act"):
        assert delta[(op, "1")] >= 2
        assert delta[(op + "_grad", "1")] == delta[(op, "1")]
    # the same program on one device asks nothing
    before = _counts()
    exe.run(main, feed=_feed(), scope=scope, fetch_list=[loss.name])
    assert _delta(before) == {}


def test_hlo_detectors_see_the_partitioner_only_form(monkeypatch):
    """The same step with per_dp_shard held to its whole-operands path is
    what the partitioner makes of the global-shape lowering: the detectors
    of the test above must find what they look for there."""
    def whole(ctx, fn, sharded=(), replicated=(), batch=None):
        return fn(E.DpShard(None, 1), *sharded, *replicated)
    from paddle_tpu.ops import nn_ops
    monkeypatch.setattr(nn_ops, "per_dp_shard", whole)
    lowered, compiled = _step_texts(_dp)
    assert _head_all_gathers(compiled) or \
        _collectives_in_while_bodies(compiled)[1]
    assert any(s.startswith(f"{BATCH}x") for s in _rng_shapes(lowered))


# (c) ------------------------------------------------------------------------

def _dropout_program(p, shape, transpose=False):
    scope, main, startup = Scope(), Program(), Program()
    with scope_guard(scope), program_guard(main, startup):
        x = layers.data("x", shape=list(shape[1:]), dtype="float32")
        x.stop_gradient = False
        h = layers.transpose(x, [1, 0]) if transpose else x
        # an explicit seed is the op's tag: two builds draw the same stream
        y = layers.dropout(h, p, seed=1234,
                           dropout_implementation="upscale_in_train")
        loss = layers.reduce_sum(y)
        from paddle_tpu.framework.backward import append_backward
        append_backward(loss)
    return scope, main, loss, y


def _run_dropout(p, shape, parallel, seed, transpose=False):
    scope, main, loss, y = _dropout_program(p, shape, transpose)
    prog = main if parallel is None else parallel(main, loss)
    out, gx = Executor().run(
        prog, feed={"x": np.ones(shape, np.float32)}, scope=scope,
        fetch_list=[y.name, "x@GRAD"], seed=seed)
    return np.asarray(out), np.asarray(gx)


def test_dropout_masks_under_dp():
    p, shape = 0.25, (64, 96)
    before = _counts()
    out, gx = _run_dropout(p, shape, _dp, seed=11)
    assert _delta(before) == {("dropout", "1"): 1, ("dropout_grad", "1"): 1}
    keep = out != 0
    # forward and backward regenerate the same bits
    np.testing.assert_array_equal(keep, gx != 0)
    np.testing.assert_allclose(out[keep], 1 / (1 - p), rtol=1e-6)
    # the byte threshold keeps round(p * 256) / 256 exactly = 0.75 here
    n = keep.size
    assert abs(keep.mean() - (1 - p)) <= 3 * np.sqrt(p * (1 - p) / n)
    shards = np.split(keep, DP)
    for i in range(DP):
        for j in range(i + 1, DP):
            assert not np.array_equal(shards[i], shards[j]), (i, j)
    again, _ = _run_dropout(p, shape, _dp, seed=11)
    np.testing.assert_array_equal(out, again)
    other, _ = _run_dropout(p, shape, _dp, seed=12)
    assert not np.array_equal(out, other)


# (d) ------------------------------------------------------------------------

def test_fallback_leading_dimension_not_divisible():
    # [8, 6] transposed: the mask's leading dimension is 6, dp is 4
    p, shape = 0.5, (8, 6)
    before = _counts()
    out, gx = _run_dropout(p, shape, _dp, seed=5, transpose=True)
    assert _delta(before) == {("dropout", "0"): 1, ("dropout_grad", "0"): 1}
    np.testing.assert_array_equal((out != 0).T, gx != 0)
    single, _ = _run_dropout(p, shape, None, seed=5, transpose=True)
    np.testing.assert_array_equal(out, single)      # today's global draw


def test_fallback_dp_mp_mesh():
    before = _counts()
    single = _loss_and_grads(None, None)
    both = _loss_and_grads(
        lambda main, loss: pt.CompiledProgram(main).with_distributed(
            mesh=M.make_mesh({"dp": 2, "mp": 2}, jax.devices()[:4])), None)
    assert _delta(before) == {("fused_lm_head_ce", "0"): 1,
                              ("fused_lm_head_ce_grad", "0"): 1}
    for name, want in single.items():
        assert _rel(both[name], want) <= 1e-5, name


def test_fallback_collective_shard_map_mode():
    """The multi-process collective mode is one shard_map already: the
    lowerings see their rank's shard, ask nothing, and still train."""
    from paddle_tpu.distributed import GradAllReduce
    eps = ",".join(f"127.0.0.1:{6470 + i}" for i in range(DP))
    before = _counts()
    main, startup = Program(), Program()
    with program_guard(main, startup), scope_guard(Scope()):
        x = layers.data("x", shape=[8], dtype="float32")
        y = layers.data("y", shape=[1], dtype="int64")
        h = layers.dropout(layers.fc(x, size=16, act="relu"), 0.3)
        loss = layers.mean(layers.cross_entropy(
            layers.fc(h, size=4, act="softmax"), y))
        opt.SGDOptimizer(0.1).minimize(loss)
        GradAllReduce().transpile(rank=0, endpoints=eps,
                                  current_endpoint="127.0.0.1:6470")
        exe = Executor()
        exe.run(startup, seed=42)
        rng = np.random.RandomState(1)
        lv, = exe.run(feed={"x": rng.rand(16, 8).astype("float32"),
                            "y": rng.randint(0, 4, (16, 1)).astype("int64")},
                      fetch_list=[loss.name])
    assert np.asarray(lv).shape[0] == DP and np.isfinite(lv).all()
    assert not any(k[1] == "1" for k in _delta(before))


@pytest.mark.parametrize("axes,collective_axis,lead,engaged", [
    ({"dp": 4}, None, 8, True),
    ({"dp": 4}, None, 6, False),
    ({"dp": 4}, "dp", 8, False),
    ({"dp": 2, "mp": 2}, None, 8, False),
    ({"dp": 4, "mp": 1}, None, 8, True),
    ({"dp": 1}, None, 8, False),
])
def test_per_dp_shard_conditions(axes, collective_axis, lead, engaged):
    n = int(np.prod(list(axes.values())))
    ctx = E.LowerCtx(0, mesh=M.make_mesh(axes, jax.devices()[:n]),
                     collective_axis=collective_axis)
    ctx.op_type = "probe"
    before = _counts()

    def fn(shard, x, w):
        assert (shard.index is not None) == engaged
        assert shard.count == (axes["dp"] if engaged else 1)
        return x * w, jnp.sum(x, axis=1)

    x = jnp.arange(lead * 3, dtype=jnp.float32).reshape(lead, 3)
    y, s = jax.jit(lambda x, w: E.per_dp_shard(
        ctx, fn, sharded=(x,), replicated=(w,)))(x, jnp.float32(2))
    np.testing.assert_allclose(y, np.asarray(x) * 2)
    np.testing.assert_allclose(s, np.asarray(x).sum(1))
    assert _delta(before) == {("probe", str(int(engaged))): 1}


# (e) ------------------------------------------------------------------------

@pytest.mark.parametrize("dp", [0, DP])
def test_fused_dense_act_draws_the_dropout_ops_bits(dp):
    """``fused_ops.py`` promises that the dropout folded into
    ``fused_dense_act`` and the plain ``dropout`` op with the same tag draw
    identical bits, so fusing changes no trajectory."""
    attrs = {"seed": 77, "dropout_prob": 0.4,
             "dropout_implementation": "upscale_in_train"}
    mesh = M.make_mesh({"dp": dp}, jax.devices()[:dp]) if dp else None

    def both(seed, x):
        ctx = E.LowerCtx(seed, mesh=mesh)
        plain = registry.get_op_info("dropout").lower(
            ctx, {"X": [x]}, attrs)["Out"][0]
        fused = registry.get_op_info("fused_dense_act").lower(
            ctx, {"X": [x], "W": [jnp.eye(x.shape[-1], dtype=x.dtype)],
                  "Bias": [jnp.zeros((x.shape[-1],), x.dtype)]},
            dict(attrs, x_num_col_dims=2))["Out"][0]
        return plain, fused

    x = jnp.ones((8, 4, 32), jnp.float32)
    plain, fused = jax.jit(both)(jnp.uint32(9), x)
    assert 0.3 < float((plain == 0).mean()) < 0.5
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(fused))
