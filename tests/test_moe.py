"""Switch-Transformer MoE (``switch_ffn`` op + ``switch_moe_ffn`` layer —
the capability behind the mesh's ``ep`` axis; no reference counterpart,
design follows GShard/Switch).  Covers: E=1 parity vs a dense FFN,
gradient flow through gate and experts, capacity-drop behavior, and
ep-sharded vs replicated loss parity on the virtual 8-device mesh."""

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu import optimizer as opt
from paddle_tpu.framework import Executor, Program, program_guard
from paddle_tpu.framework.scope import Scope, scope_guard


def _np_dense_ffn(x, w1, b1, w2, b2):
    h = np.maximum(x @ w1 + b1, 0.0)
    return h @ w2 + b2


def test_switch_ffn_e1_matches_dense_ffn():
    """With one expert the router is a no-op (softmax over one logit = 1)
    and capacity 2.0 holds every token: out == relu(x@W1+b1)@W2+b2."""
    B, T, d, F = 2, 6, 8, 16
    scope = Scope()
    with scope_guard(scope), program_guard(Program(), Program()):
        x = layers.data("x", shape=[T, d], dtype="float32")
        out, aux = layers.switch_moe_ffn(x, num_experts=1, d_inner=F,
                                         capacity_factor=2.0,
                                         param_prefix="moe1")
        exe = Executor()
        exe.run(pt.default_startup_program(), scope=scope)
        rng = np.random.RandomState(0)
        xv = rng.randn(B, T, d).astype(np.float32)
        ov, av = exe.run(feed={"x": xv}, fetch_list=[out.name, aux.name],
                         scope=scope)
        w1 = np.asarray(scope.find_var("moe1.w1"))[0]
        b1 = np.asarray(scope.find_var("moe1.b1"))[0]
        w2 = np.asarray(scope.find_var("moe1.w2"))[0]
        b2 = np.asarray(scope.find_var("moe1.b2"))[0]
    want = _np_dense_ffn(xv.reshape(-1, d), w1, b1, w2, b2).reshape(B, T, d)
    np.testing.assert_allclose(np.asarray(ov), want, rtol=1e-5, atol=1e-5)
    # aux loss with E=1: frac=1, mean prob=1 -> exactly 1.0
    np.testing.assert_allclose(float(np.asarray(av)), 1.0, rtol=1e-6)


def test_switch_ffn_gradients_flow():
    """One SGD step on loss = mean(out) + 0.01·aux must move the gate AND
    every expert weight (grad flows through dispatch and combine)."""
    B, T, d, F, E = 2, 8, 8, 16, 4
    scope = Scope()
    with scope_guard(scope), program_guard(Program(), Program()):
        x = layers.data("x", shape=[T, d], dtype="float32")
        out, aux = layers.switch_moe_ffn(x, num_experts=E, d_inner=F,
                                         param_prefix="moeg")
        loss = layers.mean(out * out) + 0.01 * aux
        opt.SGDOptimizer(learning_rate=1.0).minimize(loss)
        exe = Executor()
        exe.run(pt.default_startup_program(), scope=scope)
        names = ["moeg.gate.w", "moeg.w1", "moeg.b1", "moeg.w2", "moeg.b2"]
        before = {n: np.asarray(scope.find_var(n)).copy() for n in names}
        rng = np.random.RandomState(1)
        xv = rng.randn(B, T, d).astype(np.float32)
        lv, = exe.run(feed={"x": xv}, fetch_list=[loss.name], scope=scope)
        assert np.isfinite(float(np.asarray(lv)))
        after = {n: np.asarray(scope.find_var(n)) for n in names}
    for n in names:
        delta = np.abs(after[n] - before[n]).max()
        assert delta > 0, f"no gradient reached {n}"


def test_switch_ffn_capacity_drop():
    """Tokens routed past an expert's capacity contribute ZERO output
    (Switch recipe) — rig the gate so every token picks expert 0."""
    B, T, d, F, E = 1, 8, 4, 8, 2
    S = B * T
    cap = int(np.ceil(1.25 * S / E))          # = 5 < 8 tokens
    scope = Scope()
    with scope_guard(scope), program_guard(Program(), Program()):
        x = layers.data("x", shape=[T, d], dtype="float32")
        out, aux = layers.switch_moe_ffn(x, num_experts=E, d_inner=F,
                                         param_prefix="moec")
        exe = Executor()
        exe.run(pt.default_startup_program(), scope=scope)
        # gate: column 0 sums positive features, column 1 negated -> with
        # all-positive x, every token picks expert 0
        scope.set_var("moec.gate.w", np.stack(
            [np.ones(d), -np.ones(d)], axis=1).astype(np.float32))
        xv = np.abs(np.random.RandomState(2).randn(B, T, d)) \
            .astype(np.float32) + 0.1
        ov, = exe.run(feed={"x": xv}, fetch_list=[out.name], scope=scope)
    flat = np.asarray(ov).reshape(S, d)
    assert np.abs(flat[:cap]).max() > 0, "kept tokens must produce output"
    np.testing.assert_allclose(flat[cap:], 0.0,
                               err_msg="overflow tokens must be dropped")


def _moe_losses(make_compiled, steps=4):
    main, start = Program(), Program()
    with program_guard(main, start), scope_guard(Scope()):
        B, T, d, F, E = 8, 4, 16, 32, 4
        main.random_seed = 7
        start.random_seed = 7
        x = layers.data("x", shape=[T, d], dtype="float32")
        y = layers.data("y", shape=[T, d], dtype="float32")
        out, aux = layers.switch_moe_ffn(x, num_experts=E, d_inner=F,
                                         param_prefix="moep")
        loss = layers.mean((out - y) * (out - y)) + 0.1 * aux
        opt.AdamOptimizer(learning_rate=1e-2).minimize(loss)
        compiled = make_compiled(main)
        exe = Executor()
        exe.run(pt.default_startup_program(), seed=99)
        rng = np.random.RandomState(5)
        xv = rng.randn(B, T, d).astype(np.float32)
        yv = rng.randn(B, T, d).astype(np.float32)
        losses = []
        for _ in range(steps):
            lv, = exe.run(compiled, feed={"x": xv, "y": yv},
                          fetch_list=[loss.name])
            losses.append(float(np.asarray(lv)))
    return losses


def test_switch_ffn_ep_sharded_matches_replicated():
    """Expert-parallel GSPMD (experts sharded on the ep axis, dispatch/
    combine as all-to-alls) must train identically to the dense layout —
    the ep analog of the dp/tp parity tests (ref test_dist_base delta)."""
    import jax
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual mesh")
    single = _moe_losses(lambda m: None)
    ep = _moe_losses(lambda m: pt.CompiledProgram(m).with_distributed(
        axes={"ep": 2, "dp": 4}))
    assert all(np.isfinite(single)) and all(np.isfinite(ep))
    np.testing.assert_allclose(single, ep, rtol=2e-4, atol=1e-5)
    # and it must actually train
    assert single[-1] < single[0]


# -- moe_ffn's held path: the two un-sorts by the held row ---------------------
#
# ``paddle_tpu/pallas/held_rows.py`` in interpret mode against XLA's gather
# over every slot, mask and sum, which is what the lowering runs off the TPU
# and ran everywhere before PR 42.  Equal TO THE BIT, not to an ulp: the
# kernel adds a token's rows in slot order from zero, the order XLA's reduce
# over ``k`` has on the CPU (a slot held elsewhere adds the mask's ``+0.0``
# there and nothing here, which is the same float32), the weighted product
# is the same float32 product, and a bf16 row widens exactly.

import functools  # noqa: E402
import types  # noqa: E402

#: (experts a token, experts held, router outputs) of the four cells that run
#: the held path, at toy sizes below
SHARES = {"trinity": (8, 16, 128), "joyai": (8, 16, 256),
          "smallthinker": (6, 8, 64), "lfm2": (4, 8, 32)}
LOADS = ("none", "even", "skewed", "all")
OFFSET = 3              # the held experts are OFFSET .. OFFSET + n_held - 1


def _routing(rng, load, S, k, n_held, E):
    """``top_e`` [S, k], a token's experts distinct: no slot on a held
    expert, a uniform choice, half the tokens with every slot held and the
    others with none, every slot of every token held."""
    held = np.arange(OFFSET, OFFSET + n_held)
    others = np.setdiff1d(np.arange(E), held)
    pick = {"none": lambda t: rng.choice(others, k, replace=False),
            "even": lambda t: rng.choice(E, k, replace=False),
            "skewed": lambda t: rng.choice(held if t % 2 else others, k,
                                           replace=False),
            "all": lambda t: rng.choice(held, k, replace=False)}[load]
    return np.stack([pick(t) for t in range(S)]).astype(np.int32)


def _slots_sum(y, place, held, top_p, k):
    """The lowering's ``weighted_sum`` over the whole buffer."""
    import jax.numpy as jnp
    rows, d = y.shape
    ys = jnp.take(y, jnp.minimum(place, rows - 1), axis=0)
    ys = jnp.where(held[:, None], ys.astype(jnp.float32), 0.0)
    return jnp.sum(ys.reshape(-1, k, d) * top_p[:, :, None], axis=1)


def _slots_back(a, b, place, held, k):
    """The lowering's ``back_to_tokens`` of ``dxs_g + dxs_u``."""
    import jax.numpy as jnp
    rows, d = a.shape
    return jnp.where(held[:, None], jnp.take(
        a + b, jnp.minimum(place, rows - 1), axis=0).astype(jnp.float32),
        0.0).reshape(-1, k, d).sum(axis=1)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("load", LOADS)
@pytest.mark.parametrize("share", sorted(SHARES))
def test_held_rows_come_back_as_the_gather_over_every_slot_gives_them(
        share, load, dtype):
    """The routine alone, one source weighted (the forward's sum) and two
    sources unweighted (the backward's: each pair of rows added and rounded
    as the stored ``dxs_g + dxs_u`` is), over a buffer whose rows behind the
    held ones hold numbers nobody may read."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import moe_ops
    from paddle_tpu.pallas import held_rows
    k, n_held, E = SHARES[share]
    S, d = 16, 24
    rng = np.random.RandomState(sorted(SHARES).index(share) * 4 +
                                LOADS.index(load))
    top_e = jnp.asarray(_routing(rng, load, S, k, n_held, E))
    held, _, place = moe_ops._held_slots(top_e, OFFSET, n_held, k)
    n = int(held.sum())
    assert {"none": n == 0, "all": n == S * k}.get(load, 0 < n < S * k)
    rows = S * min(k, n_held)
    assert held_rows.fits(S, k, d, rows, dtype)
    y, a, b = (jnp.asarray(rng.randn(rows, d) * 3, dtype) for _ in range(3))
    top_p = jnp.asarray(rng.rand(S, k), jnp.float32)
    got = held_rows.held_rows_to_tokens((y,), place, held, k, top_p,
                                        interpret=True)
    want = jax.jit(_slots_sum, static_argnums=4)(y, place, held, top_p, k)
    assert got.dtype == want.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    got = held_rows.held_rows_to_tokens((a, b), place, held, k,
                                        interpret=True)
    want = jax.jit(_slots_back, static_argnums=4)(a, b, place, held, k)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_the_kernels_tile_is_a_function_of_the_shapes():
    """Tokens a grid step: a tile's slots within the copies in flight and
    the slab within its bytes, a multiple of 8 that divides the tokens; the
    lowering keeps XLA's gather at shapes with no such tile."""
    from paddle_tpu.pallas import held_rows as hr
    assert hr.tile_tokens(16384, 4, 2048, 2, 1) == 32       # LFM2, forward
    assert hr.tile_tokens(16384, 4, 2048, 2, 2) == 16       # and backward
    assert hr.tile_tokens(16384, 6, 2560, 2, 2) == 8        # SmallThinker
    assert hr.tile_tokens(8192, 8, 2048, 2, 1) == 16        # Trinity, JoyAI
    assert hr.tile_tokens(24, 2, 16, 4, 1) == 24
    assert hr.fits(16384, 4, 2048, 65536, "bfloat16")
    assert not hr.fits(14, 3, 16, 42, "float32")            # 14 tokens
    assert not hr.fits(16, 2, 16, 20, "float32")            # 20 rows
    assert not hr.fits(16, 2, 16, 32, "float16")


def _share_step(k, n_held, E, act, amp, bias, seed):
    """``moe_ffn`` + ``moe_ffn_grad`` through the lowerings for a share that
    starts at expert ``OFFSET``: every output of both."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import moe_ops
    S, d, f = 16, 24, 8
    rng = np.random.RandomState(seed)
    ctx = types.SimpleNamespace(amp=amp)
    attrs = {"top_k": k, "score_func": "sigmoid", "norm_topk_prob": True,
             "norm_eps": 1e-20, "route_scale": 2.5, "expert_offset": OFFSET,
             "act": act}
    x, d_out = (jnp.asarray(rng.randn(1, S, d), jnp.float32)
                for _ in range(2))
    wr = jnp.asarray(rng.randn(d, E) * 0.3, jnp.float32)
    wg, wu = (jnp.asarray(rng.randn(n_held, d, f) * 0.3, jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.randn(n_held, f, d) * 0.3, jnp.float32)

    def step(x, d_out, wr, wg, wu, wd, bias):
        ins = {"X": [x], "RouterW": [wr], "GateW": [wg], "UpW": [wu],
               "DownW": [wd], "SelectBias": [bias]}
        fwd = moe_ops._moe_ffn(ctx, ins, attrs)
        g_ins = {"X$" + n: v for n, v in ins.items()}
        g_ins.update({"Saved": fwd["Saved"], "OG$Out": [d_out]})
        bwd = moe_ops._moe_ffn_grad(ctx, g_ins, attrs)
        return {"Out": fwd["Out"][0], "ExpertLoad": fwd["ExpertLoad"][0],
                **{n: v[0] for n, v in bwd.items()}}
    return jax.jit(step)(x, d_out, wr, wg, wu, wd, jnp.asarray(bias))


@pytest.mark.parametrize("amp", [False, True], ids=["float32", "bf16"])
@pytest.mark.parametrize("act", ["silu", "relu"])
@pytest.mark.parametrize("share", sorted(SHARES))
def test_the_share_ops_give_the_same_bits_by_the_row_and_by_the_slot(
        monkeypatch, share, act, amp):
    """Forward and grad op with ``expert_offset`` 3, the un-sorts through the
    kernel (interpret mode, as a TPU lowers them) and through XLA's gather
    (as the CPU lowers them): ``Out`` and every gradient equal exactly, and
    the lowerings counter says which un-sort each compile took.  The load
    goes round with the case: no slot held, the fresh router's, one held
    expert in every token's choice, every slot held."""
    from paddle_tpu.ops import moe_ops
    from paddle_tpu.pallas import held_rows
    k, n_held, E = SHARES[share]
    case = sorted(SHARES).index(share) + 2 * (act == "relu") + amp
    load = LOADS[case % 4]
    bias = np.zeros(E, np.float32)
    here = slice(OFFSET, OFFSET + (1 if load == "skewed" else n_held))
    bias[here] = {"none": -10.0, "even": 0.0}.get(load, 10.0)
    ctr = moe_ops.MOE_LOWERINGS_CTR
    labels = dict(experts=str(E), top_k=str(k), held=str(n_held), act=act)
    before = {u: ctr.value(unsort=u, **labels) for u in ("rows", "slots")}
    by_slot = _share_step(k, n_held, E, act, amp, bias, case)
    assert moe_ops._rows_unsort(16, k, 24, (16 * k,), "float32") is None
    monkeypatch.setattr(moe_ops, "_rows_unsort", lambda *a: functools.partial(
        held_rows.held_rows_to_tokens, interpret=True))
    by_row = _share_step(k, n_held, E, act, amp, bias, case)
    after = {u: ctr.value(unsort=u, **labels) for u in ("rows", "slots")}
    assert (after["slots"], after["rows"]) == \
        (before["slots"] + 1, before["rows"] + 1)
    n = int(np.asarray(by_slot["ExpertLoad"])[OFFSET:OFFSET + n_held].sum())
    assert {"none": n == 0, "all": n == 16 * k}.get(load, 0 < n < 16 * k)
    assert set(by_row) == set(by_slot) and len(by_row) == 7
    for name in by_slot:
        np.testing.assert_array_equal(np.asarray(by_row[name]),
                                      np.asarray(by_slot[name]), name)


def test_every_expert_held_counts_its_unsort_by_the_slot():
    """``n_held == E`` is the branch PR 42 left alone: no kernel, whatever
    the backend, and the label says so."""
    from paddle_tpu.ops import moe_ops
    ctr = moe_ops.MOE_LOWERINGS_CTR
    labels = dict(experts="8", held="8", top_k="2", ladder="")
    before = ctr.value(unsort="slots", **labels), \
        ctr.value(unsort="rows", **labels)
    rng = np.random.RandomState(0)
    import jax.numpy as jnp
    ins = {"X": [jnp.asarray(rng.randn(1, 8, 16), jnp.float32)],
           "RouterW": [jnp.asarray(rng.randn(16, 8), jnp.float32)],
           "GateW": [jnp.asarray(rng.randn(8, 16, 4), jnp.float32)],
           "UpW": [jnp.asarray(rng.randn(8, 16, 4), jnp.float32)],
           "DownW": [jnp.asarray(rng.randn(8, 4, 16), jnp.float32)]}
    moe_ops._moe_ffn(types.SimpleNamespace(amp=False), ins, {"top_k": 2})
    assert (ctr.value(unsort="slots", **labels),
            ctr.value(unsort="rows", **labels)) == (before[0] + 1, before[1])


@pytest.mark.parametrize("load", ["even", "all"])
def test_a_ladder_of_two_rungs_gives_the_same_bits_by_the_row(
        monkeypatch, load):
    """SmallThinker's regime, a ladder of two long rungs: the row gathers,
    the gate's passes and the two cotangents walk the rung in their
    switches (the grad op's cotangents in two, the weights' before dy's),
    the un-sorts read the held rows outside any.  A load on each side of
    the rung gives the bits the gather over every slot gives."""
    from paddle_tpu.ops import moe_ops
    from paddle_tpu.pallas import held_rows
    k, n_held, E = SHARES["lfm2"]
    monkeypatch.setattr(moe_ops, "held_ladder", lambda *a: (32, 64))
    bias = np.zeros(E, np.float32)
    bias[OFFSET:OFFSET + n_held] = 10.0 if load == "all" else 0.0
    by_slot = _share_step(k, n_held, E, "silu", True, bias, 5)
    n = int(np.asarray(by_slot["ExpertLoad"])[OFFSET:OFFSET + n_held].sum())
    assert n == 64 if load == "all" else 0 < n <= 32
    monkeypatch.setattr(moe_ops, "_rows_unsort", lambda *a: functools.partial(
        held_rows.held_rows_to_tokens, interpret=True))
    ctr = moe_ops.MOE_LOWERINGS_CTR
    before = ctr.value(unsort="rows", ladder="32.64")
    by_row = _share_step(k, n_held, E, "silu", True, bias, 5)
    assert ctr.value(unsort="rows", ladder="32.64") == before + 1
    for name in by_slot:
        np.testing.assert_array_equal(np.asarray(by_row[name]),
                                      np.asarray(by_slot[name]), name)


def test_the_ladders_that_take_the_kernel_follow_their_first_rung(
        monkeypatch):
    """On a TPU the un-sorts read the held rows alone where even the first
    rung is longer than 16384 rows of 2048 bf16 (the source XLA's gather
    reads fast): LFM2's one rung and both of SmallThinker's.  Trinity's and
    JoyAI's ladders start on XLA's fast side and keep the lowering they had."""
    from paddle_tpu import device
    from paddle_tpu.ops import moe_ops
    from paddle_tpu.pallas import held_rows
    monkeypatch.setattr(device, "on_tpu", lambda: True)
    plans = {"lfm2": (16384, 4, 2048, 8, 32), "trinity": (8192, 8, 2048, 16, 128),
             "joyai": (8192, 8, 2048, 16, 256),
             "smallthinker": (16384, 6, 2560, 8, 64)}
    for name, (S, k, d, n_held, E) in plans.items():
        routine = moe_ops._rows_unsort(
            S, k, d, moe_ops.held_ladder(S, k, n_held, E), "bfloat16")
        assert (routine is held_rows.held_rows_to_tokens) == \
            (name in ("lfm2", "smallthinker")), name
    # float32 rows are twice the bytes: a 16384-row rung is a long source too
    assert moe_ops._rows_unsort(16384, 4, 2048, (16384, 65536), "float32")
    # and at 8 slots a token two float32 sources' groups leave no tile: XLA's
    assert moe_ops._rows_unsort(8192, 8, 2048, (65536,), "float32") is None
    monkeypatch.setattr(device, "on_tpu", lambda: False)
    assert moe_ops._rows_unsort(16384, 4, 2048, (65536,), "bfloat16") is None
