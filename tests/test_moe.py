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


# -- the two un-sorts' sum over a token's slots, in either index order ---------
#
# Since PR 63 XLA's gather brings a token's ``k`` slots home slot-major where
# ``k`` is no multiple of 8 (``moe_ops._sum_over_slots``): rows ``j * S .. (j
# + 1) * S`` are slot ``j`` of every token and the sum runs over the leading
# axis, so no ``[S, k, d]`` float32 view puts ``k`` on a TPU's sublanes.  The
# same products and terms in the same order.

import hashlib  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KS = (2, 4, 6, 8)


@pytest.mark.parametrize("masked", [False, True], ids=["all", "held"])
@pytest.mark.parametrize("weighted", [False, True], ids=["back", "sum"])
@pytest.mark.parametrize("k", KS)
def test_the_slots_sum_to_the_same_bits_in_either_order(k, weighted, masked):
    """The helper alone: the two index orders and the sum written out slot by
    slot, from bf16 rows, with and without the weights and the mask: the
    same float32 to the bit where the terms are only added, and within the
    rounding of a fused multiply-add where they are weighted first."""
    from paddle_tpu.ops import moe_ops
    S, d = 16, 24
    rng = np.random.RandomState(k + 10 * weighted + 20 * masked)
    rows = jnp.asarray(rng.randn(S * k, d) * 3, jnp.bfloat16)
    place = jnp.asarray(rng.permutation(S * k), jnp.int32)
    w = jnp.asarray(rng.rand(S, k), jnp.float32) if weighted else None
    held = jnp.asarray(rng.rand(S * k) < 0.5) if masked else None
    want = np.zeros((S, d), np.float32)
    for j in range(k):
        term = np.asarray(rows, np.float32)[np.asarray(place)[j::k]]
        if masked:
            term = np.where(np.asarray(held)[j::k, None], term, 0.0)
        want = want + (term * np.asarray(w)[:, j:j + 1] if weighted else term)
    minor, major = (np.asarray(jax.jit(lambda *a: moe_ops._sum_over_slots(
        a[0], a[1], S, k, w, held, order))(rows, place))
        for order in (False, True))
    assert minor.dtype == np.float32 and minor.shape == (S, d)
    if not weighted:
        np.testing.assert_array_equal(major, minor)
        np.testing.assert_array_equal(minor, want)
    # numpy rounds each product before it adds; XLA's CPU code need not, in
    # either order: an ulp or two of the terms' magnitudes
    ulp = np.spacing(np.abs(np.asarray(rows, np.float32)).max() * k)
    assert np.abs(major - minor).max() <= 2 * ulp
    assert np.abs(minor - want).max() <= 2 * ulp
    if masked:      # the mask as the column the grad op's held path hands over
        got = jax.jit(lambda *a: moe_ops._sum_over_slots(
            a[0], a[1], S, k, w, held[:, None], True))(rows, place)
        np.testing.assert_array_equal(np.asarray(got), major)


def test_the_order_follows_k_and_nothing_else():
    from paddle_tpu.ops import moe_ops
    assert [moe_ops._slot_major(k) for k in (1, 2, 4, 6, 8, 12, 16)] == \
        [True, True, True, True, False, True, False]
    # the held-rows kernel builds no view: its lowerings count as minor
    assert not moe_ops._slot_major(6, object())


#: the paths below, 16 tokens of 24 wide: every one of 32 experts held; a
#: share of 8 of them from expert ``OFFSET`` whose ladder has one rung; and
#: that share on a ladder of three rungs (``4k``, ``8k``, ``16k`` rows) under a
#: routing on each rung.  ``(a, b)``: the first ``a`` tokens have every slot
#: held here, the next ``b`` one slot, the others (two at least) none
SLOT_PATHS = {"full": None, "one": (6, 2), "rung0": (2, 2), "rung1": (6, 2),
              "rung2": (12, 2)}
S_TOY, D_TOY, F_TOY, E_TOY = 16, 24, 8, 32
SLOT_ATTRS = {"score_func": "sigmoid", "norm_topk_prob": True,
              "norm_eps": 1e-20, "route_scale": 2.5}


def _slot_step(k, path, monkeypatch):
    """``step(x, d_out, rx, wr, wg, wu, wd)``: ``moe_ffn`` + ``moe_ffn_grad``
    through the lowerings in float32, the router reading an input of its
    own (so ``IG$X`` is the backward's gather back to tokens alone and
    ``IG$RouterX`` the router's cotangent alone), on ``path``'s ladder."""
    from paddle_tpu.ops import moe_ops
    ctx = types.SimpleNamespace(amp=False)
    share = path != "full"
    attrs = dict(SLOT_ATTRS, top_k=k, expert_offset=OFFSET if share else 0)
    if path.startswith("rung"):
        monkeypatch.setattr(moe_ops, "held_ladder",
                            lambda *a: (4 * k, 8 * k, S_TOY * k))

    def step(x, d_out, rx, wr, wg, wu, wd):
        ins = {"X": [x], "RouterX": [rx], "RouterW": [wr], "GateW": [wg],
               "UpW": [wu], "DownW": [wd]}
        fwd = moe_ops._moe_ffn(ctx, ins, attrs)
        g_ins = {"X$" + n: v for n, v in ins.items()}
        g_ins.update({"Saved": fwd["Saved"], "OG$Out": [d_out]})
        bwd = moe_ops._moe_ffn_grad(ctx, g_ins, attrs)
        return {"Out": fwd["Out"][0], "ExpertLoad": fwd["ExpertLoad"][0],
                **{n: v[0] for n, v in bwd.items()}}
    return step


def _slot_operands(k, path, shapes_only=False):
    """The step's operands.  Token ``t`` raises flag ``t`` of the router's
    input and row ``t`` of the router's weight holds its scores, so the
    routing is the one drawn here: ``path``'s ``(a, b)``."""
    n_held, offset = (8, OFFSET) if path != "full" else (E_TOY, 0)
    S, d, f, E = S_TOY, D_TOY, F_TOY, E_TOY
    shapes = ((1, S, d), (1, S, d), (1, S, d), (d, E), (n_held, d, f),
              (n_held, d, f), (n_held, f, d))
    if shapes_only:
        return [jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes]
    rng = np.random.RandomState(8 * KS.index(k) +
                                sorted(SLOT_PATHS).index(path))
    here = np.arange(offset, offset + n_held)
    away = np.setdiff1d(np.arange(E), here)
    a, b = SLOT_PATHS[path] or (S, 0)
    top_e = [rng.choice(here, k, replace=False) for _ in range(a)] + \
        [np.concatenate([rng.choice(here, 1),
                         rng.choice(away, k - 1, replace=False)])
         for _ in range(b)] + \
        [rng.choice(away, k, replace=False) for _ in range(S - a - b)]
    wr = rng.randn(d, E) * 0.05
    for t, chosen in enumerate(top_e):      # distinct margins: no near tie
        wr[t] += -2.0
        wr[t, chosen] += 3.0 + 0.3 * rng.permutation(k)
    values = [rng.randn(*s) * 0.3 for s in shapes]
    values[2], values[3] = np.eye(S, d)[None], wr
    return [jnp.asarray(v, jnp.float32) for v in values], \
        np.sort(np.stack(top_e), axis=1), a * k + b


@pytest.mark.parametrize("path", sorted(SLOT_PATHS))
@pytest.mark.parametrize("k", KS)
def test_both_slot_orders_match_the_float32_reference(k, path, monkeypatch):
    """``Out``, ``IG$X``, the router's cotangent (``IG$RouterX``),
    ``IG$RouterW`` and the expert weights' gradients against
    ``benchmark/reference/trinity_mini.py``'s float32 pieces, at the limits
    the ops' other reference tests use (1e-5 of the largest entry forward,
    1e-4 backward): every expert held, a share on one rung and on each of
    three rungs, with tokens none of whose slots are held; the counter says
    which order each lowering summed in."""
    from benchmark.reference import trinity_mini as ref
    from paddle_tpu.ops import moe_ops
    (x, d_out, rx, wr, wg, wu, wd), top_e, held_rows = _slot_operands(k, path)
    ctr = moe_ops.MOE_LOWERINGS_CTR
    labels = dict(top_k=str(k), router_input="own", experts=str(E_TOY))
    before = {o: ctr.value(slot_sum=o, **labels) for o in ("major", "minor")}
    got = jax.jit(_slot_step(k, path, monkeypatch))(x, d_out, rx, wr, wg, wu,
                                                    wd)
    assert {o: ctr.value(slot_sum=o, **labels) - before[o]
            for o in before} == {"major": k != 8, "minor": k == 8}
    n_held = wg.shape[0]
    offset = OFFSET if n_held < E_TOY else 0
    load = np.asarray(got.pop("ExpertLoad"))
    np.testing.assert_array_equal(
        load, np.bincount(top_e.ravel(), minlength=E_TOY))
    assert int(load[offset:offset + n_held].sum()) == held_rows
    if path.startswith("rung"):
        assert moe_ops.held_rung(held_rows, (4 * k, 8 * k, S_TOY * k)) == \
            int(path[-1])

    def want(x, rx, wr, wg, wu, wd):
        blk = {"router_w": wr, "select_bias": jnp.zeros(E_TOY)}
        weight, _ = ref.route(rx[0], blk, k, 2.5)
        return sum(weight[:, offset + e, None] *
                   ref.gated(x[0], wg[e], wu[e], wd[e])
                   for e in range(n_held))[None]

    with jax.default_matmul_precision("highest"):
        out, vjp = jax.vjp(want, x, rx, wr, wg, wu, wd)
        grads = dict(zip(("IG$X", "IG$RouterX", "IG$RouterW", "IG$GateW",
                          "IG$UpW", "IG$DownW"), vjp(d_out)))
    assert set(got) == set(grads) | {"Out"}
    for name, value in dict(grads, Out=out).items():
        w_, g_ = np.asarray(value, np.float64), np.asarray(got[name],
                                                           np.float64)
        err = np.abs(g_ - w_).max() / max(np.abs(w_).max(), 1e-12)
        assert err <= (1e-5 if name == "Out" else 1e-4), (name, err)


#: sha256[:16] of the jaxpr text of ``_slot_step`` at the PARENT commit
#: f43df25 (jax 0.9.0), taken there with ``_slot_text``: what "k = 8 lowers
#: to the pinned text, and so does every k with the slots brought home
#: slot-minor" is held to.  The three k = 8 texts were taken again on PR
#: 64's own tree, which meant to change them (the router's choice by passes
#: of arg-max, ``moe_ffn_grad``'s router in closed form from what ``Saved``
#: now holds; full.k8 read a0d93ed3..., one.k8 ca0a37b2..., rung1.k8
#: e7bba54b... until then); k 2, 4 and 6 are narrow (``moe_ops._narrow``),
#: keep ``lax.top_k`` and the vjp, and are still PR 63's parent's
PARENT_SLOT_JAXPRS = {
    "full.k2": "5e96d016caaa4814", "full.k4": "421f5a92c9b23a0b",
    "full.k6": "3b416883f6bc55fc", "full.k8": "5c80cf9631ab81f9",
    "one.k2": "af7c94ed137917e7", "one.k4": "c03d7c44cc86c10d",
    "one.k6": "b0270ed505aef66f", "one.k8": "4521a7e0b400c54c",
    "rung1.k2": "9fdf4ef03349bd88", "rung1.k4": "6138c8f18f708ca3",
    "rung1.k6": "6e99033b6246e1c4", "rung1.k8": "66061d6babe9ab6d"}


def _slot_text(k, path, monkeypatch):
    return str(jax.make_jaxpr(_slot_step(k, path, monkeypatch))(
        *_slot_operands(k, path, shapes_only=True)))


@pytest.mark.skipif(jax.__version__ != "0.9.0",
                    reason="the pinned texts are jax 0.9.0's")
@pytest.mark.parametrize("path", ["full", "one", "rung1"])
@pytest.mark.parametrize("k", KS)
def test_k8_traces_as_the_parent_did_and_no_other_k_views_k_on_the_sublanes(
        k, path, monkeypatch):
    """The traced text of the op and its grad op: at k = 8 the pinned one, to
    the character; at k 2, 4 and 6 no ``reshape`` to ``(S, k, d)`` is left
    (the view is ``(k, S, d)``: the forward's sum and the backward's
    gather, on every rung), no index or mask is transposed, and with the order
    forced slot-minor the text is the parent's again: nothing else moved."""
    from paddle_tpu.ops import moe_ops

    def sha(text):
        return hashlib.sha256(text.encode()).hexdigest()[:16]
    text = _slot_text(k, path, monkeypatch)
    minor, major = (f"new_sizes=({a}, {b}, {D_TOY})"
                    for a, b in ((S_TOY, k), (k, S_TOY)))
    rungs = 3 if path.startswith("rung") else 1
    if k == 8:
        assert sha(text) == PARENT_SLOT_JAXPRS[f"{path}.k{k}"]
        assert (text.count(minor), text.count(major)) == (2 * rungs, 0)
        return
    assert (text.count(minor), text.count(major)) == (0, 2 * rungs)
    assert sha(text) != PARENT_SLOT_JAXPRS[f"{path}.k{k}"]
    monkeypatch.setattr(moe_ops, "_slot_major", lambda *a: False)
    parents = _slot_text(k, path, monkeypatch)
    assert sha(parents) == PARENT_SLOT_JAXPRS[f"{path}.k{k}"]
    # the slot-major order of the indices and the mask is strided slices: a
    # transposed [S, 4] int32 or bool array hangs a v5e
    for dtype in ("i32", "bool"):
        assert f"{dtype}[{k},{S_TOY}] = transpose" not in text


# -- the router: its backward by hand, its choice without a sort (PR 64) ------

from paddle_tpu.framework.core import grad_var_name  # noqa: E402


def _plain_router(xt, wr, k, renorm, score_func="softmax", bias=None,
                  norm_eps=0.0, scale=1.0, n_group=1, topk_group=1):
    """The router in its plain form, as ``moe_ffn`` and (under ``jax.vjp``)
    ``moe_ffn_grad`` ran it until PR 64: every choice a ``jax.lax.top_k``,
    the backward whatever the vjp of all this gives."""
    f32 = jnp.float32
    S, E = xt.shape[0], wr.shape[-1]
    logits = jnp.dot(xt.astype(f32), wr.astype(f32),
                     precision=jax.lax.Precision.HIGHEST)
    p = jax.nn.sigmoid(logits) if score_func == "sigmoid" else \
        jax.nn.softmax(logits, axis=-1)
    sel = jax.lax.stop_gradient(
        p if bias is None else p + bias.astype(f32)[None, :])
    if n_group > 1:
        top2, _ = jax.lax.top_k(sel.reshape(S, n_group, E // n_group), 2)
        _, best = jax.lax.top_k(jnp.sum(top2, axis=-1), topk_group)
        kept = jnp.any(best[:, :, None] == jnp.arange(n_group)[None, None, :],
                       axis=1)
        sel = jnp.where(jnp.repeat(kept, E // n_group, axis=1), sel, -jnp.inf)
    _, top_e = jax.lax.top_k(sel, k)
    top_p = jnp.take_along_axis(p, top_e, axis=-1)
    if renorm:
        denom = jnp.sum(top_p, axis=-1, keepdims=True)
        top_p = top_p / (denom + norm_eps if norm_eps else denom)
    if scale != 1.0:
        top_p = top_p * scale
    load = jnp.sum(top_e.reshape(S * k, 1) == jnp.arange(E)[None, :], axis=0,
                   dtype=jnp.int32)
    if score_func == "sigmoid":
        p = p / jnp.sum(p, axis=-1, keepdims=True)
    lb = E * jnp.sum(load.astype(f32) / S * jnp.mean(p, axis=0))
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return (top_p, lb, z), (top_e, load)


def _apart(got, want):
    got, want = (np.asarray(a, np.float64) for a in (got, want))
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


ROUTER_S, ROUTER_D, ROUTER_E, ROUTER_K = 48, 24, 32, 8
COTANGENTS = {"top_p": (1, 0, 0), "lb": (0, 1, 0), "z": (0, 0, 1),
              "all": (1, 1, 1)}


@pytest.mark.parametrize("cotangents", sorted(COTANGENTS))
@pytest.mark.parametrize("scale", [1.0, 2.5])
@pytest.mark.parametrize("renorm", [False, True], ids=["kept", "renorm"])
@pytest.mark.parametrize("groups", [(1, 1), (8, 4)], ids=["free", "8of4"])
@pytest.mark.parametrize("biased", [False, True], ids=["nobias", "bias"])
@pytest.mark.parametrize("score_func", ["softmax", "sigmoid"])
def test_the_routers_backward_by_hand_is_the_vjp_of_the_plain_form(
        score_func, biased, groups, renorm, scale, cotangents):
    """``_router`` gives the plain form's weights, losses, choice and count,
    and ``_router_backward``, from the saved logits, slots and count alone,
    the cotangents ``jax.vjp`` of the plain form gives: into
    the router's input and its weight to 1e-6 of the largest entry, float32,
    with the weights', the load-balancing loss's and the z-loss's cotangent
    each alone and all three together (5e-6 where float32 itself is no
    closer to float64 than 1e-6)."""
    from paddle_tpu.ops import moe_ops
    S, d, E, k = ROUTER_S, ROUTER_D, ROUTER_E, ROUTER_K
    case = sorted(COTANGENTS).index(cotangents) + 4 * biased + 8 * renorm
    rng = np.random.RandomState(case)
    xt = jnp.asarray(rng.randn(S, d), jnp.float32)
    wr = jnp.asarray(rng.randn(d, E) * 0.4, jnp.float32)
    bias = jnp.asarray(rng.randn(E) * 0.3, jnp.float32) if biased else None
    on = COTANGENTS[cotangents]
    d_top_p = jnp.asarray(rng.randn(S, k), jnp.float32) * on[0]
    d_lb, d_z = (jnp.asarray(c * v, jnp.float32)
                 for c, v in zip(on[1:], (0.7, -0.3)))
    weights = dict(renorm=renorm, score_func=score_func,
                   norm_eps=1e-20 if renorm else 0.0, scale=scale)
    kw = dict(weights, bias=bias, n_group=groups[0], topk_group=groups[1])
    (want_out, pull, (want_e, want_load)) = jax.vjp(
        lambda xt, wr: _plain_router(xt, wr, k, **kw), xt, wr, has_aux=True)
    want = pull((d_top_p, d_lb, d_z))
    out, (top_e, load, logits, rank) = moe_ops._router(xt, wr, k, **kw)
    np.testing.assert_array_equal(np.asarray(top_e), np.asarray(want_e))
    np.testing.assert_array_equal(np.asarray(load), np.asarray(want_load))
    for a, b in zip(out, want_out):
        assert _apart(a, b) <= 1e-6
    np.testing.assert_array_equal(
        np.asarray(rank), sum((j + 1) * (np.asarray(top_e)[:, j:j + 1]
                                         == np.arange(E)) for j in range(k)))
    got = moe_ops._router_backward(
        xt, wr, logits, rank, load,
        (d_top_p, d_lb if on[1] else None, d_z if on[2] else None), **weights)
    # the load-balancing loss's cotangent alone is nearly one value along a
    # row (the load is nearly even), which the score function's derivative
    # cancels: there the vjp and the closed form each lie some 1e-6 from the
    # float64 value (measured under softmax), so up to 5e-6 apart
    limit = 5e-6 if cotangents == "lb" else 1e-6
    for a, b, what in zip(got, want, ("dx", "d_wr")):
        assert np.abs(np.asarray(b)).max() > 0, what
        assert _apart(a, b) <= limit, (what, _apart(a, b))


def _rows_to_choose_from(case, shape, k, rng):
    a = rng.randn(*shape).astype(np.float32)
    if case == "ties":          # every value twice or more, some k times over
        a = np.round(a * 2) / 2
        a[::3, : k + 1] = 7.0
    elif case == "masked":      # k + 1 entries of a row above -inf
        keep = np.argsort(rng.rand(*shape), axis=-1)[..., : k + 1]
        masked = np.full(shape, -np.inf, np.float32)
        np.put_along_axis(masked, keep, np.take_along_axis(
            np.round(a), keep, axis=-1), axis=-1)
        a = masked
    return a + 0.0      # no -0.0, which lax.top_k orders behind 0.0


@pytest.mark.parametrize("case", ["distinct", "ties", "masked"])
@pytest.mark.parametrize("shape,k", [((40, 512), 8), ((40, 64), 8),
                                     ((40, 32), 4), ((24, 8, 64), 2),
                                     ((24, 8), 4), ((40, 128), 6)],
                         ids=lambda v: "x".join(map(str, v))
                         if isinstance(v, tuple) else f"k{v}")
def test_the_choice_by_passes_is_top_ks_in_the_same_order(shape, k, case):
    """``_top_k``'s ``k`` passes of arg-max against ``jax.lax.top_k``: the
    same values and the same indices in the same order, on distinct values,
    on rows of planted exact ties (the first index wins) and on rows with
    all but ``k + 1`` entries masked to -inf; and no ``top_k`` or ``sort``
    in its traced text."""
    from paddle_tpu.ops import moe_ops
    a = jnp.asarray(_rows_to_choose_from(
        case, shape, k, np.random.RandomState(len(shape) + k)))
    values, indices = moe_ops._top_k(a, k)
    want_values, want_indices = jax.lax.top_k(a, k)
    np.testing.assert_array_equal(np.asarray(indices),
                                  np.asarray(want_indices))
    np.testing.assert_array_equal(np.asarray(values), np.asarray(want_values))
    assert indices.dtype == jnp.int32 and indices.shape == shape[:-1] + (k,)
    text = str(jax.make_jaxpr(lambda a: moe_ops._top_k(a, k))(a))
    assert "top_k[" not in text and " sort[" not in text


def _parents_router_backward(monkeypatch, k, bias, **kw):
    """``moe_ffn_grad`` as the parent ran its router: the two gradients from
    ``jax.vjp`` of the plain form over the router's input and weight, in
    place of ``_router_backward``."""
    from paddle_tpu.ops import moe_ops

    def router_backward(xt, wr, logits, rank, load, cotangents, **routing):
        _, pull, _ = jax.vjp(
            lambda xt, wr: _plain_router(xt, wr, k, bias=bias, **kw,
                                         **routing), xt, wr, has_aux=True)
        zero = jnp.zeros((), jnp.float32)
        return pull(tuple(zero if c is None else c for c in cotangents))
    monkeypatch.setattr(moe_ops, "_router_backward", router_backward)


ROUTED = {"whole": dict(num_held=None, expert_offset=0),
          "held": dict(num_held=8, expert_offset=OFFSET)}


ROUTED_BIAS = np.random.RandomState(64).randn(32).astype(np.float32) * 0.05


def _routed_step(path, recompute, seed=5):
    """One training step of two ``moe_ffn`` layers through the executor,
    sigmoid scores in groups under the selection bias ``ROUTED_BIAS``: the
    loss and every parameter's and the input's gradient."""
    E, k = 32, ROUTER_K
    scope, main, startup = Scope(), Program(), Program()
    with scope_guard(scope), program_guard(main, startup):
        x = layers.data("x", shape=[2, 16, 24], dtype="float32",
                        append_batch_size=False)
        x.stop_gradient = False
        h, aux, checkpoints = x, [], [x]
        for i in range(2):
            out, lb, z, _ = layers.moe_ffn(
                h, E, k, 8, norm_topk_prob=True, score_func="sigmoid",
                select_bias=True, norm_eps=1e-20, route_scale=2.5,
                n_group=8, topk_group=4, param_prefix=f"moe{i}",
                **ROUTED[path])
            h = h + out
            aux += [lb * 0.05, z * 0.01]
            checkpoints.append(h)
        loss = layers.mean(h * h)
        for term in aux:
            loss = loss + term
        sgd = opt.SGD(learning_rate=0.0)
        if recompute:
            sgd = opt.RecomputeOptimizer(sgd)
            sgd._set_checkpoints(checkpoints)
        sgd.minimize(loss)
        exe = Executor()
        exe.run(startup, scope=scope, seed=seed)
        for i in range(2):
            scope.set_var(f"moe{i}.select_bias", jnp.asarray(ROUTED_BIAS))
        names = [p.name for p in main.all_parameters() if p.trainable] + ["x"]
        got = exe.run(main, feed={"x": np.random.RandomState(seed).randn(
            2, 16, 24).astype(np.float32)}, scope=scope,
            fetch_list=[loss.name] + [grad_var_name(n) for n in names])
    return dict(zip(["loss"] + names, map(np.asarray, got)))


@pytest.mark.parametrize("recompute", [False, True],
                         ids=["plain", "recompute"])
@pytest.mark.parametrize("path", sorted(ROUTED))
def test_the_step_gives_the_gradients_the_parents_op_gave(
        path, recompute, monkeypatch):
    """``moe_ffn`` + ``moe_ffn_grad`` through the executor, every expert
    held and a share of them, with and without ``RecomputeOptimizer``
    (under it the saved logits and choice are the recomputed segment's):
    the loss and every gradient against the same step with the grad op's
    router as the parent had it, ``jax.vjp`` of the plain form."""
    got = _routed_step(path, recompute)
    _parents_router_backward(monkeypatch, ROUTER_K, jnp.asarray(ROUTED_BIAS),
                             n_group=8, topk_group=4)
    want = _routed_step(path, recompute)
    assert set(got) == set(want) and len(got) == 2 * 4 + 2
    assert abs(got["loss"] - want["loss"]) <= 1e-6 * abs(want["loss"])
    for name in got:
        assert np.abs(want[name]).max() > 0, name
        assert _apart(got[name], want[name]) <= 1e-6, name


def test_the_routers_parts_ride_their_scopes_into_the_lowered_step(
        monkeypatch):
    """``router/score``, ``router/select`` and ``router/losses`` forward and
    ``router/backward`` in the grad op are in the lowered text's locations,
    no router stands under a ``jvp(`` any more, and the benchmark's reader
    (``part_scopes.part_of``) still gives all four to ``router`` while
    ``tools/trace_by_op.py``'s gives each to itself."""
    import importlib.util
    from benchmark import part_scopes
    text = jax.jit(_slot_step(8, "full", monkeypatch)).lower(
        *_slot_operands(8, "full", shapes_only=True)).as_text(debug_info=True)
    for scope in ("router/score", "router/select", "router/losses",
                  "router/backward"):
        assert scope in text, scope
    # the experts' grouped matmuls are still transposed by jax.vjp; no part
    # of the router stands inside a jvp( or a transpose( any more
    assert "experts/transpose(jvp())" in text
    assert not re.search(r"router[^\"]*jvp\(", text)
    spec = importlib.util.spec_from_file_location(
        "trace_by_op", os.path.join(ROOT, "tools", "trace_by_op.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    for rest, part in (("/router/score/dot_general", "score"),
                       ("/router/select/argmax", "select"),
                       ("/router/losses/reduce_sum", "losses"),
                       ("/router/backward/dot_general", "backward")):
        assert part_scopes.part_of(rest, part_scopes.MOE_PARTS) == "router"
        assert part_scopes.part_of(rest, tool.ROUTER_PARTS) == part
    assert part_scopes.part_of("/experts/transpose(jvp())/dot_general",
                               tool.ROUTER_PARTS) == ""


@pytest.mark.parametrize("mode", ["whole", "pieces"])
def test_the_router_probe_runs_at_its_toy_size(mode):
    """``tools/router_probe.py --cpu``: one line a cell; the router whole
    (both choices the same experts, the closed-form backward beside the
    vjp's) and, ``--pieces``, the choice's parts alone."""
    import json
    import subprocess
    import sys
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "router_probe.py"),
         "--cpu", "--calls", "1", "--cells", "ling,olmoe"]
        + ["--pieces"] * (mode == "pieces"),
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(line) for line in out.stdout.splitlines()
             if line.startswith("{")]
    assert [r["cell"] for r in lines] == ["ling", "olmoe"]
    for r in lines:
        if mode == "pieces":
            assert {"passes_ms", "sort_ms", "gather_ms",
                    "select_sums_ms"} <= set(r)
            assert ("group_mask_ms" in r) == (r["cell"] == "ling")
            continue
        assert r["same_choice"]
        assert r["d_wr_apart"] <= 1e-6 and r["dx_apart"] <= 1e-2   # bf16 x


@pytest.mark.parametrize("k", KS)
def test_a_narrow_k_keeps_the_sort_and_the_vjp_and_saves_no_routing(
        k, monkeypatch):
    """``moe_ops._narrow``: with ``top_k`` no multiple of 8 the op's choice
    is ``lax.top_k``'s, its grad op takes ``jax.vjp`` of the router computed
    again and ``Saved`` holds the five tensors it held before PR 64 (Xing4.0's
    4 of 64 at 4096 tokens never finished a step otherwise, PERF.md section
    6, PR 64); at 8 no ``top_k`` is left in either op, nothing of the router
    stands under a ``jvp(`` and ``Saved`` holds the router's five more."""
    from paddle_tpu.ops import moe_ops
    assert moe_ops._narrow(k) == (k != 8)
    text = _slot_text(k, "full", monkeypatch)
    lowered = jax.jit(_slot_step(k, "full", monkeypatch)).lower(
        *_slot_operands(k, "full", shapes_only=True)).as_text(debug_info=True)
    scope, main = Scope(), Program()
    with scope_guard(scope), program_guard(main, Program()):
        x = layers.data("x", shape=[2, 8, 16], dtype="float32",
                        append_batch_size=False)
        layers.moe_ffn(x, 16, k, 8)
    op, = [o for o in main.global_block().ops if o.type == "moe_ffn"]
    if k == 8:
        assert "top_k[" not in text and "router/backward" in lowered
        assert not re.search(r"router[^\"]*jvp\(", lowered)
        assert len(op.outputs["Saved"]) == 5 + 5
    else:
        assert text.count("top_k[") == 2         # forward, the vjp's forward
        assert "router/backward" not in lowered
        assert re.search(r"router[^\"]*jvp\(", lowered)
        assert len(op.outputs["Saved"]) == 5
