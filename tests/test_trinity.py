"""Trinity-Mini on the training path against the plain float32 reference of
``benchmark/reference/trinity_mini.py``, at a toy size on the CPU: the
windowed, grouped-query flash attention against the dense-mask oracle, the
router's new attributes and the chip's share of the experts against the
reference's masked dense experts, then the whole model's loss and every
parameter's gradient against ``jax.grad`` of the reference's loss.

Tolerances as ``tests/test_olmoe.py`` sets them and for its reasons: program
and reference are float32 on the CPU and differ by summation order (loss
1e-5, each gradient 1e-4 of its largest entry; with the fused head, which
multiplies in bf16, 5e-4 and 2e-2).  Every structural fault this file plants
(a window off by one, rotary on a full layer, the gate dropped, the
renormalisation or the scale dropped, a QK-norm over the whole projection)
moves some gradient of the dense-head model by more than ten times its
tolerance (``tests/test_trinity_model.py::test_the_tolerance_catches``: the
whole model's tests are in that file, so that the two run on two workers).
Sizes are tiny on purpose.
"""

import hashlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import paddle_tpu as pt  # noqa: E402
import test_olmoe as olmoe_test  # noqa: E402
from benchmark.models import trinity_mini as adapter  # noqa: E402
from benchmark.reference import trinity_mini as ref  # noqa: E402
from paddle_tpu import layers  # noqa: E402
from paddle_tpu.framework import (Executor, Program, Scope,  # noqa: E402
                                  program_guard, scope_guard)
from paddle_tpu.framework.backward import append_backward  # noqa: E402
from paddle_tpu.framework.core import grad_var_name  # noqa: E402
from paddle_tpu.models import transformer as T  # noqa: E402
_close, _run_op = olmoe_test._close, olmoe_test._run_op
LOSS_TOL, GRAD_TOL = olmoe_test.LOSS_TOL, olmoe_test.GRAD_TOL
TYPES = ("sliding_attention", "sliding_attention", "full_attention")


def toy_cfg(**kw):
    kw = dict(dict(vocab_size=96, d_model=32, n_layer=3, n_head=4,
                   n_kv_head=2, d_head=16, d_inner=48, d_expert=24,
                   n_experts=8, top_k=2, n_dense_layer=1, layer_types=TYPES,
                   window=8, n_held=4, expert_offset=2), **kw)
    return T.TrinityConfig(**kw)


def _model(cfg, seq, amp=False, seed=3, fused_head=False):
    scope, main, startup = Scope(), Program(), Program()
    with scope_guard(scope), program_guard(main, startup):
        _, parts, loss = T.build_trinity_pretrain(cfg, seq,
                                                  fused_head=fused_head)
        append_backward(loss)
        if amp:
            pt.amp.enable(main)
        exe = Executor()
        exe.run(startup, scope=scope, seed=seed)
    # norm scales start at 1 and the bias at 0: they would hide a norm over
    # the wrong axis and a bias that reached the weights
    rng = np.random.RandomState(seed)
    for p in main.all_parameters():
        if p.name.endswith((".ln1.w", ".ln2.w", ".ln3.w", ".ln4.w",
                            "_norm.w")):
            scope.set_var(p.name, jnp.asarray(
                rng.uniform(0.5, 1.5, p.shape).astype(np.float32)))
        elif p.name.endswith(".select_bias"):
            scope.set_var(p.name, jnp.asarray(
                rng.randn(*p.shape).astype(np.float32) * 0.1))
    return scope, main, exe, parts, loss


def _batch(cfg, b, seq, seed=0):
    return adapter.make_batch(np.random.RandomState(seed), cfg, b, seq)


# -- counters ------------------------------------------------------------------

def test_flash_lowerings_are_counted_by_window_and_groups():
    from paddle_tpu.ops.attention_ops import FLASH_LOWERINGS_CTR as ctr
    labels = dict(window="8", kv_groups="2", impl="jax", widths="16/16")
    full = dict(window="none", kv_groups="2", impl="jax",
                widths="16/16")
    before = ctr.value(**labels), ctr.value(**full)
    cfg = toy_cfg(n_layer=2, layer_types=TYPES[1:], n_dense_layer=1)
    scope, main, exe, _, loss = _model(cfg, 16)
    exe.run(main, feed=_batch(cfg, 1, 16), scope=scope,
            fetch_list=[loss.name])
    assert (ctr.value(**labels), ctr.value(**full)) == \
        (before[0] + 1, before[1] + 1)


# -- the router's attributes and the chip's share ---------------------------------

def _moe_weights(rng, d, e_total, held, f):
    return {"moe.router.w": rng.randn(d, e_total).astype(np.float32) * 0.5,
            "moe.select_bias": rng.randn(e_total).astype(np.float32) * 0.2,
            "moe.gate.w": rng.randn(held, d, f).astype(np.float32) * 0.3,
            "moe.up.w": rng.randn(held, d, f).astype(np.float32) * 0.3,
            "moe.down.w": rng.randn(held, f, d).astype(np.float32) * 0.3}


def _blk(w):
    return {"router_w": w["moe.router.w"], "select_bias":
            w["moe.select_bias"], "gate_w": w["moe.gate.w"],
            "up_w": w["moe.up.w"], "down_w": w["moe.down.w"]}


def _run_share(x, w, e_total, k, f, offset, scale=2.826, backward=True,
               **kw):
    held = w["moe.gate.w"].shape[0]

    def build():
        xv = layers.data("x", shape=list(x.shape[1:]), dtype="float32",
                         stop_gradient=False)
        out, _, _, load = layers.moe_ffn(
            xv, e_total, k, f, norm_topk_prob=True, score_func="sigmoid",
            select_bias=True, norm_eps=1e-20, route_scale=scale,
            num_held=held, expert_offset=offset, **kw)
        return [out, load], w

    if not backward:
        scope = Scope()
        with scope_guard(scope), program_guard(Program(), Program()):
            outs, params = build()
            exe = Executor()
            exe.run(pt.default_startup_program(), scope=scope, seed=5)
            scope.set_vars({n: jnp.asarray(v) for n, v in params.items()})
            out, load = exe.run(feed={"x": x}, scope=scope,
                                fetch_list=[o.name for o in outs])
        return np.asarray(out), np.asarray(load), {}
    wrt = ["x", "moe.router.w", "moe.gate.w", "moe.up.w", "moe.down.w"]
    out, load, *grads = _run_op(build, {"x": x}, wrt)
    return out, load, dict(zip(wrt, grads))


@pytest.mark.parametrize("offset,held", [
    (0, 8), (2, 4), (0, 1), pytest.param(6, 2, marks=pytest.mark.slow)])
def test_sigmoid_bias_scale_and_offset_match_the_masked_dense_reference(
        offset, held):
    """Output and the gradients of x, the router and the held experts'
    weights: sigmoid scores, a selection bias that moves the choice and not
    the weights, the kept scores renormalised (+ 1e-20) and scaled by 2.826,
    experts ``offset .. offset + held - 1`` of 8 held."""
    rng = np.random.RandomState(offset * 10 + held)
    b, t, d, e, k, f = 2, 7, 16, 8, 3, 12
    x = rng.randn(b, t, d).astype(np.float32)
    w = _moe_weights(rng, d, e, held, f)
    out, load, grads = _run_share(x, w, e, k, f, offset)
    xs = jnp.asarray(x).reshape(b * t, d)
    want, top_e = ref.routed_experts(xs, _blk(w), k, 2.826, offset)
    _close(out.reshape(b * t, d), want, 1e-5, "moe_ffn share")
    assert load.shape == (e,) and int(load.sum()) == b * t * k
    np.testing.assert_array_equal(
        load, np.bincount(np.asarray(top_e).ravel(), minlength=e))
    gx, gw = jax.grad(lambda xs, blk: jnp.sum(ref.routed_experts(
        xs, blk, k, 2.826, offset)[0] ** 2), (0, 1))(xs, _blk(w))
    _close(grads["x"].reshape(b * t, d), gx, 1e-4, "d / d x")
    for name, key in (("moe.router.w", "router_w"), ("moe.gate.w", "gate_w"),
                      ("moe.up.w", "up_w"), ("moe.down.w", "down_w")):
        _close(grads[name], gw[key], 1e-4, f"d / d {name}")


#: a toy ladder: 24 tokens, 2 experts a token, 2 of 16 experts held, row
#: tiles of 4 -> twice even routing's share is 12 rows, the most 48
TOY_LADDER = (12, 48)


@pytest.fixture
def toy_tiles(monkeypatch):
    """Row tiles of 4 on the held path, so that toy shapes have a ladder."""
    from paddle_tpu.ops import moe_ops
    monkeypatch.setattr(moe_ops, "_GMM_TILING_HELD", (4, 1024, 1024))
    return moe_ops


def _steered(n_first, n_both=0, t=24, d=16, seed=9):
    """Input [1, t, d] and weights of a share that holds experts 2 and 3 of
    16, two experts a token, with the choice steered: the router's own
    weights are small (every sigmoid score near a half), ``SelectBias`` sends
    a token to the absent experts 0 and 1, and two flag features of ``x``
    (through two large router rows) send the first ``n_first`` tokens to
    expert 3 and the first ``n_both`` of them to expert 2 besides.  Held
    rows: ``n_first + n_both``."""
    rng = np.random.RandomState(seed)
    x = rng.randn(1, t, d).astype(np.float32)
    w = _moe_weights(rng, d, 16, 2, 12)
    w["moe.router.w"] *= 0.01
    w["moe.router.w"][-2:] = 0.0
    w["moe.router.w"][-2, 3] = w["moe.router.w"][-1, 2] = 50.0
    w["moe.select_bias"] = np.array([.2, .1] + [0.] * 14, np.float32)
    x[..., -2:] = 0.0
    x[0, :n_first, -2] = 1.0
    x[0, :n_both, -1] = 1.0
    return x, w


def _run_steered(x, w, **kw):
    return _run_share(x, w, 16, 2, 12, 2, **kw)


@pytest.mark.parametrize("n_first,n_both,rung", [
    (0, 0, 0), (11, 0, 0), (12, 0, 0), (12, 1, 1), (24, 24, 1)],
    ids=["none", "L-1", "L", "L+1", "all-here"])
def test_a_skewed_router_drops_nothing_on_the_held_experts(
        toy_tiles, n_first, n_both, rung):
    """Held rows of ``L - 1``, ``L`` and ``L + 1`` around the toy ladder's
    first rung, none at all, and the worst case (every token's two experts
    held here: the last rung, full): the buffer takes all of them (no
    capacity), the rung is the shortest that holds them, the counter says
    which, and the result is the reference's."""
    x, w = _steered(n_first, n_both)
    assert toy_tiles.held_ladder(24, 2, 2, 16) == TOY_LADDER
    ctr = toy_tiles.MOE_HELD_BUFFER_CTR
    before = ctr.value(rows=str(TOY_LADDER[rung]))
    out, load, _ = _run_steered(x, w, backward=False)
    assert load[3] == n_first and load[2] == n_both
    assert toy_tiles.held_rung(n_first + n_both, TOY_LADDER) == rung
    toy_tiles.record_expert_load(load, 2, 2)
    assert ctr.value(rows=str(TOY_LADDER[rung])) == before + 1
    want, _ = ref.routed_experts(jnp.asarray(x).reshape(24, 16), _blk(w), 2,
                                 2.826, 2)
    _close(out.reshape(24, 16), want, 1e-5, "steered share")


@pytest.mark.parametrize("what", ["Out", "ExpertLoad", "x", "moe.router.w",
                                  "moe.gate.w", "moe.up.w", "moe.down.w"])
@pytest.mark.parametrize("n_first,n_both", [(5, 0), (12, 0), (12, 7)],
                         ids=["few", "L", "beyond-L"])
def test_every_rung_gives_the_full_buffer_to_the_bit(
        monkeypatch, toy_tiles, n_first, n_both, what):
    """Output, load and the gradients of x, the router and the held experts'
    weights, as the ladder's own rung gives them and as each longer rung and
    the full buffer alone give them for the same routing: equal exactly (a
    rung multiplies the same rows in the same groups; what lies behind them
    is masked)."""
    x, w = _steered(n_first, n_both)
    results = {}
    for ladder in (TOY_LADDER, (20, 48), (48,)):
        monkeypatch.setattr(toy_tiles, "held_ladder",
                            lambda *a, ladder=ladder: ladder)
        if toy_tiles.held_rung(n_first + n_both, ladder) == len(ladder) - 1 \
                and len(ladder) > 1:
            continue                     # the full buffer: (48,) covers it
        out, load, grads = _run_steered(x, w)
        results[ladder] = dict(grads, Out=out, ExpertLoad=load)
    full = results.pop((48,))
    assert results or n_first + n_both > 20
    for ladder, got in results.items():
        np.testing.assert_array_equal(got[what], full[what], str(ladder))


def test_forward_and_grad_op_choose_the_same_rung(monkeypatch, toy_tiles):
    """Every switch of both ops (three in the forward, four in the grad op)
    takes its index from the same rule (``held_rung``) over the same ladder
    and the same count, the sum of the held experts' loads."""
    x, w = _steered(9, 2)
    seen = []
    rung = toy_tiles.held_rung
    monkeypatch.setattr(toy_tiles, "held_rung", lambda rows, ladder:
                        seen.append(ladder) or rung(rows, ladder))
    _run_steered(x, w)
    # shape inference traces the forward at a stand-in batch first; the
    # step's own forward and grad op come last: 3 + 4 switches, one ladder
    assert len(seen) >= 7 and set(seen[-7:]) == {TOY_LADDER}


@pytest.mark.parametrize("shape,want", [
    ((8192, 8, 16, 128), (16384, 65536)),              # Trinity-Mini's share
    ((8192, 8, 16, 256), (8192, 16384, 65536)),        # JoyAI-LLM-Flash's
    ((8192, 8, 16, 1024), (2048, 4096, 8192, 65536)),  # at most four rungs
    ((8192, 8, 4, 128), (4096, 8192, 32768)),          # min(k, held) rows
    ((24, 2, 2, 8), (48,)),                             # under a tile: one
    ((8192, 8, 64, 128), (65536,))])                    # twice even > a quarter
def test_the_ladder_is_a_function_of_the_four_shapes(shape, want):
    from paddle_tpu.ops import moe_ops
    assert moe_ops.held_ladder(*shape) == want
    assert all(r % moe_ops._GMM_TILING_HELD[0] == 0 for r in want[:-1])
    assert list(want) == sorted(set(want))
    S, k, n_held, E = shape
    assert want[-1] == S * min(k, n_held)


def test_eight_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """The share test: the routed parts that the 8 chips' ``moe_ffn`` ops
    give (2 of 16 experts each), added up, plus the shared expert counted
    once, are the uncut reference's FFN output for the whole layer."""
    rng = np.random.RandomState(21)
    b, t, d, e, k, f = 1, 12, 16, 16, 4, 12
    x = rng.randn(b, t, d).astype(np.float32)
    whole = _moe_weights(rng, d, e, e, f)
    shared = {n: jnp.asarray(rng.randn(*s).astype(np.float32) * 0.3)
              for n, s in (("shared_gate", (d, f)), ("shared_up", (d, f)),
                           ("shared_down", (f, d)))}
    total = 0.0
    for chip in range(8):
        w = dict(whole, **{n: whole[n][2 * chip:2 * chip + 2]
                           for n in ("moe.gate.w", "moe.up.w", "moe.down.w")})
        out, load, _ = _run_share(x, w, e, k, f, 2 * chip, backward=False)
        total = total + out.reshape(b * t, d)
    xs = jnp.asarray(x).reshape(b * t, d)
    routed, _ = ref.routed_experts(xs, _blk(whole), k, 2.826, 0)
    want = ref.gated(xs, shared["shared_gate"], shared["shared_up"],
                     shared["shared_down"]) + routed
    got = total + np.asarray(ref.gated(xs, shared["shared_gate"],
                                       shared["shared_up"],
                                       shared["shared_down"]))
    _close(got, want, 1e-5, "8 shares + shared expert")
    # one chip alone is a part, not the layer
    assert np.abs(np.asarray(out.reshape(b * t, d)) - routed).max() > 1e-2


def test_routed_rows_are_counted_from_the_loads_handed_over():
    from paddle_tpu.ops import moe_ops
    ctr = moe_ops.MOE_ROUTED_ROWS_CTR
    before = ctr.value(where="all"), ctr.value(where="held")
    moe_ops.record_expert_load(np.array([5, 1, 2, 0, 4, 4]), 1, 3)
    assert ctr.value(where="all") == before[0] + 16
    assert ctr.value(where="held") == before[1] + 3


def test_moe_lowerings_carry_held_and_score_func():
    from paddle_tpu.ops.moe_ops import MOE_LOWERINGS_CTR as ctr
    labels = dict(impl="ragged_dot", experts="8", top_k="3", held="2",
                  score_func="sigmoid", ladder="10")
    before = ctr.value(**labels)
    rng = np.random.RandomState(4)
    _run_share(rng.randn(1, 5, 16).astype(np.float32),
               _moe_weights(rng, 16, 8, 2, 12), 8, 3, 12, 4)
    assert ctr.value(**labels) == before + 1


@pytest.mark.parametrize("k,order", [(3, "major"), (8, "minor")])
def test_moe_lowerings_carry_the_order_the_slots_are_summed_in(k, order):
    """``slot_sum``: ``major`` where the experts a token are no multiple of
    8 (XLA's gather brings slot ``j`` of every token home together),
    ``minor`` where they are; one lowering counts under one of the two, and a
    reader that does not name the label sees it as it did."""
    from paddle_tpu.ops.moe_ops import MOE_LOWERINGS_CTR as ctr
    assert "slot_sum" in ctr.labelnames
    labels = dict(impl="ragged_dot", experts="16", top_k=str(k), held="8",
                  score_func="sigmoid", ladder=str(5 * k))
    before = [ctr.value(**labels), ctr.value(slot_sum="major", **labels),
              ctr.value(slot_sum="minor", **labels)]
    rng = np.random.RandomState(4)
    _run_share(rng.randn(1, 5, 16).astype(np.float32),
               _moe_weights(rng, 16, 16, 8, 12), 16, k, 12, 4,
               backward=False)
    assert [ctr.value(**labels), ctr.value(slot_sum="major", **labels),
            ctr.value(slot_sum="minor", **labels)] == \
        [before[0] + 1, before[1] + (order == "major"),
         before[2] + (order == "minor")]


# -- the old lowerings are the old lowerings ----------------------------------------

#: sha256 of the StableHLO text of the OLMoE toy block's training step
#: (forward and backward, the loss and every parameter's gradient fetched, no
#: optimizer; CPU lowering: the blockwise flash fallback and ragged_dot) as
#: PR 33 left it: ``flash_attention_grad`` over the forward's Out and Lse, so
#: one forward scan a layer.  Until PR 33 the step fetched the loss alone,
#: JAX dropped the unread backward before lowering, and the hash (PR 32's,
#: taken at its parent: d6d2bc0b...) covered the forward only, which is why
#: PR 33's change of the backward did not move it.  A PR that means to change
#: OLMoE's lowering replaces it (print the text's hash from
#: ``_olmoe_step_text``) and says so.  PR 63 did, for the toy alone (it read
#: fb53143a... until then, and does still with the order forced slot-minor,
#: ``OLMOE_TOY_STEP_SLOT_MINOR``): the toy routes to two experts a token, no
#: multiple of 8, so its un-sorts bring the slots home slot-major
#: (``moe_ops._sum_over_slots``); OLMoE's own eight lower as they did
#: (``tests/test_moe.py``'s pinned k = 8 texts).
OLMOE_TOY_STEP_SHA256 = (
    "7da14bda2a33afe2ac8bc919bd50db2ce1f69cb7f0f7fcd0b11f0aabdcff63cf")
OLMOE_TOY_STEP_SLOT_MINOR = (
    "fb53143a3ba4e6e01fbdd4dbed0d149f1c63d71b5633699f940939984c39c4ae")


def _olmoe_step_text():
    cfg = olmoe_test.toy_cfg(n_layer=1)
    scope, main, exe, _, loss = olmoe_test._model(cfg, 16)
    feed = olmoe_test._batch(cfg, 1, 16)
    fetch = [loss.name] + [grad_var_name(p.name)
                           for p in main.all_parameters()]
    exe.run(main, feed=feed, scope=scope, fetch_list=fetch)
    cb = next(p for p in exe._plans.values()
              if p.cb.fetch_names == tuple(fetch)).cb
    args = ([jnp.asarray(feed[n]) for n in cb.feed_names],
            [scope.find_var(n) for n in cb.persist_ro],
            [scope.find_var(n) for n in cb.persist_rw], jnp.uint32(1))
    return re.sub(r"loc\(.*?\)", "", cb.jitted.lower(*args).as_text())


def test_olmoes_toy_block_lowers_as_it_did_before_the_new_arguments(
        monkeypatch):
    """``window=None``, ``expert_offset=0``, every expert held, softmax and
    no bias leave OLMoE's lowering alone: the lowered step's text, forward
    and backward, is the recorded one, to the byte, and apart from the order
    its two slots a token are summed in the one PR 33 left."""
    from paddle_tpu.ops import moe_ops
    text = _olmoe_step_text()
    assert text.count("stablehlo.while") == 2      # flash: forward, backward
    assert hashlib.sha256(text.encode()).hexdigest() == OLMOE_TOY_STEP_SHA256
    monkeypatch.setattr(moe_ops, "_slot_major", lambda *a: False)
    assert hashlib.sha256(_olmoe_step_text().encode()).hexdigest() == \
        OLMOE_TOY_STEP_SLOT_MINOR


def test_name_scope_rides_the_ops_and_their_grads_into_the_step():
    """``framework.name_scope``: the shared expert's and the dense FFN's ops
    carry the tag, their grad ops inherit it, the executor's scope ends in
    it, and ops outside carry none."""
    from paddle_tpu.framework import executor as E
    cfg = toy_cfg()
    scope, main, exe, _, loss = _model(cfg, 16)
    ops = main.global_block().ops
    tags = {(op.attrs.get("name_scope"), op.type.endswith("_grad"))
            for op in ops if op.attrs.get("name_scope")}
    assert tags == {("dense_ffn", False), ("dense_ffn", True),
                    ("shared_expert", False), ("shared_expert", True)}
    scoped = {E.op_scope(op) for op in ops}
    assert "pt.fwd/mul/shared_expert" in scoped
    assert "pt.bwd/mul_grad/dense_ffn" in scoped
    assert "pt.fwd/moe_ffn" in scoped and "pt.fwd/flash_attention" in scoped
