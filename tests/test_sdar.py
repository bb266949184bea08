"""What the SDAR cell (block-diffusion training) forced into the program, on
the CPU at toy widths: the three-part mask through the flash kernels in
interpret mode, the blockwise ``jax`` path and ``mha_reference`` against the
boolean array the four lines of the issue give, forward and all three
gradients; the kernels' tile ladder and index maps against a brute count;
leak tests on the model (what each half of the doubled stream may and may
not see); the toy model against the plain reference, loss, hidden states and
gradient; the recomputed step against the plain step; the eight expert
shares' sum; and planted faults, each failing a named check."""

import importlib
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import paddle_tpu as pt  # noqa: E402
from benchmark import harness  # noqa: E402
from paddle_tpu import layers  # noqa: E402
from paddle_tpu.framework import (Program, Scope, program_guard,  # noqa: E402
                                  scope_guard)
from paddle_tpu.framework.backward import append_backward  # noqa: E402
from paddle_tpu.framework.core import grad_var_name  # noqa: E402
from paddle_tpu.models import transformer as T  # noqa: E402

fa = importlib.import_module("paddle_tpu.pallas.flash_attention")
CONFIG = "sdar_30b_a3b"
REF = harness.load_module("reference", CONFIG)
MODEL = harness.load_module("models", CONFIG)


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want)
                 / max(np.linalg.norm(want), 1e-30))


def brute_mask(seq, block):
    """The issue's four lines, pair by pair."""
    t = 2 * seq
    m = np.zeros((t, t), bool)
    for i in range(t):
        for j in range(t):
            bi, bj = (i % seq) // block, (j % seq) // block
            if i < seq and j < seq:
                m[i, j] = bi == bj
            elif i < seq:
                m[i, j] = bj < bi
            elif j >= seq:
                m[i, j] = bj <= bi
    return m


# -- the mask in the kernels --------------------------------------------------

#: (L, B, block_q, block_k): L a multiple of the tile and not, tiles that
#: straddle the two halves, padding, B 1 / 4 / 32 and one that is no power
#: of two
SHAPES = [(96, 4, 64, 64), (96, 1, 64, 32), (96, 32, 32, 64),
          (100, 4, 64, 64), (128, 4, 64, 64), (36, 3, 16, 16)]


@pytest.mark.parametrize("seq, block, bq, bk", SHAPES)
def test_the_mask_and_the_tile_ladder_against_a_brute_count(seq, block, bq,
                                                            bk):
    """``BlockDiffusion.dense`` is the issue's four lines; ``tile_state``
    says live where a tile holds a visible pair and full where every pair
    is visible and none is padding; the index maps name a live tile's own
    block and, on dead steps, copy nothing a live step would not."""
    form = fa.block_diffusion(2 * seq, block)
    mask = brute_mask(seq, block)
    t = 2 * seq
    assert (np.asarray(form.dense(t, t)) == mask).all()
    assert mask.sum() == seq * seq + seq * block
    nq, nk = -(-t // bq), -(-t // bk)
    live, full = form.tile_state(np.arange(nq)[:, None] * bq,
                                 np.arange(nk)[None] * bk, bq, bk, xp=np)
    for i in range(nq):
        for j in range(nk):
            tile = mask[i * bq:(i + 1) * bq, j * bk:(j + 1) * bk]
            whole = (i + 1) * bq <= t and (j + 1) * bk <= t
            assert live[i, j] == tile.any(), (i, j)
            assert full[i, j] == (tile.all() and whole), (i, j)
    pairs = form.tile_pairs(bq, bk)
    assert pairs == {"free": int(full.sum()),
                     "masked": int((live & ~full).sum()),
                     "dead": int((~live).sum())}

    def walk(named, alive):
        copies, last = 0, None
        for step, is_live in enumerate(alive):
            at = int(named(step))
            if is_live:
                assert at == step
            copies += at != last
            last = at
        assert copies <= alive.sum() + 1

    for i in range(nq):
        walk(lambda j: form.live_k(jnp.int32(i), jnp.int32(j), bq, bk),
             live[i])
    for j in range(nk):
        walk(lambda i: form.live_q(jnp.int32(i), jnp.int32(j), bq, bk),
             live[:, j])


@pytest.mark.parametrize("seq, block, bq, bk", SHAPES)
def test_the_kernels_the_jax_path_and_the_reference_under_the_mask(
        seq, block, bq, bk):
    """Forward and dQ, dK, dV of the fused and the split Pallas backward
    (interpret mode), of the blockwise jax path and of ``mha_reference``,
    all against attention under the brute boolean array, at 8 query heads a
    K/V head."""
    form = fa.block_diffusion(2 * seq, block)
    t, h, hk, d = 2 * seq, 8, 1, 16
    r = np.random.RandomState(seq + block)
    q, k, v = (jnp.asarray(r.randn(1, n, t, d), jnp.float32)
               for n in (h, hk, hk))
    mask = jnp.asarray(brute_mask(seq, block))

    def oracle(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(k, h, 1)) / d ** 0.5
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p, jnp.repeat(v, h, 1))

    def both(f):
        weight = jnp.cos(jnp.arange(d, dtype=jnp.float32))
        return f(q, k, v), jax.grad(lambda *a: (f(*a) * weight).sum(),
                                    (0, 1, 2))(q, k, v)

    kw = dict(window=form, block_q=bq, block_k=bk)
    with jax.default_matmul_precision("highest"):
        o_ref, g_ref = both(oracle)
        paths = {
            "reference": lambda *a: fa.mha_reference(*a, window=form),
            "jax": lambda *a: fa.flash_attention(*a, **kw),
            "fused": lambda *a: fa.flash_attention(*a, interpret=True, **kw),
            "split": lambda *a: fa.flash_attention(
                *a, interpret=True, bwd_impl="split", **kw)}
        for name, f in paths.items():
            o, g = both(f)
            assert rel(o, o_ref) < 2e-6, name
            for got, want, leaf in zip(g, g_ref, "qkv"):
                assert rel(got, want) < 5e-6, (name, leaf)
    assert fa.flash_bwd_kernel(q, k, v, interpret=True, **kw) == "fused"


def test_the_mask_form_is_the_whole_mask_of_a_self_attention():
    with pytest.raises(ValueError, match="two copies"):
        fa.block_diffusion(30, 4)                 # 15 rows a copy
    with pytest.raises(ValueError, match="two copies"):
        fa.block_diffusion(31, 1)
    main = Program()
    with program_guard(main, Program()):
        q = layers.data("q", shape=[1, 2, 16, 8], dtype="float32",
                        append_batch_size=False)
        out = layers.flash_attention(q, q, q, causal=True, block_diffusion=4)
    with pytest.raises(RuntimeError, match="whole mask"):
        pt.Executor().run(main, feed={"q": np.zeros((1, 2, 16, 8), "f4")},
                          fetch_list=[out.name])


# -- the toy model ------------------------------------------------------------

def toy_cfg(block=4, **kw):
    return T.SdarConfig(vocab_size=64, d_model=32, n_layer=2, n_head=8,
                        n_kv_head=1, d_head=8, d_expert=16, n_experts=8,
                        top_k=2, block_diffusion=block, mask_token_id=63,
                        **kw)


def toy_feed(cfg, seq, seed=0, batch=1):
    return MODEL.make_batch(np.random.RandomState(seed), cfg, batch, seq)


class Toy:
    """The toy model's forward programs over one scope: block diffusion's
    (every block boundary of the doubled stream fetched) and, with the same
    weights, the causal LM's.  The head is the unfused one by default: the
    fused head's chunks multiply in bf16 whatever the precision asked for,
    which moves a toy loss by 1e-4."""

    def __init__(self, cfg, seq, seed=3, fused_head=False):
        self.cfg, self.seq, self.scope = cfg, seq, Scope()
        self.main, startup = Program(), Program()
        with scope_guard(self.scope), program_guard(self.main, startup):
            self.stream = []
            self.feeds, self.parts, self.loss = T.build_sdar_pretrain(
                cfg, seq, fused_head=fused_head, checkpoints=self.stream)
        self.exe = pt.Executor()
        self.exe.run(startup, scope=self.scope, seed=seed)

    def run(self, feed, fetch):
        with jax.default_matmul_precision("highest"):
            return [np.asarray(v) for v in self.exe.run(
                self.main, feed=feed, fetch_list=fetch, scope=self.scope)]

    def last(self, feed):
        """The last block's output over the doubled stream, [b, 2L, d]."""
        return self.run(feed, [self.stream[-1].name])[0]

    def params(self):
        return MODEL.reference_params(
            lambda n: jnp.asarray(self.scope.find_var(n), jnp.float32),
            self.cfg)


def test_the_clean_half_is_blind_to_the_noisy_ids_and_causal_at_block_1():
    """The clean half's states do not move when the noisy ids change; and
    at B = 1, with the same weights, they are the causal program's."""
    seq = 24
    for block in (4, 1):
        toy = Toy(toy_cfg(block), seq)
        feed = toy_feed(toy.cfg, seq)
        other = dict(feed, noisy_ids=np.roll(feed["noisy_ids"], 5, axis=1))
        a, b = toy.last(feed), toy.last(other)
        assert np.array_equal(a[:, seq:], b[:, seq:])
        assert rel(a[:, :seq], b[:, :seq]) > 1e-2
    causal_cfg = toy_cfg(1)
    causal_cfg.block_diffusion = None
    main = Program()
    with scope_guard(toy.scope), program_guard(main, Program()):
        outs = []
        T._causal_lm(causal_cfg, seq, checkpoints=outs)
    with jax.default_matmul_precision("highest"):
        want, = toy.exe.run(main, feed={
            "src_ids": feed["clean_ids"], "lm_label": feed["lm_label"]},
            fetch_list=[outs[-1].name], scope=toy.scope)
    assert rel(a[:, seq:], want) < 2e-6


def _leaks(toy, seq, block, b):
    """How far noisy block ``b``'s output moves when (another noisy block,
    an earlier clean block, clean block ``b``, a later clean block)
    changes."""
    feed = toy_feed(toy.cfg, seq, seed=1)
    rows = slice(b * block, (b + 1) * block)
    base = toy.last(feed)[:, rows]

    def moved(key, at):
        ids = feed[key].copy()
        ids[:, at * block:(at + 1) * block] = \
            (ids[:, at * block:(at + 1) * block] + 7) % 60 + 1
        return rel(toy.last(dict(feed, **{key: ids}))[:, rows], base)
    return (moved("noisy_ids", b + 1), moved("clean_ids", b - 1),
            moved("clean_ids", b), moved("clean_ids", b + 1))


def test_a_noisy_block_sees_its_own_block_and_the_clean_blocks_before():
    """Noisy block b's output does not move when another noisy block or a
    clean block >= b changes, and does when an earlier clean block does."""
    seq, block, b = 24, 4, 2
    other_noisy, before, own, after = _leaks(Toy(toy_cfg(block), seq), seq,
                                             block, b)
    assert other_noisy == own == after == 0.0
    assert before > 1e-3


def test_planted_less_or_equal_in_noisy_to_clean_leaks_the_answer(
        monkeypatch):
    """``<=`` for ``<`` in noisy -> clean lets a noisy block read its own
    clean tokens, the answer: the leak test above sees it."""
    def leaky(self, q_pos, k_pos, tq_real=None, tk_real=None):
        half, blk = self.half, self._blk
        qn, kn = q_pos < half, k_pos < half
        bq, bk = blk(jnp.where(qn, q_pos, q_pos - half)), \
            blk(jnp.where(kn, k_pos, k_pos - half))
        return jnp.where(kn, qn & (bq == bk), bk <= bq)
    monkeypatch.setattr(fa.BlockDiffusion, "visible", leaky)
    seq, block, b = 24, 4, 2
    _, before, own, after = _leaks(Toy(toy_cfg(block), seq), seq, block, b)
    assert own > 1e-3 and before > 1e-3 and after == 0.0


# -- the program against the reference ----------------------------------------

def _against_reference(toy, feed, label=None, weight=None):
    """``(loss, hidden, gradient)`` relative distances of the toy float32
    program from the reference on ``feed`` (``label`` / ``weight``: what
    the REFERENCE is given in their place)."""
    cfg, seq = toy.cfg, toy.seq
    params = toy.params()
    main = toy.main.clone()
    with scope_guard(toy.scope), program_guard(main, Program()):
        loss = main.global_block().var(toy.loss.name)
        append_backward(loss)
    names = [p.name for p in main.all_parameters()]
    with jax.default_matmul_precision("highest"):
        got = [np.asarray(v) for v in toy.exe.run(
            main, feed=feed, scope=toy.scope,
            fetch_list=[toy.loss.name, toy.parts["hidden"].name]
            + [grad_var_name(n) for n in names])]
    ref_feed = dict(feed)
    if label is not None:
        ref_feed["lm_label"] = label
    if weight is not None:
        ref_feed["loss_weight"] = weight
    kw = MODEL.reference_kw(cfg, 16)
    args = [jnp.asarray(ref_feed[k]) for k in MODEL.FEEDS]
    sums = REF.batch_sums(params, *args, **kw)
    want = float(REF.loss_of_sums(sums)["loss"])
    g_ref = jax.grad(lambda p: REF.loss(p, *args, **kw))(params)
    grads = MODEL.reference_params(dict(zip(names, got[2:])).__getitem__,
                                   cfg)
    g_off = MODEL.gradient_difference(
        jax.tree_util.tree_map(np.asarray, g_ref), grads)
    return (abs(float(got[0]) - want) / abs(want),
            rel(got[1], sums["hidden"]), g_off["all"])


@pytest.mark.parametrize("block, seq, batch", [(4, 24, 2), (1, 16, 1),
                                               (32, 64, 1)])
def test_the_toy_program_against_the_reference(block, seq, batch):
    """Loss, final-norm output over the noisy half and every parameter's
    gradient, float32 at ``highest``, for B in {1, 4, 32}."""
    toy = Toy(toy_cfg(block), seq)
    loss, hidden, grad = _against_reference(
        toy, toy_feed(toy.cfg, seq, seed=5, batch=batch))
    assert loss < 2e-6 and hidden < 2e-6 and grad < 2e-5, (loss, hidden,
                                                           grad)


def test_the_unfused_head_reads_the_same_loss():
    seq = 24
    feed = toy_feed(toy_cfg(), seq, seed=5)
    a = Toy(toy_cfg(), seq, fused_head=True)
    b = Toy(toy_cfg(), seq, fused_head=False)
    la, = a.run(feed, [a.loss.name])
    lb, = b.run(feed, [b.loss.name])
    assert abs(float(la) - float(lb)) < 1e-3 * abs(float(la))


def test_planted_positions_not_restarted_move_the_hidden_states(monkeypatch):
    """The second copy turned by positions L .. 2L - 1: the clean half is
    no longer the causal program's and the loss leaves the reference's."""
    monkeypatch.setattr(T, "_turned_by_copy", lambda t, turn: turn(t))
    toy = Toy(toy_cfg(4), 24)
    loss, hidden, grad = _against_reference(toy, toy_feed(toy.cfg, 24,
                                                          seed=5))
    assert hidden > 1e-2 and grad > 1e-2


def test_planted_shifted_label_and_weight_left_out_move_the_loss():
    """A label shifted by one position (the next-token habit) and a loss
    that forgets ``1 / t``: each is a different number from the
    reference's, by far more than rounding."""
    toy = Toy(toy_cfg(4), 24)
    feed = toy_feed(toy.cfg, 24, seed=5)
    # the program is fed the fault, the reference the objective
    shifted = dict(feed, lm_label=np.roll(feed["lm_label"], -1, axis=1))
    loss, _, grad = _against_reference(toy, shifted, label=feed["lm_label"])
    assert loss > 1e-3 and grad > 0.1
    flat = dict(feed, loss_weight=np.ones_like(feed["loss_weight"]))
    loss, _, grad = _against_reference(toy, flat,
                                       weight=feed["loss_weight"])
    assert loss > 0.1 and grad > 0.1


# -- the step ------------------------------------------------------------------

def _step(recompute):
    sys.path.insert(0, os.path.join(ROOT, "tests", "benchmark"))
    import test_sdar_cell as cell
    config, traffic = cell.toy_sdar(recompute=recompute)
    m = MODEL.build_train(config, traffic, 11, 1, False)
    loss, = m["exe"].run(m["program"], feed=m["ring"][0],
                         fetch_list=[m["loss"]], scope=m["scope"])
    grads = {p: np.asarray(m["scope"].find_var(v), np.float32)
             for p, v in m["moment1"].items()}
    ops = [op.type for op in m["program"].global_block().ops]
    return float(np.asarray(loss)), grads, ops


def test_the_recomputed_step_against_the_plain_step():
    """Loss and every parameter's first moment after one AMP step: the
    recomputed step (a checkpoint at every block boundary) reads what the
    plain step reads; it holds more flash forwards and no more backwards."""
    plain, g_plain, ops_plain = _step(False)
    loss, grads, ops = _step(True)
    assert abs(loss - plain) <= 1e-6 * abs(plain)
    for name, g in g_plain.items():
        # bf16 re-rounding; a router hears of it through the choice
        assert rel(grads[name], g) < (
            0.1 if "router" in name else 2e-2), name
    assert ops.count("flash_attention") == 2 * \
        ops_plain.count("flash_attention") == 4
    assert ops.count("flash_attention_grad") == 2


# -- the expert shares add up ---------------------------------------------------

def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Eight chips' shares of 16 experts (toy: the cell's eight shares of
    128): each share's partial result from ``moe_ffn``'s held path adds up
    to the reference's routed FFN over all 16 experts."""
    d, f, E, k, S = 16, 24, 16, 3, 64
    r = np.random.RandomState(7)
    full = {"router_w": r.randn(d, E), "gate_w": 0.3 * r.randn(E, d, f),
            "up_w": 0.3 * r.randn(E, d, f), "down_w": 0.3 * r.randn(E, f, d)}
    full = {n: jnp.asarray(a, jnp.float32) for n, a in full.items()}
    m = jnp.asarray(r.randn(S, d), jnp.float32)
    want = REF.whole_layer_ffn(m, full, k)
    total = np.zeros((S, d))
    for share in range(8):
        cfg = T.SdarConfig(vocab_size=8, d_model=d, n_layer=1, n_head=2,
                           n_kv_head=1, d_head=8, d_expert=f, n_experts=E,
                           top_k=k, n_held=2, expert_offset=2 * share)
        main, startup, scope = Program(), Program(), Scope()
        with scope_guard(scope), program_guard(main, startup):
            xv = layers.data("x", shape=[1, S, d], dtype="float32",
                             append_batch_size=False)
            (out,), (_, _, load) = T.routed_ffn(xv, cfg, "dec_0")
            exe = pt.Executor()
            exe.run(startup, scope=scope, seed=1)
        held = slice(2 * share, 2 * share + 2)
        for name, value in (("dec_0.moe.router.w", full["router_w"]),
                            ("dec_0.moe.gate.w", full["gate_w"][held]),
                            ("dec_0.moe.up.w", full["up_w"][held]),
                            ("dec_0.moe.down.w", full["down_w"][held])):
            assert np.shape(scope.find_var(name)) == value.shape, name
            scope.set_var(name, value)
        with jax.default_matmul_precision("highest"):
            got, rows = exe.run(main, feed={"x": np.asarray(m)[None]},
                                fetch_list=[out.name, load.name],
                                scope=scope)
        assert int(np.asarray(rows).sum()) == S * k
        total += np.asarray(got[0], np.float64)
        if share == 0:
            first = np.asarray(got[0], np.float64)
    assert rel(total, want) < 1e-5
    assert rel(first, want) > 0.05           # one share alone is not the layer
