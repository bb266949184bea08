"""``pallas/ssd.py``'s kernel pair interpreted on the CPU: against
``ssd_chunked`` / ``ssd_scan_grad``'s ``jax.numpy`` lowering and against the
token-by-token recurrence of the Nemotron reference, every gradient slot and
``States``; what ``fits`` refuses; and who runs the op (the counter's
``impl``), through the executor with ``on_tpu`` patched.  What the TPU
compiler makes of the kernels is ``tests/test_dp_collective_overlap.py``'s
and the chip's (``tools/ssd_kernel_probe.py``)."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402
from paddle_tpu import layers  # noqa: E402
from paddle_tpu.framework import (Executor, Program, Scope,  # noqa: E402
                                  program_guard, scope_guard)
from paddle_tpu.framework.backward import append_backward  # noqa: E402
from paddle_tpu.framework.core import grad_var_name  # noqa: E402
from paddle_tpu.ops import ssd_ops  # noqa: E402
from paddle_tpu.pallas import ssd  # noqa: E402

REF = harness.load_module("reference", "nemotron3_nano_30b_a3b")
SLOTS = ("X", "Dt", "ALog", "B", "C", "D", "DtBias")
P, N, CHUNK = 64, 128, ssd.CHUNK


def _values(bsz=1, chunks=1, g=1, r=2, bias=True, dtype="float32", seed=0):
    """The op's seven inputs (DtBias None without ``bias``) and a cotangent
    of Out.  The steps are fresh weights' (``Delta`` 1e-3 .. 1e-1) with a
    few strong ones, so that decays of both kinds are in every chunk."""
    rs = np.random.RandomState(seed)
    h, t = g * r, chunks * CHUNK
    dt = jnp.dtype(dtype)
    x, w = (jnp.asarray(rs.randn(bsz, t, h, P), dt) for _ in range(2))
    b, c = (jnp.asarray(rs.randn(bsz, t, g, N) * 0.3, dt) for _ in range(2))
    steps = np.exp(rs.uniform(np.log(1e-3), np.log(1e-1), (bsz, t, h)))
    steps = np.where(rs.rand(bsz, t, h) < 0.02, 3.0, steps)
    if bias:
        bias_v = rs.randn(h) * 0.3
        steps = np.log(np.expm1(steps)) - bias_v      # softplus^-1
    a_log = np.log(rs.uniform(1, 16, h))
    f32 = jnp.float32
    return [x, jnp.asarray(steps, f32), jnp.asarray(a_log, f32), b, c,
            jnp.asarray(rs.randn(h), f32),
            jnp.asarray(bias_v, f32) if bias else None], w


def _recurrence(x, dt, a_log, b, c, d, dt_bias):
    """Token by token, the reference's own step over every sequence and
    head, plus the skip; float32."""
    f32 = jnp.float32
    x, b, c = (v.astype(f32) for v in (x, b, c))
    h = x.shape[2]
    delta = dt if dt_bias is None else jax.nn.softplus(dt + dt_bias)

    def one(x, delta, b, c):
        y = jax.vmap(lambda x, dl, a, b, c: REF.recurrence(x, dl, a, b, c, 8),
                     in_axes=(1, 1, 0, 1, 1), out_axes=1)(
            x, delta, -jnp.exp(a_log), REF.heads_from_groups(b, h),
            REF.heads_from_groups(c, h))
        return y + d[None, :, None] * x
    return jax.vmap(one)(x, delta, b, c)


def _both(fn, ins, w):
    """``(Out, the gradients of the inputs that are there)`` of ``fn``."""
    live = [v for v in ins if v is not None]

    def call(*a):
        return fn(*a, *([None] * (len(ins) - len(a))))
    with jax.default_matmul_precision("highest"):
        out, back = jax.vjp(call, *live)
        return out, back(w.astype(out.dtype))


def _rel(x, y):
    x, y = (np.asarray(v, np.float64) for v in (x, y))
    return float(np.linalg.norm(x - y) / max(np.linalg.norm(y), 1e-30))


def _kernels(ins, w):
    out, states = ssd.ssd_fwd(*ins, interpret=True)
    grads = ssd.ssd_bwd(*ins, states, w, interpret=True)
    return out, states, [g for g in grads if g is not None]


# a covering subset of DtBias x dtype x b x chunks x groups x R: every value
# of every factor, and every pair of the factors that meet inside the kernels
# (chunks x groups: the scratch's reset; groups x R: the columns' places;
# dtype x bias: what is widened where)
CASES = [
    # bias, dtype, b, chunks, g, r
    (True, "float32", 1, 1, 1, 2),
    (True, "float32", 2, 3, 2, 2),
    (False, "float32", 1, 3, 1, 8),
    (False, "float32", 2, 1, 2, 2),
    (True, "bfloat16", 1, 3, 2, 8),
    (False, "bfloat16", 2, 3, 1, 2),
    (True, "bfloat16", 2, 1, 1, 8),
    (False, "bfloat16", 1, 1, 2, 2),
]


@pytest.mark.parametrize("bias, dtype, bsz, chunks, g, r", CASES, ids=[
    "{}-{}-b{}-n{}-g{}-r{}".format("bias" if c[0] else "nobias", *c[1:])
    for c in CASES])
def test_the_kernels_give_the_jnp_forms_and_the_recurrences_numbers(
        bias, dtype, bsz, chunks, g, r):
    """Out, States and every gradient slot of the interpreted pair against
    the ``jax.numpy`` lowering (``ssd_chunked`` and the grad op's own
    lowering) and against ``jax.vjp`` of the recurrence."""
    ins, w = _values(bsz, chunks, g, r, bias, dtype)
    out, states, grads = _kernels(ins, w)
    assert out.dtype == ins[0].dtype and states.dtype == jnp.float32
    assert states.shape == (bsz, g * r, chunks, P, N)

    want, want_s = ssd_ops.ssd_chunked(*ins, chunk=CHUNK, with_states=True)
    slots = {"X$" + s: [v] for s, v in zip(SLOTS, ins) if v is not None}
    lowered = ssd_ops._ssd_scan_grad(
        None, dict(slots, States=[want_s], **{"OG$Out": [w]}),
        {"chunk": CHUNK})
    lowered = [lowered["IG$" + s][0] for s, v in zip(SLOTS, ins)
               if v is not None]
    rec, rec_g = _both(_recurrence, ins, w)

    # a stream's own rounding where the stream is bf16
    def tol(v):
        return 8e-3 if v.dtype == jnp.bfloat16 else 2e-4
    assert _rel(out, want.astype(out.dtype)) < tol(out) / 20
    assert _rel(out, rec.astype(out.dtype)) < tol(out) / 20
    assert _rel(states, want_s) < 1e-4
    x_scale = np.linalg.norm(np.asarray(rec_g[0], np.float64))
    names = [s for s, v in zip(SLOTS, ins) if v is not None]
    for name, got, low, truth in zip(names, grads, lowered, rec_g):
        assert got.dtype == low.dtype and got.shape == low.shape, name
        assert np.all(np.isfinite(np.asarray(got, np.float32))), name
        # the per-head sums cancel: on the other leaves' scale where they
        # come out small (tests/test_nemotron3.py's rule)
        scale = max(np.linalg.norm(np.asarray(truth, np.float64)),
                    1e-3 * x_scale)
        for other in (low, truth):
            diff = np.linalg.norm(np.asarray(got, np.float64)
                                  - np.asarray(other, np.float64))
            assert diff < tol(got) * scale, (name, diff / scale)


def test_two_sequences_are_two_calls_to_the_bit():
    ins, w = _values(bsz=2, chunks=2, g=2, r=2)
    together = _kernels(ins, w)
    for i in range(2):
        one = [v[i:i + 1] if v is not None and v.ndim >= 3 else v
               for v in ins]
        alone = _kernels(one, w[i:i + 1])
        np.testing.assert_array_equal(together[0][i], alone[0][0])
        np.testing.assert_array_equal(together[1][i], alone[1][0])
        for name, x, y in zip(SLOTS, together[2], alone[2]):
            if x.ndim >= 3:
                np.testing.assert_array_equal(x[i], y[0], err_msg=name)


@pytest.mark.parametrize("x, b, chunk, dtypes, ok", [
    ((1, 256, 4, 64), (1, 256, 2, 128), 128, ("bfloat16",) * 3, True),
    ((1, 8192, 64, 64), (1, 8192, 8, 128), 128, ("bfloat16",) * 3, True),
    ((2, 128, 2, 128), (2, 128, 1, 128), 128, ("float32",) * 3, True),
    ((1, 256, 4, 64), (1, 256, 2, 128), 16, ("float32",) * 3, False),
    ((1, 256, 4, 64), (1, 256, 2, 64), 128, ("float32",) * 3, False),
    ((1, 300, 4, 64), (1, 300, 2, 128), 128, ("float32",) * 3, False),
    ((1, 256, 4, 64), (1, 256, 2, 128), 128,
     ("float16", "float32", "float32"), False),
    ((1, 256, 4, 8), (1, 256, 2, 128), 128, ("float32",) * 3, False),
    ((1, 256, 32, 64), (1, 256, 2, 128), 128, ("float32",) * 3, False),
], ids=["toy", "cell", "wide_head", "chunk_16", "state_64", "ragged",
        "float16", "narrow_heads", "sixteen_heads_a_group"])
def test_fits_takes_whole_lane_tiles_and_whole_chunks(x, b, chunk, dtypes,
                                                      ok):
    assert ssd.fits(x, b, chunk, dtypes) is ok


# -- the op: who runs what ---------------------------------------------------

@pytest.fixture
def as_on_a_tpu(monkeypatch):
    """The op decides as it does on a TPU, and its kernels are interpreted:
    steered here, since the program has no option for it."""
    from paddle_tpu import device
    monkeypatch.setattr(device, "on_tpu", lambda: True)
    for name in ("ssd_fwd", "ssd_bwd"):
        monkeypatch.setattr(ssd, name, functools.partial(
            getattr(ssd, name), interpret=True))


def _program(ins, w, chunk=CHUNK):
    """``sum(ssd_scan(...) * W)`` and its backward through the executor:
    ``(Out, States, the gradients)``."""
    names = [s for s, v in zip(SLOTS, ins) if v is not None]
    feed = {s: np.asarray(v, np.float32) for s, v in zip(SLOTS, ins)
            if v is not None}
    feed["W"] = np.asarray(w, np.float32)
    scope, main = Scope(), Program()
    with scope_guard(scope), program_guard(main, Program()):
        vs = {s: layers.data(s, shape=list(v.shape), dtype="float32",
                             append_batch_size=False)
              for s, v in feed.items()}
        for s in names:
            vs[s].stop_gradient = False
        out = layers.ssd_scan(vs["X"], vs["Dt"], vs["ALog"], vs["B"],
                              vs["C"], vs["D"], vs.get("DtBias"), chunk=chunk)
        append_backward(layers.reduce_sum(out * vs["W"]))
        op, = [o for o in main.global_block().ops if o.type == "ssd_scan"]
        got = Executor().run(
            main, feed=feed, scope=scope,
            fetch_list=[out.name] + op.output("States")
            + [grad_var_name(vs[s].name) for s in names])
    return got[0], got[1], got[2:]


def _count(impl, chunk=CHUNK):
    return ssd_ops.SSD_LOWERINGS_CTR.value(impl=impl, chunk=str(chunk))


def test_the_counter_names_the_kernels_where_they_run(as_on_a_tpu):
    ins, w = _values(chunks=2, g=2, r=2)
    before = _count("pallas"), _count("xla")
    out, states, grads = _program(ins, w)
    assert (_count("pallas"), _count("xla")) == (
        before[0] + 2, before[1])            # the op and its grad op
    want = _kernels(ins, w)
    for x, y in zip([out, states] + list(grads),
                    [want[0], want[1]] + want[2]):
        assert _rel(x, y) <= 1e-6


def test_the_counter_names_xla_without_a_tpu_and_where_fits_refuses(
        monkeypatch):
    ins, w = _values(chunks=2, g=2, r=2)
    before = _count("pallas"), _count("xla")
    out, _, grads = _program(ins, w)         # the kernels' shapes, no TPU
    assert (_count("pallas"), _count("xla")) == (before[0], before[1] + 2)
    want, want_g = _both(functools.partial(ssd_ops.ssd_chunked, chunk=CHUNK),
                         ins, w)
    for x, y in zip([out] + list(grads), [want] + list(want_g)):
        assert _rel(x, y) <= 1e-5
    from paddle_tpu import device
    monkeypatch.setattr(device, "on_tpu", lambda: True)
    ragged = [v[:, :200] if v is not None and v.ndim >= 3 else v
              for v in ins]
    for chunk, values, cot in ((16, ins, w), (CHUNK, ragged, w[:, :200])):
        before = (_count("pallas", chunk), _count("xla", chunk))
        _program(values, cot, chunk=chunk)   # a TPU, and what fits refuses
        assert (_count("pallas", chunk), _count("xla", chunk)) == (
            before[0], before[1] + 2), chunk


def test_nemotrons_toy_program_counts_xla_alone_on_the_cpu():
    """One training step of the toy model (chunk 16, toy widths: what
    ``tests/benchmark/test_nemotron3_cell.py`` pins) lowers its scans as
    ``jax.numpy``: two Mamba blocks forward and backward, no kernel."""
    import paddle_tpu as pt
    from paddle_tpu import optimizer as opt
    from paddle_tpu.models import transformer as T
    cfg = T.NemotronHConfig(
        vocab_size=96, d_model=32, pattern="MEM*E", n_mamba_head=4,
        d_mamba_head=8, n_group=2, d_state=16, chunk=16, n_head=4,
        n_kv_head=2, d_head=8, d_expert=16, d_shared=24, n_experts=8,
        top_k=2, n_held=4, expert_offset=2)
    ids = np.random.RandomState(0).randint(1, cfg.vocab_size, (2, 41))
    feed = {"src_ids": ids[:, :-1].astype(np.int64),
            "lm_label": ids[:, 1:].astype(np.int64)}
    before = [ssd_ops.SSD_LOWERINGS_CTR.value(impl=i) for i in
              ("pallas", "xla")] + [_count("xla", 16)]
    main, startup, scope = Program(), Program(), Scope()
    with scope_guard(scope), program_guard(main, startup):
        _, _, loss = T.build_nemotron_h_pretrain(cfg, 40, attn_impl="base")
        opt.AdamWOptimizer(learning_rate=1e-2).minimize(loss)
        exe = pt.Executor()
        exe.run(startup, scope=scope, seed=1)
        exe.run(main, feed=feed, fetch_list=[loss.name], scope=scope)
    after = [ssd_ops.SSD_LOWERINGS_CTR.value(impl=i) for i in
             ("pallas", "xla")] + [_count("xla", 16)]
    assert [b - a for a, b in zip(before, after)] == [0, 4, 4]
