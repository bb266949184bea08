"""``pallas/kda.py``: the ``kda_scan`` kernel pair interpreted on the CPU at
128-wide heads, against ``kda_chunked`` (the jnp form the op lowers to
elsewhere) and against the recurrence token by token: Out, the chunk states
and the five gradients; beta doubled and not, a ragged end, a strong decay,
near-parallel keys, bf16 streams, four chunks (``dS`` over three boundaries),
three heads of two sequences, Ling's bounded decays with beta as it comes,
rows whose beta is 0.  Then the op: who runs what (``fits``, the
counter's ``impl``), and a recomputed program whose grad op reads the states
of the segment's own copy.  (The kernels compiled for a described v5e at the
cell's shapes: ``tests/test_dp_collective_overlap.py``, the one file that
loads the TPU's compiler.)  Nothing here is a speed number."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import layers
from paddle_tpu.framework.backward import append_backward
from paddle_tpu.framework import (Executor, Program, Scope, program_guard,
                                  scope_guard)
from paddle_tpu.framework.core import grad_var_name
from paddle_tpu.framework.recompute import apply_recompute
from paddle_tpu.ops import kda_ops
from paddle_tpu.pallas import kda

SLOTS = ("Q", "K", "V", "G", "Beta")
CHUNK = 64
D = 128


def _values(t, b=1, h=2, decay=0.3, parallel=False, strong=False, seed=0,
            dtype=np.float32, bounded=False, beta_zero=False):
    r = np.random.RandomState(seed)
    shape = (b, t, h, D)
    v = {s: r.randn(*shape).astype(np.float32) for s in ("Q", "K", "V")}
    if parallel:        # every key within a few degrees of one direction
        v["K"] = (r.randn(1, 1, h, D) + 0.05 * r.randn(*shape)).astype(
            np.float32)
    v["G"] = -(np.abs(r.randn(*shape)) * decay).astype(np.float32)
    if strong:          # exp(-20 * 32) is 0 in float32 many times over
        v["G"][:, 40:72] = -20.0 - np.abs(r.randn(b, 32, h, D)).astype(
            np.float32)
    if bounded:         # Ling's gate: -5 sigmoid(.), in (-5, 0)
        v["G"] = (-5.0 / (1 + np.exp(-r.randn(*shape)))).astype(np.float32)
    logits = r.randn(b, t, h) + (2.0 if parallel else 0.0)
    v["Beta"] = (1 / (1 + np.exp(-logits))).astype(np.float32)
    if beta_zero:       # a third of the positions write nothing
        v["Beta"][r.rand(b, t, h) < 1 / 3] = 0.0
    v["W"] = r.randn(*shape).astype(np.float32)         # Out's cotangent
    for s in ("Q", "K", "V", "W"):
        v[s] = jnp.asarray(v[s], dtype)
    return {s: jnp.asarray(x) for s, x in v.items()}


def _recurrence(q, k, v, g, beta, *, neg_eigval):
    """``(out [b, t, h, d_v], states [b, h, ceil(t / CHUNK), d_k, d_v])``
    by the module's first two equations, a position at a time."""
    f32 = jnp.float32
    q, k, v, g, beta = (x.astype(f32) for x in (q, k, v, g, beta))
    q = kda_ops.l2norm(q) * q.shape[-1] ** -0.5
    k = kda_ops.l2norm(k)
    beta = beta * (2.0 if neg_eigval else 1.0)

    def head(q, k, v, g, beta):             # [t, d], beta [t]
        def step(s, x):
            q_t, k_t, v_t, g_t, b_t = x
            s_in = s
            s = jnp.exp(g_t)[:, None] * s
            u = b_t * (v_t - jnp.einsum("kv,k->v", s, k_t,
                                        precision="highest"))
            s = s + k_t[:, None] * u[None, :]
            return s, (jnp.einsum("kv,k->v", s, q_t, precision="highest"),
                       s_in)
        _, (o, before) = jax.lax.scan(
            step, jnp.zeros((q.shape[-1], v.shape[-1]), f32),
            (q, k, v, g, beta))
        return o, before[::CHUNK]
    per_batch = jax.vmap(head, in_axes=1, out_axes=(1, 0))
    return jax.vmap(per_batch)(q, k, v, g, beta)


CASES = {
    # name: (positions, values' keywords, neg_eigval)
    "doubled": (128, {}, True),
    "once": (128, {"seed": 1}, False),
    "ragged": (100, {"b": 2, "h": 1, "seed": 2}, True),
    "strong": (128, {"strong": True, "seed": 3}, True),
    "parallel": (128, {"parallel": True, "decay": 0.02, "seed": 4}, True),
    "bf16": (128, {"dtype": jnp.bfloat16, "seed": 5}, True),
    "four_chunks": (256, {"seed": 6}, True),
    "two_by_three": (128, {"b": 2, "h": 3, "seed": 7}, True),
    "bounded_once": (128, {"bounded": True, "seed": 8}, False),
    "beta_zero": (128, {"beta_zero": True, "seed": 9}, True),
}


@pytest.fixture(scope="module")
def runs():
    """Each case once: the kernels' (Out, States, gradients), the jnp
    form's and the recurrence's."""
    out = {}
    for name, (t, kw, neg) in CASES.items():
        v = _values(t, **kw)
        ins = [v[s] for s in SLOTS]
        opts = dict(chunk=CHUNK, neg_eigval=neg)
        o, states = kda.kda_fwd(*ins, interpret=True, **opts)
        grads = kda.kda_bwd(*ins, states, v["W"], interpret=True, **opts)
        w = v["W"].astype(jnp.float32)

        def both(fn):
            (o, s), back = jax.vjp(fn, *ins)
            return [o, s] + list(back((w, jnp.zeros_like(s))))
        chunked = both(functools.partial(kda_ops.kda_chunked,
                                         with_states=True, **opts))
        exact = both(functools.partial(_recurrence, neg_eigval=neg))
        out[name] = ([o, states] + list(grads), chunked, exact)
    return out


def _rel(x, y):
    x, y = (np.asarray(z, np.float32) for z in (x, y))
    return float(np.linalg.norm(x - y) / max(np.linalg.norm(y), 1e-30))


@pytest.mark.parametrize("what", ("Out", "States") + SLOTS)
@pytest.mark.parametrize("case", list(CASES))
def test_the_kernels_give_the_jnp_forms_and_the_recurrences_numbers(
        case, what, runs):
    """Out, the state before every chunk and the five gradients, in the
    inputs' dtypes.  float32 streams: within the op's 1e-4 of both.  bf16
    streams: what is stored in bf16 within bf16's rounding, the float32
    ones (States, dG, dBeta) within the rounding of dOut and of the
    streams that made them."""
    i = (("Out", "States") + SLOTS).index(what)
    got, chunked, exact = (r[i] for r in runs[case])
    assert np.isfinite(np.asarray(got, np.float32)).all()
    assert got.shape == exact.shape == chunked.shape
    if case != "bf16":
        tol = 1e-4
    else:
        tol = 1e-5 if what == "States" else 1e-2
        assert got.dtype == (jnp.float32 if what in ("States", "G", "Beta")
                             else jnp.bfloat16)
    assert _rel(got, chunked) <= tol, (case, what, "kda_chunked")
    assert _rel(got, exact) <= tol, (case, what, "recurrence")


@pytest.fixture(scope="module")
def written_out():
    """The ``ragged`` case (100 positions) with its 28 padded positions
    written out as zeros: the five gradients of the 128."""
    t, kw, neg = CASES["ragged"]
    v = {s: jnp.pad(x, [(0, 0), (0, 128 - t)] + [(0, 0)] * (x.ndim - 2))
         for s, x in _values(t, **kw).items()}
    assert (np.asarray(v["Beta"])[:, t:] == 0).all()
    ins = [v[s] for s in SLOTS]
    opts = dict(chunk=CHUNK, neg_eigval=neg, interpret=True)
    _, states = kda.kda_fwd(*ins, **opts)
    return t, kda.kda_bwd(*ins, states, v["W"], **opts)


@pytest.mark.parametrize("what", SLOTS)
def test_zeros_behind_a_ragged_end_get_zero_gradients(what, written_out,
                                                      runs):
    """Behind the end every gradient is 0 to the bit (no key, no write, no
    decay, no cotangent; and beta = 0 is divided by nowhere), and before it
    the numbers are the ragged call's."""
    t, grads = written_out
    got = np.asarray(grads[SLOTS.index(what)])
    assert (got[:, t:] == 0).all()
    np.testing.assert_array_equal(
        got[:, :t], np.asarray(runs["ragged"][0][2 + SLOTS.index(what)]))


def test_the_strong_decay_is_strong_and_the_parallel_keys_parallel(runs):
    """What the two hard cases are made of: a quotient of cumulated decays
    would be 0 / 0 in the first, and in the second ``A``'s entries stand near
    beta, up to 1.9, all of one sign: ``A^16`` alone passes 1e12, so a solve
    built from powers of ``A`` keeps no digit there."""
    v = _values(128, strong=True, seed=3)
    cum = np.exp(np.cumsum(np.asarray(v["G"]), axis=1))
    assert (cum[:, 71] == 0).all()
    v = _values(128, parallel=True, decay=0.02, seed=4)
    k = np.asarray(kda_ops.l2norm(v["K"]))[0, :CHUNK, 0]
    beta = 2 * np.asarray(v["Beta"])[0, :CHUNK, 0]
    a = np.tril(beta[:, None] * (k @ k.T), -1)
    assert np.median(k @ k.T) > 0.99 and beta.max() > 1.8
    assert np.abs(np.linalg.matrix_power(a, 16)).max() > 1e12
    assert np.abs(np.linalg.inv(np.eye(CHUNK) + a)).max() < 4


@pytest.mark.parametrize("fn,cotangents,products", [
    ("_chunk", 0, 11), ("_chunk_back", 2, 27)])
def test_every_product_of_a_chunk_is_at_highest_and_counted(fn, cotangents,
                                                            products):
    """What the kernels cost by: the chunk's products, forward eleven and
    the hand-written backward 27 (nine to make the chunk's tensors again),
    each float32 at ``highest``, and nothing differentiated by jax inside
    either (no custom rule, no loop)."""
    tile = jnp.zeros((CHUNK, D), jnp.float32)
    args = [tile] * 4 + [jnp.zeros((CHUNK, 1)), jnp.zeros((D, D))] + [
        tile, jnp.zeros((D, D))][:cotangents]
    jaxpr = jax.make_jaxpr(functools.partial(getattr(kda, fn),
                                             neg_eigval=True))(*args)
    dots = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "dot_general"]
    assert len(dots) == products
    for e in dots:
        assert e.params["preferred_element_type"] == jnp.float32
        assert set(e.params["precision"]) == {jax.lax.Precision.HIGHEST}
    names = {e.primitive.name for e in jaxpr.jaxpr.eqns}
    assert not names & {"custom_vjp_call", "custom_vjp_call_jaxpr",
                        "custom_jvp_call", "while", "scan"}


@pytest.mark.parametrize("d_k,d_v,chunk,dtypes,ok", [
    (128, 128, 64, ("bfloat16",) * 3 + ("float32",) * 2, True),
    (128, 256, 16, ("float32",) * 5, True),
    (256, 128, 128, ("float32",) * 5, True),
    (16, 16, 64, ("float32",) * 5, False),          # the tests' toy heads
    (8, 8, 16, ("float32",) * 5, False),
    (128, 64, 64, ("float32",) * 5, False),
    (128, 128, 8, ("float32",) * 5, False),         # no whole sub-block
    (128, 128, 72, ("float32",) * 5, False),
    (128, 128, 64, ("float16",) + ("float32",) * 4, False),
    (128, 128, 64, ("float32",) * 4 + ("float64",), False),
])
def test_fits_takes_whole_lane_tiles_and_sub_blocks(d_k, d_v, chunk, dtypes,
                                                    ok):
    assert kda.fits(d_k, d_v, chunk, dtypes) is ok


# -- the op: who runs what ---------------------------------------------------

@pytest.fixture
def as_on_a_tpu(monkeypatch):
    """The op decides as it does on a TPU, and its kernels are interpreted:
    steered here, since the program has no option for it."""
    from paddle_tpu import device
    monkeypatch.setattr(device, "on_tpu", lambda: True)
    for name in ("kda_fwd", "kda_bwd"):
        monkeypatch.setattr(kda, name, functools.partial(
            getattr(kda, name), interpret=True))


def _program(v, recompute=False):
    """``sum(scale(kda_scan(scale(Q), K, V, G, Beta)) * W)`` and its
    backward; ``recompute``: the two ``scale`` outputs are the checkpoints,
    so the scan between them is made again on the way back."""
    scope, main = Scope(), Program()
    with scope_guard(scope), program_guard(main, Program()):
        ins = {s: layers.data(s, shape=list(v[s].shape), dtype="float32",
                              append_batch_size=False)
               for s in SLOTS + ("W",)}
        for s in SLOTS:
            ins[s].stop_gradient = False
        first = layers.scale(ins["Q"], scale=1.0)
        out = layers.kda_scan(first, *(ins[s] for s in SLOTS[1:]),
                              chunk=CHUNK, neg_eigval=True)
        last = layers.scale(out, scale=1.0)
        loss = layers.reduce_sum(last * ins["W"])
        append_backward(loss)
        if recompute:
            apply_recompute(main, [first.name, last.name])
        got = Executor().run(
            main, feed={s: np.asarray(x) for s, x in v.items()}, scope=scope,
            fetch_list=[out.name] + [grad_var_name(ins[s].name)
                                     for s in SLOTS])
    return main, got


def _narrow(v):
    """The same values at 16-wide heads: what no kernel takes."""
    return {s: x[..., :16] if x.ndim == 4 else x for s, x in v.items()}


def _count(impl, d):
    return kda_ops.KDA_LOWERINGS_CTR.value(
        heads="2", head_dim=str(d), chunk=str(CHUNK), impl=impl,
        neg_eigval="true")


def test_the_counter_names_the_kernels_where_they_run(as_on_a_tpu, runs):
    v = _values(128)
    before = _count("pallas", D), _count("xla", D)
    _, got = _program(v)
    assert (_count("pallas", D), _count("xla", D)) == (
        before[0] + 2, before[1])            # the op and its grad op
    want = runs["doubled"][0]
    for x, y in zip(got, [want[0]] + want[2:]):
        assert _rel(x, y) <= 1e-6


def test_the_counter_names_xla_without_a_tpu_and_at_narrow_heads(
        monkeypatch):
    v = _values(128)
    before = _count("pallas", D), _count("xla", D)
    _, got = _program(v)                    # 128 wide, and no TPU
    assert (_count("pallas", D), _count("xla", D)) == (
        before[0], before[1] + 2)
    want = jax.grad(lambda *a: jnp.sum(kda_ops.kda_chunked(
        *a, chunk=CHUNK, neg_eigval=True) * v["W"]), argnums=(0, 1, 2, 3, 4))(
        *(v[s] for s in SLOTS))
    for x, y in zip(got[1:], want):
        assert _rel(x, y) <= 1e-6
    from paddle_tpu import device
    monkeypatch.setattr(device, "on_tpu", lambda: True)
    before = _count("pallas", 16), _count("xla", 16)
    _program(_narrow(v))                     # a TPU, and 16 wide
    assert (_count("pallas", 16), _count("xla", 16)) == (
        before[0], before[1] + 2)


def test_states_is_an_output_of_the_op_on_both_paths(as_on_a_tpu, runs):
    """The slot is written whoever runs the op: the kernels' states, or
    the scan's carry."""
    v = _values(128)
    for values in (v, _narrow(v)):
        scope, main = Scope(), Program()
        with scope_guard(scope), program_guard(main, Program()):
            ins = [layers.data(s, shape=list(values[s].shape),
                               dtype="float32", append_batch_size=False)
                   for s in SLOTS]
            layers.kda_scan(*ins, chunk=CHUNK, neg_eigval=True)
            op, = main.global_block().ops
            states, = Executor().run(
                main, feed={s: np.asarray(values[s]) for s in SLOTS},
                scope=scope, fetch_list=op.output("States"))
        want = _recurrence(*(values[s] for s in SLOTS), neg_eigval=True)[1]
        assert states.shape == want.shape == (1, 2, 2) + (
            values["Q"].shape[-1],) * 2
        assert _rel(states, want) <= 1e-5


def test_a_recomputed_scan_hands_its_own_states_to_the_grad_op(as_on_a_tpu):
    """Under recomputation the grad op reads the States of the segment's
    copy of the op, the forward role's States has no reader, and the numbers
    are the plain program's."""
    v = _values(128)
    _, plain = _program(v)
    main, again = _program(v, recompute=True)
    ops = main.global_block().ops
    fwd, rc = [op for op in ops if op.type == "kda_scan"]
    grad, = [op for op in ops if op.type == "kda_scan_grad"]
    assert rc.attrs.get("recomputed") and not fwd.attrs.get("recomputed")
    assert grad.input("States") == rc.output("States")
    assert grad.input("States") != fwd.output("States")
    readers = [op for op in ops
               if fwd.output("States")[0] in op.input_arg_names()]
    assert readers == []
    for x, y in zip(again, plain):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_an_op_built_without_states_is_refused_by_the_grad_maker():
    """A program from before the slot existed still runs forward (the
    executor binds the slots an op names); its backward is refused with what
    to do, as ``flash_attention``'s is without ``Lse``."""
    v = _narrow(_values(64))
    main = Program()
    with program_guard(main, Program()):
        ins = [layers.data(s, shape=list(v[s].shape), dtype="float32",
                           append_batch_size=False) for s in SLOTS]
        for x in ins:
            x.stop_gradient = False
        out = layers.kda_scan(*ins, chunk=CHUNK)
        op, = main.global_block().ops
        del op.outputs["States"]
        got, = Executor().run(main, feed={s: np.asarray(v[s]) for s in SLOTS},
                              scope=Scope(), fetch_list=[out.name])
        assert got.shape == v["V"].shape
        with pytest.raises(ValueError, match="States"):
            append_backward(layers.reduce_sum(out))
