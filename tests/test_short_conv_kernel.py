"""``pallas/short_conv.py``: the ungated ``short_conv`` kernel pair
interpreted on the CPU against the ``jax.numpy`` lowering the op keeps
everywhere else (``ops/sequence_ops.py:_ungated`` / ``_ungated_grad``) — Out,
dX, dFilter and dBias over 3 and 4 taps, with and without the bias, float32
and bf16 streams, one sequence and two (the rows in front of a sequence are
zeros, not the sequence before it), one time tile and several (the halo
across a tile's edge on either side, the filter's gradient summed over
tiles), one channel block and three, two chunks a tile and up to eight (those
between a tile's ends run in one loop).  Then the op: what ``fits`` refuses, who
runs what and what the counter's ``impl`` says.  (The kernels compiled for a
described v5e at the cells' shapes: ``tests/test_dp_collective_overlap.py``,
the one file that loads the TPU's compiler.)  Nothing here is a speed
number."""

import functools

import jax.numpy as jnp
import numpy as np
import pytest

import test_solar_open2 as solar_test
from paddle_tpu import layers
from paddle_tpu.framework import (Executor, Program, Scope, program_guard,
                                  scope_guard)
from paddle_tpu.framework.backward import append_backward
from paddle_tpu.framework.core import grad_var_name
from paddle_tpu.ops import sequence_ops
from paddle_tpu.pallas import short_conv

_close = solar_test._close
#: a grid step of two chunks: 32 rows by one lane tile
BLOCK, CHUNK = (32, 128), (16, 128)


def _values(b, t, d, taps, bias, dtype, seed=0):
    r = np.random.RandomState(seed)
    x, g = (jnp.asarray(r.randn(b, t, d), dtype) for _ in range(2))
    w = jnp.asarray(r.randn(d, taps) * 0.5, jnp.float32)
    return x, w, jnp.asarray(r.randn(d), jnp.float32) if bias else None, g


@pytest.mark.parametrize("d", [128, 384])
@pytest.mark.parametrize("t", [32, 96], ids=["one_tile", "three_tiles"])
@pytest.mark.parametrize("b", [1, 2])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("bias", [False, True], ids=["no_bias", "bias"])
@pytest.mark.parametrize("taps", [3, 4])
def test_the_kernels_give_the_jnp_lowerings_numbers(taps, bias, dtype, b, t,
                                                    d):
    x, w, bias, g = _values(b, t, d, taps, bias, dtype)
    want = sequence_ops._ungated("xla", x, w, bias)
    want_g = sequence_ops._ungated_grad("xla", x, w, bias, g)
    kw = dict(block=BLOCK, chunk=CHUNK, interpret=True)
    assert short_conv.tiles(t, d, dtype, BLOCK) == BLOCK
    out = short_conv.short_conv_fwd(x, w, bias, **kw)
    got_g = short_conv.short_conv_bwd(x, w, bias, g, **kw)
    assert out.dtype == x.dtype and out.shape == x.shape
    assert got_g[0].dtype == x.dtype and got_g[1].shape == w.shape
    # float32 as tests/test_lfm2.py holds the op, bf16 to its AMP limits
    tight = dtype == "float32"
    _close(out, want, 1e-6 if tight else 1e-2, "Out")
    for name, got, ref in zip(("dX", "dFilter", "dBias"), got_g, want_g):
        if ref is None:
            assert got is None and name == "dBias" and bias is None
            continue
        assert got.dtype == (x.dtype if name == "dX" else jnp.float32)
        _close(got, ref, 1e-5 if tight else 2e-2, name)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("bias", [False, True], ids=["no_bias", "bias"])
@pytest.mark.parametrize("chunks", [3, 4, 8])
def test_the_chunks_between_a_tiles_ends_run_in_one_loop(chunks, bias, dtype):
    """A tile of three chunks and more: the first and, backward, the last
    read a halo block and stand apart, those between are one ``fori_loop``
    at a traced row (``_over_chunks``); two tiles, two channel blocks."""
    block = (16 * chunks, 128)
    x, w, bias, g = _values(2, 2 * block[0], 256, 4, bias, dtype, seed=4)
    kw = dict(block=block, chunk=CHUNK, interpret=True)
    tight = dtype == "float32"
    _close(short_conv.short_conv_fwd(x, w, bias, **kw),
           sequence_ops._ungated("xla", x, w, bias),
           1e-6 if tight else 1e-2, "Out")
    for name, got, ref in zip(
            ("dX", "dFilter", "dBias"),
            short_conv.short_conv_bwd(x, w, bias, g, **kw),
            sequence_ops._ungated_grad("xla", x, w, bias, g)):
        if ref is not None:
            _close(got, ref, 1e-5 if tight else 2e-2, name)


def test_a_sequence_starts_and_ends_at_zeros_not_at_its_neighbour():
    """Two sequences in one call are the two calls: the halo in front of
    the second is not the first's last rows, the one behind the first not
    the second's first rows."""
    x, w, bias, g = _values(2, 64, 128, 4, True, "float32", seed=3)
    kw = dict(block=BLOCK, chunk=CHUNK, interpret=True)
    both = short_conv.short_conv_fwd(x, w, bias, **kw)
    dx, dw, db = short_conv.short_conv_bwd(x, w, bias, g, **kw)
    parts = [short_conv.short_conv_bwd(x[n:n + 1], w, bias, g[n:n + 1], **kw)
             for n in range(2)]
    for n in range(2):
        np.testing.assert_array_equal(
            both[n], short_conv.short_conv_fwd(x[n:n + 1], w, bias, **kw)[0])
        np.testing.assert_array_equal(dx[n], parts[n][0][0])
    _close(dw, parts[0][1] + parts[1][1], 1e-6, "dFilter over sequences")
    _close(db, parts[0][2] + parts[1][2], 1e-6, "dBias over sequences")
    # position 0 sees the last tap alone
    np.testing.assert_allclose(
        both[:, 0], np.asarray(_silu(x[:, 0] * w[:, -1] + bias)), rtol=1e-6,
        atol=1e-6)


def _silu(v):
    return v / (1 + jnp.exp(-v))


@pytest.mark.parametrize("t,d,dtype,block,want", [
    (8192, 6144, "bfloat16", None, (2048, 512)),     # Ling, Nemotron
    (8192, 3072, "bfloat16", None, (2048, 512)),     # Solar-Open2
    (8192, 6144, "float32", (512, 2048), (512, 2048)),
    (96, 384, "float32", None, (32, 384)),
    (48, 640, "bfloat16", None, (16, 128)),          # 640 = 5 lane tiles
    (40, 128, "bfloat16", None, (0, 0)),             # 8 rows: half a tile
    (100, 128, "float32", None, (0, 0)),
    (64, 200, "float32", None, (0, 0)),
])
def test_tiles_follow_the_length_and_the_channels(t, d, dtype, block, want):
    assert short_conv.tiles(t, d, dtype, block) == want


@pytest.mark.parametrize("shape,taps,dtype,gated,ok", [
    ((1, 8192, 6144), 4, "bfloat16", False, True),
    ((2, 64, 128), 3, "float32", False, True),
    ((2, 64, 128), 9, "float32", False, True),
    ((1, 64, 384), 4, "float32", True, False),       # the gated form
    ((2, 64, 200), 4, "float32", False, False),      # no whole lane tiles
    ((2, 100, 128), 4, "float32", False, False),     # no whole time tile
    ((2, 40, 128), 4, "bfloat16", False, False),
    ((2, 64, 128), 4, "float16", False, False),
    ((2, 64, 128), 10, "float32", False, False),     # a halo of 9 rows
    ((2, 64, 128), 1, "float32", False, False),
    ((64, 128), 4, "float32", False, False),
])
def test_fits_takes_the_ungated_form_in_whole_tiles(shape, taps, dtype, gated,
                                                    ok):
    assert short_conv.fits(shape, taps, dtype, gated) is ok


# -- the op: who runs what ----------------------------------------------------

@pytest.fixture
def as_on_a_tpu(monkeypatch):
    """The op decides as it does on a TPU, and its kernels are interpreted:
    steered here, since the program has no option for it."""
    from paddle_tpu import device
    monkeypatch.setattr(device, "on_tpu", lambda: True)
    for name in ("short_conv_fwd", "short_conv_bwd"):
        monkeypatch.setattr(short_conv, name, functools.partial(
            getattr(short_conv, name), interpret=True))


def _program(x, w, bias, probe, gated=False):
    """``sum(short_conv(x) * probe)`` and its backward through the
    executor: Out, dX, dFilter and dBias if there is one."""
    scope, main, startup = Scope(), Program(), Program()
    with scope_guard(scope), program_guard(main, startup):
        xv = layers.data("x", shape=list(x.shape), dtype="float32",
                         append_batch_size=False, stop_gradient=False)
        pv = layers.data("probe", shape=list(probe.shape), dtype="float32",
                         append_batch_size=False)
        out = layers.short_conv(
            xv, w.shape[1], param_attr="filter", gated=gated,
            bias_attr=None if bias is None else "bias")
        append_backward(layers.reduce_sum(out * pv))
        exe = Executor()
        exe.run(startup, scope=scope, seed=5)
        scope.set_var("filter", w)
        names = [out.name, grad_var_name("x"), grad_var_name("filter")]
        if bias is not None:
            scope.set_var("bias", bias)
            names.append(grad_var_name("bias"))
        return exe.run(main, feed={"x": np.asarray(x),
                                   "probe": np.asarray(probe)},
                       scope=scope, fetch_list=names)


def _count(impl, **labels):
    return sequence_ops.SHORT_CONV_LOWERINGS_CTR.value(impl=impl, **labels)


@pytest.mark.parametrize("bias", [False, True], ids=["no_bias", "bias"])
def test_the_counter_names_the_kernels_where_they_run(as_on_a_tpu, bias):
    x, w, bias, g = _values(2, 64, 256, 4, bias, "float32", seed=1)
    labels = dict(taps="4", gated="false", bias=str(bias is not None).lower())
    before = _count("pallas", **labels), _count("xla", **labels)
    got = _program(x, w, bias, g)
    assert (_count("pallas", **labels), _count("xla", **labels)) == (
        before[0] + 2, before[1])            # the op and its grad op
    want = [sequence_ops._ungated("xla", x, w, bias)] + [
        v for v in sequence_ops._ungated_grad("xla", x, w, bias, g)
        if v is not None]
    assert len(got) == len(want) == 3 + (bias is not None)
    for x_, y, tol in zip(got, want, (1e-6, 1e-5, 1e-5, 1e-5)):
        _close(x_, y, tol)


@pytest.mark.parametrize("case,shape,gated", [
    ("gated", (2, 64, 384), True), ("lanes", (2, 64, 200), False),
    ("ragged", (2, 100, 128), False)])
def test_the_op_stays_on_xla_where_fits_refuses(as_on_a_tpu, monkeypatch,
                                                case, shape, gated):
    """A TPU, and a shape or a form the kernels do not take: the
    ``jax.numpy`` lowering, counted as ``xla``, and no kernel is called."""
    def refuse(*a, **k):
        raise AssertionError("a kernel was called")
    for name in ("short_conv_fwd", "short_conv_bwd"):
        monkeypatch.setattr(short_conv, name, refuse)
    r = np.random.RandomState(2)
    d = shape[2] // (3 if gated else 1)
    x = r.randn(*shape).astype(np.float32)
    w = (r.randn(d, 4) * 0.5).astype(np.float32)
    labels = dict(taps="4", gated=str(gated).lower(), bias="false")
    before = _count("pallas", **labels), _count("xla", **labels)
    got = _program(x, w, None, r.randn(*shape[:2], d).astype(np.float32),
                   gated=gated)
    assert (_count("pallas", **labels), _count("xla", **labels)) == (
        before[0], before[1] + 2)
    assert all(np.isfinite(np.asarray(v)).all() for v in got)


def test_without_a_tpu_a_shape_that_fits_counts_xla():
    x, w, bias, g = _values(2, 64, 256, 4, True, "float32", seed=1)
    assert short_conv.fits(x.shape, 4, x.dtype)
    before = _count("pallas"), _count("xla", taps="4", bias="true")
    _program(x, w, bias, g)
    assert (_count("pallas"), _count("xla", taps="4", bias="true")) == (
        before[0], before[1] + 2)


def test_a_toy_kda_program_on_the_cpu_counts_xla():
    """Solar-Open2's toy (two KDA layers, forward and backward): every
    ``short_conv`` lowering is ``impl="xla"`` here, none ``pallas``."""
    labels = dict(taps="4", gated="false", act="silu", bias="false")
    before = _count("pallas"), _count("xla", **labels)
    solar_test._run(solar_test.toy_cfg())
    assert _count("pallas") == before[0]
    assert _count("xla", **labels) >= before[1] + 4
    assert "impl" in sequence_ops.SHORT_CONV_LOWERINGS_CTR.labelnames
