"""The ungated short convolution and its backward, each as one pass over its
streams (Pallas): ``Out = silu(conv(X) [+ Bias])`` over X [b, t, d], ``conv``
a causal depthwise convolution of ``taps`` positions with zeros before the
sequence starts (KDA's convolution in front of Q, K and V; a state-space
mixer's, with the bias).

Why a kernel: the op is a handful of multiplies a channel and nothing but
bytes, two [t, d] streams forward and three backward, and XLA's lowering of
the ``jax.numpy`` text (``ops/sequence_ops.py``) does not keep it so: it
pads the float32 copy of the tensor along time, reads it at ``taps`` row
offsets of which none is a whole sublane tile, and sums the filter's
gradient in ``taps`` reductions over the padded copy — at [8192, 6144] some
ten times the bytes' least time (PERF.md section 6, PR 58).

Here a grid step ``(b, d / block_d, t / block_t)`` loads a ``[block_t,
block_d]`` tile of every stream in the stream's dtype, and beside it the
sublane tile that ends where the tile starts (X, whose last ``taps - 1``
rows the convolution's first rows need; zeros at position 0 of every
sequence) and, backward, the sublane tile that starts where the tile ends (X
and dOut: ``dX[t]`` needs ``dc = dOut * silu'(conv + Bias)`` up to ``t +
taps - 1``, zeros behind the sequence's end).  Inside, the tile is walked in
chunks of ``[chunk_t, chunk_d]`` that are widened to float32 (the chunks at
a tile's two ends, which read a halo block, at fixed rows, those between in
one loop: what a step's set-up traces and lowers is three bodies a lane
chunk, whatever the tile); a row offset
is a sublane rotation of the chunk with its eight rows of halo
(``pltpu.roll``) cut back to whole tiles; the convolution, the bias and SiLU
are float32 as in the ``jax.numpy`` text, one rounding to the stream's dtype
at the store.  The backward makes the convolution again (nothing but X is
kept from the forward), writes dX, and sums ``dc * X[t - (taps - 1) + j]``
(and ``dc`` for the bias) in float32, eight partial rows a channel, into an
output block that every time tile of a channel block revisits: time is the
innermost, ``arbitrary`` grid axis.  The eight rows and the sequences are
summed outside, ``taps + 1`` rows of ``d`` floats.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_LANE = 128
#: rows of a float32 sublane tile: the halo a chunk carries on either side,
#: so ``taps - 1`` may be no more
_ROWS = 8
_F32 = jnp.float32

#: the largest tile of a grid step and of a chunk inside it, rows (time) by
#: lanes (channels); where they came from: ``tools/short_conv_probe.py``,
#: PERF.md section 6, PR 58
BLOCK = (2048, 512)
CHUNK = (64, 256)


def _sublanes(dtype):
    """Rows of one sublane tile of ``dtype``: 8 float32, 16 bf16."""
    return _ROWS * 4 // jnp.dtype(dtype).itemsize


def tiles(t, d, dtype, block=None):
    """``(block_t, block_d)`` of a grid step from the shapes alone: the
    largest power of two up to ``block[0]`` that divides ``t`` and holds
    whole sublane tiles of ``dtype``, and the largest multiple of 128 lanes
    up to ``block[1]`` that divides ``d``; ``(0, 0)`` where there is none."""
    max_t, max_d = block or BLOCK
    bt = 1
    while bt * 2 <= max_t and t % (bt * 2) == 0:
        bt *= 2
    if d % _LANE or bt % _sublanes(dtype):
        return 0, 0
    bd = max(n for n in range(_LANE, max(max_d, _LANE) + 1, _LANE)
             if d % n == 0)
    return bt, bd


def fits(shape, taps, dtype, gated=False):
    """Whether the kernels take X: the ungated form, [b, t, d] in float32 or
    bf16, channels in whole lane tiles, a length that divides into tiles of
    whole sublane tiles, and a filter whose ``taps - 1`` earlier rows lie in
    one float32 sublane tile.  Everything else stays ``jax.numpy``: the
    gated form, toy widths, ragged lengths."""
    if gated or len(shape) != 3 or not 1 <= taps - 1 <= _ROWS:
        return False
    if jnp.dtype(dtype) not in (jnp.dtype(_F32), jnp.dtype(jnp.bfloat16)):
        return False
    return tiles(shape[1], shape[2], dtype)[0] > 0


def _window(ref, before, after, edges, r0, lo, hi, lanes):
    """Float32 rows ``r0 + lo .. r0 + hi`` of a tile's time axis (``lo`` and
    ``hi`` multiples of 8 within one sublane tile of a chunk's ends) over
    ``lanes``.  ``r0`` a Python number: the tile's own rows from ``ref``,
    those in front of it from the sublane tile ``before`` and those behind it
    from ``after``, zeros where ``edges`` (first, last) say the sequence
    starts or ends there.  ``r0`` traced (a chunk that touches neither end
    of the tile): ``ref``'s rows alone."""
    from jax.experimental import pallas as pl
    rows, p = ref.shape[0], _sublanes(ref.dtype)
    lo_p, hi_p = lo // p * p, -(-hi // p) * p
    if not isinstance(r0, int):
        whole = ref[pl.ds(pl.multiple_of(r0 + lo_p, p), hi_p - lo_p), lanes]
        return whole.astype(_F32)[lo - lo_p:hi - lo_p]
    parts = []
    if r0 + lo_p < 0:
        parts.append(jnp.where(edges[0], 0.0, before[:, lanes].astype(_F32)))
    parts.append(ref[max(r0 + lo_p, 0):min(r0 + hi_p, rows),
                     lanes].astype(_F32))
    if r0 + hi_p > rows:
        parts.append(jnp.where(edges[1], 0.0, after[:, lanes].astype(_F32)))
    whole = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)
    return whole[lo - lo_p:hi - lo_p]


def _delayed(xe, taps):
    """``[x[t], x[t - 1], .., x[t - (taps - 1)]]`` over the rows behind
    ``xe``'s first eight: each a sublane rotation of the whole, cut back."""
    from jax.experimental.pallas import tpu as pltpu
    return [xe[_ROWS:]] + [pltpu.roll(xe, k, 0)[_ROWS:]
                           for k in range(1, taps)]


def _conv(xs, w, bias):
    """``sum_j w[j] * x[t - (taps - 1) + j] [+ bias]`` from :func:`_delayed`
    rows, in the order the ``jax.numpy`` text sums them."""
    taps = len(w)
    conv = sum(xs[taps - 1 - j] * w[j] for j in range(taps))
    return conv if bias is None else conv + bias


def _chunks(rows, width, chunk):
    """``(chunk_t, chunk_d)`` that divide a ``[rows, width]`` tile."""
    return min(chunk[0], rows), chunk[1] if width % chunk[1] == 0 else _LANE


def _over_chunks(rows, ct, body, last_apart):
    """``body(r0)`` for every chunk of ``ct`` rows: the first (and with
    ``last_apart`` the last) at a Python ``r0``, since they read a halo
    block; those between in ONE loop at a traced ``r0``, so that a tile of
    sixteen chunks is three bodies to trace, lower and hold as code, not
    sixteen (the step's set-up pays for every body of every lowering)."""
    n = rows // ct
    stop = n - 1 if last_apart and n > 1 else n
    body(0)
    if stop > 1:
        jax.lax.fori_loop(1, stop, lambda k, _: body(k * ct), None)
    if stop < n:
        body((n - 1) * ct)


def _fwd_kernel(*refs, taps, has_bias, chunk):
    """X's tile and the sublane tile before it, Filter [taps, block_d],
    Bias [1, block_d] if there is one -> Out's tile."""
    from jax.experimental import pallas as pl
    x_ref, before_ref, w_ref = refs[:3]
    o_ref = refs[-1]
    edges = (pl.program_id(2) == 0, None)
    rows, width = x_ref.shape
    ct, cd = _chunks(rows, width, chunk)
    for c0 in range(0, width, cd):
        lanes = slice(c0, c0 + cd)
        w = [w_ref[j:j + 1, lanes] for j in range(taps)]
        bias = refs[3][:, lanes] if has_bias else None

        def body(r0):
            xe = _window(x_ref, before_ref, None, edges, r0, -_ROWS, ct,
                         lanes)
            o_ref[pl.ds(r0, ct), lanes] = jax.nn.silu(
                _conv(_delayed(xe, taps), w, bias)).astype(o_ref.dtype)

        _over_chunks(rows, ct, body, last_apart=False)


def _bwd_kernel(*refs, taps, has_bias, chunk):
    """X's tile with the sublane tiles before and behind it, dOut's tile
    with the one behind it, Filter, Bias if there is one -> dX's tile, and
    ``[taps (+ 1), 8, block_d]`` float32 partial sums of the filter's (and
    the bias's) gradient, revisited over the time tiles."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    x_ref, xb_ref, xa_ref, g_ref, ga_ref, w_ref = refs[:6]
    dx_ref, dw_ref = refs[-2:]
    i = pl.program_id(2)
    edges = (i == 0, i == pl.num_programs(2) - 1)

    @pl.when(edges[0])
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    rows, width = x_ref.shape
    ct, cd = _chunks(rows, width, chunk)

    def fold(v):
        return functools.reduce(
            jnp.add, [v[r:r + _ROWS] for r in range(0, ct, _ROWS)])

    for c0 in range(0, width, cd):
        lanes = slice(c0, c0 + cd)
        w = [w_ref[j:j + 1, lanes] for j in range(taps)]
        bias = refs[6][:, lanes] if has_bias else None

        def body(r0):
            # rows r0 .. r0 + ct + 8 of the convolution, its slope and dc
            xe = _window(x_ref, xb_ref, xa_ref, edges, r0, -_ROWS,
                         ct + _ROWS, lanes)
            xs = _delayed(xe, taps)
            conv = _conv(xs, w, bias)
            s = jax.nn.sigmoid(conv)
            dc = _window(g_ref, None, ga_ref, edges, r0, 0, ct + _ROWS,
                         lanes) * (s * (1.0 + conv * (1.0 - s)))
            # the same taps towards the past: dc[t + (taps - 1) - j]
            ahead = [dc[:ct]] + [pltpu.roll(dc, ct + _ROWS - m, 0)[:ct]
                                 for m in range(1, taps)]
            dx_ref[pl.ds(r0, ct), lanes] = sum(
                ahead[taps - 1 - j] * w[j]
                for j in range(taps)).astype(dx_ref.dtype)
            for j in range(taps):
                dw_ref[j, :, lanes] += fold(xs[taps - 1 - j][:ct] * ahead[0])
            if has_bias:
                dw_ref[taps, :, lanes] += fold(ahead[0])

        _over_chunks(rows, ct, body, last_apart=True)


@functools.lru_cache(maxsize=None)
def _calls(b, t, d, dtype, taps, has_bias, block, chunk, interpret):
    """``(forward, backward)`` ``pallas_call``s at these shapes."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    bt, bd = tiles(t, d, dtype, block)
    if not bt:
        raise ValueError(f"short_conv kernels: no tiles for [{t}, {d}] "
                         f"{jnp.dtype(dtype).name} (see fits)")
    p = _sublanes(dtype)
    per, last = bt // p, t // p - 1
    tile = pl.BlockSpec((None, bt, bd), lambda n, j, i: (n, i, j))
    before = pl.BlockSpec(
        (None, p, bd), lambda n, j, i: (n, jnp.maximum(i * per - 1, 0), j))
    behind = pl.BlockSpec(
        (None, p, bd), lambda n, j, i: (n, jnp.minimum((i + 1) * per, last),
                                        j))
    weights = [pl.BlockSpec((taps, bd), lambda n, j, i: (0, j))] + \
        [pl.BlockSpec((1, bd), lambda n, j, i: (0, j))] * has_bias
    rows = taps + has_bias
    # two buffers a stream's tile, and room for the float32 chunks
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=int(6 * bt * bd * jnp.dtype(dtype).itemsize
                             + (16 << 20)))
    kw = dict(taps=taps, has_bias=bool(has_bias), chunk=chunk)
    grid = (b, d // bd, t // bt)
    stream = jax.ShapeDtypeStruct((b, t, d), dtype)
    fwd = pl.pallas_call(
        functools.partial(_fwd_kernel, **kw), grid=grid,
        in_specs=[tile, before] + weights, out_specs=tile, out_shape=stream,
        compiler_params=params, interpret=interpret, name="short_conv_fwd")
    bwd = pl.pallas_call(
        functools.partial(_bwd_kernel, **kw), grid=grid,
        in_specs=[tile, before, behind, tile, behind] + weights,
        out_specs=[tile, pl.BlockSpec((None, rows, _ROWS, bd),
                                      lambda n, j, i: (n, 0, 0, j))],
        out_shape=[stream, jax.ShapeDtypeStruct((b, rows, _ROWS, d), _F32)],
        compiler_params=params, interpret=interpret, name="short_conv_bwd")
    return fwd, bwd


def _prepared(x, filt, bias, block, chunk, interpret):
    """The two calls at X's shape and the float32 weights as the kernels
    take them: Filter [taps, d], Bias [1, d]."""
    b, t, d = x.shape
    calls = _calls(b, t, d, jnp.dtype(x.dtype), filt.shape[1],
                   bias is not None, tuple(block or BLOCK),
                   tuple(chunk or CHUNK), bool(interpret))
    weights = [filt.astype(_F32).T] + \
        ([] if bias is None else [bias.astype(_F32)[None]])
    return calls, weights


def short_conv_fwd(x, filt, bias=None, *, block=None, chunk=None,
                   interpret=False):
    """``silu(conv(x) [+ bias])`` [b, t, d] in ``x``'s dtype from X [b, t,
    d], Filter [d, taps] and Bias [d] or None.  The shapes have to pass
    :func:`fits`; ``block`` and ``chunk`` take :data:`BLOCK`'s and
    :data:`CHUNK`'s place."""
    (fwd, _), weights = _prepared(x, filt, bias, block, chunk, interpret)
    return fwd(x, x, *weights)


def short_conv_bwd(x, filt, bias, d_out, *, block=None, chunk=None,
                   interpret=False):
    """``(dX in x's dtype, dFilter [d, taps] float32, dBias [d] float32 or
    None)`` from the forward's inputs and Out's gradient."""
    (_, bwd), weights = _prepared(x, filt, bias, block, chunk, interpret)
    g = d_out.astype(x.dtype)
    dx, dw = bwd(x, x, x, g, g, *weights)
    dw = jnp.sum(dw, axis=(0, 2))
    taps = filt.shape[1]
    return dx, dw[:taps].T, None if bias is None else dw[taps]
