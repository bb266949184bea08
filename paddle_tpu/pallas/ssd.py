"""``ssd_scan`` and ``ssd_scan_grad`` as a pair of Pallas kernels: the chunked
Mamba-2 scan of ``ops/ssd_ops.py`` with every chunk-shaped tensor (the decay
matrices ``L``, ``C B^T``, their cotangents) made in VMEM and the ``P x N``
states of the heads kept in VMEM scratch from chunk to chunk.  HBM sees the
op's streams as they lie (X ``[b, t, H P]``, B and C ``[b, t, G N]``, Dt
``[b, t, H]``, in their own dtype), one float32 state a chunk and head
(``States``: what the backward starts each chunk from) and nothing
``[chunk, chunk]``-shaped.

**A group a grid step.**  Grid ``(b, t / chunk, G)``, the group innermost.
The ``R`` heads of a group share ``B`` and ``C``, so a step reads the
group's ``[chunk, R P]`` slab of X and the ``[chunk, N]`` tiles of B and C,
makes ``C B^T`` once, and keeps the group's states TRANSPOSED and side by
side, ``[N, R P]``: a head is ``P`` lanes of everything, its decays one
factor a lane.  With ``cum`` the ``Delta A`` cumulated inside the chunk::

    Y      = [(C B^T * L_h) (Delta x)]_h + e^cum * (C S_0) + D x
    S_1    = e^cum_C * S_0 + B^T (e^(cum_C - cum) * Delta x)
    L_h,ij = exp(cum_i - cum_j)   j <= i, else 0

Only the first term is a product a head (``L`` differs by head); the earlier
state's part and the chunk's own contribution to the states are ONE product
each over the group's ``R P`` lanes.  Heads narrower than a lane tile (``P``
64) sit two to a tile: a head's product runs over its whole tile of ``Delta
x`` (the MXU's columns cost the same half empty) and a lane mask keeps its
half, so no value is ever sliced or joined inside a lane tile.

**The steps of a chunk** (``softplus(dt + dt_bias)`` in float32, ``Delta A``
and its cumulated sum, a product with the 0 / 1 triangle) are made once a
chunk for all heads, at the first group, and kept in scratch in both
layouts: positions down the sublanes (a group's ``[chunk, R]`` columns,
which scale X's rows) and along the lanes (``[H, chunk]`` rows, the ``j`` of
``L``).  The second is the first transposed, so ``cum_i - cum_i`` is 0 to
the bit.

**The backward by hand** (``ssd_bwd``; no ``jax.vjp``).  It walks the chunks
from the last with the states' cotangent ``dS`` (transposed like the states)
as its carry in scratch, makes ``L`` and ``C B^T`` again from the same tiles
and ``States[n]``, and with ``M_h = C B^T * L_h``, ``xd = Delta x``, ``w =
e^(cum_C - cum)``, ``E = e^cum``::

    dxd  = [M_h^T dY_h]_h + w * (B dS_1)          dX = Delta dxd + D dY
    dM_h = dY_h xd_h^T (lower triangle)           dCB = sum_h dM_h * L_h
    dC   = dCB B + (E dY) S_0^T                   dB = dCB^T C + (w xd) dS_1^T
    dS_0 = e^cum_C dS_1 + C^T (E dY)
    dDelta_direct = sum_p dxd x
    dcum_i = sum_j dM_h,ij M_h,ij + sum_p [dY E (C S_0) - xd dxd]_ip
             + [i = C] sum (w xd (B dS_1) + e^cum_C S_0 dS_1)

(``cum`` costs no product: wherever it enters as ``e^(+-cum)`` its cotangent
is ``+-`` that factor's times the factor, and the sums over ``j`` of ``dM M``
by columns are ``sum_p xd dxd``.)  dB and dC are summed over a group's heads
in VMEM.  The per-head columns go to two ``[chunk, H]`` blocks that the
groups of a chunk revisit; at the last group the second becomes ``dDA``, the
cotangent of ``Delta A`` (the reverse cumulated sum of ``dcum``, a product
with the transposed triangle).  From those two ``[b, t, H]`` streams and
``[b, G, 8, R P]`` partial rows of ``sum dY x`` (a block that stays in VMEM
over a sequence), dDt, dALog, dD and dDtBias are finished by a few
elementwise XLA passes over 2 MB (:func:`ssd_bwd`).

**The same numbers as** ``ssd_chunked``: float32 inside, every product at
``highest``, decays as differences of cumulated ``Delta A`` with every
exponent ``<= 0`` and the causal mask applied BEFORE the ``exp``.  What
differs is the order of float32 sums and the recurrence over the chunk
states, which here is the loop it is and there one product with the
``[n, n]`` matrix of the decays between chunks."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_F32 = jnp.float32
_LANE = 128
#: rows of a float32 sublane tile: the partial rows of ``sum dY x``
_ROWS = 8
#: the chunk the kernels are written for (the tile of ``L`` and ``C B^T``)
CHUNK = 128

#: what ``tools/ssd_kernel_probe.py --leave_out`` alone passes as
#: ``leave_out``: the kernels traced WITHOUT the named parts, to read what a
#: part costs; the numbers are then wrong.  The op never passes it.
PARTS = frozenset(("diag", "decays", "state", "steps", "cols", "bc", "dots"))


def fits(x_shape, b_shape, chunk, dtypes):
    """Whether the kernels take X ``[b, t, H, P]`` with B / C ``[b, t, G,
    N]``: chunks of 128, ``t`` a whole number of them, ``N`` and a group's
    ``R P`` lanes whole lane tiles with a head inside one tile or over whole
    ones, the heads of a group a whole sublane tile at most, X, B and C in
    float32 or bf16.  ``ssd_chunked`` runs everything else (toy widths,
    chunk 16, ragged lengths)."""
    ok = (jnp.dtype(_F32), jnp.dtype(jnp.bfloat16))
    if len(x_shape) != 4 or len(b_shape) != 4 or chunk != CHUNK:
        return False
    _, t, h, p = x_shape
    g, n = b_shape[2], b_shape[3]
    if g < 1 or h % g or t < chunk or t % chunk or n % _LANE:
        return False
    r = h // g
    if r > _ROWS or (r * p) % _LANE or (_LANE % p and p % _LANE):
        return False
    return all(jnp.dtype(d) in ok for d in dtypes)


def _dot(a, b, dims=((1,), (0,)), part=None, leave_out=frozenset()):
    """``a b`` (or as ``dims`` say) in float32 at ``highest``.  ``part``:
    the probe's name for what the product belongs to (:data:`PARTS`)."""
    if leave_out & {"dots", part}:
        rows, cols = a.shape[1 - dims[0][0]], b.shape[1 - dims[1][0]]
        # a constant: what only this product read goes with it
        return jnp.full((rows, cols), 1e-3, _F32)
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=_F32)


_NT = ((1,), (1,))      # a b^T
_TN = ((0,), (0,))      # a^T b


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _lower(c):
    """The 0 / 1 triangle ``[c, c]``, ``j <= i``."""
    return (_iota((c, c), 0) >= _iota((c, c), 1)).astype(_F32)


def _steps(g, r, dt_ref, a_ref, bias_ref, delta_scr, cum_scr, row_scr, dot,
           leave_out):
    """Group ``g``'s ``Delta`` and cumulated ``Delta A`` as columns ``[chunk,
    R]`` and the latter as rows ``[R, chunk]`` too.  Once a chunk, at the
    first group, both are made for all heads from the chunk's ``[chunk, H]``
    block of Dt and kept in scratch, a group at a time as columns ``[G,
    chunk, R]`` and whole as rows ``[H, chunk]``."""
    from jax.experimental import pallas as pl

    def all_heads():
        delta = dt_ref[...].astype(_F32)
        if bias_ref is not None:
            delta = jax.nn.softplus(delta + bias_ref[...])
        cum = dot(_lower(delta.shape[0]), delta * a_ref[...])
        row_scr[...] = cum.T
        for i in range(delta.shape[1] // r):
            delta_scr[i] = delta[:, i * r:(i + 1) * r]
            cum_scr[i] = cum[:, i * r:(i + 1) * r]

    if "steps" not in leave_out:
        pl.when(g == 0)(all_heads)
    return delta_scr[g], cum_scr[g], \
        row_scr[pl.ds(pl.multiple_of(g * r, r), r), :]


def _over_lanes(cols, first, count, p, width):
    """``[chunk, width]`` from the columns ``first .. first + count`` of
    ``cols [chunk, R]``: each over its head's ``p`` lanes of the tile."""
    lane = _iota((cols.shape[0], width), 1)
    out = cols[:, first:first + 1]
    for k in range(1, count):
        out = jnp.where(lane >= k * p, cols[:, first + k:first + k + 1], out)
    return jnp.broadcast_to(out, lane.shape)


def _tiles(p, lanes):
    """How a group's ``lanes = R P`` lanes split into tiles of whole heads:
    ``(width, heads a tile, tiles)``."""
    width = max(p, _LANE)
    return width, width // p, lanes // width


def _decays(cum_col, cum_row, h, leave_out=frozenset()):
    """``L_h [chunk, chunk]`` from the group's cumulated ``Delta A`` as
    columns ``[chunk, R]`` and rows ``[R, chunk]``: the difference, the
    mask, then the ``exp``."""
    c = cum_col.shape[0]
    if "decays" in leave_out:
        return cum_col[:, h:h + 1] + cum_row[h:h + 1, :]
    keep = _iota((c, c), 0) >= _iota((c, c), 1)
    return jnp.exp(jnp.where(keep, cum_col[:, h:h + 1] - cum_row[h:h + 1, :],
                             -jnp.inf))


def _mine(width, p, k):
    """The lanes of the ``k``-th head of a tile."""
    lane = _iota((1, width), 1)
    return (lane >= k * p) & (lane < (k + 1) * p)


def _fwd_kernel(*refs, has_bias, p, leave_out):
    """X's slab, Dt's ``[chunk, H]`` block, B's and C's tiles, ``A`` and
    DtBias ``[1, H]``, D over the group's lanes ``[1, R P]`` -> Out's slab
    and the group's states before the chunk ``[R, P, N]``."""
    from jax.experimental import pallas as pl
    x_ref, dt_ref, b_ref, c_ref, a_ref = refs[:5]
    bias_ref = refs[5] if has_bias else None
    d_ref, o_ref, states_ref, st_scr, delta_scr, cum_scr, row_scr = \
        refs[5 + has_bias:]
    n, g = pl.program_id(1), pl.program_id(2)
    c, lanes = x_ref.shape
    r = lanes // p
    width, per, tiles = _tiles(p, lanes)

    dot = functools.partial(_dot, leave_out=leave_out)
    decays = functools.partial(_decays, leave_out=leave_out)

    delta_col, cum_col, cum_row = _steps(
        g, r, dt_ref, a_ref, bias_ref, delta_scr, cum_scr, row_scr, dot,
        leave_out)

    @pl.when(n == 0)
    def _():
        st_scr[g] = jnp.zeros(st_scr.shape[1:], _F32)

    st0 = st_scr[g]                                        # [N, R P]
    states_ref[...] = st0.T.reshape(states_ref.shape)
    bm, cm = b_ref[...].astype(_F32), c_ref[...].astype(_F32)
    cb = dot(cm, bm, _NT)

    ys, xds, cums = [], [], []
    for q in range(tiles):
        at = slice(q * width, (q + 1) * width)
        x = x_ref[:, at].astype(_F32)
        xd = x * _over_lanes(delta_col, q * per, per, p, width)
        y = None
        for k in range(per):
            y_h = dot(cb * decays(cum_col, cum_row, q * per + k), xd,
                       part="diag")
            y = y_h if y is None else jnp.where(_mine(width, p, k), y_h, y)
        ys.append(y + x * d_ref[:, at])
        xds.append(xd)
        cums.append(_over_lanes(cum_col, q * per, per, p, width))
    y_own, xd, cum = (jnp.concatenate(v, axis=1) for v in (ys, xds, cums))
    last = cum[c - 1:c]
    y = y_own + jnp.exp(cum) * dot(cm, st0, part="state")
    o_ref[...] = y.astype(o_ref.dtype)
    st_scr[g] = jnp.exp(last) * st0 + dot(bm, xd * jnp.exp(last - cum), _TN,
                                           part="state")


def _bwd_kernel(*refs, has_bias, p, leave_out):
    """Grid step ``n`` is chunk ``t / chunk - 1 - n`` (the index maps turn
    the axis): ``dS`` behind the last chunk is zero.  The forward's inputs,
    the group's states before the chunk and dOut's slab -> dX's slab, dB's
    and dC's tiles, the chunk's ``[chunk, H]`` blocks of ``dDelta`` (the
    direct part) and ``dDA``, and the sequence's ``[G, 8, R P]`` partial rows
    of ``sum dY x``."""
    from jax.experimental import pallas as pl
    x_ref, dt_ref, b_ref, c_ref, a_ref = refs[:5]
    bias_ref = refs[5] if has_bias else None
    (d_ref, states_ref, dy_ref, dx_ref, db_ref, dc_ref, dd_ref, dda_ref,
     dsk_ref, ds_scr, delta_scr, cum_scr, row_scr) = refs[5 + has_bias:]
    n, g = pl.program_id(1), pl.program_id(2)
    c, lanes = x_ref.shape
    r = lanes // p
    width, per, tiles = _tiles(p, lanes)

    dot = functools.partial(_dot, leave_out=leave_out)
    decays = functools.partial(_decays, leave_out=leave_out)

    delta_col, cum_col, cum_row = _steps(
        g, r, dt_ref, a_ref, bias_ref, delta_scr, cum_scr, row_scr, dot,
        leave_out)

    @pl.when(n == 0)
    def _():
        ds_scr[g] = jnp.zeros(ds_scr.shape[1:], _F32)
        dsk_ref[g] = jnp.zeros(dsk_ref.shape[1:], _F32)

    st0 = states_ref[...].reshape(lanes, -1).T             # [N, R P]
    ds1 = ds_scr[g]
    bm, cm = b_ref[...].astype(_F32), c_ref[...].astype(_F32)
    cb = dot(cm, bm, _NT)
    from_s0 = dot(cm, st0, part="state")          # C S_0, no decay yet
    from_ds = dot(bm, ds1, part="state")          # B dS_1, no decay yet

    heads = _iota((c, dd_ref.shape[1]), 1)
    dd_cols = jnp.zeros(dd_ref.shape, _F32)
    dcum_cols = jnp.zeros(dd_ref.shape, _F32)
    d_cb = jnp.zeros((c, c), _F32)
    is_last = _iota((c, 1), 0) == c - 1
    e_dys, xdws, lasts = [], [], []
    for q in range(tiles):
        at = slice(q * width, (q + 1) * width)
        x, dy = x_ref[:, at].astype(_F32), dy_ref[:, at].astype(_F32)
        delta = _over_lanes(delta_col, q * per, per, p, width)
        cum = _over_lanes(cum_col, q * per, per, p, width)
        last = cum[c - 1:c]
        xd, e, w = x * delta, jnp.exp(cum), jnp.exp(last - cum)
        dxd, rows = None, []
        for k in range(per):
            mine = _mine(width, p, k)
            lo = decays(cum_col, cum_row, q * per + k)
            dxd_h = dot(cb * lo, dy, _TN, part="diag")
            dxd = dxd_h if dxd is None else jnp.where(mine, dxd_h, dxd)
            dm_l = dot(jnp.where(mine, dy, 0.0), xd, _NT, part="diag") * lo
            d_cb = d_cb + dm_l
            rows.append(jnp.sum(dm_l * cb, axis=1, keepdims=True))
        xdw = xd * w
        dxd = dxd + w * from_ds[:, at]
        d = d_ref[:, at]
        dx_ref[:, at] = (dxd * delta + dy * d).astype(dx_ref.dtype)
        e_dy = e * dy
        # what the last position's cum also is: the chunk's whole decay
        whole = jnp.sum(xdw * from_ds[:, at], axis=0, keepdims=True) \
            + jnp.exp(last) * jnp.sum(st0[:, at] * ds1[:, at], axis=0,
                                      keepdims=True)
        to_dd = dxd * x
        to_cum = e_dy * from_s0[:, at] - xd * dxd \
            + jnp.where(is_last, whole, 0.0)
        for k in range(0 if "cols" in leave_out else per):
            mine = _mine(width, p, k)
            col = heads == g * r + q * per + k
            dd_cols = jnp.where(col, jnp.sum(
                jnp.where(mine, to_dd, 0.0), axis=1, keepdims=True), dd_cols)
            dcum_cols = jnp.where(col, rows[k] + jnp.sum(
                jnp.where(mine, to_cum, 0.0), axis=1, keepdims=True),
                dcum_cols)
        dyx = dy * x
        dsk_ref[g, :, at] += functools.reduce(
            jnp.add, [dyx[i:i + _ROWS] for i in range(0, c, _ROWS)])
        e_dys.append(e_dy)
        xdws.append(xdw)
        lasts.append(last)
    e_dy, xdw, last = (jnp.concatenate(v, axis=1)
                       for v in (e_dys, xdws, lasts))
    dc_ref[...] = (dot(d_cb, bm, part="bc")
                   + dot(e_dy, st0, _NT, part="bc")).astype(dc_ref.dtype)
    db_ref[...] = (dot(d_cb, cm, _TN, part="bc")
                   + dot(xdw, ds1, _NT, part="bc")).astype(db_ref.dtype)
    ds_scr[g] = jnp.exp(last) * ds1 + dot(cm, e_dy, _TN, part="state")

    group = (heads >= g * r) & (heads < (g + 1) * r)
    dd_ref[...] = jnp.where(group, dd_cols, dd_ref[...])
    dda_ref[...] = jnp.where(group, dcum_cols, dda_ref[...])

    @pl.when(g == pl.num_programs(2) - 1)
    def _():
        # dDA_j = sum_{i >= j} dcum_i
        dda_ref[...] = dot(_lower(c), dda_ref[...], _TN)


@functools.lru_cache(maxsize=None)
def _calls(b, t, h, p, g, n_state, has_bias, dtypes, interpret, leave_out):
    """``(forward, backward)`` for these shapes over the ``[b, t, .]``
    views; ``dtypes`` of X, Dt, B, C."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    c, r = CHUNK, h // g
    n, lanes = t // c, h // g * p
    x_dt, dt_dt, b_dt, c_dt = dtypes

    def stream(width, turn):        # a group's columns of a chunk
        return pl.BlockSpec((None, c, width), lambda i, j, l: (
            i, n - 1 - j if turn else j, l))

    def heads(turn):                # every head's column of a chunk
        return pl.BlockSpec((None, c, h), lambda i, j, l: (
            i, n - 1 - j if turn else j, 0))

    def state(turn):
        return pl.BlockSpec((None, r, None, p, n_state), lambda i, j, l: (
            i, l, n - 1 - j if turn else j, 0, 0))

    row = pl.BlockSpec((1, h), lambda i, j, l: (0, 0))
    d_lanes = pl.BlockSpec((1, lanes), lambda i, j, l: (0, l))

    def ins(turn):
        return [stream(lanes, turn), heads(turn), stream(n_state, turn),
                stream(n_state, turn), row] + [row] * has_bias + [d_lanes]

    def shape(*dims, dtype=_F32):
        return jax.ShapeDtypeStruct(dims, dtype)

    # the states (or their cotangents) of every group, the steps in both
    # layouts (a [chunk, R] column block fills whole lane tiles in VMEM)
    scratch = [pltpu.VMEM((g, n_state, lanes), _F32),
               pltpu.VMEM((g, c, r), _F32), pltpu.VMEM((g, c, r), _F32),
               pltpu.VMEM((h, c), _F32)]
    params = pltpu.CompilerParams(
        dimension_semantics=("arbitrary",) * 3,
        vmem_limit_bytes=int(g * n_state * lanes * 4 + 2 * g * c * _LANE * 4
                             + 48 * c * lanes * 4 + (16 << 20)))
    kw = dict(has_bias=bool(has_bias), p=p, leave_out=leave_out)
    fwd = pl.pallas_call(
        functools.partial(_fwd_kernel, **kw), grid=(b, n, g),
        in_specs=ins(False), out_specs=[stream(lanes, False), state(False)],
        out_shape=[shape(b, t, h * p, dtype=x_dt),
                   shape(b, h, n, p, n_state)],
        scratch_shapes=scratch, compiler_params=params, interpret=interpret,
        name="ssd_fwd")
    bwd = pl.pallas_call(
        functools.partial(_bwd_kernel, **kw), grid=(b, n, g),
        in_specs=ins(True) + [state(True), stream(lanes, True)],
        out_specs=[stream(lanes, True), stream(n_state, True),
                   stream(n_state, True), heads(True), heads(True),
                   pl.BlockSpec((None, g, _ROWS, lanes),
                                lambda i, j, l: (i, 0, 0, 0))],
        out_shape=[shape(b, t, h * p, dtype=x_dt),
                   shape(b, t, g * n_state, dtype=b_dt),
                   shape(b, t, g * n_state, dtype=c_dt),
                   shape(b, t, h), shape(b, t, h),
                   shape(b, g, _ROWS, lanes)],
        scratch_shapes=scratch, compiler_params=params, interpret=interpret,
        name="ssd_bwd")
    return fwd, bwd


def _prepared(x, dt, a_log, b, c, d, dt_bias, interpret, leave_out):
    """The two calls at these shapes and what both take: the streams' views
    and the per-head parameters as float32 rows (``A = -exp(ALog)`` and
    DtBias ``[1, H]``, D over its head's lanes ``[1, H P]``)."""
    bsz, t, h, p = x.shape
    g, n_state = b.shape[2], b.shape[3]
    calls = _calls(bsz, t, h, p, g, n_state, dt_bias is not None,
                   tuple(jnp.dtype(v.dtype) for v in (x, dt, b, c)),
                   bool(interpret), frozenset(leave_out))
    a = -jnp.exp(a_log.astype(_F32))
    rows = [a[None]] + ([] if dt_bias is None
                        else [dt_bias.astype(_F32)[None]]) \
        + [jnp.repeat(d.astype(_F32), p)[None]]
    views = [x.reshape(bsz, t, h * p), dt, b.reshape(bsz, t, g * n_state),
             c.reshape(bsz, t, g * n_state)]
    return calls, views + rows, a


def ssd_fwd(x, dt, a_log, b, c, d, dt_bias=None, *, interpret=False,
            leave_out=frozenset()):
    """``ssd_chunked``'s arguments -> ``(out [b, t, H, P] in x's dtype,
    states [b, H, t / 128, P, N] float32)``: ``states[:, :, n]`` is the
    state before chunk ``n``.  The shapes have to pass :func:`fits`."""
    (fwd, _), ins, _ = _prepared(x, dt, a_log, b, c, d, dt_bias, interpret,
                                 leave_out)
    out, states = fwd(*ins)
    return out.reshape(x.shape), states


def ssd_bwd(x, dt, a_log, b, c, d, dt_bias, states, d_out, *,
            interpret=False, leave_out=frozenset()):
    """The gradients of X, Dt, ALog, B, C, D and DtBias (None where there is
    none) in float32 or their stream's dtype, from the forward's ``states``
    and Out's gradient.  The kernel writes dX, dB, dC and, a position and
    head, the direct cotangent of ``Delta`` and the cotangent of ``Delta
    A``; the rest is elementwise over ``[b, t, H]`` and sums of it."""
    (_, bwd), ins, a = _prepared(x, dt, a_log, b, c, d, dt_bias, interpret,
                                 leave_out)
    bsz, t, h, p = x.shape
    dx, db, dc, dd, dda, dsk = bwd(
        *ins, states, d_out.astype(x.dtype).reshape(bsz, t, h * p))
    pre = dt.astype(_F32)
    if dt_bias is not None:
        pre = pre + dt_bias.astype(_F32)
    delta = pre if dt_bias is None else jax.nn.softplus(pre)
    d_delta = dd + dda * a
    d_alog = a * jnp.sum(dda * delta, axis=(0, 1))
    d_d = jnp.sum(dsk.reshape(bsz, -1, _ROWS, h // b.shape[2], p),
                  axis=(0, 2, 4)).reshape(h)
    if dt_bias is None:
        d_dt, d_bias = d_delta, None
    else:
        d_dt = d_delta * jax.nn.sigmoid(pre)
        d_bias = jnp.sum(d_dt, axis=(0, 1))
    return [dx.reshape(x.shape), d_dt, d_alog, db.reshape(b.shape),
            dc.reshape(c.shape), d_d, d_bias]
