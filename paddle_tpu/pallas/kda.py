"""``kda_scan`` and ``kda_scan_grad`` as a pair of Pallas kernels: the chunked
gated delta rule of ``ops/kda_ops.py`` with the chunk's own tensors (the decay
differences, ``P``, ``A``, the solve, ``U``) made in VMEM and the ``d_k x
d_v`` state of every head kept in VMEM scratch from chunk to chunk.  HBM sees
the op's streams and one float32 state a chunk and head (``States``: what the
backward starts each chunk from) and nothing ``[C, C]``- or ``[sub, sub,
d]``-shaped.

**One function of a chunk** (:func:`_chunk`): ``(q, k, v, g, beta, S_0) ->
(o, S_1)`` over ``[C, d]`` tiles in float32, every product at ``highest``.
The forward kernel (``kda_fwd``) runs it chunk after chunk; the backward
kernel (``kda_bwd``) walks the chunks from the last, makes the chunk's
tensors again from the same tiles and ``States[n]``, and takes the
function's ``jax.vjp`` with the cotangents ``(dO, dS_1)``: ``dS`` is the
carry.  The function is written in what Mosaic lowers and what transposes
into that: elementwise arithmetic, products, concatenations, reshapes that
split the sublanes at multiples of 8, and slices through :func:`_cut` (a
plain slice transposes to a pad, which Mosaic does not take).

**The same numbers as** ``kda_chunked``, by the same rule: decays enter as
differences of cumulated log-decays with every exponent ``<= 0``, exactly
(``exp(Gam_i - Gam_j)``) inside a sub-block of 16 positions and through the
later sub-block's first position between sub-blocks.  What differs is the
order of float32 sums, and two steps that the sequential walk allows:

- ``U = (I + A)^-1 beta (V - (K e^Gam) S_0)``: one solve against ``d_v``
  columns where the batched form, which has no ``S_0`` yet, solves against
  ``[V | K e^Gam]`` and subtracts after;
- Mosaic has no triangular solve, so ``(I + A)^-1`` is applied as forward
  substitution: inside the sub-blocks column by column on the VPU (``X <- X
  - A[:, j] X[j, :]``, all sub-blocks at once, block-diagonal ``X``), and
  between them block row by block row, ``U_m = (X R)_m - (X A_off)_m U``,
  on the MXU.  No power of ``A`` is ever formed: ``(I - A)(I + A^2)...`` is
  exact on paper and loses every digit where keys are near parallel and
  beta is doubled (``A^16``'s entries pass 1e18).

**Grid** ``(b, t / C, h)``, the head innermost: a step reads its head's
``[C, d]`` column block of the ``[b, t, h d]`` views (no transposed copy in
HBM) and the chunk's ``[C, h]`` block of Beta, which stays in VMEM over the
heads as the ``[C, h]`` block of ``dBeta`` does, each head writing its
column.  Nothing runs in parallel over the grid: the states live in scratch
``[h, d_k, d_v]``."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# the jnp form's own: the kernels' sub-block and norm are not theirs to choose
from ..ops.kda_ops import SUB, l2norm

_F32 = jnp.float32


def fits(d_k, d_v, chunk, dtypes):
    """Whether the kernels take these shapes: heads of whole lane tiles, a
    chunk of whole sub-blocks, streams in float32 or bf16.  ``kda_chunked``
    runs everything else (16- and 8-wide toy heads)."""
    ok = (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16))
    return d_k % 128 == 0 and d_v % 128 == 0 and chunk % SUB == 0 and \
        all(jnp.dtype(d) in ok for d in dtypes)


def _dot(a, b, dims=((1,), (0,))):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=_F32)


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _cut_of(x, axis, lo, hi, size):
    return jax.lax.slice_in_dim(x, lo, hi, axis=axis)


def _cut_back(axis, lo, hi, size, _, g):
    def zeros(n):
        return jnp.zeros(g.shape[:axis] + (n,) + g.shape[axis + 1:], g.dtype)
    return (jnp.concatenate([zeros(lo)] * (lo > 0) + [g]
                            + [zeros(size - hi)] * (hi < size), axis=axis),)


_cut_of.defvjp(lambda x, *where: (_cut_of(x, *where), None), _cut_back)


def _cut(x, axis, lo, hi):
    """``x[lo:hi]`` along ``axis``.  A function of its own for its way back:
    jax transposes a slice into a pad, which Mosaic does not lower; this one
    goes back as a concatenation with zeros."""
    return _cut_of(x, axis, lo, hi, x.shape[axis])


def _chunk(q, k, v, g, beta, s0, *, neg_eigval):
    """One chunk of one head.  q, k, g ``[C, d_k]``, v ``[C, d_v]``, beta
    ``[C, 1]``, s0 ``[d_k, d_v]``, float32, q and k as the op gets them ->
    ``(o [C, d_v], s1 [d_k, d_v])``.  A product at ``highest`` costs by the
    call (six passes, six loads of its right side) more than by its rows, so
    products that share a right side are one call over stacked rows."""
    c, dk = q.shape
    dv = v.shape[1]
    m = c // SUB
    q = l2norm(q) * float(dk) ** -0.5
    k = l2norm(k)
    if neg_eigval:
        beta = beta * 2.0
    row, col = _iota((c, c), 0), _iota((c, c), 1)
    row1 = _iota((c, 1), 0)
    sub3 = _iota((m, SUB, 1), 1)        # a position's place in its sub-block

    def blocks(x):      # [C, w] -> [m, SUB, w]
        return x.reshape(m, SUB, x.shape[-1])

    def rows(x, i, n=SUB):      # the ``i``-th ``n`` rows of ``x``
        return _cut(x, 0, i * n, (i + 1) * n)

    def below(parts):   # the first blocks of ``[C, w]``, zeros behind
        rest = c - sum(p.shape[0] for p in parts)
        return jnp.concatenate(
            parts + [jnp.zeros((rest, parts[0].shape[1]), _F32)], axis=0)

    def at(x3, j):      # position j of every sub-block, [m, 1, w]
        return _cut(x3, 1, j, j + 1)

    gc = _dot((row >= col).astype(_F32), g)            # cumulated log-decays
    qb, kb, gb = blocks(q), blocks(k), blocks(gc)

    # inside the sub-blocks: the differences themselves, a column of P and
    # of A at a time, and the forward substitution's step on that column
    rel = col - row // SUB * SUB
    p = jnp.zeros((c, c), _F32)
    x = (row == col).astype(_F32)                      # -> (I + A_diag)^-1
    for j in range(SUB):
        e = jnp.exp(jnp.where(sub3 >= j, gb - at(gb, j), -jnp.inf))
        kd = at(kb, j) * e
        p_col = jnp.sum(qb * kd, axis=-1, keepdims=True).reshape(c, 1)
        a_col = jnp.sum(kb * kd, axis=-1, keepdims=True).reshape(c, 1)
        p = jnp.where(rel == j, p_col, p)
        if j < SUB - 1:
            l_col = jnp.where(sub3 > j, blocks(beta * a_col), 0.0)
            x = x - (l_col * at(blocks(x), j)).reshape(c, c)

    # between sub-blocks: through the later block's first position; q's and
    # k's rows of a block against the earlier keys in one product
    first = at(gb, 0)                                  # [m, 1, d_k]
    down = jnp.exp(gb - first).reshape(c, dk)
    qd, kdn = q * down, k * down
    p_off, a_off = [jnp.zeros((SUB, c), _F32)], [jnp.zeros((SUB, c), _F32)]
    for i in range(1, m):
        up = jnp.exp(jnp.where(row1 < i * SUB, rows(gc, i * SUB, 1) - gc,
                               -jnp.inf))
        both = _dot(jnp.concatenate([rows(qd, i), rows(kdn, i)], axis=0),
                    k * up, ((1,), (1,)))
        p_off.append(rows(both, 0))
        a_off.append(rows(both, 1))
    p = p + jnp.concatenate(p_off, axis=0)
    a_off = jnp.concatenate(a_off, axis=0)

    # what reads the state before the chunk, in one product
    eg = jnp.exp(gc)
    from_s0 = _dot(jnp.concatenate([k * eg, q * eg], axis=0), s0)
    rhs = beta * (v - rows(from_s0, 0, c))
    solved = _dot(x, jnp.concatenate([rhs, beta * a_off], axis=1))
    r1, n = _cut(solved, 1, 0, dv), _cut(solved, 1, dv, dv + c)
    u = [rows(r1, 0)]
    for i in range(1, m):
        u.append(rows(r1, i) - _dot(rows(n, i), below(u)))
    u = jnp.concatenate(u, axis=0)

    o = rows(from_s0, 1, c) + _dot(p, u)
    last = rows(gc, c - 1, 1)
    k_hat = k * jnp.exp(last - gc)
    # exp(Gam_C) down the rows of the state: from along the lanes to along
    # the sublanes through the diagonal of its broadcast
    eye = _iota((dk, dk), 0) == _iota((dk, dk), 1)
    down_rows = jnp.sum(jnp.where(eye, jnp.exp(last), 0.0), axis=1,
                        keepdims=True)
    s1 = down_rows * s0 + _dot(k_hat, u, ((0,), (0,)))
    return o, s1


def _tiles(refs, beta_ref, h):
    """The step's float32 tiles: the streams as they are, the head's column
    of the ``[C, n_head]`` block of Beta as ``[C, 1]``."""
    mine = _iota(beta_ref.shape, 1) == h
    beta = jnp.sum(jnp.where(mine, beta_ref[...].astype(_F32), 0.0), axis=1,
                   keepdims=True)
    return [r[...].astype(_F32) for r in refs] + [beta], mine


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, states_ref,
                s_scr, *, neg_eigval):
    from jax.experimental import pallas as pl
    n, h = pl.program_id(1), pl.program_id(2)

    @pl.when(n == 0)
    def _():
        s_scr[h] = jnp.zeros(s_scr.shape[1:], _F32)

    s0 = s_scr[h]
    states_ref[...] = s0
    tiles, _ = _tiles((q_ref, k_ref, v_ref, g_ref), beta_ref, h)
    o, s1 = _chunk(*tiles, s0, neg_eigval=neg_eigval)
    o_ref[...] = o.astype(o_ref.dtype)
    s_scr[h] = s1


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, states_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, ds_scr, *,
                neg_eigval):
    """Grid step ``n`` is chunk ``t / C - 1 - n`` (the index maps turn the
    axis): ``dS`` behind the last chunk is zero."""
    from jax.experimental import pallas as pl
    n, h = pl.program_id(1), pl.program_id(2)

    @pl.when(n == 0)
    def _():
        ds_scr[h] = jnp.zeros(ds_scr.shape[1:], _F32)

    tiles, mine = _tiles((q_ref, k_ref, v_ref, g_ref), beta_ref, h)
    _, back = jax.vjp(functools.partial(_chunk, neg_eigval=neg_eigval),
                      *tiles, states_ref[...])
    *grads, d_beta, d_s0 = back((do_ref[...].astype(_F32), ds_scr[h]))
    for ref, grad in zip((dq_ref, dk_ref, dv_ref, dg_ref), grads):
        ref[...] = grad.astype(ref.dtype)
    dbeta_ref[...] = jnp.where(mine, d_beta, dbeta_ref[...])
    ds_scr[h] = d_s0


def _vmem_limit(chunk, d_k, d_v, h):
    """What a call may ask of VMEM: the states, the tiles twice over and
    room for what the way back keeps of a chunk (16 columns' worth of
    ``[C, d_k]`` tensors and their cotangents)."""
    return int(h * d_k * d_v * 4 + 96 * chunk * max(d_k, d_v) * 4 + (8 << 20))


@functools.lru_cache(maxsize=None)
def _calls(b, t, h, d_k, d_v, chunk, neg_eigval, dtypes, interpret):
    """``(forward, backward)`` for these shapes (``t`` a multiple of
    ``chunk``), over the ``[b, t, h d]`` views."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    n = t // chunk
    q_dt, k_dt, v_dt, g_dt, beta_dt = dtypes

    def stream(d, turn):
        return pl.BlockSpec((None, chunk, d), lambda i, j, l: (
            i, n - 1 - j if turn else j, l))

    def heads(turn):        # Beta's and dBeta's [C, h] block of a chunk
        return pl.BlockSpec((None, chunk, h), lambda i, j, l: (
            i, n - 1 - j if turn else j, 0))

    def state(turn):
        return pl.BlockSpec((None, None, None, d_k, d_v), lambda i, j, l: (
            i, l, n - 1 - j if turn else j, 0, 0))

    def streams(turn):
        return [stream(d_k, turn), stream(d_k, turn), stream(d_v, turn),
                stream(d_k, turn), heads(turn)]

    def shape(*dims, dtype=_F32):
        return jax.ShapeDtypeStruct(dims, dtype)

    params = pltpu.CompilerParams(
        dimension_semantics=("arbitrary",) * 3,
        vmem_limit_bytes=_vmem_limit(chunk, d_k, d_v, h))
    states = shape(b, h, n, d_k, d_v)
    scratch = [pltpu.VMEM((h, d_k, d_v), _F32)]
    fwd = pl.pallas_call(
        functools.partial(_fwd_kernel, neg_eigval=neg_eigval),
        grid=(b, n, h), in_specs=streams(False),
        out_specs=[stream(d_v, False), state(False)],
        out_shape=[shape(b, t, h * d_v, dtype=q_dt), states],
        scratch_shapes=scratch, compiler_params=params, interpret=interpret,
        name="kda_fwd")
    bwd = pl.pallas_call(
        functools.partial(_bwd_kernel, neg_eigval=neg_eigval),
        grid=(b, n, h),
        in_specs=streams(True) + [state(True), stream(d_v, True)],
        out_specs=streams(True),
        out_shape=[shape(b, t, h * d_k, dtype=q_dt),
                   shape(b, t, h * d_k, dtype=k_dt),
                   shape(b, t, h * d_v, dtype=v_dt),
                   shape(b, t, h * d_k, dtype=g_dt),
                   shape(b, t, h, dtype=beta_dt)],
        scratch_shapes=scratch, compiler_params=params, interpret=interpret,
        name="kda_bwd")
    return fwd, bwd


def _flat(x, pad):
    """[b, t, h, d] -> [b, t + pad, h d] (Beta: [b, t + pad, h]): zeros
    behind the end are no key, no write and no decay."""
    if x.ndim == 4:
        x = x.reshape(*x.shape[:2], -1)
    return jnp.pad(x, [(0, 0), (0, pad), (0, 0)]) if pad else x


def _prepared(q, k, v, g, beta, chunk, neg_eigval, interpret):
    b, t, h, d_k = q.shape
    d_v = v.shape[-1]
    pad = -t % chunk
    ins = [_flat(x, pad) for x in (q, k, v, g, beta)]
    calls = _calls(b, t + pad, h, d_k, d_v, int(chunk), bool(neg_eigval),
                   tuple(jnp.dtype(x.dtype) for x in ins), bool(interpret))
    return ins, calls, pad


def kda_fwd(q, k, v, g, beta, *, chunk=64, neg_eigval=False,
            interpret=False):
    """``kda_chunked``'s arguments -> ``(out [b, t, h, d_v] in q's dtype,
    states [b, h, ceil(t / chunk), d_k, d_v] float32)``: ``states[:, :, n]``
    is the state before chunk ``n``.  The shapes have to pass
    :func:`fits`."""
    b, t, h, _ = q.shape
    ins, (fwd, _), _ = _prepared(q, k, v, g, beta, chunk, neg_eigval,
                                 interpret)
    out, states = fwd(*ins)
    return out[:, :t].reshape(b, t, h, v.shape[-1]), states


def kda_bwd(q, k, v, g, beta, states, d_out, *, chunk=64, neg_eigval=False,
            interpret=False):
    """The five inputs' gradients, in their shapes and dtypes, from the
    forward's ``states`` and Out's gradient."""
    ins, (_, bwd), pad = _prepared(q, k, v, g, beta, chunk, neg_eigval,
                                   interpret)
    grads = bwd(*ins, states, _flat(d_out.astype(q.dtype), pad))
    return [dx[:, :x.shape[1]].reshape(x.shape)
            for dx, x in zip(grads, (q, k, v, g, beta))]
