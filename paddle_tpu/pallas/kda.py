"""``kda_scan`` and ``kda_scan_grad`` as a pair of Pallas kernels: the chunked
gated delta rule of ``ops/kda_ops.py`` with the chunk's own tensors (the decay
differences, ``P``, ``A``, the solve, ``U``) made in VMEM and the ``d_k x
d_v`` state of every head kept in VMEM scratch from chunk to chunk.  HBM sees
the op's streams and one float32 state a chunk and head (``States``: what the
backward starts each chunk from) and nothing ``[C, C]``- or ``[sub, sub,
d]``-shaped.

**One function of a chunk** (:func:`_chunk`): ``(q, k, v, g, beta, S_0) ->
(o, S_1)`` over ``[C, d]`` tiles in float32, every product at ``highest``.
The forward kernel (``kda_fwd``) runs it chunk after chunk.  The backward
kernel (``kda_bwd``) walks the chunks from the last with ``dS`` as its
carry, makes the chunk's tensors again from the same tiles and ``States[n]``
(:func:`_within` and :func:`_solve`, the forward's own; ``O`` and ``S_1``
are not made again) and runs **the chunk's backward written out by hand**
(:func:`_chunk_back`: its docstring has the equations), 27 products a chunk
and head where ``jax.vjp`` of the chunk made some forty: the transposed
solve is a substitution of its own with the forward's ``N`` and ``X``
transposed (nothing goes back through the steps that built ``X``), products
that share a side are one call over stacked rows, and the cumulated
log-decays' cotangent costs no product (``x e^(+-Gam)`` gives ``+- x`` times
that factor's cotangent on ``x``).  Everything is what Mosaic lowers:
elementwise arithmetic, products (also with the left side transposed),
concatenations at whole tiles, reshapes that split the sublanes at
multiples of 8, slices of whole sub-blocks of rows and of single rows.

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
from ..ops.kda_ops import L2_EPS, SUB

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


def _rows(x, i, n=SUB):
    """The ``i``-th ``n`` rows of ``x``."""
    return x[i * n:(i + 1) * n]


def _blocks(x):
    """``[C, w] -> [m, SUB, w]``: the chunk's rows by sub-block."""
    return x.reshape(-1, SUB, x.shape[-1])


def _at(x3, j):
    """Position ``j`` of every sub-block of ``[m, SUB, w]``, ``[m, 1, w]``."""
    return x3[:, j:j + 1]


def _normed(q, k, beta, neg_eigval):
    """``(q_hat, k_hat, beta', 1 / |q|, 1 / |k|)``: the rows over their
    norms, q scaled by ``d_k^-0.5``, beta doubled where the model says."""
    q_n = jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + L2_EPS)
    k_n = jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + L2_EPS)
    return (q * q_n * float(q.shape[1]) ** -0.5, k * k_n,
            beta * 2.0 if neg_eigval else beta, q_n, k_n)


def _within(q, k, g, beta, keep=False):
    """What a chunk has before any state enters it, from the normalised q
    and k ``[C, d_k]``, the log-decays and beta ``[C, 1]`` (doubled already):
    ``(Gam, P, X, A_off, kept)`` with ``P`` whole (diagonal in), ``X = (I + beta
    A_diag)^-1`` block-diagonal and ``A_off`` the blocks of ``A`` under the
    diagonal ones, without beta.  ``kept`` is None but under ``keep`` (the
    backward): what the way back multiplies by again, the sixteen columns'
    decays and keys, ``A``'s diagonal blocks and the between-sub-block
    factors."""
    c, dk = q.shape
    m = c // SUB
    row, col = _iota((c, c), 0), _iota((c, c), 1)
    row1 = _iota((c, 1), 0)
    sub3 = _iota((m, SUB, 1), 1)        # a position's place in its sub-block

    gc = _dot((row >= col).astype(_F32), g)            # cumulated log-decays
    qb, kb, gb = _blocks(q), _blocks(k), _blocks(gc)

    # inside the sub-blocks: the differences themselves, a column of P and
    # of A at a time, and the forward substitution's step on that column
    rel = col - row // SUB * SUB
    p = jnp.zeros((c, c), _F32)
    a_diag = jnp.zeros((c, c), _F32)
    x = (row == col).astype(_F32)                      # -> (I + A_diag)^-1
    kds = []
    for j in range(SUB):
        e = jnp.exp(jnp.where(sub3 >= j, gb - _at(gb, j), -jnp.inf))
        kd = _at(kb, j) * e
        p_col = jnp.sum(qb * kd, axis=-1, keepdims=True).reshape(c, 1)
        a_col = jnp.sum(kb * kd, axis=-1, keepdims=True).reshape(c, 1)
        p = jnp.where(rel == j, p_col, p)
        if keep:
            kds.append((e, kd))
            a_diag = jnp.where(rel == j, a_col, a_diag)
        if j < SUB - 1:
            l_col = jnp.where(sub3 > j, _blocks(beta * a_col), 0.0)
            x = x - (l_col * _at(_blocks(x), j)).reshape(c, c)

    # between sub-blocks: through the later block's first position; q's and
    # k's rows of a block against the earlier keys in one product
    first = _at(gb, 0)                                 # [m, 1, d_k]
    down = jnp.exp(gb - first).reshape(c, dk)
    qd, kdn = q * down, k * down
    p_off, a_off = [jnp.zeros((SUB, c), _F32)], [jnp.zeros((SUB, c), _F32)]
    offs = []
    for i in range(1, m):
        up = jnp.exp(jnp.where(row1 < i * SUB, _rows(gc, i * SUB, 1) - gc,
                               -jnp.inf))
        later = jnp.concatenate([_rows(qd, i), _rows(kdn, i)], axis=0)
        both = _dot(later, k * up, ((1,), (1,)))
        p_off.append(_rows(both, 0))
        a_off.append(_rows(both, 1))
        offs.append((later, up))
    p = p + jnp.concatenate(p_off, axis=0)
    a_off = jnp.concatenate(a_off, axis=0)
    return gc, p, x, a_off, (a_diag, kds, down, offs) if keep else None


def _solve(x, rhs, beta_a_off):
    """``(U, N)``: ``(I + beta A) U = rhs`` from the sub-blocks' inverse
    ``X``, block row by block row, ``U_m = (X rhs)_m - N_m U`` with ``N = X
    beta A_off`` (strictly under the diagonal blocks)."""
    c, dv = rhs.shape
    solved = _dot(x, jnp.concatenate([rhs, beta_a_off], axis=1))
    r1, n = solved[:, :dv], solved[:, dv:]
    u = [_rows(r1, 0)]
    for i in range(1, c // SUB):
        done = jnp.concatenate(
            u + [jnp.zeros((c - i * SUB, dv), _F32)], axis=0)
        u.append(_rows(r1, i) - _dot(_rows(n, i), done))
    return jnp.concatenate(u, axis=0), n


def _turned(x):
    """``[1, d]`` along the lanes -> ``[d, 1]`` down the sublanes, or back,
    through the diagonal of its broadcast."""
    d = max(x.shape)
    eye = _iota((d, d), 0) == _iota((d, d), 1)
    return jnp.sum(jnp.where(eye, x, 0.0), axis=1 - x.shape.index(1),
                   keepdims=True)


def _chunk(q, k, v, g, beta, s0, *, neg_eigval):
    """One chunk of one head.  q, k, g ``[C, d_k]``, v ``[C, d_v]``, beta
    ``[C, 1]``, s0 ``[d_k, d_v]``, float32, q and k as the op gets them ->
    ``(o [C, d_v], s1 [d_k, d_v])``.  A product at ``highest`` costs by the
    call (six passes, six loads of its right side) more than by its rows, so
    products that share a right side are one call over stacked rows."""
    c = q.shape[0]
    q, k, beta, _, _ = _normed(q, k, beta, neg_eigval)
    gc, p, x, a_off, _ = _within(q, k, g, beta)
    # what reads the state before the chunk, in one product
    eg = jnp.exp(gc)
    from_s0 = _dot(jnp.concatenate([k * eg, q * eg], axis=0), s0)
    u, _ = _solve(x, beta * (v - _rows(from_s0, 0, c)), beta * a_off)
    o = _rows(from_s0, 1, c) + _dot(p, u)
    last = _rows(gc, c - 1, 1)
    k_hat = k * jnp.exp(last - gc)
    s1 = _turned(jnp.exp(last)) * s0 + _dot(k_hat, u, ((0,), (0,)))
    return o, s1


def _chunk_back(q, k, v, g, beta, s0, d_o, d_s1, *, neg_eigval):
    """:func:`_chunk`'s way back, derived by hand: the chunk's tensors made
    again (no ``O``, no ``S_1``), then ``(dq, dk, dv, dg, dbeta [C, 1],
    dS_0)`` from ``dO`` and ``dS_1``.  With ``Qb = q e^Gam``, ``Kb = k
    e^Gam``, ``Kt = k e^(Gam_C - Gam)``, ``M = I + beta A``, ``R = beta (V -
    Kb S_0)``, ``U = M^-1 R``::

        dU = P^T dO + Kt dS_1         dR = M^-T dU        W = beta dR = dV
        [dQb; dKb] = [dO; -W] S_0^T   dKt = U dS_1^T
        dS_0 = [Qb; -Kb]^T [dO; W] + e^Gam_C dS_1
        dP = dO U^T, G = dR U^T (cut to the triangles)   dA = -beta G
        dbeta = sum_v dR (V - Kb S_0) - sum_j G A        (no division)

    ``M^-T`` is the transposed solve by substitution: block row by block
    row from the last with ``N^T`` (the forward's ``N = X beta A_off``),
    then ``X^T``; nothing goes back through the steps that built ``X``.  A
    product costs by what waits on it more than by the call: ``dP`` is
    apart from ``G`` because only ``G`` needs the solve.  Gam
    costs no product: wherever it enters as ``x e^(+-Gam)`` its cotangent is
    ``+- x`` times that factor's cotangent on ``x``, so ``dGam = q dq + k
    (dk_rows - dk_columns)`` elementwise, plus the last row's share of ``Kt``
    and of ``S_1``'s decay.  ``dP`` and ``dA`` go to q and k by the
    forward's two routes: the sixteen columns inside the sub-blocks on the
    VPU, the stacked products between them and their transposes."""
    c, dk = q.shape
    m = c // SUB
    q, k, beta, q_n, k_n = _normed(q, k, beta, neg_eigval)
    gc, p, x, a_off, (a_diag, kds, down, offs) = _within(q, k, g, beta,
                                                         keep=True)
    row, col = _iota((c, c), 0), _iota((c, c), 1)
    row1 = _iota((c, 1), 0)
    sub3 = _iota((m, SUB, 1), 1)

    eg = jnp.exp(gc)
    q_bar, k_bar = q * eg, k * eg
    last = _rows(gc, c - 1, 1)
    e_last = jnp.exp(last)
    to_last = jnp.exp(last - gc)
    v_in = v - _dot(k_bar, s0)
    u, n = _solve(x, beta * v_in, beta * a_off)

    # dP waits for nothing of the solve: a call of its own, so that what
    # it feeds can run beside the chain below (Mosaic's schedule also
    # follows the order of the text: with this call written behind dR's
    # the kernel alone read 3.6 % faster, PERF.md section 7 row 47)
    d_p = jnp.where(row >= col, _dot(d_o, u, ((1,), (1,))), 0.0)

    # the transposed solve: N^T's block rows from the last (N transposed
    # once, so that a step is a product over 16 rows), then X^T
    d_u = _dot(p, d_o, ((0,), (0,))) + _dot(k * to_last, d_s1)
    n_t = n.T
    y = d_u
    for i in range(m - 2, -1, -1):
        y_i = _rows(d_u, i) - _dot(_rows(n_t, i), y)
        y = jnp.concatenate([y_i if b == i else _rows(y, b)
                             for b in range(m)], axis=0)
    d_r = _dot(x, y, ((0,), (0,)))
    w = beta * d_r

    low = jnp.where(row > col, _dot(d_r, u, ((1,), (1,))), 0.0)
    d_a = -beta * low
    d_beta = jnp.sum(d_r * v_in, axis=1, keepdims=True) - jnp.sum(
        low * (a_diag + a_off), axis=1, keepdims=True)

    to_s0 = _dot(jnp.concatenate([d_o, -w], axis=0), s0, ((1,), (1,)))
    d_kt = _dot(u, d_s1, ((1,), (1,)))
    d_s0 = _dot(jnp.concatenate([q_bar, -k_bar], axis=0),
                jnp.concatenate([d_o, w], axis=0), ((0,), (0,))) \
        + _turned(e_last) * d_s1

    # dP and dA inside the sub-blocks, a column at a time: to q and to k as
    # rows (q_i, k_i of P_ij, A_ij) and to k as columns (k_j)
    rel = col - row // SUB * SUB
    qb, kb = _blocks(q), _blocks(k)
    zero = jnp.zeros((m, SUB, dk), _F32)
    dq_row, dk_row, dk_col = zero, zero, zero
    for j, (e, kd) in enumerate(kds):
        def column(d):
            return jnp.sum(jnp.where(rel == j, d, 0.0), axis=1,
                           keepdims=True).reshape(m, SUB, 1)
        dp_col, da_col = column(d_p), column(d_a)
        dq_row = dq_row + dp_col * kd
        dk_row = dk_row + da_col * kd
        at_j = jnp.sum((dp_col * qb + da_col * kb) * e, axis=1,
                       keepdims=True)
        dk_col = jnp.where(sub3 == j, at_j, dk_col)
    dq_row, dk_row, dk_col = (z.reshape(c, dk) for z in (dq_row, dk_row,
                                                         dk_col))

    # between the sub-blocks: the forward's products, transposed
    rows_off = [jnp.zeros((2 * SUB, dk), _F32)]
    for i, (later, up) in enumerate(offs, 1):
        d_both = jnp.concatenate([_rows(d_p, i), _rows(d_a, i)], axis=0)
        rows_off.append(_dot(d_both, k * up) * jnp.concatenate(
            [_rows(down, i)] * 2, axis=0))
        dk_col = dk_col + _dot(d_both, later, ((0,), (0,))) * up
    dq_row = dq_row + jnp.concatenate([_rows(z, 0) for z in rows_off], axis=0)
    dk_row = dk_row + jnp.concatenate([_rows(z, 1) for z in rows_off], axis=0)

    dq_hat = dq_row + _rows(to_s0, 0, c) * eg
    dk_rows = dk_row + _rows(to_s0, 1, c) * eg
    dk_cols = dk_col + d_kt * to_last
    dk_hat = dk_rows + dk_cols
    # the last row's Gam is also Gam_C: Kt's exponent and S_1's decay
    at_last = jnp.sum(k * d_kt * to_last, axis=0, keepdims=True) \
        + e_last * _turned(jnp.sum(s0 * d_s1, axis=1, keepdims=True))
    d_gam = q * dq_hat + k * (dk_rows - dk_cols) \
        + jnp.where(row1 == c - 1, at_last, 0.0)
    d_g = _dot((row <= col).astype(_F32), d_gam)

    # back through the norms: x_hat = s x / |x|, s^2 = 1 / d_k for q
    d_q = q_n * float(dk) ** -0.5 * (dq_hat - q * jnp.sum(
        dq_hat * q, axis=1, keepdims=True) * float(dk))
    d_k = k_n * (dk_hat - k * jnp.sum(dk_hat * k, axis=1, keepdims=True))
    return d_q, d_k, w, d_g, d_beta * (2.0 if neg_eigval else 1.0), d_s0


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
    *grads, d_beta, d_s0 = _chunk_back(
        *tiles, states_ref[...], do_ref[...].astype(_F32), ds_scr[h],
        neg_eigval=neg_eigval)
    for ref, grad in zip((dq_ref, dk_ref, dv_ref, dg_ref), grads):
        ref[...] = grad.astype(ref.dtype)
    dbeta_ref[...] = jnp.where(mine, d_beta, dbeta_ref[...])
    ds_scr[h] = d_s0


def _vmem_limit(chunk, d_k, d_v, h):
    """What a call may ask of VMEM: the states, the tiles twice over and
    room for what the way back keeps of a chunk (the sixteen columns' decays
    and decayed keys, 32 ``[C, d_k]`` tiles, and as many again for the
    chunk's other tensors and their cotangents)."""
    return int(h * d_k * d_v * 4 + 64 * chunk * max(d_k, d_v) * 4 + (8 << 20))


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
