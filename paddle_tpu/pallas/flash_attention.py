"""Flash attention: fused online-softmax attention with O(T) memory.

Two widths: Q and K are ``d_qk`` wide (the scores contract over it), V and
the output ``d_v`` wide, read from V's shape.  ``d_qk == d_v`` is every
caller before latent attention (192 over 128) and lowers exactly as before
the second width existed; nothing is padded to the wider of the two in HBM:
V's, O's and dO's blocks, the accumulators and dV are ``d_v`` wide, Q's, K's,
dQ and dK ``d_qk``.

The score as two products (PR 48): with ``q_rope`` ``[b, h, Tq, d_r]`` and
``k_rope`` ``[b, h_r, Tk, d_r]`` a pair's score is ``(q·k + q_rope·k_rope) ·
sm_scale``: every kernel adds a second product into the same float32 score
tile, the rotary key's block comes through an index map of its own (``h_r``
heads for ``h`` query heads; latent attention has one), and the backward
feeds ``ds`` into two more small products for ``dq_rope`` and ``dk_rope``.
Nothing ``d_qk + d_r`` wide and no per-head copy of the rotary key exists in
HBM.  The second product is a trace-time ``if`` on the two operands, like the
bias's: a call without them lowers to the kernels, grids, block tables and
VMEM limits it had before they existed.

Forward on TPU runs a Pallas kernel tiled for the MXU (grid over
(batch*heads, q-blocks, k-blocks), f32 accumulators in VMEM scratch);
elsewhere (CPU tests, interpret debugging) a blockwise ``lax.scan``
computes the same math.  The backward pass is the standard flash
recomputation: no O(T^2) attention matrix is ever materialized — only
per-(q-block, k-block) tiles, rebuilt from the saved logsumexp.  Two
Pallas backwards share that arithmetic and differ in where dQ (summed over
key blocks) accumulates: "fused" rebuilds each live tile pair once and keeps
both sums on the chip (dK/dV of the key block in scratch, the head's whole
dQ in a (Tq, d_qk) float32 scratch: 5 products a pair, no HBM that grows
with T², and a call that asks for more than Mosaic's default 16 MiB of
scoped VMEM); "split" runs a dQ pass and a dK/dV pass that each rebuild the
tile (7 products, no memory that grows: what a length too long for the fused
accumulator falls back to).  ``_flash_bwd_pallas`` picks from the shapes; a
bias takes the blockwise jax backward on every backend.

Masks: the causal half, a trailing window over it (``window=`` an int), or
a mask FORM, an object that is the whole mask and rides the same ``window``
argument through the entry points and the kernels (:class:`BlockDiffusion`,
PR 61: the one mask here that is no function of ``i - j``).  Four helpers
tell them apart and nothing else does: :func:`_pos_mask` (a tile's mask),
:func:`_block_dispatch` (a tile pair is skipped, mask-free or masked),
:func:`_live_k` / :func:`_live_q` (the index maps, so that a skipped step
copies no tile), and :func:`_seen` on the blockwise jax paths.  A masked
tile pair whose live region is static runs by sub-tiles, those the mask
leaves live and the mask on the edge ones alone (PR 62:
:func:`_subtile_kinds`); the others run all of their scores.

Capability anchor in the reference: attention assembled from separate
matmul/softmax/dropout ops in its Transformer recipe
(``python/paddle/fluid/tests/unittests/dist_transformer.py:1034``
scaled_dot_product_attention), which materializes [b, h, T, T] scores in
HBM.  This kernel is the TPU-native replacement.
"""

from __future__ import annotations

import collections
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..device import on_tpu

NEG_INF = -1e30
_LANE = 128      # TPU lane width: min last-dim tile


# ---------------------------------------------------------------------------
# Mask forms: a mask that is not a function of i - j
# ---------------------------------------------------------------------------

class BlockDiffusion(collections.namedtuple("BlockDiffusion", "half block")):
    """The attention mask of block-diffusion training (arXiv:2503.09573), a
    value of the kernels' ``window`` argument (:func:`block_diffusion` makes
    it).  The sequence is the noisy copy of ``half`` tokens followed by the
    clean copy (``T = 2 · half``), both cut into blocks of ``block``; with
    ``b(p) = p // block``, query ``i`` sees key ``j`` iff

    - ``i < half``, ``j < half``: ``b(i) == b(j)`` (noisy sees its own block,
      both directions);
    - ``i < half``, ``j >= half``: ``b(j - half) < b(i)`` (noisy sees the clean
      blocks strictly before its own);
    - ``i >= half``, ``j >= half``: ``b(j - half) <= b(i - half)`` (clean is
      block-causal);
    - ``i >= half``, ``j < half``: never.

    ``L² + L·block`` pairs a head (``L = half``) of the ``4 L²``.  The
    kernels take it through the places a window goes: :meth:`tile_state` and
    :meth:`dispatch` (a tile pair is skipped, mask-free or masked; the same
    question of a sub-tile, asked in numpy at trace time, is what
    :func:`_subtile_kinds` builds the three kinds of masked tile pair from:
    at blocks of 4 in tiles of 1024 the noisy x noisy diagonal runs 8 of its
    64 sub-tiles of 128, the other two diagonals 36), :meth:`tile_mask` (the
    mask of a tile or sub-tile an edge crosses, from a column of query
    blocks and a row of key blocks, no tile-wide integer work but one
    compare and one select), :meth:`live_k` and :meth:`live_q` (the index
    maps that keep dead steps from copying a tile).  ``causal`` is not read:
    the form is the whole mask."""

    __slots__ = ()
    #: the named scope the op's device operations lie under, and the
    #: counters' ``mask`` label
    scope = "block_diffusion"

    def __str__(self):
        return f"block_diffusion:{self.block}"

    def _blk(self, pos):
        shift = self.block.bit_length() - 1
        return pos >> shift if self.block == 1 << shift else pos // self.block

    def visible(self, q_pos, k_pos, tq_real=None, tk_real=None):
        """The mask over int32 positions that broadcast against each other (a
        column of queries, a row of keys, either way round), padding beyond
        ``tq_real`` / ``tk_real`` invisible.  A key is one number, its block
        (a noisy key's ``far`` higher); a query two: the number a noisy key
        must EQUAL (its own noisy block; none for a clean query) and the
        number a clean key must lie BELOW (a noisy query's block, a clean
        query's block + 1).  Every select is on a column or a row of
        integers; a pair costs two compares and an or (Mosaic has no select
        between tiles of booleans)."""
        half, far = self.half, jnp.int32(2 ** 29)
        q_noisy, k_noisy = q_pos < half, k_pos < half
        q_blk = self._blk(jnp.where(q_noisy, q_pos, q_pos - half))
        key = self._blk(jnp.where(k_noisy, k_pos, k_pos - half)) + \
            jnp.where(k_noisy, far, 0)
        equal = jnp.where(q_noisy, q_blk + far, -1)
        below = jnp.where(q_noisy, q_blk, q_blk + 1)
        if tq_real is not None:
            real = q_pos < tq_real
            equal = jnp.where(real, equal, -1)
            below = jnp.where(real, below, 0)
        if tk_real is not None:
            key = jnp.where(k_pos < tk_real, key, 2 * far)
        return (key == equal) | (key < below)

    def dense(self, tq, tk):
        """The ``[tq, tk]`` boolean array (tests, references)."""
        return self.visible(jnp.arange(tq, dtype=jnp.int32)[:, None],
                            jnp.arange(tk, dtype=jnp.int32)[None, :])

    def tile_mask(self, iq, ik, block_q, block_k, tq_real, tk_real,
                  transposed=False):
        """:func:`_pos_mask`'s result for this form."""
        import jax.lax as lax
        q_shape, k_shape = ((1, block_q), (block_k, 1)) if transposed \
            else ((block_q, 1), (1, block_k))
        q_pos = iq * block_q + lax.broadcasted_iota(
            jnp.int32, q_shape, 1 if transposed else 0)
        k_pos = ik * block_k + lax.broadcasted_iota(
            jnp.int32, k_shape, 0 if transposed else 1)
        return self.visible(q_pos, k_pos, tq_real, tk_real)

    def tile_state(self, q0, k0, block_q, block_k, xp=jnp):
        """``(live, full)`` of the tile pair whose first query is ``q0`` and
        first key ``k0`` (traced scalars, or numpy arrays with ``xp=np``):
        whether any pair of it is visible, and whether all are (then no
        padding lies in it either).  A tile that straddles the two halves is
        the union of its quarters."""
        half, block = self
        q1, k1 = q0 + (block_q - 1), k0 + (block_k - 1)
        whole = (q1 < 2 * half) & (k1 < 2 * half)
        q1, k1 = xp.minimum(q1, 2 * half - 1), xp.minimum(k1, 2 * half - 1)
        qn, qc, kn, kc = q0 < half, q1 >= half, k0 < half, k1 >= half
        # first and last block of the tile's noisy (n) and clean (c) rows
        # and columns; read only where the tile has such rows or columns
        qn0, qn1 = q0 // block, xp.minimum(q1, half - 1) // block
        kn0, kn1 = k0 // block, xp.minimum(k1, half - 1) // block
        qc0 = (xp.maximum(q0, half) - half) // block
        qc1 = (xp.maximum(q1, half) - half) // block
        kc0 = (xp.maximum(k0, half) - half) // block
        kc1 = (xp.maximum(k1, half) - half) // block
        live = (qn & kn & (kn0 <= qn1) & (kn1 >= qn0)) | \
            (qn & kc & (kc0 < qn1)) | (qc & kc & (kc0 <= qc1))
        full = whole & ~(qc & kn) & \
            (~(qn & kn) | ((qn0 == qn1) & (kn0 == kn1) & (qn0 == kn0))) & \
            (~(qn & kc) | (kc1 < qn0)) & (~(qc & kc) | (kc1 <= qc0))
        return live, full

    def dispatch(self, iq, ik, block_q, block_k, compute):
        """:func:`_block_dispatch`'s ladder for this form: a tile pair with
        no visible pair is skipped, one with every pair visible runs
        ``compute(False)``, the others ``compute(True)``."""
        from jax.experimental import pallas as pl
        live, full = self.tile_state(iq * block_q, ik * block_k, block_q,
                                     block_k)
        pl.when(full)(lambda: compute(False))
        pl.when(live & jnp.logical_not(full))(lambda: compute(True))

    def live_k(self, i, j, block_q, block_k):
        """:func:`_live_k` for this form.  The key blocks query block ``i``
        sees are two runs: its noisy rows' own blocks, and the clean blocks
        from the first to the last any of its rows sees.  A step before or
        between them names the next run's first block (one copy, which that
        run needs anyway), a step behind a run its last."""
        half, block = self
        q0 = i * block_q
        q1 = jnp.minimum(q0 + block_q, 2 * half) - 1
        last_noisy = jnp.minimum(q1, half - 1) // block
        lo1 = (q0 // block * block) // block_k
        hi1 = jnp.minimum(last_noisy * block + block - 1, half - 1) // block_k
        # the last clean key a row of the tile sees: a noisy row's lies
        # before its block, a clean row's ends its block
        last = jnp.maximum(
            jnp.where(q0 < half, last_noisy * block, 0),
            jnp.where(q1 >= half, (q1 - half) // block * block + block, 0)
        ) + (half - 1)
        lo2, hi2 = half // block_k, jnp.maximum(last, half) // block_k
        lo1, hi1 = jnp.where(q0 < half, lo1, lo2), jnp.where(q0 < half, hi1,
                                                             lo2)
        return jnp.where(j <= hi1, jnp.clip(j, lo1, hi1),
                         jnp.clip(j, lo2, hi2))

    def live_q(self, i, j, block_q, block_k):
        """:func:`_live_q` for this form.  The query blocks that see key
        block ``j`` are two runs as well: noisy rows (the blocks of its noisy
        keys; behind its first clean key's block, to the end of the half)
        and clean rows (from its first clean key's block to the end)."""
        half, block = self
        k0 = j * block_k
        k1 = jnp.minimum(k0 + block_k, 2 * half) - 1
        kn, kc = k0 < half, k1 >= half
        first_clean = (jnp.maximum(k0, half) - half) // block
        behind = (first_clean + 1) * block
        a_lo = jnp.where(kn, jnp.where(kc, jnp.minimum(k0 // block * block,
                                                       behind),
                                       k0 // block * block), behind)
        a_hi = jnp.where(kc, half - 1, jnp.minimum(
            jnp.minimum(k1, half - 1) // block * block + block - 1, half - 1))
        lo_c = (half + first_clean * block) // block_q
        hi_c = (2 * half - 1) // block_q
        lo_a, hi_a = a_lo // block_q, a_hi // block_q
        no_a = jnp.logical_not(kn) & (behind > half - 1)
        lo_a, hi_a = jnp.where(no_a, lo_c, lo_a), jnp.where(no_a, lo_c, hi_a)
        lo_c, hi_c = jnp.where(kc, lo_c, hi_a), jnp.where(kc, hi_c, hi_a)
        return jnp.where(i <= hi_a, jnp.clip(i, lo_a, hi_a),
                         jnp.clip(i, lo_c, hi_c))

    def tile_pairs(self, block_q, block_k):
        """``{"free", "masked", "dead"}``: the tile pairs of one head's grid
        at these blocks by what :meth:`dispatch` does with them."""
        t = 2 * self.half
        return dict(zip(("free", "masked", "dead"), grid_tile_pairs(
            False, self, block_q, block_k, t, t)))


def block_diffusion(t, block):
    """The :class:`BlockDiffusion` mask of a doubled sequence of ``t`` rows
    in blocks of ``block``: pass it as ``window=`` (``causal=False``)."""
    t, block = int(t), int(block)
    if block < 1 or t % 2 or (t // 2) % block:
        raise ValueError(
            f"block diffusion over {t} rows in blocks of {block}: the rows "
            "are two copies of one sequence of whole blocks")
    return BlockDiffusion(t // 2, block)


def mha_reference(q, k, v, bias=None, causal=False, sm_scale=None,
                  window=None, q_rope=None, k_rope=None):
    """O(T^2) reference attention (the math the kernel must reproduce): Q
    and K ``[b, h, T, d_qk]``, V ``[b, h, T, d_v]``, the result ``d_v`` wide.
    ``window``: with ``causal``, key ``j`` is visible to query ``i`` iff
    ``0 <= i - j < window``; a :class:`BlockDiffusion`: its dense mask,
    whatever ``causal`` says.  K and V may have fewer heads than Q: query
    head ``h`` reads KV head ``h // (n_q_heads // n_kv_heads)``.  With
    ``q_rope`` ``[b, h, T, d_r]`` and ``k_rope`` ``[b, h_r, T, d_r]`` the
    score is over ``[q | q_rope]`` and ``[k | k_rope]``, the rotary key's
    heads repeated like K's, and the default scale from the whole width."""
    if q_rope is not None:
        q = jnp.concatenate([q, q_rope], axis=-1)
        k = jnp.concatenate([
            jnp.repeat(k, q.shape[1] // k.shape[1], axis=1),
            jnp.repeat(k_rope, q.shape[1] // k_rope.shape[1], axis=1)],
            axis=-1)
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if k.shape[1] != q.shape[1]:
        k = jnp.repeat(k, q.shape[1] // k.shape[1], axis=1)
    if v.shape[1] != q.shape[1]:
        v = jnp.repeat(v, q.shape[1] // v.shape[1], axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    tq, tk = s.shape[-2], s.shape[-1]
    if isinstance(window, BlockDiffusion):
        s = jnp.where(window.dense(tq, tk), s, NEG_INF)
    elif causal:
        mask = jnp.tril(jnp.ones((tq, tk), dtype=bool), k=tk - tq)
        if window is not None:
            mask = mask & ~jnp.tril(jnp.ones((tq, tk), dtype=bool),
                                    k=tk - tq - int(window))
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)
                      ).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas TPU kernel (forward)
# ---------------------------------------------------------------------------

def _pos_mask(iq, ik, block_q, block_k, causal, offset, tq_real, tk_real,
              transposed=False, window=None):
    """[bq, bk] (or [bk, bq]) validity mask for one block pair: padding
    bounds + the causal triangle + the window's trailing edge, or what a
    mask form says (:meth:`BlockDiffusion.tile_mask`).  Shared by all four
    kernels."""
    import jax.lax as lax

    if isinstance(window, BlockDiffusion):
        return window.tile_mask(iq, ik, block_q, block_k, tq_real, tk_real,
                                transposed)
    shape = (block_k, block_q) if transposed else (block_q, block_k)
    q_axis, k_axis = (1, 0) if transposed else (0, 1)
    q_pos = iq * block_q + lax.broadcasted_iota(jnp.int32, shape, q_axis)
    k_pos = ik * block_k + lax.broadcasted_iota(jnp.int32, shape, k_axis)
    if tk_real is None:
        # a sub-tile of an unpadded call (:func:`_subtile_kinds`): the
        # causal edge or the window's, whichever crosses it, and no bound
        mask = q_pos + offset >= k_pos
        if window is not None:
            mask = mask & (q_pos + offset - k_pos < window)
        return mask
    mask = k_pos < tk_real
    if tq_real is not None:
        mask = mask & (q_pos < tq_real)
    if causal:
        mask = mask & (q_pos + offset >= k_pos)
    if window is not None:
        mask = mask & (q_pos + offset - k_pos < window)
    return mask


def _band_live(iq, ik, block_q, block_k, offset, window):
    """Whether any pair of tile pair ``(iq, ik)`` of a causal grid lies in
    the band (traced scalars or numpy arrays: plain arithmetic).  Under a
    ``window`` the band has a second edge: a tile whose every key is
    ``window`` or more behind every query is dead too."""
    live = iq * block_q + block_q - 1 + offset >= ik * block_k
    if window is not None:
        # nearest pair of the block: first query, last key
        live = live & (iq * block_q + offset
                       - ((ik + 1) * block_k - 1) < window)
    return live


def _band_full(iq, ik, block_q, block_k, offset, window):
    """Whether every pair of tile pair ``(iq, ik)`` lies in the band: no
    edge crosses it (and no padding lies in it, the caller's to know)."""
    full = (ik + 1) * block_k - 1 <= iq * block_q + offset
    if window is not None:
        # farthest pair: last query, first key
        full = full & (iq * block_q + block_q - 1 + offset
                       - ik * block_k < window)
    return full


def _tile_state(window, iq, ik, block_q, block_k, offset, xp=jnp):
    """``(live, full)`` of tile pair ``(iq, ik)`` (traced scalars, or numpy
    arrays with ``xp=np``) under the causal half, a window or a mask
    form."""
    if isinstance(window, BlockDiffusion):
        return window.tile_state(iq * block_q, ik * block_k, block_q,
                                 block_k, xp=xp)
    return (_band_live(iq, ik, block_q, block_k, offset, window),
            _band_full(iq, ik, block_q, block_k, offset, window))


# A masked tile pair by sub-tiles (PR 62).  The kernels' dispatch knows a
# tile pair as dead, free or masked, and a masked one used to run whole: all
# of its block_q x block_k scores, every product, exponential and select,
# however little the edge leaves (half of a causal diagonal tile, 0.4 % of
# block diffusion's noisy x noisy tile at blocks of 4).  Where the live
# region of a masked tile pair is STATIC -- no padding, Tq == Tk, blocks
# that are whole sub-tiles, a window that is a multiple of the sub-tile,
# halves that are whole tiles under a mask form -- the tile pair is cut into
# sub x sub sub-tiles, classified at trace time by the same
# :func:`_tile_state` (dead, free, or an edge crosses it), and run as one
# slab a row of sub-tiles (forward and dQ pass: ``sub`` query rows against
# the run of key sub-tiles that are not dead; dK/dV and fused pass,
# transposed: ``sub`` key rows against the run of query sub-tiles), the mask
# on the slab's edge sub-tiles alone.  The sub-tile table of a masked tile
# pair is a function of the tile pair's KIND -- ``q0 - k0`` and, under a mask
# form, the halves its rows and columns lie in: the causal diagonal, a
# window's trailing edge (two kinds where the window is no multiple of the
# block), block diffusion's noisy x noisy, noisy x clean and clean x clean
# diagonals -- and a kind is a ``pl.when`` like free / masked.  Anything
# else keeps the whole-tile path.
# The sub-tile, one constant a kernel, from the sweep on a v5e (table above
# ``_FWD_DEFAULTS_D128``): 512 for the forward, 128 for the backward kernels.
_SUB_FWD = 512          # the forward's sub-tile; 0: every masked tile whole
_SUB_BWD = 128          # the backward kernels' (lane tiles of the sliced axis)
_DEAD, _FREE, _EDGE = 0, 1, 2
_MAX_KINDS = 4          # bodies traced a kernel: kinds x sub-tile rows


def _grid_state(window, block_q, block_k, tq, tk):
    """``(iq, ik, live, full)`` over one head's whole grid, in numpy: a
    column of query blocks, a row of key blocks and :func:`_tile_state` of
    every tile pair."""
    iq = np.arange(-(-tq // block_q), dtype=np.int64)[:, None]
    ik = np.arange(-(-tk // block_k), dtype=np.int64)[None, :]
    live, full = (np.broadcast_to(x, (iq.size, ik.size)) for x in _tile_state(
        window, iq, ik, block_q, block_k, tk - tq, np))
    return iq, ik, live, full


@functools.lru_cache(maxsize=None)
def _subtile_kinds(causal, window, block_q, block_k, tq, tk, offset, sub):
    """``None`` (every masked tile pair runs whole) or the kinds of masked
    tile pair of this grid, a tuple of ``(q_noisy, k_noisy, delta, table,
    n)``: the tile pairs with ``q0 - k0 == delta`` (and, under a mask form,
    rows and columns in those halves) are exactly ``n`` masked ones, and the
    ``sub x sub`` sub-tiles of each are ``table`` (``[block_q // sub,
    block_k // sub]`` of ``_DEAD`` / ``_FREE`` / ``_EDGE``).  Found by
    enumeration of the grid in numpy, so that nothing is assumed of a mask
    but :func:`_tile_state`: a key whose tile pairs disagree, or that names
    a free or a dead tile pair too, gives ``None``."""
    form = isinstance(window, BlockDiffusion)
    if not sub or not (form or causal) or offset or tq != tk or \
            tq % block_q or tk % block_k or block_q % sub or block_k % sub:
        return None
    if isinstance(window, int) and window % sub:
        return None
    half = window.half if form else 0
    if half % block_q or half % block_k:
        return None
    iq, ik, live, full = _grid_state(window, block_q, block_k, tq, tk)
    q0, k0 = iq * block_q, ik * block_k
    masked = live & ~full
    nr, nc = block_q // sub, block_k // sub
    r, c = np.arange(nr)[:, None], np.arange(nc)[None, :]
    kinds = {}
    for i, j in zip(*np.nonzero(masked)):
        q, k = int(q0[i, 0]), int(k0[0, j])
        sub_live, sub_full = _tile_state(window, i * nr + r, j * nc + c, sub,
                                         sub, 0, np)
        table = np.where(sub_full, _FREE, np.where(sub_live, _EDGE, _DEAD))
        seen = kinds.setdefault((q < half, k < half, q - k), [table, 0])
        if not np.array_equal(seen[0], table):
            return None
        seen[1] += 1
    for (qn, kn, delta), (table, n) in kinds.items():
        named = ((q0 < half) == qn) & ((k0 < half) == kn) & \
            (q0 - k0 == delta)
        if int(named.sum()) != n:
            return None
    if not kinds or len(kinds) > _MAX_KINDS or \
            all((table == _EDGE).all() for table, _ in kinds.values()):
        return None
    return tuple((qn, kn, delta, table, n)
                 for (qn, kn, delta), (table, n) in sorted(
                     kinds.items(), key=lambda kv: kv[0]))


def _slabs(table, sub):
    """The slabs a sub-tile table is run by: ``(r, lo, edges, rows, cols)``
    a row ``r`` of sub-tiles with any not dead, ``lo`` the first of their
    run and ``edges`` those of the run a mask runs on (a dead one between
    two live ones, which no mask here has, would be masked, not skipped),
    ``rows`` and ``cols`` the slab's slices of the tile."""
    for r, row in enumerate(np.asarray(table)):
        live = np.nonzero(row != _DEAD)[0]
        if live.size:
            lo, hi = int(live[0]), int(live[-1]) + 1
            yield (r, lo, tuple(c for c in range(lo, hi) if row[c] != _FREE),
                   slice(r * sub, (r + 1) * sub), slice(lo * sub, hi * sub))


def _mask_slab(x, sub, lo, edges, mask_of, fill):
    """``x``, a slab of sub-tiles ``lo, lo + 1, ..`` side by side along its
    last axis, with ``fill`` where ``mask_of(c)`` is false in each sub-tile
    ``c`` of ``edges``, the others untouched: the pieces are whole lane
    tiles, so cutting and joining them moves nothing."""
    if not edges:
        return x
    n = x.shape[-1] // sub
    pieces, start = [], 0
    for i in range(n):
        if lo + i in edges:
            if start < i:
                pieces.append(x[:, start * sub:i * sub])
            pieces.append(jnp.where(mask_of(lo + i),
                                    x[:, i * sub:(i + 1) * sub], fill))
            start = i + 1
    if start < n:
        pieces.append(x[:, start * sub:])
    return pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces, axis=-1)


def grid_tile_pairs(causal, window, block_q, block_k, tq, tk, pads=False):
    """``(free, masked, dead)``: the tile pairs of one head's grid by what
    :func:`_block_dispatch` does with them (``pads``: the call pads a
    length, and then no tile pair of a causal or unmasked grid runs
    mask-free)."""
    nq, nk = -(-tq // block_q), -(-tk // block_k)
    form = isinstance(window, BlockDiffusion)
    if not form and not causal:
        return (0, nq * nk, 0) if pads else (nq * nk, 0, 0)
    _, _, live, full = _grid_state(window, block_q, block_k, tq, tk)
    if pads and not form:
        full = np.zeros_like(live)
    return (int(full.sum()), int((live & ~full).sum()), int((~live).sum()))


def subtile_counts(causal, window, block_q, block_k, tq, tk, pads, sub):
    """``{"free", "masked", "skipped", "whole"}``: of the masked tile pairs
    of one head's grid at these blocks, their sub-tiles by what the kernels
    do with them at sub-tiles of ``sub``, and under ``whole`` the masked
    tile pairs that run whole (every one of a call whose live regions are
    not static; ``pads`` as :func:`grid_tile_pairs` reads it)."""
    kinds = None if pads else _subtile_kinds(
        causal, window, block_q, block_k, tq, tk, tk - tq, sub)
    counts = dict(free=0, masked=0, skipped=0, whole=0)
    if kinds is None:
        counts["whole"] = grid_tile_pairs(causal, window, block_q, block_k,
                                          tq, tk, pads)[1]
        return counts
    for *_, table, n in kinds:
        for state, code in (("free", _FREE), ("masked", _EDGE),
                            ("skipped", _DEAD)):
            counts[state] += n * int((table == code).sum())
    return counts


def _block_dispatch(causal, pads, iq, ik, block_q, block_k, offset,
                    compute, window=None, kinds=None):
    """The shared live/full block ladder (one definition for all three
    kernels): unpadded non-causal blocks take the mask-free path;
    unpadded causal grids run masks only on DIAGONAL blocks (fully-live
    blocks below the diagonal are mask-free, dead blocks above are
    skipped); any padding falls back to masked-everywhere.  With a
    ``window`` (causal only) the band has a second edge: blocks whose every
    key is ``window`` or more behind every query are dead too, and the mask
    also runs on the blocks that trailing edge crosses.  A mask form has a
    ladder of its own (:meth:`BlockDiffusion.dispatch`).  ``compute``
    receives masked: bool.  With ``kinds`` (:func:`_subtile_kinds`; no
    padding then) a masked tile pair runs ``compute(True, table)``, its
    kind's sub-tile table: one ``pl.when`` a kind."""
    from jax.experimental import pallas as pl

    if kinds is not None:
        form = isinstance(window, BlockDiffusion)
        q0, k0 = iq * block_q, ik * block_k
        _, full = _tile_state(window, iq, ik, block_q, block_k, offset)
        pl.when(full)(lambda: compute(False))
        for q_noisy, k_noisy, delta, table, _ in kinds:
            named = q0 - k0 == delta
            if form:
                half = window.half
                named = named & ((q0 < half) if q_noisy else (q0 >= half)) \
                    & ((k0 < half) if k_noisy else (k0 >= half))
            pl.when(named)(functools.partial(compute, True, table))
        return
    if isinstance(window, BlockDiffusion):
        window.dispatch(iq, ik, block_q, block_k, compute)
        return
    if not causal and not pads:
        compute(False)
        return
    if causal:
        live = _band_live(iq, ik, block_q, block_k, offset, window)
        if not pads:
            full = _band_full(iq, ik, block_q, block_k, offset, window)

            @pl.when(full)
            def _():
                compute(False)

            @pl.when(live & jnp.logical_not(full))
            def _():
                compute(True)
        else:
            @pl.when(live)
            def _():
                compute(True)
        return
    compute(True)


# One trace a distinct call (PR 62).  A step calls one kernel at one set of
# shapes once a layer and role (SDAR's: twelve forwards and six backwards
# of one call each), every call traced the kernel's body again, and a body
# run by sub-tiles is several times the equations of the whole-tile one:
# SDAR's ``first_step_program_s`` read 47.4 s for the parent's 16.4 with the
# backward at sub-tiles of 128.  The jaxpr of a call is kept by its
# arguments' types, every other argument's value and what a trace reads of
# this module (:func:`_trace_reads`), and bound again under the caller's
# own name stack (so a trace names each call's device operations by ITS
# scopes: nothing is a ``jit`` of its own).
_TRACED = {}
_TRACED_MOST = 256      # distinct calls kept; a step has a few dozen


def _trace_reads():
    """What a trace of a kernel call reads besides the call's arguments:
    every function, class and number this module's namespace and the mask
    form's class bind NOW (the kernels, the masks, the VMEM reckoning and
    its limits, the sub-tiles, ``on_tpu``) and JAX's precision and width
    defaults.  Part of the key of :func:`_traced_once`, so a call under a
    replaced one of them is traced anew and never served another's
    jaxpr."""
    bound = list(globals().values()) + list(vars(BlockDiffusion).values())
    return tuple(v for v in bound
                 if callable(v) or isinstance(v, (int, float))) + (
        jax.config.jax_default_matmul_precision, jax.config.jax_enable_x64)


def _traced_once(fn):
    """``fn`` (arrays and hashable values in, a tree of arrays out) traced
    once a process for each distinct call: the arrays' types, the other
    arguments and :func:`_trace_reads`.  Inside the executor's
    ``shard_map`` (``check_vma=False``) the types are a shard's on the
    map's mesh, a key of their own; under ``check_vma=True``
    ``pallas_call`` itself refuses these kernels, kept or not: their
    results name no ``vma``."""
    import inspect
    from jax.extend.core import jaxpr_as_fun
    sig = inspect.signature(fn)

    @functools.wraps(fn)
    def call(*args, **kw):
        bound = sig.bind(*args, **kw)
        bound.apply_defaults()
        arrays = {n: v for n, v in bound.arguments.items()
                  if hasattr(v, "dtype")}
        types = tuple((n, jax.typeof(v)) for n, v in arrays.items())
        statics = tuple((n, v) for n, v in bound.arguments.items()
                        if n not in arrays)
        key = (fn, types, statics, _trace_reads())
        if key not in _TRACED:
            if len(_TRACED) >= _TRACED_MOST:
                _TRACED.clear()
            closed, out = jax.make_jaxpr(
                lambda *xs: fn(**dict(statics), **dict(zip(arrays, xs))),
                return_shape=True)(*(
                    jax.ShapeDtypeStruct(t.shape, t.dtype,
                                         weak_type=t.weak_type)
                    for _, t in types))
            _TRACED[key] = jaxpr_as_fun(closed), jax.tree.structure(out)
        run, tree = _TRACED[key]
        return jax.tree.unflatten(tree, run(*arrays.values()))
    return call


def _kv_head(b, group):
    """The collapsed K/V row a collapsed query row ``b`` reads: with
    ``group`` query heads to a KV head, ``[batch * heads]`` collapses so
    that it is ``b // group``."""
    return b if group == 1 else b // group


def _live_k(i, j, window, block_q, block_k, offset, nk):
    """The key block grid step (query block ``i``, key step ``j``) names:
    ``j`` itself, or under a window the nearest block of ``i``'s band, so
    that the dead steps before and after the band name the block already
    resident and copy nothing; under a mask form the nearest live block of
    ``i``'s (:meth:`BlockDiffusion.live_k`)."""
    if isinstance(window, BlockDiffusion):
        return window.live_k(i, j, block_q, block_k)
    if window is None:
        return j
    lo = jnp.maximum(i * block_q + offset - (window - 1), 0) // block_k
    hi = jnp.minimum((i * block_q + block_q - 1 + offset) // block_k, nk - 1)
    return jnp.clip(j, lo, hi)


def _live_q(i, j, window, block_q, block_k, offset, nq):
    """:func:`_live_k` for the grid that walks query blocks ``i`` inside a
    key block ``j`` (the split backward's dk/dv pass)."""
    if isinstance(window, BlockDiffusion):
        return window.live_q(i, j, block_q, block_k)
    if window is None:
        return i
    lo = jnp.maximum(j * block_k - offset, 0) // block_q
    hi = jnp.minimum((window + (j + 1) * block_k - 2 - offset) // block_q,
                     nq - 1)
    return jnp.clip(i, lo, hi)


def _seen(window, q_pos, k_pos):
    """The blockwise jax paths' second mask: a window's trailing edge, or the
    mask form."""
    if isinstance(window, BlockDiffusion):
        return window.visible(q_pos, k_pos)
    return q_pos - k_pos < window


def _k_spec(block_q, block_k, d, window, group, offset, nk):
    """The K/V BlockSpec of a (bh, iq, ik) grid: the plain ``(b, j, 0)`` map
    (lowered as before the arguments existed) unless a window or grouped
    heads ask for :func:`_live_k` / :func:`_kv_head`."""
    from jax.experimental import pallas as pl
    if window is None and group == 1:
        return pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0))
    return pl.BlockSpec(
        (1, block_k, d), lambda b, i, j: (
            _kv_head(b, group),
            _live_k(i, j, window, block_q, block_k, offset, nk), 0))


# A v5e TensorCore's VMEM and the share of it one kernel may ask for.  Mosaic
# scopes a kernel to 16 MiB unless the call says otherwise (vmem_limit_bytes);
# the forward says what _fwd_vmem_bytes reckons from its shapes and the fused
# backward what _fused_vmem_bytes does, and where the latter passes the share
# (a head's dQ accumulator grows with Tq · d_qk) the split kernels run instead.
_VMEM_BYTES = 128 << 20
_FUSED_VMEM_SHARE = 0.75


def _lse_rows(block_q, tqp):
    """Whether the forward writes ``lse`` as ``[bh, 1, Tq]`` rows, lane-dense
    along ``Tq`` (the form the backward's dK/dV pass reads), or as the
    ``[bh, Tq, lanes]`` columns of its running-max scratch: rows wherever a
    query block fills whole lanes, or is the whole padded length and fills
    whole sublanes (both are shapes Mosaic transposes a (block_q, 128) tile
    at), columns at the ragged toy blocks that are left."""
    return block_q % _LANE == 0 or (block_q == tqp and block_q % 8 == 0)


def _fwd_kernel(q_ref, k_ref, v_ref, b_ref, o_ref, lse_ref,
                acc_sc, m_sc, l_sc, q_sc, *, sm_scale, causal, block_q,
                block_k, tk_real, offset, pads, window=None, lse_rows=False,
                rope=None, kinds=None):
    """One (bh, iq, ik) grid step of online-softmax attention.

    ``rope`` (trace-time, like ``b_ref``): ``(qr_ref, kr_ref, qr_sc)``, the
    score's second product.  The query block's rotary part is scaled once
    into ``qr_sc`` beside ``q_sc`` and every key step adds ``qr_sc · krᵀ``
    into the same float32 score tile: the sum of two float32 products of the
    same values is the one wide contraction in another order of addition.
    The rotary key's block comes through its own index map (one head for a
    group of query heads), so nothing is broadcast or concatenated in HBM.

    The grid walks ik innermost (sequentially on TPU), so the VMEM scratch
    carries a query block's state across its key blocks: the float32 output
    accumulator, the running max and denominator (a (block_q, 1) column
    each: one live lane's worth of loads, stores and rescaling a step, where
    the 128-lane broadcast of them cost 3.5 to 6.5 % of the kernel), and the
    query block itself, cast to float32 and scaled ONCE, at its first key
    step (sm_scale folds into q: a [bq, d] multiply, never a [bq, bk] one).  What a step does is
    its two products and the tile-wide softmax work, and nothing else where
    it can be told apart statically or by block: padding masks vanish when
    the sequence divides the blocks (``pads`` is a trace-time constant),
    the causal and window masks run only on the blocks an edge crosses, and
    blocks wholly outside the band are skipped (:func:`_block_dispatch`).
    A block an edge crosses is itself run by the sub-tiles the mask leaves
    live where they are static (``kinds``, :func:`_subtile_kinds`):
    ``_compute_sub`` takes ``sub`` query rows at a time against the run of
    key sub-tiles they can see, ONE score product, the mask on the slab's
    edge sub-tiles alone, one max / exp / sum / rescale over the slab's own
    rows and one ``p · V[slice]``; max, denominator and accumulator are per
    row, so a row is rescaled as often as before and sees the same keys in
    the same order.  Without ``kinds`` (padding, a bias, ``Tq != Tk``,
    ragged blocks) such a block runs all of its scores, as before PR 62.
    At the last key step the block's output is normalised and ``lse = m +
    log l`` leaves as a (1, block_q) row of ``[bh, 1, Tq]`` (``lse_rows``:
    the column transposed on the XLU, an exact move, one row stored) or, at
    the ragged blocks :func:`_lse_rows` leaves, as the lane-broadcast
    column.  What is left in it (float32 operands, the 192-wide
    contraction's two MXU passes): PERF.md section 7, row 18.
    """
    import jax.lax as lax
    from jax.experimental import pallas as pl

    iq, ik = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)
    qr_ref, kr_ref, qr_sc = rope or (None,) * 3

    @pl.when(ik == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)
        q_sc[...] = q_ref[0].astype(jnp.float32) * sm_scale
        if rope is not None:
            qr_sc[...] = qr_ref[0].astype(jnp.float32) * sm_scale

    def _compute(masked, table=None):
        if table is not None:
            return _compute_sub(table)
        k = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q_sc[...], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if rope is not None:
            s = s + jax.lax.dot_general(
                qr_sc[...], kr_ref[0].astype(jnp.float32),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
        if b_ref is not None:
            s = s + b_ref[0].astype(jnp.float32)
        if masked:
            s = jnp.where(_pos_mask(iq, ik, block_q, block_k, causal,
                                    offset, None, tk_real, window=window),
                          s, NEG_INF)
        m_prev = m_sc[...]                         # (bq, 1)
        l_prev = l_sc[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_sc[...] = acc_sc[...] * alpha + jax.lax.dot_general(
            p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_sc[...] = m_new
        l_sc[...] = l_new

    def _compute_sub(table):
        # the masked tile pair of one kind, a slab a row of sub-tiles: the
        # step above over ``sub`` query rows and the keys they can see
        sub = block_q // len(table)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        if rope is not None:
            kr = kr_ref[0].astype(jnp.float32)
        for r, lo, edges, rows, cols in _slabs(table, sub):
            s = jax.lax.dot_general(
                q_sc[rows, :], k[cols], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            if rope is not None:
                s = s + jax.lax.dot_general(
                    qr_sc[rows, :], kr[cols], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
            s = _mask_slab(s, sub, lo, edges, lambda c: _pos_mask(
                iq * len(table) + r, ik * (block_k // sub) + c, sub, sub,
                causal, offset, None, None, window=window), NEG_INF)
            m_prev = m_sc[rows, :]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_sc[rows, :] = alpha * l_sc[rows, :] + jnp.sum(
                p, axis=-1, keepdims=True)
            acc_sc[rows, :] = acc_sc[rows, :] * alpha + jax.lax.dot_general(
                p, v[cols], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_sc[rows, :] = m_new

    _block_dispatch(causal, pads, iq, ik, block_q, block_k, offset,
                    _compute, window=window, kinds=kinds)

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_sc[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)          # fully-masked rows
        o_ref[0] = (acc_sc[...] / l_safe).astype(o_ref.dtype)
        lse = m_sc[...] + jnp.log(l_safe)
        if lse_rows:
            lse_ref[0] = jnp.broadcast_to(lse, (block_q, _LANE)).T[:1]
        else:
            lse_ref[0] = jnp.broadcast_to(lse, lse_ref[0].shape)


def _lanes(w):
    """A minor dimension as VMEM holds it: whole 128-lane tiles."""
    return -(-w // _LANE) * _LANE


def _fwd_vmem_bytes(d, d_v, block_q, block_k, itemsize, bias_itemsize=0,
                    d_r=0):
    """The VMEM the forward asks for, from its shapes, as
    :func:`_fused_vmem_bytes` reckons the fused backward's: the operand and
    output blocks (double-buffered; ``lse``'s at the lane-broadcast form's
    size, the larger), the scratch (accumulator, float32 query block, max
    and denominator: a (block_q, 1) float32 column takes whole 128-lane
    tiles of VMEM all the same), the float32 tiles (s, p, the mask and what the
    reductions hold) and K's and V's float32 copies; a quarter more for
    what XLA fuses into the call inside a step.  Never under Mosaic's
    default 16 MiB (small blocks compiled into it before the call asked)
    and never over the share of a core's VMEM a kernel may ask for.  [32,
    8192, 192 | 128] bf16 asks 28.8 MiB at (1024, 1024), 51.9 at (1024,
    2048) and 54.4 at (2048, 1024), where 12, 20 and 24 are the least that
    compile alone (tools/joyai_kernel_probe.py --aot --forward).  ``d_r``:
    the rotary parts' width under the two-product score, whose blocks, scratch
    and copies lie beside Q's and K's at whole lanes (64 takes 128)."""
    wide = d + d_v + _lanes(d_r)
    blocks = 2 * (itemsize * wide * (block_q + block_k)
                  + bias_itemsize * block_q * block_k + block_q * _LANE * 4)
    scratch = block_q * (wide + 2 * _LANE) * 4
    tiles = 4 * block_q * block_k * 4 + block_k * wide * 4
    return min(max(int(1.25 * (blocks + scratch + tiles)), 16 << 20),
               int(_FUSED_VMEM_SHARE * _VMEM_BYTES))


@_traced_once
def _flash_fwd_pallas(q, k, v, bias, causal, sm_scale, block_q, block_k,
                      offset, interpret, window=None, group=1, q_rope=None,
                      k_rope=None):
    """Returns (o [bh,Tq,dv], lse [bh,Tq]) on padded collapsed inputs; K is
    [bh // group, Tk, d] and V [bh // group, Tk, dv]; ``q_rope`` [bh, Tq,
    d_r] and ``k_rope`` [bh // its own group, Tk, d_r] where the score is two
    products."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, tq, d = q.shape
    d_v = v.shape[2]
    tk = k.shape[1]
    tk_real = tk
    block_q = min(block_q, tq)
    block_k = min(block_k, tk)
    pad_q = (-tq) % block_q
    pad_k = (-tk) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0)))
    if bias is not None and (pad_q or pad_k):
        bias = jnp.pad(bias, ((0, 0), (0, pad_q), (0, pad_k)))
    tqp, tkp = tq + pad_q, tk + pad_k
    nq, nk = tqp // block_q, tkp // block_k

    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        _k_spec(block_q, block_k, d, window, group, offset, nk),
        _k_spec(block_q, block_k, d_v, window, group, offset, nk),
    ]
    args = [q, k, v]
    d_r = 0 if q_rope is None else q_rope.shape[2]
    if d_r:
        if pad_q:
            q_rope = jnp.pad(q_rope, ((0, 0), (0, pad_q), (0, 0)))
        if pad_k:
            k_rope = jnp.pad(k_rope, ((0, 0), (0, pad_k), (0, 0)))
        in_specs += [
            pl.BlockSpec((1, block_q, d_r), lambda b, i, j: (b, i, 0)),
            _k_spec(block_q, block_k, d_r, window, bh // k_rope.shape[0],
                    offset, nk)]
        args += [q_rope, k_rope]
    if bias is not None:
        nb = bias.shape[0]
        in_specs.append(pl.BlockSpec(
            (1, block_q, block_k),
            (lambda b, i, j: (b, i, j)) if nb > 1 else
            (lambda b, i, j: (0, i, j))))
        args.append(bias)

    lse_rows = _lse_rows(block_q, tqp)
    kinds = None if bias is not None or pad_q or pad_k else _subtile_kinds(
        causal, window, block_q, block_k, tq, tk, offset, _SUB_FWD)

    def kernel(q_ref, k_ref, v_ref, *rest):
        # rest = ([qr_ref, kr_ref,] [b_ref,] o_ref, lse_ref, acc, m, l, q32
        # [, qr32]) depending on the rotary parts and the bias
        rope = None
        if d_r:
            rope, rest = (rest[0], rest[1], rest[-1]), rest[2:-1]
        b_ref = rest[0] if bias is not None else None
        _fwd_kernel(q_ref, k_ref, v_ref, b_ref, *rest[-6:],
                    sm_scale=sm_scale, causal=causal,
                    block_q=block_q, block_k=block_k,
                    tk_real=tk_real, offset=offset,
                    pads=tkp != tk_real, window=window, lse_rows=lse_rows,
                    rope=rope, kinds=kinds)

    if lse_rows:
        lse_spec = pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i))
        lse_shape = (bh, 1, tqp)
    else:
        lane = min(_LANE, block_k)
        lse_spec = pl.BlockSpec((1, block_q, lane), lambda b, i, j: (b, i, 0))
        lse_shape = (bh, tqp, lane)
    o, lse = pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d_v), lambda b, i, j: (b, i, 0)),
            lse_spec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tqp, d_v), q.dtype),
            jax.ShapeDtypeStruct(lse_shape, jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d_v), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ] + ([pltpu.VMEM((block_q, d_r), jnp.float32)] if d_r else []),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_fwd_vmem_bytes(
                d, d_v, block_q, block_k, q.dtype.itemsize,
                0 if bias is None else bias.dtype.itemsize, d_r)),
        interpret=interpret,
        name="flash_fwd",
    )(*args)
    lse = lse.reshape(bh, tqp) if lse_rows else lse[..., 0]
    return o[:, :tq], lse[:, :tq]


# ---------------------------------------------------------------------------
# Pallas TPU kernels (backward): dq pass + dk/dv pass, FlashAttention-2
# recomputation from the saved logsumexp.  No O(T^2) tensor touches HBM.
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_sc, *, sm_scale, causal, block_q, block_k,
                   tq_real, tk_real, offset, pads, window=None, rope=None,
                   kinds=None):
    """Grid (bh, iq, ik): accumulate dq over k-blocks in VMEM scratch.
    Mask/scale elision as in _fwd_kernel (r5 skeleton microbench), and its
    slabs of sub-tiles under ``kinds``.  ``rope``: ``(qr_ref, kr_ref,
    dqr_ref, dqr_sc)``, the score's second product and the rotary query's
    gradient, ``ds · kr``, accumulated like dq."""
    import jax.lax as lax
    from jax.experimental import pallas as pl

    iq, ik = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)
    qr_ref, kr_ref, dqr_ref, dqr_sc = rope or (None,) * 4

    @pl.when(ik == 0)
    def _init():
        dq_sc[...] = jnp.zeros_like(dq_sc)
        if rope is not None:
            dqr_sc[...] = jnp.zeros_like(dqr_sc)

    def _compute_sub(table):
        # one kind of masked tile pair by slabs, as in _fwd_kernel
        sub = block_q // len(table)
        q = q_ref[0].astype(jnp.float32) * sm_scale
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]                             # (bq, 1)
        delta = delta_ref[0]
        if rope is not None:
            kr = kr_ref[0].astype(jnp.float32)
            qr = qr_ref[0].astype(jnp.float32) * sm_scale
        for r, lo, edges, rows, cols in _slabs(table, sub):
            s = lax.dot_general(q[rows], k[cols], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            if rope is not None:
                s = s + lax.dot_general(
                    qr[rows], kr[cols], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
            p = _mask_slab(
                jnp.exp(s - lse[rows]), sub, lo, edges,
                lambda c: _pos_mask(
                    iq * len(table) + r, ik * (block_k // sub) + c, sub,
                    sub, causal, offset, None, None, window=window), 0.0)
            dp = lax.dot_general(do[rows], v[cols], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
            ds = p * (dp - delta[rows])
            dq_sc[rows, :] = dq_sc[rows, :] + lax.dot_general(
                ds, k[cols], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            if rope is not None:
                dqr_sc[rows, :] = dqr_sc[rows, :] + lax.dot_general(
                    ds, kr[cols], (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)

    def _compute(masked, table=None):
        if table is not None:
            return _compute_sub(table)
        q = q_ref[0].astype(jnp.float32) * sm_scale
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]                             # (bq, 1)
        delta = delta_ref[0]
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        if rope is not None:
            kr = kr_ref[0].astype(jnp.float32)
            s = s + lax.dot_general(
                qr_ref[0].astype(jnp.float32) * sm_scale, kr,
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        if masked:
            s = jnp.where(_pos_mask(iq, ik, block_q, block_k, causal,
                                    offset, tq_real, tk_real,
                                    window=window), s, NEG_INF)
            p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - lse))
        else:
            p = jnp.exp(s - lse)
        dp = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dq_sc[...] = dq_sc[...] + lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if rope is not None:
            dqr_sc[...] = dqr_sc[...] + lax.dot_general(
                ds, kr, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    _block_dispatch(causal, pads, iq, ik, block_q, block_k, offset,
                    _compute, window=window, kinds=kinds)

    @pl.when(ik == nk - 1)
    def _finalize():
        dq_ref[0] = (dq_sc[...] * sm_scale).astype(dq_ref.dtype)
        if rope is not None:
            dqr_ref[0] = (dqr_sc[...] * sm_scale).astype(dqr_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *more, sm_scale, causal, block_q,
                    block_k, tq_real, tk_real, offset, pads, window=None,
                    rope=None, kinds=None):
    """Grid (bh, ik, iq): accumulate dk/dv over q-blocks in VMEM scratch
    (transposed tiles: everything is (bk, ·) so the MXU contractions stay
    tall).  Mask/scale elision as in _fwd_kernel (r5 microbench).  ``more``
    is the scratch ``(dk_sc, dv_sc)`` of the split backward's dk/dv pass,
    or ``(dq_ref, dk_sc, dv_sc, dq_sc)`` of the FUSED backward: the same
    pass, ONE recompute per live (i, j) pair, with a fifth product, dq's
    ds_tᵀ·k (the one contraction over a tile's first axis), into a (Tq,
    d_qk) float32 scratch that stays in VMEM for the whole head.  dq's
    output block is the head's whole dQ, one index for both inner axes, so
    it is written back once, at the head's last step.  5 MXU products a
    pair for the split kernels' 7 and no partials in HBM; operand types,
    precision and the order of both accumulations (dq over key blocks
    ascending, dk/dv over query blocks ascending) are the split kernels'
    own.  Fused, it needs more than Mosaic's default scoped VMEM:
    _fused_vmem_bytes.  ``rope``: ``(qr_ref, kr_ref, dkr_ref, dkr_sc,
    dqr_ref, dqr_sc)`` (the last two ``None`` in the split pass), the
    score's second product, ``kr · qrᵀ`` into the same tile, and the two
    small products ``ds_t`` feeds beside its others: ``dkr += ds_t · qr``,
    one partial a QUERY head, summed over the rotary key's group outside
    like a grouped K's, and ``dqr += ds_tᵀ · kr`` beside dq.  ``kinds``
    (:func:`_subtile_kinds`): a tile pair an edge crosses runs by slabs of
    ``sub`` KEY rows, each against the run of query sub-tiles that can see
    them (``[c · sub, block_q)`` on the causal diagonal): all five products,
    the ``lse`` / ``delta`` rows and dq's rows over that run alone, the mask
    on the probabilities of the edge sub-tiles; without them it runs all of
    its scores."""
    import jax.lax as lax
    from jax.experimental import pallas as pl

    dq_ref, dk_sc, dv_sc, dq_sc = more if len(more) == 4 else \
        (None, *more, None)
    ik, iq = pl.program_id(1), pl.program_id(2)
    nk, nq = pl.num_programs(1), pl.num_programs(2)

    qr_ref, kr_ref, dkr_ref, dkr_sc, dqr_ref, dqr_sc = rope or (None,) * 6

    if dq_sc is not None:
        @pl.when((ik == 0) & (iq == 0))
        def _init_dq():
            dq_sc[...] = jnp.zeros_like(dq_sc)
            if rope is not None:
                dqr_sc[...] = jnp.zeros_like(dqr_sc)

    @pl.when(iq == 0)
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)
        if rope is not None:
            dkr_sc[...] = jnp.zeros_like(dkr_sc)

    def _compute(masked, table=None):
        if table is not None:
            return _compute_sub(table)
        # sm_scale folds into q: s_t = k @ (q·scale) and
        # dk = ds_t @ (q·scale) each carry exactly one scale factor (dq
        # takes its factor on the accumulated head, at its end)
        q = q_ref[0].astype(jnp.float32) * sm_scale
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]                             # (1, bq)
        delta = delta_ref[0]
        s_t = lax.dot_general(k, q, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
        if rope is not None:
            qr = qr_ref[0].astype(jnp.float32) * sm_scale
            kr = kr_ref[0].astype(jnp.float32)
            s_t = s_t + lax.dot_general(
                kr, qr, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
        if masked:
            s_t = jnp.where(_pos_mask(iq, ik, block_q, block_k, causal,
                                      offset, tq_real, tk_real,
                                      transposed=True, window=window),
                            s_t, NEG_INF)
            p_t = jnp.where(s_t <= NEG_INF / 2, 0.0, jnp.exp(s_t - lse))
        else:
            p_t = jnp.exp(s_t - lse)
        dv_sc[...] = dv_sc[...] + lax.dot_general(
            p_t, do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp_t = lax.dot_general(v, do, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)
        ds_t = p_t * (dp_t - delta)
        dk_sc[...] = dk_sc[...] + lax.dot_general(
            ds_t, q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if dq_sc is not None:
            rows = pl.ds(pl.multiple_of(iq * block_q, block_q), block_q)
            dq_sc[rows, :] = dq_sc[rows, :] + lax.dot_general(
                ds_t, k, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        if rope is not None:
            dkr_sc[...] = dkr_sc[...] + lax.dot_general(
                ds_t, qr, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            if dq_sc is not None:
                dqr_sc[rows, :] = dqr_sc[rows, :] + lax.dot_general(
                    ds_t, kr, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)

    def _compute_sub(table):
        # the masked tile pair of one kind, a slab a COLUMN of sub-tiles
        # (the tiles are transposed): the step above over ``sub`` key rows
        # and the queries that can see them, dq's rows among them
        table = np.asarray(table).T
        sub = block_k // len(table)
        q = q_ref[0].astype(jnp.float32) * sm_scale
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        if rope is not None:
            qr = qr_ref[0].astype(jnp.float32) * sm_scale
            kr = kr_ref[0].astype(jnp.float32)
        for c, lo, edges, keys, cols in _slabs(table, sub):
            s_t = lax.dot_general(k[keys], q[cols], (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
            if rope is not None:
                s_t = s_t + lax.dot_general(
                    kr[keys], qr[cols], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
            # the mask on the probabilities: the visible ones are the whole
            # tile's bits, the others exactly 0 as there
            # the rows' slabs from the refs: a (1, bq) value cut at a lane
            # offset keeps the offset in its layout, and Mosaic broadcasts
            # no such row over sublanes
            p_t = _mask_slab(
                jnp.exp(s_t - lse_ref[0, :, cols]), sub, lo, edges,
                lambda r: _pos_mask(
                    iq * (block_q // sub) + r, ik * len(table) + c, sub, sub,
                    causal, offset, None, None, transposed=True,
                    window=window), 0.0)
            dv_sc[keys, :] = dv_sc[keys, :] + lax.dot_general(
                p_t, do[cols], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp_t = lax.dot_general(v[keys], do[cols], (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)
            ds_t = p_t * (dp_t - delta_ref[0, :, cols])
            dk_sc[keys, :] = dk_sc[keys, :] + lax.dot_general(
                ds_t, q[cols], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            if dq_sc is not None:
                rows = pl.ds(pl.multiple_of(iq * block_q + lo * sub, sub),
                             cols.stop - cols.start)
                dq_sc[rows, :] = dq_sc[rows, :] + lax.dot_general(
                    ds_t, k[keys], (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            if rope is not None:
                dkr_sc[keys, :] = dkr_sc[keys, :] + lax.dot_general(
                    ds_t, qr[cols], (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                if dq_sc is not None:
                    dqr_sc[rows, :] = dqr_sc[rows, :] + lax.dot_general(
                        ds_t, kr[keys], (((0,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)

    _block_dispatch(causal, pads, iq, ik, block_q, block_k, offset,
                    _compute, window=window, kinds=kinds)

    @pl.when(iq == nq - 1)
    def _finalize():
        dk_ref[0] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[...].astype(dv_ref.dtype)
        if rope is not None:
            dkr_ref[0] = dkr_sc[...].astype(dkr_ref.dtype)

    if dq_sc is not None:
        @pl.when((ik == nk - 1) & (iq == nq - 1))
        def _finalize_dq():
            dq_ref[0] = (dq_sc[...] * sm_scale).astype(dq_ref.dtype)
            if rope is not None:
                dqr_ref[0] = (dqr_sc[...] * sm_scale).astype(dqr_ref.dtype)


def _bwd_prologue(q, k, v, o, lse, do, block_q, block_k):
    """Shared pad/delta setup of the backward kernels."""
    bh, tq, d = q.shape
    tk = k.shape[1]
    block_q = min(block_q, tq)
    block_k = min(block_k, tk)
    pad_q = (-tq) % block_q
    pad_k = (-tk) % block_k
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)                                    # [bh, tq]
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0)))
        do = jnp.pad(do, ((0, 0), (0, pad_q), (0, 0)))
        lse = jnp.pad(lse, ((0, 0), (0, pad_q)))
        delta = jnp.pad(delta, ((0, 0), (0, pad_q)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0)))
    return (q, k, v, do, lse, delta, block_q, block_k,
            tq + pad_q, tk + pad_k)


def _fused_vmem_bytes(tq, d, d_v, block_q, block_k, itemsize, d_r=0):
    """The VMEM the fused backward asks for, from its shapes: the head's dQ
    accumulator and its output block, the operand and dK/dV blocks (every
    block double-buffered), the (1, block_q) lse/delta rows at a sublane
    tile each, the dK/dV scratch, the float32 tiles (s_t, p_t, dp_t, ds_t
    and ds_t transposed for dQ's first-axis contraction) and the operands'
    float32 copies; a quarter more for what XLA fuses into the call inside
    a step.  [32, 8192, 192 | 128] bf16 asks 34 MiB at (1024, 512) and 49.5
    at (1024, 1024), where 28 and 40 are the least that compile alone (20
    for the 43 asked at [32, 8192, 128 | 128] (1024, 1024): the compiler
    shares tiles this sum counts apart).  ``d_r``: the rotary parts' width
    under the two-product score: a second accumulator (dQRope's) and blocks,
    scratch and copies beside Q's and K's, each at whole lanes."""
    tq = -(-tq // block_q) * block_q
    wide = d + d_v + _lanes(d_r)
    acc = tq * (d + _lanes(d_r)) * (4 + 2 * itemsize)
    blocks = 2 * itemsize * wide * (block_q + 2 * block_k)
    rows = 2 * 2 * 8 * block_q * 4
    scratch = block_k * wide * 4
    tiles = 5 * block_q * block_k * 4 + (block_q + block_k) * wide * 4
    return int(1.25 * (acc + blocks + rows + scratch + tiles))


_BWD_IMPLS = ("fused", "split")


def _bwd_kernel_name(q, k, v, block_q, block_k, impl=None, d_r=0):
    """Which of the two Pallas backwards runs for collapsed ``q``, ``k``,
    ``v`` (anything with a shape and a dtype) at these blocks: the fused
    kernel where a head's dQ accumulator and the blocks fit
    ``_FUSED_VMEM_SHARE`` of a core's VMEM, else the split kernels, which
    fit everywhere.  ``impl="split"`` asks for the split kernels outright.
    ``d_r``: the rotary parts' width where the score is two products."""
    if impl == "split":
        return "split"
    tq, d = q.shape[1:]
    tk, d_v = k.shape[1], v.shape[2]
    block_q, block_k = min(block_q, tq), min(block_k, tk)
    need = _fused_vmem_bytes(tq, d, d_v, block_q, block_k,
                             jnp.dtype(q.dtype).itemsize, d_r)
    return "fused" if need <= _FUSED_VMEM_SHARE * _VMEM_BYTES else "split"


def _flash_bwd_pallas(q, k, v, o, lse, do, causal, sm_scale, block_q,
                      block_k, offset, interpret, impl=None, window=None,
                      group=1, q_rope=None, k_rope=None):
    name = _bwd_kernel_name(q, k, v, block_q, block_k, impl,
                            0 if q_rope is None else q_rope.shape[2])
    return _flash_bwd_pallas_split(q, k, v, o, lse, do, causal, sm_scale,
                                   block_q, block_k, offset, interpret,
                                   window, group, fused=name == "fused",
                                   q_rope=q_rope, k_rope=k_rope)


@_traced_once
def _flash_bwd_pallas_split(q, k, v, o, lse, do, causal, sm_scale, block_q,
                            block_k, offset, interpret, window=None,
                            group=1, fused=False, q_rope=None, k_rope=None):
    """(dq, dk, dv) via the dq pass and the dk/dv pass (no-bias path), or,
    ``fused``, via the dk/dv pass alone with the head's dq accumulated
    beside them: no HBM temporaries but delta and the lse view either way.
    The dk/dv pass writes one result per query head, summed over each KV
    head's ``group`` outside.  With ``q_rope`` / ``k_rope`` (the score as
    two products) the same passes take the two more operands and return
    ``(dq, dk, dv, dq_rope, dk_rope)``, the rotary key's per-head partials
    summed over ITS group (one head for all: every query head) in the same
    way."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, tq, d = q.shape
    d_v = v.shape[2]
    tk = k.shape[1]
    (q, k, v, do, lse, delta, block_q, block_k, tqp, tkp) = \
        _bwd_prologue(q, k, v, o, lse, do, block_q, block_k)
    nq, nk = tqp // block_q, tkp // block_k
    statics = dict(sm_scale=sm_scale, causal=causal, block_q=block_q,
                   block_k=block_k, tq_real=tq, tk_real=tk, offset=offset,
                   pads=tqp != tq or tkp != tk, window=window,
                   kinds=None if tqp != tq or tkp != tk else _subtile_kinds(
                       causal, window, block_q, block_k, tq, tk, offset,
                       _SUB_BWD))
    d_r = 0 if q_rope is None else q_rope.shape[2]
    if d_r:
        rope_group = bh // k_rope.shape[0]
        if tqp != tq:
            q_rope = jnp.pad(q_rope, ((0, 0), (0, tqp - tq), (0, 0)))
        if tkp != tk:
            k_rope = jnp.pad(k_rope, ((0, 0), (0, tkp - tk), (0, 0)))

    if not fused:
        # lse/delta ride as [bh, tq, 1]: block (1, block_q, 1) keeps the
        # last dim equal to the array's (mosaic tiling constraint)
        def q_spec_q(w):
            return pl.BlockSpec((1, block_q, w), lambda b, i, j: (b, i, 0))

        def k_spec_q(w):
            return _k_spec(block_q, block_k, w, window, group, offset, nk)
        row_spec_q = pl.BlockSpec((1, block_q, 1),
                                  lambda b, i, j: (b, i, 0))
        dq_in = [q_spec_q(d), k_spec_q(d), k_spec_q(d_v), q_spec_q(d_v),
                 row_spec_q, row_spec_q]
        dq_args = [q, k, v, do, lse[..., None], delta[..., None]]
        dq_kernel = functools.partial(_bwd_dq_kernel, **statics)
        dq_out = q_spec_q(d)
        dq_shape = jax.ShapeDtypeStruct((bh, tqp, d), q.dtype)
        dq_scratch = [pltpu.VMEM((block_q, d), jnp.float32)]
        if d_r:
            def dq_kernel(*refs):
                # six operands, then (qr, kr | dq, dqr | dq_sc, dqr_sc)
                qr, kr, dq_ref, dqr_ref, dq_sc, dqr_sc = refs[6:]
                _bwd_dq_kernel(*refs[:6], dq_ref, dq_sc, **statics,
                               rope=(qr, kr, dqr_ref, dqr_sc))
            dq_in += [q_spec_q(d_r), _k_spec(block_q, block_k, d_r, window,
                                             rope_group, offset, nk)]
            dq_args += [q_rope, k_rope]
            dq_out = [dq_out, q_spec_q(d_r)]
            dq_shape = [dq_shape, jax.ShapeDtypeStruct((bh, tqp, d_r),
                                                       q_rope.dtype)]
            dq_scratch.append(pltpu.VMEM((block_q, d_r), jnp.float32))
        dq = pl.pallas_call(
            dq_kernel,
            grid=(bh, nq, nk),
            in_specs=dq_in,
            out_specs=dq_out,
            out_shape=dq_shape,
            scratch_shapes=dq_scratch,
            interpret=interpret,
            name="flash_bwd_dq",
        )(*dq_args)
        if d_r:
            dq, dq_rope = dq

    # dk/dv pass: grid iterates q innermost per k-block; lse/delta ride
    # TRANSPOSED [bh, 1, tq] so the kernel reads (1, bq) rows directly (a
    # [bh, tq, 1] array is tiled to 128 lanes in HBM, 134 MB each at [32,
    # 8192], and its (bq, 1) blocks to as many in VMEM).  The plain maps
    # lower as before the window and the groups existed
    if window is None and group == 1:
        def iq_of(i, j):
            return i
    else:
        def iq_of(i, j):
            return _live_q(i, j, window, block_q, block_k, offset, nq)

    def q_spec(w):
        return pl.BlockSpec((1, block_q, w),
                            lambda b, j, i: (b, iq_of(i, j), 0))

    def k_spec(w):
        return pl.BlockSpec((1, block_k, w),
                            lambda b, j, i: (_kv_head(b, group), j, 0))

    def out_spec(w):
        return pl.BlockSpec((1, block_k, w), lambda b, j, i: (b, j, 0))
    row_spec = pl.BlockSpec((1, 1, block_q),
                            lambda b, j, i: (b, 0, iq_of(i, j)))
    in_specs = [q_spec(d), k_spec(d), k_spec(d_v), q_spec(d_v), row_spec,
                row_spec]
    args = [q, k, v, do, lse[:, None, :], delta[:, None, :]]
    out_specs = [out_spec(d), out_spec(d_v)]
    out_shape = [jax.ShapeDtypeStruct((bh, tkp, d), k.dtype),
                 jax.ShapeDtypeStruct((bh, tkp, d_v), v.dtype)]
    scratch = [pltpu.VMEM((block_k, d), jnp.float32),
               pltpu.VMEM((block_k, d_v), jnp.float32)]
    extra = {}
    if fused:
        # the head's whole dQ: one block for every (ik, iq) of a head
        def head_spec(w):
            return pl.BlockSpec((1, tqp, w), lambda b, j, i: (b, 0, 0))
        out_specs.append(head_spec(d))
        out_shape.append(jax.ShapeDtypeStruct((bh, tqp, d), q.dtype))
        scratch.append(pltpu.VMEM((tqp, d), jnp.float32))
        extra["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=_fused_vmem_bytes(
                tqp, d, d_v, block_q, block_k, q.dtype.itemsize, d_r))
    kernel = functools.partial(_bwd_dkv_kernel, **statics)
    if d_r:
        # the rotary operands, results and scratch behind the others of
        # their kind: dKRope a partial per QUERY head, dQRope the head's
        in_specs += [q_spec(d_r), pl.BlockSpec(
            (1, block_k, d_r),
            lambda b, j, i: (_kv_head(b, rope_group), j, 0))]
        args += [q_rope, k_rope]
        out_specs.append(out_spec(d_r))
        out_shape.append(jax.ShapeDtypeStruct((bh, tkp, d_r), k_rope.dtype))
        scratch.append(pltpu.VMEM((block_k, d_r), jnp.float32))
        if fused:
            out_specs.append(head_spec(d_r))
            out_shape.append(
                jax.ShapeDtypeStruct((bh, tqp, d_r), q_rope.dtype))
            scratch.append(pltpu.VMEM((tqp, d_r), jnp.float32))

        def kernel(*refs):
            qr, kr = refs[6:8]
            if fused:
                (dk, dv, dq, dkr, dqr, dk_sc, dv_sc, dq_sc, dkr_sc,
                 dqr_sc) = refs[8:]
                more = (dq, dk_sc, dv_sc, dq_sc)
            else:
                dk, dv, dkr, dk_sc, dv_sc, dkr_sc = refs[8:]
                more, dqr, dqr_sc = (dk_sc, dv_sc), None, None
            _bwd_dkv_kernel(*refs[:6], dk, dv, *more, **statics,
                            rope=(qr, kr, dkr, dkr_sc, dqr, dqr_sc))
    dk, dv, *more = pl.pallas_call(
        kernel,
        grid=(bh, nk, nq),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
        name="flash_bwd_fused" if fused else "flash_bwd_dkv",
        **extra,
    )(*args)
    if fused:
        dq, *more = more
    if group > 1:
        dk = dk.reshape(bh // group, group, tkp, d).astype(
            jnp.float32).sum(axis=1).astype(k.dtype)
        dv = dv.reshape(bh // group, group, tkp, d_v).astype(
            jnp.float32).sum(axis=1).astype(v.dtype)
    if not d_r:
        return dq[:, :tq], dk[:, :tk], dv[:, :tk]
    dk_rope = more[0]
    if fused:
        dq_rope = more[1]
    if rope_group > 1:
        dk_rope = dk_rope.reshape(bh // rope_group, rope_group, tkp, d_r
                                  ).astype(jnp.float32).sum(axis=1).astype(
                                      k_rope.dtype)
    return (dq[:, :tq], dk[:, :tk], dv[:, :tk], dq_rope[:, :tq],
            dk_rope[:, :tk])


# ---------------------------------------------------------------------------
# Blockwise JAX fallback (same math, lax.scan over k-blocks)
# ---------------------------------------------------------------------------

def _rope_chunks(q_rope, k_rope, bh, pad_k, nk, block_k):
    """The jax fallbacks' rotary operands: the query part in float32 and the
    rotary key repeated over its group, padded and cut into K's chunks."""
    kr = jnp.repeat(k_rope, bh // k_rope.shape[0], axis=0)
    if pad_k:
        kr = jnp.pad(kr, ((0, 0), (0, pad_k), (0, 0)))
    return q_rope.astype(jnp.float32), kr.reshape(
        bh, nk, block_k, kr.shape[2]).transpose(1, 0, 2, 3)


def _flash_fwd_jax(q, k, v, bias, causal, sm_scale, block_k, offset,
                   window=None, group=1, q_rope=None, k_rope=None):
    """(o, lse) via scan over k chunks — O(T*block_k) memory on any backend.
    No block is skipped here (this path runs where no TPU is): a window is
    a mask, and grouped K/V heads (and a rotary key's) are repeated."""
    if group > 1:
        k, v = jnp.repeat(k, group, axis=0), jnp.repeat(v, group, axis=0)
    bh, tq, d = q.shape
    d_v = v.shape[2]
    tk = k.shape[1]
    block_k = min(block_k, tk)
    pad_k = (-tk) % block_k
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0)))
        if bias is not None:
            bias = jnp.pad(bias, ((0, 0), (0, 0), (0, pad_k)),
                           constant_values=NEG_INF)
    nk = (tk + pad_k) // block_k
    kc = k.reshape(bh, nk, block_k, d).transpose(1, 0, 2, 3)
    vc = v.reshape(bh, nk, block_k, d_v).transpose(1, 0, 2, 3)
    if bias is not None:
        bc = bias.reshape(bias.shape[0], tq, nk, block_k
                          ).transpose(2, 0, 1, 3)
    q32 = q.astype(jnp.float32)
    q_pos = offset + jnp.arange(tq)[:, None]
    if q_rope is not None:
        qr32, krc = _rope_chunks(q_rope, k_rope, bh, pad_k, nk, block_k)

    def step(carry, xs):
        m_prev, l_prev, acc = carry
        if q_rope is not None:
            *xs, krj = xs
        if bias is not None:
            kj, vj, bj, j = xs
        else:
            kj, vj, j = xs
        s = jnp.einsum("bqd,bkd->bqk", q32, kj.astype(jnp.float32))
        if q_rope is not None:
            s = s + jnp.einsum("bqd,bkd->bqk", qr32,
                               krj.astype(jnp.float32))
        s = s * sm_scale
        if bias is not None:
            s = s + bj.astype(jnp.float32)
        k_pos = j * block_k + jnp.arange(block_k)[None, :]
        mask = k_pos < tk
        if causal:
            mask = mask & (q_pos >= k_pos)
        if window is not None:
            mask = mask & _seen(window, q_pos, k_pos)
        s = jnp.where(mask[None], s, NEG_INF)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum("bqk,bkd->bqd", p,
                                       vj.astype(jnp.float32))
        return (m_new, l_new, acc), None

    # zero derived from the inputs so the carry inherits their device-
    # varying type under shard_map (scan carries must type-match)
    zero = (q32[0, 0, 0] + k[0, 0, 0].astype(jnp.float32)) * 0.0
    init = (jnp.full((bh, tq, 1), NEG_INF, jnp.float32) + zero,
            jnp.zeros((bh, tq, 1), jnp.float32) + zero,
            jnp.zeros((bh, tq, d_v), jnp.float32) + zero)
    xs = (kc, vc, bc, jnp.arange(nk)) if bias is not None else \
         (kc, vc, jnp.arange(nk))
    if q_rope is not None:
        xs = xs + (krc,)
    (m, l, acc), _ = jax.lax.scan(step, init, xs)
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o = (acc / l_safe).astype(q.dtype)
    lse = (m + jnp.log(l_safe))[..., 0]
    return o, lse


def _flash_bwd_jax(q, k, v, bias, o, lse, do, causal, sm_scale, block_k,
                   offset, delta=None, need_dbias=True, window=None,
                   group=1, q_rope=None, k_rope=None):
    """Flash backward: scan over k chunks rebuilding P from saved lse.

    dq accumulates across chunks; dk/dv are emitted per chunk (stacked by
    scan) — memory stays O(T*block_k).  ``window``/``group`` as in
    :func:`_flash_fwd_jax`; dk and dv come back summed over each group.
    With ``q_rope`` / ``k_rope`` the result is ``(dq, dk, dv, db, dq_rope,
    dk_rope)``, the rotary key's gradient summed over its own group.
    """
    def fold(g, group):
        return g.astype(jnp.float32).reshape(
            (-1, group) + g.shape[1:]).sum(axis=1).astype(g.dtype)
    if group > 1:
        dq, dk, dv, *more = _flash_bwd_jax(
            q, jnp.repeat(k, group, axis=0), jnp.repeat(v, group, axis=0),
            bias, o, lse, do, causal, sm_scale, block_k, offset, delta,
            need_dbias, window, 1, q_rope, k_rope)
        return (dq, fold(dk, group), fold(dv, group), *more)
    bh, tq, d = q.shape
    d_v = v.shape[2]
    tk = k.shape[1]
    block_k = min(block_k, tk)
    pad_k = (-tk) % block_k
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0)))
        if bias is not None:
            bias = jnp.pad(bias, ((0, 0), (0, 0), (0, pad_k)),
                           constant_values=NEG_INF)
    nk = (tk + pad_k) // block_k
    kc = k.reshape(bh, nk, block_k, d).transpose(1, 0, 2, 3)
    vc = v.reshape(bh, nk, block_k, d_v).transpose(1, 0, 2, 3)
    if bias is not None:
        bc = bias.reshape(bias.shape[0], tq, nk, block_k
                          ).transpose(2, 0, 1, 3)
    q32 = q.astype(jnp.float32)
    do32 = do.astype(jnp.float32)
    if delta is None:
        delta = jnp.sum(do32 * o.astype(jnp.float32), axis=-1)  # [bh, tq]
    q_pos = offset + jnp.arange(tq)[:, None]
    if q_rope is not None:
        qr32, krc = _rope_chunks(q_rope, k_rope, bh, pad_k, nk, block_k)

    def step(dq_acc, xs):
        if q_rope is not None:
            dq_acc, dqr_acc = dq_acc
            *xs, krj = xs
            krj32 = krj.astype(jnp.float32)
        if bias is not None:
            kj, vj, bj, j = xs
        else:
            kj, vj, j = xs
        kj32, vj32 = kj.astype(jnp.float32), vj.astype(jnp.float32)
        s = jnp.einsum("bqd,bkd->bqk", q32, kj32)
        if q_rope is not None:
            s = s + jnp.einsum("bqd,bkd->bqk", qr32, krj32)
        s = s * sm_scale
        if bias is not None:
            s = s + bj.astype(jnp.float32)
        k_pos = j * block_k + jnp.arange(block_k)[None, :]
        mask = k_pos < tk
        if causal:
            mask = mask & (q_pos >= k_pos)
        if window is not None:
            mask = mask & _seen(window, q_pos, k_pos)
        s = jnp.where(mask[None], s, NEG_INF)
        # true softmax from saved lse; guard fully-masked rows (lse=-inf)
        p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - lse[..., None]))
        dv_j = jnp.einsum("bqk,bqd->bkd", p, do32)
        dp = jnp.einsum("bqd,bkd->bqk", do32, vj32)
        ds = p * (dp - delta[..., None])                   # dL/ds_ij
        dq_acc = dq_acc + sm_scale * jnp.einsum("bqk,bkd->bqd", ds, kj32)
        dk_j = sm_scale * jnp.einsum("bqk,bqd->bkd", ds, q32)
        outs = (dk_j, dv_j)
        if bias is not None and need_dbias:
            nb = bias.shape[0]
            outs += (ds if nb == q.shape[0] else
                     jnp.sum(ds, axis=0, keepdims=True),)
        if q_rope is not None:
            dq_acc = (dq_acc, dqr_acc + sm_scale * jnp.einsum(
                "bqk,bkd->bqd", ds, krj32))
            outs += (sm_scale * jnp.einsum("bqk,bqd->bkd", ds, qr32),)
        return dq_acc, outs

    xs = (kc, vc, bc, jnp.arange(nk)) if bias is not None else \
         (kc, vc, jnp.arange(nk))
    zero = (q32[0, 0, 0] + k[0, 0, 0].astype(jnp.float32)
            + do32[0, 0, 0]) * 0.0
    init = jnp.zeros((bh, tq, d), jnp.float32) + zero
    if q_rope is not None:
        xs = xs + (krc,)
        init = (init, jnp.zeros(qr32.shape, jnp.float32) + zero)
    dq, outs = jax.lax.scan(step, init, xs)
    if q_rope is not None:
        (dq, dqr), (*outs, dkrc) = dq, outs
    if bias is not None and need_dbias:
        dkc, dvc, dbc = outs
    else:
        dkc, dvc = outs
        dbc = None
    dk = dkc.transpose(1, 0, 2, 3).reshape(bh, tk + pad_k, d)[:, :tk]
    dv = dvc.transpose(1, 0, 2, 3).reshape(bh, tk + pad_k, d_v)[:, :tk]
    db = None
    if dbc is not None:
        db = dbc.transpose(1, 2, 0, 3).reshape(
            bias.shape[0], tq, tk + pad_k)[:, :, :tk]
    if q_rope is None:
        return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
                db)
    dkr = dkrc.transpose(1, 0, 2, 3).reshape(bh, tk + pad_k, -1)[:, :tk]
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype), db,
            dqr.astype(q_rope.dtype),
            fold(dkr.astype(k_rope.dtype), bh // k_rope.shape[0]))


# ---------------------------------------------------------------------------
# Public custom-vjp op
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(4, 5, 6, 7, 8, 9, 10, 11, 12))
def _flash(q, k, v, bias, causal, sm_scale, block_q, block_k, bwd_blocks,
           bwd_impl, interpret, window=None, group=1, q_rope=None,
           k_rope=None):
    o, _ = _flash_fwd(q, k, v, bias, causal, sm_scale, block_q, block_k,
                      interpret, window, group, q_rope, k_rope)
    return o


def _flash_fwd(q, k, v, bias, causal, sm_scale, block_q, block_k, interpret,
               window=None, group=1, q_rope=None, k_rope=None):
    # end-aligned causal mask (matches jnp.tril(k=tk-tq)): the last query
    # attends to every key — the KV-cache decode convention
    offset = k.shape[1] - q.shape[1]
    rope = {} if q_rope is None else dict(q_rope=q_rope, k_rope=k_rope)
    if on_tpu() or interpret:
        return _flash_fwd_pallas(q, k, v, bias, causal, sm_scale,
                                 block_q, block_k, offset, interpret,
                                 window, group, **rope)
    return _flash_fwd_jax(q, k, v, bias, causal, sm_scale, block_k, offset,
                          window, group, **rope)


def _flash_vjp_fwd(q, k, v, bias, causal, sm_scale, block_q, block_k,
                   bwd_blocks, bwd_impl, interpret, window=None, group=1,
                   q_rope=None, k_rope=None):
    o, lse = _flash_fwd(q, k, v, bias, causal, sm_scale, block_q, block_k,
                        interpret, window, group, q_rope, k_rope)
    return o, (q, k, v, bias, o, lse, q_rope, k_rope)


def _flash_vjp_bwd(causal, sm_scale, block_q, block_k, bwd_blocks,
                   bwd_impl, interpret, window, group, res, do,
                   need_dbias=True):
    """``(dq, dk, dv, dbias, dq_rope, dk_rope)``: one cotangent an operand of
    :func:`_flash`, the last two ``None`` where the score is one product."""
    q, k, v, bias, o, lse, q_rope, k_rope = res
    offset = k.shape[1] - q.shape[1]
    bq_b, bk_b = bwd_blocks if bwd_blocks is not None else (block_q, block_k)
    rope = {} if q_rope is None else dict(q_rope=q_rope, k_rope=k_rope)
    if bias is None and (on_tpu() or interpret):
        dq, dk, dv, *dr = _flash_bwd_pallas(q, k, v, o, lse, do, causal,
                                            sm_scale, bq_b, bk_b, offset,
                                            interpret, impl=bwd_impl,
                                            window=window, group=group,
                                            **rope)
        return (dq, dk, dv, None, *(dr or (None, None)))
    dq, dk, dv, db, *dr = _flash_bwd_jax(q, k, v, bias, o, lse, do, causal,
                                         sm_scale, bk_b, offset,
                                         need_dbias=need_dbias,
                                         window=window, group=group, **rope)
    return (dq, dk, dv, db, *(dr or (None, None)))


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


# End-to-end-validated block defaults per sequence length (r4 sweep,
# LONGCTX_ABLATION.md).  Keys are max(Tq, Tk); anything else takes the
# (512, 1024) baseline.  Every backward row is a block pair for the fused
# kernel (``_bwd_kernel_name`` falls back to split where it does not fit).
# re-swept IN-GRAPH after the r5 mask/scale elision (the r4 optima moved:
# wide 2048 k-blocks now win the non-causal fwd at 4k/8k — less per-block
# bookkeeping per element once the masks are gone; measured e2e on v5e:
# 4k 275→267 ms, 8k 436→422 ms, 16k 693→681 ms; the 2k causal table
# re-validated unchanged)
# 2048, 4096, 8192 at d <= 64: the backward rows are the r4 sweep's for the
# "combined" kernel (one recompute, dK/dV as float32 partials per query block
# in HBM, summed outside), which went in PR 44: on a v5e at causal [1, 16 | 32
# over 16 | 8, T, 64] bf16 (tools/trinity_kernel_probe.py --head_dim 64
# --window 0, PR 44; backward ms = forward + backward less the forward, 16
# heads / 32 over 8), fused against combined at the row's blocks:
#   2048 (1024, 512)   0.437 / 0.870 against 0.493 / 1.217 (split 0.603 / 1.243)
#   4096 (1024, 1024)  1.321 / 2.958 against 1.968 / 4.244 (split 1.981 / 4.128)
#   8192 (1024, 512)   4.884 / 10.57 against 7.776 / 15.59 and 1.3 / 2.6 GB
#                      of partials (split at (1024, 1024) 7.086 / 15.36)
# and fused at (1024, 1024) and (512, 512) within 4 % of the row's blocks at
# 4096 and 8192 (at 2048 (512, 512) reads 0.386 / 0.760: not taken, no step
# has run it).  At 1024 (no row: the baseline's blocks; 64 passes chained in
# one jit, since a single call is mostly the host's dispatch; forward +
# backward ms) fused 0.242 / 0.472 against 0.278 / 0.519, at batch 8 1.877 /
# 3.971 against 2.359 / 5.929; at (1024, 1024) blocks and batch 1 the two
# tie within 1 % (0.244 / 0.472 against 0.247 / 0.477).
# 16384, re-swept on a v5e at causal [32, 16384, 64] bf16 over 8 K/V heads,
# groups of 4 (tools/trinity_kernel_probe.py --seq 16384 --heads 32
# --kv_heads 8 --head_dim 64 --window 0, PR 40; the r4 sweep had every head
# its own K/V and no fused backward): forward (1024, 1024) 18.38 ms, (1024,
# 2048) 19.01, (512, 2048) 21.19 (the r4 row), (512, 1024) 21.46.  Backward
# (forward + backward less the forward): fused (1024, 1024) 33.6 to 34.8,
# (512, 1024) 34.97, (2048, 512) 39.61, (1024, 512) 39.05, and (512, 512)
# runs out of VMEM; split (1024, 1024) 51.08, (512, 1024) 53.82, (1024, 512)
# 56.17, (512, 512) 60.07 ("combined" would have kept 4.3 GB of partials:
# past its budget, it was the split kernels).  A head's [16384, 64] dQ
# accumulator and the (1024, 1024) blocks ask for 40.8 MiB of VMEM
# (_fused_vmem_bytes).  Against the dense-mask oracle: o 2.1e-3, dq 2.5e-3,
# dk 3.0e-3, dv 2.4e-3.
_FWD_DEFAULTS = {2048: (1024, 1024), 4096: (512, 2048),
                 8192: (512, 2048), 16384: (1024, 1024)}
_BWD_DEFAULTS = {2048: (1024, 512), 4096: (1024, 1024), 8192: (1024, 512),
                 16384: (1024, 1024)}
# head_dim 128 (64 < d <= 128), swept on a v5e at causal [64, 4096, 128]
# bf16 (tools/olmoe_kernel_sweep.py, PRs 27 and 37): forward (1024, 1024)
# 3.17 ms against the (512, 1024) baseline's 4.01; fused (1024, 1024) alone,
# forward (512, 1024) + backward, 9.85 ms (combined (1024, 512) 12.77, split
# (1024, 1024) 12.87; fused (512, 1024) 10.03, (512, 512) 10.20, (1024, 512)
# 10.28, 2048-wide 10.8 to 11.0); in OLMoE's step 19.41 samples/s against
# combined's 19.14 and 133 MB less at the peak (my chip runs, PR 37).
# Lengths other than 4096, 8192 and 16384 at this width keep the baseline
# until they are swept.
# 8192, on a v5e at [32, 8192, 128] bf16 over 4 KV heads
# (tools/trinity_kernel_probe.py, PRs 32 and 37; backward ms = forward +
# backward less the forward): forward (1024, 1024) 5.45 ms full and 3.57
# under a window of 2048 ((512, 1024) 7.08 / 4.46, (512, 512) 11.3 / 6.4).
# Full backward: fused (1024, 1024) 10.10, (512, 1024) 10.43, (1024, 512)
# 10.79, (512, 512) 11.19, (2048, 512) 11.47, (1024, 256) 12.31 (combined
# (1024, 512) 15.19 with 2.15 GB of partials; split (1024, 512) 15.42).
# Under the window: fused (1024, 1024) 7.11, (512, 512) 7.25, (512, 1024)
# 7.31, (1024, 512) 7.61, (256, 1024) 8.04, (1024, 256) 8.81, (256, 256)
# 14.57 (combined 12.6; split (512, 512) 10.73).  In Trinity's step (the
# window alone, samples/s): full (1024, 1024) with the window at (512, 512)
# 3.941, at (1024, 1024) 3.939, at (512, 1024) 3.927; full (1024, 512)
# 3.926; the split kernels 3.642.  The two best differ by less than a seed
# does (0.3 %): the window keeps the smaller blocks, which hug the band and
# ask for 20 MiB of VMEM, not 43.
# 16384, on a v5e at [28, 16384, 128] bf16 over 4 KV heads, groups of 7
# (tools/trinity_kernel_probe.py --seq 16384 --heads 28 --window 4096, PR 38;
# ms, under the window of 4096 / full): forward (1024, 1024) 9.70 / 17.69,
# (1024, 2048) 11.13 / 17.19, (512, 2048) 12.37 / 20.18, (512, 1024) 12.38 /
# 22.82, (2048, 512) 16.45 / 27.62, (1024, 512) 18.13 / 33.91, and (2048,
# 1024) runs out of VMEM; the forward table does not know the window, and
# three window layers to one full make (1024, 1024) the better row by 8 %.
# Backward: fused (1024, 1024) 19.26 / 32.30, (512, 1024) 21.27 / 33.80,
# (1024, 512) 21.68 / 36.07, (512, 2048) 22.89 / 33.04, (512, 512) 23.80 /
# 38.31, (256, 1024) 25.67 / 39.79; split (1024, 512) 31.36 / 50.43, (512,
# 512) 34.90 / 57.35 (combined: 15 GB of partials, so it was the split
# kernels).  A head's dQ accumulator and the (1024, 1024) blocks ask
# for 52.7 MiB of VMEM (_fused_vmem_bytes), of the 96 the fused backward
# may ask for.  At 16384 the band of 4096 is four blocks of 1024 wide, and
# the larger blocks win under the window too (at 8192 the band of 2048 was
# two).  In SmallThinker's step (the window alone, samples/s, one seed):
# the rows as shipped 2.146; the window's backward at (512, 512) 2.108, at
# (512, 1024) stalled, no reading; forward (1024, 2048) 2.132; split at (1024,
# 512) for both kinds of layer 1.925.
# The sub-tile a masked tile pair is run by (``_SUB_FWD``, ``_SUB_BWD``; PR 62),
# swept on a v5e at the rows' own blocks, bf16, ms a call, forward | fused
# backward (forward + backward less the forward), "off" the kernels before
# PR 62 (tools/trinity_kernel_probe.py --subs 0,128,256,512; my chip run, PR
# 62; a second run of every row but "off" read within 0.04 ms of this one):
#                                           off            128            256            512
#   block diffusion 4, [32 over 4, 16384]   12.20 | 22.61  11.65 | 19.65  11.78 | 19.85  11.58 | 20.77
#   causal [32 over 4, 16384, 128]          18.95 | 35.73  19.06 | 33.98  18.78 | 34.10  18.53 | 34.46
#   causal [32 over 4, 8192, 128]            5.16 |  9.69   5.20 |  8.80   5.07 |  8.88   4.95 |  9.04
#   window 2048 [32 over 4, 8192, 128]       3.38 |  6.91   3.32 |  6.40   3.15 |  6.40   2.98 |  6.93
#     (the backward at (512, 512): 512 is the whole tile)
#   causal [32, 8192, 128 + 64 | 128]        6.98 | 15.10   6.94 | 13.71   6.69 | 13.84   6.69 | 14.13
#   causal [64, 4096, 128]                   2.97 |  5.55   3.02 |  4.66   2.88 |  4.73   2.75 |  4.93
#   causal [32 over 8, 16384, 64] (LFM2)    18.38 | 34.77  18.44 | 33.04  18.17 | 33.17  17.94 | 33.53
#   the SPLIT pair's backward (dQ pass + dK/dV pass; no listed step runs it):
#     block diffusion 4 as above                    33.33          28.82              -          30.49
#     causal [32 over 4, 8192, 128]                 13.96          12.78              -          13.11
#     (at 128 it gains 4.51 and 1.18 ms where the fused kernel gains 2.96 and
#     0.89: the dQ pass's slabs, rows of 128 like the forward's, gain too)
# The backward's work is all area (five products and the exponentials of a
# slab) and falls with the sub-tiles skipped, the more the finer: 128 reads
# -5 (head width 64) to -16 %.  The forward keeps a cost a ROW of a tile
# whatever its width (max, sum, the rescaling of the accumulator) and a slab
# of few rows feeds the MXU badly: 512 is its best in every row (-2.2 to
# -11.8 %), 128 loses to "off" in five of seven.  Reading the row state of all slabs before the
# first and writing it after the last (so that no slab waits on a store)
# moved no row by more than 0.03 ms: not taken.  Distances from the dense-mask
# oracle are the same to the three digits printed at every sub-tile and
# "off": block diffusion o 2.58e-3, dq 2.88e-3, dk 3.70e-3, dv 2.37e-3; causal
# at 16384 2.50e-3, 2.89e-3, 3.69e-3, 2.39e-3; the two-product score o 2.50e-3,
# dq 2.86e-3, dk 3.29e-3, dv 2.64e-3, dq_rope 2.87e-3, dk_rope 3.70e-3.
# From the "off" rows, a grid step a head by least squares over the five
# one-product cases (forward, residuals under 0.008 ms) and the four at (1024,
# 1024) (backward): free 4.06 | 7.77 us, masked 4.28 | 8.72, DEAD 0.25 | 0.32:
# block diffusion's 176 dead steps a head are 1.4 ms of its forward's 12.2
# and 1.8 of its backward's 22.6, the causal half's 120 at 16384 1.0 and 1.2.
_FWD_DEFAULTS_D128 = {4096: (1024, 1024), 8192: (1024, 1024),
                      16384: (1024, 1024)}
_BWD_DEFAULTS_D128 = {4096: (1024, 1024), 8192: (1024, 1024),
                      16384: (1024, 1024)}
_BWD_WINDOW_DEFAULTS_D128 = {8192: (512, 512), 16384: (1024, 1024)}
# score width 128 < d_qk <= 256 (the values may be narrower: 192 over 128 is
# latent attention's pair), on a v5e at causal [32, 8192, 192 | 128] bf16,
# every head its own K/V (tools/joyai_kernel_probe.py, PRs 34 and 37):
# forward (1024, 1024) 8.28 ms ((512, 2048) 9.65, (512, 1024) 10.15, (1024,
# 512) 13.46, (256, 1024) 13.47, (512, 512) 14.21; all compile).  Backward
# ms: fused (1024, 1024) 15.74, (512, 1024) 16.09, (1024, 512) 16.79, (512,
# 512) 16.90, (512, 2048) 16.91, (256, 1024) 16.98, (2048, 512) 17.85,
# (1024, 256) 18.94, (2048, 1024) 37.07 (it spills); split (1024, 512) 24.03,
# (512, 512) 25.49, and split (1024, 1024) does not fit the default 16 MiB
# of VMEM ("combined": 2.68 GB of partials, so it was the split kernels).
# In JoyAI's step (the window alone, samples/s): fused (1024, 1024) 2.361,
# (512, 1024) 2.350, (1024, 512) 2.328, split (1024, 512) 2.120.  The fused
# kernel on (bq, bk) tiles with (bq, 1) lse/delta rows, tried first, read
# 16.73 at (1024, 1024) and 19.13 at (1024, 512) and kept 268 MB more in HBM
# (a [bh, Tq, 1] float32 array is tiled to 128 lanes): the transposed tiles
# ship.
# The split kernels at 128 | 128 read 5.46 forward and 15.8 backward: a
# 192-wide contraction fills two 128-deep MXU passes, so the scores cost
# what 256 would.  Other
# lengths at this width keep the baseline until they are swept, and so do
# float32 inputs (blocks twice the bytes: in the cell's float32 forward
# program, where XLA fuses the operands' producers into the call, (1024,
# 1024) passed the 16 MiB of scoped VMEM by 12 KiB, though the kernel alone
# compiles; my chip run, PR 34).
_FWD_DEFAULTS_D256 = {8192: (1024, 1024)}
_BWD_DEFAULTS_D256 = {8192: (1024, 1024)}


def _collapse_bias(bias, b, h, tq, tk):
    """A bias of any accepted shape as the kernels read it: [1, Tq, Tk] where
    it is one for all (batch, head), else [b * h, Tq, Tk]."""
    if bias.ndim == 2:
        bias = bias[None, None]
    b0, h0 = bias.shape[:2]
    if b0 == 1 and h0 == 1:
        return bias.reshape(1, tq, tk)
    # [b,1], [1,h] or [b,h]: materialize full batch*heads
    return jnp.broadcast_to(bias, (b, h, tq, tk)).reshape(b * h, tq, tk)


def _statics(q, k, v, causal, sm_scale, block_q, block_k, block_q_bwd,
             block_k_bwd, bwd_impl, interpret, window, q_rope=None,
             k_rope=None):
    """``_flash``'s non-differentiated arguments, in its order, from the
    shapes and types of ``q``, ``k``, ``v`` alone: the checks, the block
    choice from the tables and the window folded away where it is the whole
    causal half.  With ``q_rope`` / ``k_rope`` the default scale and the
    tables' rows are those of the whole score width, Q's plus the rotary
    part's: the blocks a concatenated Q and K would get."""
    b, h, tq, d = q.shape
    hk, tk = k.shape[1], k.shape[2]
    if (q_rope is None) != (k_rope is None):
        raise ValueError("q_rope and k_rope come together: the score's "
                         "second product needs both")
    if bwd_impl is not None and bwd_impl not in _BWD_IMPLS:
        raise ValueError(f"bwd_impl {bwd_impl!r}: the Pallas backwards are "
                         f"{_BWD_IMPLS} (None: fused where it fits VMEM)")
    if h % hk or v.shape[1] != hk:
        raise ValueError(f"{h} query heads over {hk}/{v.shape[1]} K/V heads")
    if k.shape[3] != d:
        raise ValueError(f"Q is {d} wide and K {k.shape[3]}: the scores "
                         "contract over one width (V's may differ)")
    group = h // hk
    if q_rope is not None:
        hr, d_r = k_rope.shape[1], k_rope.shape[3]
        if q_rope.shape != (b, h, tq, d_r) or h % hr or \
                k_rope.shape != (b, hr, tk, d_r):
            raise ValueError(
                f"q_rope {q_rope.shape} and k_rope {k_rope.shape} beside Q "
                f"{q.shape} and K {k.shape}: [b, h, Tq, d_r] and [b, h_r, "
                "Tk, d_r] with h % h_r == 0")
        d = d + d_r                # the score's width: scale and tables
    if window is not None and not isinstance(window, BlockDiffusion):
        if not causal:
            raise ValueError("window= needs causal=True")
        window = int(window)
        if window < 1:
            raise ValueError(f"window {window} < 1")
        if window >= max(tq, tk):
            window = None              # the band is the whole causal half
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    # per-length defaults from the r4 IN-GRAPH sweep on v5e (d=64,
    # bh 12–48, LONGCTX_ABLATION.md): standalone-kernel optima do NOT
    # transfer (XLA overlap + VMEM pressure shift the landscape), so the
    # tables hold the end-to-end winners.  Wider heads double the tile VMEM
    # (2048-wide K/V at d=128 matches configs that failed to compile), so
    # 64<d<=128 and 128<d<=256 have tables of their own, filled only where
    # swept (4096, 8192 and 16384; 8192, bf16 inputs only), keyed by the SCORE
    # width d (Q's and K's; V's may differ and was swept at 128 under 192
    # only); d>256 and every other length keep the long-validated (512,
    # 1024) baseline
    fwd_table, bwd_table = (
        (_FWD_DEFAULTS, _BWD_DEFAULTS) if d <= 64 else
        (_FWD_DEFAULTS_D128, _BWD_DEFAULTS_D128) if d <= 128 else
        (_FWD_DEFAULTS_D256, _BWD_DEFAULTS_D256)
        if d <= 256 and q.dtype.itemsize <= 2 else ({}, {}))
    if block_q is None and block_k is None:
        block_q, block_k = fwd_table.get(max(tq, tk), (512, 1024))
    if block_q is None:
        block_q = min(512, tq)
    if block_k is None:
        block_k = min(1024, tk)
    block_q, block_k = min(block_q, tq), min(block_k, tk)
    bwd_blocks = None
    if block_q_bwd is not None or block_k_bwd is not None:
        bwd_blocks = (min(block_q_bwd or block_q, tq),
                      min(block_k_bwd or block_k, tk))
    else:
        t = max(tq, tk)
        if window is not None and 64 < d <= 128 and \
                t in _BWD_WINDOW_DEFAULTS_D128:
            bwd_table = _BWD_WINDOW_DEFAULTS_D128
        if t in bwd_table:
            bq_b, bk_b = bwd_table[t]
            bwd_blocks = (min(bq_b, tq), min(bk_b, tk))
    return (causal, sm_scale, block_q, block_k, bwd_blocks, bwd_impl,
            interpret, window, group)


def _plan(q, k, v, bias, causal, sm_scale, block_q, block_k, block_q_bwd,
          block_k_bwd, bwd_impl, interpret, window, q_rope=None,
          k_rope=None):
    """What :func:`flash_attention` and its two halves share: :func:`_statics`,
    the heads collapsed into the batch and the bias broadcast.  Returns
    ``((q, k, v, bias) collapsed, rest)``, ``rest`` being :func:`_flash`'s
    other arguments in its order: the nine statics, then ``q_rope`` and
    ``k_rope`` collapsed (``None`` twice where the score is one product)."""
    statics = _statics(q, k, v, causal, sm_scale, block_q, block_k,
                       block_q_bwd, block_k_bwd, bwd_impl, interpret, window,
                       q_rope, k_rope)
    b, h, tq, d = q.shape
    hk, tk = k.shape[1], k.shape[2]
    qc = q.reshape(b * h, tq, d)
    kc = k.reshape(b * hk, tk, d)
    vc = v.reshape(b * hk, tk, v.shape[3])   # V's own width, the output's
    bc = None if bias is None else _collapse_bias(bias, b, h, tq, tk)
    rope = (None, None) if q_rope is None else (
        q_rope.reshape(b * h, tq, -1),
        k_rope.reshape(b * k_rope.shape[1], tk, -1))
    return (qc, kc, vc, bc), statics + rope


def flash_attention(q, k, v, bias: Optional[jax.Array] = None,
                    causal: bool = False, sm_scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    block_q_bwd: Optional[int] = None,
                    block_k_bwd: Optional[int] = None,
                    bwd_impl: Optional[str] = None,
                    interpret: bool = False,
                    window: Optional[int] = None,
                    q_rope: Optional[jax.Array] = None,
                    k_rope: Optional[jax.Array] = None):
    """Fused attention over [batch, heads, T, head_dim] tensors: Q and K
    ``[.., d_qk]``, V ``[.., d_v]``, the result ``[batch, heads, Tq, d_v]``
    (``d_v`` read from V; the two are one width for every model but latent
    attention's, and then the lowering is what it always was).  ``sm_scale``
    defaults to ``d_qk ** -0.5``.

    ``window`` (with ``causal=True``): key ``j`` is visible to query ``i``
    iff ``0 <= i - j < window``.  The kernels skip the blocks wholly outside
    that band as they skip the blocks above the diagonal (no MXU work, and
    K/V index maps that name the resident block, so no copy), and run masks
    only on the blocks the two edges cross.  ``window=None`` lowers exactly
    as before the argument existed.  ``window=block_diffusion(T, B)`` (a
    mask form, :class:`BlockDiffusion`; ``causal=False``, self-attention):
    the rows are a noisy and a clean copy of one sequence under block
    diffusion's three-part mask, its dead tile pairs skipped and their K/V
    tiles not copied in the same way.

    K and V may have fewer heads than Q (``[batch, kv_heads, T, d]``, with
    ``heads % kv_heads == 0``): query head ``h`` reads KV head ``h //
    (heads // kv_heads)`` through the kernels' index maps, nothing is
    expanded in HBM, and dK/dV are summed over each group.

    ``bias`` broadcasts over (batch, heads): accepted shapes are
    [b, h, Tq, Tk], [1, 1, Tq, Tk] or [Tq, Tk].

    Default blocks are per-sequence-length tables (below) at d_qk≤64 and,
    for the lengths swept there, at 64<d_qk≤128 (4096, 8192, 16384) and
    128<d_qk≤256 (8192, at 192 over 128, bf16 inputs), else (512, 1024) capped at the
    sequence lengths — measured on v5e: ahead
    of XLA's O(T²) attention from T≈1024, and the only runnable path
    beyond ~8k (r4 prior: 11.0 ms fwd / 45.1 ms f+b at [12,16384,64] —
    LONGCTX_ABLATION.md).
    The backward kernels take their own ``block_q_bwd``/``block_k_bwd``
    (default: the ``_BWD_DEFAULTS`` table at d≤64 for 2k/4k/8k/16k, else
    the forward blocks) — swept separately in LONGCTX_ABLATION.md.
    ``bwd_impl``: ``None`` or "fused" (single-recompute, dk/dv and the
    head's dq accumulated in VMEM; falls back to split where the accumulator
    and the blocks pass ``_FUSED_VMEM_SHARE`` of a core's VMEM: ``Tq · d_qk``
    some 4 to 8 times the cells') or "split" (two-pass, outright); anything
    else raises.  :func:`flash_bwd_kernel` says which one a call gets.

    ``q_rope`` ``[batch, heads, Tq, d_r]`` and ``k_rope`` ``[batch, h_r, Tk,
    d_r]`` (``heads % h_r == 0``; latent attention: one rotary key head for
    all, ``d_r`` 64 beside 128): the score of a pair is ``(q·k + q_rope·
    k_rope) · sm_scale``, a second product into the same float32 tile in
    every kernel, so that no ``[q | q_rope]`` and no per-head copy of the
    rotary key is ever built in HBM: query head ``h`` reads rotary head ``h
    // (heads // h_r)`` through an index map, and ``k_rope``'s gradient is
    summed over that group.  ``sm_scale`` defaults to ``(d_qk + d_r) **
    -0.5`` and the blocks are the tables' at that whole width.  Without the
    two the lowering is what it was before they existed.
    """
    (qc, kc, vc, bc), rest = _plan(
        q, k, v, bias, causal, sm_scale, block_q, block_k, block_q_bwd,
        block_k_bwd, bwd_impl, interpret, window, q_rope, k_rope)
    return _flash(qc, kc, vc, bc, *rest).reshape(
        q.shape[:3] + v.shape[3:])


def flash_attention_fwd(q, k, v, bias=None, causal=False, sm_scale=None,
                        block_q=None, block_k=None, block_q_bwd=None,
                        block_k_bwd=None, bwd_impl=None, interpret=False,
                        window=None, q_rope=None, k_rope=None):
    """The forward half of :func:`flash_attention` as a plain function, for
    a caller that keeps the residuals itself (the ``flash_attention`` op of a
    ``Program``): ``(o [b, h, Tq, d], lse [b, h, Tq] float32)``, the
    log-sum-exp of each query's scaled, biased, masked scores.  Same
    arguments and block choice; the ``custom_vjp``'s forward rule, called
    plainly."""
    b, h, tq, _ = q.shape
    (qc, kc, vc, bc), rest = _plan(
        q, k, v, bias, causal, sm_scale, block_q, block_k, block_q_bwd,
        block_k_bwd, bwd_impl, interpret, window, q_rope, k_rope)
    o, res = _flash_vjp_fwd(qc, kc, vc, bc, *rest)
    return o.reshape(b, h, tq, v.shape[3]), res[5].reshape(b, h, tq)


def flash_attention_bwd(q, k, v, bias, o, lse, do, causal=False,
                        sm_scale=None, block_q=None, block_k=None,
                        block_q_bwd=None, block_k_bwd=None, bwd_impl=None,
                        interpret=False, window=None, need_dbias=True,
                        q_rope=None, k_rope=None):
    """The backward half: ``(dq, dk, dv, dbias)`` from the inputs, what
    :func:`flash_attention_fwd` returned for them and the output's gradient;
    ``dbias`` has the bias's shape, and is ``None`` without a bias or where
    ``need_dbias`` is false (nobody keeps its [Tq, Tk] tiles then).
    The kernels and blocks are the ones ``jax.grad`` of
    :func:`flash_attention` runs (``_flash_vjp_bwd``); nothing of the forward
    is computed again but the scores, tile by tile, from ``lse``.  With
    ``q_rope`` / ``k_rope`` the result is ``(dq, dk, dv, dbias, dq_rope,
    dk_rope)``."""
    b, h, tq, _ = q.shape
    (qc, kc, vc, bc), rest = _plan(
        q, k, v, bias, causal, sm_scale, block_q, block_k, block_q_bwd,
        block_k_bwd, bwd_impl, interpret, window, q_rope, k_rope)
    dq, dk, dv, db, dqr, dkr = _flash_vjp_bwd(
        *rest[:-2], (qc, kc, vc, bc, o.reshape(b * h, tq, v.shape[3]),
                     lse.reshape(b * h, tq), *rest[-2:]),
        do.reshape(b * h, tq, v.shape[3]), need_dbias)
    if db is not None:
        # the transpose of _collapse_bias: summed over what it broadcast
        db, = jax.vjp(lambda x: _collapse_bias(x, b, h, tq, k.shape[2]),
                      bias)[1](db.astype(bias.dtype))
    grads = dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape), db
    if q_rope is None:
        return grads
    return grads + (dqr.reshape(q_rope.shape), dkr.reshape(k_rope.shape))


def flash_bwd_kernel(q, k, v, bias=None, causal=False, sm_scale=None,
                     block_q=None, block_k=None, block_q_bwd=None,
                     block_k_bwd=None, bwd_impl=None, interpret=False,
                     window=None, q_rope=None, k_rope=None):
    """Which backward :func:`flash_attention_bwd` (and ``jax.grad`` of
    :func:`flash_attention`) runs for these arguments, from their shapes
    alone: ``"fused"`` or ``"split"`` of the Pallas kernels,
    or ``"jax"``, the blockwise fallback (a bias, or no TPU)."""
    *_, block_q, block_k, bwd_blocks, bwd_impl, interpret, _, _ = _statics(
        q, k, v, causal, sm_scale, block_q, block_k, block_q_bwd,
        block_k_bwd, bwd_impl, interpret, window, q_rope, k_rope)
    if bias is not None or not (on_tpu() or interpret):
        return "jax"

    def collapsed(x):
        return jax.ShapeDtypeStruct(
            (x.shape[0] * x.shape[1],) + tuple(x.shape[2:]), x.dtype)
    return _bwd_kernel_name(collapsed(q), collapsed(k), collapsed(v),
                            *(bwd_blocks or (block_q, block_k)), bwd_impl,
                            0 if q_rope is None else q_rope.shape[3])


def flash_lse_layout(q, k, v, causal=False, sm_scale=None, block_q=None,
                     block_k=None, interpret=False, window=None, q_rope=None,
                     k_rope=None, **_):
    """How the forward kernel writes ``lse`` for these arguments, from their
    shapes alone (:func:`_lse_rows` at the blocks the tables give):
    ``"row"``, ``[bh, 1, Tq]`` as the backward reads it, or ``"lanes"``, the
    lane-broadcast ``[bh, Tq, 128]`` columns of which one lane is kept."""
    block_q = _statics(q, k, v, causal, sm_scale, block_q, block_k, None,
                       None, None, interpret, window, q_rope, k_rope)[2]
    tq = q.shape[2]
    return "row" if _lse_rows(block_q, tq + (-tq) % block_q) else "lanes"


def _statics_of(q, k, v, kw):
    """:func:`_statics` from the keyword arguments of an entry point."""
    return _statics(
        q, k, v, kw.get("causal", False), kw.get("sm_scale"),
        kw.get("block_q"), kw.get("block_k"), kw.get("block_q_bwd"),
        kw.get("block_k_bwd"), kw.get("bwd_impl"),
        kw.get("interpret", False), kw.get("window"), kw.get("q_rope"),
        kw.get("k_rope"))


def flash_blocks(q, k, v, **kw):
    """``((block_q, block_k) of the forward, (block_q, block_k) of the
    backward)`` a call with these arguments gets, from the shapes alone."""
    *_, block_q, block_k, bwd_blocks, _, _, _, _ = _statics_of(q, k, v, kw)
    return (block_q, block_k), bwd_blocks or (block_q, block_k)


def flash_subtiles(q, k, v, bias=None, **kw):
    """``(forward, backward)`` of :func:`subtile_counts` for a call with
    these arguments, from the shapes alone: what becomes of its masked tile
    pairs at the blocks the tables give and the kernels' sub-tiles (with a
    ``bias`` every one runs whole: the forward keeps the whole-tile path and
    the backward is the blockwise jax one)."""
    causal, _, block_q, block_k, bwd_blocks, _, _, window, _ = _statics_of(
        q, k, v, kw)
    tq, tk = q.shape[2], k.shape[2]
    bq_b, bk_b = bwd_blocks or (block_q, block_k)
    return (subtile_counts(causal, window, block_q, block_k, tq, tk,
                           bool(tk % block_k),
                           0 if bias is not None else _SUB_FWD),
            subtile_counts(causal, window, bq_b, bk_b, tq, tk,
                           bool(tq % bq_b or tk % bk_b),
                           0 if bias is not None else _SUB_BWD))
