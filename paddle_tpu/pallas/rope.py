"""The rotary embedding as one pass over the tensor (Pallas).

Why a kernel: the rotation pairs every lane of a head with a partner lane
(``i <-> i + head_dim / 2`` in the rotate-half convention, ``2i <-> 2i + 1``
for adjacent pairs) and XLA:TPU has no lane rotation to fuse.  Its lowering
of the jnp form (``ops/attention_ops.py:_rope_xla``) writes the float32 copy
of the tensor to HBM, the two half-width halves (each fills half of every
128-lane tile, so each costs what the whole would) and a pad that glues
them; the adjacent-pair form goes through two gathers over index tables and
a relayout; the backward is the transpose of either, pads and scatters.  Four
to nine times the tensor's bytes (PERF.md section 5, PR 43).

Here a grid step loads a ``[rows, block_t, width]`` tile in the stream's
dtype, widens it to float32 in VMEM, forms each lane's partner with lane
rotations (``pltpu.roll``, the XLU; a select between the two directions for
adjacent pairs), computes ``x * C + partner(x) * S`` in float32 and stores
the stream's dtype: the float32 products and sums of the jnp form, one
rounding at the end as there.  ``C`` and ``S`` are ``[T, head_dim]`` float32
tables, cosines repeated and sines signed (``-sin`` on the lane whose
partner is subtracted), which XLA makes once a step; the grid goes through
the positions outermost, so a table block stays in VMEM over the heads and
sequences.  The tensor is taken as it lies, 3-D or 4-D: a reshape before the
call would be the root the producer's fusion is named by.  The gradient is
the same kernel with ``S`` negated: a rotation's transpose turns the other
way, and nothing of the forward is kept.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_LANE = 128
#: bytes of the stream a grid step loads (and stores): large enough that the
#: step's fixed cost (~0.35 us) is a few per cent of its copy, and under the
#: 1.75 MiB from which blocks of a 16384-long sequence's heads (4 MiB apart)
#: ran at 264 GB/s for 428 (PERF.md section 6, PR 43: 0.5, 1, 2 and 4 MiB
#: tie at every other shape of the cells)
_BLOCK_BYTES = 1 << 20
#: positions a block may span: the two float32 tables' blocks are
#: ``block_t * head_dim * 8`` bytes, twice for the pipeline
_MAX_BLOCK_T = 1024


def _lanes(n):
    """``n`` lanes as the whole 128-lane tiles they fill, in VMEM as in
    HBM."""
    return -(-n // _LANE) * _LANE


def yarn_frequencies(head_dim, theta, rope_scaling):
    """``[head_dim / 2]`` float32 on the host: YaRN's per-pair blend of the
    original and the interpolated frequencies (arXiv:2309.00071 §3.2, as the
    DeepSeek family's code builds its static table).  With ``f_i = theta^(-2i
    / head_dim)`` and ``c(beta) = head_dim ln(original / (2 pi beta)) / (2 ln
    theta)``: ``lo = floor(c(beta_fast))``, ``hi = ceil(c(beta_slow))`` (both
    kept inside the table), ``g_i = clip((i - lo) / (hi - lo), 0, 1)`` and
    ``f'_i = (1 - g_i) f_i + g_i f_i / factor``: the fast pairs turn as they
    did, the slow ones ``factor`` times slower, at every length.  The sines
    and cosines are not scaled: ``mscale / mscale_all_dim`` has to be 1 (the
    length scaling of the softmax is the attention's ``sm_scale``)."""
    import numpy as np
    s = rope_scaling
    if s.get("type", s.get("rope_type")) != "yarn":
        raise ValueError(f"rope_scaling {s!r}: yarn is the one form here")
    if float(s.get("mscale", 1)) != float(s.get("mscale_all_dim", 1)):
        raise ValueError("rope_scaling with mscale != mscale_all_dim would "
                         "scale the sines and cosines: not implemented")
    half = head_dim // 2

    def correction(beta):
        return head_dim * np.log(
            s["original_max_position_embeddings"] / (2 * np.pi * beta)) / (
                2 * np.log(theta))

    lo = max(int(np.floor(correction(s["beta_fast"]))), 0)
    hi = min(int(np.ceil(correction(s["beta_slow"]))), head_dim - 1)
    ramp = np.clip((np.arange(half) - lo) / (max(hi - lo, 1e-3)), 0.0, 1.0)
    f = float(theta) ** (-2.0 * np.arange(half) / head_dim)
    return ((1.0 - ramp) * f + ramp * f / float(s["factor"])).astype(
        np.float32)


def angles(t, head_dim, theta, rope_scaling=None):
    """``[t, head_dim / 2]`` float32: ``pos * theta^(-2i / head_dim)``, what
    both forms of the op turn by; with ``rope_scaling`` ``pos *`` the table
    of :func:`yarn_frequencies`, a constant of the program."""
    if rope_scaling is not None:
        inv = jnp.asarray(yarn_frequencies(head_dim, theta, rope_scaling))
    else:
        inv = theta ** (-jnp.arange(head_dim // 2, dtype=jnp.float32) * 2.0
                        / head_dim)
    return jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]


def tables(t, head_dim, theta, interleaved, rope_scaling=None):
    """``(C, S)``, ``[t, head_dim]`` float32: the cosine of each lane's angle
    and its sine, signed: ``out = x * C + partner(x) * S``."""
    ang = angles(t, head_dim, theta, rope_scaling)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if interleaved:
        return (jnp.repeat(cos, 2, axis=1),
                jnp.stack([-sin, sin], axis=-1).reshape(t, head_dim))
    return (jnp.concatenate([cos, cos], axis=1),
            jnp.concatenate([-sin, sin], axis=1))


def blocks(rows, t, width, itemsize):
    """``(block_rows, block_t)`` of a grid step from the shapes alone:
    ``block_t`` the largest power of two up to ``_MAX_BLOCK_T`` that divides
    ``t``, is a whole number of the dtype's sublane tiles and keeps a row's
    tile within ``_BLOCK_BYTES``, ``block_rows`` as many rows as still do;
    ``(0, 0)`` where ``t`` has no such divisor (a ragged length)."""
    sublanes = 8 * 4 // itemsize
    row_bytes = _lanes(width) * itemsize
    bt = _MAX_BLOCK_T
    while bt >= sublanes and (t % bt or (
            bt > sublanes and bt * row_bytes > _BLOCK_BYTES)):
        bt //= 2
    if bt < sublanes:
        return 0, 0
    br = max(1, _BLOCK_BYTES // (bt * row_bytes))
    while rows % br:
        br -= 1
    return br, bt


def fits(shape, head_dim, dtype):
    """Whether the kernel takes ``x``: 3-D ``[b, t, heads * head_dim]`` or
    4-D ``[b, heads, t, head_dim]``, bf16 or float32, a head that fills
    whole 128-lane tiles (or, 4-D, the 64 lanes of a half tile, taken as it
    lies), and a length that divides into blocks.  The lowering keeps XLA's
    form where it does not: toy widths, ragged lengths."""
    dtype = jnp.dtype(dtype)
    if dtype not in (jnp.float32, jnp.bfloat16) or len(shape) not in (3, 4):
        return False
    whole_tiles = head_dim % _LANE == 0
    if not (whole_tiles or (len(shape) == 4 and head_dim == _LANE // 2)):
        return False
    if shape[-1] % head_dim:
        return False
    return blocks(1, shape[-2], shape[-1], dtype.itemsize)[1] > 0


def _kernel(x_ref, c_ref, s_ref, o_ref, *, head_dim, interleaved):
    """One tile ``[rows, block_t, width]`` and the tables' ``[block_t,
    head_dim]`` blocks; ``width`` is ``head_dim`` (4-D) or every head's
    lanes side by side (3-D), rotated head by head."""
    from jax.experimental.pallas import tpu as pltpu
    f32 = jnp.float32
    c, s = c_ref[...], s_ref[...]
    if interleaved:
        lane = jax.lax.broadcasted_iota(jnp.int32, c.shape, 1)
        even = lane % 2 == 0
    for r in range(x_ref.shape[0]):
        for lo in range(0, x_ref.shape[2], head_dim):
            x = x_ref[r, :, lo:lo + head_dim].astype(f32)
            if interleaved:
                # lane 2i takes 2i + 1 (a turn to the left), 2i + 1 takes 2i
                partner = jnp.where(even, pltpu.roll(x, head_dim - 1, 1),
                                    pltpu.roll(x, 1, 1))
            else:
                partner = pltpu.roll(x, head_dim // 2, 1)
            o_ref[r, :, lo:lo + head_dim] = \
                (x * c + partner * s).astype(o_ref.dtype)


@functools.lru_cache(maxsize=None)
def _call(shape, dtype, head_dim, interleaved, interpret):
    """The kernel over ``x`` as it lies, kept a shape: the rows of a tile
    are heads of one sequence (4-D) or sequences (3-D)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    itemsize = jnp.dtype(dtype).itemsize
    rows, t, width = shape[-3:]
    br, bt = blocks(rows, t, width, itemsize)
    if len(shape) == 4:
        grid = (t // bt, shape[0], rows // br)
        tile = pl.BlockSpec((None, br, bt, width),
                            lambda i, b, j: (b, j, i, 0))
    else:
        grid = (t // bt, rows // br)
        tile = pl.BlockSpec((br, bt, width), lambda i, j: (j, i, 0))
    table = pl.BlockSpec((bt, head_dim), lambda i, *_: (i, 0))
    # two buffers a block (whole lane tiles), and as much again for the
    # float32 the compiler keeps beside them
    vmem = 2 * (2 * br * bt * _lanes(width) * itemsize +
                2 * bt * _lanes(head_dim) * 4)
    return pl.pallas_call(
        functools.partial(_kernel, head_dim=head_dim,
                          interleaved=interleaved),
        grid=grid, in_specs=[tile, table, table], out_specs=tile,
        out_shape=jax.ShapeDtypeStruct(shape, dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) + ("arbitrary",) * (
                len(grid) - 1),
            vmem_limit_bytes=int(2 * vmem + (4 << 20))),
        interpret=interpret, name="rope")


def rope(x, head_dim, theta=10000.0, interleaved=False, transpose=False,
         interpret=False, rope_scaling=None):
    """``x`` rotated by its position: 3-D ``[b, t, heads * head_dim]`` or 4-D
    ``[b, heads, t, head_dim]``, in ``x``'s dtype.  ``transpose``: turned
    back, which is the gradient of the rotation with respect to ``x`` at the
    cotangent ``x``.  ``rope_scaling``: the frequencies of
    :func:`yarn_frequencies` in ``theta^(-2i / head_dim)``'s place.  The
    shapes have to pass :func:`fits`."""
    c, s = tables(x.shape[-2], head_dim, theta, bool(interleaved),
                  rope_scaling)
    call = _call(tuple(x.shape), jnp.dtype(x.dtype), int(head_dim),
                 bool(interleaved), bool(interpret))
    return call(x, c, -s if transpose else s)
