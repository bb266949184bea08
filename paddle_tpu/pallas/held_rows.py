"""The held rows of a chip's share of the experts, brought back to their
tokens: ``moe_ffn``'s two un-sorts (the forward's weighted sum over a token's
``k`` slots, the backward's sum of the rows' gradients) at the cost of the rows
routed here and not of the ``S * k`` slots.

XLA's lowering is a gather over every slot (``jnp.take(buf, place)``) whose
result is masked where the slot's expert lives on another chip: three slots in
four at LFM2's share, seven in eight at Trinity's, and a row gather costs by
the slot.  This kernel goes through the held slots of a token tile at a time,
starts one asynchronous copy HBM -> VMEM for each and none for a slot held
elsewhere, and adds each copied row, widened to float32 and weighted, into
its token's row of the output tile, slot by slot: the arithmetic and the
order of ``sum_j where(held_j, row_j.astype(f32), 0) * w_j``.

The repo's first kernel with hand-made copies.  What shapes them:

- Mosaic slices a tiled dimension of an HBM buffer at whole tiles only (8
  rows, float32 and bf16 alike on a v5e: ``(8, 128)`` and ``(8, 128)(2, 1)``),
  so a copy moves the aligned group of ``_GROUP`` rows that holds the wanted
  one, and the kernel reads the one row out of the group in VMEM.  A group of
  2048-wide bf16 rows is one contiguous 32 KiB.
- the slot table of a step is 256-384 KiB of int32, too much for a scalar
  prefetch, and the scalar core walks it at 9 ns a slot: XLA lists each
  tile's held slots first (``_held_lists``: vector work, no sort and no
  scatter), a grid step gets its own tile's list and the next one's as blocks
  in SMEM, and only the tiles' counts are prefetched.
- the copies of tile ``i + 1`` are started before the rows of tile ``i`` are
  read, into the other half of the slab, so that only the first tile of a
  call waits for its first row.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

#: rows a copy moves: the HBM tiling's rows (module docstring)
_GROUP = 8
#: copies a tile may have in flight, and so the slab's groups a source and
#: half, the entries of a tile's list and the radix of its words
_MAX_SLOTS = 128
#: tiles' lists of held slots a block in SMEM: the block's sublanes
_LISTS = 8
#: the slab's bytes (both halves, every source) a call may ask of VMEM
_SLAB_BYTES = 12 << 20


def tile_tokens(S, k, d, itemsize, n_src):
    """Tokens a grid step brings home, from the shapes alone: as many as keep
    a tile's slots within ``_MAX_SLOTS`` copies in flight and the two halves
    of the slab within ``_SLAB_BYTES``, a multiple of 8 (the output tile's
    sublanes) that divides ``S``; 0 where there is none."""
    group_bytes = _GROUP * d * itemsize
    slots = min(_MAX_SLOTS, _SLAB_BYTES // (2 * n_src * group_bytes))
    tt = slots // k // 8 * 8
    while tt and S % tt:
        tt -= 8
    return tt


def fits(S, k, d, rows, dtype):
    """Whether the kernel takes these shapes, with one source and with two;
    the lowering keeps XLA's gather where it does not (ragged toy sizes, a
    dtype whose rows are neither words nor half-words of a float32)."""
    dtype = jnp.dtype(dtype)
    return dtype in (jnp.float32, jnp.bfloat16) and rows % _GROUP == 0 and \
        tile_tokens(S, k, d, dtype.itemsize, 2) > 0


def _vmem_bytes(tt, k, d, itemsize, n_src):
    """What the call asks of VMEM: the slab's two halves a source, the output
    tile twice (the pipeline's two buffers) and as much again for what the
    compiler keeps beside them."""
    slab = 2 * n_src * tt * k * _GROUP * d * itemsize
    return int(slab + 4 * tt * d * 4 + (4 << 20))


def _as_stored(x, dtype):
    """Float32 ``x`` rounded to ``dtype`` (float32 or bf16) and widened again.
    For bf16 in integer arithmetic, round to nearest even on the bits: a
    ``convert`` pair is what XLA:CPU, where the tests interpret this kernel,
    removes from a fused computation, and then the sum is not the stored one."""
    if dtype == jnp.float32:
        return x
    u32 = jnp.uint32
    bits = jax.lax.bitcast_convert_type(x, u32)
    rounded = (bits + u32(0x7FFF) + ((bits >> u32(16)) & u32(1))) \
        & u32(0xFFFF0000)
    return jnp.where(x != x, x, jax.lax.bitcast_convert_type(rounded,
                                                             jnp.float32))


def _row(slab, src, half, n, r):
    """Row ``r`` of a copied group, ``[1, d]`` widened to float32.  A bf16 row
    is half of a 32-bit sublane (rows ``2i`` and ``2i + 1`` share the words of
    sublane ``i``, the even row in the low halves) and Mosaic loads a sublane
    at a dynamic offset in 32-bit words only: the row's sublane is read as
    words and its half moved to the top, which is the float32 of that bf16."""
    from jax.experimental import pallas as pl
    if slab.dtype == jnp.float32:
        return slab[src, half, n, pl.ds(r, 1), :]
    u32 = jnp.uint32
    words = slab.bitcast(u32)[src, half, n, pl.ds(r // 2, 1), :]
    shift = (r % 2 * 16).astype(u32)
    return jax.lax.bitcast_convert_type((words >> shift) << u32(16),
                                        jnp.float32)


def _kernel(counts, list_ref, next_ref, *refs, k, n_src, weighted, n_tiles):
    """One token tile.  ``counts`` (every tile's held slots, prefetched),
    this tile's list of them and the next tile's, the weights' block, the
    sources in HBM, the output tile, the slab and a semaphore a half."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    w_ref = None
    if weighted:
        w_ref, refs = refs[0], refs[1:]
    srcs, out_ref = refs[:n_src], refs[n_src]
    slab, sems = refs[n_src + 1:]
    i = pl.program_id(0)
    f32 = jnp.float32

    def copy(src, half, n, row):
        start = pl.multiple_of(row // _GROUP * _GROUP, _GROUP)
        return pltpu.make_async_copy(
            srcs[src].at[pl.ds(start, _GROUP), :], slab.at[src, half, n],
            sems.at[half])

    def start_tile(held, tile, half):
        """A copy a source for each held slot of tile ``tile``."""
        def issue(n, carry):
            for src in range(n_src):
                copy(src, half, n,
                     held[tile % _LISTS, n] // _MAX_SLOTS).start()
            return carry
        jax.lax.fori_loop(0, counts[tile], issue, 0)

    @pl.when(i == 0)
    def _():
        start_tile(list_ref, 0, 0)

    @pl.when(i + 1 < n_tiles)
    def _():
        start_tile(next_ref, i + 1, (i + 1) % 2)

    half = i % 2
    out_ref[...] = jnp.zeros(out_ref.shape, f32)

    def bring(n, carry):
        word = list_ref[i % _LISTS, n]
        s, row = word % _MAX_SLOTS, word // _MAX_SLOTS
        r = row % _GROUP
        for src in range(n_src):
            copy(src, half, n, row).wait()
        x = _row(slab, 0, half, n, r)
        if n_src == 2:
            # the two parts added and rounded to their own dtype as XLA's
            # stored dxs_g + dxs_u is, widened after
            x = _as_stored(x + _row(slab, 1, half, n, r), slab.dtype)
        if weighted:
            x = x * w_ref[s // k, s % k]
        t = s // k
        out_ref[pl.ds(t, 1), :] = out_ref[pl.ds(t, 1), :] + x
        return carry
    jax.lax.fori_loop(0, counts[i], bring, 0)


def _held_lists(place, held, n_tiles, n):
    """``(lists [n_tiles, _MAX_SLOTS], counts [n_tiles])``: for each tile of
    ``n`` slots its held slots in slot order at the front of its list, row
    and slot id in one word, and how many they are.  Vector work over the
    slot table, done by XLA before the kernel, whose scalar core would walk
    it at 9 ns a slot (0.5 ms for 65536: half of what the kernel took at
    Trinity's load); no sort and no scatter: entry ``c`` of a tile is the
    slot whose count of held slots up to itself is ``c + 1``."""
    i32 = jnp.int32
    held = held.reshape(n_tiles, n)
    rank = jnp.cumsum(held, axis=1, dtype=i32) - 1
    word = place.reshape(n_tiles, n).astype(i32) * _MAX_SLOTS + \
        jnp.arange(n, dtype=i32)
    entry = jnp.arange(_MAX_SLOTS, dtype=i32)[None, :, None]
    lists = jnp.sum(jnp.where(held[:, None, :] & (rank[:, None, :] == entry),
                              word[:, None, :], 0), axis=2, dtype=i32)
    lists = jnp.pad(lists, ((0, -n_tiles % _LISTS), (0, 0)))
    return lists, jnp.sum(held, axis=1, dtype=i32)


@functools.lru_cache(maxsize=None)
def _call(S, k, d, dtype, n_src, weighted, interpret):
    """The kernel for these shapes, jitted and kept: a step holds two a layer
    of one or two shapes."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    itemsize = jnp.dtype(dtype).itemsize
    tt = tile_tokens(S, k, d, itemsize, n_src)
    n_tiles = S // tt
    smem = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)
    in_specs = [smem((_LISTS, _MAX_SLOTS), lambda i, counts: (i // _LISTS, 0)),
                smem((_LISTS, _MAX_SLOTS), lambda i, counts: (
                    jnp.minimum(i + 1, n_tiles - 1) // _LISTS, 0))]
    if weighted:
        in_specs.append(smem((tt, k), lambda i, counts: (i, 0)))
    in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * n_src
    kernel = pl.pallas_call(
        functools.partial(_kernel, k=k, n_src=n_src, weighted=weighted,
                          n_tiles=n_tiles),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n_tiles,), in_specs=in_specs,
            out_specs=pl.BlockSpec((tt, d), lambda i, counts: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((n_src, 2, tt * k, _GROUP, d), dtype),
                pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((S, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_vmem_bytes(tt, k, d, itemsize, n_src)),
        interpret=interpret, name="moe_held_rows")

    def call(place, held, weights, *srcs):
        lists, counts = _held_lists(place, held, n_tiles, tt * k)
        return kernel(counts, lists, lists, *weights, *srcs)
    return jax.jit(call)


def held_rows_to_tokens(srcs, place, held, k, weights=None, interpret=False):
    """``sum_j where(held[t, j], sum(src[place[t, j]] for src in srcs)
    .astype(f32), 0) * weights[t, j]`` -> ``[S, d]`` float32, reading the held
    rows alone.

    ``srcs``: one or two ``[rows, d]`` buffers of one dtype whose first rows
    are the held slots' (two are added row by row as stored, before the
    widening); ``place`` ``[S * k]`` int32, each slot's row; ``held`` ``[S *
    k]`` bool; ``weights`` ``[S, k]`` float32, or None for a plain sum.  The
    shapes have to pass :func:`fits`."""
    S = place.shape[0] // k
    call = _call(S, k, srcs[0].shape[1], jnp.dtype(srcs[0].dtype), len(srcs),
                 weights is not None, bool(interpret))
    weights = () if weights is None else (
        weights.astype(jnp.float32).reshape(S, k),)
    return call(place, held, weights, *srcs)
