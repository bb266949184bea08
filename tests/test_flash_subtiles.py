"""A masked flash tile pair run by the sub-tiles its mask leaves live (PR 62):
the kernels in interpret mode against ``mha_reference`` (the dense-mask
oracle) one case a KIND of masked tile pair, the calls whose live regions
are not static against the whole-tile path bit for bit, the sub-tile tables
against a brute count of the mask, and the counter the mechanism brings.
"""

import importlib
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops import attention_ops

fa = importlib.import_module("paddle_tpu.pallas.flash_attention")


@pytest.fixture
def sub16(monkeypatch):
    """Sub-tiles of 16 under blocks of 64: the published 256 under 1024 at
    a size the interpreter runs in a second."""
    monkeypatch.setattr(fa, "_SUB_FWD", 16)
    monkeypatch.setattr(fa, "_SUB_BWD", 16)


@pytest.fixture
def slabs(monkeypatch):
    """The slabs the kernels' bodies were traced with, as ``_slabs`` gave
    them: empty where every masked tile pair ran whole."""
    seen, real = [], fa._slabs

    def spy(table, sub):
        for slab in real(table, sub):
            seen.append(slab)
            yield slab
    monkeypatch.setattr(fa, "_slabs", spy)
    return seen


def _rand(i, *shape):
    return jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(62), i),
                             shape, jnp.float32)


def _both(fn, do, *ops):
    out, back = jax.vjp(fn, *ops)
    return (out,) + tuple(back(do))


def _case(t, heads=2, kv_heads=2, d=32, rope=0, tk=None):
    ops = [_rand(0, 1, heads, t, d), _rand(1, 1, kv_heads, tk or t, d),
           _rand(2, 1, kv_heads, tk or t, d)]
    if rope:
        ops += [_rand(3, 1, heads, t, rope), _rand(4, 1, 1, tk or t, rope)]
    return _rand(5, 1, heads, t, d), ops


def _attn(fn, **kw):
    def go(q, k, v, *r):
        return fn(q, k, v, **kw, **(dict(q_rope=r[0], k_rope=r[1])
                                    if r else {}))
    return go


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


#: name -> (mask arguments, the kinds the grid has as (q_noisy, k_noisy,
#: q0 - k0), further arguments of ``_case``, the backward)
KINDS = {
    "causal_diagonal": (dict(causal=True), [(False, False, 0)], {}, None),
    "causal_diagonal_split": (dict(causal=True), [(False, False, 0)], {},
                              "split"),
    "window_of_one_block": (dict(causal=True, window=64),
                            [(False, False, 0), (False, False, 64)], {},
                            None),
    "window_of_a_block_and_a_half": (
        dict(causal=True, window=96),
        [(False, False, 0), (False, False, 64), (False, False, 128)], {},
        None),
    "window_inside_a_block": (dict(causal=True, window=32),
                              [(False, False, 0), (False, False, 64)], {},
                              "split"),
    "block_diffusion_4": (
        dict(window=fa.block_diffusion(256, 4)),
        [(False, False, 0), (True, False, -128), (True, True, 0)], {}, None),
    "block_diffusion_32_over_sub_16": (
        dict(window=fa.block_diffusion(256, 32)),
        [(False, False, 0), (True, False, -128), (True, True, 0)], {},
        None),
    "block_diffusion_4_split": (
        dict(window=fa.block_diffusion(256, 4)),
        [(False, False, 0), (True, False, -128), (True, True, 0)], {},
        "split"),
    "grouped_heads": (dict(causal=True), [(False, False, 0)],
                      dict(heads=4, kv_heads=1), None),
    "two_product_score": (dict(causal=True), [(False, False, 0)],
                          dict(rope=16), None),
    "two_product_score_split": (dict(causal=True), [(False, False, 0)],
                                dict(rope=16), "split"),
    "block_diffusion_grouped": (
        dict(window=fa.block_diffusion(256, 4)),
        [(False, False, 0), (True, False, -128), (True, True, 0)],
        dict(heads=4, kv_heads=1), None),
}


@pytest.mark.parametrize("name", sorted(KINDS))
def test_a_kind_of_masked_tile_pair_against_the_dense_mask_oracle(
        name, sub16, slabs):
    """Output and every gradient of the sub-tiled kernels against
    ``mha_reference`` at float32 (1e-5: the whole-tile kernels' own
    distance), the grid's kinds as listed, and the bodies traced by slab."""
    mask, kinds, shape, impl = KINDS[name]
    do, ops = _case(256, **shape)
    found = fa._subtile_kinds(mask.get("causal", False), mask.get("window"),
                              64, 64, 256, 256, 0, 16)
    assert [k[:3] for k in found] == kinds
    got = _both(_attn(fa.flash_attention, interpret=True, block_q=64,
                      block_k=64, bwd_impl=impl, **mask), do, *ops)
    assert slabs, "every masked tile pair ran whole"
    with jax.default_matmul_precision("highest"):
        want = _both(_attn(fa.mha_reference, **mask), do, *ops)
    assert len(got) == len(want) == 1 + len(ops)
    for g, w in zip(got, want):
        assert _rel(g, w) < 1e-5


@pytest.mark.parametrize("name, mask, blocks", [
    ("causal_non_square_forward", dict(causal=True), (32, 64)),
    ("causal_non_square_backward", dict(causal=True), (64, 32)),
    ("window_non_square", dict(causal=True, window=48), (32, 64)),
])
def test_non_square_blocks_have_kinds_too(name, mask, blocks, sub16, slabs):
    """Xing4.0's forward runs (512, 1024): a causal edge crosses two kinds
    of tile pair there, ``q0 - k0`` 0 and 512."""
    do, ops = _case(256)
    got = _both(_attn(fa.flash_attention, interpret=True, block_q=blocks[0],
                      block_k=blocks[1], **mask), do, *ops)
    assert slabs
    with jax.default_matmul_precision("highest"):
        want = _both(_attn(fa.mha_reference, **mask), do, *ops)
    for g, w in zip(got, want):
        assert _rel(g, w) < 1e-5


@pytest.mark.parametrize("name, mask, t, tk, blocks", [
    ("padded_length", dict(causal=True), 250, None, (64, 64)),
    ("padded_block_diffusion", dict(window=fa.block_diffusion(200, 4)), 200,
     None, (64, 64)),
    ("more_keys_than_queries", dict(causal=True), 128, 256, (64, 64)),
    ("window_no_multiple_of_the_sub_tile", dict(causal=True, window=100),
     256, None, (64, 64)),
    ("ragged_blocks", dict(causal=True), 240, None, (40, 40)),
    ("halves_that_are_no_whole_tiles", dict(
        window=fa.block_diffusion(192, 4)), 192, None, (64, 64)),
])
def test_a_call_without_static_kinds_is_the_whole_tile_path_bit_for_bit(
        name, mask, t, tk, blocks, sub16, slabs, monkeypatch):
    """Padding, ``Tq != Tk``, a window that is no multiple of the sub-tile,
    blocks that are no whole sub-tiles, a mask form whose halves are no whole
    tiles: no slab is traced, and output and gradients are the bits of the
    kernels with the mechanism off (sub-tile 0: the parent's text)."""
    do, ops = _case(t, tk=tk)
    kw = dict(interpret=True, block_q=blocks[0], block_k=blocks[1], **mask)
    got = _both(_attn(fa.flash_attention, **kw), do, *ops)
    assert not slabs
    monkeypatch.setattr(fa, "_SUB_FWD", 0)
    monkeypatch.setattr(fa, "_SUB_BWD", 0)
    want = _both(_attn(fa.flash_attention, **kw), do, *ops)
    for g, w in zip(got, want):
        assert np.array_equal(np.asarray(g), np.asarray(w))
    counts = fa.flash_subtiles(*ops[:3], **{k: v for k, v in kw.items()
                                            if k != "interpret"})
    for c in counts:
        assert c["whole"] and not (c["free"] or c["masked"] or c["skipped"])


@pytest.mark.parametrize("mask", [
    dict(causal=True), dict(causal=True, window=512),
    dict(window=fa.block_diffusion(2048, 4))],
    ids=["causal", "window_512", "block_diffusion_4"])
def test_the_published_sub_tiles(mask, slabs):
    """The module's own constants (no patch): the forward's sub-tiles of
    512 in blocks of 1024 and the backward's of 128 in blocks of 512, at
    head width 64."""
    assert (fa._SUB_FWD, fa._SUB_BWD) == (512, 128)
    do, ops = _case(2048, heads=1, kv_heads=1, d=64)
    got = _both(_attn(fa.flash_attention, interpret=True, block_q=1024,
                      block_k=1024, block_q_bwd=512, block_k_bwd=512,
                      **mask), do, *ops)
    assert slabs
    with jax.default_matmul_precision("highest"):
        want = _both(_attn(fa.mha_reference, **mask), do, *ops)
    for g, w in zip(got, want):
        assert _rel(g, w) < 1e-5


def _dense(mask, t):
    if isinstance(mask.get("window"), fa.BlockDiffusion):
        return np.asarray(mask["window"].dense(t, t))
    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    seen = i >= j
    if mask.get("window"):
        seen &= i - j < mask["window"]
    return seen


@pytest.mark.parametrize("mask, t, blocks, sub", [
    (dict(causal=True), 256, (64, 64), 16),
    (dict(causal=True), 256, (32, 64), 16),
    (dict(causal=True, window=96), 256, (64, 64), 16),
    (dict(causal=True, window=32), 256, (64, 64), 32),
    (dict(window=fa.block_diffusion(256, 4)), 256, (64, 64), 16),
    (dict(window=fa.block_diffusion(256, 32)), 256, (64, 64), 16),
    (dict(window=fa.block_diffusion(256, 64)), 256, (128, 128), 16),
    (dict(causal=True, window=64), 512, (128, 64), 32),
])
def test_the_sub_tile_tables_against_a_brute_count_of_the_mask(mask, t,
                                                               blocks, sub):
    """Every masked tile pair of the grid belongs to one kind, and the
    kind's table says of each of its sub-tiles what the dense mask says:
    dead where nothing is visible, free where everything is."""
    bq, bk = blocks
    kinds = fa._subtile_kinds(mask.get("causal", False), mask.get("window"),
                              bq, bk, t, t, 0, sub)
    dense = _dense(mask, t)
    half = mask["window"].half if "causal" not in mask else 0
    free, masked, dead = fa.grid_tile_pairs(
        mask.get("causal", False), mask.get("window"), bq, bk, t, t)
    n_masked = 0
    for q0 in range(0, t, bq):
        for k0 in range(0, t, bk):
            tile = dense[q0:q0 + bq, k0:k0 + bk]
            if tile.all() or not tile.any():
                continue
            n_masked += 1
            table, = [k[3] for k in kinds
                      if k[:3] == (q0 < half, k0 < half, q0 - k0)]
            for r in range(bq // sub):
                for c in range(bk // sub):
                    part = tile[r * sub:(r + 1) * sub, c * sub:(c + 1) * sub]
                    assert table[r, c] == (
                        fa._FREE if part.all() else
                        fa._EDGE if part.any() else fa._DEAD)
    assert n_masked == masked == sum(k[4] for k in kinds)
    assert free + masked + dead == (t // bq) * (t // bk)


# -- the counter --------------------------------------------------------------

def _lower(attrs, t, heads=8, forward=True, pad=0):
    """One lowering of the op (or its grad op) at ``[1, heads over 1, t,
    128]`` bf16, traced and not run."""
    t = t - pad
    q = jax.ShapeDtypeStruct((1, heads, t, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 1, t, 128), jnp.bfloat16)
    ctx = types.SimpleNamespace()
    if forward:
        return jax.eval_shape(
            lambda q, k, v: attention_ops._flash_attention(
                ctx, {"Q": [q], "K": [k], "V": [v]}, attrs), q, k, k)
    lse = jax.ShapeDtypeStruct((1, heads, t), jnp.float32)
    return jax.eval_shape(
        lambda q, k, v, o, lse, do: attention_ops._flash_attention_grad(
            ctx, {"X$Q": [q], "X$K": [k], "X$V": [v], "Out": [o],
                  "Lse": [lse], "OG$Out": [do]}, attrs), q, k, k, q, lse, q)


def _counted(ctr, **labels):
    return {state: ctr.value(state=state, **labels)
            for state in ("free", "masked", "skipped", "whole", "dead")}


@pytest.mark.parametrize("forward", [True, False], ids=["fwd", "bwd"])
def test_one_lowering_counts_the_cells_masked_tile_pairs_by_sub_tile(
        forward):
    """SDAR's call, ``[., 16384, 128]`` at (1024, 1024) under block
    diffusion in blocks of 4: of the 24 masked tile pairs a head (8 a
    kind), forward at sub-tiles of 512, the clean diagonal's and the noisy x
    clean diagonal's run 3 of 4 (1 free, 2 masked), noisy x noisy's 2
    masked ones: 16 free, 48 masked, 32 skipped; backward at sub-tiles of
    128 the two diagonals run 36 of 64 (28 free, 8 masked) and noisy x
    noisy 8 masked: 448 free, 192 masked, 896 skipped; none whole; and the
    tile pairs' own counter reads 56 / 24 / 176 as before."""
    assert (fa._SUB_FWD, fa._SUB_BWD) == (512, 128)
    labels = dict(mask="block_diffusion", block="4",
                  **{"pass": "fwd" if forward else "bwd"})
    subs, pairs = attention_ops.FLASH_SUBTILES_CTR, \
        attention_ops.FLASH_TILE_PAIRS_CTR
    before = _counted(subs, **labels), _counted(pairs, **labels)
    _lower(dict(block_diffusion=4), 16384, forward=forward)
    after = _counted(subs, **labels), _counted(pairs, **labels)
    moved = [{s: a[s] - b[s] for s in a} for a, b in zip(after, before)]
    assert moved[0] == dict(whole=0, dead=0, **(
        dict(free=16, masked=48, skipped=32) if forward else
        dict(free=448, masked=192, skipped=896)))
    assert moved[1] == dict(free=56, masked=24, dead=176, skipped=0, whole=0)


@pytest.mark.parametrize("name, attrs, t, pad, want", [
    ("causal_8192", dict(causal=True), 8192, 0,
     dict(free=8, masked=16, skipped=8, whole=0)),
    # 8000 rows: no row of the block tables, so (512, 1024), 72 live pairs
    ("causal_padded", dict(causal=True), 8192, 192,
     dict(free=0, masked=0, skipped=0, whole=72)),
    ("window_2048", dict(causal=True, window=2048), 8192, 0,
     dict(free=14, masked=28, skipped=14, whole=0)),
    ("window_no_multiple", dict(causal=True, window=2000), 8192, 0,
     dict(free=0, masked=0, skipped=0, whole=21)),
])
def test_the_counter_under_the_causal_half_and_a_window(name, attrs, t, pad,
                                                        want):
    """Every mask counts: in the forward a causal diagonal tile pair of 1024
    runs 3 of its 4 sub-tiles of 512, a window's trailing edge likewise; a
    padded call and a window that is no multiple of the sub-tile count
    ``whole`` alone."""
    mask = "window" if attrs.get("window") else "causal"
    labels = dict(mask=mask, block="0", **{"pass": "fwd"})
    before = _counted(attention_ops.FLASH_SUBTILES_CTR, **labels)
    _lower(attrs, t, pad=pad)
    after = _counted(attention_ops.FLASH_SUBTILES_CTR, **labels)
    assert {s: after[s] - before[s] for s in want} == want


def test_an_unmasked_call_counts_nothing():
    before = sum(c.get() for _, c in
                 attention_ops.FLASH_SUBTILES_CTR.series())
    _lower({}, 4096)
    assert sum(c.get() for _, c in
               attention_ops.FLASH_SUBTILES_CTR.series()) == before


# -- one trace a distinct call ------------------------------------------------

def test_a_distinct_call_is_traced_once_and_named_by_each_callers_scope(
        monkeypatch):
    """Two layers' calls of one kernel at one set of shapes trace its body
    once; each call's ``pallas_call`` lies under ITS caller's scope (the
    benchmark names device time by them); another precision default,
    another sub-tile or another shape is another trace."""
    traced, real = [], fa._fwd_kernel

    def spy(*a, **kw):
        traced.append(kw["block_q"])
        return real(*a, **kw)
    monkeypatch.setattr(fa, "_fwd_kernel", spy)
    s = jax.ShapeDtypeStruct((2, 2048, 64), jnp.bfloat16)

    def call(q, k, v, block=1024):
        return fa._flash_fwd_pallas(q, k, v, None, True, 0.125, block, block,
                                    0, False)

    def two_layers(q, k, v):
        with jax.named_scope("layer_0"):
            o, _ = call(q, k, v)
        with jax.named_scope("layer_1"):
            return call(o, k, v)
    jaxpr = jax.make_jaxpr(two_layers)(s, s, s)
    assert traced == [1024]
    assert [str(e.source_info.name_stack) for e in jaxpr.eqns
            if e.primitive.name == "pallas_call"] == [
                "layer_0/flash_fwd", "layer_1/flash_fwd"]
    jax.make_jaxpr(two_layers)(s, s, s)
    assert traced == [1024]
    with jax.default_matmul_precision("highest"):
        jax.make_jaxpr(call)(s, s, s)
    monkeypatch.setattr(fa, "_SUB_FWD", 256)
    jax.make_jaxpr(call)(s, s, s)
    jax.make_jaxpr(lambda q, k, v: call(q, k, v, 512))(s, s, s)
    assert traced == [1024, 1024, 1024, 512]


@pytest.mark.parametrize("what", [
    "on_tpu", "_fwd_vmem_bytes", "_FUSED_VMEM_SHARE", "_pos_mask",
    "BlockDiffusion.visible"])
def test_a_replaced_read_of_the_module_is_another_trace(what, monkeypatch):
    """What a trace reads of the module besides the call's arguments is part
    of the kept jaxpr's key: a call under a patched kernel, mask, limit or
    ``on_tpu`` is traced anew, and the patch's end brings the first trace
    back (a leaky mask traced once is not served to the next caller)."""
    traced, real = [], fa._fwd_kernel
    monkeypatch.setattr(fa, "_fwd_kernel",
                        lambda *a, **kw: traced.append(1) or real(*a, **kw))
    s = jax.ShapeDtypeStruct((2, 256, 32), jnp.float32)

    def call():
        jax.make_jaxpr(lambda q, k, v: fa._flash_fwd_pallas(
            q, k, v, None, False, 0.125, 64, 64, 0, True,
            window=fa.block_diffusion(256, 4)))(s, s, s)
    call()
    call()
    assert len(traced) == 1
    owner, _, name = ("fa." + what).rpartition(".")
    owner = fa if owner == "fa" else fa.BlockDiffusion
    old = getattr(owner, name)
    with monkeypatch.context() as m:
        m.setattr(owner, name, 0.5 if isinstance(old, float) else
                  (lambda *a, **kw: old(*a, **kw)))
        call()
        assert len(traced) == 2
    call()
    assert len(traced) == 2


def test_under_the_executors_shard_map_the_kept_jaxpr_serves(sub16):
    """Inside a ``shard_map`` with ``check_vma=False`` (the executor's) a
    call's types are a shard's on the map's mesh: a key of their own, one
    more kept jaxpr a kernel, and a shard's result and gradients are the
    unsharded call's bit for bit.
    With ``check_vma=True`` ``pallas_call`` refuses the kernels themselves
    (their results name no ``vma``), as before there was anything kept."""
    from jax.sharding import Mesh, PartitionSpec as P
    do, ops = _case(256)
    attn = _attn(fa.flash_attention, interpret=True, block_q=64, block_k=64,
                 causal=True)
    want = _both(attn, do, *ops)
    kept = len(fa._TRACED)

    def sharded(check_vma):
        return jax.shard_map(
            lambda do, *ops: _both(attn, do, *ops),
            mesh=Mesh(np.array(jax.devices()[:1]), ("dp",)),
            in_specs=(P("dp"),) * 4, out_specs=(P("dp"),) * 4,
            check_vma=check_vma)(do, *ops)
    got = sharded(False)
    assert len(fa._TRACED) == 2 * kept == 4
    sharded(False)
    assert len(fa._TRACED) == 4
    for g, w in zip(got, want):
        assert np.array_equal(np.asarray(g), np.asarray(w))
    with pytest.raises(ValueError, match="vma"):
        sharded(True)
