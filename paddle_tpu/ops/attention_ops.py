"""Fused attention ops backed by the Pallas kernels.

The reference builds attention from separate matmul/softmax/dropout ops
(``tests/unittests/dist_transformer.py:1034``); these ops fuse the whole
pattern so the [b, h, T, T] score matrix never reaches HBM.

- ``flash_attention``: single-device fused attention (Pallas on TPU).
- ``ring_attention``: the same contract, but when the active mesh has an
  ``sp`` axis the sequence dimension is sharded and KV shards rotate over
  the ring (``paddle_tpu.pallas.ring_attention``); without an sp axis it
  degrades to flash attention, so programs are portable across meshes.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

from .. import monitor as _monitor
from ..framework.core import grad_var_name
from ..framework.registry import register_op
from .common import X

FLASH_LOWERINGS_CTR = _monitor.REGISTRY.counter(
    "paddle_tpu_flash_lowerings_total",
    "flash_attention forward lowerings by the window (none = the whole "
    "causal half or no mask), the query heads to a KV head, the "
    "implementation (the Pallas kernels or the blockwise jax fallback) and "
    "the two widths (widths = d_qk/d_v, e.g. 128/128, or d_qk+d_r/d_v where "
    "the score is two products, the op's QRope and KRope slots: latent "
    "attention's 128+64/128) and the form the forward kernel writes lse in at "
    "these shapes "
    "(lse = row: [bh, 1, Tq], what the backward reads, wherever a query "
    "block fills lanes; lanes: the [bh, Tq, 128] broadcast of which one "
    "lane is kept, at ragged toy blocks) — counted while tracing, once per "
    "compile of a block that holds the op, nothing per step",
    ("window", "kv_groups", "impl", "widths", "lse"))

FLASH_GRAD_LOWERINGS_CTR = _monitor.REGISTRY.counter(
    "paddle_tpu_flash_grad_lowerings_total",
    "flash_attention_grad lowerings, labelled as the forward's: the grad op "
    "that takes the forward op's Out and Lse and runs the backward kernels "
    "alone (with a bias, the blockwise jax backward on every backend) — "
    "counted while tracing, once per compile, nothing per step",
    ("window", "kv_groups", "impl", "widths"))

FLASH_BWD_KERNEL_CTR = _monitor.REGISTRY.counter(
    "paddle_tpu_flash_bwd_kernel_total",
    "flash_attention_grad lowerings by the backward they got: fused (one "
    "pass, a head's dQ resident in VMEM), split (a dQ pass and a dK/dV "
    "pass: what a sequence too long for the fused accumulator runs) or "
    "jax (the blockwise fallback: a bias, or no TPU) — counted beside "
    "paddle_tpu_flash_grad_lowerings_total, once per compile, nothing per "
    "step", ("kernel", "window", "widths"))

FLASH_MASK_LOWERINGS_CTR = _monitor.REGISTRY.counter(
    "paddle_tpu_flash_mask_lowerings_total",
    "flash_attention and flash_attention_grad lowerings by the mask's form "
    "(none, causal, window, block_diffusion), the block length of a "
    "block-diffusion mask (0 otherwise), the implementation (pallas or the "
    "blockwise jax fallback) and the kernel (fwd; fused, split or jax for "
    "the grad op) — counted while tracing, once per compile, nothing per "
    "step", ("mask", "block", "impl", "kernel"))

FLASH_TILE_PAIRS_CTR = _monitor.REGISTRY.counter(
    "paddle_tpu_flash_tile_pairs_total",
    "the tile pairs of one head's grid in a flash lowering under a mask "
    "form, by what the kernels do with them at the call's blocks: free "
    "(every pair visible: no mask runs), masked (an edge crosses the tile) "
    "or dead (skipped, and its K/V tile not copied); pass = fwd or bwd — "
    "counted while tracing, once per compile, nothing per step",
    ("mask", "block", "pass", "state"))

FLASH_SUBTILES_CTR = _monitor.REGISTRY.counter(
    "paddle_tpu_flash_subtiles_total",
    "of the MASKED tile pairs of one head's grid in a flash lowering "
    "(causal, window or a mask form; the states of "
    "paddle_tpu_flash_tile_pairs_total are theirs), what the kernels run "
    "of them at the call's blocks: their sub-tiles by state, free (run "
    "mask-free), masked (the mask runs on the sub-tile) or skipped (no "
    "product, no exponential), where the tile pair's live region is static "
    "and it is run by sub-tiles; whole: the masked tile pairs that run "
    "all of their scores (padding, Tq != Tk, ragged blocks, a window that "
    "is no multiple of the sub-tile, a bias); pass = fwd or bwd — counted "
    "while tracing, once per compile, nothing per step",
    ("mask", "block", "pass", "state"))


def _flash_call(ctx, attrs, q, k, v, counter, pallas, more_labels=None,
                q_rope=None, k_rope=None, bias=None):
    """What the op and its grad op share: ``(window or None, the attributes
    as keyword arguments of the kernel's entry points, the widths label)``,
    and one count of the lowering in ``counter`` (``pallas``: whether a TPU
    would run the kernels here; ``more_labels``: a function of those keyword
    arguments that gives the counter's further labels).  ``q_rope`` /
    ``k_rope``: the op's optional slots, handed on among the keyword
    arguments only where they are given, so that a call without them is the
    call it always was.  Under the attribute ``block_diffusion`` the window
    returned, and handed to the kernels, is the mask form
    (:func:`_mask_form`)."""
    from ..device import on_tpu
    bq, bk = attrs.get("block_q"), attrs.get("block_k")
    window = int(attrs.get("window") or 0) or None
    if window is not None and window >= max(q.shape[2], k.shape[2]):
        window = None
    if attrs.get("block_diffusion"):
        window = _mask_form(attrs, q, k)
    kw = dict(
        causal=bool(attrs.get("causal", False)),
        sm_scale=attrs.get("sm_scale") or None,
        block_q=int(bq) if bq else None,  # None → kernel's tuned default
        block_k=int(bk) if bk else None,
        bwd_impl=attrs.get("bwd_impl") or None, window=window)
    widths = f"{q.shape[3]}/{v.shape[3]}"
    if q_rope is not None:
        kw.update(q_rope=q_rope, k_rope=k_rope)
        widths = f"{q.shape[3]}+{q_rope.shape[3]}/{v.shape[3]}"
    if not getattr(ctx, "is_abstract", False):
        counter.inc(window="none" if window is None else str(window),
                    kv_groups=str(q.shape[1] // k.shape[1]),
                    impl="pallas" if pallas and on_tpu() else "jax",
                    widths=widths,
                    **(more_labels(kw) if more_labels else {}))
        _count_mask(kw, q, k, v, pallas, counter is FLASH_LOWERINGS_CTR,
                    bias)
    return window, kw, widths


def _mask_form(attrs, q, k):
    """The op's ``block_diffusion`` attribute (the block length B; the rows
    are the noisy copy and the clean copy of one sequence, T = 2L) as the
    kernels' mask form.  The form is the whole mask: no ``causal``, no
    ``window`` beside it, and self-attention only."""
    from ..pallas.flash_attention import block_diffusion
    if attrs.get("causal") or attrs.get("window") or \
            q.shape[2] != k.shape[2]:
        raise ValueError(
            "flash_attention: block_diffusion is the whole mask of a "
            "self-attention (no causal, no window, Tq == Tk); got causal="
            f"{attrs.get('causal')}, window={attrs.get('window')}, Tq="
            f"{q.shape[2]}, Tk={k.shape[2]}")
    return block_diffusion(q.shape[2], attrs["block_diffusion"])


def _count_mask(kw, q, k, v, pallas, forward, bias=None):
    """One count of a lowering by its mask's form; under a mask form, of
    its grid's tile pairs by what becomes of them; and under any mask, of
    its masked tile pairs' sub-tiles (``bias``: the op's, under which every
    masked tile pair runs whole)."""
    from ..device import on_tpu
    from ..pallas.flash_attention import (BlockDiffusion, flash_blocks,
                                          flash_bwd_kernel, flash_subtiles)
    window = kw["window"]
    form = isinstance(window, BlockDiffusion)
    mask = window.scope if form else "window" if window is not None else \
        "causal" if kw["causal"] else "none"
    block = str(window.block) if form else "0"
    kernel = "fwd" if forward else "jax" if not pallas else \
        flash_bwd_kernel(q, k, v, **kw)
    FLASH_MASK_LOWERINGS_CTR.inc(
        mask=mask, block=block, kernel=kernel,
        impl="pallas" if pallas and on_tpu() else "jax")
    which = {"pass": "fwd" if forward else "bwd"}
    if form:
        blocks = flash_blocks(q, k, v, **kw)[0 if forward else 1]
        for state, n in window.tile_pairs(*blocks).items():
            FLASH_TILE_PAIRS_CTR.inc(n, mask=mask, block=block, state=state,
                                     **which)
    if mask != "none":
        for state, n in flash_subtiles(q, k, v, bias=bias, **kw)[
                0 if forward else 1].items():
            if n:
                FLASH_SUBTILES_CTR.inc(n, mask=mask, block=block,
                                       state=state, **which)


def _window_scope(window):
    """A windowed layer's device operations lie under a ``window`` scope
    inside the op's own, forward and backward, so that a trace tells the
    windowed layers from the full ones; a full layer's lie under none, and
    a mask form's under the form's own name (``block_diffusion``)."""
    if window is None:
        return contextlib.nullcontext()
    return jax.named_scope(
        "window" if isinstance(window, int) else window.scope)


def _flash_attention(ctx, ins, attrs):
    """Q [b, h, Tq, d_qk]; K [b, h_kv, Tk, d_qk] and V [b, h_kv, Tk, d_v]
    with ``h % h_kv == 0`` (query head ``i`` reads KV head ``i // (h //
    h_kv)``); ``d_v`` is V's own and may differ from ``d_qk`` (latent
    attention: 192 over 128).  ``window`` > 0 with ``causal``: key ``j`` is
    visible to query ``i`` iff ``0 <= i - j < window``.  ``block_diffusion``
    = B > 0 (alone: no ``causal``, no ``window``, Tq == Tk): the rows are a
    noisy and a clean copy of one sequence in blocks of B under block
    diffusion's three-part mask, inside the kernels
    (``pallas.flash_attention.BlockDiffusion``).  Outputs: Out [b, h,
    Tq, d_v] (shape inference runs this lowering abstractly, so Out's and
    the grad op's shapes follow V's) and Lse [b, h, Tq] float32, each
    query's log-sum-exp over its visible keys, which ``flash_attention_grad``
    rebuilds the probabilities from (an op without that slot still runs:
    the executor binds the slots an op names).

    Optional QRope [b, h, Tq, d_r] and KRope [b, h_r, Tk, d_r] with ``h %
    h_r == 0`` (latent attention: ``h_r`` 1, ``d_r`` 64 beside 128-wide Q
    and K): the score of a pair is ``(q·k + q_rope·k_rope) · sm_scale``, the
    kernels' second product into the same tile, query head ``i`` reading
    rotary head ``i // (h // h_r)``; ``sm_scale`` stays the caller's.  The
    code observes its input: the second product exists where the two slots
    are given, and an op without them lowers as before they existed.  The
    counters then label ``widths`` ``d_qk+d_r/d_v``."""
    from ..pallas.flash_attention import (flash_attention_fwd,
                                          flash_lse_layout)
    q, k, v = X(ins, "Q"), X(ins, "K"), X(ins, "V")
    window, kw, _ = _flash_call(
        ctx, attrs, q, k, v, FLASH_LOWERINGS_CTR, pallas=True,
        more_labels=lambda kw: {"lse": flash_lse_layout(q, k, v, **kw)},
        q_rope=X(ins, "QRope"), k_rope=X(ins, "KRope"), bias=X(ins, "Bias"))
    with _window_scope(window):
        out, lse = flash_attention_fwd(q, k, v, X(ins, "Bias"), **kw)
        # Lse leaves with Out: where the kernel still writes the lane-
        # broadcast [b * h, Tq, 128] form (ragged blocks), XLA:TPU without
        # the barrier sinks the slice that makes the [b, h, Tq] rows into
        # the backward and keeps that buffer until then
        out, lse = jax.lax.optimization_barrier((out, lse))
    return {"Out": [out], "Lse": [lse]}


def _flash_attention_grad_maker(op, block, no_grad_set):
    if not op.output("Lse"):
        raise ValueError(
            "flash_attention op without an Lse output (a program built "
            "before the op saved its log-sum-exp): build it again with "
            "layers.flash_attention to train it")
    slots = [s for s in ("Q", "K", "V", "Bias", "QRope", "KRope")
             if op.input(s)]
    g_inputs = {"X$" + s: op.input(s) for s in slots}
    g_inputs["Out"], g_inputs["Lse"] = op.output("Out"), op.output("Lse")
    g_inputs["OG$Out"] = [grad_var_name(n) for n in op.output("Out")]
    g_outputs = {"IG$" + s: ["" if n in no_grad_set else grad_var_name(n)
                             for n in op.input(s)] for s in slots}
    # the [Tq, Tk] tiles of a bias's gradient are kept only for a reader
    attrs = dict(op.attrs, need_dbias=any(g_outputs.get("IG$Bias", ())))
    return [{"type": "flash_attention_grad", "inputs": g_inputs,
             "outputs": g_outputs, "attrs": attrs}]


register_op("flash_attention", _flash_attention,
            grad_maker=_flash_attention_grad_maker)


@register_op("flash_attention_grad")
def _flash_attention_grad(ctx, ins, attrs):
    """The backward of ``flash_attention`` from what the forward saved: the
    backward kernels on (Q, K, V, Out, Lse, dOut) and no forward kernel (the
    generic vjp ran it a second time for these two residuals; XLA does not
    merge two Mosaic calls).  With a bias the blockwise jax backward, on
    every backend, and dBias only where the grad maker found a reader.
    Where the forward had QRope and KRope the same kernels take them and
    return their gradients too, KRope's summed over the query heads that
    read each of its heads."""
    from ..pallas.flash_attention import (flash_attention_bwd,
                                          flash_bwd_kernel)
    q, k, v = X(ins, "X$Q"), X(ins, "X$K"), X(ins, "X$V")
    bias, out, d_out = X(ins, "X$Bias"), X(ins, "Out"), X(ins, "OG$Out")
    window, kw, widths = _flash_call(
        ctx, attrs, q, k, v, FLASH_GRAD_LOWERINGS_CTR, pallas=bias is None,
        q_rope=X(ins, "X$QRope"), k_rope=X(ins, "X$KRope"), bias=bias)
    if not getattr(ctx, "is_abstract", False):
        FLASH_BWD_KERNEL_CTR.inc(
            kernel=flash_bwd_kernel(q, k, v, bias, **kw),
            window="none" if window is None else str(window), widths=widths)
    d_out = jnp.zeros_like(out) if d_out is None else d_out.astype(out.dtype)
    with _window_scope(window):
        dq, dk, dv, db, *d_rope = flash_attention_bwd(
            q, k, v, bias, out, X(ins, "Lse"), d_out,
            need_dbias=bool(attrs.get("need_dbias", True)), **kw)
    grads = {"IG$Q": [dq], "IG$K": [dk], "IG$V": [dv], "IG$Bias": [db]}
    if d_rope:
        grads["IG$QRope"], grads["IG$KRope"] = [d_rope[0]], [d_rope[1]]
    return grads


@register_op("ring_attention")
def _ring_attention(ctx, ins, attrs):
    from ..parallel.mesh import current_mesh
    q, k, v = X(ins, "Q"), X(ins, "K"), X(ins, "V")
    causal = bool(attrs.get("causal", False))
    sm_scale = attrs.get("sm_scale") or None
    axis = attrs.get("axis_name", "sp") or "sp"

    mesh = current_mesh()
    if mesh is not None and axis in mesh.axis_names and \
            mesh.shape[axis] > 1:
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from ..pallas import ring_attention as _ring
        spec = P(None, None, axis, None)
        fn = shard_map(
            lambda q_, k_, v_: _ring(q_, k_, v_, axis, causal=causal,
                                     sm_scale=sm_scale),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
        return {"Out": [fn(q, k, v)]}

    from ..pallas import flash_attention
    return {"Out": [flash_attention(q, k, v, causal=causal,
                                    sm_scale=sm_scale)]}


ROPE_LOWERINGS_CTR = _monitor.REGISTRY.counter(
    "paddle_tpu_rope_lowerings_total",
    "rope and rope_grad lowerings by the form they got (kernel: one pass of "
    "pallas/rope.py over the tensor; xla: the jnp form, wherever the "
    "kernel's conditions fail: no TPU, a head that is not 64 (4-D) or whole "
    "128-lane tiles wide, a ragged length, a mesh of several devices), the "
    "pairing (half: i with i + head_dim / 2; interleaved: 2i with 2i + 1) "
    "the width of what is rotated (head_dim) and where the frequencies come "
    "from (theta: theta^(-2i / head_dim) computed in the program; table: a "
    "constant worked out on the host from the op's rope_scaling attribute) — "
    "counted while tracing, once per compile of a block that holds the op, "
    "nothing per step",
    ("form", "pairing", "width", "frequencies"))


def _rope_xla(x, dh, theta, interleaved, rope_scaling=None):
    """The jnp form: float32 angles, sines and rotation, X's dtype out."""
    from ..pallas.rope import angles
    half = dh // 2
    t = x.shape[2] if x.ndim == 4 else x.shape[1]
    ang = angles(t, dh, theta, rope_scaling)
    if x.ndim == 4:                              # [b, heads, t, dh]
        cos, sin, xf = jnp.cos(ang), jnp.sin(ang), x.astype(jnp.float32)
    else:
        b, _, d = x.shape
        cos = jnp.cos(ang)[None, :, None, :]
        sin = jnp.sin(ang)[None, :, None, :]
        xf = x.astype(jnp.float32).reshape(b, t, d // dh, dh)
    if interleaved:
        # adjacent pairs: (2i, 2i + 1) turn by the angle of frequency i
        x1, x2 = xf[..., 0::2], xf[..., 1::2]
        out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
        return out.reshape(x.shape).astype(x.dtype)
    x1, x2 = xf[..., :half], xf[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                          axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _rope_call(ctx, x, attrs, transpose):
    """What the op and its grad op share: ``x`` turned (``transpose``: turned
    back) in the form its shape gets, and one count of the lowering.  The
    kernel where everything the lowering can see allows it: a TPU, one
    device, ``pallas.rope.fits``; else the jnp form, whose transpose is
    ``jax.vjp``'s."""
    from ..device import on_tpu
    from ..pallas import rope as kernel
    dh = int(attrs["head_dim"])
    theta = float(attrs.get("theta", 10000.0))
    interleaved = bool(attrs.get("interleaved"))
    scaling = attrs.get("rope_scaling")
    # shape inference runs this lowering abstractly: the jnp form, uncounted
    abstract = getattr(ctx, "is_abstract", False)
    mesh = ctx.mesh
    use_kernel = not abstract and (mesh is None or mesh.size == 1) and \
        kernel.fits(x.shape, dh, x.dtype) and on_tpu()
    if not abstract:
        ROPE_LOWERINGS_CTR.inc(
            form="kernel" if use_kernel else "xla",
            pairing="interleaved" if interleaved else "half", width=str(dh),
            frequencies="theta" if scaling is None else "table")
    if use_kernel:
        return kernel.rope(x, dh, theta, interleaved, transpose=transpose,
                           rope_scaling=scaling)
    if transpose:
        return jax.vjp(lambda v: _rope_xla(v, dh, theta, interleaved,
                                           scaling), x)[1](x)[0]
    return _rope_xla(x, dh, theta, interleaved, scaling)


def _rope(ctx, ins, attrs):
    """Rotary position embedding (Su et al. 2021) in the rotate-half
    convention over the whole head: X is [batch, T, n * head_dim], the
    position is the index along axis 1, and within each head dimension ``i``
    pairs with ``i + head_dim / 2`` at the angle ``pos * f_i``.  The
    frequencies have two forms: ``f_i = theta^(-2i / head_dim)``, computed in
    the program from the attribute ``theta`` (the default), or, with the
    attribute ``rope_scaling`` (a configuration's YaRN group: ``factor``,
    ``original_max_position_embeddings``, ``beta_fast``, ``beta_slow``), a
    ``[head_dim / 2]`` table worked out on the host and held as a constant
    (``pallas.rope.yarn_frequencies``: each pair's blend of the original and
    the ``factor`` times slower frequency; the same table at every length,
    sines and cosines unscaled).  Both forms go through the same kernel or
    the same jnp.  Angles, sines and the rotation are float32; the output
    has the input's dtype.  A 4-D X is [batch, heads, T, head_dim], after
    the head split (where a per-head norm comes first), with the position
    along axis 2.  ``interleaved`` (default false): dimension ``2i`` pairs
    with ``2i + 1`` at the same angle, the pairing of the DeepSeek family's
    ``rope_interleave`` (its code permutes each pair's members to the two
    halves and rotates halves; permuted alike on Q and K the scores are
    those of this pairwise rotation).  ``head_dim`` may be a slice of the
    head: the caller splits the rotary part off and hands that over.

    Neither form fuses with what is round it.  On a TPU, on one device, a
    head of 64 (4-D) or whole 128-lane tiles and a length that divides into
    blocks get ``pallas/rope.py``: one pass over the tensor, a custom call
    that the norm before it and the flash kernel behind it end at.  Every
    other shape keeps the jnp form, which XLA:TPU compiles to a float32
    copy of the tensor, its two halves and a pad that glues them (or two
    gathers and a relayout for ``interleaved``): four to nine times the
    tensor's bytes (PERF.md section 5, PR 43), with the norm before the 3-D
    form riding its first fusion and nothing of the projection's matmul.
    ``paddle_tpu_rope_lowerings_total`` says which form each lowering got.
    The gradient is the op's own, ``rope_grad``: the same rotation turned
    back over Out's gradient, and nothing of the forward."""
    return {"Out": [_rope_call(ctx, X(ins, "X"), attrs, transpose=False)]}


def _rope_grad_maker(op, block, no_grad_set):
    # a rotation is linear in X: its gradient reads Out's gradient alone
    def wanted(n):
        v = block.var(n) if block.has_var(n) else None
        return n not in no_grad_set and not (v is not None
                                             and v.stop_gradient)
    return [{"type": "rope_grad",
             "inputs": {"OG$Out": [grad_var_name(n)
                                   for n in op.output("Out")]},
             "outputs": {"IG$X": [grad_var_name(n) if wanted(n) else ""
                                  for n in op.input("X")]},
             "attrs": dict(op.attrs)}]


register_op("rope", _rope, grad_maker=_rope_grad_maker)


@register_op("rope_grad")
def _rope_grad(ctx, ins, attrs):
    """The transpose of ``rope``: Out's gradient turned back by the same
    angles, in the form the forward's shape got."""
    return {"IG$X": [_rope_call(ctx, X(ins, "OG$Out"), attrs,
                                transpose=True)]}
