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

import jax
import jax.numpy as jnp

from .. import monitor as _monitor
from ..framework.registry import register_op
from .common import X

FLASH_LOWERINGS_CTR = _monitor.REGISTRY.counter(
    "paddle_tpu_flash_lowerings_total",
    "flash_attention forward lowerings by the window (none = the whole "
    "causal half or no mask), the query heads to a KV head and the "
    "implementation (the Pallas kernels or the blockwise jax fallback) — "
    "counted while tracing, once per compile of a block that holds the op, "
    "nothing per step", ("window", "kv_groups", "impl"))


@register_op("flash_attention")
def _flash_attention(ctx, ins, attrs):
    """Q [b, h, Tq, d]; K, V [b, h_kv, Tk, d] with ``h % h_kv == 0`` (query
    head ``i`` reads KV head ``i // (h // h_kv)``).  ``window`` > 0 with
    ``causal``: key ``j`` is visible to query ``i`` iff ``0 <= i - j <
    window``; such an op's device operations lie under a ``window`` scope
    inside the op's own, so that a trace tells the windowed layers from the
    full ones."""
    from ..device import on_tpu
    from ..pallas import flash_attention
    q, k, v = X(ins, "Q"), X(ins, "K"), X(ins, "V")
    bias = X(ins, "Bias")
    bq, bk = attrs.get("block_q"), attrs.get("block_k")
    window = int(attrs.get("window") or 0) or None
    if window is not None and window >= max(q.shape[2], k.shape[2]):
        window = None
    # the generic grad op lowers this forward again for its vjp: not counted
    if not getattr(ctx, "is_abstract", False) and \
            getattr(ctx, "op_type", "flash_attention") == "flash_attention":
        FLASH_LOWERINGS_CTR.inc(
            window="none" if window is None else str(window),
            kv_groups=str(q.shape[1] // k.shape[1]),
            impl="pallas" if on_tpu() else "jax")
    kw = dict(bias=bias, causal=bool(attrs.get("causal", False)),
              sm_scale=attrs.get("sm_scale") or None,
              block_q=int(bq) if bq else None,  # None → kernel's tuned default
              block_k=int(bk) if bk else None,
              bwd_impl=attrs.get("bwd_impl") or None)
    if window is None:
        return {"Out": [flash_attention(q, k, v, **kw)]}
    with jax.named_scope("window"):
        return {"Out": [flash_attention(q, k, v, window=window, **kw)]}


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


@register_op("rope")
def _rope(ctx, ins, attrs):
    """Rotary position embedding (Su et al. 2021) in the rotate-half
    convention over the whole head: X is [batch, T, n * head_dim], the
    position is the index along axis 1, and within each head dimension ``i``
    pairs with ``i + head_dim / 2`` at the angle ``pos * theta^(-2i /
    head_dim)``.  Angles, sines and the rotation are float32; the output
    has the input's dtype.  Applied before the head split so that it fuses
    with the projection's epilogue and the QK-norm; a 4-D X is [batch,
    heads, T, head_dim], after the split (where a per-head norm comes
    first), with the position along axis 2."""
    x = X(ins, "X")
    dh = int(attrs["head_dim"])
    theta = float(attrs.get("theta", 10000.0))
    half = dh // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / dh)
    t = x.shape[2] if x.ndim == 4 else x.shape[1]
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    if x.ndim == 4:                              # [b, heads, t, dh]
        cos, sin, xf = jnp.cos(ang), jnp.sin(ang), x.astype(jnp.float32)
    else:
        b, _, d = x.shape
        cos = jnp.cos(ang)[None, :, None, :]
        sin = jnp.sin(ang)[None, :, None, :]
        xf = x.astype(jnp.float32).reshape(b, t, d // dh, dh)
    x1, x2 = xf[..., :half], xf[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                          axis=-1)
    return {"Out": [out.reshape(x.shape).astype(x.dtype)]}
