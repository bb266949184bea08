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

from ..framework.registry import register_op
from .common import X


@register_op("flash_attention")
def _flash_attention(ctx, ins, attrs):
    from ..pallas import flash_attention
    q, k, v = X(ins, "Q"), X(ins, "K"), X(ins, "V")
    bias = X(ins, "Bias")
    bq, bk = attrs.get("block_q"), attrs.get("block_k")
    out = flash_attention(
        q, k, v, bias=bias, causal=bool(attrs.get("causal", False)),
        sm_scale=attrs.get("sm_scale") or None,
        block_q=int(bq) if bq else None,     # None → kernel's tuned default
        block_k=int(bk) if bk else None,
        bwd_impl=attrs.get("bwd_impl") or None)
    return {"Out": [out]}


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
    with the projection's epilogue and the QK-norm."""
    x = X(ins, "X")
    dh = int(attrs["head_dim"])
    theta = float(attrs.get("theta", 10000.0))
    b, t, d = x.shape
    half = dh // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / dh)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    xf = x.astype(jnp.float32).reshape(b, t, d // dh, dh)
    x1, x2 = xf[..., :half], xf[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                          axis=-1)
    return {"Out": [out.reshape(b, t, d).astype(x.dtype)]}
