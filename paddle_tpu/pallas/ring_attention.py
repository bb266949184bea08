"""Ring attention: exact attention over a sequence-sharded mesh axis.

Each device holds a [b, h, T/n, d] shard of Q, K, V along the sequence.
KV shards rotate around the ``sp`` ring with ``lax.ppermute`` (XLA lowers
this to ICI collective-permute, overlapping the transfer with the current
step's compute) while every step's partial attention merges into the
running online softmax — so the full [T, T] score matrix never exists on
any chip and sequence length scales with the ring size.

The backward pass recomputes per-step tiles from the saved logsumexp
(flash style) and accumulates dK/dV in a buffer that travels around the
ring *with* its KV shard, arriving home after the final rotation.

This is the long-context capability the reference lacks (SURVEY §5.7:
"The reference has NO sequence/context parallelism") — its sequence story
is LoD ragged tensors + ``sequence_ops``; here long sequences are a mesh
axis.  Usable directly under ``shard_map`` or via the ``sp`` axis of
``paddle_tpu.parallel``.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..device import on_tpu
from .flash_attention import (NEG_INF, _flash_bwd_jax, _flash_fwd_jax,
                              _flash_fwd_pallas)


def _chunk_fwd(q, k, v, bias, sm_scale, interpret):
    """(o, lse) of one q-shard vs one kv-shard, Pallas on TPU."""
    if on_tpu() or interpret:
        return _flash_fwd_pallas(q, k, v, bias, False, sm_scale,
                                 128, 128, 0, interpret)
    return _flash_fwd_jax(q, k, v, bias, False, sm_scale, 128, 0)


def _merge(o1, lse1, o2, lse2):
    """Merge two normalized attention partials by their logsumexps."""
    m = jnp.maximum(lse1, lse2)
    m_safe = jnp.where(m <= NEG_INF / 2, 0.0, m)
    w1 = jnp.where(lse1 <= NEG_INF / 2, 0.0, jnp.exp(lse1 - m_safe))
    w2 = jnp.where(lse2 <= NEG_INF / 2, 0.0, jnp.exp(lse2 - m_safe))
    den = w1 + w2
    den_safe = jnp.where(den == 0.0, 1.0, den)
    o = (o1 * (w1 / den_safe)[..., None].astype(o1.dtype)
         + o2 * (w2 / den_safe)[..., None].astype(o2.dtype))
    lse = jnp.where(den == 0.0, NEG_INF, m_safe + jnp.log(den_safe))
    return o, lse


def _causal_bias(my, src, tq, tk):
    """[1, tq, tk] additive bias masking global k_pos > q_pos."""
    q_pos = my * tq + jnp.arange(tq)[:, None]
    k_pos = src * tk + jnp.arange(tk)[None, :]
    return jnp.where(q_pos >= k_pos, 0.0, NEG_INF)[None].astype(jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _ring(q, k, v, axis_name, causal, sm_scale, interpret):
    o, _ = _ring_fwd_impl(q, k, v, axis_name, causal, sm_scale, interpret)
    return o


def _ring_fwd_impl(q, k, v, axis_name, causal, sm_scale, interpret):
    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    bh, tq, d = q.shape
    tk = k.shape[1]

    def step(carry, s):
        o_run, lse_run, kc, vc = carry
        src = (my - s) % n
        bias = _causal_bias(my, src, tq, tk) if causal else None
        o_p, lse_p = _chunk_fwd(q, kc, vc, bias, sm_scale, interpret)
        o_run, lse_run = _merge(o_run, lse_run, o_p, lse_p)
        kc = lax.ppermute(kc, axis_name, perm)
        vc = lax.ppermute(vc, axis_name, perm)
        return (o_run, lse_run, kc, vc), None

    # zeros derived from inputs so scan carries are typed device-varying
    zero = (q[0, 0, 0] + k[0, 0, 0]) * 0
    init = (jnp.zeros((bh, tq, d), q.dtype) + zero,
            jnp.full((bh, tq), NEG_INF, jnp.float32)
            + zero.astype(jnp.float32), k, v)
    (o, lse, _, _), _ = lax.scan(step, init, jnp.arange(n))
    return o, lse


def _ring_vjp_fwd(q, k, v, axis_name, causal, sm_scale, interpret):
    o, lse = _ring_fwd_impl(q, k, v, axis_name, causal, sm_scale, interpret)
    return o, (q, k, v, o, lse)


def _ring_vjp_bwd(axis_name, causal, sm_scale, interpret, res, do):
    q, k, v, o, lse = res
    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    bh, tq, d = q.shape
    tk = k.shape[1]
    # loop-invariant across ring steps: hoist out of the scan
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)

    def step(carry, s):
        dq_acc, dk_acc, dv_acc, kc, vc = carry
        src = (my - s) % n
        bias = _causal_bias(my, src, tq, tk) if causal else None
        dq_p, dk_p, dv_p, _ = _flash_bwd_jax(
            q, kc, vc, bias, o, lse, do, False, sm_scale, 128, 0,
            delta=delta, need_dbias=False)
        dq_acc = dq_acc + dq_p.astype(jnp.float32)
        dk_acc = dk_acc + dk_p.astype(jnp.float32)
        dv_acc = dv_acc + dv_p.astype(jnp.float32)
        # dk/dv accumulators travel the ring with their kv shard
        kc = lax.ppermute(kc, axis_name, perm)
        vc = lax.ppermute(vc, axis_name, perm)
        dk_acc = lax.ppermute(dk_acc, axis_name, perm)
        dv_acc = lax.ppermute(dv_acc, axis_name, perm)
        return (dq_acc, dk_acc, dv_acc, kc, vc), None

    zero = ((q[0, 0, 0] + k[0, 0, 0] + do[0, 0, 0]) * 0
            ).astype(jnp.float32)
    init = (jnp.zeros((bh, tq, d), jnp.float32) + zero,
            jnp.zeros((bh, tk, d), jnp.float32) + zero,
            jnp.zeros((bh, tk, d), jnp.float32) + zero, k, v)
    (dq, dk, dv, _, _), _ = lax.scan(step, init, jnp.arange(n))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_ring.defvjp(_ring_vjp_fwd, _ring_vjp_bwd)


def ring_attention(q, k, v, axis_name: str, causal: bool = False,
                   sm_scale: Optional[float] = None,
                   interpret: bool = False):
    """Sequence-parallel attention on [b, h, T_local, d] shards.

    Call under ``shard_map`` (or pjit with manual axes) with Q/K/V sharded
    along the sequence dimension over ``axis_name``.
    """
    b, h, tq, d = q.shape
    tk = k.shape[2]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    o = _ring(q.reshape(b * h, tq, d), k.reshape(b * h, tk, d),
              v.reshape(b * h, tk, d), axis_name, causal, sm_scale,
              interpret)
    return o.reshape(b, h, tq, d)
