"""The state-space scan of Mamba-2 in its chunked (SSD, "state-space duality")
form, arXiv:2405.21060 §6: a scalar decay a head, an input-dependent ``B`` /
``C`` pair shared by the heads of a group, no delta rule.  Per head ``h`` (of
group ``h // (H / G)``), ``S`` a ``P x N`` state that starts at zero::

    Delta_t = softplus(dt_t + dt_bias_h)          (DtBias given; else dt_t)
    a_t     = exp(Delta_t A_h),  A_h = -exp(A_log_h)
    S_t     = a_t S_{t-1} + Delta_t x_t B_t^T
    y_t     = S_t C_t + D_h x_t

Two ops::

    Out, States = ssd_scan(X, Dt, ALog, B, C, D[, DtBias])
    Y = gated_rms_norm(X, Z, Scale)      w * rms_groups(x * silu(z))

``ssd_scan`` runs the recurrence in chunks of ``chunk`` positions.  A chunk
with the state ``S_0`` before it, ``cum_i`` the ``Delta A`` cumulated inside
the chunk up to and including ``i`` (all ``<= 0``)::

    L_ij    = exp(cum_i - cum_j)                   j <= i, else 0
    Y_diag  = ((C B^T) * L) (Delta x)              the chunk on itself
    Y_off_i = exp(cum_i) S_0 C_i                   what came before it
    S_C     = exp(cum_C) S_0 + sum_j exp(cum_C - cum_j) Delta_j x_j B_j^T

Everything that has no state in it is batched over all ``t / chunk`` chunks at
once; what is left, the recurrence over the ``t / chunk`` chunk states, is
ONE product with the ``[n, n]`` matrix of the decays between chunks
(:func:`_between_chunks`: the paper's form, no loop), whose result fills
``States``.  ``ssd_scan_grad`` starts from ``States``: the states' cotangents
(``lambda_c = exp(cum_C) lambda_{c+1} + sum_i exp(cum_i) dY_i C_i^T``) are the
product with that matrix transposed, and every chunk's part of the inputs'
gradients is then the vjp of that chunk's own arithmetic at its saved state,
all chunks at once: the forward's recurrence does not run again.

**Decays enter as differences of cumulated ``Delta A`` and never as a
quotient of cumulated products**, every exponent ``<= 0``, and the causal
mask is applied BEFORE the ``exp`` (``exp(-inf) = 0``), so no ``inf * 0``
reaches a gradient.  Float32 inside whatever AMP says, the products at
``highest`` precision; Out comes back in X's dtype.

Two lowerings of the one algorithm, chosen from the input
(``pallas/ssd.py:fits`` beside ``device.on_tpu()``; no attribute, flag or
environment variable chooses):

- ``pallas``, on a TPU where the chunk is 128, ``t`` a whole number of
  chunks, ``N`` and a group's ``R P`` lanes whole lane tiles and X, B, C
  float32 or bf16: the kernel pair ``ssd_fwd`` / ``ssd_bwd`` of
  ``pallas/ssd.py``.  A grid step is a chunk of a group; the streams are read
  as they lie, ``L`` and ``C B^T`` live in VMEM, the group's states in VMEM
  scratch from chunk to chunk (the recurrence over the chunk states is the
  loop it is, in both directions), and the backward is written by hand.
- ``xla``, everywhere else (the CPU, toy widths, chunk 16, ragged ``t``): the
  ``jax.numpy`` text below, which is also what the kernels are tested
  against.

``paddle_tpu_ssd_lowerings_total{impl, chunk}`` counts the lowerings."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import monitor as _monitor
from ..framework.core import grad_var_name
from ..framework.registry import register_op
from .common import X

SSD_LOWERINGS_CTR = _monitor.REGISTRY.counter(
    "paddle_tpu_ssd_lowerings_total",
    "ssd_scan and ssd_scan_grad lowerings by what implements the op (pallas: "
    "the kernels ssd_fwd and ssd_bwd of pallas/ssd.py, the states in VMEM; "
    "xla: jnp that XLA fuses, the recurrence over the chunk states one "
    "product) and the chunk — counted while tracing, once per compile of a "
    "program that holds the op", ("impl", "chunk"))

_SCAN_IN = ("X", "Dt", "ALog", "B", "C", "D", "DtBias")


def _chunked_inputs(x, dt, a_log, b, c, dt_bias, chunk):
    """The op's inputs in float32 as ``[batch, G, R, n, chunk, ...]`` (``x``
    [.., P], ``delta``) and ``[batch, G, n, chunk, N]`` (``b``, ``c``), ``G``
    groups of ``R`` heads, ``n`` chunks; ``a`` [G, R] the heads' negative
    rates.  ``t`` is padded to a multiple of ``chunk`` with ``Delta = 0``
    (no decay, no write)."""
    f32 = jnp.float32
    bsz, t, h, p = x.shape
    g = b.shape[2]
    r = h // g
    delta = dt.astype(f32)
    if dt_bias is not None:
        delta = jax.nn.softplus(delta + dt_bias.astype(f32))
    x, b, c = (v.astype(f32) for v in (x, b, c))
    pad = -t % chunk
    if pad:
        x, b, c = (jnp.pad(v, [(0, 0), (0, pad), (0, 0), (0, 0)])
                   for v in (x, b, c))
        delta = jnp.pad(delta, [(0, 0), (0, pad), (0, 0)])
    n = (t + pad) // chunk
    x = x.reshape(bsz, n, chunk, g, r, p).transpose(0, 3, 4, 1, 2, 5)
    delta = delta.reshape(bsz, n, chunk, g, r).transpose(0, 3, 4, 1, 2)
    b, c = (v.reshape(bsz, n, chunk, g, -1).transpose(0, 3, 1, 2, 4)
            for v in (b, c))
    return x, delta, -jnp.exp(a_log.astype(f32)).reshape(g, r), b, c


def _own_state(cum, xd, b):
    """What each chunk adds to the state it leaves behind, ``[batch, G, R,
    n, P, N]``: ``sum_j exp(cum_C - cum_j) Delta_j x_j B_j^T``."""
    return jnp.einsum("bgrnj,bgrnjp,bgnjs->bgrnps",
                      jnp.exp(cum[..., -1:] - cum), xd, b)


def _chunks(x, delta, a, b, c, d, s_in):
    """Every chunk from the state before it, all chunks at once: ``(y
    [batch, G, R, n, chunk, P], s_out [batch, G, R, n, P, N])``, the output
    and the state after each chunk (the module's docstring)."""
    chunk = x.shape[4]
    cum = jnp.cumsum(delta * a[:, :, None, None], axis=-1)
    i = jnp.arange(chunk)
    lower = jnp.exp(jnp.where(i[:, None] >= i[None, :],
                              cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))
    xd = x * delta[..., None]
    cb = jnp.einsum("bgnis,bgnjs->bgnij", c, b)
    y = jnp.einsum("bgrnij,bgrnjp->bgrnip", cb[:, :, None] * lower, xd)
    y = y + jnp.einsum("bgrni,bgnis,bgrnps->bgrnip", jnp.exp(cum), c, s_in)
    y = y + x * d.reshape(a.shape)[:, :, None, None, None]
    s_out = jnp.exp(cum[..., -1:])[..., None] * s_in + _own_state(cum, xd, b)
    return y, s_out


def _between_chunks(last):
    """``M`` [batch, G, R, n, n] from each chunk's whole decay ``last``
    [batch, G, R, n] (the ``Delta A`` cumulated over the chunk): ``M[c, d] =
    exp(last_{c+1} + .. + last_{d-1})`` for ``d > c``, else 0: what a state
    left behind chunk ``c`` has decayed to before chunk ``d``.  The
    recurrence over the chunk states is a product with it (the state before
    chunk ``d`` is ``sum_c M[c, d] new_c``) and the recurrence of their
    cotangents a product with its transpose, neither a loop; differences of
    cumulated decays, masked before the ``exp``."""
    upto = jnp.cumsum(last, axis=-1)
    n = jnp.arange(last.shape[-1])
    return jnp.exp(jnp.where(
        n[None, :] > n[:, None],
        (upto - last)[..., None, :] - upto[..., :, None], -jnp.inf))


def _states(x, delta, a, b):
    """The state before every chunk, ``[batch, G, R, n, P, N]``: each
    chunk's own contribution for all chunks at once, then the recurrence
    over the chunks as one product with :func:`_between_chunks`."""
    cum = jnp.cumsum(delta * a[:, :, None, None], axis=-1)
    return jnp.einsum("bgrcd,bgrcps->bgrdps", _between_chunks(cum[..., -1]),
                      _own_state(cum, x * delta[..., None], b))


def _unchunk(y, t):
    """[batch, G, R, n, chunk, P] -> [batch, t, H, P]."""
    bsz, g, r, n, chunk, p = y.shape
    return y.transpose(0, 3, 4, 1, 2, 5).reshape(bsz, n * chunk, g * r,
                                                 p)[:, :t]


def ssd_chunked(x, dt, a_log, b, c, d, dt_bias=None, *, chunk=128,
                with_states=False):
    """x [batch, t, H, P], dt [batch, t, H], a_log, d, dt_bias [H], b, c
    [batch, t, G, N] -> y [batch, t, H, P] float32 (the module's docstring);
    ``with_states``: ``(y, states [batch, H, ceil(t / chunk), P, N])``, the
    state before every chunk."""
    t, (bsz, _, h, p) = x.shape[1], x.shape
    g, s = b.shape[2], b.shape[3]
    xc, delta, a, bc, cc = _chunked_inputs(x, dt, a_log, b, c, dt_bias, chunk)
    with jax.default_matmul_precision("highest"):
        before = _states(xc, delta, a, bc)
        y, _ = _chunks(xc, delta, a, bc, cc, d.astype(jnp.float32), before)
    y = _unchunk(y, t)
    if with_states:
        return y, before.reshape(bsz, h, -1, p, s)
    return y


def _lowering(ctx, prim, attrs):
    """``(impl, chunk)`` for the op and its grad op alike, decided from the
    inputs and the backend, and one count of it."""
    from ..device import on_tpu
    from ..pallas import ssd
    chunk = int(attrs.get("chunk", 128))
    x, _, _, b, c = prim[:5]
    impl = "pallas" if ssd.fits(x.shape, b.shape, chunk,
                                [v.dtype for v in (x, b, c)]) and on_tpu() \
        else "xla"
    # shape inference runs the lowering abstractly: uncounted
    if not getattr(ctx, "is_abstract", False):
        SSD_LOWERINGS_CTR.inc(impl=impl, chunk=str(chunk))
    return impl, chunk


def _ssd_scan(ctx, ins, attrs):
    """X [b, t, H, P], Dt [b, t, H], ALog [H], B, C [b, t, G, N] (head ``h``
    reads group ``h // (H / G)``), D [H], optional DtBias [H] -> Out [b, t,
    H, P] in X's dtype: Mamba-2's recurrence in chunks of ``chunk`` positions
    (the module's docstring).  With DtBias the step is ``softplus(Dt +
    DtBias)``, computed here in float32; without it Dt is the step itself.
    States [b, H, ceil(t / chunk), P, N] float32: the state before every
    chunk, which ``ssd_scan_grad`` starts each chunk from; it carries no
    gradient.  Attribute ``chunk`` (128; ``t`` is padded to a multiple
    inside)."""
    from ..pallas import ssd
    prim = [X(ins, s) for s in _SCAN_IN]
    impl, chunk = _lowering(ctx, prim, attrs)
    if impl == "pallas":
        out, states = ssd.ssd_fwd(*prim)
    else:
        out, states = ssd_chunked(*prim, chunk=chunk, with_states=True)
    return {"Out": [out.astype(prim[0].dtype)], "States": [states]}


def _ssd_scan_grad_maker(op, block, no_grad_set):
    def wanted(n):
        v = block.var(n) if block.has_var(n) else None
        return n not in no_grad_set and not (v is not None
                                             and v.stop_gradient)
    slots = [s for s in _SCAN_IN if op.input(s)]
    inputs = {"X$" + s: op.input(s) for s in slots}
    inputs["States"] = op.output("States")
    inputs["OG$Out"] = [grad_var_name(n) for n in op.output("Out")]
    outputs = {"IG$" + s: [grad_var_name(n) if wanted(n) else ""
                           for n in op.input(s)] for s in slots}
    return [{"type": "ssd_scan_grad", "inputs": inputs, "outputs": outputs,
             "attrs": dict(op.attrs)}]


register_op("ssd_scan", _ssd_scan, grad_maker=_ssd_scan_grad_maker)


@register_op("ssd_scan_grad")
def _ssd_scan_grad(ctx, ins, attrs):
    """``ssd_scan``'s backward from its inputs, Out's gradient and the
    forward op's States.  ``pallas``: the backward kernel, which walks the
    chunks from the last with the states' cotangent in VMEM scratch and goes
    back through each chunk by the hand-derived equations of
    ``pallas/ssd.py``, and a few elementwise passes over ``[b, t, H]`` that
    finish dDt, dALog, dD and dDtBias.  ``xla``: the states' cotangents by
    one product with the decays between chunks, then every chunk's vjp at its
    saved state, all chunks at once (the module's docstring).  On neither
    path does the recurrence over the chunk states run again; a chunk's own
    tensors (its decay matrix, ``C B^T``) are made again, not saved."""
    from ..pallas import ssd
    f32 = jnp.float32
    prim = [X(ins, "X$" + s) for s in _SCAN_IN]
    impl, chunk = _lowering(ctx, prim, attrs)
    x, dt, a_log, b, c, d, dt_bias = prim
    bsz, t, h, p = x.shape
    g = b.shape[2]
    d_out = X(ins, "OG$Out")
    if impl == "pallas":
        if d_out is None:
            d_out = jnp.zeros(x.shape, x.dtype)
        grads = ssd.ssd_bwd(*prim, X(ins, "States"), d_out)
        return {"IG$" + s: [gr.astype(v.dtype)]
                for s, gr, v in zip(_SCAN_IN, grads, prim) if v is not None}
    d_out = jnp.zeros(x.shape, f32) if d_out is None else d_out.astype(f32)
    s_in = X(ins, "States").reshape(bsz, g, h // g, -1, p, b.shape[3])
    n = s_in.shape[3]
    dy = jnp.pad(d_out, [(0, 0), (0, n * chunk - t), (0, 0), (0, 0)]) \
        .reshape(bsz, n, chunk, g, h // g, p).transpose(0, 3, 4, 1, 2, 5)

    def chunks_of(x, dt, a_log, b, c, d, dt_bias):
        xc, delta, a, bc, cc = _chunked_inputs(x, dt, a_log, b, c, dt_bias,
                                               chunk)
        cum = jnp.cumsum(delta * a[:, :, None, None], axis=-1)
        # cum and cc: what the states' cotangents read, not differentiated
        return _chunks(xc, delta, a, bc, cc, d.astype(f32), s_in), (cum, cc)

    with jax.default_matmul_precision("highest"):
        _, back, (cum, cc) = jax.vjp(chunks_of, *prim, has_aux=True)
        # lambda_c, the cotangent of the state BEFORE chunk c; a chunk's
        # vjp takes lambda_{c+1} for the state it leaves behind
        direct = jnp.einsum("bgrni,bgnis,bgrnip->bgrnps", jnp.exp(cum), cc,
                            dy)
        lam_next = jnp.einsum("bgrcd,bgrdps->bgrcps",
                              _between_chunks(cum[..., -1]), direct)
        grads = back((dy, lam_next))
    return {"IG$" + s: [gr.astype(v.dtype)]
            for s, gr, v in zip(_SCAN_IN, grads, prim) if v is not None}


@register_op("gated_rms_norm")
def _gated_rms_norm(ctx, ins, attrs):
    """Mamba-2's gated norm with the gate first (``norm_before_gate``
    false): ``Y = Scale * rms_g(X * silu(Z))``, the RMS over each of
    ``groups`` consecutive groups of the last axis (``mean`` of squares
    ``+ epsilon``), Scale one learned number a channel.  Float32 inside, the
    statistics of the float32 product; Y in X's dtype.  The backward is the
    registry's ``jax.vjp`` of this lowering."""
    f32 = jnp.float32
    x, z, w = X(ins, "X"), X(ins, "Z"), X(ins, "Scale")
    groups = int(attrs.get("groups", 1))
    v = x.astype(f32) * jax.nn.silu(z.astype(f32))
    vg = v.reshape(*v.shape[:-1], groups, v.shape[-1] // groups)
    vg = vg * jax.lax.rsqrt(jnp.mean(vg * vg, axis=-1, keepdims=True)
                            + float(attrs.get("epsilon", 1e-5)))
    return {"Y": [(vg.reshape(v.shape) * w.astype(f32)).astype(x.dtype)]}
