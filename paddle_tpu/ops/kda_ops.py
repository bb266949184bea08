"""Gated delta-rule linear attention with a per-channel decay (KDA, Kimi
Delta Attention: arXiv:2510.26692 §3; the delta rule of arXiv:2406.06484 with
the diagonal gate of arXiv:2412.06464 made a vector a head): the first ops
here whose forward carries state along the sequence.  Per head, ``S`` a
``d_k x d_v`` state that starts at zero::

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

``g_t`` in R^{d_k} is the LOG of the decay (``<= 0``), ``beta_t`` a scalar.
Two ops::

    G, Beta = kda_gate(X, B; ALog, DtBias)   g = -exp(A_log_h) softplus(x + dt_bias)
                                             or, bounded: g = lower_bound
                                             sigmoid(exp(A_log_h) (x + dt_bias))
                                             beta = sigmoid(b)
    Out, States = kda_scan(Q, K, V, G, Beta)     States: S before each chunk

``kda_scan`` runs the recurrence in its chunked (WY / UT-transform) form.  A
chunk of ``C`` positions with the state ``S_0`` before it, ``Gam_i`` the log
decays cumulated inside the chunk up to and including ``i``::

    u_i   = beta_i (v_i - (Diag(exp(g_i)) S_{i-1})^T k_i)      so that
    S_i   = Diag(exp(g_i)) S_{i-1} + k_i u_i^T
    A_ij  = beta_i sum_c k_ic exp(Gam_ic - Gam_jc) k_jc          j <  i
    P_ij  =        sum_c q_ic exp(Gam_ic - Gam_jc) k_jc          j <= i
    (I + A) [W_v | W_k] = Diag(beta) [V | K * exp(Gam)]          unit lower
    U     = W_v - W_k S_0
    O     = (Q * exp(Gam)) S_0 + P U
    S_C   = Diag(exp(Gam_C)) S_0 + (K * exp(Gam_C - Gam))^T U

Two lowerings, chosen from the input (``pallas/kda.py:fits`` beside
``device.on_tpu()``; no attribute, flag or environment variable chooses):

- ``pallas``, on a TPU where ``d_k`` and ``d_v`` are multiples of 128 and the
  chunk one of 16: one kernel forward (``kda_fwd``) and one backward
  (``kda_bwd``) that walk the chunks with the states in VMEM and make ``A``,
  ``P``, the decay differences and the solve there.  The forward writes
  ``States`` (the state before every chunk, float32), ``kda_scan_grad`` reads
  it and runs no forward scan: the backward kernel makes a chunk's tensors
  again and runs the chunk's backward as derived by hand
  (``pallas/kda.py:_chunk_back``), no ``jax.vjp`` inside.
- ``xla`` everywhere else (the CPU, narrow toy heads): :func:`kda_chunked`,
  plain ``jax.numpy``.  ``A``, ``P``, the triangular solve and every product
  that has no ``S_0`` in it are batched matmuls over all chunks at once
  through HBM; what is left is ONE ``lax.scan`` over the ``t / C`` chunk
  states (three small products a step), whose carry fills ``States``;
  ``kda_scan_grad`` is ``jax.vjp`` of the same function, forward again and
  back.  It is the form the tests hold the kernels to.

**Decays enter as differences of cumulated log-decays and never as a
quotient of cumulated products**: ``exp(Gam_i) / exp(Gam_j)`` is ``0 / 0`` or
``x / 0`` once a strong decay has run for a few positions (``exp(-20 * 16)``
is 0 in float32).  Inside a sub-block of ``sub`` (16) positions the
differences are taken exactly, ``exp(Gam_i - Gam_j)`` over ``[sub, sub,
d_k]``; between sub-blocks through the first position ``r`` of the later one,
``exp(Gam_i - Gam_r) exp(Gam_r - Gam_j)`` with ``j < r <= i``: both exponents
are ``<= 0``, so nothing overflows, and a factor that underflows bounds a
product that is as small.  Everything inside is float32 whatever AMP says,
its products at ``highest`` precision (they are small: some 12 MFLOP a chunk
and head); Out comes back in Q's dtype.

``paddle_tpu_kda_lowerings_total{impl}`` counts the lowerings of each."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import monitor as _monitor
from ..framework.core import grad_var_name
from ..framework.registry import register_op
from .common import X

KDA_LOWERINGS_CTR = _monitor.REGISTRY.counter(
    "paddle_tpu_kda_lowerings_total",
    "kda_scan and kda_scan_grad lowerings by the heads held, their width, "
    "the chunk, what implements the op (pallas: the kernels kda_fwd and "
    "kda_bwd, the chunk's tensors in VMEM and the chunk states kept from "
    "forward to backward; xla: jnp that XLA fuses, one lax.scan over the "
    "chunk states, forward again inside the grad op) and whether beta is "
    "doubled (negative eigenvalues) — counted while tracing, once per "
    "compile of a program that holds the op",
    ("heads", "head_dim", "chunk", "impl", "neg_eigval"))

#: positions whose decays are differenced exactly, [sub, sub, d_k] a block
SUB = 16
L2_EPS = 1e-6


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def _block_diag(blocks):
    """[.., m, s, s] -> [.., m s, m s] with the blocks on the diagonal."""
    *lead, m, s, _ = blocks.shape
    eye = jnp.eye(m, dtype=blocks.dtype)[:, None, :, None]
    return (blocks[..., :, :, None, :] * eye).reshape(*lead, m * s, m * s)


def _within_chunks(q, k, gc, sub):
    """``(P, A)`` [.., C, C] of the module's docstring without ``A``'s beta
    and before the triangles are cut, from q, k, gc [.., C, d] (gc the log
    decays cumulated in the chunk)."""
    *lead, c, d = q.shape
    m = c // sub
    qb, kb, gb = (x.reshape(*lead, m, sub, d) for x in (q, k, gc))
    # inside a sub-block: the differences themselves
    i = jnp.arange(sub)
    upto = (i[:, None] >= i[None, :])[:, :, None]
    decay = jnp.exp(jnp.where(
        upto, gb[..., :, None, :] - gb[..., None, :, :], -jnp.inf))
    kd = decay * kb[..., None, :, :]
    p_diag = jnp.sum(qb[..., :, None, :] * kd, axis=-1)
    a_diag = jnp.sum(kb[..., :, None, :] * kd, axis=-1)
    # between sub-blocks: through the later block's first position r
    ref = gb[..., :, :1, :]
    down = jnp.exp(gb - ref)                        # exp(Gam_i - Gam_r)
    earlier = (jnp.arange(c)[None, :] < (jnp.arange(m) * sub)[:, None])
    up = jnp.exp(jnp.where(earlier[:, :, None],     # exp(Gam_r - Gam_j)
                           ref - gc[..., None, :, :], -jnp.inf))
    k_bar = k[..., None, :, :] * up                 # [.., m, C, d]
    p_off = jnp.einsum("...id,...jd->...ij", qb * down, k_bar)
    a_off = jnp.einsum("...id,...jd->...ij", kb * down, k_bar)
    return (p_off.reshape(*lead, c, c) + _block_diag(p_diag),
            a_off.reshape(*lead, c, c) + _block_diag(a_diag))


def kda_chunked(q, k, v, g, beta, *, chunk=64, neg_eigval=False,
                with_states=False):
    """q, k [b, t, h, d_k], v [b, t, h, d_v], g [b, t, h, d_k] (log decay),
    beta [b, t, h] -> o [b, t, h, d_v], float32 (the module's docstring).
    q and k are divided by their norms over d_k first and q scaled by
    ``d_k^-0.5``; ``neg_eigval``: beta doubled, so that ``I - beta k k^T``
    has eigenvalues in (-1, 1).  Everything that has no state in it for all
    ``t / chunk`` chunks at once, then the scan over them.  ``with_states``:
    ``(o, states [b, h, ceil(t / chunk), d_k, d_v])``, the scan's carry
    before every chunk."""
    f32 = jnp.float32
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    q, k, v, g, beta = (x.astype(f32) for x in (q, k, v, g, beta))
    q, k = l2norm(q) * float(dk) ** -0.5, l2norm(k)
    if neg_eigval:
        beta = beta * 2.0
    sub = min(SUB, chunk)
    assert chunk % sub == 0, f"chunk {chunk} is not a multiple of {sub}"
    pad = -t % chunk
    if pad:     # zeros after the end: no key, no write, no decay
        q, k, v, g = (jnp.pad(x, [(0, 0), (0, pad), (0, 0), (0, 0)])
                      for x in (q, k, v, g))
        beta = jnp.pad(beta, [(0, 0), (0, pad), (0, 0)])
    n = (t + pad) // chunk

    def chunks(x):      # [b, T, h, ...] -> [b, h, n, C, ...]
        return jnp.moveaxis(x.reshape(b, n, chunk, *x.shape[2:]), 3, 1)

    q, k, v, g, beta = (chunks(x) for x in (q, k, v, g, beta))
    with jax.default_matmul_precision("highest"):
        gc = jnp.cumsum(g, axis=3)
        p, a = _within_chunks(q, k, gc, sub)
        i = jnp.arange(chunk)
        p = jnp.where(i[:, None] >= i[None, :], p, 0.0)
        a = jnp.where(i[:, None] > i[None, :], a, 0.0) * beta[..., None]
        w = jax.scipy.linalg.solve_triangular(
            a + jnp.eye(chunk, dtype=a.dtype),
            beta[..., None] * jnp.concatenate([v, k * jnp.exp(gc)], axis=-1),
            lower=True, unit_diagonal=True)
        last = gc[..., -1:, :]
        per_chunk = (w[..., :dv], w[..., dv:], q * jnp.exp(gc), p,
                     k * jnp.exp(last - gc), jnp.exp(last[..., 0, :]))

        def step(s, x):
            w_v, w_k, q_g, p_n, k_hat, d_last = x
            u = w_v - w_k @ s
            o = q_g @ s + p_n @ u
            s1 = d_last[..., None] * s + jnp.swapaxes(k_hat, -1, -2) @ u
            return s1, ((o, s) if with_states else o)

        _, o = jax.lax.scan(step, jnp.zeros((b, h, dk, dv), f32),
                            tuple(jnp.moveaxis(x, 2, 0) for x in per_chunk))
    if with_states:
        o, states = o
    # [n, b, h, C, d_v] -> [b, T, h, d_v]
    o = o.transpose(1, 0, 3, 2, 4).reshape(b, n * chunk, h, dv)[:, :t]
    return (o, jnp.moveaxis(states, 0, 2)) if with_states else o


_SCAN_IN = ("Q", "K", "V", "G", "Beta")


def _lowering(ctx, prim, attrs):
    """``(impl, chunk and neg_eigval)`` for the op and its grad op alike,
    decided from the five inputs and the backend, and one count of it."""
    from ..device import on_tpu
    from ..pallas import kda
    q, _, v, *_ = prim
    kw = dict(chunk=int(attrs.get("chunk", 64)),
              neg_eigval=bool(attrs.get("neg_eigval", False)))
    impl = "pallas" if kda.fits(q.shape[3], v.shape[3], kw["chunk"],
                                [x.dtype for x in prim]) and on_tpu() \
        else "xla"
    # shape inference runs the lowering abstractly: uncounted
    if not getattr(ctx, "is_abstract", False):
        KDA_LOWERINGS_CTR.labels(
            heads=str(q.shape[2]), head_dim=str(q.shape[3]),
            chunk=str(kw["chunk"]), impl=impl,
            neg_eigval=str(kw["neg_eigval"]).lower()).inc()
    return impl, kw


def _kda_scan(ctx, ins, attrs):
    """Q, K [b, t, h, d_k], V [b, t, h, d_v], G [b, t, h, d_k] (the log of
    the per-channel decay), Beta [b, t, h] -> Out [b, t, h, d_v] in Q's
    dtype: the gated delta rule in chunks of ``chunk`` positions (the
    module's docstring); Q and K are normalised over d_k inside and Q scaled
    by ``d_k^-0.5``.  States [b, h, ceil(t / chunk), d_k, d_v] float32: the
    state before every chunk, which the kernels' backward starts each chunk
    from; it carries no gradient, and where nothing reads it (the forward
    role's copy of a recomputed segment, the ``xla`` path, whose grad op
    runs the scan again) XLA drops it.  Attributes: ``chunk`` (64; ``t`` is
    padded to a multiple inside), ``neg_eigval`` (Beta doubled)."""
    from ..pallas import kda
    prim = [X(ins, s) for s in _SCAN_IN]
    impl, kw = _lowering(ctx, prim, attrs)
    if impl == "pallas":
        out, states = kda.kda_fwd(*prim, **kw)
    else:
        out, states = kda_chunked(*prim, with_states=True, **kw)
    return {"Out": [out.astype(prim[0].dtype)], "States": [states]}


def _kda_scan_grad_maker(op, block, no_grad_set):
    def wanted(n):
        v = block.var(n) if block.has_var(n) else None
        return n not in no_grad_set and not (v is not None
                                             and v.stop_gradient)
    if not op.output("States"):
        raise ValueError(
            "kda_scan op without a States output (a program built before "
            "the op kept its chunk states): build it again with "
            "layers.kda_scan to train it")
    inputs = {"X$" + s: op.input(s) for s in _SCAN_IN}
    inputs["States"] = op.output("States")
    inputs["OG$Out"] = [grad_var_name(n) for n in op.output("Out")]
    outputs = {"IG$" + s: [grad_var_name(n) if wanted(n) else ""
                           for n in op.input(s)] for s in _SCAN_IN}
    return [{"type": "kda_scan_grad", "inputs": inputs, "outputs": outputs,
             "attrs": dict(op.attrs)}]


register_op("kda_scan", _kda_scan, grad_maker=_kda_scan_grad_maker)


@register_op("kda_scan_grad")
def _kda_scan_grad(ctx, ins, attrs):
    """``kda_scan``'s backward from its five inputs, Out's gradient and the
    forward op's States.  ``pallas``: the backward kernel alone, which walks
    the chunks from the last, starts each from ``States[n]``, makes the
    chunk's tensors again in VMEM and goes back through the chunk by the
    hand-derived equations of ``pallas/kda.py:_chunk_back`` (the transposed
    solve by substitution, stacked products, no product for the decays'
    cotangent); no forward scan runs here and no ``jax.vjp``.  At [1, 8192,
    8, 128] it reads Q, K, V and dOut (16.8 MB each in bf16), G (33.6 MB
    float32), Beta (0.26 MB) and States (67 MB) and writes the five
    gradients in their dtypes; it has no temporary in HBM.  ``xla``: nothing
    of the forward is used, the grad op runs ``kda_chunked`` again and back
    (``jax.vjp``), with ``U``, ``W_v``, ``W_k`` (34 MB each at that shape),
    ``P``, ``A`` (17 MB each) and the [16, 16, 128] decay differences
    (537 MB) as its own temporaries."""
    from ..pallas import kda
    prim = [X(ins, "X$" + s) for s in _SCAN_IN]
    impl, kw = _lowering(ctx, prim, attrs)
    d_out = X(ins, "OG$Out")
    if impl == "pallas":
        if d_out is None:
            d_out = jnp.zeros(prim[2].shape, prim[0].dtype)
        grads = kda.kda_bwd(*prim, X(ins, "States"), d_out, **kw)
    else:
        out, back = jax.vjp(functools.partial(kda_chunked, **kw), *prim)
        cot = jnp.zeros_like(out) if d_out is None \
            else d_out.astype(out.dtype)
        grads = back(cot)
    return {"IG$" + s: [g.astype(p.dtype)]
            for s, g, p in zip(_SCAN_IN, grads, prim)}


KDA_GATE_LOWERINGS_CTR = _monitor.REGISTRY.counter(
    "paddle_tpu_kda_gate_lowerings_total",
    "kda_gate lowerings by the form of the decay's gate (softplus: g = "
    "-exp(A_log) softplus(x + dt_bias), unbounded below; bounded: g = "
    "lower_bound sigmoid(exp(A_log) (x + dt_bias)), in (lower_bound, 0)) "
    "and the rank of the projection that made x ('full' where the layer "
    "projects at full rank, '' where the builder did not say) — counted "
    "while tracing, once per compile of a program that holds the op",
    ("form", "rank"))


@register_op("kda_gate")
def _kda_gate(ctx, ins, attrs):
    """KDA's two gates in float32 whatever AMP says: X [b, t, h d] (the
    decay's projection), B [b, t, h] (beta's logits), ALog [h], DtBias [h d]
    -> G [b, t, h, d], the log of the per-channel decay, and Beta [b, t, h] =
    ``sigmoid(B)`` (``kda_scan`` doubles it where the model allows negative
    eigenvalues).  Two forms of G: ``-exp(ALog_h) softplus(X + DtBias)``
    (``<= 0``, unbounded), and with the attribute ``lower_bound`` (a negative
    number; the fla layer's ``lower_bound`` / "safe" gate) ``lower_bound
    sigmoid(exp(ALog_h) (X + DtBias))``, in ``(lower_bound, 0)``: no channel
    decays faster than ``exp(lower_bound)`` a position.  The attribute
    ``rank`` only labels the count.  The backward is the registry's
    ``jax.vjp`` of this lowering."""
    f32 = jnp.float32
    x, a_log, dt_bias = X(ins, "X"), X(ins, "ALog"), X(ins, "DtBias")
    h = a_log.shape[0]
    bound = attrs.get("lower_bound")
    if not getattr(ctx, "is_abstract", False):
        KDA_GATE_LOWERINGS_CTR.inc(
            form="softplus" if bound is None else "bounded",
            rank=str(attrs.get("rank", "") or ""))
    pre = x.astype(f32) + dt_bias.astype(f32)
    heads = (*x.shape[:-1], h, x.shape[-1] // h)
    if bound is None:
        gate = jax.nn.softplus(pre).reshape(heads)
        g = -jnp.exp(a_log.astype(f32))[:, None] * gate
    else:
        g = float(bound) * jax.nn.sigmoid(
            jnp.exp(a_log.astype(f32))[:, None] * pre.reshape(heads))
    return {"G": [g], "Beta": [jax.nn.sigmoid(X(ins, "B").astype(f32))]}
