"""Solar-Open2-250B (Upstage, ``upstage/Solar-Open2-250B``, ``model_type``
``solar_open2``, 250B-A15B): the training loss of a batch in plain float32
``jax.numpy``, matmuls at ``highest`` precision.  No chunks, no WY form, no
kernels, no sort, no dispatch: the linear-attention layers run their
recurrence TOKEN BY TOKEN (a ``lax.scan`` over the positions, the state
updated by multiplies and sums, no matmul inside), the softmax layers over
dense ``[T, T]`` masks a block of queries at a time, and every held expert's
FFN runs over every token and is masked by the top-k choice; so this shares
nothing with the program's ``kda_scan`` / ``short_conv`` ops, flash kernels
or routing.

One block (``x``, ``z``, ``m`` are ``[T, d]``; ``RMS(v) = w * v /
sqrt(mean(v^2) + eps)``), pre-norm, two norms, no bias anywhere:

    z = RMS1(x)
    KDA layer (per head, d_k = d_v = 128, everything float32):
        c(y)[t] = sum_{j=0..3} w[:, j] * y[t - 3 + j]       depthwise, causal,
                                        zeros before the sequence starts
        q_t = l2norm(silu(c(z Wq))_t) * 128^-0.5
        k_t = l2norm(silu(c(z Wk))_t);  v_t = silu(c(z Wv))_t
                                        l2norm(y) = y / sqrt(sum y^2 + 1e-6)
        g_t = -exp(A_log_h) * softplus(z Wf_down Wf_up + dt_bias)    [128]
        beta_t = 2 sigmoid(z Wbeta)                                 scalar
        S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
        o_t = S_t^T q_t                                 S_0 = 0, S [128, 128]
        op  = [RMS_head(o_t) * sigmoid(z Wg_down Wg_up)] Wo
                                        RMS_head over each head's 128, a
                                        [128] weight
    GQA layer:  q = z Wq -> [T, H, 128];  k = z Wk, v = z Wv -> [T, Hkv, 128]
        scores q k^T * 128^-0.5, causal over all earlier positions, NO
        positional term; query head i reads KV head i // (H // Hkv)
        op = [softmax(scores) v * sigmoid(z Wgate)] Wo
    h = x + op
    m = RMS2(h)
    s = sigmoid(m Wr) in float32;  sel = top-k(s + b);
    w = s[sel] / (sum s[sel] + 1e-20) * routed_scaling_factor
    out = h + shared(m) + sum_{e in sel, e held here} w_e expert_e(m)
                                        expert(m) = Wd (silu(Wg m) * Wu m)

After the last block a final RMSNorm and an untied head; loss = mean
next-token cross-entropy over every position, nothing else.

Departures from the published description, each also in the configuration
file under ``assumed``: ``config.json`` is silent on the layer equations,
which are Kimi Linear's (arXiv:2510.26692 and the ``fla`` KDA layer published
with it) without the one bias that layer has (on the output gate's
up-projection); the routing is the family's published one (``config.json``
gives no ``scoring_func``); the selection bias ``b`` is whatever the
parameters hold, zero at initialisation, and no gradient trains it.

The chip's share: ``wq``/``wk``/``wv``/... hold the heads HELD here (under
tensor parallelism a chip holds a share of a layer's heads, each at its
published 128), ``gate_w``/``up_w``/``down_w`` the ``E_here`` experts held,
numbers ``expert_offset .. expert_offset + E_here - 1`` of the ``E`` the
router scores; the output projections give the partial sum over the held
heads, the experts' part the partial sum over the held experts, and what the
absent ones would add is left out, as in the program.  The shared expert, the
router and the norms are whole.

The 8192-step recurrence is checkpointed in blocks of ``kda_block``
positions, so that a gradient keeps one state a block (8192 states of 8 x 64
KB would be 4.3 GB a layer) and runs each block's steps again.

Parameters: {"wte" [V, d], "blocks": [{"ln1_w", "ln2_w", either (GQA) "wq"
[d, H*dh], "wk" [d, Hkv*dh], "wv", "w_gate" [d, H*dh], "wo" [H*dh, d] or
(KDA) "wq", "wk", "wv" [d, Hk*dh], "conv_q", "conv_k", "conv_v" [Hk*dh, 4],
"wf_down" [d, r], "wf_up" [r, Hk*dh], "a_log" [Hk], "dt_bias" [Hk*dh],
"w_beta" [d, Hk], "wg_down" [d, r], "wg_up" [r, Hk*dh], "o_norm_w" [dh],
"wo" [Hk*dh, d]; and "shared_gate" [d, f], "shared_up", "shared_down" [f, d],
"router_w" [d, E], "select_bias" [E], "gate_w" [E_here, d, f], "up_w",
"down_w" [E_here, f, d]}], "final_norm_w" [d], "head_w" [d, V]}.
"""

import jax
import jax.numpy as jnp

NORM_EPS = 1e-20          # joins the sum that renormalises the kept scores
L2_EPS = 1e-6


def rms_norm(z, w, eps):
    return w * z / jnp.sqrt(jnp.mean(jnp.square(z), axis=-1, keepdims=True)
                            + eps)


def gated(m, wg, wu, wd):
    return (jax.nn.silu(m @ wg) * (m @ wu)) @ wd


def shifted(g, back):
    """``g[t - back]`` over [T, d], zeros before the sequence starts."""
    if back == 0:
        return g
    return jnp.concatenate([jnp.zeros_like(g[:back]), g[:-back]], axis=0)


def conv_silu(y, w):
    """y [T, c], w [c, L] -> silu of the causal depthwise convolution, tap
    ``j`` reading position ``t - (L - 1) + j``."""
    taps = w.shape[1]
    return jax.nn.silu(sum(w[:, j] * shifted(y, taps - 1 - j)
                           for j in range(taps)))


def l2norm(y):
    return y / jnp.sqrt(jnp.sum(jnp.square(y), axis=-1, keepdims=True)
                        + L2_EPS)


def delta_rule(q, k, v, g, beta, block):
    """The recurrence itself, one head: q, k, g [T, dk], v [T, dv], beta [T]
    -> o [T, dv].  Token by token; ``block`` positions a checkpoint."""
    t, dk = q.shape
    dv = v.shape[1]
    if t % block:
        block = t

    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = jnp.exp(g_t)[:, None] * s                   # Diag(alpha) S
        read = jnp.sum(s * k_t[:, None], axis=0)        # S^T k
        s = s + b_t * k_t[:, None] * (v_t - read)[None, :]
        return s, jnp.sum(s * q_t[:, None], axis=0)     # S^T q

    @jax.checkpoint
    def some(s, xs):
        return jax.lax.scan(step, s, xs)

    _, o = jax.lax.scan(some, jnp.zeros((dk, dv), q.dtype), tuple(
        x.reshape(t // block, block, *x.shape[1:])
        for x in (q, k, v, g, beta)))
    return o.reshape(t, dv)


def kda(z, blk, d_head, eps, neg_eigval, kda_block):
    """z [T, d] -> the KDA layer's output after ``Wo``, [T, d]."""
    t = z.shape[0]
    h = blk["a_log"].shape[0]

    def heads(y):
        return y.reshape(t, h, d_head)

    q = l2norm(heads(conv_silu(z @ blk["wq"], blk["conv_q"]))) \
        * d_head ** -0.5
    k = l2norm(heads(conv_silu(z @ blk["wk"], blk["conv_k"])))
    v = heads(conv_silu(z @ blk["wv"], blk["conv_v"]))
    g = -jnp.exp(blk["a_log"])[None, :, None] * heads(jax.nn.softplus(
        (z @ blk["wf_down"]) @ blk["wf_up"] + blk["dt_bias"]))
    beta = jax.nn.sigmoid(z @ blk["w_beta"]) * (2.0 if neg_eigval else 1.0)
    o = jax.vmap(lambda *a: delta_rule(*a, kda_block), in_axes=1,
                 out_axes=1)(q, k, v, g, beta)
    o = rms_norm(o, blk["o_norm_w"], eps).reshape(t, h * d_head)
    gate = jax.nn.sigmoid((z @ blk["wg_down"]) @ blk["wg_up"])
    return (o * gate) @ blk["wo"]


def attention(z, blk, d_head, q_block):
    """z [T, d] -> the gated attention output after ``Wo``, [T, d]; the
    heads read off the weights' widths."""
    t = z.shape[0]
    n_head = blk["wq"].shape[1] // d_head
    n_kv_head = blk["wk"].shape[1] // d_head
    q = (z @ blk["wq"]).reshape(t, n_head, d_head)
    k = (z @ blk["wk"]).reshape(t, n_kv_head, d_head)
    v = (z @ blk["wv"]).reshape(t, n_kv_head, d_head)
    group = n_head // n_kv_head
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    j = jnp.arange(t)[None, :]
    if t % q_block:
        q_block = t

    @jax.checkpoint          # a gradient keeps no block's [H, q_block, T]
    def rows(_, xs):
        qb, start = xs
        i = start + jnp.arange(q_block)[:, None]
        s = jnp.einsum("qhd,khd->hqk", qb, k) * d_head ** -0.5
        s = jnp.where((j <= i)[None], s, -jnp.inf)
        return None, jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1),
                                v)

    _, o = jax.lax.scan(rows, None, (
        q.reshape(t // q_block, q_block, n_head, d_head),
        jnp.arange(0, t, q_block)))
    o = o.reshape(t, n_head * d_head) * jax.nn.sigmoid(z @ blk["w_gate"])
    return o @ blk["wo"]


def route(m, blk, top_k, route_scale):
    """``(weight [S, E], top_e [S, k])``: each token's weight on every
    expert (zero off its top-k), over all ``E`` the router scores."""
    s = jax.nn.sigmoid(m.astype(jnp.float32)
                       @ blk["router_w"].astype(jnp.float32))
    _, top_e = jax.lax.top_k(s + blk["select_bias"].astype(jnp.float32),
                             top_k)
    chosen = jnp.sum(jax.nn.one_hot(top_e, s.shape[-1], dtype=s.dtype),
                     axis=1)
    kept = s * chosen
    w = kept / (jnp.sum(kept, axis=-1, keepdims=True) + NORM_EPS)
    return w * route_scale, top_e


def routed_experts(m, blk, top_k, route_scale, expert_offset=0):
    """m [S, d] -> ``(out [S, d], top_e [S, k])``: the part of the routed
    experts' output that the experts held in ``blk`` give."""
    weight, top_e = route(m, blk, top_k, route_scale)
    held = blk["gate_w"].shape[0]
    w_here = jax.lax.dynamic_slice_in_dim(weight, expert_offset, held, axis=1)

    @jax.checkpoint          # a gradient computes each expert's FFN again
    def one_expert(acc, xs):
        wg, wu, wd, w_e = xs
        return acc + w_e[:, None].astype(m.dtype) * gated(m, wg, wu, wd), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(m),
                          (blk["gate_w"], blk["up_w"], blk["down_w"],
                           w_here.T))
    return out, top_e


def mixer(z, blk, d_head, eps, neg_eigval, q_block, kda_block):
    """The block's sequence mixer, read off the block's own keys."""
    if "a_log" in blk:
        return kda(z, blk, d_head, eps, neg_eigval, kda_block)
    return attention(z, blk, d_head, q_block)


def block(x, blk, d_head, top_k, eps, route_scale, expert_offset, neg_eigval,
          q_block, kda_block):
    """x [T, d] -> (out [T, d], top_e [T, k])."""
    h = x + mixer(rms_norm(x, blk["ln1_w"], eps), blk, d_head, eps,
                  neg_eigval, q_block, kda_block)
    m = rms_norm(h, blk["ln2_w"], eps)
    f, top_e = routed_experts(m, blk, top_k, route_scale, expert_offset)
    shared = gated(m, blk["shared_gate"], blk["shared_up"],
                   blk["shared_down"])
    return h + shared + f, top_e


def head_ce(hidden, head_w, labels, rows):
    """The summed cross-entropy of hidden [N, d] under ``head_w`` [d, V],
    ``rows`` positions at a time (a gradient computes each block's logits
    again)."""
    n = hidden.shape[0]
    if n % rows:
        rows = n

    @jax.checkpoint
    def some(total, xs):
        h, y = xs
        logp = jax.nn.log_softmax((h @ head_w).astype(jnp.float32), axis=-1)
        return total - jnp.sum(
            jnp.take_along_axis(logp, y[:, None], axis=-1)), None

    total, _ = jax.lax.scan(some, jnp.float32(0.0), (
        hidden.reshape(n // rows, rows, -1), labels.reshape(n // rows, rows)))
    return total


def batch_sums(params, ids, labels, d_head, top_k, eps, route_scale=1.0,
               expert_offset=0, neg_eigval=True, q_block=512, kda_block=128):
    """Everything the loss needs of ids/labels [B, T], as sums over their
    tokens: {"tokens", "ce"}; and, not sums, "top_e" [L, B*T, k] (each
    token's experts, all ``E`` numbered) and "hidden" [B, T, d] (the final
    RMSNorm's output, what the head reads)."""
    with jax.default_matmul_precision("highest"):
        d = params["wte"].shape[1]
        hidden, tops = [], []
        for b in range(ids.shape[0]):
            h = params["wte"][ids[b]]
            seq_tops = []
            for blk in params["blocks"]:
                # checkpointed: a gradient keeps a block's input and
                # computes its inside again (the values are the same)
                h, top_e = jax.checkpoint(
                    lambda h, blk: block(
                        h, blk, d_head, top_k, eps, route_scale,
                        expert_offset, neg_eigval, q_block, kda_block))(
                            h, blk)
                seq_tops.append(top_e)
            hidden.append(rms_norm(h, params["final_norm_w"], eps))
            tops.append(jnp.stack(seq_tops))
        hidden = jnp.stack(hidden)
        ce = head_ce(hidden.reshape(-1, d), params["head_w"],
                     labels.reshape(-1), q_block)
        return {"tokens": jnp.float32(ids.size), "ce": ce,
                "top_e": jnp.concatenate(tops, axis=1), "hidden": hidden}


def loss_of_sums(sums):
    """{"loss"} from :func:`batch_sums` (or the element-wise sum of
    several)."""
    return {"loss": sums["ce"] / sums["tokens"]}


def loss(params, ids, labels, **kw):
    """The training loss of a whole batch; ``jax.grad`` of it gives the
    reference gradients."""
    return loss_of_sums(batch_sums(params, ids, labels, **kw))["loss"]


def warmup_rate(step, learning_rate, warmup_steps, start):
    """The rate of step ``step`` (0 the first): linear from ``start`` to
    ``learning_rate`` over ``warmup_steps`` steps, ``learning_rate`` after."""
    if step >= warmup_steps:
        return float(learning_rate)
    return start + (learning_rate - start) * step / float(warmup_steps)


def adamw(p, steps, weight_decay, beta1=0.9, beta2=0.999, eps=1e-8):
    """One parameter after AdamW steps from zeroed moments, ``steps`` a list
    of ``(rate, gradient)``, in float64 numpy on the host: Adam as Kingma &
    Ba's section 2 closes it (``rate_t = rate sqrt(1 - beta2^t) / (1 -
    beta1^t)``, ``p -= rate_t m / (sqrt(v) + eps)``: the epsilon beside the
    uncorrected second moment, which is the ``adam`` op's definition in the
    framework this repo rebuilds) with Loshchilov & Hutter's decoupled decay
    (``p -= rate weight_decay p``, of the parameter before the step)."""
    import numpy as np
    p = np.asarray(p, np.float64)
    m, v = np.zeros_like(p), np.zeros_like(p)
    for t, (rate, g) in enumerate(steps, 1):
        g = np.asarray(g, np.float64)
        m = beta1 * m + (1 - beta1) * g
        v = beta2 * v + (1 - beta2) * np.square(g)
        rate_t = rate * (1 - beta2 ** t) ** 0.5 / (1 - beta1 ** t)
        p = p - rate_t * m / (np.sqrt(v) + eps) - rate * weight_decay * p
    return p


sequence_sums = jax.jit(batch_sums, static_argnames=(
    "d_head", "top_k", "eps", "route_scale", "expert_offset", "neg_eigval",
    "q_block", "kda_block"))
