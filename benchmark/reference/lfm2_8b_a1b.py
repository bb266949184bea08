"""LFM2-8B-A1B (Liquid AI, ``LiquidAI/LFM2-8B-A1B``, ``model_type``
``lfm2_moe``, 8.3B-A1.5B): the training loss of a batch in plain float32
``jax.numpy``, matmuls at ``highest`` precision.  No kernels, no sort, no
dispatch: the convolution is three shifted multiplies, attention runs over
dense ``[T, T]`` masks (a block of queries at a time so that 16384 positions
fit), and every held expert's FFN runs over every token and is masked by the
top-k choice, so this shares nothing with the program's ``short_conv`` op,
flash kernels or routing.

One block (``x``, ``z``, ``m`` are ``[T, d]``; ``RMS(v) = w * v /
sqrt(mean(v^2) + eps)``), pre-norm, two norms, no bias anywhere:

    z = RMS1(x)
    conv layer:       [B, C, u] = split3(z W_in)            W_in [d, 3 d]
                      g = B * u
                      c[t] = sum_{j=0..L-1} w[:, j] * g[t - (L - 1) + j]
                                      depthwise over the d channels, zeros
                                      before the sequence starts; w [d, L]
                      op = (C * c) W_out                    W_out [d, d]
                      no activation, no positional term
    attention layer:  q = z Wq -> [T, H, dh];  k = z Wk, v = z Wv -> [T,
                      Hkv, dh];  q = RMS_q(q), k = RMS_k(k) per head, over
                      dh (weights [dh]); then q, k = RoPE(q, k), rotate-half
                      over the whole head, angle pos * theta^(-2i/dh);
                      scores q k^T / sqrt(dh), causal; query head i reads KV
                      head i // (H // Hkv);  op = (softmax(scores) v) Wo
    h = x + op
    m = RMS2(h)
    dense layer:      f = Wd (silu(Wg m) * Wu m)
    expert layer:     s = sigmoid(m Wr) in float32;  sel = top-k(s + b);
                      w = s[sel] / (sum s[sel] + 1e-6) * routed_scaling_factor
                      f = sum_{e in sel, e held here} w_e expert_e(m)
    out = h + f

After the last block a final RMSNorm, then logits over the EMBEDDING TABLE
ITSELF (``hidden @ wte.T``); loss = mean next-token cross-entropy over every
position, nothing else (the published recipe balances load by steering the
selection bias ``b``, which no gradient trains; here ``b`` is whatever the
parameters hold, zero at initialisation).

What ``config.json`` does not say and the published modelling code
(``transformers`` ``lfm2_moe``) does, each also listed in the configuration
file under ``assumed``: the order ``B | C | u`` of the input projection's
three parts; the filter's orientation (a ``Conv1d`` with ``groups = d`` and
``padding = L - 1`` cut to the first T outputs: tap ``j`` reads position ``t
- (L - 1) + j``); the per-head QK-norm before the rotary embedding; the
rotate-half pairing; ``1e-6`` in the renormalising sum; that the selection
bias joins the choice only; that the embedding and the head are one table
(the published 8.34 B parameters is the count with the table shared).
Departure: a parameter tree that holds ``"head_w"`` [d, V] gets an untied
head (the tests plant that fault; the cell never does).

The chip's share: ``gate_w``/``up_w``/``down_w`` hold ``E_here`` experts,
numbers ``expert_offset .. expert_offset + E_here - 1`` of the ``E`` the
router scores.  The weights ``w`` are normalised over all ``k`` chosen, as
published; what the absent experts would add is left out, as in the program.

Everything the loss needs is a sum over tokens, so a caller short of memory
adds up :func:`batch_sums` of one sequence at a time.

Parameters: {"wte" [V, d], "blocks": [{"ln1_w", "ln2_w", and either "in_w"
[d, 3 d], "conv_w" [d, L], "out_w" [d, d] (conv layer) or "wq" [d, H*dh],
"wk" [d, Hkv*dh], "wv", "q_norm_w" [dh], "k_norm_w" [dh], "wo" [H*dh, d]
(attention layer), and either "ffn_gate" [d, F], "ffn_up", "ffn_down" [F, d]
(dense layer) or "router_w" [d, E], "select_bias" [E], "gate_w" [E_here, d,
f], "up_w", "down_w" [E_here, f, d] (expert layer)}], "final_norm_w" [d]}.
"""

import jax
import jax.numpy as jnp

NORM_EPS = 1e-6           # joins the sum that renormalises the kept scores


def rms_norm(z, w, eps):
    return w * z / jnp.sqrt(jnp.mean(jnp.square(z), axis=-1, keepdims=True)
                            + eps)


def rope(x, theta):
    """x [T, H, dh]."""
    t, dh = x.shape[0], x.shape[2]
    half = dh // 2
    freq = theta ** (-2.0 * jnp.arange(half, dtype=jnp.float32) / dh)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None]
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return (x * cos + rot * sin).astype(x.dtype)


def gated(m, wg, wu, wd):
    return (jax.nn.silu(m @ wg) * (m @ wu)) @ wd


def shifted(g, back):
    """``g[t - back]`` over [T, d], zeros before the sequence starts."""
    if back == 0:
        return g
    return jnp.concatenate([jnp.zeros_like(g[:back]), g[:-back]], axis=0)


def short_conv(z, blk):
    """z [T, d] -> the operator's output after ``W_out``, [T, d]: the
    convolution written as its L shifted multiplies."""
    b_, c_, u = jnp.split(z @ blk["in_w"], 3, axis=-1)
    g = b_ * u
    taps = blk["conv_w"].shape[1]
    c = sum(blk["conv_w"][:, j] * shifted(g, taps - 1 - j)
            for j in range(taps))
    return (c_ * c) @ blk["out_w"]


def attention(z, blk, n_head, n_kv_head, d_head, eps, theta, q_block):
    """z [T, d] -> the attention output after ``Wo``, [T, d]."""
    t = z.shape[0]
    q = rope(rms_norm((z @ blk["wq"]).reshape(t, n_head, d_head),
                      blk["q_norm_w"], eps), theta)
    k = rope(rms_norm((z @ blk["wk"]).reshape(t, n_kv_head, d_head),
                      blk["k_norm_w"], eps), theta)
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
        s = jnp.einsum("qhd,khd->hqk", qb, k) / jnp.sqrt(
            jnp.asarray(d_head, q.dtype))
        s = jnp.where((j <= i)[None], s, -jnp.inf)
        return None, jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1),
                                v)

    # one block of queries after another (a scan, so that neither the pass
    # nor its gradient holds two blocks' scores at once)
    _, o = jax.lax.scan(rows, None, (
        q.reshape(t // q_block, q_block, n_head, d_head),
        jnp.arange(0, t, q_block)))
    return o.reshape(t, n_head * d_head) @ blk["wo"]


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


def block(x, blk, n_head, n_kv_head, d_head, top_k, eps, theta, route_scale,
          expert_offset, q_block):
    """x [T, d] -> (out [T, d], top_e [T, k] or None).  The kind of operator
    and of FFN is read off the block's own keys."""
    z = rms_norm(x, blk["ln1_w"], eps)
    if "conv_w" in blk:
        h = x + short_conv(z, blk)
    else:
        h = x + attention(z, blk, n_head, n_kv_head, d_head, eps, theta,
                          q_block)
    m = rms_norm(h, blk["ln2_w"], eps)
    if "ffn_gate" in blk:
        return h + gated(m, blk["ffn_gate"], blk["ffn_up"],
                         blk["ffn_down"]), None
    f, top_e = routed_experts(m, blk, top_k, route_scale, expert_offset)
    return h + f, top_e


def head_ce(hidden, table, labels, rows, untied=None):
    """The summed cross-entropy of hidden [N, d] under the head that reads
    the embedding ``table`` [V, d] (``untied`` [d, V]: that weight in its
    place), ``rows`` positions at a time (a gradient computes each block's
    logits again)."""
    n = hidden.shape[0]
    if n % rows:
        rows = n

    @jax.checkpoint
    def some(total, xs):
        h, y = xs
        logits = h @ table.T if untied is None else h @ untied
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return total - jnp.sum(
            jnp.take_along_axis(logp, y[:, None], axis=-1)), None

    total, _ = jax.lax.scan(some, jnp.float32(0.0), (
        hidden.reshape(n // rows, rows, -1), labels.reshape(n // rows, rows)))
    return total


def batch_sums(params, ids, labels, n_head, n_kv_head, d_head, top_k, eps,
               theta, route_scale=1.0, expert_offset=0, q_block=512):
    """Everything the loss needs of ids/labels [B, T], as sums over their
    tokens: {"tokens", "ce"}; and, not sums, "top_e" [L_expert, B*T, k]
    (each token's experts, all ``E`` numbered) and "hidden" [B, T, d] (the
    final RMSNorm's output, what the head reads)."""
    with jax.default_matmul_precision("highest"):
        d = params["wte"].shape[1]
        hidden, tops = [], []
        for b in range(ids.shape[0]):
            h = params["wte"][ids[b]]
            seq_tops = []
            for blk in params["blocks"]:
                # checkpointed: a gradient at 16384 positions keeps a
                # block's input and computes its inside again (the values
                # are the same either way)
                h, top_e = jax.checkpoint(
                    lambda h, blk: block(
                        h, blk, n_head, n_kv_head, d_head, top_k, eps, theta,
                        route_scale, expert_offset, q_block))(h, blk)
                if top_e is not None:
                    seq_tops.append(top_e)
            hidden.append(rms_norm(h, params["final_norm_w"], eps))
            tops.append(jnp.stack(seq_tops))
        hidden = jnp.stack(hidden)
        ce = head_ce(hidden.reshape(-1, d), params["wte"],
                     labels.reshape(-1), q_block, params.get("head_w"))
        return {"tokens": jnp.float32(ids.size), "ce": ce,
                "top_e": jnp.concatenate(tops, axis=1), "hidden": hidden}


def loss_of_sums(sums):
    """{"loss"} from :func:`batch_sums` (or the element-wise sum of
    several)."""
    return {"loss": sums["ce"] / sums["tokens"]}


def loss(params, ids, labels, **kw):
    """The training loss of a whole batch; ``jax.grad`` of it gives the
    reference gradients (the table's leaf holds the sum of the lookup's and
    the head's)."""
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
    "n_head", "n_kv_head", "d_head", "top_k", "eps", "theta", "route_scale",
    "expert_offset", "q_block"))
