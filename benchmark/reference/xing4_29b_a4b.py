"""Xing4.0-29B-A4B (``XingChen-AGI/Xing4.0-29B-A4B``, ``model_type``
``xing4_0``, 29B-A4B): the training loss of a batch in plain float32
``jax.numpy``, matmuls at ``highest`` precision.  No kernels, no sort, no
dispatch, no op of the program: dense ``[T, T]`` masks a block of queries at a
time, every held expert's FFN over every token masked by the top-k choice,
Sinkhorn-Knopp as a Python loop of ``hc_sinkhorn_iters`` over ``[T, n, n]``
matrices, YaRN's frequency table from its formulas.  It imports nothing of
``paddle_tpu`` and nothing of another configuration's reference.

The residual stream is ``n = hc_mult`` streams wide: ``X [T, n, C]``.  Entry:
the embedding of a token copied to every stream.  Exit: ``z = RMS_f(sum_i
X_L[i])``, then the untied head; loss = mean next-token cross-entropy.

A manifold-constrained hyper-connection round a sublayer ``F``
(arXiv:2512.24880 §4), with ``x = vec(X)`` [n C] and ``r = (mean(x^2) +
rms_norm_eps)^-1/2``::

    m = r * (x Phi)                      Phi [n C, 2 n + n^2]: pre | post | res
    H_pre  = sigmoid(alpha_pre m[0:n] + b[0:n])                      [n]
    H_post = 2 sigmoid(alpha_post m[n:2n] + b[n:2n])                 [n]
    A      = clip(alpha_res mat_{n x n}(m[2n:]) + mat(b[2n:]), lo, hi)
    H_res  = SK(A): M = exp(A); 20 times: M <- M / (sum over each column +
             hc_eps), then M <- M / (sum over each row + hc_eps)
    u  = sum_j H_pre[j] X[j]                                         [C]
    y  = F(RMS(u))                       the block's ln1 / ln2
    X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y

A block is that twice: ``F`` = latent attention, then ``F`` = the FFN.

Latent attention (``a`` = the normed ``u``, [T, d])::

    c_q = RMS_q(a W_qa);  [q_nope | q_rope] = c_q W_qb       [T, H, dn | dr]
    [c_kv | k_r] = a W_kva;  c_kv = RMS_kv(c_kv)
    [k_nope | v] = c_kv W_kvb                                [T, H, dn | dv]
    q_rope, k_r rotated as published (``rope_interleave``: each adjacent
        pair's members moved to the two halves, the halves rotated) by the
        angle pos * f'_i, YaRN's table: f_i = theta^(-2i/dr); c(beta) = dr
        ln(original / (2 pi beta)) / (2 ln theta); lo = floor(c(beta_fast)),
        hi = ceil(c(beta_slow)); g_i = clip((i - lo) / (hi - lo), 0, 1);
        f'_i = (1 - g_i) f_i + g_i f_i / factor; cos and sin unscaled
        (mscale / mscale_all_dim = 1)
    k = [k_nope | k_r for every head];  q = [q_nope | q_rope]
    s = q k^T (dn + dr)^-1/2 (0.1 mscale_all_dim ln(factor) + 1)^2, causal,
        softmax;  o = s v;  out = o W_o

FFN (``m`` = the normed ``u``): a dense layer ``Wd (silu(Wg m) * Wu m)``; an
expert layer ``shared(m) + sum_{e in sel, e held here} w_e expert_e(m)`` with
``s = sigmoid(m Wr)`` in float32, ``sel`` = the ``top_k`` largest of ``s +
b``, ``w = s[sel] / (sum s[sel] + 1e-20) * routed_scaling_factor``.

What ``config.json`` names and does not spell out, each also under
``assumed`` in the configuration file: the entry and exit of the streams
(arXiv:2409.19606 §3); Sinkhorn-Knopp's order (columns, then rows), ``hc_eps``
in both denominators and the clamp before the exponential; the latent norms,
the shared rotary key and the ``1e-20`` (the family's code); the selection
bias ``b``, whatever the parameters hold.

The chip's share: ``gate_w``/``up_w``/``down_w`` hold ``E_here`` experts,
numbers ``expert_offset .. expert_offset + E_here - 1`` of the ``E`` the
router scores; what the absent experts would add is left out.

Parameters: {"wte" [V, d], "blocks": [{"hc_attn": {"phi" [n d, 2 n + n^2],
"alpha" [3], "bias" [2 n + n^2]}, "hc_ffn": the same, "ln1_w", "w_qa" [d,
r_q], "q_norm_w", "w_qb" [r_q, H (dn + dr)], "w_kva" [d, r_kv + dr],
"kv_norm_w", "w_kvb" [r_kv, H (dn + dv)], "wo" [H dv, d], "ln2_w", and either
"ffn_gate" [d, F], "ffn_up", "ffn_down" [F, d] or "shared_gate" [d, f],
"shared_up", "shared_down" [f, d], "router_w" [d, E], "select_bias" [E],
"gate_w" [E_here, d, f], "up_w", "down_w" [E_here, f, d]}], "final_norm_w"
[d], "head_w" [d, V]}.
"""

import math

import jax
import jax.numpy as jnp

NORM_EPS = 1e-20          # joins the sum that renormalises the kept scores


def rms_norm(z, w, eps):
    return w * z / jnp.sqrt(jnp.mean(jnp.square(z), axis=-1, keepdims=True)
                            + eps)


def gated(m, wg, wu, wd):
    return (jax.nn.silu(m @ wg) * (m @ wu)) @ wd


def yarn_frequencies(d_rope, theta, yarn):
    """[d_rope / 2] float32.  ``yarn`` = (factor, original length, beta_fast,
    beta_slow, mscale, mscale_all_dim)."""
    factor, original, beta_fast, beta_slow = yarn[:4]
    half = d_rope // 2

    def c(beta):
        return d_rope * math.log(original / (2 * math.pi * beta)) / (
            2 * math.log(theta))

    lo, hi = max(math.floor(c(beta_fast)), 0), \
        min(math.ceil(c(beta_slow)), d_rope - 1)
    out = []
    for i in range(half):
        f = theta ** (-2.0 * i / d_rope)
        g = min(max((i - lo) / float(hi - lo), 0.0), 1.0)
        out.append((1.0 - g) * f + g * f / factor)
    return jnp.asarray(out, jnp.float32)


def softmax_scale(d_nope, d_rope, yarn):
    mscale = 0.1 * yarn[5] * math.log(yarn[0]) + 1.0
    return (d_nope + d_rope) ** -0.5 * mscale * mscale


def rope_published(x, freq):
    """x [T, H, dr] -> the same shape, each adjacent pair's members moved to
    the two halves and the halves rotated by pos * freq (the family's
    ``apply_rotary_pos_emb`` under ``rope_interleave``)."""
    t, h, dr = x.shape
    x = x.reshape(t, h, dr // 2, 2).transpose(0, 1, 3, 2).reshape(t, h, dr)
    half = dr // 2
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None]
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rot * sin


def attention(a, blk, n_head, d_nope, d_rope, d_v, eps, theta, yarn,
              q_block):
    """a [T, d] -> the latent attention's output after ``W_o``, [T, d]."""
    t = a.shape[0]
    r_kv = blk["kv_norm_w"].shape[0]
    freq = yarn_frequencies(d_rope, theta, yarn)
    scale = softmax_scale(d_nope, d_rope, yarn)
    q = (rms_norm(a @ blk["w_qa"], blk["q_norm_w"], eps)
         @ blk["w_qb"]).reshape(t, n_head, d_nope + d_rope)
    ckv = a @ blk["w_kva"]
    kv = (rms_norm(ckv[:, :r_kv], blk["kv_norm_w"], eps)
          @ blk["w_kvb"]).reshape(t, n_head, d_nope + d_v)
    k_r = rope_published(ckv[:, None, r_kv:], freq)           # one head
    q = jnp.concatenate([q[..., :d_nope],
                         rope_published(q[..., d_nope:], freq)], axis=-1)
    k = jnp.concatenate([kv[..., :d_nope], jnp.broadcast_to(
        k_r, (t, n_head, d_rope))], axis=-1)
    v = kv[..., d_nope:]
    j = jnp.arange(t)[None, :]
    if t % q_block:
        q_block = t

    @jax.checkpoint          # a gradient keeps no block's [H, q_block, T]
    def rows(_, xs):
        qb, start = xs
        i = start + jnp.arange(q_block)[:, None]
        s = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        s = jnp.where((j <= i)[None], s, -jnp.inf)
        return None, jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1),
                                v)

    _, o = jax.lax.scan(rows, None, (
        q.reshape(t // q_block, q_block, n_head, d_nope + d_rope),
        jnp.arange(0, t, q_block)))
    return o.reshape(t, n_head * d_v) @ blk["wo"]


def route(m, blk, top_k, route_scale):
    """``(weight [S, E], top_e [S, k])``: each token's weight on every
    expert the router scores, zero off its top-k."""
    s = jax.nn.sigmoid(m @ blk["router_w"])
    _, top_e = jax.lax.top_k(s + blk["select_bias"], top_k)
    kept = s * jnp.sum(jax.nn.one_hot(top_e, s.shape[-1], dtype=s.dtype),
                       axis=1)
    return kept / (jnp.sum(kept, axis=-1, keepdims=True) + NORM_EPS) \
        * route_scale, top_e


def routed_experts(m, blk, top_k, route_scale, expert_offset=0):
    """m [S, d] -> ``(out [S, d], top_e [S, k])``: the part of the routed
    experts' output that the experts held in ``blk`` give."""
    weight, top_e = route(m, blk, top_k, route_scale)
    held = blk["gate_w"].shape[0]
    w_here = jax.lax.dynamic_slice_in_dim(weight, expert_offset, held, axis=1)

    @jax.checkpoint          # a gradient computes each expert's FFN again
    def one_expert(acc, xs):
        wg, wu, wd, w_e = xs
        return acc + w_e[:, None] * gated(m, wg, wu, wd), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(m),
                          (blk["gate_w"], blk["up_w"], blk["down_w"],
                           w_here.T))
    return out, top_e


def sinkhorn(a, iters, eps):
    """a [T, n, n] logits -> [T, n, n]; axis 1 runs down a column."""
    m = jnp.exp(a)
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)    # each column
        m = m / (jnp.sum(m, axis=2, keepdims=True) + eps)    # each row
    return m


def hc_maps(x, hc, eps, hc_iters, hc_eps, hc_clamp):
    """X [T, n, C] -> ``(H_pre [T, n], H_post [T, n], H_res [T, n, n])``."""
    t, n, _ = x.shape
    vec = x.reshape(t, -1)
    r = 1.0 / jnp.sqrt(jnp.mean(jnp.square(vec), axis=-1, keepdims=True)
                       + eps)
    m = r * (vec @ hc["phi"])
    alpha, b = hc["alpha"], hc["bias"]
    h_pre = jax.nn.sigmoid(alpha[0] * m[:, :n] + b[:n])
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * m[:, n:2 * n] + b[n:2 * n])
    a = jnp.clip(alpha[2] * m[:, 2 * n:] + b[2 * n:], hc_clamp[0],
                 hc_clamp[1]).reshape(t, n, n)
    return h_pre, h_post, sinkhorn(a, hc_iters, hc_eps)


def hyper_connection(x, hc, sublayer, eps, hc_iters, hc_eps, hc_clamp):
    """X [T, n, C] -> ``(X', what else the sublayer returns)``; ``sublayer``
    takes ``u`` [T, C] and returns ``(y [T, C], extra)``."""
    h_pre, h_post, h_res = hc_maps(x, hc, eps, hc_iters, hc_eps, hc_clamp)
    y, extra = sublayer(jnp.einsum("tj,tjc->tc", h_pre, x))
    return jnp.einsum("tij,tjc->tic", h_res, x) \
        + h_post[:, :, None] * y[:, None, :], extra


def block(x, blk, n_head, d_nope, d_rope, d_v, top_k, eps, theta, yarn,
          route_scale, expert_offset, q_block, hc_iters, hc_eps, hc_clamp):
    """X [T, n, C] -> (X', top_e [T, k] or None)."""
    def attn(u):
        return attention(rms_norm(u, blk["ln1_w"], eps), blk, n_head, d_nope,
                         d_rope, d_v, eps, theta, yarn, q_block), None

    def ffn(u):
        m = rms_norm(u, blk["ln2_w"], eps)
        if "ffn_gate" in blk:
            return gated(m, blk["ffn_gate"], blk["ffn_up"],
                         blk["ffn_down"]), None
        routed, top_e = routed_experts(m, blk, top_k, route_scale,
                                       expert_offset)
        return gated(m, blk["shared_gate"], blk["shared_up"],
                     blk["shared_down"]) + routed, top_e

    hc = (eps, hc_iters, hc_eps, hc_clamp)
    x, _ = hyper_connection(x, blk["hc_attn"], attn, *hc)
    return hyper_connection(x, blk["hc_ffn"], ffn, *hc)


def entry(e, hc_mult):
    """The embeddings [T, d] copied to every stream: [T, n, d]."""
    return jnp.broadcast_to(e[:, None, :], (e.shape[0], hc_mult, e.shape[1]))


def head_ce(hidden, head_w, labels, rows):
    """The summed cross-entropy of hidden [N, d] under the untied head,
    ``rows`` positions at a time (a gradient computes each block's logits
    again)."""
    n = hidden.shape[0]
    if n % rows:
        rows = n

    @jax.checkpoint
    def some(total, xs):
        h, y = xs
        logp = jax.nn.log_softmax(h @ head_w, axis=-1)
        return total - jnp.sum(
            jnp.take_along_axis(logp, y[:, None], axis=-1)), None

    total, _ = jax.lax.scan(some, jnp.float32(0.0), (
        hidden.reshape(n // rows, rows, -1), labels.reshape(n // rows, rows)))
    return total


def batch_sums(params, ids, labels, n_head, d_nope, d_rope, d_v, top_k, eps,
               theta, yarn, route_scale, hc_mult, hc_iters, hc_eps, hc_clamp,
               expert_offset=0, q_block=512):
    """Everything the loss needs of ids/labels [B, T] (tokens ``i`` and ``i +
    1``), as sums over their tokens: {"tokens", "ce"}; and, not sums, "top_e"
    [L_expert, B*T, k] and "hidden" [B, T, d] (the final RMSNorm's
    output)."""
    with jax.default_matmul_precision("highest"):
        d = params["wte"].shape[1]

        def run(x, blk):
            # checkpointed: a gradient keeps a block's input stream and
            # computes its inside, the maps among it, again
            return jax.checkpoint(lambda x, blk: block(
                x, blk, n_head, d_nope, d_rope, d_v, top_k, eps, theta, yarn,
                route_scale, expert_offset, q_block, hc_iters, hc_eps,
                hc_clamp))(x, blk)

        hidden, tops = [], []
        for b in range(ids.shape[0]):
            x = entry(params["wte"][ids[b]], hc_mult)
            seq_tops = []
            for blk in params["blocks"]:
                x, top_e = run(x, blk)
                if top_e is not None:
                    seq_tops.append(top_e)
            hidden.append(rms_norm(jnp.sum(x, axis=1),
                                   params["final_norm_w"], eps))
            tops.append(jnp.stack(seq_tops))
        hidden = jnp.stack(hidden)
        return {"tokens": jnp.float32(ids.size),
                "ce": head_ce(hidden.reshape(-1, d), params["head_w"],
                              labels.reshape(-1), q_block),
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
    uncorrected second moment, the ``adam`` op's definition in the framework
    this repo rebuilds) with Loshchilov & Hutter's decoupled decay (``p -=
    rate weight_decay p``, of the parameter before the step)."""
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
    "n_head", "d_nope", "d_rope", "d_v", "top_k", "eps", "theta", "yarn",
    "route_scale", "hc_mult", "hc_iters", "hc_eps", "hc_clamp",
    "expert_offset", "q_block"))
