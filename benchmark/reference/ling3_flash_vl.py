"""Ling-3.0-flash-VL's language model (inclusionAI,
``inclusionAI/Ling-3.0-flash-VL``; the vision tower is not built): the
training loss of a batch in plain float32 ``jax.numpy``, matmuls at
``highest`` precision.  No chunks, no WY form, no kernels, no ``top_k``, no
dispatch: the KDA layers run their recurrence TOKEN BY TOKEN (a ``lax.scan``
over the positions), the latent-attention layer the literal softmax over dense
``[T, T]`` masks a block of queries at a time with K and V expanded per head,
the router sorts (``argsort``) where the program selects, and every held
expert's FFN runs over every token and is masked by the choice.  It shares no
code with ``paddle_tpu/`` and none with the other cells' references.

One block (``x``, ``z``, ``u``, ``m`` are ``[T, d]``; ``RMS_w(v) = w * v /
sqrt(mean(v^2) + eps)``), pre-norm, two norms, no bias anywhere:

    z = RMS1(x)
    KDA layer (per head, d_k = d_v = 128, float32):
        c(y)[t] = sum_{j=0..3} w[:, j] * y[t - 3 + j]     depthwise, causal,
                                        zeros before the sequence starts
        q_t = l2(silu(c(z Wq))_t) * 128^-0.5;  k_t = l2(silu(c(z Wk))_t)
        v_t = silu(c(z Wv))_t           l2(y) = y / sqrt(sum y^2 + 1e-6)
        g_t = lower_bound * sigmoid(exp(A_log_h) * (z Wf + dt_bias))   [128]
                                        in (lower_bound, 0); Wf FULL rank
        beta_t = sigmoid(z Wbeta)       scalar a head, NOT doubled
        S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
        o_t = S_t^T q_t                 S_0 = 0, S [128, 128]; exp(g_t) is
                                        computed as 1 + expm1(g_t)
        mix = [RMS_head(o_t) * sigmoid(z Wg)] Wo         Wg FULL rank
    MLA layer (q_lora_rank null: Q at full rank):
        [q_nope | q_rope]_i = (z Wq)_i  per head, 128 | 64
        c_kv = RMS_kv(z Wkva)  [T, 512];  k_r = z Wkr  [T, 64], one head
        [k_nope | v]_i = (c_kv Wkvb)_i  per head, 128 | 128
        q_nope_i <- RMS_qn(q_nope_i);  k_nope_i <- RMS_kn(k_nope_i)
                                        one [128] scale for all query heads,
                                        one for all key heads; the rotary
                                        slices are not normed
        q_rope_i, k_r: each adjacent pair (x_2j, x_2j+1) turned by the angle
                                        pos * theta^(-2j / 64)
        score = (q_nope k_nope^T + q_rope k_r^T) * 192^-0.5, causal, softmax
        mix = [ctx_i * sigmoid((z Wgate)_i)] Wo    Wgate [d, H]: one gate a
                                        head and token
    u = x + mix;  m = RMS2(u)
    dense layer:  out = u + Wd (silu(Wg m) * Wu m)
    expert layer: s = sigmoid(m Wr) in float32, E = 512 scores
        groups of E / n_group consecutive experts; a group's score the sum
        of its two largest (s + b); the topk_group best groups kept, the
        others' entries out of the choice; sel = the top_k largest (s + b)
        among what is left (ties: the lower number)
        w = s[sel] / (sum s[sel] + 1e-20) * routed_scaling_factor
        out = u + shared(m) + sum_{e in sel, e held here} w_e expert_e(m)

After the last block a final RMSNorm and an untied head; loss = mean
next-token cross-entropy over every position, nothing else.

Departures from the published description, each also in the configuration
file under ``assumed``: ``config.json`` is silent on the layer equations,
which are the fla KDA layer's (arXiv:2510.26692) with its ``lower_bound``
gate and without the one bias that layer has, DeepSeek-V2's latent attention
(arXiv:2405.04434 section 2.1), the gated-attention paper's head-wise gate
(arXiv:2505.06708) and Ling 2.0's router; the rotary here turns adjacent
pairs in place, which gives the scores of the family's ``rope_interleave``
form (the same permutation on both sides of the product); the QK-norm is
read as the family's ``query_layernorm`` / ``key_layernorm`` over
``head_dim`` 128, the content width; the selection bias ``b`` is whatever
the parameters hold, zero at initialisation, and no gradient trains it; the
clamp of the gated FFNs (``expert_swiglu_limit_list``) is 0 in every layer
kept and is not built; no MTP module.

The chip's share: the mixers' weights hold the heads HELD here (each at its
published width; the K/V latent, its norm and the rotary key whole),
``gate_w`` / ``up_w`` / ``down_w`` the ``E_here`` experts held, numbers
``expert_offset .. expert_offset + E_here - 1`` of the ``E`` the router
scores; the output projections give the partial sum over the held heads, the
experts' part the partial sum over the held experts; what the absent ones
would add is left out, as in the program.  Shared expert, router, norms and
the dense FFN are whole.

The 8192-step recurrence is checkpointed in blocks of ``kda_block``
positions: a gradient keeps one state a block and head and runs the block's
steps again.

Parameters: {"wte" [V, d], "blocks": [{"ln1_w", "ln2_w"; a KDA layer "wq",
"wk", "wv" [d, Hk*dh], "conv_q", "conv_k", "conv_v" [Hk*dh, 4], "wf" [d,
Hk*dh], "a_log" [Hk], "dt_bias" [Hk*dh], "w_beta" [d, Hk], "wg" [d, Hk*dh],
"o_norm_w" [dh], "wo" [Hk*dh, d]; the MLA layer "wq" [d, H*(dn+dr)], "w_kva"
[d, r], "w_kr" [d, dr], "kv_norm_w" [r], "w_kvb" [r, H*(dn+dv)], "qn_w",
"kn_w" [dn], "w_hgate" [d, H], "wo" [H*dv, d]; a dense layer "ffn_gate",
"ffn_up" [d, F], "ffn_down" [F, d]; an expert layer "shared_gate",
"shared_up" [d, f], "shared_down" [f, d], "router_w" [d, E], "select_bias"
[E], "gate_w" [E_here, d, f], "up_w", "down_w" [E_here, f, d]}],
"final_norm_w" [d], "head_w" [d, V]}.
"""

import jax
import jax.numpy as jnp

NORM_EPS = 1e-20          # joins the sum that renormalises the kept scores
L2_EPS = 1e-6


def rms(v, w, eps):
    return w * v * jax.lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True)
                                 + eps)


def swiglu(m, w_gate, w_up, w_down):
    return (jax.nn.silu(m @ w_gate) * (m @ w_up)) @ w_down


def causal_conv_silu(y, w):
    """y [T, c], w [c, L]: ``silu(sum_j w[:, j] y[t - (L - 1) + j])``, zeros
    before the start."""
    t, taps = y.shape[0], w.shape[1]
    padded = jnp.concatenate([jnp.zeros((taps - 1, y.shape[1]), y.dtype), y])
    return jax.nn.silu(sum(w[:, j] * padded[j:j + t] for j in range(taps)))


def unit(y):
    return y / jnp.sqrt(jnp.sum(y * y, axis=-1, keepdims=True) + L2_EPS)


def bounded_gate(pre, a_log, lower_bound):
    """pre [T, H, dk] (``z Wf + dt_bias``), a_log [H] -> the log-decay in
    ``(lower_bound, 0)``."""
    return lower_bound * jax.nn.sigmoid(jnp.exp(a_log)[None, :, None] * pre)


def recurrence(q, k, v, g, beta, block):
    """One head, token by token: q, k, g [T, dk], v [T, dv], beta [T] -> o
    [T, dv]; ``block`` positions a checkpoint."""
    t, dk = q.shape
    dv = v.shape[1]
    if t % block:
        block = t

    def one(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        # multiplies and sums, no matmul inside the step.  alpha = exp(g)
        # written 1 + expm1(g): at fresh weights nearly every g is within
        # 1e-10 of 0 and the state never forgets, so whatever the device's
        # exp returns beside 1 there is compounded 8192 times
        # (tools/kda_recurrence_probe.py; PERF.md section 6, PR 55)
        state = state * (1.0 + jnp.expm1(g_t))[:, None]     # Diag(alpha) S
        err = v_t - jnp.sum(state * k_t[:, None], axis=0)   # v - S^T k
        state = state + (b_t * k_t)[:, None] * err[None, :]
        return state, jnp.sum(state * q_t[:, None], axis=0)

    @jax.checkpoint
    def run(state, xs):
        return jax.lax.scan(one, state, xs)

    xs = tuple(a.reshape(t // block, block, *a.shape[1:])
               for a in (q, k, v, g, beta))
    _, o = jax.lax.scan(run, jnp.zeros((dk, dv), q.dtype), xs)
    return o.reshape(t, dv)


def kda(z, blk, d_head, eps, lower_bound, kda_block):
    t, h = z.shape[0], blk["a_log"].shape[0]

    def per_head(y):
        return y.reshape(t, h, d_head)

    q = unit(per_head(causal_conv_silu(z @ blk["wq"], blk["conv_q"]))) \
        * d_head ** -0.5
    k = unit(per_head(causal_conv_silu(z @ blk["wk"], blk["conv_k"])))
    v = per_head(causal_conv_silu(z @ blk["wv"], blk["conv_v"]))
    g = bounded_gate(per_head(z @ blk["wf"] + blk["dt_bias"]), blk["a_log"],
                     lower_bound)
    beta = jax.nn.sigmoid(z @ blk["w_beta"])
    o = jax.vmap(lambda *head: recurrence(*head, kda_block), in_axes=1,
                 out_axes=1)(q, k, v, g, beta)
    o = rms(o, blk["o_norm_w"], eps).reshape(t, h * d_head)
    return (o * jax.nn.sigmoid(z @ blk["wg"])) @ blk["wo"]


def turn_pairs(x, theta):
    """x [T, H, dr]: each adjacent pair ``(x_2j, x_2j+1)`` turned by ``pos *
    theta^(-2j / dr)``."""
    t, h, dr = x.shape
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * theta ** (
        -jnp.arange(0, dr, 2, dtype=jnp.float32) / dr)[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(t, h, dr)


def latent_attention(z, blk, d_nope, d_rope, d_v, eps, theta, q_block):
    t = z.shape[0]
    h = blk["w_hgate"].shape[1]
    q = (z @ blk["wq"]).reshape(t, h, d_nope + d_rope)
    c_kv = rms(z @ blk["w_kva"], blk["kv_norm_w"], eps)
    kv = (c_kv @ blk["w_kvb"]).reshape(t, h, d_nope + d_v)
    q_nope = rms(q[..., :d_nope], blk["qn_w"], eps)
    k_nope = rms(kv[..., :d_nope], blk["kn_w"], eps)
    v = kv[..., d_nope:]
    q_rope = turn_pairs(q[..., d_nope:], theta)
    k_rope = turn_pairs((z @ blk["w_kr"])[:, None, :], theta)[:, 0]
    scale = (d_nope + d_rope) ** -0.5
    cols = jnp.arange(t)[None, :]
    if t % q_block:
        q_block = t

    @jax.checkpoint          # a gradient keeps no block's [H, q_block, T]
    def some_queries(_, xs):
        qn, qr, first = xs
        s = (jnp.einsum("qhd,khd->hqk", qn, k_nope)
             + jnp.einsum("qhd,kd->hqk", qr, k_rope)) * scale
        rows = first + jnp.arange(q_block)[:, None]
        p = jax.nn.softmax(jnp.where((cols <= rows)[None], s, -jnp.inf),
                           axis=-1)
        return None, jnp.einsum("hqk,khd->qhd", p, v)

    n = t // q_block
    _, ctx = jax.lax.scan(some_queries, None, (
        q_nope.reshape(n, q_block, h, d_nope),
        q_rope.reshape(n, q_block, h, d_rope), jnp.arange(0, t, q_block)))
    ctx = ctx.reshape(t, h, d_v) * jax.nn.sigmoid(z @ blk["w_hgate"])[:, :,
                                                                       None]
    return ctx.reshape(t, h * d_v) @ blk["wo"]


def choose(scores, bias, top_k, n_group, topk_group):
    """scores [S, E] -> top_e [S, top_k]: the group-limited choice, by plain
    sorting; ties go to the lower number."""
    s, e = scores.shape
    sel = scores + bias
    if n_group > 1:
        by_group = jnp.sort(sel.reshape(s, n_group, e // n_group), axis=-1)
        group_score = by_group[..., -1] + by_group[..., -2]
        place = jnp.argsort(jnp.argsort(-group_score, axis=-1, stable=True),
                            axis=-1, stable=True)
        kept = jnp.repeat(place < topk_group, e // n_group, axis=1)
        sel = jnp.where(kept, sel, -jnp.inf)
    return jnp.argsort(-sel, axis=-1, stable=True)[:, :top_k]


def route(m, blk, top_k, n_group, topk_group, route_scale):
    """``(weight [S, E], top_e [S, k])``: each token's weight on every
    expert, zero off its chosen ``k``."""
    s = jax.nn.sigmoid(m.astype(jnp.float32)
                       @ blk["router_w"].astype(jnp.float32))
    top_e = choose(jax.lax.stop_gradient(s),
                   blk["select_bias"].astype(jnp.float32), top_k, n_group,
                   topk_group)
    chosen = jnp.zeros_like(s).at[
        jnp.arange(s.shape[0])[:, None], top_e].set(1.0)
    kept = s * chosen
    return kept / (jnp.sum(kept, axis=-1, keepdims=True) + NORM_EPS) \
        * route_scale, top_e


def held_experts(m, blk, weight, expert_offset):
    """The part of the routed experts' output that the experts held in
    ``blk`` give: every held expert over every token, times the token's
    weight on it."""
    held = blk["gate_w"].shape[0]
    w_here = jax.lax.dynamic_slice_in_dim(weight, expert_offset, held,
                                          axis=1)

    @jax.checkpoint          # a gradient computes each expert's FFN again
    def one(acc, xs):
        w_gate, w_up, w_down, w_e = xs
        return acc + w_e[:, None].astype(m.dtype) \
            * swiglu(m, w_gate, w_up, w_down), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(m), (
        blk["gate_w"], blk["up_w"], blk["down_w"], w_here.T))
    return out


def block(x, blk, kw):
    """x [T, d] -> (out [T, d], top_e [T, k] or None)."""
    z = rms(x, blk["ln1_w"], kw["eps"])
    if "a_log" in blk:
        mix = kda(z, blk, kw["d_head"], kw["eps"], kw["lower_bound"],
                  kw["kda_block"])
    else:
        mix = latent_attention(z, blk, kw["d_nope"], kw["d_rope"], kw["d_v"],
                               kw["eps"], kw["theta"], kw["q_block"])
    u = x + mix
    m = rms(u, blk["ln2_w"], kw["eps"])
    if "ffn_gate" in blk:
        return u + swiglu(m, blk["ffn_gate"], blk["ffn_up"],
                          blk["ffn_down"]), None
    weight, top_e = route(m, blk, kw["top_k"], kw["n_group"],
                          kw["topk_group"], kw["route_scale"])
    shared = swiglu(m, blk["shared_gate"], blk["shared_up"],
                    blk["shared_down"])
    return u + shared + held_experts(m, blk, weight, kw["expert_offset"]), \
        top_e


def summed_ce(hidden, head_w, labels, rows):
    """The summed cross-entropy of hidden [N, d] under ``head_w`` [d, V],
    ``rows`` positions at a time."""
    n = hidden.shape[0]
    if n % rows:
        rows = n

    @jax.checkpoint
    def some(total, xs):
        h, y = xs
        logp = jax.nn.log_softmax((h @ head_w).astype(jnp.float32), axis=-1)
        return total - jnp.sum(logp[jnp.arange(rows), y]), None

    total, _ = jax.lax.scan(some, jnp.float32(0.0), (
        hidden.reshape(n // rows, rows, -1), labels.reshape(n // rows, rows)))
    return total


def batch_sums(params, ids, labels, d_head, d_nope, d_rope, d_v, top_k,
               n_group, topk_group, eps, theta, lower_bound, route_scale=1.0,
               expert_offset=0, q_block=512, kda_block=128):
    """Everything the loss needs of ids/labels [B, T], as sums over their
    tokens: {"tokens", "ce"}; and, not sums, "top_e" [L_expert, B*T, k] (each
    token's experts in every expert layer, all ``E`` numbered) and "hidden"
    [B, T, d] (the final RMSNorm's output, what the head reads)."""
    kw = dict(d_head=d_head, d_nope=d_nope, d_rope=d_rope, d_v=d_v,
              top_k=top_k, n_group=n_group, topk_group=topk_group, eps=eps,
              theta=theta, lower_bound=lower_bound, route_scale=route_scale,
              expert_offset=expert_offset, q_block=q_block,
              kda_block=kda_block)
    with jax.default_matmul_precision("highest"):
        hidden, tops = [], []
        for b in range(ids.shape[0]):
            h = params["wte"][ids[b]]
            seq_tops = []
            for blk in params["blocks"]:
                # a gradient keeps a block's input and computes its inside
                # again (the values are the same)
                h, top_e = jax.checkpoint(
                    lambda h, blk: block(h, blk, kw))(h, blk)
                if top_e is not None:
                    seq_tops.append(top_e)
            hidden.append(rms(h, params["final_norm_w"], eps))
            tops.append(jnp.stack(seq_tops))
        hidden = jnp.stack(hidden)
        ce = summed_ce(hidden.reshape(-1, hidden.shape[-1]),
                       params["head_w"], labels.reshape(-1), q_block)
        return {"tokens": jnp.float32(ids.size), "ce": ce,
                "top_e": jnp.concatenate(tops, axis=1), "hidden": hidden}


def loss_of_sums(sums):
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
    m1, m2 = np.zeros_like(p), np.zeros_like(p)
    for t, (rate, g) in enumerate(steps, 1):
        g = np.asarray(g, np.float64)
        m1 = beta1 * m1 + (1 - beta1) * g
        m2 = beta2 * m2 + (1 - beta2) * g * g
        step = rate * (1 - beta2 ** t) ** 0.5 / (1 - beta1 ** t)
        p = p - step * m1 / (np.sqrt(m2) + eps) - rate * weight_decay * p
    return p


sequence_sums = jax.jit(batch_sums, static_argnames=(
    "d_head", "d_nope", "d_rope", "d_v", "top_k", "n_group", "topk_group",
    "eps", "theta", "lower_bound", "route_scale", "expert_offset", "q_block",
    "kda_block"))
