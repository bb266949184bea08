"""OLMoE-1B-7B (Muennighoff et al. 2024, arXiv:2409.02060; ``olmoe`` in
``config.json``): the training loss of a batch in plain float32
``jax.numpy``, matmuls at ``highest`` precision.  No kernels, no sort, no
dispatch: every expert's FFN runs over every token and is masked by the
top-k choice, so this shares nothing with the program's routing.

Per layer, with ``RMSNorm(z) = w * z / sqrt(mean(z^2) + eps)``:

    n = RMSNorm(x)
    q = RoPE(split(RMSNorm_q(n Wq)));  k = RoPE(split(RMSNorm_k(n Wk)))
    v = split(n Wv)                      (QK-norm over the whole projection,
                                          before the head split)
    h = x + (causal softmax(q k^T / sqrt(dh)) v) Wo
    m = RMSNorm(h);  p = softmax(m Wr) over all experts
    out = h + sum_{e in top-k of p} p_e * Wd_e (silu(Wg_e m) * Wu_e m)
                                         (the kept p_e are not renormalised)

RoPE rotates the whole head, dimension ``i`` with ``i + dh/2``, at the angle
``pos * theta^(-2i/dh)``.  After the last layer a final RMSNorm and an untied
bias-free head.  Loss = mean next-token cross-entropy + ``lb_coef`` * mean
over layers of ``E * sum_e f_e P_e`` + ``z_coef`` * mean over layers of the
mean over tokens of ``logsumexp(m Wr)^2``; ``f_e`` = slots that chose ``e``
÷ tokens (it sums to k), ``P_e`` = mean of ``p_e`` over tokens.

Departures, listed in the configuration file under ``assumed``: the two
coefficients (the paper's recipe, not in config.json), the mean over layers
of the auxiliary terms, labels as the input pipeline shifted them.

Everything the loss needs is a sum over tokens until :func:`loss_of_sums`, so
a caller short of memory (the chip, at published widths) adds up
:func:`batch_sums` of one sequence at a time; ``loss`` does it for a whole
batch at once.

Parameters: {"wte" [V, d], "blocks": [{"ln1_w", "wq" [d, d], "wk", "wv",
"q_norm_w" [d], "k_norm_w", "wo", "ln2_w", "router_w" [d, E], "gate_w"
[E, d, f], "up_w" [E, d, f], "down_w" [E, f, d]}], "final_norm_w" [d],
"head_w" [d, V]}.
"""


import jax
import jax.numpy as jnp


def rms_norm(z, w, eps):
    return w * z / jnp.sqrt(jnp.mean(jnp.square(z), axis=-1, keepdims=True)
                            + eps)


def rope(x, theta):
    """x [B, T, H, dh]."""
    t, dh = x.shape[1], x.shape[3]
    half = dh // 2
    freq = theta ** (-2.0 * jnp.arange(half, dtype=jnp.float32) / dh)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, :, None]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, :, None]
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rot * sin


def attention(n, blk, n_head, eps, theta):
    b, t, d = n.shape
    dh = d // n_head
    q = rms_norm(n @ blk["wq"], blk["q_norm_w"], eps)
    k = rms_norm(n @ blk["wk"], blk["k_norm_w"], eps)
    v = n @ blk["wv"]
    q = rope(q.reshape(b, t, n_head, dh), theta)
    k = rope(k.reshape(b, t, n_head, dh), theta)
    v = v.reshape(b, t, n_head, dh)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(dh))
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None, None], s, -jnp.inf)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    return ctx.reshape(b, t, d) @ blk["wo"]


def moe(m, blk, top_k):
    """m [S, d] -> (out [S, d], logits [S, E], p [S, E], top_e [S, k])."""
    logits = m @ blk["router_w"]
    p = jax.nn.softmax(logits, axis=-1)
    _, top_e = jax.lax.top_k(p, top_k)
    e = p.shape[-1]
    chosen = jnp.sum(jax.nn.one_hot(top_e, e, dtype=p.dtype), axis=1)
    weight = p * chosen                              # kept as they are

    def one_expert(acc, xs):
        wg, wu, wd, w_e = xs
        y = (jax.nn.silu(m @ wg) * (m @ wu)) @ wd    # every token
        return acc + w_e[:, None] * y, None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(m),
                          (blk["gate_w"], blk["up_w"], blk["down_w"],
                           weight.T))
    return out, logits, p, top_e


def batch_sums(params, ids, labels, n_head, top_k, eps, theta):
    """Everything the loss needs of ids/labels [B, T], as sums over their
    tokens: {"tokens", "ce", "p" [L, E], "load" [L, E], "lse2" [L]}; and,
    not sums, "top_e" [L, B*T, k] (each token's experts) and "hidden"
    [B, T, d] (the final RMSNorm's output, what the head reads)."""
    with jax.default_matmul_precision("highest"):
        b, t = ids.shape
        x = params["wte"][ids]
        ps, loads, lse2s, tops = [], [], [], []
        for blk in params["blocks"]:
            x = x + attention(rms_norm(x, blk["ln1_w"], eps), blk, n_head,
                              eps, theta)
            m = rms_norm(x, blk["ln2_w"], eps).reshape(b * t, -1)
            out, logits, p, top_e = moe(m, blk, top_k)
            x = x + out.reshape(x.shape)
            ps.append(jnp.sum(p, axis=0))
            loads.append(jnp.sum(
                jax.nn.one_hot(top_e, p.shape[-1], dtype=jnp.float32),
                axis=(0, 1)))
            lse2s.append(jnp.sum(jnp.square(
                jax.nn.logsumexp(logits, axis=-1))))
            tops.append(top_e)
        hidden = rms_norm(x, params["final_norm_w"], eps)
        lg = hidden @ params["head_w"]
        logp = jax.nn.log_softmax(lg, axis=-1)
        ce = -jnp.take_along_axis(logp, labels[..., None], axis=-1)
        return {"tokens": jnp.float32(b * t), "ce": jnp.sum(ce),
                "p": jnp.stack(ps), "load": jnp.stack(loads),
                "lse2": jnp.stack(lse2s), "top_e": jnp.stack(tops),
                "hidden": hidden}


def loss_of_sums(sums, lb_coef, z_coef):
    """The loss and its parts from :func:`batch_sums` (or the element-wise
    sum of several): {"loss", "ce", "lb", "z"}."""
    n = sums["tokens"]
    e = sums["p"].shape[-1]
    ce = sums["ce"] / n
    lb = jnp.mean(e * jnp.sum((sums["load"] / n) * (sums["p"] / n), axis=-1))
    z = jnp.mean(sums["lse2"] / n)
    return {"loss": ce + lb_coef * lb + z_coef * z, "ce": ce, "lb": lb,
            "z": z}


def loss(params, ids, labels, n_head, top_k, eps, theta, lb_coef, z_coef):
    """The training loss of a whole batch; ``jax.grad`` of it gives the
    reference gradients."""
    return loss_of_sums(batch_sums(params, ids, labels, n_head, top_k, eps,
                                   theta), lb_coef, z_coef)["loss"]


sequence_sums = jax.jit(batch_sums,
                        static_argnames=("n_head", "top_k", "eps", "theta"))
