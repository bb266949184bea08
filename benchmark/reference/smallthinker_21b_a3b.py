"""SmallThinker-21BA3B-Instruct (``PowerInfer/SmallThinker-21BA3B-Instruct``,
``model_name`` ``smallthinker_21b_instruct``): the training loss of a batch
in plain float32 ``jax.numpy``, matmuls at ``highest`` precision.  No
kernels, no sort, no dispatch: dense ``[T, T]`` masks (computed a block of
queries at a time so that 16384 positions fit), and every held expert's FFN
runs over every token and is masked by the top-k choice, so this shares
nothing with the program's flash kernels or routing.

One block (``x``, ``n``, ``m`` are ``[T, d]``; ``RMS(z) = w * z /
sqrt(mean(z^2) + eps)``), two norms:

    n = RMS1(x)
    r = n Wr in float32               the router reads the layer's INPUT norm,
    sel = top-k(r)                    before attention;
    p = softmax(r[sel])               the published order: the k largest
                                      logits, then a softmax over those k
    q = n Wq -> [T, H, dh];  k = n Wk, v = n Wv -> [T, Hkv, dh]
                                      no bias, no QK-norm, no gate
    window layers (layout 1): q, k = RoPE(q, k), rotate-half over the whole
        head, angle pos * theta^(-2i/dh); key j visible iff 0 <= i - j <
        window.  Full layers (layout 0): no positional term at all, causal
    scores q k^T / sqrt(dh); query head i reads KV head i // (H // Hkv)
    h = x + (softmax(scores) v) Wo
    m = RMS2(h)
    y = sum_{e in sel, e held here} p_e Wd_e (relu(Wg_e m) * Wu_e m)
    out = h + y

After the last block a final RMSNorm and an untied bias-free head; loss =
mean next-token cross-entropy over every position, nothing else.

What ``config.json`` does not say, each also listed in the configuration
file under ``assumed``: that the router reads the NORMED input ``n`` (the
tensor attention's projections read) and not the raw residual ``x``; that no
projection has a bias; the rotate-half pairing; ReLU on the gate branch
(the catalog's ``described_as``: "sparse ReGLU", "router placed before
attention").  ``described_as`` also says "primary + secondary experts":
``config.json`` has primary experts only, and ``config.json`` wins.

The chip's share: ``gate_w``/``up_w``/``down_w`` hold ``E_here`` experts,
numbers ``expert_offset .. expert_offset + E_here - 1`` of the ``E`` the
router scores.  The weights ``p`` are the softmax over all ``k`` chosen, as
published; what the absent experts would add is left out, as in the program.

Everything the loss needs is a sum over tokens, so a caller short of memory
adds up :func:`batch_sums` of one sequence at a time.

Parameters: {"wte" [V, d], "blocks": [{"ln1_w", "router_w" [d, E], "wq" [d,
H*dh], "wk" [d, Hkv*dh], "wv", "wo" [H*dh, d], "ln2_w", "gate_w" [E_here, d,
f], "up_w", "down_w" [E_here, f, d]}], "final_norm_w" [d], "head_w" [d, V]}.
"""

import jax
import jax.numpy as jnp


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


def attention(n, blk, window, rotary, n_head, n_kv_head, d_head, theta,
              q_block):
    """n [T, d] -> the attention output after ``Wo``, [T, d].  ``window``
    0: the whole causal half."""
    t = n.shape[0]
    q = (n @ blk["wq"]).reshape(t, n_head, d_head)
    k = (n @ blk["wk"]).reshape(t, n_kv_head, d_head)
    v = (n @ blk["wv"]).reshape(t, n_kv_head, d_head)
    if rotary:
        q, k = rope(q, theta), rope(k, theta)
    group = n_head // n_kv_head
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    j = jnp.arange(t)[None, :]
    if t % q_block:
        q_block = t

    @jax.checkpoint          # a gradient keeps no block's [H, q_block, T]
    def rows(_, xs):
        qb, start = xs
        i = start + jnp.arange(q_block)[:, None]
        mask = j <= i
        if window:
            mask = mask & (i - j < window)
        s = jnp.einsum("qhd,khd->hqk", qb, k) / jnp.sqrt(
            jnp.asarray(d_head, q.dtype))
        s = jnp.where(mask[None], s, -jnp.inf)
        return None, jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1),
                                v)

    # one block of queries after another (a scan, so that neither the pass
    # nor its gradient holds two blocks' scores at once)
    _, o = jax.lax.scan(rows, None, (
        q.reshape(t // q_block, q_block, n_head, d_head),
        jnp.arange(0, t, q_block)))
    return o.reshape(t, n_head * d_head) @ blk["wo"]


def route(n, blk, top_k):
    """``(weight [S, E], top_e [S, k])``: each token's weight on every
    expert (zero off its top-k), over all ``E`` the router scores: the ``k``
    largest logits, then a softmax over those ``k``."""
    r = n.astype(jnp.float32) @ blk["router_w"].astype(jnp.float32)
    top_r, top_e = jax.lax.top_k(r, top_k)
    p = jax.nn.softmax(top_r, axis=-1)                          # [S, k]
    weight = jnp.sum(jax.nn.one_hot(top_e, r.shape[-1], dtype=r.dtype)
                     * p[:, :, None], axis=1)
    return weight, top_e


def relu_gated(m, wg, wu, wd):
    return (jax.nn.relu(m @ wg) * (m @ wu)) @ wd


def routed_experts(m, weight, blk, expert_offset=0):
    """m [S, d], weight [S, E] -> the part of the routed experts' output
    that the experts held in ``blk`` give, [S, d]."""
    held = blk["gate_w"].shape[0]
    w_here = jax.lax.dynamic_slice_in_dim(weight, expert_offset, held, axis=1)

    @jax.checkpoint          # a gradient computes each expert's FFN again
    def one_expert(acc, xs):
        wg, wu, wd, w_e = xs
        return acc + w_e[:, None].astype(m.dtype) * relu_gated(
            m, wg, wu, wd), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(m),
                          (blk["gate_w"], blk["up_w"], blk["down_w"],
                           w_here.T))
    return out


def block(x, blk, window, rotary, n_head, n_kv_head, d_head, top_k, eps,
          theta, expert_offset, q_block):
    """x [T, d] -> (out [T, d], top_e [T, k])."""
    n = rms_norm(x, blk["ln1_w"], eps)
    weight, top_e = route(n, blk, top_k)          # before attention
    h = x + attention(n, blk, window, rotary, n_head, n_kv_head, d_head,
                      theta, q_block)
    m = rms_norm(h, blk["ln2_w"], eps)
    return h + routed_experts(m, weight, blk, expert_offset), top_e


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
        logp = jax.nn.log_softmax((h @ head_w).astype(jnp.float32), axis=-1)
        return total - jnp.sum(
            jnp.take_along_axis(logp, y[:, None], axis=-1)), None

    total, _ = jax.lax.scan(some, jnp.float32(0.0), (
        hidden.reshape(n // rows, rows, -1), labels.reshape(n // rows, rows)))
    return total


def batch_sums(params, ids, labels, windows, rotary, n_head, n_kv_head,
               d_head, top_k, eps, theta, expert_offset=0, q_block=512):
    """Everything the loss needs of ids/labels [B, T], as sums over their
    tokens: {"tokens", "ce"}; and, not sums, "top_e" [L, B*T, k] (each
    token's experts, all ``E`` numbered) and "hidden" [B, T, d] (the final
    RMSNorm's output, what the head reads).  ``windows[i]``: layer ``i``'s
    window, 0 for a full layer; ``rotary[i]``: whether it rotates Q and
    K."""
    with jax.default_matmul_precision("highest"):
        d = params["wte"].shape[1]
        hidden, tops = [], []
        for b in range(ids.shape[0]):
            h = params["wte"][ids[b]]
            seq_tops = []
            for w, rot, blk in zip(windows, rotary, params["blocks"]):
                # checkpointed: a gradient at 16384 positions keeps a
                # block's input and computes its inside again (the values
                # are the same either way)
                h, top_e = jax.checkpoint(
                    lambda h, blk, w=w, rot=rot: block(
                        h, blk, w, rot, n_head, n_kv_head, d_head, top_k,
                        eps, theta, expert_offset, q_block))(h, blk)
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
    "windows", "rotary", "n_head", "n_kv_head", "d_head", "top_k", "eps",
    "theta", "expert_offset", "q_block"))
