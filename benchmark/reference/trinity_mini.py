"""Trinity-Mini (Arcee, ``arcee-ai/Trinity-Mini``, ``model_type`` ``afmoe``,
26B-A3B): the training loss of a batch in plain float32 ``jax.numpy``,
matmuls at ``highest`` precision.  No kernels, no sort, no dispatch: dense
``[T, T]`` masks (computed a block of queries at a time so that 8192
positions fit), and every held expert's FFN runs over every token and is
masked by the top-k choice, so this shares nothing with the program's flash
kernels or routing.

One block (``a``, ``m`` are ``[T, d]``; ``RMS(z) = w * z / sqrt(mean(z^2) +
eps)``), four norms:

    a = RMS1(h)
    q = a Wq -> [T, H, dh];  k = a Wk, v = a Wv -> [T, Hkv, dh];  g = a Wg
    q = RMS_q(q), k = RMS_k(k)          per head, over dh (weights [dh])
    sliding_attention layers: q, k = RoPE(q, k), rotate-half over the whole
        head, angle pos * theta^(-2i/dh); full_attention layers: no
        positional term at all
    scores q k^T / sqrt(dh), causal; on sliding layers also i - j < window;
        query head i reads KV head i // (H // Hkv)
    o = softmax(scores) v
    h = h + RMS2((o * sigmoid(g)) Wo)                the output gate
    m = RMS3(h)
    dense layer:  f = Wd (silu(Wg' m) * Wu m)
    expert layer: s = sigmoid(m Wr) in float32;  sel = top-k(s + b);
                  w = s[sel] / (sum s[sel] + 1e-20) * route_scale
                  f = shared(m) + sum_{e in sel, e held here} w_e expert_e(m)
    h = h + RMS4(f)

``x0 = E[ids] * sqrt(d)`` (``mup_enabled``).  After the last block a final
RMSNorm and an untied bias-free head; loss = mean next-token cross-entropy
over every position, nothing else (the published recipe balances load by
steering the selection bias ``b``, which no gradient trains; here ``b`` is
whatever the parameters hold, zero at initialisation).

What ``config.json`` does not say and the published modelling code
(``modeling_afmoe.py``) does — each also listed in the configuration file
under ``assumed``: the output gate, the per-head QK-norm, the four norms
(before and after each sub-layer), no rotary embedding on full-attention
layers, the selection bias and the embedding scale.

The chip's share: ``gate_w``/``up_w``/``down_w`` hold ``E_here`` experts,
numbers ``expert_offset .. expert_offset + E_here - 1`` of the ``E`` the
router scores.  The weights ``w`` are normalised over all ``k`` chosen, as
published; what the absent experts would add is left out, as in the program.

Everything the loss needs is a sum over tokens, so a caller short of memory
adds up :func:`batch_sums` of one sequence at a time.

Parameters: {"wte" [V, d], "blocks": [{"ln1_w", "wq" [d, H*dh], "wk" [d,
Hkv*dh], "wv", "wg" [d, H*dh], "q_norm_w" [dh], "k_norm_w" [dh], "wo" [H*dh,
d], "ln2_w", "ln3_w", "ln4_w", and either "ffn_gate" [d, F], "ffn_up",
"ffn_down" [F, d] (dense layer) or "shared_gate" [d, f], "shared_up",
"shared_down" [f, d], "router_w" [d, E], "select_bias" [E], "gate_w" [E_here,
d, f], "up_w", "down_w" [E_here, f, d] (expert layer)}], "final_norm_w" [d],
"head_w" [d, V]}.
"""

import jax
import jax.numpy as jnp

NORM_EPS = 1e-20          # joins the sum that renormalises the kept scores


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


def attention(a, blk, sliding, n_head, n_kv_head, d_head, eps, theta, window,
              q_block):
    """a [T, d] -> the gated attention output before ``Wo``'s norm,
    [T, d]."""
    t = a.shape[0]
    q = rms_norm((a @ blk["wq"]).reshape(t, n_head, d_head),
                 blk["q_norm_w"], eps)
    k = rms_norm((a @ blk["wk"]).reshape(t, n_kv_head, d_head),
                 blk["k_norm_w"], eps)
    v = (a @ blk["wv"]).reshape(t, n_kv_head, d_head)
    if sliding:
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
        if sliding:
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
    o = o.reshape(t, n_head * d_head)
    return (o * jax.nn.sigmoid(a @ blk["wg"])) @ blk["wo"]


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


def block(h, blk, sliding, n_head, n_kv_head, d_head, top_k, eps, theta,
          window, route_scale, expert_offset, q_block):
    """h [T, d] -> (h', top_e [T, k] or None)."""
    a = rms_norm(h, blk["ln1_w"], eps)
    h = h + rms_norm(attention(a, blk, sliding, n_head, n_kv_head, d_head,
                               eps, theta, window, q_block),
                     blk["ln2_w"], eps)
    m = rms_norm(h, blk["ln3_w"], eps)
    if "ffn_gate" in blk:
        f, top_e = gated(m, blk["ffn_gate"], blk["ffn_up"],
                         blk["ffn_down"]), None
    else:
        routed, top_e = routed_experts(m, blk, top_k, route_scale,
                                       expert_offset)
        f = gated(m, blk["shared_gate"], blk["shared_up"],
                  blk["shared_down"]) + routed
    return h + rms_norm(f, blk["ln4_w"], eps), top_e


def head_ce(hidden, head_w, labels, rows):
    """The summed cross-entropy of hidden [N, d] under the untied head,
    ``rows`` positions at a time (the logits of 8192 positions over the
    vocabulary are the largest array of the pass; a gradient computes each
    block's again)."""
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


def batch_sums(params, ids, labels, layer_types, n_head, n_kv_head, d_head,
               top_k, eps, theta, window, route_scale, expert_offset=0,
               mup=True, q_block=1024):
    """Everything the loss needs of ids/labels [B, T], as sums over their
    tokens: {"tokens", "ce"}; and, not sums, "top_e" [L_expert, B*T, k]
    (each token's experts, all ``E`` numbered) and "hidden" [B, T, d] (the
    final RMSNorm's output, what the head reads)."""
    with jax.default_matmul_precision("highest"):
        d = params["wte"].shape[1]
        hidden, tops = [], []
        for b in range(ids.shape[0]):
            h = params["wte"][ids[b]]
            if mup:
                h = h * jnp.sqrt(jnp.asarray(d, h.dtype))
            seq_tops = []
            for kind, blk in zip(layer_types, params["blocks"]):
                # checkpointed: a gradient at 8192 positions keeps a block's
                # input and computes its inside again (the values are the
                # same either way)
                h, top_e = jax.checkpoint(
                    lambda h, blk, kind=kind: block(
                        h, blk, kind == "sliding_attention", n_head,
                        n_kv_head, d_head, top_k, eps, theta, window,
                        route_scale, expert_offset, q_block))(h, blk)
                if top_e is not None:
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


sequence_sums = jax.jit(batch_sums, static_argnames=(
    "layer_types", "n_head", "n_kv_head", "d_head", "top_k", "eps", "theta",
    "window", "route_scale", "expert_offset", "mup", "q_block"))
