"""SDAR-30B-A3B-Chat (JetLM, ``JetLM/SDAR-30B-A3B-Chat``, ``model_type``
``sdar_moe``; arXiv:2510.06303) under its training objective, block diffusion
(arXiv:2503.09573): the loss of a batch in plain float32 ``jax.numpy``,
matmuls at ``highest`` precision.  No kernels, no skipping, no sort, no
dispatch: the attention mask is a dense boolean array built from the four
lines below (a block of queries at a time, so that 16384 rows fit), and every
held expert's FFN runs over every row and is masked by the top-k choice, so
this shares nothing with the program's flash kernels or routing.

The objective.  A sequence ``x_0`` of ``L`` tokens in blocks of ``B``, ``b(p)
= p // B``; ``x_t`` is ``x_0`` with some tokens replaced by the mask id (the
caller draws them: each token of block ``b`` with probability ``t_b``).  The
stream is ``[E(x_t); E(x_0)]``, ``2L`` rows through one table, positions ``p(i)
= i mod L``.  In every layer query ``i`` sees key ``j`` iff

    i <  L, j <  L:   b(i) == b(j)              noisy sees its own block
    i <  L, j >= L:   b(j - L) <  b(i)          and the clean blocks before
    i >= L, j >= L:   b(j - L) <= b(i - L)      clean is block-causal
    i >= L, j <  L:   never

One block (Qwen3-MoE's, which ``sdar_moe`` keeps; ``a``, ``m`` are ``[2L,
d]``; ``RMS(z) = w * z / sqrt(mean(z^2) + eps)``):

    a = RMS1(h)
    q = a Wq -> [2L, H, dh];  k = a Wk, v = a Wv -> [2L, Hkv, dh]
    q = RMS_q(q), k = RMS_k(k)          per head, over dh (weights [dh])
    q, k = RoPE(q, k) at p(i), rotate-half, angle p * theta^(-2i/dh)
    scores q k^T / sqrt(dh) under the mask; query head i reads KV head
        i // (H // Hkv);  o = softmax(scores) v
    h = h + o Wo                              no bias, no gate
    m = RMS2(h)
    s = softmax(m Wr) in float32 over E;  sel = top-k(s)
    w = s[sel] / sum s[sel]                   (``norm_topk_prob``)
    h = h + sum_{e in sel, e held here} w_e Wd_e (silu(Wg_e m) * Wu_e m)

After the last block the NOISY half alone goes on: a final RMSNorm and an
untied bias-free head over its ``L`` rows.  Loss = ``(1 / (B_seq L)) sum_{i <
L, label_i > 0} weight_i * (-log softmax(logits_i)[label_i])``: the token AT
its position (no shift), ``label`` the clean id where ``x_t`` is the mask id
and 0 elsewhere, ``weight`` ``1 / t_{b(i)}``.  No auxiliary term
(``config.json`` has no coefficient).

The chip's share: ``gate_w``/``up_w``/``down_w`` hold ``E_here`` experts,
numbers ``expert_offset .. expert_offset + E_here - 1`` of the ``E`` the
router scores; the weights ``w`` are normalised over all ``k`` chosen, as
published, and what the absent experts would add is left out, as in the
program.

Parameters: {"wte" [V, d], "blocks": [{"ln1_w", "wq" [d, H*dh], "wk" [d,
Hkv*dh], "wv", "q_norm_w" [dh], "k_norm_w" [dh], "wo" [H*dh, d], "ln2_w",
"router_w" [d, E], "gate_w" [E_here, d, f], "up_w", "down_w" [E_here, f,
d]}], "final_norm_w" [d], "head_w" [d, V]}.
"""

import jax
import jax.numpy as jnp

# what the cell's replayed update reads of a reference: the schedule and
# AdamW in float64 numpy, the decoders' own
from .smallthinker_21b_a3b import adamw, warmup_rate  # noqa: F401
from .trinity_mini import gated, rms_norm


def visible(i, j, half, block):
    """The mask over broadcastable positions ``i`` (queries) and ``j``
    (keys) of the doubled sequence: the four lines of the module's text."""
    bi = jnp.where(i < half, i, i - half) // block
    bj = jnp.where(j < half, j, j - half) // block
    noisy_noisy = (i < half) & (j < half) & (bi == bj)
    noisy_clean = (i < half) & (j >= half) & (bj < bi)
    clean_clean = (i >= half) & (j >= half) & (bj <= bi)
    return noisy_noisy | noisy_clean | clean_clean


def rope(x, theta, positions):
    """x [T, H, dh] turned by ``positions`` [T]."""
    dh = x.shape[2]
    half = dh // 2
    freq = theta ** (-2.0 * jnp.arange(half, dtype=jnp.float32) / dh)
    ang = positions.astype(jnp.float32)[:, None] * freq[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None]
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return (x * cos + rot * sin).astype(x.dtype)


def attention(a, blk, n_head, n_kv_head, d_head, eps, theta, block, q_block):
    """a [2L, d] -> the attention output, [2L, d]."""
    t = a.shape[0]
    half = t // 2
    positions = jnp.arange(t) % half
    q = rope(rms_norm((a @ blk["wq"]).reshape(t, n_head, d_head),
                      blk["q_norm_w"], eps), theta, positions)
    k = rope(rms_norm((a @ blk["wk"]).reshape(t, n_kv_head, d_head),
                      blk["k_norm_w"], eps), theta, positions)
    v = (a @ blk["wv"]).reshape(t, n_kv_head, d_head)
    group = n_head // n_kv_head
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    j = jnp.arange(t)[None, :]
    if t % q_block:
        q_block = t

    @jax.checkpoint          # a gradient keeps no block's [H, q_block, 2L]
    def rows(_, xs):
        qb, start = xs
        i = start + jnp.arange(q_block)[:, None]
        s = jnp.einsum("qhd,khd->hqk", qb, k) / jnp.sqrt(
            jnp.asarray(d_head, q.dtype))
        s = jnp.where(visible(i, j, half, block)[None], s, -jnp.inf)
        return None, jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1),
                                v)

    _, o = jax.lax.scan(rows, None, (
        q.reshape(t // q_block, q_block, n_head, d_head),
        jnp.arange(0, t, q_block)))
    return o.reshape(t, n_head * d_head) @ blk["wo"]


def route(m, blk, top_k):
    """``(weight [S, E], top_e [S, k])``: each row's weight on every expert
    (zero off its top-k), over all ``E`` the router scores."""
    s = jax.nn.softmax(m.astype(jnp.float32)
                       @ blk["router_w"].astype(jnp.float32), axis=-1)
    _, top_e = jax.lax.top_k(s, top_k)
    kept = s * jnp.sum(jax.nn.one_hot(top_e, s.shape[-1], dtype=s.dtype),
                       axis=1)
    return kept / jnp.sum(kept, axis=-1, keepdims=True), top_e


def routed_experts(m, blk, top_k, expert_offset=0):
    """m [S, d] -> ``(out [S, d], top_e [S, k])``: the part of the routed
    experts' output that the experts held in ``blk`` give."""
    weight, top_e = route(m, blk, top_k)
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


def block_of(h, blk, n_head, n_kv_head, d_head, top_k, eps, theta, block,
             expert_offset, q_block):
    """h [2L, d] -> (h', top_e [2L, k])."""
    h = h + attention(rms_norm(h, blk["ln1_w"], eps), blk, n_head, n_kv_head,
                      d_head, eps, theta, block, q_block)
    routed, top_e = routed_experts(rms_norm(h, blk["ln2_w"], eps), blk,
                                   top_k, expert_offset)
    return h + routed, top_e


def weighted_ce(hidden, head_w, labels, weights, rows):
    """``sum_i [label_i > 0] weight_i CE_i`` of hidden [N, d] under the
    untied head, ``rows`` positions at a time."""
    n = hidden.shape[0]
    if n % rows:
        rows = n

    @jax.checkpoint
    def some(total, xs):
        h, y, w = xs
        logp = jax.nn.log_softmax((h @ head_w).astype(jnp.float32), axis=-1)
        ce = -jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0]
        return total + jnp.sum(jnp.where(y > 0, w * ce, 0.0)), None

    total, _ = jax.lax.scan(some, jnp.float32(0.0), (
        hidden.reshape(n // rows, rows, -1), labels.reshape(n // rows, rows),
        weights.reshape(n // rows, rows)))
    return total


def batch_sums(params, clean_ids, noisy_ids, labels, weights, n_head,
               n_kv_head, d_head, top_k, eps, theta, block, expert_offset=0,
               q_block=512):
    """Everything the loss needs of a batch ``[B, L]``, as sums over its
    positions: {"rows", "wce"}; and, not sums, "top_e" [layers, B * 2L, k]
    (each stream row's experts, all ``E`` numbered; a sequence's noisy rows,
    then its clean rows) and "hidden" [B, L, d] (the final RMSNorm's output
    over the noisy half, what the head reads)."""
    with jax.default_matmul_precision("highest"):
        d = params["wte"].shape[1]
        n = clean_ids.shape[1]
        hidden, tops = [], []
        for b in range(clean_ids.shape[0]):
            h = jnp.concatenate([params["wte"][noisy_ids[b]],
                                 params["wte"][clean_ids[b]]])
            seq_tops = []
            for blk in params["blocks"]:
                h, top_e = jax.checkpoint(lambda h, blk: block_of(
                    h, blk, n_head, n_kv_head, d_head, top_k, eps, theta,
                    block, expert_offset, q_block))(h, blk)
                seq_tops.append(top_e)
            hidden.append(rms_norm(h[:n], params["final_norm_w"], eps))
            tops.append(jnp.stack(seq_tops))
        hidden = jnp.stack(hidden)
        wce = weighted_ce(hidden.reshape(-1, d), params["head_w"],
                          labels.reshape(-1), weights.reshape(-1), q_block)
        return {"rows": jnp.float32(clean_ids.size), "wce": wce,
                "top_e": jnp.concatenate(tops, axis=1), "hidden": hidden}


def loss_of_sums(sums):
    """{"loss"} from :func:`batch_sums` (or the element-wise sum of
    several)."""
    return {"loss": sums["wce"] / sums["rows"]}


def loss(params, clean_ids, noisy_ids, labels, weights, **kw):
    """The training loss of a whole batch; ``jax.grad`` of it gives the
    reference gradients."""
    return loss_of_sums(batch_sums(params, clean_ids, noisy_ids, labels,
                                   weights, **kw))["loss"]


def whole_layer_ffn(m, blk, top_k):
    """The uncut layer's routed FFN over m [S, d], ``blk`` holding all ``E``
    experts: what the shares' parts add up to (the share test)."""
    with jax.default_matmul_precision("highest"):
        return routed_experts(m, blk, top_k, 0)[0]


sequence_sums = jax.jit(batch_sums, static_argnames=(
    "n_head", "n_kv_head", "d_head", "top_k", "eps", "theta", "block",
    "expert_offset", "q_block"))
