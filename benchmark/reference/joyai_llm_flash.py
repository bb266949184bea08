"""JoyAI-LLM-Flash (JD, ``jdopensource/JoyAI-LLM-Flash``, ``model_type``
``joyai_llm_flash``, 48B-A2.7B, the DeepSeek-V3 family's architecture): the
training loss of a batch, its main term and its multi-token-prediction term,
in plain float32 ``jax.numpy``, matmuls at ``highest`` precision.  No kernels,
no sort, no dispatch: dense ``[T, T]`` masks (a block of queries at a time so
that 8192 positions fit), every held expert's FFN over every token masked by
the top-k choice (the routing half is ``reference/trinity_mini.py``'s, whose
equations are the same: sigmoid scores, a selection bias, the kept scores
renormalised with ``1e-20`` and scaled), so this shares nothing with the
program's flash kernels, rotary op or routing.

One block (``a``, ``m`` are ``[T, d]``; ``RMS(z) = w * z / sqrt(mean(z^2) +
eps)``), two norms, no bias anywhere::

    a = RMS1(h)
    c_q = RMS_q(a W_qa)                          [T, r_q]       latent norm
    [q_nope | q_rope] = c_q W_qb                 [T, H, dn | dr]
    [c_kv | k_r] = a W_kva                       [T, r_kv | dr]
    c_kv = RMS_kv(c_kv)                                         latent norm
    [k_nope | v] = c_kv W_kvb                    [T, H, dn | dv]
    q_rope, k_r = RoPE(theta) as published (``rope_interleave``): each
        adjacent pair's members are moved to the two halves of the slice
        (x0 x2 x4 .. | x1 x3 x5 ..) and the halves rotated, angle pos *
        theta^(-2i/dr); the same permutation on both sides, so the scores
        are those of rotating the adjacent pairs in place
    k = [k_nope | k_r for every head];  q = [q_nope | q_rope]
    s = q k^T / sqrt(dn + dr), causal, softmax;  o = s v   [T, H * dv]
    h = h + o W_o
    m = RMS2(h)
    dense layer:  f = Wd (silu(Wg m) * Wu m)
    expert layer: s = sigmoid(m Wr) in float32;  sel = top-k(s + b);
                  w = s[sel] / (sum s[sel] + 1e-20) * routed_scaling_factor
                  f = shared(m) + sum_{e in sel, e held here} w_e expert_e(m)
    h = h + f

Main loss: ``z = RMS_f(h_L)``, mean cross-entropy of ``z W_head`` against
token ``i + 1``.  Multi-token prediction, depth 1 (arXiv:2412.19437 §2.2)::

    e = RMS_e(E[t_{i+1}]);  g = RMS_h(z_i);  u = [e | g] W_eh    [T, d]
    u' = Block_MTP(u)        one whole expert-layer block, positions 0..T-1
    L_mtp = mean cross-entropy of RMS_s(u') W_head against token i + 2

with ``E`` and ``W_head`` the main model's.  ``L = L_main + lambda L_mtp``.

What ``config.json`` names and does not spell out, from the family's
description (arXiv:2412.19437 §2.1-2.2) and its public modelling code, each
also under ``assumed`` in the configuration file: the RMSNorms on the two
latents; that ``k_r`` bypasses the latent and is one head shared by all; the
``1e-20``; the selection bias ``b`` (``e_score_correction_bias``), whatever
the parameters hold (zero at initialisation; no gradient reaches it); the
MTP wiring (``enorm``, ``hnorm``, ``eh_proj`` with the embedding first, the
block, ``shared_head.norm``, the shared ``E`` and head; ``g`` taken from the
main model's final-normed output, as the family's public serving code passes
it); lambda = 0.3.

The chip's share: ``gate_w``/``up_w``/``down_w`` hold ``E_here`` experts,
numbers ``expert_offset .. expert_offset + E_here - 1`` of the ``E`` the
router scores; what the absent experts would add is left out, as in the
program.

Parameters: {"wte" [V, d], "blocks": [{"ln1_w", "w_qa" [d, r_q], "q_norm_w"
[r_q], "w_qb" [r_q, H (dn + dr)], "w_kva" [d, r_kv + dr], "kv_norm_w" [r_kv],
"w_kvb" [r_kv, H (dn + dv)], "wo" [H dv, d], "ln2_w", and either "ffn_gate"
[d, F], "ffn_up", "ffn_down" [F, d] or "shared_gate" [d, f], "shared_up",
"shared_down" [f, d], "router_w" [d, E], "select_bias" [E], "gate_w" [E_here,
d, f], "up_w", "down_w" [E_here, f, d]}], "final_norm_w" [d], "head_w" [d, V],
"mtp": {"enorm_w", "hnorm_w", "eh_w" [2 d, d], "block": an expert-layer
block, "norm_w"}}.
"""

import jax
import jax.numpy as jnp

from .trinity_mini import gated, head_ce, rms_norm, routed_experts


def rope_published(x, theta):
    """x [T, H, dr] -> the same shape, each adjacent pair's members moved to
    the two halves and the halves rotated (the family's
    ``apply_rotary_pos_emb`` under ``rope_interleave``)."""
    t, h, dr = x.shape
    x = x.reshape(t, h, dr // 2, 2).transpose(0, 1, 3, 2).reshape(t, h, dr)
    half = dr // 2
    freq = theta ** (-2.0 * jnp.arange(half, dtype=jnp.float32) / dr)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None]
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return (x * cos + rot * sin).astype(x.dtype)


def attention(a, blk, n_head, d_nope, d_rope, d_v, eps, theta, q_block):
    """a [T, d] -> the latent attention's output after ``W_o``, [T, d]."""
    t = a.shape[0]
    r_kv = blk["kv_norm_w"].shape[0]
    q = (rms_norm(a @ blk["w_qa"], blk["q_norm_w"], eps)
         @ blk["w_qb"]).reshape(t, n_head, d_nope + d_rope)
    ckv = a @ blk["w_kva"]
    kv = (rms_norm(ckv[:, :r_kv], blk["kv_norm_w"], eps)
          @ blk["w_kvb"]).reshape(t, n_head, d_nope + d_v)
    k_r = rope_published(ckv[:, None, r_kv:], theta)          # one head
    q = jnp.concatenate([q[..., :d_nope],
                         rope_published(q[..., d_nope:], theta)], axis=-1)
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
        s = jnp.einsum("qhd,khd->hqk", qb, k) / jnp.sqrt(
            jnp.asarray(d_nope + d_rope, q.dtype))
        s = jnp.where((j <= i)[None], s, -jnp.inf)
        return None, jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1),
                                v)

    _, o = jax.lax.scan(rows, None, (
        q.reshape(t // q_block, q_block, n_head, d_nope + d_rope),
        jnp.arange(0, t, q_block)))
    return o.reshape(t, n_head * d_v) @ blk["wo"]


def block(h, blk, n_head, d_nope, d_rope, d_v, top_k, eps, theta,
          route_scale, expert_offset, q_block):
    """h [T, d] -> (h', top_e [T, k] or None)."""
    h = h + attention(rms_norm(h, blk["ln1_w"], eps), blk, n_head, d_nope,
                      d_rope, d_v, eps, theta, q_block)
    m = rms_norm(h, blk["ln2_w"], eps)
    if "ffn_gate" in blk:
        return h + gated(m, blk["ffn_gate"], blk["ffn_up"],
                         blk["ffn_down"]), None
    routed, top_e = routed_experts(m, blk, top_k, route_scale, expert_offset)
    return h + gated(m, blk["shared_gate"], blk["shared_up"],
                     blk["shared_down"]) + routed, top_e


def batch_sums(params, ids, labels, labels2, n_head, d_nope, d_rope, d_v,
               top_k, eps, theta, route_scale, expert_offset=0,
               q_block=1024):
    """Everything the loss needs of ids/labels/labels2 [B, T] (tokens ``i``,
    ``i + 1``, ``i + 2``), as sums over their tokens: {"tokens", "ce",
    "mtp_ce"}; and, not sums, "top_e" [L_expert (+ 1 with the MTP module,
    last), B*T, k], "hidden" [B, T, d] (the final RMSNorm's output) and
    "mtp_hidden" (the MTP module's normed output; absent without "mtp" in
    ``params``)."""
    with jax.default_matmul_precision("highest"):
        d = params["wte"].shape[1]
        mtp = params.get("mtp")

        def run(h, blk):
            # checkpointed: a gradient at 8192 positions keeps a block's
            # input and computes its inside again
            return jax.checkpoint(lambda h, blk: block(
                h, blk, n_head, d_nope, d_rope, d_v, top_k, eps, theta,
                route_scale, expert_offset, q_block))(h, blk)

        hidden, hidden2, tops = [], [], []
        for b in range(ids.shape[0]):
            h = params["wte"][ids[b]]
            seq_tops = []
            for blk in params["blocks"]:
                h, top_e = run(h, blk)
                if top_e is not None:
                    seq_tops.append(top_e)
            z = rms_norm(h, params["final_norm_w"], eps)
            hidden.append(z)
            if mtp is not None:
                u = jnp.concatenate(
                    [rms_norm(params["wte"][labels[b]], mtp["enorm_w"], eps),
                     rms_norm(z, mtp["hnorm_w"], eps)], axis=-1) @ mtp["eh_w"]
                u, top_e = run(u, mtp["block"])
                seq_tops.append(top_e)
                hidden2.append(rms_norm(u, mtp["norm_w"], eps))
            tops.append(jnp.stack(seq_tops))
        hidden = jnp.stack(hidden)
        out = {"tokens": jnp.float32(ids.size),
               "ce": head_ce(hidden.reshape(-1, d), params["head_w"],
                             labels.reshape(-1), q_block),
               "top_e": jnp.concatenate(tops, axis=1), "hidden": hidden}
        if mtp is not None:
            hidden2 = jnp.stack(hidden2)
            out.update(mtp_hidden=hidden2, mtp_ce=head_ce(
                hidden2.reshape(-1, d), params["head_w"],
                labels2.reshape(-1), q_block))
        return out


def loss_of_sums(sums, mtp_weight=0.3):
    """{"loss", "main", "mtp"} from :func:`batch_sums` (or the element-wise
    sum of several)."""
    main = sums["ce"] / sums["tokens"]
    mtp = sums["mtp_ce"] / sums["tokens"] if "mtp_ce" in sums else 0.0
    return {"loss": main + mtp_weight * mtp, "main": main, "mtp": mtp}


def loss(params, ids, labels, labels2, mtp_weight=0.3, **kw):
    """The training loss of a whole batch; ``jax.grad`` of it gives the
    reference gradients."""
    return loss_of_sums(batch_sums(params, ids, labels, labels2, **kw),
                        mtp_weight)["loss"]


def adamw_first_step(theta, g, lr, weight_decay, beta1=0.9, beta2=0.999,
                     epsilon=1e-8, store=None):
    """``theta_1 - theta_0`` of the first AdamW step from zero moments, one
    array, in float32 numpy (what the step's state is kept in): Fluid's
    Adam (``adam_op.h``, which ``optimizer.AdamOptimizer`` follows: the
    bias corrections folded into the rate, ``epsilon`` added outside them)
    with a decoupled decay::

        m = (1 - beta1) g;  v = (1 - beta2) g^2
        lr_t = lr sqrt(1 - beta2) / (1 - beta1)
        theta_1 = theta_0 - lr_t m / (sqrt(v) + epsilon) - lr decay theta_0

    so the step is ``lr g / (|g| + epsilon / sqrt(1 - beta2))``, the sign
    of the gradient wherever it is not tiny, plus the decay.  ``store``: a
    dtype the new parameter is rounded through (the program keeps float32;
    the control of the cell's check keeps bfloat16)."""
    import numpy as np
    theta = np.asarray(theta, np.float32)
    g = np.asarray(g, np.float32)
    lr_t = lr * np.sqrt(1 - beta2) / (1 - beta1)
    step = np.abs(g)                      # sqrt(v) = sqrt(1 - beta2) |g|
    step *= np.float32(np.sqrt(1 - beta2))
    step += np.float32(epsilon)
    np.divide(g, step, out=step)
    step *= np.float32(-lr_t * (1 - beta1))
    step -= np.float32(lr * weight_decay) * theta
    if store is None:
        return step
    kept = np.asarray(jnp.asarray(theta + step).astype(store)
                      .astype(jnp.float32))
    return kept - theta


sequence_sums = jax.jit(batch_sums, static_argnames=(
    "n_head", "d_nope", "d_rope", "d_v", "top_k", "eps", "theta",
    "route_scale", "expert_offset", "q_block"))
