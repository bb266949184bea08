"""NVIDIA-Nemotron-3-Nano-30B-A3B (``nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-
BF16``, ``model_type`` ``nemotron_h``): the training loss of a batch in plain
float32 ``jax.numpy``, matmuls at ``highest`` precision.  No chunks, no
kernels, no ``top_k``, no dispatch: the Mamba-2 blocks run their recurrence
TOKEN BY TOKEN (a ``lax.scan`` over the positions), the attention block the
literal softmax over dense ``[T, T]`` masks a block of queries at a time with
K and V repeated per query head, the router sorts (``argsort``) where the
program selects, and every held expert's FFN runs over every token and is
masked by the choice.  It shares no code with ``paddle_tpu/`` and none with
the other cells' references.

One block has ONE sublayer (``x``, ``m`` are ``[T, d]``; ``RMS_w(v) = w * v /
sqrt(mean(v^2) + eps)``), pre-norm, no bias on any projection::

    m = RMS(x);  out = x + Mixer(m),  Mixer by the pattern's letter

    M, Mamba-2 (H heads of P, G groups, state N, float32):
        [z | xBC | dt] = m W_in            H P | H P + 2 G N | H
        xBC = silu(c(xBC) + b)             c(y)[t] = sum_{j=0..3} w[:, j] *
                                           y[t - 3 + j], depthwise, causal,
                                           zeros before the sequence starts
        [x | B | C] = xBC                  x_t [H, P]; B_t, C_t [G, N]; head
                                           h reads group h // (H / G)
        Delta_t = softplus(dt_t + dt_bias) [H], no clamp
        a_t = exp(Delta_t A),  A = -exp(A_log), written 1 + expm1(Delta_t A)
        S_t = a_t S_{t-1} + Delta_t x_t B_t^T      S_0 = 0, S [P, N] a head
        y_t = S_t C_t + D x_t
        mix = [w * RMS_groups(y * silu(z))] W_out  the gate FIRST, then the
                                           RMS over each of the G groups of
                                           H P / G channels, one [H P] scale
    *, attention (Hq query heads over Hkv K/V heads of dh, NO position):
        q, k, v = m Wq, m Wk, m Wv;  score = q k^T dh^-0.5, causal, softmax
        mix = ctx W_o                      no gate, no QK-norm, no rotary
    E, experts: s = sigmoid(m W_r) in float32, E scores
        sel = the top_k largest (s + b) (ties: the lower number)
        w = s[sel] / (sum s[sel] + 1e-20) * routed_scaling_factor
        expert_e(m) = relu(m W_up_e)^2 W_down_e    no gate branch
        mix = shared(m) + sum_{e in sel, e held here} w_e expert_e(m)
                                           shared: the same form, wider

After the last block a final RMSNorm and an untied head; loss = mean
next-token cross-entropy over every position, nothing else.

Departures from the published description, each also in the configuration
file under ``assumed``: ``config.json`` is silent on the layer equations,
which are ``modeling_nemotron_h``'s (Mamba-2: arXiv:2405.21060; Nemotron-H:
arXiv:2504.03624; the router DeepSeek-V3's ``noaux_tc`` at one group);
``rope_theta`` and ``partial_rotary_factor`` stand in the row unused (the
family's attention applies no position); ``time_step_limit`` has no key and
the family's default (0, inf) clamps nothing; the selection bias ``b`` is
whatever the parameters hold, zero at initialisation, and no gradient trains
it; no MTP module; ``num_logits_to_keep`` is serving's.

The chip's share: ``up_w`` / ``down_w`` hold the ``E_here`` experts held,
numbers ``expert_offset .. expert_offset + E_here - 1`` of the ``E`` the
router scores; the experts' part is the partial sum over the held experts,
what the absent ones would add is left out, as in the program.  Mixers,
shared expert, router and norms are whole.

The 8192-step recurrence is checkpointed in blocks of ``scan_block``
positions: a gradient keeps one state a block and head and runs the block's
steps again.

Parameters: {"wte" [V, d], "blocks": [{"norm_w" [d]; an M block "w_in" [d, 2
H P + 2 G N + H], "conv_w" [H P + 2 G N, 4], "conv_b", "a_log", "d_skip",
"dt_bias" [H], "gnorm_w" [H P], "w_out" [H P, d]; the * block "wq" [d, Hq
dh], "wk", "wv" [d, Hkv dh], "wo" [Hq dh, d]; an E block "router_w" [d, E],
"select_bias" [E], "shared_up" [d, fs], "shared_down" [fs, d], "up_w"
[E_here, d, f], "down_w" [E_here, f, d]}], "final_norm_w" [d], "head_w" [d,
V]}.
"""

import jax
import jax.numpy as jnp

NORM_EPS = 1e-20          # joins the sum that renormalises the kept scores


def rms(v, w, eps):
    return w * v * jax.lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True)
                                 + eps)


def relu2_ffn(m, w_up, w_down):
    return jnp.square(jax.nn.relu(m @ w_up)) @ w_down


def causal_conv_silu(y, w, b):
    """y [T, c], w [c, L], b [c]: ``silu(sum_j w[:, j] y[t - (L - 1) + j] +
    b)``, zeros before the start."""
    t, taps = y.shape[0], w.shape[1]
    padded = jnp.concatenate([jnp.zeros((taps - 1, y.shape[1]), y.dtype), y])
    return jax.nn.silu(sum(w[:, j] * padded[j:j + t] for j in range(taps))
                       + b)


def recurrence(x, delta, a, b, c, block):
    """One head, token by token: x [T, P], delta [T], a a scalar (negative),
    b, c [T, N] -> y [T, P] without the skip; ``block`` positions a
    checkpoint."""
    t, p = x.shape
    n = b.shape[1]
    if t % block:
        block = t

    def one(state, xs):
        x_t, d_t, b_t, c_t = xs
        # multiplies and sums, no matmul inside the step; the decay written
        # 1 + expm1(Delta A): a device's exp beside 0 is some ulps off, and
        # the recurrence compounds it 8192 times (PERF.md section 6, PR 55)
        state = state * (1.0 + jnp.expm1(d_t * a)) \
            + (d_t * x_t)[:, None] * b_t[None, :]
        return state, jnp.sum(state * c_t[None, :], axis=1)

    @jax.checkpoint
    def run(state, xs):
        return jax.lax.scan(one, state, xs)

    xs = tuple(v.reshape(t // block, block, *v.shape[1:])
               for v in (x, delta, b, c))
    _, y = jax.lax.scan(run, jnp.zeros((p, n), x.dtype), xs)
    return y.reshape(t, p)


def heads_from_groups(v, heads):
    """v [T, G, N] -> [T, H, N]: head ``h`` reads group ``h // (H / G)``."""
    return jnp.repeat(v, heads // v.shape[1], axis=1)


def gated_group_norm(y, z, w, groups, eps):
    """``w * RMS_groups(y * silu(z))`` over [T, d]: the gate FIRST
    (``norm_before_gate`` false), then the RMS over each of ``groups``
    consecutive groups of ``d / groups`` channels, then one scale a
    channel."""
    t, d = y.shape
    gated = (y * jax.nn.silu(z)).reshape(t, groups, d // groups)
    gated = gated * jax.lax.rsqrt(
        jnp.mean(gated * gated, axis=-1, keepdims=True) + eps)
    return gated.reshape(t, d) * w


def mamba2(m, blk, groups, d_state, eps, scan_block):
    t = m.shape[0]
    h = blk["a_log"].shape[0]
    d_in = blk["gnorm_w"].shape[0]
    p, gn = d_in // h, groups * d_state
    zxd = m @ blk["w_in"]
    z, xbc, dt = zxd[:, :d_in], zxd[:, d_in:2 * d_in + 2 * gn], \
        zxd[:, 2 * d_in + 2 * gn:]
    xbc = causal_conv_silu(xbc, blk["conv_w"], blk["conv_b"])
    x = xbc[:, :d_in].reshape(t, h, p)
    b = heads_from_groups(xbc[:, d_in:d_in + gn].reshape(t, groups, d_state),
                          h)
    c = heads_from_groups(xbc[:, d_in + gn:].reshape(t, groups, d_state), h)
    delta = jax.nn.softplus(dt + blk["dt_bias"])
    a = -jnp.exp(blk["a_log"])
    y = jax.vmap(lambda x, dl, a, b, c: recurrence(x, dl, a, b, c,
                                                   scan_block),
                 in_axes=(1, 1, 0, 1, 1), out_axes=1)(x, delta, a, b, c)
    y = (y + blk["d_skip"][None, :, None] * x).reshape(t, d_in)
    return gated_group_norm(y, z, blk["gnorm_w"], groups, eps) @ blk["w_out"]


def positions(q, k):
    """What the attention does to q [T, Hq, dh] and k [T, Hkv, dh] for
    their positions: nothing (the family's attention applies no rotary and
    no other position; ``rope_theta`` stands in the row unused)."""
    return q, k


def attention(m, blk, d_head, q_block):
    t = m.shape[0]
    hq = blk["wq"].shape[1] // d_head
    hkv = blk["wk"].shape[1] // d_head
    q, k = positions((m @ blk["wq"]).reshape(t, hq, d_head),
                     (m @ blk["wk"]).reshape(t, hkv, d_head))
    k = jnp.repeat(k, hq // hkv, axis=1)
    v = jnp.repeat((m @ blk["wv"]).reshape(t, hkv, d_head), hq // hkv, axis=1)
    cols = jnp.arange(t)[None, :]
    if t % q_block:
        q_block = t

    @jax.checkpoint          # a gradient keeps no block's [H, q_block, T]
    def some_queries(_, xs):
        qb, first = xs
        s = jnp.einsum("qhd,khd->hqk", qb, k) * d_head ** -0.5
        rows = first + jnp.arange(q_block)[:, None]
        p = jax.nn.softmax(jnp.where((cols <= rows)[None], s, -jnp.inf),
                           axis=-1)
        return None, jnp.einsum("hqk,khd->qhd", p, v)

    _, ctx = jax.lax.scan(some_queries, None, (
        q.reshape(t // q_block, q_block, hq, d_head),
        jnp.arange(0, t, q_block)))
    return ctx.reshape(t, hq * d_head) @ blk["wo"]


def route(m, blk, top_k, route_scale):
    """``(weight [S, E], top_e [S, k])``: each token's weight on every
    expert, zero off its chosen ``k``; plain top-k by sorting, ties to the
    lower number."""
    s = jax.nn.sigmoid(m.astype(jnp.float32)
                       @ blk["router_w"].astype(jnp.float32))
    sel = jax.lax.stop_gradient(s) + blk["select_bias"].astype(jnp.float32)
    top_e = jnp.argsort(-sel, axis=-1, stable=True)[:, :top_k]
    chosen = jnp.zeros_like(s).at[
        jnp.arange(s.shape[0])[:, None], top_e].set(1.0)
    kept = s * chosen
    return kept / (jnp.sum(kept, axis=-1, keepdims=True) + NORM_EPS) \
        * route_scale, top_e


def held_experts(m, blk, weight, expert_offset):
    """The part of the routed experts' output that the experts held in
    ``blk`` give: every held expert over every token, times the token's
    weight on it."""
    held = blk["up_w"].shape[0]
    w_here = jax.lax.dynamic_slice_in_dim(weight, expert_offset, held,
                                          axis=1)

    @jax.checkpoint          # a gradient computes each expert's FFN again
    def one(acc, xs):
        w_up, w_down, w_e = xs
        return acc + w_e[:, None].astype(m.dtype) \
            * relu2_ffn(m, w_up, w_down), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(m),
                          (blk["up_w"], blk["down_w"], w_here.T))
    return out


def block(x, blk, kw):
    """x [T, d] -> (out [T, d], top_e [T, k] or None)."""
    m = rms(x, blk["norm_w"], kw["eps"])
    if "a_log" in blk:
        return x + mamba2(m, blk, kw["groups"], kw["d_state"], kw["eps"],
                          kw["scan_block"]), None
    if "wq" in blk:
        return x + attention(m, blk, kw["d_head"], kw["q_block"]), None
    weight, top_e = route(m, blk, kw["top_k"], kw["route_scale"])
    return x + relu2_ffn(m, blk["shared_up"], blk["shared_down"]) \
        + held_experts(m, blk, weight, kw["expert_offset"]), top_e


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


def batch_sums(params, ids, labels, groups, d_state, d_head, top_k, eps,
               route_scale=1.0, expert_offset=0, q_block=512,
               scan_block=128):
    """Everything the loss needs of ids/labels [B, T], as sums over their
    tokens: {"tokens", "ce"}; and, not sums, "top_e" [L_expert, B*T, k] (each
    token's experts in every expert block, all ``E`` numbered) and "hidden"
    [B, T, d] (the final RMSNorm's output, what the head reads)."""
    kw = dict(groups=groups, d_state=d_state, d_head=d_head, top_k=top_k,
              eps=eps, route_scale=route_scale, expert_offset=expert_offset,
              q_block=q_block, scan_block=scan_block)
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
    rate weight_decay p``, of the parameter before the step), on EVERY
    parameter, ``A_log``, ``D``, ``dt_bias`` and the norm scales among them
    (as this repo's AdamW decays ``kda``'s ``A_log`` and ``dt_bias``)."""
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
    "groups", "d_state", "d_head", "top_k", "eps", "route_scale",
    "expert_offset", "q_block", "scan_block"))
