"""Post-LN transformer stack in plain float32 ``jax.numpy``.

The block of Vaswani et al. 2017 as BERT (Devlin et al. 2018) and GPT-1
(Radford et al. 2018) use it: token + learned position embeddings,
``x = LN(x + Attn(x))``, ``x = LN(x + FFN(x))`` with a GELU FFN.  No kernels,
no cache, no fused projections beyond one [d, 3d] matrix for Q, K, V (which
is how both models are published).  Matmul precision is forced to
``highest``: on a TPU a float32 matmul otherwise multiplies in bfloat16.

Parameters are a dict of float32 arrays:
    wte [V, d], wpe [P, d], emb_ln_w/emb_ln_b [d] (optional),
    blocks: list of {qkv_w [d, 3d], qkv_b, proj_w [d, d], proj_b,
                     ln1_w, ln1_b, fc1_w [d, F], fc1_b, fc2_w [F, d], fc2_b,
                     ln2_w, ln2_b},
    head_w [d, V], head_b [V]
"""

import jax
import jax.numpy as jnp


def layer_norm(x, w, b, eps):
    m = jnp.mean(x, axis=-1, keepdims=True)
    v = jnp.mean(jnp.square(x - m), axis=-1, keepdims=True)
    return (x - m) / jnp.sqrt(v + eps) * w + b


def gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / jnp.sqrt(2.0).astype(x.dtype)))


def encode(params, tokens, n_head, eps, causal):
    """tokens [B, T] int -> hidden states [B, T, d]."""
    with jax.default_matmul_precision("highest"):
        b, t = tokens.shape
        x = params["wte"][tokens] + params["wpe"][:t][None]
        if "emb_ln_w" in params:
            x = layer_norm(x, params["emb_ln_w"], params["emb_ln_b"], eps)
        d = x.shape[-1]
        dh = d // n_head
        mask = jnp.tril(jnp.ones((t, t), bool)) if causal else None
        for blk in params["blocks"]:
            qkv = x @ blk["qkv_w"] + blk["qkv_b"]
            q, k, v = jnp.split(qkv, 3, axis=-1)
            q = q.reshape(b, t, n_head, dh).transpose(0, 2, 1, 3)
            k = k.reshape(b, t, n_head, dh).transpose(0, 2, 1, 3)
            v = v.reshape(b, t, n_head, dh).transpose(0, 2, 1, 3)
            s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(
                jnp.float32(dh))
            if mask is not None:
                s = jnp.where(mask[None, None], s, -jnp.inf)
            a = jax.nn.softmax(s, axis=-1)
            ctx = jnp.einsum("bhqk,bhkd->bhqd", a, v)
            ctx = ctx.transpose(0, 2, 1, 3).reshape(b, t, d)
            x = layer_norm(x + ctx @ blk["proj_w"] + blk["proj_b"],
                           blk["ln1_w"], blk["ln1_b"], eps)
            h = gelu(x @ blk["fc1_w"] + blk["fc1_b"])
            x = layer_norm(x + h @ blk["fc2_w"] + blk["fc2_b"],
                           blk["ln2_w"], blk["ln2_b"], eps)
        return x


def head(params, hidden):
    with jax.default_matmul_precision("highest"):
        return hidden @ params["head_w"] + params["head_b"]
