"""BERT-base (Devlin et al. 2018, ``bert-base-uncased``): the masked-LM loss
of a batch, float32, dropout off.

Departures listed in the configuration file under ``assumed``: no
next-sentence head and no segment embedding (the repo's recipe feeds
neither), the MLM head is one [d, V] projection with a bias (no transform
layer), LayerNorm epsilon 1e-5.
"""

import functools

import jax
import jax.numpy as jnp

from . import _transformer as T


@functools.partial(jax.jit, static_argnames=("n_head", "eps"))
def mlm_loss(params, src_ids, mask_pos, lm_label, n_head, eps):
    """src_ids [B, T]; mask_pos [B, N] flat positions b*T + t; lm_label
    [B, N] with 0 = not a target.  Mean cross-entropy over the targets."""
    hid = T.encode(params, src_ids, n_head, eps, causal=False)
    b, t, d = hid.shape
    picked = hid.reshape(b * t, d)[mask_pos.reshape(-1)]
    lg = T.head(params, picked)
    logp = jax.nn.log_softmax(lg, axis=-1)
    lab = lm_label.reshape(-1)
    ce = -jnp.take_along_axis(logp, lab[:, None], axis=1)[:, 0]
    w = (lab > 0).astype(jnp.float32)
    return jnp.sum(ce * w) / (jnp.sum(w) + 1e-6)
