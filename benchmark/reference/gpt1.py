"""GPT-1 (Radford et al. 2018, ``openai-gpt``): next-token logits of a whole
sequence in one full-context forward pass — what prefill-then-decode through
a KV cache has to agree with.

Departures from the published model, which the configuration file lists
under ``assumed`` because the repo's model has them: a LayerNorm on the
embeddings, an output head that is not tied to the embedding, erf GELU.
"""

import functools

import jax

from . import _transformer as T


@functools.partial(jax.jit, static_argnames=("n_head", "eps"))
def logits(params, tokens, n_head, eps):
    """tokens [B, T] -> logits [B, T, V]; row t predicts token t + 1."""
    return T.head(params, T.encode(params, tokens, n_head, eps, causal=True))
