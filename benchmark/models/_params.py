"""The repo's transformer parameter names (``models/transformer.py``) in the
layout of ``reference/_transformer.py``; shared by the BERT and GPT adapters,
which differ only in the name of the output head."""


def transformer_reference_params(scope, n_layer, head, on_host=False):
    """``on_host=True`` where training steps will run before the parameters
    are used: a step donates the scope's buffers, and a copy kept on the
    device would count in the system's peak memory."""
    import jax.numpy as jnp
    import numpy as np

    def g(name):
        v = scope.find_var(name)
        return np.asarray(v, np.float32) if on_host else \
            jnp.asarray(v, jnp.float32)

    blocks = []
    for i in range(n_layer):
        p = f"enc_{i}"
        blocks.append({
            "qkv_w": g(f"{p}.attn.qkv.w"), "qkv_b": g(f"{p}.attn.qkv.b"),
            "proj_w": g(f"{p}.attn.out.w"), "proj_b": g(f"{p}.attn.out.b"),
            "ln1_w": g(f"{p}.ln1.w"), "ln1_b": g(f"{p}.ln1.b"),
            "fc1_w": g(f"{p}.ffn.fc1.w"), "fc1_b": g(f"{p}.ffn.fc1.b"),
            "fc2_w": g(f"{p}.ffn.fc2.w"), "fc2_b": g(f"{p}.ffn.fc2.b"),
            "ln2_w": g(f"{p}.ln2.w"), "ln2_b": g(f"{p}.ln2.b")})
    return {"wte": g("word_embedding"), "wpe": g("pos_embedding"),
            "emb_ln_w": g("pre_encoder.ln.w"),
            "emb_ln_b": g("pre_encoder.ln.b"), "blocks": blocks,
            "head_w": g(f"{head}.w"), "head_b": g(f"{head}.b")}
