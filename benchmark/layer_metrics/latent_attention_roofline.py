"""Share of their roofline the flash attention ops of a latent-attention
model reach: the least time the chip could take for each block's forward and
backward on the causal half at the two widths (scores over ``qk_nope_head_dim
+ qk_rope_head_dim``, values over ``v_head_dim``; the rotary key counted at
the one head the model makes, not at the copy a program may broadcast;
``joyai_flops.latent_flash_layer_kernels``: the larger of FLOPs over the bf16
peak and bytes over the HBM bandwidth, the backward's recomputed scores not
counted), the multi-token-prediction module's block among them, times the
traced steps, over the device time under the program ops ``flash_attention``
and ``flash_attention_grad``.  Read by program op, not by kernel name
(PERF.md section 4): whatever implements the ops is measured against the
same needed work.  Nothing to read where the configuration has no
``kv_lora_rank`` or the trace holds no such op."""

from .. import flops, joyai_flops, op_scopes


def read(inputs):
    peaks, c = inputs["peaks"], inputs["config"]
    steps = inputs["counters"].get("steps_traced")
    if not peaks or not steps or "kv_lora_rank" not in c:
        return None
    ms = op_scopes.train_ms_of_ops(inputs, ("flash_attention",))
    if not ms:
        return None
    batch = inputs["facts"]["batch"] // inputs["facts"]["chips"]
    least = batch * sum(
        flops.roofline_seconds(fl, by, peaks)[0]
        for layer in joyai_flops.flash_kernels_of_model(
            c, inputs["traffic"]["seq_len"]) for fl, by in layer)
    return 100.0 * least / (ms / 1e3)
