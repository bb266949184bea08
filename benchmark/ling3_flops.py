"""Operations and bytes of the Ling-3.0-flash-VL cell, computed from shapes
on ``trinity_flops``'s, ``joyai_flops``'s and ``solar_open2_flops``'s pure
functions (a file of its own: the benchmark's existing files are not edited).
Needed work only, of the heads and experts HELD here: a KDA layer counts its
one fused input projection (Q, K, V, both full-rank gates and beta), its three
convolutions and, apart, the chunked scan; the latent-attention layer its
projections and the causal half at the held heads' two widths; an expert
layer the rows routed to the held experts (expected ``T * k * held / E`` under
even routing) beside the whole shared expert and router; the dense layer its
gated FFN; the head the slice of the vocabulary held here; recomputation
counts nothing.

``flash_work``, ``held_experts_work``, ``short_conv_work`` and ``kda_work``
are the hooks that the readers ``layer_metrics/flash_roofline.py``,
``held_experts_roofline.py``, ``short_conv_roofline.py`` and
``kda_scan_roofline.py`` look up in the module the configuration file names
under ``flops_module``."""

from __future__ import annotations

from typing import Dict, List, Tuple

from . import joyai_flops, solar_open2_flops, trinity_flops

live_pairs = trinity_flops.live_pairs


def layers(c: dict) -> Dict[str, int]:
    """How many of the kept layers are of each kind, by the published rule
    on the published numbers (``assumed.first_layer`` is the number of the
    first kept layer; the file's ``first_k_dense_replace`` counts the dense
    layers KEPT, which lead the slice): latent attention where ``(i + 1) %
    layer_group_size == 0``, KDA elsewhere."""
    first = c["assumed"]["first_layer"]
    numbers = range(first, first + c["num_hidden_layers"])
    mla = sum((i + 1) % c["layer_group_size"] == 0 for i in numbers)
    dense = min(c["first_k_dense_replace"], len(numbers))
    return {"mla": mla, "kda": len(numbers) - mla, "dense": dense,
            "expert": len(numbers) - dense}


def _kda_as_solar(c: dict) -> dict:
    """The keys ``solar_open2_flops``'s scan and convolution counts read, for
    this configuration's KDA layers."""
    n = layers(c)
    return {"hidden_size": c["hidden_size"], "head_dim": c["head_dim"],
            "num_attention_heads": 0, "num_key_value_heads": 0,
            "num_hidden_layers": n["kda"], "gqa_layers": [],
            "linear_attn_config": {
                "num_heads": c["num_attention_heads"],
                "head_dim": c["head_dim"],
                "short_conv_kernel_size": c["short_conv_kernel_size"]}}


def kda_work(c: dict, seq: int, chunk: int = 64, act_bytes: int = 2
             ) -> List[Tuple[float, float]]:
    """(FLOPs, least bytes) of every ``kda_scan`` op call of one sequence's
    training step, a forward and a backward a KDA layer at the heads held
    (``solar_open2_flops.kda_work``: the same op, the same count)."""
    return solar_open2_flops.kda_work(_kda_as_solar(c), seq, chunk,
                                      act_bytes)


def short_conv_work(c: dict, traffic: dict, act_bytes: int = 2
                    ) -> List[Tuple[float, float]]:
    """(FLOPs, least bytes) of every ``short_conv`` op call of one
    sequence's training step: a KDA layer's one ungated convolution over the
    Q, K and V channels of the heads held."""
    return solar_open2_flops.short_conv_work(_kda_as_solar(c), traffic,
                                             act_bytes)


def flash_work(c: dict, traffic: dict) -> List[Tuple[float, float]]:
    """(FLOPs, least bytes) of every flash attention kernel call of one
    sequence's training step: the latent-attention layers' forward and
    backward on the causal half at the heads held, scores over
    ``qk_nope_head_dim + qk_rope_head_dim``, values over ``v_head_dim``, the
    rotary key at its one head (``joyai_flops.latent_flash_layer_kernels``)."""
    return joyai_flops.latent_flash_layer_kernels(
        c["num_attention_heads"], traffic["seq_len"], c["qk_nope_head_dim"],
        c["qk_rope_head_dim"], c["v_head_dim"]) * layers(c)["mla"]


def held_experts_work(c: dict, traffic: dict, rows_share: float = None
                      ) -> List[Tuple[float, float]]:
    """(FLOPs, least bytes) of every grouped matmul of the held experts in
    one sequence's training step, all expert layers: nine a layer over the
    rows routed here, ``rows_share`` of the ``T * k`` slots a layer (default
    even routing's, held over routed-over)."""
    if rows_share is None:
        rows_share = c["num_experts"] / float(c["assumed"]["router_outputs"])
    rows = traffic["seq_len"] * c["num_experts_per_tok"] * rows_share
    return trinity_flops.held_experts_matmuls(
        rows, c["hidden_size"], c["moe_intermediate_size"],
        c["num_experts"]) * layers(c)["expert"]


def forward_flops_by_part(c: dict, seq: int) -> Dict[str, float]:
    """Forward FLOPs of one sequence of ``seq`` tokens by part of the model,
    from the configuration file's keys (the heads and ``num_experts`` are
    those HELD; ``assumed.router_outputs`` the experts routed over)."""
    n = layers(c)
    d, h, dk = c["hidden_size"], c["num_attention_heads"], c["head_dim"]
    dn, dr, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                  c["v_head_dim"])
    r, taps = c["kv_lora_rank"], c["short_conv_kernel_size"]
    f, fs = c["moe_intermediate_size"], c["moe_shared_expert_intermediate_size"]
    routed_over = c["assumed"]["router_outputs"]
    rows = seq * c["num_experts_per_tok"] * c["num_experts"] \
        / float(routed_over)
    dq = h * dk
    return {
        "kda_projections": (2.0 * seq * d * (5 * dq + h)
                            + 2.0 * seq * dq * d) * n["kda"],
        "kda_conv": seq * 3 * dq * 2.0 * taps * n["kda"],
        "kda_scan": sum(fl for fl, _ in kda_work(c, seq)[::2]),
        "attention_projections": (
            2.0 * seq * d * (h * (dn + dr) + r + dr + h)
            + 2.0 * seq * r * h * (dn + dv)
            + 2.0 * seq * h * dv * d) * n["mla"],
        "attention_scores": 2.0 * (dn + dr + dv) * h * live_pairs(seq)
        * n["mla"],
        "dense_ffn": 6.0 * seq * d * c["intermediate_size"] * n["dense"],
        "shared_expert": 6.0 * seq * d * fs * n["expert"],
        "routed_experts": 6.0 * rows * d * f * n["expert"],
        "router": 2.0 * seq * d * routed_over * n["expert"],
        "head": 2.0 * seq * d * c["vocab_size"],
    }


def train_flops_per_sample(c: dict, seq: int) -> float:
    """Forward + backward = 3 x forward, per sequence of ``seq`` tokens."""
    return 3.0 * sum(forward_flops_by_part(c, seq).values())


def parameters(c: dict) -> Dict[str, int]:
    """Parameters by part, from the shapes the program holds (the selection
    bias, which no gradient trains, with its router)."""
    n = layers(c)
    d, h, dk = c["hidden_size"], c["num_attention_heads"], c["head_dim"]
    dn, dr, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                  c["v_head_dim"])
    r, taps = c["kv_lora_rank"], c["short_conv_kernel_size"]
    f, fs = c["moe_intermediate_size"], c["moe_shared_expert_intermediate_size"]
    routed_over = c["assumed"]["router_outputs"]
    dq = h * dk
    return {
        "kda": n["kda"] * (d * (5 * dq + h) + 3 * dq * taps + h + dq + dk
                           + dq * d),
        "attention": n["mla"] * (d * h * (dn + dr) + d * (r + dr) + r
                                 + r * h * (dn + dv) + 2 * dn + d * h
                                 + h * dv * d),
        "norms": c["num_hidden_layers"] * 2 * d + d,
        "dense_ffn": n["dense"] * 3 * d * c["intermediate_size"],
        "shared_expert": n["expert"] * 3 * d * fs,
        "router": n["expert"] * (d * routed_over + routed_over),
        "experts": n["expert"] * c["num_experts"] * 3 * d * f,
        "embedding_and_head": 2 * c["vocab_size"] * d,
    }
