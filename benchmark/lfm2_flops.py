"""Operations and bytes of the LFM2-8B-A1B cell, computed from shapes on
``trinity_flops``'s pure functions (a file of its own: the benchmark's
existing files are not edited).  Needed work only: an attention layer counts
the causal half; a conv layer its two projections and, apart, the gates and
the taps of its core; an expert layer counts the rows routed to the experts
held here (expected ``T * k * held / E`` under even routing, or the rows a
run really counted) and no other; the head counts the slice of the
vocabulary held here; recomputation counts nothing.

``flash_work``, ``held_experts_work`` and ``short_conv_work`` are the hooks
that the readers ``layer_metrics/flash_roofline.py``,
``held_experts_roofline.py`` and ``short_conv_roofline.py`` look up in the
module the configuration file names under ``flops_module``: the readers
themselves know no configuration's keys."""

from __future__ import annotations

from typing import Dict, List, Tuple

from . import trinity_flops

live_pairs = trinity_flops.live_pairs


def _layers(c: dict) -> Tuple[int, int, int, int]:
    """(conv layers, attention layers, dense layers, expert layers)."""
    kinds = c["layer_types"]
    n_conv = sum(k == "conv" for k in kinds)
    n_dense = c["num_dense_layers"]
    return n_conv, len(kinds) - n_conv, n_dense, len(kinds) - n_dense


def forward_flops_by_part(c: dict, seq: int) -> Dict[str, float]:
    """Forward FLOPs of one sequence of ``seq`` tokens by part of the model,
    from the configuration file's keys (``num_experts`` = the experts held,
    ``assumed.router_outputs`` = the experts routed over).  2 per
    multiply-add; the conv core's gates are one multiply each."""
    d, h, hkv = c["hidden_size"], c["num_attention_heads"], \
        c["num_key_value_heads"]
    dh = d // h
    taps = c["conv_L_cache"]
    n_conv, n_attn, n_dense, n_moe = _layers(c)
    routed_over = c["assumed"]["router_outputs"]
    k, held = c["num_experts_per_tok"], c["num_experts"]
    rows = seq * k * held / float(routed_over)
    return {
        "conv_projections": (2.0 * seq * d * 3 * d + 2.0 * seq * d * d)
        * n_conv,
        "conv_core": seq * d * (2.0 * taps + 2.0) * n_conv,
        "attention_projections": (2.0 * seq * d * (h * dh + 2 * hkv * dh)
                                  + 2.0 * seq * h * dh * d) * n_attn,
        "attention_scores": 4.0 * dh * h * live_pairs(seq) * n_attn,
        "dense_ffn": 6.0 * seq * d * c["intermediate_size"] * n_dense,
        "routed_experts": 6.0 * rows * d * c["moe_intermediate_size"] * n_moe,
        "router": 2.0 * seq * d * routed_over * n_moe,
        "head": 2.0 * seq * d * c["vocab_size"],
    }


def train_flops_per_sample(c: dict, seq: int) -> float:
    """Forward + backward = 3 x forward, per sequence of ``seq`` tokens."""
    return 3.0 * sum(forward_flops_by_part(c, seq).values())


def parameters(c: dict) -> Dict[str, int]:
    """Parameters by part, from the shapes the program holds (the selection
    bias, which no gradient trains, with its router; the table once: the
    head reads it)."""
    d, h, hkv = c["hidden_size"], c["num_attention_heads"], \
        c["num_key_value_heads"]
    dh = d // h
    n_conv, n_attn, n_dense, n_moe = _layers(c)
    routed_over = c["assumed"]["router_outputs"]
    return {
        "conv_operators": n_conv * (d * 3 * d + d * c["conv_L_cache"]
                                    + d * d),
        "attention": n_attn * (d * (h * dh + 2 * hkv * dh) + h * dh * d
                               + 2 * dh),
        "norms": (n_conv + n_attn) * 2 * d + d,
        "dense_ffn": n_dense * 3 * d * c["intermediate_size"],
        "router": n_moe * (d * routed_over + routed_over),
        "experts": n_moe * c["num_experts"] * 3 * d
        * c["moe_intermediate_size"],
        "table": c["vocab_size"] * d,
    }


def flash_work(c: dict, traffic: dict) -> List[Tuple[float, float]]:
    """(FLOPs, least bytes) of every flash attention kernel call of one
    sequence's training step: each attention layer's forward and its
    backward over the whole causal half
    (``trinity_flops.flash_layer_kernels``)."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    return [kernel for kind in c["layer_types"] if kind == "full_attention"
            for kernel in trinity_flops.flash_layer_kernels(
                h, c["num_key_value_heads"], traffic["seq_len"], d // h)]


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
        c["num_experts"]) * _layers(c)[3]


def short_conv_work(c: dict, traffic: dict, act_bytes: int = 2
                    ) -> List[Tuple[float, float]]:
    """(FLOPs, least bytes) of every ``short_conv`` op call of one
    sequence's training step: each conv layer's forward (the gate ``B * u``,
    ``L`` multiply-adds a channel, the gate ``C * c``; reads the three [T,
    d] parts of the input projection and the filter, writes one [T, d]) and
    its backward (``dc``, ``dC``, the taps run towards the past for ``dg``
    and once more for the filter's gradient, ``dB``, ``du``; reads the three
    parts and ``dOut``, writes the three parts' gradients and the float32
    filter gradient).  What the backward computes again of the forward (the
    gate product and the convolution) and what recomputation runs again
    count nothing; the two projections are ``mul`` ops and not in here."""
    seq, d, taps = traffic["seq_len"], c["hidden_size"], c["conv_L_cache"]
    stream = seq * d * act_bytes
    filt = d * taps * 4
    fwd = (seq * d * (2.0 * taps + 2.0), float(4 * stream + filt))
    bwd = (seq * d * (4.0 * taps + 4.0), float(7 * stream + 2 * filt))
    return [fwd, bwd] * _layers(c)[0]
