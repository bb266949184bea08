"""Operations and bytes of the Nemotron-3-Nano cell, computed from shapes (a
file of its own: the benchmark's existing files are not edited; the flash
kernels' count is ``trinity_flops``'s pure function).  Needed work only, of
the experts HELD here: a Mamba-2 block counts its fused input projection,
its convolution, the output projection and, apart, the chunked scan; the
attention block its projections and the causal half at 32 over 2 heads; an
expert block the rows routed to the held experts (expected ``T * k * held /
E`` under even routing) beside the whole shared expert and router, every
expert at TWO products (``Wd relu(Wu x)^2``: no gate branch); the head the
slice of the vocabulary held here; recomputation counts nothing.

``ssd_work``, ``short_conv_work``, ``flash_work`` and ``held_experts_work``
are the hooks that the readers ``layer_metrics/ssd_scan_roofline.py``,
``short_conv_roofline.py``, ``flash_roofline.py`` and
``held_experts_roofline.py`` look up in the module the configuration file
names under ``flops_module``."""

from __future__ import annotations

from typing import Dict, List, Tuple

from . import trinity_flops

live_pairs = trinity_flops.live_pairs


def layers(c: dict) -> Dict[str, int]:
    """How many of the kept blocks are of each kind, by the letters of
    ``hybrid_override_pattern`` (one sublayer a block)."""
    pattern = c["hybrid_override_pattern"]
    assert len(pattern) == c["num_hidden_layers"], pattern
    return {"mamba": pattern.count("M"), "attention": pattern.count("*"),
            "expert": pattern.count("E")}


def _mamba(c: dict) -> Tuple[int, int, int, int, int]:
    """``(H, P, G, N, channels of the convolution)``."""
    h, p = c["mamba_num_heads"], c["mamba_head_dim"]
    g, n = c["n_groups"], c["ssm_state_size"]
    return h, p, g, n, h * p + 2 * g * n


def ssd_flops_per_chunk(chunk: int, heads: int, p: int, groups: int, n: int
                        ) -> float:
    """Forward FLOPs of one chunk of the chunked algorithm, all heads: ``C
    B^T`` a group, the masked decay matrix and its product with ``Delta x`` a
    head, the chunk's state and the earlier state's part of the output."""
    return (groups * 2.0 * chunk * chunk * n
            + heads * (2.0 * chunk * chunk * p + 2.0 * chunk * chunk
                       + 2 * 2.0 * chunk * p * n + 2.0 * p * n))


def ssd_work(c: dict, seq: int, chunk: int = None, act_bytes: int = 2
             ) -> List[Tuple[float, float]]:
    """(FLOPs, least bytes) of every ``ssd_scan`` op call of one sequence's
    training step, a forward and a backward a Mamba-2 block.  FLOPs:
    :func:`ssd_flops_per_chunk` over the ``seq / chunk`` chunks, twice that
    backward: the work of the chunked ALGORITHM, whatever implements it.
    Least bytes, from the op's streams and the chunk states: forward reads x
    (``act_bytes``), B, C and dt, writes Out and one float32 ``P x N`` state a
    chunk and head; backward reads those, the states and dOut and writes the
    four streams' gradients.  What the backward makes again of a chunk and
    what recomputation runs again count nothing; the projections, the
    convolution and the gated norm are other ops."""
    h, p, g, n, _ = _mamba(c)
    chunk = chunk or c["chunk_size"]
    chunks = -(-seq // chunk)
    fwd_flops = chunks * ssd_flops_per_chunk(chunk, h, p, g, n)
    streams = seq * (h * p + 2 * g * n + h) * act_bytes
    out = seq * h * p * act_bytes
    states = chunks * h * p * n * 4
    fwd = (fwd_flops, float(streams + out + states))
    bwd = (2.0 * fwd_flops, float(2 * (streams + out) + states))
    return [fwd, bwd] * layers(c)["mamba"]


def short_conv_work(c: dict, traffic: dict, act_bytes: int = 2
                    ) -> List[Tuple[float, float]]:
    """(FLOPs, least bytes) of every ``short_conv`` op call of one
    sequence's training step: a Mamba-2 block's one ungated convolution over
    the x, B and C channels, ``silu(conv(x) + b)`` (``solar_open2_flops.
    short_conv_work``'s count with the bias: one more add a channel and
    position forward, one more sum backward)."""
    _, _, _, _, channels = _mamba(c)
    seq, taps = traffic["seq_len"], c["conv_kernel"]
    stream = seq * channels * act_bytes
    filt = channels * (taps + 1) * 4
    fwd = (seq * channels * (2.0 * taps + 5.0), float(2 * stream + filt))
    bwd = (seq * channels * (4.0 * taps + 7.0), float(3 * stream + 2 * filt))
    return [fwd, bwd] * layers(c)["mamba"]


def flash_work(c: dict, traffic: dict) -> List[Tuple[float, float]]:
    """(FLOPs, least bytes) of every flash attention kernel call of one
    sequence's training step: the attention block's forward and backward
    over the whole causal half, 32 query heads over 2 K/V heads
    (``trinity_flops.flash_layer_kernels``)."""
    return trinity_flops.flash_layer_kernels(
        c["num_attention_heads"], c["num_key_value_heads"],
        traffic["seq_len"], c["head_dim"]) * layers(c)["attention"]


def held_experts_work(c: dict, traffic: dict, rows_share: float = None
                      ) -> List[Tuple[float, float]]:
    """(FLOPs, least bytes) of every grouped matmul of the held experts in
    one sequence's training step, all expert blocks: SIX a block over the
    rows routed here (forward up and down, their two row cotangents, their
    two weight gradients: an un-gated expert has no third product),
    ``rows_share`` of the ``T * k`` slots a block (default even routing's,
    held over routed-over)."""
    if rows_share is None:
        rows_share = c["n_routed_experts"] / float(
            c["assumed"]["router_outputs"])
    rows = traffic["seq_len"] * c["num_experts_per_tok"] * rows_share
    nine = trinity_flops.held_experts_matmuls(
        rows, c["hidden_size"], c["moe_intermediate_size"],
        c["n_routed_experts"])
    return (nine[:4] + nine[-2:]) * layers(c)["expert"]


def forward_flops_by_part(c: dict, seq: int) -> Dict[str, float]:
    """Forward FLOPs of one sequence of ``seq`` tokens by part of the model,
    from the configuration file's keys (``n_routed_experts`` the experts
    HELD; ``assumed.router_outputs`` the experts routed over)."""
    k = layers(c)
    d = c["hidden_size"]
    h, p, g, n, channels = _mamba(c)
    hq, hkv, dh = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"])
    routed_over = c["assumed"]["router_outputs"]
    rows = seq * c["num_experts_per_tok"] * c["n_routed_experts"] \
        / float(routed_over)
    return {
        "mamba_projections": (2.0 * seq * d * (h * p + channels + h)
                              + 2.0 * seq * h * p * d) * k["mamba"],
        "mamba_conv": seq * channels * 2.0 * c["conv_kernel"] * k["mamba"],
        "ssd_scan": sum(fl for fl, _ in ssd_work(c, seq)[::2]),
        "attention_projections": (2.0 * seq * d * (hq + 2 * hkv) * dh
                                  + 2.0 * seq * hq * dh * d)
        * k["attention"],
        "attention_scores": 4.0 * dh * hq * live_pairs(seq) * k["attention"],
        "shared_expert": 4.0 * seq * d
        * c["moe_shared_expert_intermediate_size"] * k["expert"],
        "routed_experts": 4.0 * rows * d * c["moe_intermediate_size"]
        * k["expert"],
        "router": 2.0 * seq * d * routed_over * k["expert"],
        "head": 2.0 * seq * d * c["vocab_size"],
    }


def train_flops_per_sample(c: dict, seq: int) -> float:
    """Forward + backward = 3 x forward, per sequence of ``seq`` tokens."""
    return 3.0 * sum(forward_flops_by_part(c, seq).values())


def parameters(c: dict) -> Dict[str, int]:
    """Parameters by part, from the shapes the program holds (the selection
    bias, which no gradient trains, with its router; a block's one norm with
    its sublayer)."""
    k = layers(c)
    d = c["hidden_size"]
    h, p, g, n, channels = _mamba(c)
    hq, hkv, dh = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"])
    routed_over = c["assumed"]["router_outputs"]
    return {
        "mamba": k["mamba"] * (d * (h * p + channels + h)
                               + channels * (c["conv_kernel"] + 1) + 3 * h
                               + h * p + h * p * d + d),
        "attention": k["attention"] * (d * (hq + 2 * hkv) * dh
                                       + hq * dh * d + d),
        "shared_expert": k["expert"] * 2 * d
        * c["moe_shared_expert_intermediate_size"],
        "router": k["expert"] * (d * routed_over + routed_over),
        "experts": k["expert"] * c["n_routed_experts"] * 2 * d
        * c["moe_intermediate_size"],
        "expert_norms": k["expert"] * d,
        "final_norm": d,
        "embedding_and_head": 2 * c["vocab_size"] * d,
    }
