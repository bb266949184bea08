"""Operations and bytes of the Xing4.0-29B-A4B cell, computed from shapes (a
file of its own: the benchmark's existing files are not edited).  Needed work
only: attention counts the causal half at the two widths; an expert layer
counts the rows routed to the experts held here (expected ``T * k * held /
E`` under even routing, or the rows a run really counted) and no other; the
head counts the slice of the vocabulary held here; a hyper-connection counts
its projection onto the ``2 n + n^2`` coefficients and its two mixes of the
streams (Sinkhorn-Knopp's 40 normalisations of 16 numbers a token are a
rounding error beside them and are left out); recomputation counts nothing.

``flash_work``, ``held_experts_work`` and ``hc_work`` are the hooks that the
readers ``layer_metrics/flash_roofline.py``, ``held_experts_roofline.py`` and
``hyper_connection_roofline.py`` look up in the module the configuration file
names under ``flops_module``: the readers themselves know no configuration's
keys."""

from __future__ import annotations

from typing import Dict, List, Tuple

from . import joyai_flops, trinity_flops


def _blocks(c: dict) -> Tuple[int, int]:
    """(dense blocks, expert blocks); no multi-token-prediction module."""
    assert c["num_nextn_predict_layers"] == 0
    n_dense = c["first_k_dense_replace"]
    return n_dense, c["num_hidden_layers"] - n_dense


def _maps(c: dict) -> int:
    n = c["hc_mult"]
    return 2 * n + n * n


def forward_flops_by_part(c: dict, seq: int) -> Dict[str, float]:
    """Forward FLOPs of one sequence of ``seq`` tokens by part of the model,
    from the configuration file's keys (``n_routed_experts`` = the experts
    held, ``assumed.router_outputs`` = the experts routed over).  2 per
    multiply-add.  The latent attention, FFN, router and head parts are
    ``joyai_flops``'s (the same sublayers); ``hyper_connections``: two a
    block, each the ``[n d, 2 n + n^2]`` projection, the read mix (``n``
    multiply-adds a lane) and the write mix (``n^2 + n`` a lane)."""
    parts = joyai_flops.forward_flops_by_part(c, seq)
    assert parts.pop("mtp_eh_proj") == 0.0
    d, n = c["hidden_size"], c["hc_mult"]
    parts["hyper_connections"] = 2 * sum(_blocks(c)) * seq * (
        2.0 * n * d * _maps(c) + 2.0 * n * d + 2.0 * (n * n + n) * d)
    return parts


def train_flops_per_sample(c: dict, seq: int) -> float:
    """Forward + backward = 3 x forward, per sequence of ``seq`` tokens."""
    return 3.0 * sum(forward_flops_by_part(c, seq).values())


def parameters(c: dict) -> Dict[str, int]:
    """Parameters by part, from the shapes the program holds (the selection
    bias, which no gradient trains, with its router)."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    dn, dr, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                  c["v_head_dim"])
    rq, rkv = c["q_lora_rank"], c["kv_lora_rank"]
    f = c["moe_intermediate_size"]
    routed_over = c["assumed"]["router_outputs"]
    n_dense, n_moe = _blocks(c)
    blocks = n_dense + n_moe
    return {
        "latent_attention": blocks * (
            d * (rq + rkv + dr) + rq * h * (dn + dr) + rkv * h * (dn + dv)
            + h * dv * d + rq + rkv),
        "norms": blocks * 2 * d + d,
        "hyper_connections": blocks * 2 * (
            c["hc_mult"] * d * _maps(c) + _maps(c) + 3),
        "dense_ffn": n_dense * 3 * d * c["intermediate_size"],
        "shared_expert": n_moe * 3 * d * f * c["n_shared_experts"],
        "router": n_moe * (d * routed_over + routed_over),
        "experts": n_moe * c["n_routed_experts"] * 3 * d * f,
        "embedding_and_head": 2 * c["vocab_size"] * d,
    }


def flash_work(c: dict, traffic: dict) -> List[Tuple[float, float]]:
    """(FLOPs, least bytes) of every flash attention kernel call of one
    sequence's training step: each block's forward and backward on the
    causal half at the two widths
    (``joyai_flops.latent_flash_layer_kernels``)."""
    return [kernel for layer in joyai_flops.flash_kernels_of_model(
        c, traffic["seq_len"]) for kernel in layer]


def held_experts_work(c: dict, traffic: dict, rows_share: float = None
                      ) -> List[Tuple[float, float]]:
    """(FLOPs, least bytes) of every grouped matmul of the held experts in
    one sequence's training step, all expert layers: nine a layer over the
    rows routed here, ``rows_share`` of the ``T * k`` slots a layer (default
    even routing's, held over routed-over)."""
    if rows_share is None:
        rows_share = c["n_routed_experts"] / float(
            c["assumed"]["router_outputs"])
    rows = traffic["seq_len"] * c["num_experts_per_tok"] * rows_share
    return trinity_flops.held_experts_matmuls(
        rows, c["hidden_size"], c["moe_intermediate_size"],
        c["n_routed_experts"]) * _blocks(c)[1]


def hc_work(c: dict, traffic: dict, act_bytes: int = 2
            ) -> List[Tuple[float, float]]:
    """(FLOPs, least bytes) of every hyper-connection op call of one
    sequence's training step: for each of a block's two sublayers ``hc_pre``
    forward (one read of the ``[T, n d]`` stream, one ``[T, d]`` write, Phi
    and the maps), ``hc_post`` forward (one read of the stream, one ``[T,
    d]`` read, one write of the stream, the maps) and the two backward ops at
    twice their forward's operations and bytes (the stream's and ``y``'s
    gradients in and out beside the forward's inputs read again).  The maps
    are float32 ``[T, n + n^2]``; Phi float32.  What the backward computes
    again of the forward and what recomputation runs again count nothing."""
    seq, d, n = traffic["seq_len"], c["hidden_size"], c["hc_mult"]
    stream, one = seq * n * d * act_bytes, seq * d * act_bytes
    maps, phi = seq * (n + n * n) * 4, n * d * _maps(c) * 4
    pre = (seq * (2.0 * n * d * _maps(c) + 2.0 * n * d),
           float(stream + one + phi + maps))
    post = (seq * 2.0 * (n * n + n) * d, float(2 * stream + one + maps))
    twice = [(2 * fl, 2 * by) for fl, by in (pre, post)]
    return ([pre, post] + twice) * (2 * sum(_blocks(c)))
