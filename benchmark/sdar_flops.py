"""Operations and bytes of the SDAR-30B-A3B cell under block-diffusion
training, computed from shapes (a file of its own: the benchmark's existing
files are not edited).  A sample is one document of ``seq`` tokens; the step
runs its noisy copy beside its clean copy, ``2 * seq`` rows through every
layer, and the head over the noisy half alone.  Needed work only: attention
counts the pairs the three-part mask lets through (``seq^2 + seq * block`` a
head, of the ``4 seq^2``), an expert layer the rows routed to the experts
held here, recomputation nothing.  Pure functions of sizes."""

from __future__ import annotations

from typing import Dict, List, Tuple

from . import trinity_flops


def block_length(c: dict) -> int:
    return int(c["assumed"]["block_length"])


def live_pairs(seq: int, block: int) -> float:
    """(query, key) pairs a head attends under the block-diffusion mask of a
    document of ``seq`` tokens in blocks of ``block``: noisy on noisy ``seq *
    block`` (each block on itself), noisy on clean ``seq (seq - block) / 2``
    (the blocks strictly before), clean on clean ``seq (seq + block) / 2``
    (block-causal): ``seq^2 + seq * block``."""
    return float(seq) * seq + float(seq) * block


def flash_layer_kernels(c: dict, seq: int, act_bytes: int = 2
                        ) -> List[Tuple[float, float]]:
    """(FLOPs, least bytes) of one layer's flash attention forward and of
    its backward over one document: ``trinity_flops.flash_layer_kernels``'s
    count on this mask's live pairs and the ``2 * seq`` rows of the doubled
    stream (forward ``4 dh`` a pair and head, backward ``8 dh``; the
    backward's recomputed scores count nothing; Q, O, dO and dQ at ``2 seq``
    rows of every query head, K, V and their gradients of every K/V head,
    the float32 log-sum-exp rows)."""
    h, hkv, dh = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    pairs = live_pairs(seq, block_length(c))
    rows = 2 * seq
    q = h * rows * dh * act_bytes
    kv = hkv * rows * dh * act_bytes
    lse = h * rows * 4
    return [(4.0 * dh * h * pairs, float(2 * q + 2 * kv + lse)),
            (8.0 * dh * h * pairs, float(4 * q + 4 * kv + lse))]


def flash_work(c: dict, traffic: dict) -> List[Tuple[float, float]]:
    """(FLOPs, least bytes) of every flash attention kernel call of one
    document's training step: every layer's forward and backward
    (``layer_metrics/flash_roofline.py``'s hook)."""
    return flash_layer_kernels(c, traffic["seq_len"]) * \
        c["num_hidden_layers"]


def held_rows(c: dict, seq: int, rows_share: float = None) -> float:
    """Rows of one layer's routed slots that land on the experts held here:
    ``rows_share`` of the ``2 seq * k`` slots (default even routing's, held
    over routed-over)."""
    if rows_share is None:
        rows_share = c["num_experts"] / float(c["assumed"]["router_outputs"])
    return 2.0 * seq * c["num_experts_per_tok"] * rows_share


def held_experts_work(c: dict, traffic: dict, rows_share: float = None
                      ) -> List[Tuple[float, float]]:
    """(FLOPs, least bytes) of every grouped matmul of the held experts in
    one document's training step, all layers: nine a layer over the rows
    routed here (``trinity_flops.held_experts_matmuls``;
    ``layer_metrics/held_experts_roofline.py``'s hook)."""
    return trinity_flops.held_experts_matmuls(
        held_rows(c, traffic["seq_len"], rows_share), c["hidden_size"],
        c["moe_intermediate_size"], c["num_experts"]) * \
        c["num_hidden_layers"]


def forward_flops_by_part(c: dict, seq: int) -> Dict[str, float]:
    """Forward FLOPs of one document of ``seq`` tokens by part of the model,
    from the configuration file's keys (``num_experts`` the experts HELD;
    ``assumed.router_outputs`` the experts routed over).  2 per
    multiply-add."""
    n, d = c["num_hidden_layers"], c["hidden_size"]
    h, hkv, dh = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    rows = 2 * seq
    return {
        "attention_projections": n * (2.0 * rows * d * (h + 2 * hkv) * dh
                                      + 2.0 * rows * h * dh * d),
        "attention_scores": n * 4.0 * dh * h
        * live_pairs(seq, block_length(c)),
        "routed_experts": n * 6.0 * held_rows(c, seq) * d
        * c["moe_intermediate_size"],
        "router": n * 2.0 * rows * d * c["assumed"]["router_outputs"],
        "head": 2.0 * seq * d * c["vocab_size"],
    }


def train_flops_per_sample(c: dict, seq: int) -> float:
    """Forward + backward = 3 x forward, per document of ``seq`` tokens."""
    return 3.0 * sum(forward_flops_by_part(c, seq).values())


def parameters(c: dict) -> Dict[str, int]:
    """Parameters by part, from the shapes the program holds."""
    n, d = c["num_hidden_layers"], c["hidden_size"]
    h, hkv, dh = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    return {
        "attention": n * (d * (h + 2 * hkv) * dh + h * dh * d + 2 * dh),
        "block_norms": n * 2 * d,
        "router": n * d * c["assumed"]["router_outputs"],
        "experts": n * c["num_experts"] * 3 * d * c["moe_intermediate_size"],
        "final_norm": d,
        "embedding_and_head": 2 * c["vocab_size"] * d,
    }
