"""Operations and bytes of the Trinity-Mini cell, computed from shapes (a
file of its own beside ``flops.py`` and ``olmoe_flops.py``: the benchmark's
existing files are not edited).  Needed work only: a sliding layer counts the
band ``0 <= i - j < window`` and a full layer the causal half; an expert
layer counts the rows routed to the experts held here (expected ``T * k *
held / E`` under even routing, or the rows a run really counted) and no
other; recomputation counts nothing.  Pure functions of sizes."""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple


def live_pairs(seq: int, window: int = 0) -> float:
    """(query, key) pairs a causal layer attends: ``sum_i min(i + 1, W)``
    with a window ``W``, ``T (T + 1) / 2`` without."""
    if not window or window >= seq:
        return seq * (seq + 1) / 2.0
    return window * (window + 1) / 2.0 + float(seq - window) * window


def forward_flops_by_part(c: dict, seq: int) -> Dict[str, float]:
    """Forward FLOPs of one sequence of ``seq`` tokens by part of the model,
    from a configuration file's keys (``num_experts`` = the experts held,
    ``assumed.router_outputs`` = the experts routed over).  2 per
    multiply-add."""
    d, dh = c["hidden_size"], c["head_dim"]
    h, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    f_dense, f = c["intermediate_size"], c["moe_intermediate_size"]
    routed_over = c["assumed"]["router_outputs"]
    k, held = c["num_experts_per_tok"], c["num_experts"]
    n_dense = c["num_dense_layers"]
    n_moe = c["num_hidden_layers"] - n_dense
    proj = 2.0 * seq * d * (2 * h * dh + 2 * hkv * dh) \
        + 2.0 * seq * h * dh * d              # Q, gate, K, V; output
    scores = sum(4.0 * dh * h * live_pairs(
        seq, c["sliding_window"] if kind == "sliding_attention" else 0)
        for kind in c["layer_types"])
    rows = seq * k * held / float(routed_over)
    return {
        "attention_projections": proj * c["num_hidden_layers"],
        "attention_scores": scores,
        "dense_ffn": 6.0 * seq * d * f_dense * n_dense,
        "shared_expert": 6.0 * seq * d * f * c["num_shared_experts"] * n_moe,
        "routed_experts": 6.0 * rows * d * f * n_moe,
        "router": 2.0 * seq * d * routed_over * n_moe,
        "head": 2.0 * seq * d * c["vocab_size"],
    }


def train_flops_per_sample(c: dict, seq: int) -> float:
    """Forward + backward = 3 x forward, per sequence of ``seq`` tokens."""
    return 3.0 * sum(forward_flops_by_part(c, seq).values())


def flash_layer_kernels(heads: int, kv_heads: int, seq: int, head_dim: int,
                        window: int = 0, act_bytes: int = 2
                        ) -> List[Tuple[float, float]]:
    """(FLOPs, least bytes) of one layer's flash attention forward and of its
    backward over one sequence: matmul FLOPs on the live pairs only, forward
    QK^T and PV (``4 dh`` a pair and head), backward dV, dP, dQ, dK (``8
    dh``; the backward's second QK^T is recomputation and counts nothing).
    Least bytes: forward reads Q and the ``kv_heads`` K and V once and
    writes O and the float32 log-sum-exp rows; backward reads Q, K, V, O, dO
    and those rows and writes dQ, dK, dV."""
    pairs = live_pairs(seq, window)
    q = heads * seq * head_dim * act_bytes
    kv = kv_heads * seq * head_dim * act_bytes
    lse = heads * seq * 4
    fwd = (4.0 * head_dim * heads * pairs, float(2 * q + 2 * kv + lse))
    bwd = (8.0 * head_dim * heads * pairs, float(4 * q + 4 * kv + lse))
    return [fwd, bwd]


def flash_kernels_of_model(c: dict, seq: int
                           ) -> Sequence[List[Tuple[float, float]]]:
    """:func:`flash_layer_kernels` of every layer of a configuration."""
    return [flash_layer_kernels(
        c["num_attention_heads"], c["num_key_value_heads"], seq,
        c["head_dim"],
        c["sliding_window"] if kind == "sliding_attention" else 0)
        for kind in c["layer_types"]]


def held_experts_matmuls(rows: float, hidden: int, expert_width: int,
                         held: int, act_bytes: int = 2
                         ) -> List[Tuple[float, float]]:
    """(FLOPs, least bytes) of each of the nine grouped matmuls of one
    layer's held experts in a training step, over the ``rows`` routed to
    them: forward gate, up, down; backward d-rows of each; backward
    d-weights of each.  Every one is ``2 * rows * d * f`` FLOPs.  Least
    bytes: the row operand and the row result once each over those rows
    (not over the static buffer behind them), the ``held`` experts' weights
    once (bf16 as an operand, float32 as a gradient)."""
    d, f = hidden, expert_width
    flops = 2.0 * rows * d * f
    w16, w32 = held * d * f * act_bytes, held * d * f * 4
    wide, thin = rows * d * act_bytes, rows * f * act_bytes
    return [(flops, float(wide + w16 + thin))] * 6 + \
        [(flops, float(wide + thin + w32))] * 3
