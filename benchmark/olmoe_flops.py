"""Operations and bytes of the OLMoE cell, computed from shapes (a file of
its own beside ``flops.py``: the benchmark's existing files are not edited).
Needed work only: causal attention counts the half of the score matrix it
needs, each token counts its ``k`` experts and no other, recomputation
counts nothing.  Pure functions of sizes."""

from __future__ import annotations

from typing import List, Tuple


def olmoe_forward_flops_per_token(hidden: int, layers: int, experts: int,
                                  top_k: int, expert_width: int, vocab: int,
                                  seq: int) -> float:
    """2 per multiply-add: Q, K, V and output projections ``8 d^2``; causal
    scores and context ``2 T d`` (half of the full ``4 T d``); the router
    ``2 d E``; ``k`` gated experts of three matmuls, ``6 k d f``; all per
    layer; the head ``2 d V`` once."""
    per_layer = (8.0 * hidden * hidden + 2.0 * seq * hidden
                 + 2.0 * hidden * experts
                 + 6.0 * top_k * hidden * expert_width)
    return layers * per_layer + 2.0 * hidden * vocab


def olmoe_train_flops_per_sample(hidden: int, layers: int, experts: int,
                                 top_k: int, expert_width: int, vocab: int,
                                 seq: int) -> float:
    """Forward + backward = 3 x forward, per sequence of ``seq`` tokens."""
    return 3.0 * seq * olmoe_forward_flops_per_token(
        hidden, layers, experts, top_k, expert_width, vocab, seq)


def moe_experts_matmuls(rows: int, hidden: int, expert_width: int,
                        experts: int, act_bytes: int = 2
                        ) -> List[Tuple[float, float]]:
    """(FLOPs, least bytes) of each of the nine grouped matmuls of one
    layer's experts in a training step, over ``rows`` routed rows in all:
    forward gate, up, down; backward d-rows of each; backward d-weights of
    each.  Every one is ``2 * rows * d * f`` FLOPs.  Least bytes: the row
    operand and the row result once each, the expert weights once (bf16 as
    an operand, float32 as a gradient)."""
    d, f, e = hidden, expert_width, experts
    flops = 2.0 * rows * d * f
    w16, w32 = e * d * f * act_bytes, e * d * f * 4
    wide, thin = rows * d * act_bytes, rows * f * act_bytes
    through_weights = float(wide + w16 + thin)      # rows in, rows out
    to_weights = float(wide + thin + w32)           # two row operands in
    return [(flops, through_weights)] * 6 + [(flops, to_weights)] * 3


def flash_attention_kernels(batch_heads: int, seq: int, head_dim: int,
                            act_bytes: int = 2
                            ) -> List[Tuple[float, float]]:
    """(FLOPs, least bytes) of causal flash attention's forward and of its
    backward over ``batch_heads`` [seq, head_dim] problems.  Needed matmul
    FLOPs on the causal half of the score matrix: forward QK^T and PV,
    ``2 * 2 * (T^2 / 2) * dh``; backward dV, dP, dQ, dK, ``4 * 2 * (T^2 / 2)
    * dh`` (the backward's second QK^T is recomputation and counts nothing).
    Least bytes: forward reads Q, K, V and writes O and the float32
    log-sum-exp rows; backward reads Q, K, V, O, dO and those rows and
    writes dQ, dK, dV."""
    half = 0.5 * seq * seq
    tensor = batch_heads * seq * head_dim * act_bytes
    lse = batch_heads * seq * 4
    fwd = (batch_heads * 2 * 2.0 * half * head_dim, float(4 * tensor + lse))
    bwd = (batch_heads * 4 * 2.0 * half * head_dim, float(8 * tensor + lse))
    return [fwd, bwd]
