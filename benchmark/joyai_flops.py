"""Operations and bytes of the JoyAI-LLM-Flash cell, computed from shapes (a
file of its own beside ``flops.py``, ``olmoe_flops.py`` and
``trinity_flops.py``: the benchmark's existing files are not edited).  Needed
work only: attention counts the causal half at the two widths (scores over
``qk_nope_head_dim + qk_rope_head_dim``, values over ``v_head_dim``); an
expert layer counts the rows routed to the experts held here (expected ``T *
k * held / E`` under even routing) and no other; the multi-token-prediction
module is one more expert-layer block, one ``[2 d, d]`` projection and a
second pass of the head; recomputation counts nothing.  Pure functions of
sizes."""

from __future__ import annotations

from typing import Dict, List, Tuple

from .trinity_flops import live_pairs


def _blocks(c: dict) -> Tuple[int, int]:
    """(dense blocks, expert blocks), the MTP modules' among the latter."""
    n_dense = c["first_k_dense_replace"]
    return n_dense, (c["num_hidden_layers"] - n_dense
                     + c["num_nextn_predict_layers"])


def forward_flops_by_part(c: dict, seq: int) -> Dict[str, float]:
    """Forward FLOPs of one sequence of ``seq`` tokens by part of the model,
    from a configuration file's keys (``n_routed_experts`` = the experts
    held, ``assumed.router_outputs`` = the experts routed over).  2 per
    multiply-add."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    dn, dr, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                  c["v_head_dim"])
    rq, rkv = c["q_lora_rank"], c["kv_lora_rank"]
    f_dense, f = c["intermediate_size"], c["moe_intermediate_size"]
    routed_over = c["assumed"]["router_outputs"]
    k, held = c["num_experts_per_tok"], c["n_routed_experts"]
    n_dense, n_moe = _blocks(c)
    n_mtp = c["num_nextn_predict_layers"]
    proj = 2.0 * seq * (d * (rq + rkv + dr) + rq * h * (dn + dr)
                        + rkv * h * (dn + dv) + h * dv * d)
    rows = seq * k * held / float(routed_over)
    return {
        "attention_projections": proj * (n_dense + n_moe),
        "attention_scores": 2.0 * (dn + dr + dv) * h * live_pairs(seq)
        * (n_dense + n_moe),
        "dense_ffn": 6.0 * seq * d * f_dense * n_dense,
        "shared_expert": 6.0 * seq * d * f * c["n_shared_experts"] * n_moe,
        "routed_experts": 6.0 * rows * d * f * n_moe,
        "router": 2.0 * seq * d * routed_over * n_moe,
        "mtp_eh_proj": 2.0 * seq * 2 * d * d * n_mtp,
        "head": 2.0 * seq * d * c["vocab_size"] * (1 + n_mtp),
    }


def train_flops_per_sample(c: dict, seq: int) -> float:
    """Forward + backward = 3 x forward, per sequence of ``seq`` tokens."""
    return 3.0 * sum(forward_flops_by_part(c, seq).values())


def latent_flash_layer_kernels(heads: int, seq: int, d_nope: int,
                               d_rope: int, d_v: int, act_bytes: int = 2
                               ) -> List[Tuple[float, float]]:
    """(FLOPs, least bytes) of one block's flash attention forward and of
    its backward over one sequence, on the causal half: forward ``Q K^T`` at
    ``d_nope + d_rope`` and ``P V`` at ``d_v`` (``2 (d_qk + d_v)`` a pair
    and head), backward dV and dP at ``d_v``, dQ and dK at ``d_qk`` (``2 (2
    d_qk + 2 d_v)``; the backward's second ``Q K^T`` is recomputation and
    counts nothing).  Least bytes: Q at ``d_qk``; K's content part at every
    head and its rotary part at ONE (what the model makes: the broadcast
    copy the program builds is time, not work); V, O and dO at ``d_v``; the
    float32 log-sum-exp rows; backward writes dQ, dK (content part at every
    head, rotary part at one) and dV."""
    pairs = live_pairs(seq)
    d_qk = d_nope + d_rope
    q = heads * seq * d_qk * act_bytes
    k = (heads * d_nope + d_rope) * seq * act_bytes
    v = heads * seq * d_v * act_bytes
    lse = heads * seq * 4
    fwd = (2.0 * (d_qk + d_v) * heads * pairs, float(q + k + 2 * v + lse))
    bwd = (2.0 * (2 * d_qk + 2 * d_v) * heads * pairs,
           float(2 * q + 2 * k + 4 * v + lse))
    return [fwd, bwd]


def flash_kernels_of_model(c: dict, seq: int
                           ) -> List[List[Tuple[float, float]]]:
    """:func:`latent_flash_layer_kernels` of every block of a configuration,
    the MTP modules' among them."""
    return [latent_flash_layer_kernels(
        c["num_attention_heads"], seq, c["qk_nope_head_dim"],
        c["qk_rope_head_dim"], c["v_head_dim"])] * sum(_blocks(c))
