"""Operations and bytes of the Solar-Open2-250B cell, computed from shapes on
``trinity_flops``'s pure functions (a file of its own: the benchmark's
existing files are not edited).  Needed work only, of the heads and experts
HELD here: a softmax layer counts the causal half at the held query heads; a
KDA layer its projections, its three convolutions and, apart, the chunked
scan; an expert layer the rows routed to the held experts (expected ``T * k *
held / E`` under even routing) beside the whole shared expert and router; the
head counts the slice of the vocabulary held here; recomputation counts
nothing.

``flash_work``, ``held_experts_work``, ``short_conv_work`` and ``kda_work``
are the hooks that the readers ``layer_metrics/flash_roofline.py``,
``held_experts_roofline.py``, ``short_conv_roofline.py`` and
``kda_scan_roofline.py`` look up in the module the configuration file names
under ``flops_module``."""

from __future__ import annotations

from typing import Dict, List, Tuple

from . import trinity_flops

live_pairs = trinity_flops.live_pairs


def _sizes(c: dict):
    lin = c["linear_attn_config"]
    n_gqa = len(c["gqa_layers"])
    return (c["hidden_size"], c["head_dim"], c["num_attention_heads"],
            c["num_key_value_heads"], lin["num_heads"], lin["head_dim"],
            n_gqa, c["num_hidden_layers"] - n_gqa)


def scan_flops_per_chunk(chunk: int, dk: int, dv: int) -> float:
    """Forward FLOPs of one head's chunk of ``chunk`` positions in the
    chunked (WY) form, 2 a multiply-add, the triangles at their halves: ``A``
    and ``P`` (``C^2 d_k`` each), the unit-triangular solve against ``[V | K
    e^Gam]`` (``C^2 (d_k + d_v)``), ``U = W_v - W_k S`` , ``(Q e^Gam) S`` and
    the state's update (``2 C d_k d_v`` each) and ``P U`` (``C^2 d_v``)."""
    c = float(chunk)
    return 2 * c * c * dk + c * c * (dk + dv) + 6 * c * dk * dv + c * c * dv


def kda_work(c: dict, seq: int, chunk: int = 64, act_bytes: int = 2
             ) -> List[Tuple[float, float]]:
    """(FLOPs, least bytes) of every ``kda_scan`` op call of one sequence's
    training step, a forward and a backward a KDA layer, at the heads held.
    FLOPs: :func:`scan_flops_per_chunk` over the ``seq / chunk`` chunks and
    the heads, twice that backward.  Least bytes, from the op's streams and
    the chunk states: forward reads Q, K, V (``act_bytes``), the float32
    log-decay and beta, writes Out and one float32 ``d_k x d_v`` state a
    chunk and head; backward reads those six, the states and dOut and
    writes the five gradients.  What the backward computes again of the
    forward (here: all of it) and what recomputation runs again count
    nothing; the projections, the convolutions and the gates are other
    ops."""
    _, _, _, _, h, dk, _, n_kda = _sizes(c)
    dv = dk
    n = -(-seq // chunk)
    fwd_flops = n * h * scan_flops_per_chunk(chunk, dk, dv)
    qkv = seq * h * (2 * dk + dv) * act_bytes
    out = seq * h * dv * act_bytes
    gates = seq * h * (dk + 1) * 4
    states = n * h * dk * dv * 4
    fwd = (fwd_flops, float(qkv + gates + out + states))
    bwd = (2.0 * fwd_flops, float(2 * (qkv + gates + out) + states))
    return [fwd, bwd] * n_kda


def short_conv_work(c: dict, traffic: dict, act_bytes: int = 2
                    ) -> List[Tuple[float, float]]:
    """(FLOPs, least bytes) of every ``short_conv`` op call of one
    sequence's training step: a KDA layer's one ungated convolution over the
    Q, K and V channels of the heads held, ``silu(conv(x))``.  Forward: the
    taps and SiLU; reads one [T, 3 h d_k] stream and the filter, writes one.
    Backward: SiLU's slope, the taps run towards the past and once more for
    the filter's gradient; reads the input and dOut, writes dX and the
    float32 filter gradient.  What the backward computes again of the
    forward (the convolution) and what recomputation runs again count
    nothing."""
    _, _, _, _, h, dk, _, n_kda = _sizes(c)
    seq = traffic["seq_len"]
    channels = 3 * h * dk
    taps = c["linear_attn_config"]["short_conv_kernel_size"]
    stream = seq * channels * act_bytes
    filt = channels * taps * 4
    fwd = (seq * channels * (2.0 * taps + 4.0), float(2 * stream + filt))
    bwd = (seq * channels * (4.0 * taps + 6.0), float(3 * stream + 2 * filt))
    return [fwd, bwd] * n_kda


def forward_flops_by_part(c: dict, seq: int) -> Dict[str, float]:
    """Forward FLOPs of one sequence of ``seq`` tokens by part of the model,
    from the configuration file's keys (the heads and ``n_routed_experts``
    are those HELD; ``assumed.router_outputs`` the experts routed over)."""
    d, dh, h, hkv, hk, dk, n_gqa, n_kda = _sizes(c)
    lin = c["linear_attn_config"]
    r, taps = c["assumed"]["kda_gate_rank"], lin["short_conv_kernel_size"]
    f = c["moe_intermediate_size"]
    routed_over = c["assumed"]["router_outputs"]
    k, held = c["num_experts_per_tok"], c["n_routed_experts"]
    layers = n_gqa + n_kda
    rows = seq * k * held / float(routed_over)
    dq = hk * dk
    return {
        "kda_projections": (2.0 * seq * d * (3 * dq + 2 * r + hk)
                            + 2 * 2.0 * seq * r * dq
                            + 2.0 * seq * dq * d) * n_kda,
        "kda_conv": seq * 3 * dq * 2.0 * taps * n_kda,
        "kda_scan": sum(fl for fl, _ in kda_work(c, seq)[::2]),
        "attention_projections": (2.0 * seq * d * (2 * h * dh + 2 * hkv * dh)
                                  + 2.0 * seq * h * dh * d) * n_gqa,
        "attention_scores": 4.0 * dh * h * live_pairs(seq) * n_gqa,
        "shared_expert": 6.0 * seq * d * f * c["n_shared_experts"] * layers,
        "routed_experts": 6.0 * rows * d * f * layers,
        "router": 2.0 * seq * d * routed_over * layers,
        "head": 2.0 * seq * d * c["vocab_size"],
    }


def train_flops_per_sample(c: dict, seq: int) -> float:
    """Forward + backward = 3 x forward, per sequence of ``seq`` tokens."""
    return 3.0 * sum(forward_flops_by_part(c, seq).values())


def parameters(c: dict) -> Dict[str, int]:
    """Parameters by part, from the shapes the program holds (the selection
    bias, which no gradient trains, with its router)."""
    d, dh, h, hkv, hk, dk, n_gqa, n_kda = _sizes(c)
    lin = c["linear_attn_config"]
    r, taps = c["assumed"]["kda_gate_rank"], lin["short_conv_kernel_size"]
    f = c["moe_intermediate_size"]
    routed_over = c["assumed"]["router_outputs"]
    layers = n_gqa + n_kda
    dq = hk * dk
    return {
        "kda": n_kda * (d * (3 * dq + 2 * r + hk) + 3 * dq * taps
                        + 2 * r * dq + hk + dq + dk + dq * d),
        "attention": n_gqa * (d * (2 * h * dh + 2 * hkv * dh) + h * dh * d),
        "norms": layers * 2 * d + d,
        "shared_expert": layers * 3 * d * f * c["n_shared_experts"],
        "router": layers * (d * routed_over + routed_over),
        "experts": layers * c["n_routed_experts"] * 3 * d * f,
        "embedding_and_head": 2 * c["vocab_size"] * d,
    }


def flash_work(c: dict, traffic: dict) -> List[Tuple[float, float]]:
    """(FLOPs, least bytes) of every flash attention kernel call of one
    sequence's training step: each softmax layer's forward and backward over
    the whole causal half at the heads held
    (``trinity_flops.flash_layer_kernels``)."""
    return [kernel for _ in c["gqa_layers"]
            for kernel in trinity_flops.flash_layer_kernels(
                c["num_attention_heads"], c["num_key_value_heads"],
                traffic["seq_len"], c["head_dim"])]


def held_experts_work(c: dict, traffic: dict, rows_share: float = None
                      ) -> List[Tuple[float, float]]:
    """(FLOPs, least bytes) of every grouped matmul of the held experts in
    one sequence's training step, all layers: nine a layer over the rows
    routed here, ``rows_share`` of the ``T * k`` slots a layer (default even
    routing's, held over routed-over)."""
    if rows_share is None:
        rows_share = c["n_routed_experts"] / float(
            c["assumed"]["router_outputs"])
    rows = traffic["seq_len"] * c["num_experts_per_tok"] * rows_share
    return trinity_flops.held_experts_matmuls(
        rows, c["hidden_size"], c["moe_intermediate_size"],
        c["n_routed_experts"]) * c["num_hidden_layers"]
