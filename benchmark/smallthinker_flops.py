"""Operations and bytes of the SmallThinker-21BA3B cell, computed from shapes
on ``trinity_flops``'s pure functions (a file of its own: the benchmark's
existing files are not edited).  Needed work only: a window layer counts the
band ``0 <= i - j < window`` and a full layer the causal half; an expert
layer counts the rows routed to the experts held here (expected ``T * k *
held / E`` under even routing, or the rows a run really counted) and no
other; recomputation counts nothing.  The attention projections are Q, K, V
and the output (no gate slice); there is no dense or shared FFN.

``flash_work`` and ``held_experts_work`` are the hooks that the readers
``layer_metrics/flash_roofline.py`` and ``held_experts_roofline.py`` look up
in a configuration's ``<config>_flops`` module or, as here, in the module
the configuration file names under ``flops_module``: the readers themselves
know no configuration's keys."""

from __future__ import annotations

from typing import Dict, List, Tuple

from . import trinity_flops

live_pairs = trinity_flops.live_pairs


def layer_windows(c: dict) -> List[int]:
    """Each layer's window, 0 for a full layer."""
    return [c["sliding_window_size"] if w else 0
            for w in c["sliding_window_layout"]]


def forward_flops_by_part(c: dict, seq: int) -> Dict[str, float]:
    """Forward FLOPs of one sequence of ``seq`` tokens by part of the model,
    from the configuration file's keys (``moe_num_primary_experts`` = the
    experts held, ``assumed.router_outputs`` = the experts routed over).  2
    per multiply-add."""
    d, dh = c["hidden_size"], c["head_dim"]
    h, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    f, layers = c["moe_ffn_hidden_size"], c["num_hidden_layers"]
    routed_over = c["assumed"]["router_outputs"]
    k, held = c["moe_num_active_primary_experts"], c["moe_num_primary_experts"]
    proj = 2.0 * seq * d * (h * dh + 2 * hkv * dh) \
        + 2.0 * seq * h * dh * d                      # Q, K, V; output
    rows = seq * k * held / float(routed_over)
    return {
        "attention_projections": proj * layers,
        "attention_scores": sum(4.0 * dh * h * live_pairs(seq, w)
                                for w in layer_windows(c)),
        "routed_experts": 6.0 * rows * d * f * layers,
        "router": 2.0 * seq * d * routed_over * layers,
        "head": 2.0 * seq * d * c["vocab_size"],
    }


def train_flops_per_sample(c: dict, seq: int) -> float:
    """Forward + backward = 3 x forward, per sequence of ``seq`` tokens."""
    return 3.0 * sum(forward_flops_by_part(c, seq).values())


def parameters(c: dict) -> Dict[str, int]:
    """Parameters by part, from the shapes the program holds."""
    d, dh = c["hidden_size"], c["head_dim"]
    h, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    f, layers = c["moe_ffn_hidden_size"], c["num_hidden_layers"]
    return {
        "attention": layers * (d * (h * dh + 2 * hkv * dh) + h * dh * d),
        "norms": layers * 2 * d + d,
        "router": layers * d * c["assumed"]["router_outputs"],
        "experts": layers * c["moe_num_primary_experts"] * 3 * d * f,
        "embedding": c["vocab_size"] * d,
        "head": d * c["vocab_size"],
    }


def flash_work(c: dict, traffic: dict) -> List[Tuple[float, float]]:
    """(FLOPs, least bytes) of every flash attention kernel call of one
    sequence's training step: each layer's forward and its backward
    (``trinity_flops.flash_layer_kernels``)."""
    return [kernel for w in layer_windows(c)
            for kernel in trinity_flops.flash_layer_kernels(
                c["num_attention_heads"], c["num_key_value_heads"],
                traffic["seq_len"], c["head_dim"], w)]


def held_experts_work(c: dict, traffic: dict, rows_share: float = None
                      ) -> List[Tuple[float, float]]:
    """(FLOPs, least bytes) of every grouped matmul of the held experts in
    one sequence's training step, all layers: nine a layer over the rows
    routed here, ``rows_share`` of the ``T * k`` slots a layer (default even
    routing's, held over routed-over)."""
    if rows_share is None:
        rows_share = c["moe_num_primary_experts"] / float(
            c["assumed"]["router_outputs"])
    rows = traffic["seq_len"] * c["moe_num_active_primary_experts"] \
        * rows_share
    return trinity_flops.held_experts_matmuls(
        rows, c["hidden_size"], c["moe_ffn_hidden_size"],
        c["moe_num_primary_experts"]) * c["num_hidden_layers"]
