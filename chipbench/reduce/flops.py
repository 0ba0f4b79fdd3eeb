"""Operations a decoder needs, computed from its shapes.

Model FLOPs are what the forward and backward passes REQUIRE: recomputation
(activation checkpointing) is not counted, so a utilization built on them
falls when a change adds recompute. XLA's ``cost_analysis`` counts what the
compiled program executes, recompute and fused ops included, and is not
used.
"""

from __future__ import annotations

from typing import Any, Dict, Optional


def matmul_params(model: Dict[str, Any], active_only: bool = True) -> int:
    """Parameters that sit in a matrix multiplication on a token's path:
    attention projections, feed-forward (for a sparse mixture the experts a
    token is routed to, and the router) and the output head. The embedding
    is a lookup, and norm gains are not matmuls."""
    h = model["hidden_size"]
    d = model.get("head_dim") or h // model["num_attention_heads"]
    hq, hkv = model["num_attention_heads"], model["num_key_value_heads"]
    f = model["intermediate_size"]
    attn = h * hq * d + 2 * h * hkv * d + hq * d * h
    ffn = 3 * h * f
    experts = model.get("num_local_experts")
    if experts:
        used = model["num_experts_per_tok"] if active_only else experts
        ffn = used * ffn + h * experts
    return model["num_hidden_layers"] * (attn + ffn) + h * model["vocab_size"]


def attention_flops_per_token(model: Dict[str, Any], seq_len: int,
                              training: bool) -> float:
    """Score and value matmuls of causal attention, averaged over the
    positions of a sequence of ``seq_len``: position ``t`` attends to
    ``min(t + 1, window)`` keys, 2 FLOPs a multiply-add, two matmuls;
    the backward pass costs twice the forward."""
    h = model["hidden_size"]
    d = model.get("head_dim") or h // model["num_attention_heads"]
    hq = model["num_attention_heads"]
    window: Optional[int] = model.get("sliding_window")
    keys = 0
    for t in range(seq_len):
        keys += min(t + 1, window) if window else t + 1
    fwd = 2 * 2 * hq * d * keys / seq_len
    return model["num_hidden_layers"] * fwd * (3 if training else 1)


def train_flops_per_token(model: Dict[str, Any], seq_len: int) -> float:
    """6 FLOPs per matmul parameter per token (2 forward, 4 backward) plus
    attention; no recompute."""
    return 6 * matmul_params(model) + attention_flops_per_token(
        model, seq_len, training=True)


def mfu_percent(tokens_per_s_per_chip: float, flops_per_token: float,
                peak_flops: float) -> float:
    return 100.0 * tokens_per_s_per_chip * flops_per_token / peak_flops
