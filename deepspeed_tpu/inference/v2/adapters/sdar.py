"""sdar_moe's weights (JetLM SDAR) as the ragged programs take them."""

from __future__ import annotations

from typing import Dict, Optional, Tuple

from deepspeed_tpu.inference.v2.adapters._stacks import _stack_leaf_by_leaf
from deepspeed_tpu.inference.v2.model_spec import RaggedModelSpec


def adapt_sdar(params: Dict, config,
               max_context: Optional[int] = None) -> Tuple[RaggedModelSpec, Dict]:
    """models/sdar.py param tree (SdarMoeForCausalLM).

    The spec of a plain GQA MoE decoder — q/k norm by the gains' presence in
    a layer's weights, as afmoe's; the linear router's softmax over all, top-k,
    renormalised is ``moe_route``'s Mixtral branch (a softmax over the chosen
    logits: the same numbers); every expert held — plus what makes the family
    generate by blocks: ``causal_block`` and ``mask_token_id``."""
    spec = RaggedModelSpec(
        family="sdar_moe",
        num_layers=config.num_hidden_layers,
        hidden_size=config.hidden_size,
        num_heads=config.num_attention_heads,
        num_kv_heads=config.num_key_value_heads,
        head_dim=config.head_dim,
        vocab_size=config.vocab_size,
        norm="rms", activation="swiglu", rope_theta=config.rope_theta,
        eps=config.rms_norm_eps,
        moe={"num_experts": config.num_experts,
             "top_k": config.num_experts_per_tok},
        causal_block=config.block_length,
        mask_token_id=config.mask_token_id, dtype=config.dtype)

    def layer(i):
        lp = params[f"layers_{i}"]
        attn, mlp = lp["self_attn"], lp["mlp"]
        return {
            "ln1": {"scale": lp["input_layernorm"]["weight"]},
            "ln2": {"scale": lp["post_attention_layernorm"]["weight"]},
            "wq": attn["q_proj"]["kernel"], "wk": attn["k_proj"]["kernel"],
            "wv": attn["v_proj"]["kernel"], "wo": attn["o_proj"]["kernel"],
            "q_norm": attn["q_norm"]["weight"],
            "k_norm": attn["k_norm"]["weight"],
            "moe": {"router": mlp["gate"]["kernel"], "w_gate": mlp["w_gate"],
                    "w_up": mlp["w_up"], "w_down": mlp["w_down"]},
        }

    weights = {
        "embed": params["embed_tokens"]["embedding"],
        "layers": _stack_leaf_by_leaf(
            [layer(i) for i in range(config.num_hidden_layers)]),
        "final_norm": {"scale": params["norm"]["weight"]},
        "lm_head": params["lm_head"]["kernel"],
    }
    return spec, weights
