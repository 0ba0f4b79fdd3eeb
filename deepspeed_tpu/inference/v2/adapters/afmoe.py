"""afmoe's weights (Trinity) as the ragged programs take them."""

from __future__ import annotations

from typing import Dict, Optional, Tuple

from deepspeed_tpu.inference.v2.adapters._stacks import _stack_units
from deepspeed_tpu.inference.v2.model_spec import (LayerKind, RaggedModelSpec,
                                                   layer_runs)


def adapt_afmoe(params: Dict, config,
                max_context: Optional[int] = None) -> Tuple[RaggedModelSpec, Dict]:
    """models/afmoe.py param tree (AfmoeForCausalLM; Arcee Trinity).

    Everything that sets the family apart is read from the config and the
    tree: one :class:`LayerKind` per layer from ``layer_types`` and
    ``num_dense_layers``; q/k norm (``q_norm``/``k_norm``), the output gate
    (``wg``) and the sandwich norms (``ln1_post``/``ln2_post``) by their
    presence in a layer's weights; the router by ``spec.moe``."""
    window = config.sliding_window
    if max_context is not None and max_context <= window:
        window = None           # as adapt_llama: no position sees past it
    kinds = tuple(
        LayerKind(window if t == "sliding_attention" else None,
                  t == "sliding_attention", config.is_moe_layer(i))
        for i, t in enumerate(config.layer_types))
    spec = RaggedModelSpec(
        family="afmoe",
        num_layers=config.num_hidden_layers,
        hidden_size=config.hidden_size,
        num_heads=config.num_attention_heads,
        num_kv_heads=config.num_key_value_heads,
        head_dim=config.head_dim,
        vocab_size=config.vocab_size,
        norm="rms", activation="swiglu", rope_theta=config.rope_theta,
        embed_scale_by_sqrt_dim=config.mup_enabled, eps=config.rms_norm_eps,
        moe={"num_experts": config.num_experts,
             "top_k": config.num_experts_per_tok,
             "score_func": config.score_func,
             "route_norm": config.route_norm,
             "route_scale": config.route_scale},
        layer_kinds=kinds, dtype=config.dtype)
    if len(set(kinds)) == 1:    # one kind after all: the scalar fields say it
        spec = layer_runs(spec)[0][0]

    def swiglu(p):
        return {"w_gate": p["gate_proj"]["kernel"],
                "w_up": p["up_proj"]["kernel"],
                "w_down": p["down_proj"]["kernel"]}

    def layer(i):
        lp = params[f"layers_{i}"]
        attn = lp["self_attn"]
        out = {
            "ln1": {"scale": lp["input_layernorm"]["weight"]},
            "ln1_post": {"scale": lp["post_attention_layernorm"]["weight"]},
            "ln2": {"scale": lp["pre_mlp_layernorm"]["weight"]},
            "ln2_post": {"scale": lp["post_mlp_layernorm"]["weight"]},
            "wq": attn["q_proj"]["kernel"], "wk": attn["k_proj"]["kernel"],
            "wv": attn["v_proj"]["kernel"], "wo": attn["o_proj"]["kernel"],
            "wg": attn["gate_proj"]["kernel"],
            "q_norm": attn["q_norm"]["weight"],
            "k_norm": attn["k_norm"]["weight"],
        }
        mlp = lp["mlp"]
        if config.is_moe_layer(i):
            out["moe"] = {"router": mlp["router"]["kernel"],
                          "expert_bias": mlp["expert_bias"],
                          "w_gate": mlp["w_gate"], "w_up": mlp["w_up"],
                          "w_down": mlp["w_down"]}
            if "shared_experts" in mlp:
                out["moe"]["shared"] = swiglu(mlp["shared_experts"])
        else:
            out["mlp"] = swiglu(mlp)
        return out

    stacks = _stack_units(spec, layer)
    weights = {
        "embed": params["embed_tokens"]["embedding"],
        "layers": stacks if spec.layer_kinds is not None else stacks[0],
        "final_norm": {"scale": params["norm"]["weight"]},
        "lm_head": params["lm_head"]["kernel"],
    }
    return spec, weights
