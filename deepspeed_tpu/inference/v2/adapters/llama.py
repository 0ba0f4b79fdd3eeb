"""The llama lineage's weights (llama, mistral, mixtral, qwen2, gemma) as the
ragged programs take them."""

from __future__ import annotations

from typing import Dict, Optional, Tuple

from deepspeed_tpu.inference.v2.adapters._stacks import _stack
from deepspeed_tpu.inference.v2.model_spec import RaggedModelSpec


def adapt_llama(params: Dict, config,
                max_context: Optional[int] = None) -> Tuple[RaggedModelSpec, Dict]:
    """models/llama.py param tree (LlamaForCausalLM / MixtralForCausalLM).

    Parity anchors: reference ``inference/v2/model_implementations/llama_v2`` /
    ``mistral`` / ``mixtral``."""
    moe = None
    if hasattr(config, "num_local_experts"):
        moe = {"num_experts": config.num_local_experts,
               "top_k": config.num_experts_per_tok}
    # Gemma lineage rides the llama adapter: its structural differences are
    # config flags on LlamaConfig (module_inject/containers.py GemmaPolicy)
    mlp_act = getattr(config, "mlp_act", "silu")
    if mlp_act not in ("silu", "gelu"):
        raise ValueError(f"llama-lineage mlp_act '{mlp_act}' has no ragged "
                         "gated-MLP mapping (expected 'silu' or 'gelu')")
    window = getattr(config, "sliding_window", None)
    if window is not None and (max_context is not None
                               and max_context <= window):
        # no position can ever see past the window: full attention is
        # exactly equivalent, so skip the window masks (and their small
        # kernel cost) entirely
        window = None
    spec = RaggedModelSpec(
        family="mixtral" if moe else "llama",
        num_layers=config.num_hidden_layers,
        hidden_size=config.hidden_size,
        num_heads=config.num_attention_heads,
        num_kv_heads=config.num_key_value_heads,
        head_dim=config.head_dim,
        vocab_size=config.vocab_size,
        norm="rms",
        activation="swiglu" if mlp_act == "silu" else "geglu",
        rope_theta=config.rope_theta,
        embed_scale_by_sqrt_dim=getattr(config, "embed_scale_by_sqrt_dim", False),
        norm_plus_one=getattr(config, "norm_plus_one", False),
        eps=config.rms_norm_eps, moe=moe, window=window, dtype=config.dtype)

    layers = []
    for i in range(config.num_hidden_layers):
        lp = params[f"layers_{i}"]
        attn = lp["self_attn"]
        layer = {
            "ln1": {"scale": lp["input_layernorm"]["weight"]},
            "ln2": {"scale": lp["post_attention_layernorm"]["weight"]},
            "wq": attn["q_proj"]["kernel"],
            "wk": attn["k_proj"]["kernel"],
            "wv": attn["v_proj"]["kernel"],
            "wo": attn["o_proj"]["kernel"],
        }
        if "bias" in attn["q_proj"]:   # Qwen2 lineage: biased q/k/v
            layer["bq"] = attn["q_proj"]["bias"]
            layer["bk"] = attn["k_proj"]["bias"]
            layer["bv"] = attn["v_proj"]["bias"]
        if moe:
            mb = lp["block_sparse_moe"]
            layer["moe"] = {
                "router": mb["gate"]["kernel"],
                "w_gate": mb["w_gate"], "w_up": mb["w_up"], "w_down": mb["w_down"],
            }
        else:
            layer["mlp"] = {
                "w_gate": lp["mlp"]["gate_proj"]["kernel"],
                "w_up": lp["mlp"]["up_proj"]["kernel"],
                "w_down": lp["mlp"]["down_proj"]["kernel"],
            }
        layers.append(layer)

    weights = {
        "embed": params["embed_tokens"]["embedding"],
        "layers": _stack(layers),
        "final_norm": {"scale": params["norm"]["weight"]},
        "lm_head": params["lm_head"]["kernel"],
    }
    return spec, weights
