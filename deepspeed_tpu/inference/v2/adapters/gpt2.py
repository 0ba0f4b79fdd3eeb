"""GPT-2's weights as the ragged programs take them."""

from __future__ import annotations

from typing import Dict, Optional, Tuple

from deepspeed_tpu.inference.v2.adapters._stacks import _stack
from deepspeed_tpu.inference.v2.model_spec import RaggedModelSpec


def adapt_gpt2(params: Dict, config,
               max_context: Optional[int] = None) -> Tuple[RaggedModelSpec, Dict]:
    """models/gpt2.py param tree (GPT2LMHead): fused c_attn qkv, tied head."""
    spec = RaggedModelSpec(
        family="gpt2",
        num_layers=config.n_layer,
        hidden_size=config.n_embd,
        num_heads=config.n_head,
        num_kv_heads=config.n_head,
        head_dim=config.n_embd // config.n_head,
        vocab_size=config.vocab_size,
        norm="ln", activation="gelu", rope_theta=None, learned_pos=True,
        tied_lm_head=True, eps=1e-5, dtype=config.dtype)

    E = config.n_embd
    layers = []
    for i in range(config.n_layer):
        lp = params[f"h_{i}"]
        wqkv = lp["attn"]["c_attn"]["kernel"]     # [E, 3E]
        bqkv = lp["attn"]["c_attn"]["bias"]
        layers.append({
            "ln1": {"scale": lp["ln_1"]["scale"], "bias": lp["ln_1"]["bias"]},
            "ln2": {"scale": lp["ln_2"]["scale"], "bias": lp["ln_2"]["bias"]},
            "wq": wqkv[:, :E], "wk": wqkv[:, E:2 * E], "wv": wqkv[:, 2 * E:],
            "bq": bqkv[:E], "bk": bqkv[E:2 * E], "bv": bqkv[2 * E:],
            "wo": lp["attn"]["c_proj"]["kernel"],
            "bo": lp["attn"]["c_proj"]["bias"],
            "mlp": {
                "w_up": lp["mlp"]["c_fc"]["kernel"],
                "b_up": lp["mlp"]["c_fc"]["bias"],
                "w_down": lp["mlp"]["c_proj"]["kernel"],
                "b_down": lp["mlp"]["c_proj"]["bias"],
            },
        })

    weights = {
        "embed": params["wte"]["embedding"],
        "pos_embed": params["wpe"]["embedding"],
        "layers": _stack(layers),
        "final_norm": {"scale": params["ln_f"]["scale"],
                       "bias": params["ln_f"]["bias"]},
    }
    return spec, weights
