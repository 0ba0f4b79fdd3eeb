"""From a configuration file to the program's model, its weights made on
the device from the seed, and the same weights as the plain reference
(``chipbench/reference/decoder_ref.py``) reads them.

A configuration file holds the model's published ``config.json`` keys at
its top level (``hidden_size``, ``num_hidden_layers``, ...), as they are
run; the keys below are handed to the program's config class of the file's
``family`` under the same names.
"""

from __future__ import annotations

import importlib
from typing import Any, Dict

import numpy as np

FAMILIES = {
    "llama": ("deepspeed_tpu.models.llama", "LlamaConfig", "LlamaForCausalLM"),
    "mixtral": ("deepspeed_tpu.models.mixtral", "MixtralConfig",
                "MixtralForCausalLM"),
}
MODEL_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
              "num_hidden_layers", "num_attention_heads",
              "num_key_value_heads", "max_position_embeddings", "rope_theta",
              "rms_norm_eps", "sliding_window", "num_local_experts",
              "num_experts_per_tok")


def build_model(cfg: Dict[str, Any], dtype, **extra):
    """The program's flax module for configuration file ``cfg``."""
    if cfg.get("hidden_act", "silu") != "silu" or cfg.get(
            "tie_word_embeddings", False):
        raise ValueError("the benchmark's reference covers SwiGLU decoders "
                         "with an untied head only")
    module, config_cls, model_cls = FAMILIES[cfg["family"]]
    mod = importlib.import_module(module)
    keys = {k: cfg[k] for k in MODEL_KEYS if k in cfg}
    return getattr(mod, model_cls)(getattr(mod, config_cls)(
        **keys, dtype=dtype, **extra))


def jax_key(seed: int):
    """A PRNG key from any whole number (``--seed`` may pass 2**31)."""
    import jax
    word = np.random.SeedSequence(
        [int(seed) & 0xFFFFFFFF, int(seed) >> 32]).generate_state(1)[0]
    return jax.random.PRNGKey(int(word) & 0x7FFFFFFF)


def init_params(model, seed: int, dtype):
    """Random weights from the seed, made on the device in one jitted call,
    in the type they are served in."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.utils.tree import tree_cast
    probe = jnp.zeros((1, 8), jnp.int32)
    return jax.jit(lambda k: tree_cast(model.init(k, probe)["params"],
                                       dtype))(jax_key(seed))


def reference_hp(cfg: Dict[str, Any]) -> Dict[str, Any]:
    return {"num_heads": cfg["num_attention_heads"],
            "num_kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg.get("head_dim") or
            cfg["hidden_size"] // cfg["num_attention_heads"],
            "rope_theta": float(cfg["rope_theta"]),
            "eps": float(cfg["rms_norm_eps"]),
            "window": cfg.get("sliding_window"),
            "top_k": cfg.get("num_experts_per_tok")}


def reference_weights(params: Dict[str, Any], cfg: Dict[str, Any]
                      ) -> Dict[str, Any]:
    """The zoo's parameter tree under the reference's names (no copy)."""
    layers = []
    for i in range(cfg["num_hidden_layers"]):
        lp = params[f"layers_{i}"]
        attn = lp["self_attn"]
        layer = {"ln1": lp["input_layernorm"]["weight"],
                 "ln2": lp["post_attention_layernorm"]["weight"],
                 "wq": attn["q_proj"]["kernel"], "wk": attn["k_proj"]["kernel"],
                 "wv": attn["v_proj"]["kernel"], "wo": attn["o_proj"]["kernel"]}
        if "block_sparse_moe" in lp:
            moe = lp["block_sparse_moe"]
            layer.update(router=moe["gate"]["kernel"], w_gate=moe["w_gate"],
                         w_up=moe["w_up"], w_down=moe["w_down"])
        else:
            mlp = lp["mlp"]
            layer.update(w_gate=mlp["gate_proj"]["kernel"],
                         w_up=mlp["up_proj"]["kernel"],
                         w_down=mlp["down_proj"]["kernel"])
        layers.append(layer)
    return {"embed": params["embed_tokens"]["embedding"], "layers": layers,
            "final_norm": params["norm"]["weight"],
            "lm_head": params["lm_head"]["kernel"]}
