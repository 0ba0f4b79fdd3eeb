"""The sdar_moe family (JetLM SDAR) for the benchmark: from a configuration
file to the program's model, and the program's weights under the names of
the plain reference (``chipbench/reference/sdar_ref.py``).

A family module is found by the configuration's ``family`` key
(``chipbench/families/<family>.py``). This one gives the serving bring-up of
``drivers/serve_closed_blocks.py``: ``REFERENCE``, ``build_model``,
``init_params`` (the weights a layer at a time: 1.16 GiB a layer),
``reference_hp``, ``reference_weights``, ``kv_layout`` and ``check_engine``,
which holds the engine to the configuration.

``block_length`` and ``mask_token_id`` are not keys of the published
``config.json``: the file states them under ``assumed`` and at its top level,
where the program's config class reads them.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

#: module under chipbench/reference with forward_logits(weights, ids, hp,
#: rows=, with_margin=, act_dtype=, causal=), forward_many, denoise_choice
REFERENCE = "sdar_ref"

MODEL_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
              "moe_intermediate_size", "num_hidden_layers",
              "num_attention_heads", "num_key_value_heads", "head_dim",
              "max_position_embeddings", "rope_theta", "rms_norm_eps",
              "num_experts", "num_experts_per_tok", "norm_topk_prob",
              "decoder_sparse_step", "mlp_only_layers", "block_length",
              "mask_token_id")


def build_model(cfg: Dict[str, Any], dtype):
    """The program's flax module for configuration file ``cfg``."""
    from deepspeed_tpu.models.sdar import SdarMoeConfig, SdarMoeForCausalLM
    if cfg.get("hidden_act", "silu") != "silu" or cfg.get(
            "tie_word_embeddings", False) or cfg.get("rope_scaling") \
            or cfg.get("attention_bias") or cfg.get("use_sliding_window"):
        raise ValueError("the reference covers SwiGLU experts, an untied "
                         "head, unscaled rotary frequencies, no bias and no "
                         "window only")
    keys = {k: cfg[k] for k in MODEL_KEYS if k in cfg}
    return SdarMoeForCausalLM(SdarMoeConfig(**keys, dtype=dtype))


def init_params(model, seed: int, dtype):
    """Random weights from the seed in the tree ``model.init`` gives, made on
    the device a layer at a time: one small program for a layer (every layer
    is of one kind) and one for the embedding, the final norm and the head,
    as ``families/joyai.py`` does and for its reason. The keys are of jax's
    ``rbg`` generator; a seed still gives the same weights."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    from chipbench import models
    from deepspeed_tpu.models.sdar import SdarBlock, SdarMoeForCausalLM
    from deepspeed_tpu.utils.tree import tree_cast

    cfg = model.config
    key = jax.random.wrap_key_data(
        jnp.tile(jax.random.key_data(models.jax_key(seed)), 2), impl="rbg")
    probe = jnp.zeros((1, 8), jnp.int32)
    x = jnp.zeros((1, 8, cfg.hidden_size), dtype)
    ends = SdarMoeForCausalLM(dataclasses.replace(cfg, num_hidden_layers=0))
    params = dict(jax.jit(lambda k: tree_cast(
        ends.init(k, probe)["params"], dtype))(
            jax.random.fold_in(key, cfg.num_hidden_layers)))
    layer = jax.jit(lambda k: tree_cast(
        SdarBlock(cfg).init(k, x, probe)["params"], dtype))
    for i in range(cfg.num_hidden_layers):
        params[f"layers_{i}"] = layer(jax.random.fold_in(key, i))
    return params


def kv_layout(cfg: Dict[str, Any]) -> Tuple[int, int, int]:
    """(layers, key/value heads, head size) of the paged cache."""
    return (cfg["num_hidden_layers"], cfg["num_key_value_heads"],
            cfg["head_dim"])


def reference_hp(cfg: Dict[str, Any]) -> Dict[str, Any]:
    return {"num_heads": cfg["num_attention_heads"],
            "num_kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"],
            "rope_theta": float(cfg["rope_theta"]),
            "eps": float(cfg["rms_norm_eps"]),
            "top_k": cfg["num_experts_per_tok"],
            "block_length": int(cfg["block_length"]),
            "mask_token_id": int(cfg["mask_token_id"])}


def reference_weights(params: Dict[str, Any], cfg: Dict[str, Any]
                      ) -> Dict[str, Any]:
    """The zoo's parameter tree under the reference's names (no copy)."""
    layers = []
    for i in range(cfg["num_hidden_layers"]):
        lp = params[f"layers_{i}"]
        attn, mlp = lp["self_attn"], lp["mlp"]
        layers.append({
            "ln_in": lp["input_layernorm"]["weight"],
            "ln_ff": lp["post_attention_layernorm"]["weight"],
            "wq": attn["q_proj"]["kernel"], "wk": attn["k_proj"]["kernel"],
            "wv": attn["v_proj"]["kernel"], "wo": attn["o_proj"]["kernel"],
            "q_norm": attn["q_norm"]["weight"],
            "k_norm": attn["k_norm"]["weight"],
            "router": mlp["gate"]["kernel"], "w_gate": mlp["w_gate"],
            "w_up": mlp["w_up"], "w_down": mlp["w_down"]})
    return {"embed": params["embed_tokens"]["embedding"], "layers": layers,
            "final_norm": params["norm"]["weight"],
            "lm_head": params["lm_head"]["kernel"]}


def check_engine(cfg: Dict[str, Any], engine) -> str:
    """What is wrong with the engine's spec and pool against the
    configuration's, or ''."""
    spec = engine.spec
    if spec.layer_kinds is not None or spec.window is not None:
        return "the engine runs layers of several kinds, or a window"
    if spec.causal_block != cfg["block_length"] \
            or spec.mask_token_id != cfg["mask_token_id"]:
        return (f"the engine's block is {spec.causal_block} with mask "
                f"{spec.mask_token_id}; the file says {cfg['block_length']} "
                f"and {cfg['mask_token_id']}")
    moe = spec.moe or {}
    if moe.get("num_experts") != cfg["num_experts"] or moe.get(
            "top_k") != cfg["num_experts_per_tok"] or "held" in moe \
            or "score_func" in moe:
        return f"the engine's router is {moe}"
    layers, heads, dim = kv_layout(cfg)
    kvc = engine.kv.config
    if (kvc.num_layers, kvc.num_kv_heads, kvc.head_dim) != (layers, heads,
                                                            dim):
        return (f"the page pool has {kvc.num_layers} layers of "
                f"{kvc.num_kv_heads} x {kvc.head_dim}; the file's layers are "
                f"{layers} of {heads} x {dim}")
    if engine.packed_prefill:
        return "the packed prefill pass is on (it does not know the block rule)"
    if spec.tied_lm_head or spec.rope_theta != float(cfg["rope_theta"]):
        return "the head is tied or the rotary base is not the file's"
    return ""
