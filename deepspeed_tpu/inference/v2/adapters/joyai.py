"""JoyAI-LLM-Flash's weights, and GLM-5's over them (latent attention; with
a learned selection), as the ragged programs take them."""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax.numpy as jnp

from deepspeed_tpu.inference.v2.adapters._stacks import _stack_units
from deepspeed_tpu.inference.v2.model_spec import (LayerKind, RaggedModelSpec,
                                                   layer_runs)
from deepspeed_tpu.utils.logging import log_dist


def adapt_joyai(params: Dict, config, max_context: Optional[int] = None,
                family: str = "joyai", index: Optional[Dict[str, int]] = None
                ) -> Tuple[RaggedModelSpec, Dict]:
    """models/joyai.py param tree (JoyaiForCausalLM; JoyAI-LLM-Flash).

    Latent attention (``spec.mla``): ``kv_b_proj`` is stored split by what
    it makes and head-major, ``w_uk`` ``[H, R, nope]`` (keys) and ``w_uv``
    ``[H, R, v]`` (values): the layout the decode step's per-head products
    read in place (``[R, H, .]`` was copied transposed in every layer). Each
    is used by the expanded form (latent -> keys/values) and by the absorbed
    form (queries -> latent space, latent output -> values) alike. One leading run of dense layers, then MoE layers whose stacks
    hold ``config.held`` of the router's ``n_routed_experts``. The
    multi-token-prediction module (``layers_<num_hidden_layers>`` and on in
    a converted checkpoint) feeds no logit and is not loaded."""
    del max_context
    H = config.num_attention_heads
    R, dn, dr, dv = (config.kv_lora_rank, config.qk_nope_head_dim,
                     config.qk_rope_head_dim, config.v_head_dim)
    skipped = sorted(k for k in params if k.startswith("layers_")
                     and int(k[len("layers_"):]) >= config.num_hidden_layers)
    if skipped:
        log_dist(f"adapt_{family}: {skipped} (the multi-token-prediction "
                 "module) not loaded", ranks=[0])
    kinds = tuple(LayerKind(None, True, config.is_moe_layer(i))
                  for i in range(config.num_hidden_layers))
    first, count = config.held
    moe = {"num_experts": config.n_routed_experts,
           "top_k": config.num_experts_per_tok, "score_func": "sigmoid",
           "route_norm": config.norm_topk_prob,
           "route_scale": config.routed_scaling_factor}
    if count != config.n_routed_experts:
        moe["held"] = (first, count)
    mla = {"q_lora_rank": config.q_lora_rank, "kv_lora_rank": R,
           "qk_nope_head_dim": dn, "qk_rope_head_dim": dr, "v_head_dim": dv}
    if index is not None:
        mla["index"] = index
    spec = RaggedModelSpec(
        family=family,
        num_layers=config.num_hidden_layers,
        hidden_size=config.hidden_size,
        num_heads=H, num_kv_heads=H, head_dim=dv,
        vocab_size=config.vocab_size,
        norm="rms", activation="swiglu", rope_theta=config.rope_theta,
        eps=config.rms_norm_eps, moe=moe, layer_kinds=kinds, mla=mla,
        dtype=config.dtype)
    if len(set(kinds)) == 1:    # one kind after all: the scalar fields say it
        spec = layer_runs(spec)[0][0]

    def swiglu(p):
        return {"w_gate": p["gate_proj"]["kernel"],
                "w_up": p["up_proj"]["kernel"],
                "w_down": p["down_proj"]["kernel"]}

    def layer(i):
        lp = params[f"layers_{i}"]
        attn = lp["self_attn"]
        kvb = jnp.transpose(
            attn["kv_b_proj"]["kernel"].reshape(R, H, dn + dv), (1, 0, 2))
        out = {
            "ln1": {"scale": lp["input_layernorm"]["weight"]},
            "ln2": {"scale": lp["post_attention_layernorm"]["weight"]},
            "wqa": attn["q_a_proj"]["kernel"],
            "q_a_norm": attn["q_a_layernorm"]["weight"],
            "wqb": attn["q_b_proj"]["kernel"],
            "wkva": attn["kv_a_proj_with_mqa"]["kernel"],
            "kv_a_norm": attn["kv_a_layernorm"]["weight"],
            "w_uk": kvb[..., :dn], "w_uv": kvb[..., dn:],
            "wo": attn["o_proj"]["kernel"],
        }
        if index is not None:
            ix = attn["indexer"]
            out["index"] = {"wq": ix["wq_b"]["kernel"],
                            "wk": ix["wk"]["kernel"],
                            "k_norm": ix["k_norm"]["scale"],
                            "k_bias": ix["k_norm"]["bias"],
                            "ww": ix["weights_proj"]["kernel"]}
        mlp = lp["mlp"]
        if config.is_moe_layer(i):
            out["moe"] = {"router": mlp["gate"]["kernel"],
                          "expert_bias": mlp["e_score_correction_bias"],
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


def adapt_glm_dsa(params: Dict, config, max_context: Optional[int] = None
                  ) -> Tuple[RaggedModelSpec, Dict]:
    """models/glm_dsa.py param tree (GlmDsaForCausalLM; GLM-5,
    ``glm_moe_dsa``): :func:`adapt_joyai`'s latent attention, router and held
    experts, and in every layer an indexer — ``spec.mla["index"]``: ``heads``
    of ``head_dim`` whose first ``rope_dim`` values are rotated, keeping the
    ``topk`` best cached tokens a query; ``eps`` of the index key's LayerNorm
    — whose weights ride in the layer as ``w["index"]``: ``wq`` (from the
    normed query latent), ``wk``, ``k_norm``/``k_bias`` and ``ww`` (from the
    layer's normed input). The pool gains one index key a token a layer
    (``ragged/kv_cache.py``) and the programs are ragged_mla.py's with a
    selection (``ops/pallas/sparse_mla.py``)."""
    return adapt_joyai(params, config, max_context, family="glm_dsa", index={
        "heads": config.index_n_heads, "head_dim": config.index_head_dim,
        "topk": config.index_topk, "rope_dim": config.qk_rope_head_dim,
        "eps": config.index_norm_eps})
