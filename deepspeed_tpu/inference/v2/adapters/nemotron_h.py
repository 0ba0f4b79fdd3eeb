"""Nemotron-H's weights as the ragged programs take them."""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax.numpy as jnp

from deepspeed_tpu.inference.v2.adapters._stacks import (_pad_expert_width,
                                                         _stack_units)
from deepspeed_tpu.inference.v2.model_spec import BlockKind, RaggedModelSpec
from deepspeed_tpu.models import nemotron_h as zoo


def adapt_nemotron_h(params: Dict, config,
                     max_context: Optional[int] = None
                     ) -> Tuple[RaggedModelSpec, Dict]:
    """models/nemotron_h.py param tree (NemotronHForCausalLM; NVIDIA
    Nemotron-H, ``nemotron_h``).

    One :class:`BlockKind` per layer from ``hybrid_override_pattern``: each
    layer is ONE block behind its one norm (``ln1``) — a Mamba-2 mixer
    (``spec.mamba`` with ``n_groups`` pairs of B and C), attention without
    window or positions, or routed experts. The experts are two stacks
    (``w_up``, ``w_down``: no gate; the width zero-padded to whole lane
    tiles, :func:`_pad_expert_width`) with ``relu2`` between them, and so is
    the shared expert (unpadded: a dense product); the router is the sigmoid
    one with its selection bias (``expert_bias``), weights normalised over
    the chosen and scaled; the stacks hold ``config.held`` of its
    ``n_routed_experts``."""
    del max_context
    what = {zoo.MAMBA: "mamba", zoo.MOE: "moe", zoo.ATTENTION: "attention"}
    kinds = tuple(BlockKind(what[c]) for c in config.hybrid_override_pattern)
    first, count = config.held
    moe = {"num_experts": config.n_routed_experts,
           "top_k": config.num_experts_per_tok, "score_func": "sigmoid",
           "route_norm": bool(config.norm_topk_prob),
           "route_scale": float(config.routed_scaling_factor),
           "act": config.mlp_hidden_act}
    if count != config.n_routed_experts:
        moe["held"] = (first, count)
    spec = RaggedModelSpec(
        family="nemotron_h",
        num_layers=config.num_hidden_layers,
        hidden_size=config.hidden_size,
        num_heads=config.num_attention_heads,
        num_kv_heads=config.num_key_value_heads,
        head_dim=config.head_dim,
        vocab_size=config.vocab_size,
        norm="rms", activation=config.mlp_hidden_act, rope_theta=None,
        tied_lm_head=False, eps=config.norm_eps,
        moe=moe if any(k.moe for k in kinds) else None,
        layer_kinds=kinds, dtype=config.dtype,
        mamba={"kind": "mamba2", "d_inner": config.mamba_d_inner,
               "n_heads": config.mamba_num_heads,
               "d_head": config.mamba_head_dim,
               "n_groups": config.n_groups,
               "d_state": config.ssm_state_size,
               "d_conv": config.conv_kernel,
               "chunk": config.chunk_size} if any(
                   k.mamba for k in kinds) else None)

    def layer(i):
        lp = params[f"layers_{i}"]
        m = lp["mixer"]
        out = {"ln1": {"scale": lp["norm"]["weight"]}}
        if kinds[i].mamba:
            out["mamba"] = {
                "in_proj": m["in_proj"]["kernel"],
                "conv_w": jnp.transpose(m["conv_weight"]),       # [K, W]
                "conv_b": m["conv_bias"],
                "dt_bias": m["dt_bias"], "A_log": m["A_log"], "D": m["D"],
                "norm": m["norm"],
                "out_proj": m["out_proj"]["kernel"],
            }
        elif kinds[i].moe:
            w_up, w_down = _pad_expert_width(m["w_up"], m["w_down"])
            out["moe"] = {
                "router": m["router"]["kernel"],
                "expert_bias": m["e_score_correction_bias"],
                "w_up": w_up, "w_down": w_down,
                "shared": {"w_up": m["shared_up"]["kernel"],
                           "w_down": m["shared_down"]["kernel"]}}
        else:
            out.update(wq=m["q_proj"]["kernel"], wk=m["k_proj"]["kernel"],
                       wv=m["v_proj"]["kernel"], wo=m["o_proj"]["kernel"])
        return out

    weights = {
        "embed": params["embed_tokens"]["embedding"],
        "layers": _stack_units(spec, layer),
        "final_norm": {"scale": params["norm_f"]["weight"]},
        "lm_head": params["lm_head"]["kernel"],
    }
    return spec, weights
