"""Granite 4.0-H's weights as the ragged programs take them."""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax.numpy as jnp

from deepspeed_tpu.inference.v2.adapters._stacks import _stack_units
from deepspeed_tpu.inference.v2.model_spec import (LayerKind, MambaKind,
                                                   RaggedModelSpec, layer_runs)
from deepspeed_tpu.models.granite import MAMBA


def adapt_granite(params: Dict, config,
                  max_context: Optional[int] = None
                  ) -> Tuple[RaggedModelSpec, Dict]:
    """models/granite.py param tree (GraniteForCausalLM; IBM Granite 4.0-H,
    ``granitemoehybrid``).

    One kind per layer from the config's ``layer_types``:
    :class:`MambaKind` with routed experts, or an attention
    :class:`LayerKind` without window or positions, with them too. The
    mixer is Mamba-2 (``spec.mamba["kind"] == "mamba2"``): a run of Mamba
    layers stacks ``in_proj`` (gate, convolution input, ``dt`` a head), the
    convolution over x, B and C together, ``A_log``/``D``/``dt_bias`` a head,
    the gated norm's gain and ``out_proj``. The router is the softmax one
    (top-k of the logits, softmax over the chosen); the stacks hold
    ``config.held`` of its ``num_local_experts``; the shared MLP rides as the
    layer's ``shared`` expert. The four published multipliers are the spec's
    plain floats."""
    del max_context
    kinds = tuple(MambaKind(True) if t == MAMBA
                  else LayerKind(None, False, True)
                  for t in config.layer_types)
    first, count = config.held
    moe = {"num_experts": config.num_local_experts,
           "top_k": config.num_experts_per_tok}
    if count != config.num_local_experts:
        moe["held"] = (first, count)
    spec = RaggedModelSpec(
        family="granite",
        num_layers=config.num_hidden_layers,
        hidden_size=config.hidden_size,
        num_heads=config.num_attention_heads,
        num_kv_heads=config.num_key_value_heads,
        head_dim=config.head_dim,
        vocab_size=config.vocab_size,
        norm="rms", activation="swiglu", rope_theta=None,
        tied_lm_head=True, eps=config.rms_norm_eps, moe=moe,
        layer_kinds=kinds, dtype=config.dtype,
        embed_scale=float(config.embedding_multiplier),
        residual_scale=float(config.residual_multiplier),
        logits_scale=1.0 / float(config.logits_scaling),
        attn_scale=float(config.attention_multiplier),
        mamba={"kind": "mamba2", "d_inner": config.mamba_d_inner,
               "n_heads": config.mamba_n_heads,
               "d_head": config.mamba_d_head,
               "n_groups": config.mamba_n_groups,
               "d_state": config.mamba_d_state,
               "d_conv": config.mamba_d_conv,
               "chunk": config.mamba_chunk_size} if any(
                   k.mamba for k in kinds) else None)
    if len(set(kinds)) == 1:    # one kind after all: the scalar fields say it
        spec = layer_runs(spec)[0][0]

    def swiglu(p):
        return {"w_gate": p["gate_proj"]["kernel"],
                "w_up": p["up_proj"]["kernel"],
                "w_down": p["down_proj"]["kernel"]}

    def layer(i):
        lp = params[f"layers_{i}"]
        ff = lp["block_sparse_moe"]
        out = {
            "ln1": {"scale": lp["input_layernorm"]["weight"]},
            "ln2": {"scale": lp["post_attention_layernorm"]["weight"]},
            "moe": {"router": ff["router"]["kernel"],
                    "w_gate": ff["w_gate"], "w_up": ff["w_up"],
                    "w_down": ff["w_down"],
                    "shared": swiglu(ff["shared_mlp"])},
        }
        if kinds[i].mamba:
            m = lp["mamba"]
            out["mamba"] = {
                "in_proj": m["in_proj"]["kernel"],
                "conv_w": jnp.transpose(m["conv_weight"]),       # [K, W]
                "conv_b": m["conv_bias"],
                "dt_bias": m["dt_bias"], "A_log": m["A_log"], "D": m["D"],
                "norm": m["norm"],
                "out_proj": m["out_proj"]["kernel"],
            }
        else:
            attn = lp["self_attn"]
            out.update(wq=attn["q_proj"]["kernel"], wk=attn["k_proj"]["kernel"],
                       wv=attn["v_proj"]["kernel"], wo=attn["o_proj"]["kernel"])
        return out

    stacks = _stack_units(spec, layer)
    weights = {
        "embed": params["embed_tokens"]["embedding"],
        "layers": stacks if spec.layer_kinds is not None else stacks[0],
        "final_norm": {"scale": params["norm"]["weight"]},
    }
    return spec, weights
