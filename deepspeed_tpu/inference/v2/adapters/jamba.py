"""Jamba's weights as the ragged programs take them."""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax.numpy as jnp

from deepspeed_tpu.inference.v2.adapters._stacks import _stack_units
from deepspeed_tpu.inference.v2.model_spec import (LayerKind, MambaKind,
                                                   RaggedModelSpec, layer_runs)
from deepspeed_tpu.models.jamba import MAMBA


def adapt_jamba(params: Dict, config,
                max_context: Optional[int] = None) -> Tuple[RaggedModelSpec, Dict]:
    """models/jamba.py param tree (JambaForCausalLM; AI21 Jamba).

    One kind per layer from the config's ``layer_types`` (what
    ``attn_layer_period``/``attn_layer_offset`` build): :class:`MambaKind` or
    an attention :class:`LayerKind` without window or positions, every FFN
    dense. A run of Mamba layers stacks
    the mixer's matrices under their own names (``in_proj`` ... ``out_proj``)
    where a run of attention layers has ``wq``..``wo``; ``A_log`` is stored
    transposed, ``[N, E]``, the state's layout (ops/pallas/ssm.py)."""
    kinds = tuple(MambaKind() if t == MAMBA else LayerKind(None, False, False)
                  for t in config.layer_types)
    spec = RaggedModelSpec(
        family="jamba",
        num_layers=config.num_hidden_layers,
        hidden_size=config.hidden_size,
        num_heads=config.num_attention_heads,
        num_kv_heads=config.num_key_value_heads,
        head_dim=config.head_dim,
        vocab_size=config.vocab_size,
        norm="rms", activation="swiglu", rope_theta=None,
        tied_lm_head=True, eps=config.rms_norm_eps,
        layer_kinds=kinds, dtype=config.dtype,
        mamba={"d_inner": config.mamba_d_inner,
               "d_state": config.mamba_d_state,
               "dt_rank": config.mamba_dt_rank,
               "d_conv": config.mamba_d_conv} if any(
                   k.mamba for k in kinds) else None)
    if len(set(kinds)) == 1:    # one kind after all: the scalar fields say it
        spec = layer_runs(spec)[0][0]

    def layer(i):
        lp = params[f"layers_{i}"]
        ff = lp["feed_forward"]
        out = {
            "ln1": {"scale": lp["input_layernorm"]["weight"]},
            "ln2": {"scale": lp["pre_ff_layernorm"]["weight"]},
            "mlp": {"w_gate": ff["gate_proj"]["kernel"],
                    "w_up": ff["up_proj"]["kernel"],
                    "w_down": ff["down_proj"]["kernel"]},
        }
        if kinds[i].mamba:
            m = lp["mamba"]
            out["mamba"] = {
                "in_proj": m["in_proj"]["kernel"],
                "conv_w": jnp.transpose(m["conv_weight"]),       # [K, E]
                "conv_b": m["conv_bias"],
                "x_proj": m["x_proj"]["kernel"],
                "dt_norm": m["dt_layernorm"]["weight"],
                "b_norm": m["b_layernorm"]["weight"],
                "c_norm": m["c_layernorm"]["weight"],
                "dt_proj": m["dt_proj"]["kernel"],
                "dt_bias": m["dt_bias"],
                "A_log": jnp.transpose(m["A_log"]),              # [N, E]
                "D": m["D"],
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
        "final_norm": {"scale": params["final_layernorm"]["weight"]},
    }
    return spec, weights
