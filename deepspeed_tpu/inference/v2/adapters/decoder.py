"""The generic decoder lineage's weights (opt, falcon, phi, gpt_neox, gptj,
gpt_bigcode, bloom) as the ragged programs take them."""

from __future__ import annotations

from typing import Dict, Optional, Tuple

from deepspeed_tpu.inference.v2.adapters._stacks import _stack
from deepspeed_tpu.inference.v2.model_spec import RaggedModelSpec


def adapt_decoder(params: Dict, config,
                  max_context: Optional[int] = None) -> Tuple[RaggedModelSpec, Dict]:
    """models/decoder.py (DecoderLM — opt/falcon/phi/gpt_neox/gptj/
    gpt_bigcode): canonical names, so adaptation is re-rooting + stacking.
    Parity anchors: reference ``inference/v2/model_implementations/
    {opt,falcon,phi}``. Guards on the FEATURES the ragged path can't carry
    (not family names), so a config with e.g. alibi under any family is
    rejected instead of silently served wrong."""
    unsupported = []
    if getattr(config, "local_window", None) is not None:
        unsupported.append("local_window")
    if any(k == "local" for k in getattr(config, "attention_layers", None) or ()):
        unsupported.append("attention_layers with 'local' entries")
    if getattr(config, "attn_scale", None) is not None:
        unsupported.append("attn_scale")
    if unsupported:
        # neither is a kernel limit any more: the paged kernels take a score
        # scale (``spec.attn_scale``; granite's, PR 39) and the spec carries a
        # kind per layer — this adapter maps neither yet
        raise ValueError(
            f"config features {unsupported} are not served by the ragged "
            "(paged) attention path: this adapter maps neither a score scale "
            "other than 1/sqrt(head_dim) onto spec.attn_scale nor "
            "'local' attention_layers onto the spec's per-layer kinds — "
            "serve through deepspeed_tpu.init_inference (v1 dense engine) "
            "instead")
    spec = RaggedModelSpec(
        family=config.family,
        num_layers=config.num_hidden_layers,
        hidden_size=config.hidden_size,
        num_heads=config.num_attention_heads,
        num_kv_heads=config.kv_heads,
        head_dim=config.head_dim,
        vocab_size=config.vocab_size,
        norm=config.norm, activation=config.activation,
        rope_theta=config.rope_theta, rotary_dim=config.rotary_dim,
        learned_pos=config.learned_pos, pos_offset=config.pos_offset,
        parallel_block=config.parallel_block,
        parallel_dual_norm=config.parallel_dual_norm,
        tied_lm_head=config.tied_lm_head, head_bias=config.head_bias,
        alibi=getattr(config, "alibi", False),
        embed_norm=getattr(config, "embed_norm", False),
        eps=config.eps, dtype=config.dtype)

    layers = [params[f"layers_{i}"] for i in range(config.num_hidden_layers)]
    weights = {
        "embed": params["embed"]["embedding"],
        "layers": _stack(layers),
        "final_norm": params["final_norm"],
    }
    if spec.embed_norm:
        weights["embed_norm"] = params["embed_norm"]
    if config.learned_pos:
        weights["pos_embed"] = params["pos_embed"]["embedding"]
    if not config.tied_lm_head:
        weights["lm_head"] = params["lm_head"]
    if config.head_bias:
        weights["lm_head_bias"] = params["lm_head_bias"]
    return spec, weights
