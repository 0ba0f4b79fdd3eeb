"""Qwen3-Next's weights as the ragged programs take them."""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference.v2.adapters._stacks import _stack_units
from deepspeed_tpu.inference.v2.model_spec import (DeltaKind, LayerKind,
                                                   RaggedModelSpec, layer_runs)


def adapt_qwen3_next(params: Dict, config,
                     max_context: Optional[int] = None
                     ) -> Tuple[RaggedModelSpec, Dict]:
    """models/qwen3_next.py param tree (Qwen3NextForCausalLM; Qwen3-Next,
    ``qwen3_next``), published layout.

    One kind per layer: :class:`DeltaKind` (Gated DeltaNet; ``spec.mamba``
    with ``"kind": "gdn"``) or a full-attention :class:`LayerKind` with
    rotation, both over routed experts. What the published layout fuses is
    taken apart here, once:

    - ``in_proj_qkvz``'s columns (a key head's q, k, its value heads' v and z
      together) are put in the order ``[q | k | v | z]`` over all heads, so
      that the convolution's input is the product's first ``2 Hk N + E``
      columns; ``in_proj_ba``'s likewise ``[b | a]``;
    - ``q_proj`` (a head's query, then its gate) becomes ``wq`` and the output
      gate ``wg`` of the branch afmoe's gated attention takes;
    - the rotation pairs value ``i`` with ``i + rotary_dim / 2`` where the
      ragged path's pairs ``2i`` with ``2i + 1``: the first ``rotary_dim``
      columns of each q and k head (and their norms' gains) are interleaved,
      the same way in both, which leaves every ``q . k`` as it was.

    Every norm but the mixer's own scales by ``1 + w`` (``norm_plus_one``).
    The router is the softmax one (top-k of the logits, softmax over the
    chosen = softmax over all, top-k, renormalised); the stacks hold
    ``config.held`` of its ``num_experts``; the shared expert rides as the
    layer's ``shared`` expert behind ``shared_gate``."""
    del max_context
    kinds = tuple(LayerKind(None, True, True) if config.is_attention_layer(i)
                  else DeltaKind(True)
                  for i in range(config.num_hidden_layers))
    first, count = config.held
    moe = {"num_experts": config.num_experts,
           "top_k": config.num_experts_per_tok, "shared_gate": True}
    if count != config.num_experts:
        moe["held"] = (first, count)
    Hk, Hv = config.linear_num_key_heads, config.linear_num_value_heads
    N, P = config.linear_key_head_dim, config.linear_value_head_dim
    spec = RaggedModelSpec(
        family="qwen3_next",
        num_layers=config.num_hidden_layers,
        hidden_size=config.hidden_size,
        num_heads=config.num_attention_heads,
        num_kv_heads=config.num_key_value_heads,
        head_dim=config.head_dim,
        vocab_size=config.vocab_size,
        norm="rms", activation="swiglu", rope_theta=config.rope_theta,
        rotary_dim=config.rotary_dim, norm_plus_one=True,
        tied_lm_head=False, eps=config.rms_norm_eps, moe=moe,
        layer_kinds=kinds, dtype=config.dtype,
        mamba={"kind": "gdn", "d_inner": config.value_dim, "n_heads": Hv,
               "d_head": P, "n_key_heads": Hk, "d_state": N,
               "d_conv": config.linear_conv_kernel_dim,
               "conv_dim": config.conv_dim,
               "chunk": config.chunk_size} if any(
                   k.mamba for k in kinds) else None)
    if len(set(kinds)) == 1:    # one kind after all: the scalar fields say it
        spec = layer_runs(spec)[0][0]

    H, D, rd = config.num_attention_heads, config.head_dim, config.rotary_dim
    # half-split pairs -> interleaved pairs, inside a head's first rd values
    turn = np.concatenate([np.arange(rd).reshape(2, rd // 2).T.reshape(-1),
                           np.arange(rd, D)])
    heads = lambda x, n: x.reshape(x.shape[0], n, -1)
    R = Hv // Hk

    def swiglu(p):
        return {"w_gate": p["gate_proj"]["kernel"],
                "w_up": p["up_proj"]["kernel"],
                "w_down": p["down_proj"]["kernel"]}

    def layer(i):
        lp = params[f"layers_{i}"]
        ff = lp["mlp"]
        out = {
            "ln1": {"scale": lp["input_layernorm"]["weight"]},
            "ln2": {"scale": lp["post_attention_layernorm"]["weight"]},
            "moe": {"router": ff["gate"]["kernel"],
                    "w_gate": ff["w_gate"], "w_up": ff["w_up"],
                    "w_down": ff["w_down"],
                    "shared": swiglu(ff["shared_expert"]),
                    "shared_gate": ff["shared_expert_gate"]["kernel"]},
        }
        if kinds[i].mamba:
            m = lp["linear_attn"]
            qkvz = heads(m["in_proj_qkvz"]["kernel"], Hk)
            ba = heads(m["in_proj_ba"]["kernel"], Hk)
            flat = lambda x: x.reshape(x.shape[0], -1)
            out["gdn"] = {
                "in_proj": jnp.concatenate(
                    [flat(qkvz[..., :N]), flat(qkvz[..., N:2 * N]),
                     flat(qkvz[..., 2 * N:2 * N + R * P]),
                     flat(qkvz[..., 2 * N + R * P:])], axis=1),
                "in_ba": jnp.concatenate(
                    [flat(ba[..., :R]), flat(ba[..., R:])], axis=1),
                "conv_w": jnp.transpose(m["conv_weight"]),       # [K, W]
                "dt_bias": m["dt_bias"], "A_log": m["A_log"],
                "norm": m["norm"],
                "out_proj": m["out_proj"]["kernel"],
            }
        else:
            attn = lp["self_attn"]
            qg = heads(attn["q_proj"]["kernel"], H)              # [hid, H, 2D]
            wq = qg[..., :D][..., turn]
            wk = heads(attn["k_proj"]["kernel"],
                       config.num_key_value_heads)[..., turn]
            out.update(
                wq=wq.reshape(wq.shape[0], -1),
                wg=qg[..., D:].reshape(qg.shape[0], -1),
                wk=wk.reshape(wk.shape[0], -1),
                wv=attn["v_proj"]["kernel"], wo=attn["o_proj"]["kernel"],
                q_norm=attn["q_norm"]["weight"][turn],
                k_norm=attn["k_norm"]["weight"][turn])
        return out

    stacks = _stack_units(spec, layer)
    weights = {
        "embed": params["embed_tokens"]["embedding"],
        "layers": stacks if spec.layer_kinds is not None else stacks[0],
        "final_norm": {"scale": params["norm"]["weight"]},
        "lm_head": params["lm_head"]["kernel"],
    }
    return spec, weights
