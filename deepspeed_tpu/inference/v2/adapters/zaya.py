"""ZAYA1's weights as the ragged programs take them, and the order its
channels are laid out in."""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference.v2.adapters._stacks import _stack
from deepspeed_tpu.inference.v2.model_spec import RaggedModelSpec


def adapt_zaya(params: Dict, config,
               max_context: Optional[int] = None
               ) -> Tuple[RaggedModelSpec, Dict]:
    """models/zaya.py param tree (ZayaForCausalLM; Zyphra ZAYA1, ``zaya``),
    published layout. Every layer is of one kind, :class:`CcaKind` over
    routed experts, so the scalar fields say it (``spec.cca``, ``spec.moe``
    with ``"router": "mlp"``).

    - the four compressed projections become ONE matrix ``cca.in_proj``,
      columns ``[qp | kp | z | v1]``: the first ``tail_channels`` are what a
      sequence keeps a tail of (q and k of every head for the convolutions,
      ``z`` for the shifted value), the token's own value last;
    - the depthwise taps are stored ``[tap, channel]`` and the grouped
      convolution's ``[tap, head, in, out]`` (PyTorch: ``[out, in, tap]``);
    - the rotation pairs value ``i`` with ``i + rotary_dim / 2`` where the
      ragged path's pairs ``2i`` with ``2i + 1``: the first ``rotary_dim``
      channels of each q and k head are interleaved, the same way in the
      projections, both convolutions' weights and biases (the q-k mean is
      channel by channel and the norm does not see the order), which leaves
      every ``q . k`` as it was. The tail pool holds the channels in that
      order (:func:`zaya_channel_order`);
    - the router's state scale ``gamma`` of the FIRST layer is zero: its
      router is handed a state of zeros and adds ``gamma * 0``, which is the
      published "every layer but the first" without a layer of another
      shape."""
    del max_context
    H, Hk, D = (config.num_attention_heads, config.num_key_value_heads,
                config.head_dim)
    C, K0, K1 = config.conv_dim, config.cca_time0, config.cca_time1
    E = config.num_experts
    spec = RaggedModelSpec(
        family="zaya",
        num_layers=config.num_hidden_layers,
        hidden_size=config.hidden_size,
        num_heads=H, num_kv_heads=Hk, head_dim=D,
        vocab_size=config.vocab_size,
        norm="rms", activation="swiglu", rope_theta=config.rope_theta,
        rotary_dim=config.rotary_dim, tied_lm_head=True,
        eps=config.rms_norm_eps,
        moe={"num_experts": E, "top_k": 1, "router": "mlp",
             "router_hidden": config.router_hidden_size, "skip": True},
        cca={"time0": K0, "time1": K1, "conv_dim": C,
             "tail_channels": C + D, "taps": config.tail_taps},
        dtype=config.dtype)
    turn = zaya_channel_order(H + Hk, D, config.rotary_dim)     # [C]
    turn_d = turn[:D]

    def layer(i):
        lp = params[f"layers_{i}"]
        attn, ff = lp["self_attn"], lp["mlp"]
        w1 = attn["conv1_weight"].reshape(C // D, D, D, K1)   # h, out, in, tap
        gamma = ff["router_state_scale"]
        return {
            "ln1": {"scale": lp["input_layernorm"]["weight"]},
            "ln2": {"scale": lp["post_attention_layernorm"]["weight"]},
            "res_scale": lp["residual_scale"],
            "res_bias": lp["residual_bias"],
            "cca": {
                "in_proj": jnp.concatenate(
                    [attn["q_proj"]["kernel"][:, turn[:H * D]],
                     attn["k_proj"]["kernel"][:, turn[H * D:] - H * D],
                     attn["v_prev_proj"]["kernel"],
                     attn["v_proj"]["kernel"]], axis=1),
                "conv0_w": jnp.transpose(attn["conv0_weight"][turn]),
                "conv0_b": attn["conv0_bias"][turn],
                "conv1_w": jnp.transpose(
                    w1[:, turn_d][:, :, turn_d], (3, 0, 2, 1)),
                "conv1_b": attn["conv1_bias"][turn],
                "temp": attn["temp"],
            },
            "wo": attn["o_proj"]["kernel"],
            "moe": {
                "router_down": ff["router_down"]["kernel"],
                "router_down_b": ff["router_down"]["bias"],
                "router_gamma": gamma if i else jnp.zeros_like(gamma),
                "router_norm": ff["router_norm"]["weight"],
                "router_fc1": ff["router_fc1"]["kernel"],
                "router_fc1_b": ff["router_fc1"]["bias"],
                "router_fc2": ff["router_fc2"]["kernel"],
                "router_fc2_b": ff["router_fc2"]["bias"],
                "router_out": ff["router_out"]["kernel"],
                "router_bias": ff["balancing_bias"],
                "w_gate": ff["w_gate"], "w_up": ff["w_up"],
                "w_down": ff["w_down"],
            },
        }

    weights = {
        "embed": params["embed_tokens"]["embedding"],
        "layers": _stack([layer(i) for i in range(config.num_hidden_layers)]),
        "final_norm": {"scale": params["norm"]["weight"]},
    }
    return spec, weights


def zaya_channel_order(heads: int, head_dim: int, rotary_dim: int
                       ) -> np.ndarray:
    """For each channel of ``heads`` heads of ``head_dim`` as
    :func:`adapt_zaya` lays them out, the published channel it holds: inside
    a head's first ``rotary_dim`` values, half-split pairs (``i``, ``i +
    rotary_dim / 2``) become neighbours (``2i``, ``2i + 1``)."""
    turn = np.concatenate([
        np.arange(rotary_dim).reshape(2, rotary_dim // 2).T.reshape(-1),
        np.arange(rotary_dim, head_dim)])
    return (np.arange(heads)[:, None] * head_dim + turn[None]).reshape(-1)
