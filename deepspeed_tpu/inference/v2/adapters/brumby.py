"""Brumby's weights as the ragged programs take them."""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import numpy as np

from deepspeed_tpu.inference.v2.adapters._stacks import _stack
from deepspeed_tpu.inference.v2.model_spec import RaggedModelSpec
from deepspeed_tpu.ops.pallas.power_retention import (
    state_cols as pr_state_cols, state_rows as pr_state_rows)


def adapt_brumby(params: Dict, config,
                 max_context: Optional[int] = None
                 ) -> Tuple[RaggedModelSpec, Dict]:
    """models/brumby.py param tree (BrumbyForCausalLM; Manifest AI Brumby,
    ``brumby``), published layout. Every layer is of one kind,
    :class:`PowerKind` over a dense SwiGLU: the model holds NO pages
    (``num_page_layers`` 0), and a sequence's device state is its slot of the
    state pool, ``N x D`` float32 a layer (``spec.mamba`` under ``"kind":
    "pr"``; ``ops/pallas/power_retention.py`` gives the layout).

    The rotation pairs value ``i`` with ``i + d / 2`` where the ragged path's
    pairs ``2i`` with ``2i + 1``: each q and k head's columns (and their
    norms' gains) are interleaved, the same way in both, which leaves every
    ``q . k`` — all the layer reads of them — as it was."""
    del max_context
    H, Hk, D = (config.num_attention_heads, config.num_key_value_heads,
                config.head_dim)
    spec = RaggedModelSpec(
        family="brumby",
        num_layers=config.num_hidden_layers,
        hidden_size=config.hidden_size,
        num_heads=H, num_kv_heads=Hk, head_dim=D,
        vocab_size=config.vocab_size,
        norm="rms", activation="swiglu", rope_theta=config.rope_theta,
        tied_lm_head=False, eps=config.rms_norm_eps, dtype=config.dtype,
        mamba={"kind": "pr", "d_inner": pr_state_cols(D),
               "d_state": pr_state_rows(Hk, D), "d_conv": 1,
               "chunk": config.chunk_size, "eps": config.retention_eps})
    turn = np.arange(D).reshape(2, D // 2).T.reshape(-1)
    heads = lambda x, n: x.reshape(x.shape[0], n, D)[..., turn].reshape(
        x.shape)

    def layer(i):
        lp = params[f"layers_{i}"]
        attn, ff = lp["self_attn"], lp["mlp"]
        return {
            "ln1": {"scale": lp["input_layernorm"]["weight"]},
            "ln2": {"scale": lp["post_attention_layernorm"]["weight"]},
            "pr": {"wq": heads(attn["q_proj"]["kernel"], H),
                   "wk": heads(attn["k_proj"]["kernel"], Hk),
                   "wv": attn["v_proj"]["kernel"],
                   "wg": attn["g_proj"]["kernel"], "g_bias": attn["g_bias"],
                   "q_norm": attn["q_norm"]["weight"][turn],
                   "k_norm": attn["k_norm"]["weight"][turn],
                   "wo": attn["o_proj"]["kernel"]},
            "mlp": {"w_gate": ff["gate_proj"]["kernel"],
                    "w_up": ff["up_proj"]["kernel"],
                    "w_down": ff["down_proj"]["kernel"]},
        }

    weights = {
        "embed": params["embed_tokens"]["embedding"],
        "layers": _stack([layer(i) for i in range(config.num_hidden_layers)]),
        "final_norm": {"scale": params["norm"]["weight"]},
        "lm_head": params["lm_head"]["kernel"],
    }
    return spec, weights
