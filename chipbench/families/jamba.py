"""The jamba family (AI21 Jamba2) for the benchmark: from a configuration
file to the program's model, and the program's weights under the names of the
plain reference (``chipbench/reference/jamba_ref.py``).

A family module is found by the configuration's ``family`` key
(``chipbench/families/<family>.py``). This one gives the serving bring-up of
``drivers/serve_closed_state.py``: ``REFERENCE``, ``build_model``,
``reference_hp``, ``reference_weights``, ``kv_layout`` (the pages' layers:
the attention layers only), ``state_layout`` (the recurrent state a sequence
holds) and ``check_engine``, which holds the engine to the configuration.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

#: module under chipbench/reference with forward_logits(weights, ids, hp,
#: rows=, state_dtype=, act_dtype=, with_state=)
REFERENCE = "jamba_ref"

MODEL_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
              "num_hidden_layers", "num_attention_heads",
              "num_key_value_heads", "attn_layer_period", "attn_layer_offset",
              "expert_layer_period", "expert_layer_offset", "num_experts",
              "num_experts_per_tok", "mamba_d_state", "mamba_d_conv",
              "mamba_expand", "mamba_dt_rank", "mamba_conv_bias",
              "mamba_proj_bias", "use_mamba_kernels",
              "max_position_embeddings", "rms_norm_eps", "sliding_window",
              "tie_word_embeddings", "hidden_act")
MAMBA = "mamba"


def build_model(cfg: Dict[str, Any], dtype):
    """The program's flax module for configuration file ``cfg``."""
    from deepspeed_tpu.models.jamba import JambaConfig, JambaForCausalLM
    keys = {k: cfg[k] for k in MODEL_KEYS if k in cfg}
    return JambaForCausalLM(JambaConfig(**keys, dtype=dtype))


def layer_kinds(cfg: Dict[str, Any]) -> List[str]:
    """One of ``"mamba"``/``"attention"`` a layer, as the ``jamba`` model type
    builds them from ``attn_layer_period`` and ``attn_layer_offset``."""
    return ["attention" if i % cfg["attn_layer_period"]
            == cfg["attn_layer_offset"] else MAMBA
            for i in range(cfg["num_hidden_layers"])]


def head_dim(cfg: Dict[str, Any]) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def kv_layout(cfg: Dict[str, Any]) -> Tuple[int, int, int]:
    """(layers, key/value heads, head size) of the paged cache: the layers
    that attend, and no other."""
    return (layer_kinds(cfg).count("attention"), cfg["num_key_value_heads"],
            head_dim(cfg))


def state_layout(cfg: Dict[str, Any]) -> Dict[str, int]:
    """What a sequence holds beside its pages: per Mamba layer ``h`` ``[N, E]``
    and the convolution's tail ``[K - 1, E]``, both held in float32."""
    E = cfg["mamba_expand"] * cfg["hidden_size"]
    N, K = cfg["mamba_d_state"], cfg["mamba_d_conv"]
    layers = layer_kinds(cfg).count(MAMBA)
    return {"layers": layers, "d_inner": E, "d_state": N, "d_conv": K,
            "bytes_per_sequence": layers * 4 * E * (N + K - 1)}


def reference_hp(cfg: Dict[str, Any]) -> Dict[str, Any]:
    return {"num_heads": cfg["num_attention_heads"],
            "num_kv_heads": cfg["num_key_value_heads"],
            "head_dim": head_dim(cfg), "eps": float(cfg["rms_norm_eps"]),
            "dt_rank": cfg["mamba_dt_rank"], "d_state": cfg["mamba_d_state"],
            "kinds": layer_kinds(cfg)}


def reference_weights(params: Dict[str, Any], cfg: Dict[str, Any]
                      ) -> Dict[str, Any]:
    """The zoo's parameter tree under the reference's names (no copy)."""
    layers = []
    for i, kind in enumerate(layer_kinds(cfg)):
        lp = params[f"layers_{i}"]
        ff = lp["feed_forward"]
        layer = {"ln_in": lp["input_layernorm"]["weight"],
                 "ln_ff": lp["pre_ff_layernorm"]["weight"],
                 "w_gate": ff["gate_proj"]["kernel"],
                 "w_up": ff["up_proj"]["kernel"],
                 "w_down": ff["down_proj"]["kernel"]}
        if kind == MAMBA:
            m = lp["mamba"]
            layer.update(
                w_in=m["in_proj"]["kernel"], conv_w=m["conv_weight"],
                conv_b=m["conv_bias"], w_x=m["x_proj"]["kernel"],
                g_dt=m["dt_layernorm"]["weight"],
                g_b=m["b_layernorm"]["weight"],
                g_c=m["c_layernorm"]["weight"], w_dt=m["dt_proj"]["kernel"],
                b_dt=m["dt_bias"], A_log=m["A_log"], D=m["D"],
                w_out=m["out_proj"]["kernel"])
        else:
            attn = lp["self_attn"]
            layer.update(wq=attn["q_proj"]["kernel"],
                         wk=attn["k_proj"]["kernel"],
                         wv=attn["v_proj"]["kernel"],
                         wo=attn["o_proj"]["kernel"])
        layers.append(layer)
    return {"embed": params["embed_tokens"]["embedding"], "layers": layers,
            "final_norm": params["final_layernorm"]["weight"]}


def check_engine(cfg: Dict[str, Any], engine) -> str:
    """What is wrong with the engine's layers and pools against the
    configuration's, or ''."""
    kinds = engine.spec.layer_kinds
    if kinds is None:
        return "the engine runs every layer as one kind"
    got = [MAMBA if k.mamba else "attention" for k in kinds]
    if got != layer_kinds(cfg):
        return f"the engine's layer kinds are {got}, the file's {layer_kinds(cfg)}"
    if any(k.rope or k.window is not None or k.moe for k in kinds):
        return "a layer rotates positions, has a window or routes experts"
    layers, heads, dim = kv_layout(cfg)
    kvc = engine.kv.config
    if (kvc.num_layers, kvc.num_kv_heads, kvc.head_dim) != (layers, heads, dim):
        return (f"the page pool has {kvc.num_layers} layers of {kvc.num_kv_heads}"
                f" x {kvc.head_dim}, the file's attention layers are {layers} "
                f"of {heads} x {dim}")
    want = state_layout(cfg)
    sc = engine.state_config
    if sc is None or (sc.num_layers, sc.d_inner, sc.d_state, sc.d_conv) != (
            want["layers"], want["d_inner"], want["d_state"], want["d_conv"]):
        return f"the state pool is {sc}, the file's state {want}"
    if sc.bytes_per_slot() != want["bytes_per_sequence"]:
        return "a state slot's bytes are not the file's"
    if engine.kv.kv.ssm.dtype.name != "float32":
        return f"the recurrent state is held in {engine.kv.kv.ssm.dtype}"
    if not engine.spec.tied_lm_head or engine.spec.rope_theta is not None:
        return "the head is untied or a position embedding is on"
    return ""
