"""The afmoe family (Arcee Trinity) for the benchmark: from a configuration
file to the program's model, and the program's weights under the names of
the plain reference (``chipbench/reference/afmoe_ref.py``).

A family module is found by the configuration's ``family`` key
(``chipbench/families/<family>.py``) and gives the serving bring-up
(``drivers/serve_closed_kinds.py``) five things: ``REFERENCE``,
``build_model``, ``reference_hp``, ``reference_weights`` and
``kv_layout``; ``check_engine`` holds the engine to the configuration and
``router_readings`` its router, by itself, to the reference's.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

#: module under chipbench/reference with forward_logits(weights, ids, hp,
#: rows=, with_margin=)
REFERENCE = "afmoe_ref"

MODEL_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
              "moe_intermediate_size", "num_hidden_layers", "num_dense_layers",
              "num_attention_heads", "num_key_value_heads", "head_dim",
              "max_position_embeddings", "rope_theta", "rms_norm_eps",
              "sliding_window", "global_attn_every_n_layers", "layer_types",
              "num_experts", "num_experts_per_tok", "num_shared_experts",
              "score_func", "route_norm", "route_scale", "mup_enabled")
SLIDING = "sliding_attention"


def build_model(cfg: Dict[str, Any], dtype):
    """The program's flax module for configuration file ``cfg``."""
    from deepspeed_tpu.models.afmoe import AfmoeConfig, AfmoeForCausalLM
    if cfg.get("hidden_act", "silu") != "silu" or cfg.get(
            "tie_word_embeddings", False) or cfg.get("rope_scaling"):
        raise ValueError("the reference covers SwiGLU, an untied head and "
                         "unscaled rotary frequencies only")
    keys = {k: cfg[k] for k in MODEL_KEYS if k in cfg}
    return AfmoeForCausalLM(AfmoeConfig(**keys, dtype=dtype))


def kv_layout(cfg: Dict[str, Any]) -> Tuple[int, int, int]:
    """(layers, key/value heads, head size) of the paged cache."""
    return (cfg["num_hidden_layers"], cfg["num_key_value_heads"],
            cfg["head_dim"])


def reference_hp(cfg: Dict[str, Any]) -> Dict[str, Any]:
    kinds = cfg["layer_types"]
    return {"num_heads": cfg["num_attention_heads"],
            "num_kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"],
            "rope_theta": float(cfg["rope_theta"]),
            "eps": float(cfg["rms_norm_eps"]),
            "top_k": cfg["num_experts_per_tok"],
            "route_norm": bool(cfg["route_norm"]),
            "route_scale": float(cfg["route_scale"]),
            "embed_scale": float(cfg["hidden_size"]) ** 0.5
            if cfg["mup_enabled"] else 1.0,
            "windows": [cfg["sliding_window"] if t == SLIDING else None
                        for t in kinds],
            "rotary": [t == SLIDING for t in kinds]}


def _swiglu(p: Dict[str, Any]) -> Dict[str, Any]:
    return {"w_gate": p["gate_proj"]["kernel"], "w_up": p["up_proj"]["kernel"],
            "w_down": p["down_proj"]["kernel"]}


def reference_weights(params: Dict[str, Any], cfg: Dict[str, Any]
                      ) -> Dict[str, Any]:
    """The zoo's parameter tree under the reference's names (no copy)."""
    layers = []
    for i in range(cfg["num_hidden_layers"]):
        lp = params[f"layers_{i}"]
        attn, mlp = lp["self_attn"], lp["mlp"]
        layer = {"ln_in": lp["input_layernorm"]["weight"],
                 "ln_attn_out": lp["post_attention_layernorm"]["weight"],
                 "ln_mlp_in": lp["pre_mlp_layernorm"]["weight"],
                 "ln_mlp_out": lp["post_mlp_layernorm"]["weight"],
                 "wq": attn["q_proj"]["kernel"], "wk": attn["k_proj"]["kernel"],
                 "wv": attn["v_proj"]["kernel"], "wo": attn["o_proj"]["kernel"],
                 "w_attn_gate": attn["gate_proj"]["kernel"],
                 "q_norm": attn["q_norm"]["weight"],
                 "k_norm": attn["k_norm"]["weight"]}
        if "router" in mlp:
            layer.update(router=mlp["router"]["kernel"],
                         expert_bias=mlp["expert_bias"], w_gate=mlp["w_gate"],
                         w_up=mlp["w_up"], w_down=mlp["w_down"])
            if "shared_experts" in mlp:
                layer["shared"] = _swiglu(mlp["shared_experts"])
        else:
            layer.update(_swiglu(mlp))
        layers.append(layer)
    return {"embed": params["embed_tokens"]["embedding"], "layers": layers,
            "final_norm": params["norm"]["weight"],
            "lm_head": params["lm_head"]["kernel"]}


def check_engine(cfg: Dict[str, Any], engine) -> str:
    """What is wrong with the engine's layer kinds against the
    configuration's, or ''."""
    kinds = engine.spec.layer_kinds
    if kinds is None:
        return "the engine runs every layer as one kind"
    want = [(cfg["sliding_window"] if t == SLIDING else None, t == SLIDING,
             i >= cfg["num_dense_layers"])
            for i, t in enumerate(cfg["layer_types"])]
    got = [tuple(k) for k in kinds]
    if got != want:
        return f"the engine's layer kinds are {got}, the file's {want}"
    if engine.scheduler.ring_pages is not None:
        return "the page ring is on beside full-attention layers"
    return ""


def router_readings(engine, reference, hp: Dict[str, Any], x, below: float
                    ) -> Dict[str, float]:
    """The program's router by itself, on the device the engine runs on:
    ``ragged_model.moe_route`` (what every serving program's MoE layer
    calls) with the engine's own router matrix and selection bias of each
    MoE layer, against the reference's ``route`` on the same inputs ``x``
    ``[T, hidden]`` (bfloat16 values, so both sides see the same numbers).

    ``err`` is the largest difference between the two in any expert's
    routing weight for any token (a choice of another expert shows as the
    whole weight). ``control`` is the same reading of the reference against
    itself with its router computed in bfloat16, the smallest of the MoE
    layers' readings: it has to come out over the tolerance ``err`` is held
    to, or the check could not tell. Tokens whose margin (the
    reference's, 8th against 9th of ``scores + expert_bias``) is under
    ``below`` are left out of both: there float32's own order of summation
    chooses."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.v2 import ragged_model

    spec = engine.spec
    top_k = spec.moe["top_k"]
    route = jax.jit(lambda x, w: ragged_model.moe_route(x, w, top_k, spec.moe))
    low = dict(hp, router_dtype=jnp.bfloat16)
    err, control, rows = 0.0, float("inf"), 0
    stacks = engine.weights["layers"]
    stacks = stacks if isinstance(stacks, tuple) else (stacks,)
    for (run, _, n), stack in zip(ragged_model.layer_runs(spec), stacks):
        if run.moe is None:
            continue
        for i in range(n):
            w = {k: stack["moe"][k][i] for k in ("router", "expert_bias")}
            gates, ids = route(x, w)
            got = jnp.sum(jax.nn.one_hot(ids, w["router"].shape[-1],
                                         dtype=jnp.float32)
                          * gates[..., None], axis=1)
            with jax.default_matmul_precision("highest"):
                want, margin = reference.route(x.astype(jnp.float32), w, hp)
                rounded, _ = reference.route(x.astype(jnp.float32), w, low)
            keep = (margin >= below)[:, None]
            rows += int(keep.sum())
            err = max(err, float(jnp.max(jnp.abs(got - want) * keep)))
            control = min(control,
                          float(jnp.max(jnp.abs(rounded - want) * keep)))
    return {"err": err, "control": control, "rows": rows}
