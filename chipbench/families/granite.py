"""The granite family (IBM Granite 4.0-H, ``granitemoehybrid``) for the
benchmark: from a configuration file to the program's model, and the
program's weights under the names of the plain reference
(``chipbench/reference/granite_ref.py``).

A family module is found by the configuration's ``family`` key
(``chipbench/families/<family>.py``). This one gives the serving bring-up of
``drivers/serve_closed_state_moe.py``: ``REFERENCE``, ``build_model``,
``init_params`` (the weights a layer at a time), ``reference_hp``,
``reference_weights``, ``kv_layout`` (the pages' layers: the attention layers
only), ``state_layout`` (the recurrent state a sequence holds),
``check_engine``, which holds the engine to the configuration, and
``held_touched_share``.

The configuration file's ``num_local_experts`` counts the experts HELD here
(one chip's share: ``deployment.held_first`` on); the router's width is
``published.num_local_experts`` where the file has one.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

#: module under chipbench/reference with forward_logits(weights, ids, hp,
#: held=, rows=, with_margin=, with_state=, act_dtype=, state_dtype=)
REFERENCE = "granite_ref"

MODEL_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
              "shared_intermediate_size", "num_hidden_layers", "layer_types",
              "num_attention_heads", "num_key_value_heads",
              "num_experts_per_tok", "mamba_n_heads", "mamba_d_head",
              "mamba_d_state", "mamba_n_groups", "mamba_d_conv",
              "mamba_expand", "mamba_chunk_size", "mamba_conv_bias",
              "mamba_proj_bias", "attention_bias", "attention_multiplier",
              "embedding_multiplier", "residual_multiplier", "logits_scaling",
              "position_embedding_type", "normalization_function",
              "max_position_embeddings", "rms_norm_eps",
              "tie_word_embeddings", "hidden_act")
MAMBA = "mamba"


def experts(cfg: Dict[str, Any]) -> Tuple[int, Tuple[int, int]]:
    """(the router's width, (first, count) of the experts held here)."""
    held = int(cfg["num_local_experts"])
    width = int(cfg.get("published", {}).get("num_local_experts", held))
    deployment = cfg.get("deployment")
    first = int(deployment.get("held_first", 0)) \
        if isinstance(deployment, dict) else 0
    return width, (first, held)


def build_model(cfg: Dict[str, Any], dtype):
    """The program's flax module for configuration file ``cfg``."""
    from deepspeed_tpu.models.granite import (GraniteConfig,
                                              GraniteForCausalLM)
    keys = {k: cfg[k] for k in MODEL_KEYS if k in cfg}
    keys["layer_types"] = tuple(keys["layer_types"])
    width, held = experts(cfg)
    return GraniteForCausalLM(GraniteConfig(
        **keys, num_local_experts=width,
        experts_held=None if held[1] == width else held, dtype=dtype))


def init_params(model, seed: int, dtype):
    """Random weights from the seed in the tree ``model.init`` gives, made on
    the device a layer at a time: one small program a kind of layer (Mamba,
    attention) and one for the embedding and the final norm, as
    ``families/joyai.py`` does and for its reason (a whole model's ``init``
    is every layer's random draws unrolled in ONE program). The keys are of
    jax's ``rbg`` generator; a seed still gives the same weights."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    from chipbench import models
    from deepspeed_tpu.models.granite import GraniteBlock, GraniteForCausalLM
    from deepspeed_tpu.utils.tree import tree_cast

    cfg = model.config
    key = jax.random.wrap_key_data(
        jnp.tile(jax.random.key_data(models.jax_key(seed)), 2), impl="rbg")
    probe = jnp.zeros((1, 8), jnp.int32)
    x = jnp.zeros((1, 8, cfg.hidden_size), dtype)
    ends = GraniteForCausalLM(dataclasses.replace(
        cfg, num_hidden_layers=0, layer_types=()))
    params = dict(jax.jit(lambda k: tree_cast(
        ends.init(k, probe)["params"], dtype))(
            jax.random.fold_in(key, cfg.num_hidden_layers)))
    made = {}
    for i, kind in enumerate(cfg.layer_types):
        if kind not in made:
            made[kind] = jax.jit(lambda k, i=i: tree_cast(
                GraniteBlock(cfg, i).init(k, x)["params"], dtype))
        params[f"layers_{i}"] = made[kind](jax.random.fold_in(key, i))
    return params


def layer_kinds(cfg: Dict[str, Any]) -> List[str]:
    return list(cfg["layer_types"])


def head_dim(cfg: Dict[str, Any]) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def kv_layout(cfg: Dict[str, Any]) -> Tuple[int, int, int]:
    """(layers, key/value heads, head size) of the paged cache: the layers
    that attend, and no other."""
    return (layer_kinds(cfg).count("attention"), cfg["num_key_value_heads"],
            head_dim(cfg))


def state_layout(cfg: Dict[str, Any]) -> Dict[str, int]:
    """What a sequence holds beside its pages: per Mamba layer the state
    ``[N, E]`` (a ``[P, N]`` matrix a head, ``E = H P``) and the
    convolution's tail, ``K - 1`` taps over the ``E + 2 G N`` channels of x,
    B and C padded to a multiple of 1,024, both held in float32."""
    E = cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    N, K = cfg["mamba_d_state"], cfg["mamba_d_conv"]
    conv_dim = E + 2 * cfg["mamba_n_groups"] * N
    width = -(-conv_dim // 1024) * 1024
    layers = layer_kinds(cfg).count(MAMBA)
    return {"layers": layers, "d_inner": E, "d_state": N, "d_conv": K,
            "conv_dim": conv_dim, "conv_width": width,
            "bytes_per_sequence": layers * 4 * (E * N + (K - 1) * width)}


def reference_hp(cfg: Dict[str, Any]) -> Dict[str, Any]:
    width, held = experts(cfg)
    return {"num_heads": cfg["num_attention_heads"],
            "num_kv_heads": cfg["num_key_value_heads"],
            "head_dim": head_dim(cfg),
            "attn_scale": float(cfg["attention_multiplier"]),
            "eps": float(cfg["rms_norm_eps"]),
            "mamba_heads": cfg["mamba_n_heads"],
            "mamba_head_dim": cfg["mamba_d_head"],
            "d_state": cfg["mamba_d_state"],
            "top_k": cfg["num_experts_per_tok"],
            "held": None if held[1] == width else held,
            "embed_scale": float(cfg["embedding_multiplier"]),
            "residual_scale": float(cfg["residual_multiplier"]),
            "logits_scaling": float(cfg["logits_scaling"]),
            "kinds": layer_kinds(cfg)}


def _swiglu(p: Dict[str, Any]) -> Dict[str, Any]:
    return {"w_gate": p["gate_proj"]["kernel"], "w_up": p["up_proj"]["kernel"],
            "w_down": p["down_proj"]["kernel"]}


def reference_weights(params: Dict[str, Any], cfg: Dict[str, Any]
                      ) -> Dict[str, Any]:
    """The zoo's parameter tree under the reference's names (no copy)."""
    layers = []
    for i, kind in enumerate(layer_kinds(cfg)):
        lp = params[f"layers_{i}"]
        ff = lp["block_sparse_moe"]
        layer = {"ln_in": lp["input_layernorm"]["weight"],
                 "ln_ff": lp["post_attention_layernorm"]["weight"],
                 "router": ff["router"]["kernel"], "w_gate": ff["w_gate"],
                 "w_up": ff["w_up"], "w_down": ff["w_down"],
                 "shared": _swiglu(ff["shared_mlp"])}
        if kind == MAMBA:
            m = lp["mamba"]
            layer.update(
                w_in=m["in_proj"]["kernel"], conv_w=m["conv_weight"],
                conv_b=m["conv_bias"], b_dt=m["dt_bias"], A_log=m["A_log"],
                D=m["D"], g_norm=m["norm"], w_out=m["out_proj"]["kernel"])
        else:
            attn = lp["self_attn"]
            layer.update(wq=attn["q_proj"]["kernel"],
                         wk=attn["k_proj"]["kernel"],
                         wv=attn["v_proj"]["kernel"],
                         wo=attn["o_proj"]["kernel"])
        layers.append(layer)
    return {"embed": params["embed_tokens"]["embedding"], "layers": layers,
            "final_norm": params["norm"]["weight"]}


def check_engine(cfg: Dict[str, Any], engine) -> str:
    """What is wrong with the engine's layers and pools against the
    configuration's, or ''."""
    spec = engine.spec
    kinds = spec.layer_kinds
    if kinds is None:
        return "the engine runs every layer as one kind"
    got = [MAMBA if k.mamba else "attention" for k in kinds]
    if got != layer_kinds(cfg):
        return f"the engine's layer kinds are {got}, the file's {layer_kinds(cfg)}"
    if any(k.rope or k.window is not None or not k.moe for k in kinds):
        return "a layer rotates positions, has a window or a dense FFN"
    if (spec.mamba or {}).get("kind") != "mamba2":
        return "the engine's recurrence is not Mamba-2"
    layers, heads, dim = kv_layout(cfg)
    kvc = engine.kv.config
    if (kvc.num_layers, kvc.num_kv_heads, kvc.head_dim) != (layers, heads, dim):
        return (f"the page pool has {kvc.num_layers} layers of {kvc.num_kv_heads}"
                f" x {kvc.head_dim}, the file's attention layers are {layers} "
                f"of {heads} x {dim}")
    want = state_layout(cfg)
    sc = engine.state_config
    if sc is None or (sc.num_layers, sc.d_inner, sc.d_state, sc.d_conv,
                      sc.conv_dim) != tuple(want[k] for k in (
                          "layers", "d_inner", "d_state", "d_conv",
                          "conv_dim")):
        return f"the state pool is {sc}, the file's state {want}"
    if sc.bytes_per_slot() != want["bytes_per_sequence"]:
        return "a state slot's bytes are not the file's"
    if engine.kv.kv.ssm.dtype.name != "float32":
        return f"the recurrent state is held in {engine.kv.kv.ssm.dtype}"
    width, held = experts(cfg)
    if spec.moe["num_experts"] != width or spec.moe.get(
            "held", (0, width)) != held or "score_func" in spec.moe:
        return (f"the engine routes over {spec.moe['num_experts']} experts "
                f"({spec.moe.get('score_func', 'softmax')}) and holds "
                f"{spec.moe.get('held')}; the file says {width} and {held}")
    scales = (spec.embed_scale, spec.residual_scale, spec.logits_scale,
              spec.attn_scale)
    want_scales = (cfg["embedding_multiplier"], cfg["residual_multiplier"],
                   1.0 / cfg["logits_scaling"], cfg["attention_multiplier"])
    if any(abs(a - b) > 1e-12 for a, b in zip(scales, want_scales)):
        return f"the engine's multipliers are {scales}, the file's {want_scales}"
    if not spec.tied_lm_head or spec.rope_theta is not None:
        return "the head is untied or a position embedding is on"
    return ""


def _routers(engine):
    """The router matrix of every layer of the engine."""
    stacks = engine.weights["layers"]
    for stack in stacks if isinstance(stacks, tuple) else (stacks,):
        yield from stack["moe"]["router"]


def held_touched_share(engine, x, rows_a_step: int) -> float:
    """Of the experts held here, the share that a step of ``rows_a_step``
    rows reaches, a layer, in the mean over the layers and over ``x``'s
    ``[T, hidden]`` rows taken ``rows_a_step`` at a time (the engine's own
    router, the published top-k over all experts)."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.v2 import ragged_model

    spec = engine.spec
    width = spec.moe["num_experts"]
    first, count = spec.moe.get("held", (0, width))
    steps = x.shape[0] // rows_a_step

    @jax.jit
    def share(x, router):
        _, ids = ragged_model.moe_route(x, {"router": router},
                                        spec.moe["top_k"], spec.moe)
        hit = jax.nn.one_hot(ids, width, dtype=jnp.float32)[
            ..., first:first + count].reshape(steps, -1, count)
        return jnp.mean(jnp.max(hit, axis=1))

    x = x[:steps * rows_a_step]
    shares = [float(share(x, r)) for r in _routers(engine)]
    return sum(shares) / len(shares)
