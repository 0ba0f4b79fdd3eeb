"""The nemotron_h family (NVIDIA Nemotron-H; Nemotron 3 Nano 30B-A3B) for the
benchmark: from a configuration file to the program's model, and the
program's weights under the names of the plain reference
(``chipbench/reference/nemotron_h_ref.py``).

A family module is found by the configuration's ``family`` key
(``chipbench/families/<family>.py``). This one gives the serving bring-up of
``drivers/serve_closed_state_moe.py``: ``REFERENCE``, ``build_model``,
``init_params`` (the weights a layer at a time), ``reference_hp``,
``reference_weights``, ``kv_layout`` (the pages' layers: the attention layers
only), ``state_layout`` (the recurrent state a sequence holds: the Mamba
layers only), ``check_engine``, which holds the engine to the configuration,
and ``held_touched_share``.

A layer is ONE block: ``hybrid_override_pattern`` gives ``M`` (Mamba-2), ``E``
(routed experts) or ``*`` (attention) a layer. The configuration file's
``n_routed_experts`` counts the experts HELD here (one chip's share:
``deployment.held_first`` on); the router's width is
``published.n_routed_experts`` where the file has one.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

#: module under chipbench/reference with forward_variants(weights, ids, hp,
#: variants, rows=)
REFERENCE = "nemotron_h_ref"

MODEL_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
              "hybrid_override_pattern", "num_attention_heads",
              "num_key_value_heads", "head_dim", "attention_bias",
              "mamba_num_heads", "mamba_head_dim", "ssm_state_size",
              "n_groups", "conv_kernel", "chunk_size", "expand",
              "use_conv_bias", "mamba_proj_bias", "mamba_hidden_act",
              "intermediate_size", "mlp_hidden_act", "mlp_bias",
              "num_experts_per_tok", "moe_intermediate_size",
              "n_shared_experts", "moe_shared_expert_intermediate_size",
              "n_group", "topk_group", "norm_topk_prob",
              "routed_scaling_factor", "norm_eps", "layer_norm_epsilon",
              "max_position_embeddings", "rope_theta",
              "partial_rotary_factor", "tie_word_embeddings", "use_bias",
              "time_step_min", "time_step_max", "time_step_floor")
KINDS = {"M": "mamba", "E": "moe", "*": "attention"}


def experts(cfg: Dict[str, Any]) -> Tuple[int, Tuple[int, int]]:
    """(the router's width, (first, count) of the experts held here)."""
    held = int(cfg["n_routed_experts"])
    width = int(cfg.get("published", {}).get("n_routed_experts", held))
    deployment = cfg.get("deployment")
    first = int(deployment.get("held_first", 0)) \
        if isinstance(deployment, dict) else 0
    return width, (first, held)


def build_model(cfg: Dict[str, Any], dtype):
    """The program's flax module for configuration file ``cfg``."""
    from deepspeed_tpu.models.nemotron_h import (NemotronHConfig,
                                                 NemotronHForCausalLM)
    keys = {k: cfg[k] for k in MODEL_KEYS if k in cfg}
    width, held = experts(cfg)
    return NemotronHForCausalLM(NemotronHConfig(
        **keys, n_routed_experts=width,
        experts_held=None if held[1] == width else held, dtype=dtype))


def init_params(model, seed: int, dtype):
    """Random weights from the seed in the tree ``model.init`` gives, made on
    the device a layer at a time: one small program a kind of block and one
    for the embedding, the final norm and the head, as ``families/granite.py``
    does and for its reason. The keys are of jax's ``rbg`` generator; a seed
    still gives the same weights."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    from chipbench import models
    from deepspeed_tpu.models.nemotron_h import (NemotronHBlock,
                                                 NemotronHForCausalLM)
    from deepspeed_tpu.utils.tree import tree_cast

    cfg = model.config
    key = jax.random.wrap_key_data(
        jnp.tile(jax.random.key_data(models.jax_key(seed)), 2), impl="rbg")
    probe = jnp.zeros((1, 8), jnp.int32)
    x = jnp.zeros((1, 8, cfg.hidden_size), dtype)
    ends = NemotronHForCausalLM(dataclasses.replace(
        cfg, num_hidden_layers=0, hybrid_override_pattern=""))
    params = dict(jax.jit(lambda k: tree_cast(
        ends.init(k, probe)["params"], dtype))(
            jax.random.fold_in(key, cfg.num_hidden_layers)))
    made = {}
    for i, kind in enumerate(cfg.hybrid_override_pattern):
        if kind not in made:
            made[kind] = jax.jit(lambda k, i=i: tree_cast(
                NemotronHBlock(cfg, i).init(k, x)["params"], dtype))
        params[f"layers_{i}"] = made[kind](jax.random.fold_in(key, i))
    return params


def layer_kinds(cfg: Dict[str, Any]) -> List[str]:
    return [KINDS[c] for c in cfg["hybrid_override_pattern"]]


def kv_layout(cfg: Dict[str, Any]) -> Tuple[int, int, int]:
    """(layers, key/value heads, head size) of the paged cache: the layers
    that attend, and no other."""
    return (layer_kinds(cfg).count("attention"), cfg["num_key_value_heads"],
            cfg["head_dim"])


def state_layout(cfg: Dict[str, Any]) -> Dict[str, int]:
    """What a sequence holds beside its pages: per Mamba layer the state
    ``[N, E]`` (a ``[P, N]`` matrix a head, ``E = H P``) and the
    convolution's tail, ``K - 1`` taps over the ``E + 2 G N`` channels of x,
    B and C padded to a multiple of 1,024, both held in float32. An expert
    layer holds nothing."""
    E = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    N, K = cfg["ssm_state_size"], cfg["conv_kernel"]
    conv_dim = E + 2 * cfg["n_groups"] * N
    width = -(-conv_dim // 1024) * 1024
    layers = layer_kinds(cfg).count("mamba")
    return {"layers": layers, "d_inner": E, "d_state": N, "d_conv": K,
            "conv_dim": conv_dim, "conv_width": width,
            "bytes_per_sequence": layers * 4 * (E * N + (K - 1) * width)}


def reference_hp(cfg: Dict[str, Any]) -> Dict[str, Any]:
    width, held = experts(cfg)
    return {"num_heads": cfg["num_attention_heads"],
            "num_kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"],
            "eps": float(cfg["norm_eps"]),
            "mamba_heads": cfg["mamba_num_heads"],
            "mamba_head_dim": cfg["mamba_head_dim"],
            "d_state": cfg["ssm_state_size"],
            "n_groups": cfg["n_groups"],
            "top_k": cfg["num_experts_per_tok"],
            "route_scale": float(cfg["routed_scaling_factor"]),
            "held": None if held[1] == width else held,
            "kinds": layer_kinds(cfg)}


def reference_weights(params: Dict[str, Any], cfg: Dict[str, Any]
                      ) -> Dict[str, Any]:
    """The zoo's parameter tree under the reference's names (no copy)."""
    layers = []
    for i, kind in enumerate(layer_kinds(cfg)):
        lp = params[f"layers_{i}"]
        m = lp["mixer"]
        layer = {"ln": lp["norm"]["weight"]}
        if kind == "mamba":
            layer.update(
                w_in=m["in_proj"]["kernel"], conv_w=m["conv_weight"],
                conv_b=m["conv_bias"], b_dt=m["dt_bias"], A_log=m["A_log"],
                D=m["D"], g_norm=m["norm"], w_out=m["out_proj"]["kernel"])
        elif kind == "moe":
            layer.update(
                router=m["router"]["kernel"],
                bias=m["e_score_correction_bias"], w_up=m["w_up"],
                w_down=m["w_down"],
                shared={"w_up": m["shared_up"]["kernel"],
                        "w_down": m["shared_down"]["kernel"]})
        else:
            layer.update(wq=m["q_proj"]["kernel"], wk=m["k_proj"]["kernel"],
                         wv=m["v_proj"]["kernel"], wo=m["o_proj"]["kernel"])
        layers.append(layer)
    return {"embed": params["embed_tokens"]["embedding"], "layers": layers,
            "final_norm": params["norm_f"]["weight"],
            "head": params["lm_head"]["kernel"]}


def check_engine(cfg: Dict[str, Any], engine) -> str:
    """What is wrong with the engine's layers and pools against the
    configuration's, or ''."""
    spec = engine.spec
    kinds = spec.layer_kinds
    if kinds is None:
        return "the engine runs every layer as one kind"
    got = [getattr(k, "what", "a mixer and an FFN") for k in kinds]
    if got != layer_kinds(cfg):
        return f"the engine's layer kinds are {got}, the file's {layer_kinds(cfg)}"
    if any(k.rope or k.window is not None for k in kinds):
        return "a layer rotates positions or has a window"
    m = spec.mamba or {}
    if m.get("kind") != "mamba2" or m.get("n_groups") != cfg["n_groups"] \
            or m.get("chunk") != cfg["chunk_size"]:
        return (f"the engine's recurrence is {m}, the file's Mamba-2 with "
                f"{cfg['n_groups']} groups and chunks of {cfg['chunk_size']}")
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
    moe = spec.moe
    if moe["num_experts"] != width or moe.get("held", (0, width)) != held \
            or moe.get("score_func") != "sigmoid" or not moe.get("route_norm") \
            or abs(moe.get("route_scale", 1.0)
                   - cfg["routed_scaling_factor"]) > 1e-12 \
            or moe.get("act") != cfg["mlp_hidden_act"] \
            or moe["top_k"] != cfg["num_experts_per_tok"]:
        return (f"the engine's routing is {moe}; the file says a sigmoid "
                f"router over {width}, top-{cfg['num_experts_per_tok']}, "
                f"normalised, times {cfg['routed_scaling_factor']}, "
                f"{cfg['mlp_hidden_act']} experts, {held} held")
    if spec.tied_lm_head or spec.rope_theta is not None:
        return "the head is tied or a position embedding is on"
    if any(s not in (None, 1.0) for s in (
            spec.embed_scale, spec.residual_scale, spec.logits_scale)):
        return "a multiplier the family does not have is on"
    return ""


def _routers(engine):
    """(router matrix, selection bias) of every expert layer of the engine."""
    from deepspeed_tpu.inference.v2.ragged_model import _layer_stacks
    for stack in _layer_stacks(engine.weights["layers"]):
        if "moe" in stack:
            moe = stack["moe"]
            yield from zip(moe["router"], moe["expert_bias"])


def held_touched_share(engine, x, rows_a_step: int) -> float:
    """Of the experts held here, the share that a step of ``rows_a_step``
    rows reaches, a layer, in the mean over the expert layers and over
    ``x``'s ``[T, hidden]`` rows taken ``rows_a_step`` at a time (the
    engine's own router, the published top-k over all experts)."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.v2 import ragged_model

    spec = engine.spec
    width = spec.moe["num_experts"]
    first, count = spec.moe.get("held", (0, width))
    steps = x.shape[0] // rows_a_step

    @jax.jit
    def share(x, router, bias):
        _, ids = ragged_model.moe_route(
            x, {"router": router, "expert_bias": bias}, spec.moe["top_k"],
            spec.moe)
        hit = jax.nn.one_hot(ids, width, dtype=jnp.float32)[
            ..., first:first + count].reshape(steps, -1, count)
        return jnp.mean(jnp.max(hit, axis=1))

    x = x[:steps * rows_a_step]
    shares = [float(share(x, r, b)) for r, b in _routers(engine)]
    return sum(shares) / len(shares)
