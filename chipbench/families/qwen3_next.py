"""The qwen3_next family (Qwen3-Next-80B-A3B-Instruct) for the benchmark: from
a configuration file to the program's model, and the program's weights under
the names of the plain reference (``chipbench/reference/qwen3_next_ref.py``).

A family module is found by the configuration's ``family`` key
(``chipbench/families/<family>.py``). This one gives the serving bring-up of
``drivers/serve_closed_state_moe.py``: ``REFERENCE``, ``build_model``,
``init_params`` (the weights a layer at a time), ``reference_hp``,
``reference_weights``, ``kv_layout`` (the pages' layers: the attention layers
only), ``state_layout`` (the recurrent state a sequence holds: the Gated
DeltaNet layers only), ``check_engine``, which holds the engine to the
configuration, and ``held_touched_share``.

Layer ``l`` attends where ``(l + 1) % full_attention_interval == 0`` and is a
Gated DeltaNet layer otherwise; every layer's feed-forward routes experts.
The configuration file's ``num_experts`` counts the experts HELD here (one
chip's share: ``deployment.held_first`` on); the router's width is
``published.num_experts`` where the file has one.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

#: module under chipbench/reference with forward_variants(weights, ids, hp,
#: variants, rows=)
REFERENCE = "qwen3_next_ref"

MODEL_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
              "full_attention_interval", "num_attention_heads",
              "num_key_value_heads", "head_dim", "partial_rotary_factor",
              "rope_theta", "rope_scaling", "linear_num_key_heads",
              "linear_num_value_heads", "linear_key_head_dim",
              "linear_value_head_dim", "linear_conv_kernel_dim", "hidden_act",
              "intermediate_size", "decoder_sparse_step", "mlp_only_layers",
              "num_experts_per_tok", "moe_intermediate_size",
              "shared_expert_intermediate_size", "norm_topk_prob",
              "rms_norm_eps", "max_position_embeddings",
              "tie_word_embeddings", "use_sliding_window")
DELTA, ATTENTION = "delta", "attention"


def experts(cfg: Dict[str, Any]) -> Tuple[int, Tuple[int, int]]:
    """(the router's width, (first, count) of the experts held here)."""
    held = int(cfg["num_experts"])
    width = int(cfg.get("published", {}).get("num_experts", held))
    deployment = cfg.get("deployment")
    first = int(deployment.get("held_first", 0)) \
        if isinstance(deployment, dict) else 0
    return width, (first, held)


def build_model(cfg: Dict[str, Any], dtype):
    """The program's flax module for configuration file ``cfg``."""
    from deepspeed_tpu.models.qwen3_next import (Qwen3NextConfig,
                                                 Qwen3NextForCausalLM)
    keys = {k: cfg[k] for k in MODEL_KEYS if k in cfg}
    assumed = cfg.get("assumed_numbers", {})
    width, held = experts(cfg)
    return Qwen3NextForCausalLM(Qwen3NextConfig(
        **keys, num_experts=width,
        experts_held=None if held[1] == width else held,
        chunk_size=int(assumed.get("chunk_size", 64)),
        slow_heads=int(assumed.get("slow_heads", 4)), dtype=dtype))


def init_params(model, seed: int, dtype):
    """Random weights from the seed in the tree ``model.init`` gives, made on
    the device a layer at a time: one small program a kind of layer and one
    for the embedding, the final norm and the head, as ``families/granite.py``
    does and for its reason. The keys are of jax's ``rbg`` generator; a seed
    still gives the same weights."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    from chipbench import models
    from deepspeed_tpu.models.qwen3_next import (Qwen3NextForCausalLM,
                                                 Qwen3NextLayer)
    from deepspeed_tpu.utils.tree import tree_cast

    cfg = model.config
    key = jax.random.wrap_key_data(
        jnp.tile(jax.random.key_data(models.jax_key(seed)), 2), impl="rbg")
    probe = jnp.zeros((1, 8), jnp.int32)
    x = jnp.zeros((1, 8, cfg.hidden_size), dtype)
    ends = Qwen3NextForCausalLM(dataclasses.replace(cfg, num_hidden_layers=0))
    params = dict(jax.jit(lambda k: tree_cast(
        ends.init(k, probe)["params"], dtype))(
            jax.random.fold_in(key, cfg.num_hidden_layers)))
    made = {}
    for i in range(cfg.num_hidden_layers):
        kind = cfg.is_attention_layer(i)
        if kind not in made:
            made[kind] = jax.jit(lambda k, i=i: tree_cast(
                Qwen3NextLayer(cfg, i).init(k, x)["params"], dtype))
        params[f"layers_{i}"] = made[kind](jax.random.fold_in(key, i))
    return params


def layer_kinds(cfg: Dict[str, Any]) -> List[str]:
    every = int(cfg["full_attention_interval"])
    return [ATTENTION if (i + 1) % every == 0 else DELTA
            for i in range(int(cfg["num_hidden_layers"]))]


def kv_layout(cfg: Dict[str, Any]) -> Tuple[int, int, int]:
    """(layers, key/value heads, head size) of the paged cache: the layers
    that attend, and no other."""
    return (layer_kinds(cfg).count(ATTENTION), cfg["num_key_value_heads"],
            cfg["head_dim"])


def state_layout(cfg: Dict[str, Any]) -> Dict[str, int]:
    """What a sequence holds beside its pages: per Gated DeltaNet layer the
    state ``[N, E]`` (an ``[N, P]`` matrix a value head, ``E = Hv P``, ``N``
    the key heads' width) and the convolution's tail, ``K - 1`` taps over the
    ``2 Hk N + E`` channels of q, k and v (a multiple of 1,024 at the
    published widths, padded to one otherwise), both held in float32."""
    E = cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]
    N, K = cfg["linear_key_head_dim"], cfg["linear_conv_kernel_dim"]
    conv_dim = E + 2 * cfg["linear_num_key_heads"] * N
    width = -(-conv_dim // 1024) * 1024
    layers = layer_kinds(cfg).count(DELTA)
    return {"layers": layers, "d_inner": E, "d_state": N, "d_conv": K,
            "conv_dim": conv_dim, "conv_width": width,
            "bytes_per_sequence": layers * 4 * (E * N + (K - 1) * width)}


def reference_hp(cfg: Dict[str, Any]) -> Dict[str, Any]:
    width, held = experts(cfg)
    return {"num_heads": cfg["num_attention_heads"],
            "num_kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"],
            "rotary_dim": int(cfg["head_dim"] * cfg["partial_rotary_factor"]),
            "rope_theta": float(cfg["rope_theta"]),
            "eps": float(cfg["rms_norm_eps"]),
            "key_heads": cfg["linear_num_key_heads"],
            "value_heads": cfg["linear_num_value_heads"],
            "key_dim": cfg["linear_key_head_dim"],
            "value_dim": cfg["linear_value_head_dim"],
            "top_k": cfg["num_experts_per_tok"],
            "held": None if held[1] == width else held,
            "kinds": layer_kinds(cfg)}


def reference_weights(params: Dict[str, Any], cfg: Dict[str, Any]
                      ) -> Dict[str, Any]:
    """The zoo's parameter tree under the reference's names (no copy)."""
    layers = []
    for i, kind in enumerate(layer_kinds(cfg)):
        lp = params[f"layers_{i}"]
        ff = lp["mlp"]
        sh = ff["shared_expert"]
        layer = {"ln_in": lp["input_layernorm"]["weight"],
                 "ln_ff": lp["post_attention_layernorm"]["weight"],
                 "router": ff["gate"]["kernel"], "w_gate": ff["w_gate"],
                 "w_up": ff["w_up"], "w_down": ff["w_down"],
                 "shared": {"w_gate": sh["gate_proj"]["kernel"],
                            "w_up": sh["up_proj"]["kernel"],
                            "w_down": sh["down_proj"]["kernel"]},
                 "shared_gate": ff["shared_expert_gate"]["kernel"]}
        if kind == DELTA:
            m = lp["linear_attn"]
            layer.update(
                w_qkvz=m["in_proj_qkvz"]["kernel"],
                w_ba=m["in_proj_ba"]["kernel"], conv_w=m["conv_weight"],
                b_dt=m["dt_bias"], A_log=m["A_log"], g_norm=m["norm"],
                w_out=m["out_proj"]["kernel"])
        else:
            a = lp["self_attn"]
            layer.update(
                wq=a["q_proj"]["kernel"], wk=a["k_proj"]["kernel"],
                wv=a["v_proj"]["kernel"], wo=a["o_proj"]["kernel"],
                q_norm=a["q_norm"]["weight"], k_norm=a["k_norm"]["weight"])
        layers.append(layer)
    return {"embed": params["embed_tokens"]["embedding"], "layers": layers,
            "final_norm": params["norm"]["weight"],
            "head": params["lm_head"]["kernel"]}


def check_engine(cfg: Dict[str, Any], engine) -> str:
    """What is wrong with the engine's layers and pools against the
    configuration's, or ''."""
    spec = engine.spec
    kinds = spec.layer_kinds
    if kinds is None:
        return "the engine runs every layer as one kind"
    got = [DELTA if k.mamba else ATTENTION for k in kinds]
    if got != layer_kinds(cfg):
        return f"the engine's layer kinds are {got}, the file's {layer_kinds(cfg)}"
    if any(k.window is not None or k.rope == k.mamba or not k.moe
           for k in kinds):
        return ("a layer has a window, an attention layer no rotation, a "
                "delta layer one, or a layer no experts")
    m = spec.mamba or {}
    want = state_layout(cfg)
    chunk = int(cfg.get("assumed_numbers", {}).get("chunk_size", 64))
    if m.get("kind") != "gdn" or m.get("chunk") != chunk \
            or m.get("n_key_heads") != cfg["linear_num_key_heads"] \
            or m.get("n_heads") != cfg["linear_num_value_heads"] \
            or m.get("d_head") != cfg["linear_value_head_dim"]:
        return (f"the engine's recurrence is {m}, the file's the gated delta "
                f"rule in chunks of {chunk}")
    rd = int(cfg["head_dim"] * cfg["partial_rotary_factor"])
    if spec.rotary_dim != rd or spec.rope_theta != cfg["rope_theta"] \
            or not spec.norm_plus_one:
        return (f"rotation of {spec.rotary_dim} values at theta "
                f"{spec.rope_theta} (the file's: {rd}, {cfg['rope_theta']}), "
                f"or norms that do not scale by 1 + w")
    layers, heads, dim = kv_layout(cfg)
    kvc = engine.kv.config
    if (kvc.num_layers, kvc.num_kv_heads, kvc.head_dim) != (layers, heads, dim):
        return (f"the page pool has {kvc.num_layers} layers of {kvc.num_kv_heads}"
                f" x {kvc.head_dim}, the file's attention layers are {layers} "
                f"of {heads} x {dim}")
    sc = engine.state_config
    if sc is None or (sc.num_layers, sc.d_inner, sc.d_state, sc.d_conv,
                      sc.conv_dim) != tuple(want[k] for k in (
                          "layers", "d_inner", "d_state", "d_conv",
                          "conv_dim")):
        return f"the state pool is {sc}, the file's state {want}"
    if sc.bytes_per_slot() != want["bytes_per_sequence"]:
        return "a state slot's bytes are not the file's"
    # (off the chip the rehearsal's widths are laid over the file: the
    # account's numbers are the chip's)
    numbers = None if "rehearsal_hbm_bytes" in cfg \
        else cfg.get("memory_account_numbers")
    if numbers and (
            sc.bytes_per_slot() != numbers["state_bytes_a_sequence"]
            or sc.num_slots + 1 != numbers["state_slots"]
            or kvc.bytes_per_block() != numbers["bytes_a_page"]):
        return ("the engine's slots or pages are not the memory account's: "
                f"{sc.bytes_per_slot()} B a slot x {sc.num_slots + 1}, "
                f"{kvc.bytes_per_block()} B a page")
    if engine.kv.kv.ssm.dtype.name != "float32":
        return f"the recurrent state is held in {engine.kv.kv.ssm.dtype}"
    width, held = experts(cfg)
    moe = spec.moe
    if moe["num_experts"] != width or moe.get("held", (0, width)) != held \
            or moe.get("score_func") is not None \
            or not moe.get("shared_gate") \
            or moe["top_k"] != cfg["num_experts_per_tok"]:
        return (f"the engine's routing is {moe}; the file says a softmax "
                f"router over {width}, top-{cfg['num_experts_per_tok']}, "
                f"renormalised, a gated shared expert, {held} held")
    if spec.tied_lm_head:
        return "the head is tied"
    if any(s not in (None, 1.0) for s in (
            spec.embed_scale, spec.residual_scale, spec.logits_scale,
            spec.attn_scale)):
        return "a multiplier the family does not have is on"
    return ""


def _routers(engine):
    """The router matrix of every layer of the engine."""
    from deepspeed_tpu.inference.v2.ragged_model import _layer_stacks
    for stack in _layer_stacks(engine.weights["layers"]):
        if "moe" in stack:
            yield from stack["moe"]["router"]


def held_touched_share(engine, x, rows_a_step: int) -> float:
    """Of the experts held here, the share that a step of ``rows_a_step``
    rows reaches, a layer, in the mean over the layers and over ``x``'s ``[T,
    hidden]`` rows taken ``rows_a_step`` at a time (the engine's own router,
    the published top-k over all experts)."""
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
