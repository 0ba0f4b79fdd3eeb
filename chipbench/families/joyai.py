"""The joyai family (JoyAI-LLM-Flash) for the benchmark: from a configuration
file to the program's model, and the program's weights under the names of the
plain reference (``chipbench/reference/joyai_ref.py``).

A family module is found by the configuration's ``family`` key
(``chipbench/families/<family>.py``). This one gives the serving bring-up of
``drivers/serve_closed_latent.py``: ``REFERENCE``, ``build_model``,
``init_params`` (the weights a layer at a time), ``reference_hp``,
``reference_weights``, ``page_layout`` (what a token holds in the pool: a
latent row a layer, no key/value pair), ``check_engine``,
which holds the engine to the configuration, ``router_readings`` (its router,
by itself, against the reference's) and ``held_touched_share``.

The configuration file's ``n_routed_experts`` counts the experts HELD here
(one chip's share: ``deployment.held_first`` on); the router's width is
``published.n_routed_experts`` where the file has one.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

#: module under chipbench/reference with forward_logits(weights, ids, hp,
#: rows=, with_margin=, act_dtype=)
REFERENCE = "joyai_ref"

MODEL_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
              "moe_intermediate_size", "num_hidden_layers",
              "first_k_dense_replace", "moe_layer_freq",
              "num_attention_heads", "num_key_value_heads", "q_lora_rank",
              "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
              "v_head_dim", "max_position_embeddings", "rope_theta",
              "rms_norm_eps", "n_shared_experts", "num_experts_per_tok",
              "norm_topk_prob", "routed_scaling_factor", "scoring_func",
              "topk_method", "n_group", "topk_group",
              "num_nextn_predict_layers")


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
    from deepspeed_tpu.models.joyai import JoyaiConfig, JoyaiForCausalLM
    if cfg.get("hidden_act", "silu") != "silu" or cfg.get(
            "tie_word_embeddings", False) or cfg.get("rope_scaling") \
            or cfg.get("attention_bias", False):
        raise ValueError("the reference covers SwiGLU, an untied head, "
                         "bias-free attention and unscaled rotary "
                         "frequencies only")
    keys = {k: cfg[k] for k in MODEL_KEYS if k in cfg}
    width, held = experts(cfg)
    return JoyaiForCausalLM(JoyaiConfig(
        **keys, n_routed_experts=width,
        experts_held=None if held[1] == width else held, dtype=dtype))


def init_params(model, seed: int, dtype):
    """Random weights from the seed in the tree ``model.init`` gives, made on
    the device a layer at a time: one small program a kind of layer (dense,
    MoE) and one for the embedding, the final norm and the head. The whole
    model's ``init`` is forty unrolled layers of random draws in ONE program,
    which the chip's compiler took 188 s over (my chip run, PR 33). The keys
    are of jax's ``rbg`` generator (the device's own random bits): the three
    programs compile in 14 s where the default counter-based generator's
    took 32 (compile, PR 33), and a seed still gives the same weights."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    from chipbench import models
    from deepspeed_tpu.models.joyai import JoyaiBlock, JoyaiForCausalLM
    from deepspeed_tpu.utils.tree import tree_cast

    cfg = model.config
    key = jax.random.wrap_key_data(
        jnp.tile(jax.random.key_data(models.jax_key(seed)), 2), impl="rbg")
    probe = jnp.zeros((1, 8), jnp.int32)
    x = jnp.zeros((1, 8, cfg.hidden_size), dtype)
    ends = JoyaiForCausalLM(dataclasses.replace(cfg, num_hidden_layers=0))
    params = dict(jax.jit(lambda k: tree_cast(
        ends.init(k, probe)["params"], dtype))(
            jax.random.fold_in(key, cfg.num_hidden_layers)))
    made = {}
    for i in range(cfg.num_hidden_layers):
        moe = cfg.is_moe_layer(i)
        if moe not in made:
            made[moe] = jax.jit(lambda k, i=i: tree_cast(
                JoyaiBlock(cfg, i).init(k, x, probe)["params"], dtype))
        params[f"layers_{i}"] = made[moe](jax.random.fold_in(key, i))
    return params


def page_layout(cfg: Dict[str, Any]) -> Dict[str, int]:
    """What the pool holds: ``layers`` layers of pages whose rows are
    ``latent_dim`` values a token — the latent and the one rotary key,
    padded to whole 128-lane tiles — and nothing per head."""
    row = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    return {"layers": cfg["num_hidden_layers"], "row_values": row,
            "latent_dim": -(-row // 128) * 128}


def reference_hp(cfg: Dict[str, Any]) -> Dict[str, Any]:
    width, held = experts(cfg)
    return {"num_heads": cfg["num_attention_heads"],
            "kv_lora_rank": cfg["kv_lora_rank"],
            "qk_nope_head_dim": cfg["qk_nope_head_dim"],
            "qk_rope_head_dim": cfg["qk_rope_head_dim"],
            "v_head_dim": cfg["v_head_dim"],
            "rope_theta": float(cfg["rope_theta"]),
            "eps": float(cfg["rms_norm_eps"]),
            "top_k": cfg["num_experts_per_tok"],
            "route_norm": bool(cfg["norm_topk_prob"]),
            "route_scale": float(cfg["routed_scaling_factor"]),
            "held": None if held[1] == width else held}


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
                 "ln_mlp": lp["post_attention_layernorm"]["weight"],
                 "wqa": attn["q_a_proj"]["kernel"],
                 "q_a_norm": attn["q_a_layernorm"]["weight"],
                 "wqb": attn["q_b_proj"]["kernel"],
                 "wkva": attn["kv_a_proj_with_mqa"]["kernel"],
                 "kv_a_norm": attn["kv_a_layernorm"]["weight"],
                 "wkvb": attn["kv_b_proj"]["kernel"],
                 "wo": attn["o_proj"]["kernel"]}
        if "gate" in mlp:
            layer.update(router=mlp["gate"]["kernel"],
                         expert_bias=mlp["e_score_correction_bias"],
                         w_gate=mlp["w_gate"], w_up=mlp["w_up"],
                         w_down=mlp["w_down"])
            if "shared_experts" in mlp:
                layer["shared"] = _swiglu(mlp["shared_experts"])
        else:
            layer.update(_swiglu(mlp))
        layers.append(layer)
    return {"embed": params["embed_tokens"]["embedding"], "layers": layers,
            "final_norm": params["norm"]["weight"],
            "lm_head": params["lm_head"]["kernel"]}


def check_engine(cfg: Dict[str, Any], engine) -> str:
    """What is wrong with the engine against the configuration, or ''."""
    spec, layout = engine.spec, page_layout(cfg)
    width, held = experts(cfg)
    if spec.mla is None:
        return "the engine does not run latent attention"
    pool = engine.kv.kv
    want = (layout["layers"], engine.kv.config.num_blocks,
            cfg["engine"]["kv_cache"]["block_size"], layout["latent_dim"])
    if tuple(pool.shape) != want:
        return f"the pool is {tuple(pool.shape)}, not latent rows {want}"
    dense = cfg["first_k_dense_replace"]
    got = [bool(k.moe) for k in spec.layer_kinds or ()]
    if got != [i >= dense for i in range(cfg["num_hidden_layers"])]:
        return f"the engine's MoE layers are {got}"
    if spec.moe["num_experts"] != width or spec.moe.get(
            "held", (0, width)) != held:
        return (f"the engine routes over {spec.moe['num_experts']} experts "
                f"and holds {spec.moe.get('held')}; the file says {width} "
                f"and {held}")
    stacks = engine.weights["layers"]
    stack = stacks[-1] if isinstance(stacks, tuple) else stacks
    if stack["moe"]["w_gate"].shape[1] != held[1] \
            or stack["moe"]["router"].shape[-1] != width:
        return "the expert stacks or the router have another width"
    return ""


def _moe_layers(engine):
    """(router matrix, selection bias) of every MoE layer of the engine."""
    from deepspeed_tpu.inference.v2 import ragged_model
    stacks = engine.weights["layers"]
    stacks = stacks if isinstance(stacks, tuple) else (stacks,)
    for (run, _, n), stack in zip(ragged_model.layer_runs(engine.spec),
                                  stacks):
        if run.moe is not None:
            for i in range(n):
                yield {k: stack["moe"][k][i]
                       for k in ("router", "expert_bias")}


def router_readings(engine, reference, hp: Dict[str, Any], x, below: float
                    ) -> Dict[str, float]:
    """The program's router by itself, on the device the engine runs on:
    ``ragged_model.moe_route`` (what every serving program's MoE layer
    calls) with the engine's own router matrix and selection bias of each
    MoE layer, against the reference's ``route`` on the same inputs ``x``
    ``[T, hidden]`` (bfloat16 values, so both sides see the same numbers),
    over ALL the router's experts, held or not.

    ``err`` is the largest difference between the two in any expert's
    routing weight for any token (a choice of another expert shows as the
    whole weight). ``control`` is the same reading of the reference against
    itself with its router computed in bfloat16, the smallest of the MoE
    layers' readings: it has to come out over the tolerance ``err`` is held
    to. Tokens whose gap between the last expert chosen and the first left
    out is under ``below`` are left out of both: there float32's own order
    of summation chooses."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.v2 import ragged_model

    spec = engine.spec
    top_k = spec.moe["top_k"]
    every = dict(hp, held=None)        # the margin between ANY two experts
    low = dict(every, router_dtype=jnp.bfloat16)

    @jax.jit
    def read(x, w):
        gates, ids = ragged_model.moe_route(x, w, top_k, spec.moe)
        got = jnp.sum(jax.nn.one_hot(ids, w["router"].shape[-1],
                                     dtype=jnp.float32)
                      * gates[..., None], axis=1)
        with jax.default_matmul_precision("highest"):
            want, margin, _ = reference.route(x.astype(jnp.float32), w, every)
            rounded, _, _ = reference.route(x.astype(jnp.float32), w, low)
        keep = (margin >= below)[:, None]
        return (jnp.max(jnp.abs(got - want) * keep),
                jnp.max(jnp.abs(rounded - want) * keep), jnp.sum(keep))

    err, control, rows = 0.0, float("inf"), 0
    for w in _moe_layers(engine):
        e, c, n = read(x, w)
        err, control, rows = max(err, float(e)), min(control, float(c)), \
            rows + int(n)
    return {"err": err, "control": control, "rows": rows}


def held_touched_share(engine, x, rows_a_step: int) -> float:
    """Of the experts held here, the share that a step of ``rows_a_step``
    rows reaches, a MoE layer, in the mean over the layers and over ``x``'s
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
    def share(x, w):
        _, ids = ragged_model.moe_route(x, w, spec.moe["top_k"], spec.moe)
        hit = jax.nn.one_hot(ids, width, dtype=jnp.float32)[
            ..., first:first + count].reshape(steps, -1, count)
        return jnp.mean(jnp.max(hit, axis=1))

    x = x[:steps * rows_a_step]
    shares = [float(share(x, w)) for w in _moe_layers(engine)]
    return sum(shares) / len(shares)
