"""The zaya family (Zyphra ZAYA1-8B) for the benchmark: from a configuration
file to the program's model, and the program's weights under the names of
the plain reference (``chipbench/reference/zaya_ref.py``).

A family module is found by the configuration's ``family`` key
(``chipbench/families/<family>.py``). This one gives the serving bring-up of
``drivers/serve_closed_state_moe.py``: ``REFERENCE``, ``build_model``,
``init_params`` (the weights a layer at a time), ``reference_hp``,
``reference_weights``, ``kv_layout`` (the pages' layers: every layer),
``state_layout`` (what a sequence holds beside its pages: a convolution tail
a layer and no recurrent state), ``check_engine``, which holds the engine to
the configuration, and ``held_touched_share`` with ``skip_share`` beside it.

Every layer is of one kind (``layer_types: "hybrid"``): compressed
convolutional attention, which writes pages AND keeps a tail, over 16
experts chosen top-1 (or none: the skip choice) by a router MLP whose state
goes from layer to layer. All 16 experts are held here.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

#: module under chipbench/reference with forward_variants(weights, ids, hp,
#: variants, rows=)
REFERENCE = "zaya_ref"

MODEL_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
              "num_attention_heads", "num_key_value_heads", "head_dim",
              "cca_time0", "cca_time1", "partial_rotary_factor", "hidden_act",
              "num_experts", "num_experts_per_tok", "moe_intermediate_size",
              "router_hidden_size", "rms_norm_eps", "max_position_embeddings",
              "tie_word_embeddings", "attention_bias", "lm_head_bias",
              "sliding_window", "layer_types")


def rope_theta(cfg: Dict[str, Any]) -> float:
    return float(cfg["rope_parameters"]["hybrid"]["rope_theta"])


def build_model(cfg: Dict[str, Any], dtype):
    """The program's flax module for configuration file ``cfg``."""
    from deepspeed_tpu.models.zaya import ZayaConfig, ZayaForCausalLM
    keys = {k: cfg[k] for k in MODEL_KEYS if k in cfg}
    # (the list is the published one, whole; the layers built are its first)
    keys["layer_types"] = tuple(keys["layer_types"])[
        :keys["num_hidden_layers"]]
    return ZayaForCausalLM(ZayaConfig(**keys, rope_theta=rope_theta(cfg),
                                      dtype=dtype))


def init_params(model, seed: int, dtype):
    """Random weights from the seed in the tree ``model.init`` gives, made on
    the device a layer at a time: one small program for a layer (every layer
    is of the one kind; only the first router's state scale is unused) and
    one for the embedding and the final norm, as ``families/granite.py`` does
    and for its reason. The keys are of jax's ``rbg`` generator; a seed still
    gives the same weights."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    from chipbench import models
    from deepspeed_tpu.models.zaya import ZayaForCausalLM, ZayaLayer
    from deepspeed_tpu.utils.tree import tree_cast

    cfg = model.config
    key = jax.random.wrap_key_data(
        jnp.tile(jax.random.key_data(models.jax_key(seed)), 2), impl="rbg")
    probe = jnp.zeros((1, 8), jnp.int32)
    x = jnp.zeros((1, 8, cfg.hidden_size), dtype)
    ends = ZayaForCausalLM(dataclasses.replace(
        cfg, num_hidden_layers=0, layer_types=None))
    params = dict(jax.jit(lambda k: tree_cast(
        ends.init(k, probe)["params"], dtype))(
            jax.random.fold_in(key, cfg.num_hidden_layers)))
    # (a layer past the first: the trees are the same, and so one program)
    make = jax.jit(lambda k: tree_cast(
        ZayaLayer(cfg, 1).init(k, x)["params"], dtype))
    for i in range(cfg.num_hidden_layers):
        params[f"layers_{i}"] = make(jax.random.fold_in(key, i))
    balance(params, cfg, jax.random.fold_in(key, cfg.num_hidden_layers + 1))
    return params


#: the share of a layer's tokens the balanced router sends past the experts
SKIP_TARGET = 0.03


def balance(params, cfg, key, rows: int = 4096, steps: int = 300,
            rate: float = 0.05) -> None:
    """Each layer's ``balancing_bias`` set as its name says: so that, on
    ``rows`` unit-normal rows, every expert is chosen as often as every
    other and the skip choice by ``SKIP_TARGET`` of the rows — the
    bias-only balancing a trained router's buffer comes from (``beta +=
    rate * (target - load)``, ``steps`` times; no gradient, no other weight
    touched), in place of a draw. A random router with a drawn bias favours
    a few experts, by how much depending on the seed: a 64-row step then
    reached 71-76% of the 16 experts and the seed's draw of that share moved
    the cell's tokens/s by 3% (PERF.md, PR 50). The probabilities are the
    reference's (``zaya_ref.router_probabilities``); layer ``l``'s router is
    handed the state layer ``l - 1``'s returned on rows of its own."""
    import jax
    import jax.numpy as jnp
    from chipbench.reference import zaya_ref

    E = cfg.num_experts
    f32 = jnp.float32
    hp = {"eps": float(cfg.rms_norm_eps)}
    target = jnp.concatenate([jnp.full((E,), (1 - SKIP_TARGET) / E, f32),
                              jnp.full((1,), SKIP_TARGET, f32)])

    @jax.jit
    def one(layer, r_in, key):
        with jax.default_matmul_precision("highest"):
            p, r = zaya_ref.router_probabilities(
                jax.random.normal(key, (rows, cfg.hidden_size), f32), layer,
                hp, r_in)

        def step(_, beta):
            load = jnp.mean(jax.nn.one_hot(jnp.argmax(p + beta, axis=-1),
                                           E + 1, dtype=f32), axis=0)
            return beta + rate * (target - load)

        return jax.lax.fori_loop(0, steps, step, jnp.zeros((E + 1,), f32)), r

    # (the first layer's router is handed zeros: gamma * 0 adds nothing)
    r = jnp.zeros((rows, cfg.router_hidden_size), f32)
    for i in range(cfg.num_hidden_layers):
        mlp = params[f"layers_{i}"]["mlp"]
        beta, r = one(_router_of(mlp), r, jax.random.fold_in(key, i))
        mlp["balancing_bias"] = beta.astype(mlp["balancing_bias"].dtype)


def _router_of(ff: Dict[str, Any]) -> Dict[str, Any]:
    """A layer's router under the reference's names."""
    return {"router_down": ff["router_down"]["kernel"],
            "router_down_b": ff["router_down"]["bias"],
            "gamma": ff["router_state_scale"],
            "router_norm": ff["router_norm"]["weight"],
            "router_fc1": ff["router_fc1"]["kernel"],
            "router_fc1_b": ff["router_fc1"]["bias"],
            "router_fc2": ff["router_fc2"]["kernel"],
            "router_fc2_b": ff["router_fc2"]["bias"],
            "router_out": ff["router_out"]["kernel"],
            "beta": ff["balancing_bias"]}


def kv_layout(cfg: Dict[str, Any]) -> Tuple[int, int, int]:
    """(layers, key/value heads, head size) of the paged cache: every layer
    attends."""
    return (cfg["num_hidden_layers"], cfg["num_key_value_heads"],
            cfg["head_dim"])


def state_layout(cfg: Dict[str, Any]) -> Dict[str, int]:
    """What a sequence holds beside its pages: per layer the tail of the two
    convolutions over q and k of every head and of the shifted value —
    ``cca_time0 + cca_time1 - 2`` taps over ``(Hq + Hk) d + d`` channels,
    padded to a multiple of 1,024, held in float32 — and no recurrent
    state."""
    d = cfg["head_dim"]
    conv_dim = (cfg["num_attention_heads"] + cfg["num_key_value_heads"]) * d
    taps = max(1, cfg["cca_time0"] + cfg["cca_time1"] - 2)
    width = -(-(conv_dim + d) // 1024) * 1024
    layers = cfg["num_hidden_layers"]
    return {"layers": layers, "conv_dim": conv_dim,
            "tail_channels": conv_dim + d, "taps": taps, "conv_width": width,
            "bytes_per_sequence": layers * 4 * taps * width}


def tail_order(cfg: Dict[str, Any]):
    """For each channel of the engine's tail, the published channel it
    holds: ``adapt_zaya`` interleaves the rotated values of each q and k
    head; the shifted value's channels follow in their own order."""
    import numpy as np
    from deepspeed_tpu.inference.v2.ragged_model import zaya_channel_order
    d = cfg["head_dim"]
    heads = cfg["num_attention_heads"] + cfg["num_key_value_heads"]
    order = zaya_channel_order(heads, d,
                               int(d * cfg["partial_rotary_factor"]))
    return tuple(int(i) for i in np.concatenate(
        [order, heads * d + np.arange(d)]))


def reference_hp(cfg: Dict[str, Any]) -> Dict[str, Any]:
    return {"num_heads": cfg["num_attention_heads"],
            "num_kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"],
            "rotary_dim": int(cfg["head_dim"] * cfg["partial_rotary_factor"]),
            "rope_theta": rope_theta(cfg),
            "eps": float(cfg["rms_norm_eps"]),
            "num_experts": cfg["num_experts"],
            "tail_order": tail_order(cfg)}


def reference_weights(params: Dict[str, Any], cfg: Dict[str, Any]
                      ) -> Dict[str, Any]:
    """The zoo's parameter tree under the reference's names (no copy; the
    tied head is a view of the embedding)."""
    layers = []
    for i in range(cfg["num_hidden_layers"]):
        lp = params[f"layers_{i}"]
        a, ff = lp["self_attn"], lp["mlp"]
        layers.append({
            "ln_in": lp["input_layernorm"]["weight"],
            "ln_ff": lp["post_attention_layernorm"]["weight"],
            "res_scale": lp["residual_scale"],
            "res_bias": lp["residual_bias"],
            "wq": a["q_proj"]["kernel"], "wk": a["k_proj"]["kernel"],
            "wv1": a["v_proj"]["kernel"], "wv2": a["v_prev_proj"]["kernel"],
            "conv0_w": a["conv0_weight"], "conv0_b": a["conv0_bias"],
            "conv1_w": a["conv1_weight"], "conv1_b": a["conv1_bias"],
            "temp": a["temp"], "wo": a["o_proj"]["kernel"],
            **_router_of(ff),
            "w_gate": ff["w_gate"], "w_up": ff["w_up"],
            "w_down": ff["w_down"]})
    embed = params["embed_tokens"]["embedding"]
    return {"embed": embed, "layers": layers,
            "final_norm": params["norm"]["weight"], "head": embed.T}


def check_engine(cfg: Dict[str, Any], engine) -> str:
    """What is wrong with the engine's layers and pools against the
    configuration's, or ''."""
    from deepspeed_tpu.inference.v2.ragged_model import (num_page_layers,
                                                         num_state_layers)
    spec = engine.spec
    L = cfg["num_hidden_layers"]
    want = state_layout(cfg)
    c = spec.cca or {}
    if spec.layer_kinds is not None or (
            num_page_layers(spec), num_state_layers(spec)) != (L, L):
        return ("not every layer of the engine holds pages and a tail: "
                f"{num_page_layers(spec)} hold pages, "
                f"{num_state_layers(spec)} a state slot, of {L}")
    if (c.get("time0"), c.get("time1"), c.get("conv_dim"),
            c.get("tail_channels"), c.get("taps")) != (
                cfg["cca_time0"], cfg["cca_time1"], want["conv_dim"],
                want["tail_channels"], want["taps"]):
        return f"the engine's convolutions are {c}, the file's state {want}"
    rd = int(cfg["head_dim"] * cfg["partial_rotary_factor"])
    if spec.rotary_dim != rd or spec.rope_theta != rope_theta(cfg) \
            or spec.window is not None or spec.norm_plus_one:
        return (f"rotation of {spec.rotary_dim} values at theta "
                f"{spec.rope_theta} (the file's: {rd}, {rope_theta(cfg)}), a "
                "window, or norms that scale by 1 + w")
    layers, heads, dim = kv_layout(cfg)
    kvc = engine.kv.config
    if (kvc.num_layers, kvc.num_kv_heads, kvc.head_dim) != (layers, heads, dim):
        return (f"the page pool has {kvc.num_layers} layers of {kvc.num_kv_heads}"
                f" x {kvc.head_dim}, the file's layers are {layers} of "
                f"{heads} x {dim}")
    sc = engine.state_config
    if sc is None or (sc.num_layers, sc.d_inner, sc.d_state, sc.d_conv,
                      sc.conv_dim) != (L, 0, 0, want["taps"] + 1,
                                       want["tail_channels"]):
        return f"the state pool is {sc}, the file's tails {want}"
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
    if engine.kv.kv.conv.dtype.name != "float32" or engine.kv.kv.ssm.size:
        return "the tails are not float32, or a recurrent state is held"
    moe = spec.moe
    if moe["num_experts"] != cfg["num_experts"] or moe["top_k"] != 1 \
            or moe.get("router") != "mlp" or not moe.get("skip") \
            or moe.get("router_hidden") != cfg["router_hidden_size"] \
            or "held" in moe:
        return (f"the engine's routing is {moe}; the file says an MLP router "
                f"of width {cfg['router_hidden_size']} over "
                f"{cfg['num_experts']} experts and a skip choice, top-1, "
                "every expert held")
    if not spec.tied_lm_head:
        return "the head is untied"
    if any(s not in (None, 1.0) for s in (
            spec.embed_scale, spec.residual_scale, spec.logits_scale,
            spec.attn_scale)):
        return "a multiplier the family does not have is on"
    return ""


def held_touched_share(engine, x, rows_a_step: int) -> float:
    """Of the 16 experts, the share that a step of ``rows_a_step`` rows
    reaches, a layer, in the mean over the layers and over ``x``'s ``[T,
    hidden]`` rows taken ``rows_a_step`` at a time — the engine's own
    routers, layer after layer, each handed the state the one before it
    returned (the same rows as every layer's input). Beside it the share of
    those choices that are the skip choice: logged, and
    ``serve/moe/skip_share`` in ``tracer.totals``."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.v2 import ragged_model
    from deepspeed_tpu.monitor.trace import tracer
    from deepspeed_tpu.utils.logging import log_dist

    spec = engine.spec
    E = spec.moe["num_experts"]
    steps = x.shape[0] // rows_a_step
    x = x[:steps * rows_a_step]
    moe = {k: v for k, v in engine.weights["layers"]["moe"].items()
           if k.startswith("router_")}

    @jax.jit
    def shares(x, moe):
        def layer(r, w):
            _, ids, r = ragged_model.moe_route_mlp(x, w, spec.moe, r,
                                                   spec.eps)
            hit = jax.nn.one_hot(ids[:, 0], E + 1, dtype=jnp.float32)
            touched = jnp.mean(jnp.max(
                hit[:, :E].reshape(steps, -1, E), axis=1))
            return r, (touched, jnp.mean(hit[:, E]))

        r0 = jnp.zeros((x.shape[0], spec.moe["router_hidden"]), jnp.float32)
        _, (touched, skipped) = jax.lax.scan(layer, r0, moe)
        return jnp.mean(touched), jnp.mean(skipped)

    touched, skipped = (float(v) for v in shares(x, moe))
    tracer.note("serve/moe/skip_share", skipped)
    log_dist(f"skip choice: {100 * skipped:.2f}% of {x.shape[0]} unit-normal "
             f"rows a layer in the mean over {spec.num_layers} layers "
             "(serve/moe/skip_share)", ranks=[0])
    return touched


def skip_share(engine) -> float:
    """``serve/moe/skip_share`` as :func:`held_touched_share` left it."""
    from deepspeed_tpu.monitor.trace import tracer
    return float(tracer.totals.get("serve/moe/skip_share", float("nan")))
