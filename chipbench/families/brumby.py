"""The brumby family (Manifest AI Brumby-14B-Base) for the benchmark: from a
configuration file to the program's model, and the program's weights under
the names of the plain reference (``chipbench/reference/brumby_ref.py``).

Found by the configuration's ``family`` key. It gives the serving bring-up of
``drivers/serve_closed_state.py`` (which ``drivers/serve_closed_slots.py``
runs): ``REFERENCE``, ``build_model``, ``reference_hp``,
``reference_weights``, ``kv_layout``, ``state_layout`` and ``check_engine``,
which holds the engine to the configuration — above all to holding NO page
but the scratch page.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import numpy as np

#: module under chipbench/reference with forward_logits(weights, ids, hp,
#: rows=, state_dtype=, act_dtype=, with_state=)
REFERENCE = "brumby_ref"

MODEL_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
              "num_hidden_layers", "num_attention_heads",
              "num_key_value_heads", "head_dim", "hidden_act",
              "attention_bias", "max_position_embeddings",
              "max_window_layers", "rms_norm_eps", "rope_theta",
              "rope_scaling", "sliding_window", "use_sliding_window",
              "tie_word_embeddings", "model_type")


def assumed(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """What the file assumes in numbers (``assumed_numbers``), under the
    program's names."""
    n = cfg["assumed_numbers"]
    return {"power": n["power"], "retention_eps": n["retention_eps"],
            "chunk_size": n["chunk_size"],
            "gate_init": tuple(tuple(r) for r in n["gate_init"])}


def build_model(cfg: Dict[str, Any], dtype):
    """The program's flax module for configuration file ``cfg``."""
    from deepspeed_tpu.models.brumby import BrumbyConfig, BrumbyForCausalLM
    keys = {k: cfg[k] for k in MODEL_KEYS if k in cfg}
    return BrumbyForCausalLM(BrumbyConfig(**keys, **assumed(cfg),
                                          dtype=dtype))


def kv_layout(cfg: Dict[str, Any]) -> Tuple[int, int, int]:
    """(layers, key/value heads, head size) of the paged cache: NO layer
    holds pages; the pool is the scratch page of one layer, which the
    programs' padding rows address."""
    return 1, cfg["num_key_value_heads"], cfg["head_dim"]


def state_layout(cfg: Dict[str, Any]) -> Dict[str, int]:
    """What a sequence holds: per layer ``Hk`` heads' ``S`` (``d`` rows each)
    and a normaliser a head (in whole eights) down the sublanes, the key's
    expansion (``d / 2 + 1`` tiles of ``d``) along the lanes, float32; no
    convolution tail."""
    Hk, d = cfg["num_key_value_heads"], cfg["head_dim"]
    N, D = Hk * d + -(-Hk // 8) * 8, d * (d // 2 + 1)
    layers = cfg["num_hidden_layers"]
    return {"layers": layers, "d_inner": D, "d_state": N, "d_conv": 1,
            "bytes_per_sequence": layers * 4 * N * D}


def expansion(cfg: Dict[str, Any]):
    """The order of the program's state entries in the REFERENCE's indexing
    of a head's values: the program interleaves a head's halves (value ``i``
    beside ``i + d / 2``, the ragged path's rotation), so its value ``c`` is
    the published value ``turn[c]``."""
    from deepspeed_tpu.ops.pallas.power_retention import expansion as pairs
    d = cfg["head_dim"]
    turn = np.arange(d).reshape(2, d // 2).T.reshape(-1)
    i, j, m = pairs(d)
    return turn[i].astype(np.int32), turn[j].astype(np.int32), m


def reference_hp(cfg: Dict[str, Any]) -> Dict[str, Any]:
    return {"num_heads": cfg["num_attention_heads"],
            "num_kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"], "eps": float(cfg["rms_norm_eps"]),
            "rope_theta": float(cfg["rope_theta"]),
            "retention_eps": float(assumed(cfg)["retention_eps"]),
            "expansion": expansion(cfg)}


def reference_weights(params: Dict[str, Any], cfg: Dict[str, Any]
                      ) -> Dict[str, Any]:
    """The zoo's parameter tree under the reference's names (no copy)."""
    layers = []
    for i in range(cfg["num_hidden_layers"]):
        lp = params[f"layers_{i}"]
        a, ff = lp["self_attn"], lp["mlp"]
        layers.append({
            "ln_in": lp["input_layernorm"]["weight"],
            "ln_ff": lp["post_attention_layernorm"]["weight"],
            "wq": a["q_proj"]["kernel"], "wk": a["k_proj"]["kernel"],
            "wv": a["v_proj"]["kernel"], "wg": a["g_proj"]["kernel"],
            "b_g": a["g_bias"], "q_norm": a["q_norm"]["weight"],
            "k_norm": a["k_norm"]["weight"], "wo": a["o_proj"]["kernel"],
            "w_gate": ff["gate_proj"]["kernel"],
            "w_up": ff["up_proj"]["kernel"],
            "w_down": ff["down_proj"]["kernel"]})
    return {"embed": params["embed_tokens"]["embedding"], "layers": layers,
            "final_norm": params["norm"]["weight"],
            "head": params["lm_head"]["kernel"]}


def gate_spread(engine) -> Dict[str, Tuple[float, float]]:
    """The range of ``g = sigmoid(b_g)`` over the layers' slow (even) and
    fast (odd) KV heads, as the engine holds the bias."""
    b = np.asarray(engine.weights["layers"]["pr"]["g_bias"], np.float32)
    g = 1.0 / (1.0 + np.exp(-b))
    return {"slow": (float(g[:, 0::2].min()), float(g[:, 0::2].max())),
            "fast": (float(g[:, 1::2].min()), float(g[:, 1::2].max()))}


def check_engine(cfg: Dict[str, Any], engine) -> str:
    """What is wrong with the engine's layers and pools against the
    configuration's, or ''."""
    spec = engine.spec
    if spec.layer_kinds is not None:
        return "the engine runs layers of several kinds"
    m = spec.mamba or {}
    want = state_layout(cfg)
    extra = assumed(cfg)
    if m.get("kind") != "pr" or m.get("chunk") != extra["chunk_size"] \
            or m.get("eps") != extra["retention_eps"]:
        return (f"the engine's recurrence is {m}, the file's power retention "
                f"in chunks of {extra['chunk_size']}")
    if spec.rope_theta != cfg["rope_theta"] or spec.rotary_dim is not None \
            or spec.window is not None or spec.moe is not None:
        return ("no rotation of the whole head at the file's theta, a "
                "window, or routed experts")
    # no layer holds pages: the pool is its scratch page, the allocator hands
    # out nothing and the scheduler funds no block
    kvc = engine.kv.config
    if (kvc.num_layers, kvc.num_blocks) != (1, 1) \
            or engine.allocator.total_blocks != 0 \
            or not engine.scheduler.pageless:
        return (f"the page pool has {kvc.num_layers} layers of "
                f"{kvc.num_blocks} pages and the allocator "
                f"{engine.allocator.total_blocks}: a model that holds no "
                "pages has the scratch page alone")
    sc = engine.state_config
    if sc is None or (sc.num_layers, sc.d_inner, sc.d_state, sc.d_conv) != (
            want["layers"], want["d_inner"], want["d_state"], want["d_conv"]):
        return f"the state pool is {sc}, the file's state {want}"
    if sc.bytes_per_slot() != want["bytes_per_sequence"] \
            or engine.kv.kv.conv.size:
        return "a state slot's bytes are not the file's, or it keeps a tail"
    # (off the chip the rehearsal's widths are laid over the file: the
    # account's numbers are the chip's)
    numbers = None if "rehearsal_hbm_bytes" in cfg \
        else cfg.get("memory_account_numbers")
    if numbers and (
            sc.bytes_per_slot() != numbers["state_bytes_a_sequence"]
            or sc.num_slots + 1 != numbers["state_slots"]):
        return ("the engine's slots are not the memory account's: "
                f"{sc.bytes_per_slot()} B a slot x {sc.num_slots + 1}")
    if engine.kv.kv.ssm.dtype.name != "float32":
        return f"the state is held in {engine.kv.kv.ssm.dtype}"
    if spec.tied_lm_head:
        return "the head is tied"
    spread, (slow, fast) = gate_spread(engine), extra["gate_init"]
    if not (slow[0] - 1e-3 <= spread["slow"][0]
            and spread["slow"][1] <= slow[1] + 1e-3
            and fast[0] - 1e-2 <= spread["fast"][0]
            and spread["fast"][1] <= fast[1] + 1e-2):
        return (f"the gates' biases give g in {spread}, the file's ranges "
                f"are {slow} (even heads) and {fast} (odd heads)")
    return ""
