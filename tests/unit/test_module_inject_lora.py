"""LoRA adapter checkpoint loading (module_inject/lora.py): validation
refusals pinned against the base model's spec, the rank-slice page packing
(alpha/rank folded into B, absent targets zero, per-layer leaves), and the
registry's duplicate-name semantics through ``load_lora_adapter``.
docs/SERVING.md "Multi-tenant LoRA" describes the surface under test."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
from deepspeed_tpu.inference.v2.model_spec import RaggedModelSpec
from deepspeed_tpu.inference.v2.ragged_model import lora_page_layout
from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from deepspeed_tpu.module_inject.lora import (load_lora_adapter,
                                              pack_lora_pages,
                                              validate_lora_adapter)

SPEC = RaggedModelSpec(family="llama", num_layers=2, hidden_size=8,
                       num_heads=2, num_kv_heads=2, head_dim=4,
                       vocab_size=64, dtype=jnp.float32)
TARGETS = ("q", "v")     # both projections are [8, 8] under SPEC


def _pair(din=8, dout=8, r=2, seed=0):
    g = np.random.RandomState(seed)
    return {"A": g.standard_normal((din, r)).astype(np.float32),
            "B": g.standard_normal((r, dout)).astype(np.float32)}


# --------------------------------------------------------------------------- #
# validation: every refusal message is part of the API (load-time loudness)
# --------------------------------------------------------------------------- #

def test_valid_adapter_returns_rank():
    state = {"q": _pair(r=3), "v": _pair(r=3, seed=1)}
    assert validate_lora_adapter(SPEC, TARGETS, state) == 3


def test_empty_state_is_a_valid_rank0_adapter():
    assert validate_lora_adapter(SPEC, TARGETS, {}) == 0
    assert pack_lora_pages(SPEC, TARGETS, {}) is None


def test_untargeted_projection_refused():
    # "o" is a real projection, just not one this engine applies deltas to —
    # silently dropping it would serve the wrong model
    with pytest.raises(ValueError, match="applies LoRA to"):
        validate_lora_adapter(SPEC, TARGETS, {"o": _pair()})


def test_missing_ab_pair_refused():
    with pytest.raises(ValueError, match="the PEFT layout"):
        validate_lora_adapter(SPEC, TARGETS, {"q": {"A": _pair()["A"]}})


def test_a_shape_mismatch_refused():
    state = {"q": _pair(din=7)}
    with pytest.raises(ValueError, match="shape/sharding mismatch"):
        validate_lora_adapter(SPEC, TARGETS, state)


def test_b_shape_mismatch_refused():
    state = {"q": _pair(dout=9)}
    with pytest.raises(ValueError, match="shape/sharding mismatch"):
        validate_lora_adapter(SPEC, TARGETS, state)


def test_ab_rank_mismatch_refused():
    state = {"q": {"A": _pair(r=2)["A"], "B": _pair(r=3)["B"]}}
    with pytest.raises(ValueError, match="A rank 2 != B rank 3"):
        validate_lora_adapter(SPEC, TARGETS, state)


def test_inconsistent_ranks_across_targets_refused():
    state = {"q": _pair(r=2), "v": _pair(r=3, seed=1)}
    with pytest.raises(ValueError, match="one adapter, one rank"):
        validate_lora_adapter(SPEC, TARGETS, state)


def test_rank_past_max_rank_refused():
    state = {"q": _pair(r=5)}
    with pytest.raises(ValueError, match="program grid stops there"):
        validate_lora_adapter(SPEC, TARGETS, state, max_rank=4)
    # at the edge is fine — the warmup ladder covers it
    assert validate_lora_adapter(SPEC, TARGETS, state, max_rank=5) == 5


def test_per_layer_leaves_need_matching_leading_axis():
    L = SPEC.num_layers
    g = np.random.RandomState(2)
    ok = {"q": {"A": g.standard_normal((L, 8, 2)).astype(np.float32),
                "B": g.standard_normal((L, 2, 8)).astype(np.float32)}}
    assert validate_lora_adapter(SPEC, TARGETS, ok) == 2
    mixed = {"q": {"A": ok["q"]["A"], "B": ok["q"]["B"][0]}}
    with pytest.raises(ValueError, match="leading axis on BOTH"):
        validate_lora_adapter(SPEC, TARGETS, mixed)
    wrong_l = {"q": {"A": ok["q"]["A"][:1], "B": ok["q"]["B"][:1]}}
    with pytest.raises(ValueError, match="leading axis on BOTH"):
        validate_lora_adapter(SPEC, TARGETS, wrong_l)


# --------------------------------------------------------------------------- #
# packing: page j = A column j + (alpha/rank-scaled) B row j, all layers
# --------------------------------------------------------------------------- #

def test_pack_layout_and_alpha_fold():
    state = {"q": _pair(r=2, seed=3), "alpha": 4.0}
    pages = pack_lora_pages(SPEC, TARGETS, state)
    elements, in_max, out_max = lora_page_layout(SPEC, TARGETS)
    assert pages.shape == (2, elements)
    L, nproj = SPEC.num_layers, len(TARGETS)
    grid = pages.reshape(2, L, nproj, in_max + out_max)
    a, b = state["q"]["A"], state["q"]["B"]
    for j in range(2):
        for layer in range(L):     # flat leaves = same delta every layer
            assert np.array_equal(grid[j, layer, 0, :8], a[:, j])
            # alpha/rank (= 4/2) folded into B exactly once at pack time
            assert np.allclose(grid[j, layer, 0, in_max:in_max + 8],
                               b[j] * 2.0)
    # the absent target ("v") stays an exact-zero delta
    assert not grid[:, :, 1, :].any()


def test_pack_per_layer_leaves_differ_by_layer():
    L = SPEC.num_layers
    g = np.random.RandomState(4)
    a = g.standard_normal((L, 8, 1)).astype(np.float32)
    b = g.standard_normal((L, 1, 8)).astype(np.float32)
    pages = pack_lora_pages(SPEC, TARGETS, {"q": {"A": a, "B": b}})
    elements, in_max, out_max = lora_page_layout(SPEC, TARGETS)
    grid = pages.reshape(1, L, len(TARGETS), in_max + out_max)
    for layer in range(L):
        assert np.array_equal(grid[0, layer, 0, :8], a[layer, :, 0])
        assert np.allclose(grid[0, layer, 0, in_max:in_max + 8], b[layer, 0])


# --------------------------------------------------------------------------- #
# load_lora_adapter: the engine-facing surface + duplicate-name semantics
# --------------------------------------------------------------------------- #

def _engine_state(engine, rank, seed, scale=0.02):
    spec = engine.spec
    douts = {"q": spec.num_heads * spec.head_dim,
             "k": spec.num_kv_heads * spec.head_dim,
             "v": spec.num_kv_heads * spec.head_dim,
             "o": spec.hidden_size}
    g = np.random.RandomState(seed)
    state = {"alpha": float(rank)}
    for t in engine.config.lora.targets:
        state[t] = {"A": (g.standard_normal((spec.hidden_size, rank))
                          * scale).astype(np.float32),
                    "B": (g.standard_normal((rank, douts[t]))
                          * scale).astype(np.float32)}
    return state


@pytest.fixture(scope="module")
def lora_engine():
    """One unwarmed engine with the adapter registry enabled (these tests
    exercise registration, never decode, so no programs are needed)."""
    cfg = LlamaConfig.tiny(vocab_size=128)
    model = LlamaForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        {"input_ids": jnp.zeros((1, 8), jnp.int32)})["params"]
    econf = {"dtype": jnp.float32,
             "state_manager": {"max_tracked_sequences": 4,
                               "max_ragged_sequence_count": 4,
                               "max_ragged_batch_size": 32,
                               "max_context": 128},
             "kv_cache": {"block_size": 16},
             "lora": {"enabled": True, "pool_pages": 8, "max_rank": 4,
                      "swap_buffers": 8}}
    return InferenceEngineV2(model=model, model_parameters=params,
                             config=econf)


def test_load_refuses_engine_without_registry():
    class _Plain:
        lora = None

    with pytest.raises(RuntimeError, match="no LoRA registry"):
        load_lora_adapter(_Plain(), "x", {})


def test_load_and_rank0_register(lora_engine):
    e = lora_engine
    assert load_lora_adapter(e, "mj-r2", _engine_state(e, 2, seed=0)) == 2
    assert e.lora.rank("mj-r2") == 2
    # rank-0 adapters register too: no pages, trivially resident
    assert load_lora_adapter(e, "mj-zero", {}) == 0
    assert e.lora.is_resident("mj-zero")
    e.lora.unregister("mj-zero")
    e.lora.unregister("mj-r2")


def test_duplicate_name_semantics(lora_engine):
    e = lora_engine
    state = _engine_state(e, 2, seed=1)
    load_lora_adapter(e, "mj-dup", state)
    # identical payload: idempotent re-register
    load_lora_adapter(e, "mj-dup", state)
    assert e.lora.names.count("mj-dup") == 1
    other = _engine_state(e, 3, seed=2)
    e.lora.acquire(7001, "mj-dup")
    try:
        with pytest.raises(ValueError,
                           match="must wait until they finish"):
            load_lora_adapter(e, "mj-dup", other)
        assert e.lora.rank("mj-dup") == 2      # old payload untouched
    finally:
        e.lora.release(7001)
    # idle now: a different payload replaces in place
    load_lora_adapter(e, "mj-dup", other)
    assert e.lora.rank("mj-dup") == 3
    e.lora.unregister("mj-dup")


def test_registry_rejects_foreign_payload_shape(lora_engine):
    e = lora_engine
    with pytest.raises(ValueError, match="page layout"):
        e.lora.register("mj-bad", np.zeros((2, 5), np.float32))
