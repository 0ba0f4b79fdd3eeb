"""afmoe (Arcee Trinity) through InferenceEngineV2: layers of several kinds
in one model — window+rotary beside full attention without positions, dense
beside MoE feed-forward — gated attention, the sigmoid router and the shared
expert, against the plain reference ``chipbench/reference/afmoe_ref.py``."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from deepspeed_tpu.inference.v2.adapters import adapt_model  # noqa: E402
from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2  # noqa: E402
from deepspeed_tpu.inference.v2.model_spec import (  # noqa: E402
    LayerKind, RaggedModelSpec, layer_runs)
from deepspeed_tpu.inference.v2.ragged_model import _scan_layers  # noqa: E402
from deepspeed_tpu.models.afmoe import (FULL, SLIDING, AfmoeConfig,  # noqa: E402
                                        AfmoeForCausalLM)

ENGINE = {"dtype": "float32",
          "state_manager": {"max_context": 128, "max_tracked_sequences": 4,
                            "max_ragged_sequence_count": 4,
                            "max_ragged_batch_size": 4 + 2 * 16,
                            "prefill_chunk_size": 16},
          "kv_cache": {"block_size": 8, "num_blocks": 64}}
#: float32 engine against the float32 reference: what is left is the order
#: of summation in the kernels (online softmax by page, grouped GEMM by
#: expert), a few float32 ulps through four layers. A wrong kind of layer, a
#: dropped gate or a misweighted expert is 1e-2 and more
#: (tests/chipbench/test_afmoe_reference.py)
TOL = 2e-4
WINDOW = 8          # every context below is longer


def build(seed=0, **kw):
    cfg = AfmoeConfig.tiny(dtype=jnp.float32, **kw)
    model = AfmoeForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(seed),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return cfg, model, params


def reference_logits(cfg, params, ids):
    from chipbench.harness import Registry
    from chipbench.reference import afmoe_ref
    fam = Registry().module("families", "afmoe")
    d = {k: getattr(cfg, k) for k in fam.MODEL_KEYS}
    return np.asarray(afmoe_ref.forward_logits(
        fam.reference_weights(params, d), jnp.asarray(ids),
        fam.reference_hp(d)))


@pytest.fixture(scope="module")
def served():
    """One engine run: packed prefill, paged chunk prefill, four tokens
    through the cache, then the fused decode step on a second sequence."""
    cfg, model, params = build()
    ids = np.random.default_rng(0).integers(0, 256, 40).astype(np.int32)
    want = reference_logits(cfg, params, ids)
    eng = InferenceEngineV2(model=model, model_parameters=params,
                            config=dict(ENGINE))
    got = {"packed": (eng.put([1], [ids[:24]])[0], want[23]),
           "paged_chunk": (eng.put([1], [ids[24:36]])[0], want[35])}
    for i in range(36, 40):
        got[f"decode_{i}"] = (eng.put([1], [ids[i:i + 1]])[0], want[i])
    eng.flush([1])
    eng.put([2], [ids[:36]])
    got["fused_tokens"] = (eng.decode_pipeline([2]).run(1)[0],
                           [int(np.argmax(want[35]))])
    return eng, got


@pytest.mark.parametrize("phase", ["packed", "paged_chunk", "decode_36",
                                   "decode_37", "decode_38", "decode_39"])
def test_engine_logits_match_the_reference(served, phase):
    got, want = served[1][phase]
    assert np.max(np.abs(got - want)) <= TOL * np.max(np.abs(want))


def test_fused_decode_step_gives_the_reference_token(served):
    got, want = served[1]["fused_tokens"]
    assert [int(t) for t in got] == want


def _faulty_route(fault):
    """``ragged_model.moe_route`` for the sigmoid router, with one fault."""
    def route(x, w, top_k, routing=None):
        logits = x.astype(jnp.float32) @ w["router"].astype(jnp.float32)
        if fault == "bfloat16":
            logits = logits.astype(jnp.bfloat16)
        scores = jax.nn.sigmoid(logits).astype(jnp.float32)
        top, ids = jax.lax.top_k(scores + w["expert_bias"], top_k)
        gates = top if fault == "bias_weighs" \
            else jnp.take_along_axis(scores, ids, axis=-1)
        gates = gates / gates.sum(axis=-1, keepdims=True)
        return gates * routing["route_scale"], ids
    return route


@pytest.mark.parametrize("fault", [None, "bfloat16", "bias_weighs"])
def test_router_by_itself_is_held_to_the_reference(served, fault, monkeypatch):
    """What the benchmark's check on the chip runs beside the logits (whose
    rows with a small routing margin are not compared): the program's router
    alone, on the engine's own router weights, against the reference's."""
    from chipbench.harness import Registry
    from chipbench.reference import afmoe_ref
    from deepspeed_tpu.inference.v2 import ragged_model
    fam = Registry().module("families", "afmoe")
    eng = served[0]
    cfg = AfmoeConfig.tiny(dtype=jnp.float32)
    hp = fam.reference_hp({k: getattr(cfg, k) for k in fam.MODEL_KEYS})
    tol = 2e-4      # check.tol_router of the afmoe configuration
    x = jnp.asarray(np.random.default_rng(3).standard_normal(
        (256, cfg.hidden_size)), jnp.bfloat16)
    if fault:
        monkeypatch.setattr(ragged_model, "moe_route", _faulty_route(fault))
    got = fam.router_readings(eng, afmoe_ref, hp, x, tol)
    assert got["rows"] > 3 * 200 and got["control"] > tol
    assert (got["err"] > tol) == bool(fault), got


def test_mixed_kinds_keep_whole_context_pages(served):
    eng = served[0]
    kinds = eng.spec.layer_kinds
    assert kinds == (LayerKind(WINDOW, True, False), LayerKind(WINDOW, True, True),
                     LayerKind(WINDOW, True, True), LayerKind(None, False, True))
    assert eng.spec.window is None and eng.scheduler.ring_pages is None
    assert [(n, l0) for _, l0, n in layer_runs(eng.spec)] == [
        (1, 0), (2, 1), (1, 3)]
    assert isinstance(eng.weights["layers"], tuple) \
        and len(eng.weights["layers"]) == 3


def test_all_window_model_is_one_kind_with_the_ring():
    """Every layer sliding and sparse: the adapter reports one kind through
    the scalar fields, the page ring engages, and the logits still match."""
    cfg, model, params = build(seed=1, num_dense_layers=0,
                               layer_types=(SLIDING,) * 4)
    eng = InferenceEngineV2(model=model, model_parameters=params,
                            config=dict(ENGINE))
    assert eng.spec.layer_kinds is None and eng.spec.window == WINDOW
    assert eng.scheduler.ring_pages is not None
    assert len(layer_runs(eng.spec)) == 1
    assert not isinstance(eng.weights["layers"], tuple)
    ids = np.random.default_rng(2).integers(0, 256, 30).astype(np.int32)
    want = reference_logits(cfg, params, ids)
    eng.put([1], [ids[:29]])
    got = eng.put([1], [ids[29:]])[0]
    assert np.max(np.abs(got - want[29])) <= TOL * np.max(np.abs(want))


@pytest.mark.parametrize("feature", ["prefix_cache", "spec_decode"])
def test_window_refusals_are_the_rings(feature):
    """Both refusals belong to the page ring: a model of mixed kinds has no
    ring and is not refused; one whose every layer is windowed is, and the
    message says so."""
    extra = {"prefix_cache": {"prefix_cache": {"enabled": True}},
             "spec_decode": {"spec_decode": {"enabled": True, "k": 2}}}[feature]
    _, model, params = build()
    InferenceEngineV2(model=model, model_parameters=params,
                      config={**ENGINE, **extra})
    _, model, params = build(num_dense_layers=0, layer_types=(SLIDING,) * 4)
    with pytest.raises(NotImplementedError,
                       match=r"every layer is windowed \(8 tokens\)"):
        InferenceEngineV2(model=model, model_parameters=params,
                          config={**ENGINE, **extra})


def _count_scans(spec, stacks):
    seen = []

    def make_body(rs, experts, l0):
        def body(carry, scanned):
            w, l = scanned
            seen.append((rs.window, rs.rope_theta is not None,
                         rs.moe is not None, l0))
            return carry + w["a"] * (l - l0 + 1), None
        return body

    def loop(s):
        return _scan_layers(spec, s, make_body, jnp.float32(0.0))

    total = float(jax.jit(lambda s: loop(s))(stacks))
    seen.clear()
    jaxpr = jax.make_jaxpr(lambda s: loop(s))(stacks)
    n = sum(1 for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan")
    return n, seen, total


RUNS = {
    "one_kind": (None, [4]),
    "one_kind_windowed": (None, [4]),
    "dense_then_moe": ((LayerKind(8, True, False),) * 2
                       + (LayerKind(8, True, True),) * 2, [2, 2]),
    "three_kinds": ((LayerKind(8, True, False), LayerKind(8, True, True),
                     LayerKind(8, True, True), LayerKind(None, False, True)),
                    [1, 2, 1]),
    # kinds that alternate are ONE scan over the repeating pair (a unit of
    # layer_units), not a scan a layer
    "alternating": ((LayerKind(8, True, True), LayerKind(None, False, True))
                    * 2, [(2, 2)]),
}


@pytest.mark.parametrize("case", list(RUNS))
def test_layer_loop_scans_each_run_of_equal_kinds_once(case):
    kinds, sizes = RUNS[case]
    spec = RaggedModelSpec(
        family="t", num_layers=4, hidden_size=8, num_heads=1, num_kv_heads=1,
        head_dim=8, vocab_size=8, moe={"num_experts": 2, "top_k": 1},
        window=8 if case == "one_kind_windowed" else None, layer_kinds=kinds)
    stacks = tuple({"a": jnp.ones((n,), jnp.float32)} if isinstance(n, int)
                   else tuple({"a": jnp.ones((n[1],), jnp.float32)}
                              for _ in range(n[0])) for n in sizes)
    if kinds is None:
        stacks = stacks[0]
    n, seen, total = _count_scans(spec, stacks)
    assert n == len(sizes)                  # one scan a run, as before for one
    if case == "alternating":
        # the pair's body is traced once: each of its two layers under its
        # own kind, and each at place i of its own stack in repeat i
        assert [s[:3] for s in seen] == [tuple(k) for k in kinds[:2]]
        assert total == 2 * (1 + 2)
        return
    assert [s[3] for s in seen] == list(np.cumsum([0] + sizes[:-1]))
    # each layer of a run is handed its index in the run's own stacks
    assert total == sum(n * (n + 1) / 2 for n in sizes)
    if kinds is not None:
        assert [s[:3] for s in seen] == [tuple(kinds[l0]) for _, l0, _ in
                                         layer_runs(spec)]
    else:
        assert seen[0][:3] == (spec.window, True, True)


def test_one_kind_families_adapt_as_before():
    """Mistral keeps its scalar window, Mixtral its one stacked tree."""
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from deepspeed_tpu.models.mixtral import MixtralConfig, MixtralForCausalLM
    probe = {"input_ids": jnp.zeros((1, 8), jnp.int32)}
    cfg = LlamaConfig.tiny(dtype=jnp.float32, sliding_window=8)
    params = LlamaForCausalLM(cfg).init(jax.random.PRNGKey(0), probe)["params"]
    spec, weights = adapt_model("mistral", params, cfg, max_context=64)
    assert spec.layer_kinds is None and spec.window == 8
    assert layer_runs(spec) == [(spec, 0, cfg.num_hidden_layers)]
    cfg = MixtralConfig.tiny(dtype=jnp.float32)
    params = MixtralForCausalLM(cfg).init(jax.random.PRNGKey(0),
                                          probe)["params"]
    spec, weights = adapt_model("mixtral", params, cfg)
    assert spec.layer_kinds is None and isinstance(weights["layers"], dict)
    assert weights["layers"]["moe"]["w_gate"].shape[:2] == (
        cfg.num_hidden_layers, cfg.num_local_experts)


def test_window_dead_tokens_by_hand():
    """Three windowed layers and one full, window 8: a 20-token and a
    5-token sequence hold (20 + 5) x 4 tokens x layers, of which the
    windowed layers will never read 3 x (20 - 8) again."""
    _, model, params = build()
    eng = InferenceEngineV2(model=model, model_parameters=params,
                            config=dict(ENGINE))
    assert eng.kv_window_dead_tokens() == (0, 0)
    rng = np.random.default_rng(3)
    eng.put([1, 2], [rng.integers(0, 256, 20).astype(np.int32),
                     rng.integers(0, 256, 5).astype(np.int32)])
    assert eng.kv_window_dead_tokens() == (3 * 12, 4 * 25)
    eng.flush([1])
    assert eng.kv_window_dead_tokens() == (0, 4 * 5)


def test_window_dead_tokens_under_the_ring():
    """Every layer windowed: a sequence holds at most its ring, and what is
    dead is the ring's slack over the window."""
    _, model, params = build(num_dense_layers=0, layer_types=(SLIDING,) * 4)
    eng = InferenceEngineV2(model=model, model_parameters=params,
                            config=dict(ENGINE))
    cap = eng.scheduler.ring_pages * 8
    ids = np.random.default_rng(4).integers(0, 256, cap + 16).astype(np.int32)
    eng.put([1], [ids])
    assert eng.kv_window_dead_tokens() == (4 * (cap - WINDOW), 4 * cap)


def test_config_derives_layer_types_and_refuses_a_bad_list():
    cfg = AfmoeConfig.tiny(num_hidden_layers=8, global_attn_every_n_layers=4)
    assert cfg.layer_types == (SLIDING, SLIDING, SLIDING, FULL) * 2
    assert not cfg.is_moe_layer(0) and cfg.is_moe_layer(1)
    with pytest.raises(ValueError, match="layer_types has 2 entries"):
        AfmoeConfig.tiny(layer_types=(SLIDING, FULL))
    with pytest.raises(ValueError, match="unknown layer_types"):
        AfmoeConfig.tiny(layer_types=(SLIDING, "chunked", SLIDING, FULL))


def test_gpt_neo_refusal_names_what_still_blocks_it():
    with pytest.raises(ValueError, match="unscaled attention scores"):
        adapt_model("gpt_neo", {}, None)
