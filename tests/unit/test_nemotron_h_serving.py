"""Nemotron-H (``nemotron_h``; Nemotron 3 Nano) through InferenceEngineV2:
layers that are ONE block each — a Mamba-2 mixer with groups of B and C, OR
two-matrix relu^2 experts behind a sigmoid router with a selection bias and a
shared expert, OR attention without positions — scanned as repeating units.
Against the plain reference ``chipbench/reference/nemotron_h_ref.py`` through
the packed pass, the paged passes, single tokens through the cache and the
fused decode step; the shares of the experts; units against one-layer scans;
what the spec says of pools and kinds."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from deepspeed_tpu.inference.v2 import (  # noqa: E402
    adapters, model_spec as ms, ragged_model as rm)
from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2  # noqa: E402
from deepspeed_tpu.models.nemotron_h import (NemotronHConfig,  # noqa: E402
                                             NemotronHForCausalLM)
from deepspeed_tpu.monitor.trace import tracer  # noqa: E402

#: 2 chunk slots of 16 rows a pass (32 tokens), pages of 16, 4 decode rows
ENGINE = {"dtype": "float32",
          "state_manager": {"max_context": 256, "max_tracked_sequences": 4,
                            "max_ragged_sequence_count": 4,
                            "max_ragged_batch_size": 4 + 2 * 16,
                            "prefill_chunk_size": 16},
          "kv_cache": {"block_size": 16, "num_blocks": 64}}
#: float32 engine against the float32 reference: what is left is the order
#: of summation; a dropped gate, norm, tap, bias or group is 1e-2 and more
#: (tests/chipbench/test_nemotron_h_reference.py)
TOL = 3e-4


def build(seed=0, **kw):
    """``MEM*EME`` at toy widths: 8 Mamba heads of 64 in 2 groups over a
    state of 128 (E = 512: the kernels are the real ones, interpreted), 4
    query heads over 2 KV heads of 32, 8 experts top-3."""
    kw = dict(dict(mamba_num_heads=8), **kw)
    cfg = NemotronHConfig.tiny(dtype=jnp.float32, **kw)
    model = NemotronHForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(seed),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return cfg, model, params


def family():
    from chipbench.harness import Registry
    return Registry().module("families", "nemotron_h")


def as_file(cfg):
    """``cfg`` as a configuration file's keys."""
    fam = family()
    d = {k: getattr(cfg, k) for k in fam.MODEL_KEYS}
    first, count = cfg.held
    d.update(n_routed_experts=count, deployment={"held_first": first},
             published={"n_routed_experts": cfg.n_routed_experts})
    return d


def reference(cfg, params, ids, **kw):
    from chipbench.reference import nemotron_h_ref
    fam, d = family(), as_file(cfg)
    return nemotron_h_ref.forward_logits(fam.reference_weights(params, d),
                                         np.asarray(ids), fam.reference_hp(d),
                                         **kw)


def engine_for(model, params, **over):
    return InferenceEngineV2(model=model, model_parameters=params,
                             config={**ENGINE, **over})


def close(got, want, tol=TOL):
    return np.max(np.abs(np.asarray(got) - np.asarray(want))) \
        <= tol * np.max(np.abs(np.asarray(want)))


def as_pool(states):
    """The reference's states ``[Lm, H, P, N]`` as the pool lays them out."""
    s = np.asarray(states)
    return np.swapaxes(s.reshape(s.shape[0], -1, s.shape[-1]), 1, 2)


@pytest.fixture(scope="module")
def built():
    return build()


@pytest.fixture(scope="module")
def served(built):
    """One engine run of one sequence: a packed pass (two slots, the second
    short), paged passes (state handed from pass to pass), four single
    tokens, 24 fused decode steps (the attention layer's context crosses a
    page at 112), a forced token through the ragged pass; the reference then
    runs over the prompt and the engine's own tokens."""
    cfg, model, params = built
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 256, 100).astype(np.int32)
    eng = engine_for(model, params)
    got = {"packed": eng.put([1], [prompt[:27]])[0],
           "paged": eng.put([1], [prompt[27:96]])[0]}
    for i in range(96, 100):
        got[f"single_{i}"] = eng.put([1], [prompt[i:i + 1]])[0]
    toks = eng.decode_pipeline([1]).run(24)[0]
    last = np.asarray([7], np.int32)
    got["after_24_fused"] = eng.put([1], [last])[0]
    ids = np.concatenate([prompt, toks, last])
    state = eng.sequence_state(1)
    want, want_state = reference(cfg, params, ids, with_state=True)
    rows = {"packed": 26, "paged": 95, "after_24_fused": len(ids) - 1,
            **{f"single_{i}": i for i in range(96, 100)}}
    return (eng, got, np.asarray(want), rows, toks, state,
            np.asarray(want_state))


@pytest.mark.parametrize("row", ["packed", "paged", "single_96", "single_97",
                                 "single_98", "single_99", "after_24_fused"])
def test_logits_are_the_references(served, row):
    _, got, want, rows, *_ = served
    assert close(got[row], want[rows[row]]), row


def test_fused_steps_choose_the_references_tokens_and_leave_its_state(served):
    _, _, want, _, toks, state, want_state = served
    assert (np.argmax(want[99:99 + len(toks)], axis=-1) == toks).all()
    assert len(toks) >= 8
    assert close(state, as_pool(want_state), 2e-5)


def test_the_reference_is_the_zoos_dense_forward(built):
    cfg, model, params = built
    ids = np.random.default_rng(1).integers(0, 256, 40).astype(np.int32)
    dense = np.asarray(model.apply({"params": params}, ids[None])[0])
    assert close(reference(cfg, params, ids), dense, 2e-5)


def test_one_block_a_layer_kinds_pools_and_description(served):
    eng = served[0]
    spec = eng.spec
    assert [k.what for k in spec.layer_kinds] == [
        "mamba", "moe", "mamba", "attention", "moe", "mamba", "moe"]
    assert all(isinstance(k, ms.BlockKind) for k in spec.layer_kinds)
    # an E layer addresses neither pool: pages count attention layers, not
    # "what is left", and states the Mamba layers
    assert ms.num_page_layers(spec) == 1 and ms.num_state_layers(spec) == 3
    assert ms._layer_holds(spec) == ["state", None, "state", "pages", None,
                                     "state", None]
    assert ms._pool_index(spec) == [0, 0, 1, 0, 1, 2, 2]
    assert eng.kv.config.num_layers == 1
    assert eng.state_config.num_layers == 3
    assert eng.state_config.conv_dim == 512 + 2 * 2 * 128
    text = ms.describe_layer_kinds(spec)
    assert text.count("Mamba state-space mixer alone") == 3
    assert text.count("routed experts alone (no pages, no state)") == 3
    assert text.count("attention alone (full, no positions)") == 1
    assert "FFN" not in text
    runs = ms.layer_runs(spec)
    assert [rs.block for rs, _, _ in runs] == [
        "mixer", "ffn", "mixer", "mixer", "ffn", "mixer", "ffn"]
    assert all((rs.moe is not None) == (rs.block == "ffn")
               for rs, _, _ in runs)
    totals = tracer.totals
    assert totals["serve/layers/blocks/mamba"] == 3
    assert totals["serve/layers/blocks/moe"] == 3
    assert totals["serve/layers/blocks/attention"] == 1
    assert totals["serve/state/bytes_per_sequence"] \
        == eng.state_config.bytes_per_slot()


# --------------------------------------------------------------------------- #
# the shares of the experts add up
# --------------------------------------------------------------------------- #

def test_two_held_shares_add_up_to_the_uncut_expert_layer():
    """One ``E`` layer through the program's ``_moe_ffn``: experts 0-3 and
    4-7 run as two ``held`` shares. Their routed parts summed, the shared
    expert counted once, equal the layer with every expert held, and that
    equals the reference's uncut ``E`` block."""
    from chipbench.reference import nemotron_h_ref
    cfg, _, params = build(num_hidden_layers=1, hybrid_override_pattern="E")
    spec, weights = adapters.adapt_nemotron_h(params, cfg)
    w = jax.tree_util.tree_map(lambda a: a[0], weights["layers"][0])["moe"]
    x = jnp.asarray(np.random.default_rng(2).standard_normal((24, 128)),
                    jnp.float32)
    whole = rm._moe_ffn(x, w, 3, jnp.float32, routing=spec.moe)
    shared = rm._mm(rm._plain_act("relu2")(rm._mm(x, w["shared"]["w_up"])),
                    w["shared"]["w_down"])
    parts = []
    for first in (0, 4):
        part = {**w, "w_up": w["w_up"][first:first + 4],
                "w_down": w["w_down"][first:first + 4]}
        parts.append(rm._moe_ffn(x, part, 3, jnp.float32,
                                 routing={**spec.moe, "held": (first, 4)}))
    # each share carries the shared expert: count it once
    assert close(parts[0] + parts[1] - shared, whole, 2e-5)
    fam, d = family(), as_file(cfg)
    layer = fam.reference_weights(params, d)["layers"][0]
    with jax.default_matmul_precision("highest"):
        want, _ = nemotron_h_ref.expert_block(x, layer, fam.reference_hp(d))
    assert close(whole, want, 2e-5)
    assert not close(parts[0], whole, 1e-2)


@pytest.mark.parametrize("held", [(0, 4), (4, 4)])
def test_an_engine_that_holds_a_share_is_the_reference_with_that_share(held):
    cfg, model, params = build(seed=3, experts_held=held)
    eng = engine_for(model, params)
    ids = np.random.default_rng(4).integers(0, 256, 40).astype(np.int32)
    got = eng.put([1], [ids])[0]
    want = np.asarray(reference(cfg, params, ids))[-1]
    assert eng.spec.moe["held"] == held
    assert close(got, want)
    other = dataclasses.replace(cfg, experts_held=(4 - held[0], 4))
    assert not close(got, np.asarray(reference(other, params, ids))[-1], 1e-2)


def test_the_router_chooses_by_score_plus_bias_and_weighs_by_score():
    """Three experts, top-2: the bias lifts expert 2 over expert 1 in the
    choice; the weights are the chosen experts' sigmoid scores over their
    sum, times the scale, and the bias is in none of them."""
    logits = jnp.asarray([[2.0, 0.5, 0.0]], jnp.float32)
    w = {"router": jnp.eye(3, dtype=jnp.float32),
         "expert_bias": jnp.asarray([0.0, 0.0, 0.2], jnp.float32)}
    routing = {"score_func": "sigmoid", "route_norm": True,
               "route_scale": 2.5}
    gates, ids = rm.moe_route(logits, w, 2, routing)
    s = 1.0 / (1.0 + np.exp(-np.asarray(logits[0])))
    assert s[1] > s[2] and s[1] < s[2] + 0.2
    assert sorted(np.asarray(ids[0]).tolist()) == [0, 2]
    want = {0: s[0], 2: s[2]}
    for g, i in zip(np.asarray(gates[0]), np.asarray(ids[0])):
        assert abs(g - 2.5 * want[int(i)] / (s[0] + s[2])) < 1e-6
    no_bias, ids2 = rm.moe_route(logits, {"router": w["router"]}, 2, routing)
    assert sorted(np.asarray(ids2[0]).tolist()) == [0, 1]


# --------------------------------------------------------------------------- #
# repeating units
# --------------------------------------------------------------------------- #

UNITS = {
    # pattern: (period, repeats) of each unit
    "MEM*EME": [(1, 1)] * 7,
    "MEMEM*EMEMEM": [(1, 1), (2, 2), (1, 1), (2, 3)],
    "MEMEM*EMEMEM*EME": [(1, 1), (1, 1), (7, 2)],
    "MMEEM": [(1, 2), (1, 2), (1, 1)],
    "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME": None,
}


@pytest.mark.parametrize("pattern", list(UNITS))
def test_the_pattern_is_cut_into_repeating_units(pattern):
    what = {"M": "mamba", "E": "moe", "*": "attention"}
    kinds = tuple(ms.BlockKind(what[c]) for c in pattern)
    cuts = ms._unit_cuts(kinds)
    # the units tile the layers in order, and each repeats what it says
    at = 0
    for l0, p, r in cuts:
        assert l0 == at and (p == 1 or r >= 2)
        assert all(kinds[l0 + i * p:l0 + (i + 1) * p] == kinds[l0:l0 + p]
                   for i in range(r))
        at += p * r
    assert at == len(pattern)
    if UNITS[pattern] is not None:
        assert [(p, r) for _, p, r in cuts] == UNITS[pattern]
    else:       # the published 52 layers: a handful of scans, not 52
        assert len(cuts) <= 8 and max(p for _, p, _ in cuts) >= 7


def test_maximal_runs_stay_units_of_their_own():
    """The accepted families' patterns are cut as they always were: one unit
    a run, whatever repeats a longer period would find (Jamba's 28 layers
    are (7 M, A, 6 M) twice over)."""
    M, A = ms.MambaKind(False), ms.LayerKind(None, False, False)
    jamba = (M,) * 7 + (A,) + (M,) * 13 + (A,) + (M,) * 6
    assert [(p, r) for _, p, r in ms._unit_cuts(jamba)] == [
        (1, 7), (1, 1), (1, 13), (1, 1), (1, 6)]
    granite = (ms.MambaKind(True),) * 5 + (ms.LayerKind(None, False, True),) \
        + (ms.MambaKind(True),) * 4
    assert [(p, r) for _, p, r in ms._unit_cuts(granite)] == [
        (1, 5), (1, 1), (1, 4)]


def test_unit_scans_give_what_one_layer_scans_give(monkeypatch):
    """``MEMEM*EMEMEM`` (units M, (EM) x 2, *, (EM) x 3) through the packed
    pass, a paged pass and fused steps; then the same weights with every
    layer a unit of its own (twelve scans): the same logits and tokens."""
    cfg, model, params = build(num_hidden_layers=12,
                               hybrid_override_pattern="MEMEM*EMEMEM")
    prompt = np.random.default_rng(5).integers(0, 256, 50).astype(np.int32)

    def run():
        eng = engine_for(model, params)
        out = [eng.put([1], [prompt[:20]])[0], eng.put([1], [prompt[20:]])[0]]
        toks = eng.decode_pipeline([1]).run(8)[0]
        return eng, out, toks

    eng, out, toks = run()
    units = [(len(s), n) for s, _, n in ms.layer_units(eng.spec)]
    assert units == [(1, 1), (2, 2), (1, 1), (2, 3)]
    assert isinstance(eng.weights["layers"][1], tuple)
    monkeypatch.setattr(ms, "_unit_cuts",
                        lambda kinds: [(i, 1, 1) for i in range(len(kinds))])
    eng1, out1, toks1 = run()
    assert len(ms.layer_units(eng1.spec)) == 12
    assert all(close(a, b, 1e-5) for a, b in zip(out, out1))
    assert (toks == toks1).all()
    ids = np.concatenate([prompt, toks])
    want = np.asarray(reference(cfg, params, ids))
    assert close(out[1], want[49]) and close(out[0], want[19])


def test_the_experts_width_is_padded_to_whole_lane_tiles_when_adapted():
    """A width that is not whole 128-lane tiles (the published 1856) is
    zero-padded in the engine's stacks, and only there: the same numbers."""
    cfg, model, params = build(moe_intermediate_size=72)
    spec, weights = adapters.adapt_nemotron_h(params, cfg)
    moe = weights["layers"][1]["moe"]
    assert params["layers_1"]["mixer"]["w_up"].shape == (8, 128, 72)
    assert moe["w_up"].shape == (1, 8, 128, 128)
    assert moe["w_down"].shape == (1, 8, 128, 128)
    assert not np.asarray(moe["w_up"][..., 72:]).any()
    assert not np.asarray(moe["w_down"][..., 72:, :]).any()
    ids = np.random.default_rng(6).integers(0, 256, 30).astype(np.int32)
    got = engine_for(model, params).put([1], [ids])[0]
    assert close(got, np.asarray(reference(cfg, params, ids))[-1])
    up = jnp.zeros((2, 2688, 1856), jnp.bfloat16)
    padded = jax.eval_shape(adapters._stacks._pad_expert_width, up,
                            jnp.zeros((2, 1856, 2688), jnp.bfloat16))
    assert [p.shape for p in padded] == [(2, 2688, 1920), (2, 1920, 2688)]
    # 9.8 MiB a matrix: the Pallas kernel's, by the rule's size limit
    assert rm.moe_grouped_kernel(padded[0], jnp.bfloat16) == "pallas"
    assert rm.moe_grouped_kernel(up, jnp.bfloat16) == "xla"
    assert rm.moe_grouped_kernel(jnp.zeros((8, 4096, 14336), jnp.bfloat16),
                                 jnp.bfloat16) == "xla"


def test_what_is_not_built_is_refused():
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        NemotronHConfig.tiny(hybrid_override_pattern="MEM-EME")
    with pytest.raises(ValueError, match="n_groups"):
        NemotronHConfig.tiny(n_groups=3)
    with pytest.raises(ValueError, match="group-restricted"):
        NemotronHConfig.tiny(n_group=2)
    with pytest.raises(ValueError, match="experts_held"):
        NemotronHConfig.tiny(experts_held=(6, 4))
