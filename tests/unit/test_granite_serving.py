"""Granite 4.0-H (``granitemoehybrid``) through InferenceEngineV2: Mamba-2
layers whose state is a matrix per head, in the state pool beside the paged
KV of one attention layer without positions; every FFN a mixture of routed
experts (of which the engine may hold a share) plus a shared MLP; four plain
multipliers. Against the plain reference ``chipbench/reference/granite_ref.py``
through the packed pass, the paged passes, single tokens through the cache,
the fused decode step in both its forms; over splits of a prompt, a
reused state slot, a page boundary; the shares of the experts; what is
refused."""

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
from deepspeed_tpu.inference.v2.ragged.state_pool import StatefulKV  # noqa: E402
from deepspeed_tpu.models.granite import (ATTENTION, MAMBA, GraniteConfig,  # noqa: E402
                                          GraniteForCausalLM)
from deepspeed_tpu.monitor.trace import tracer  # noqa: E402
from tests.unit import test_serving_program_text as program_text  # noqa: E402

#: 2 chunk slots of 16 rows a pass (32 tokens), pages of 16, 4 decode rows
ENGINE = {"dtype": "float32",
          "state_manager": {"max_context": 256, "max_tracked_sequences": 4,
                            "max_ragged_sequence_count": 4,
                            "max_ragged_batch_size": 4 + 2 * 16,
                            "prefill_chunk_size": 16},
          "kv_cache": {"block_size": 16, "num_blocks": 64}}
#: float32 engine against the float32 reference: what is left is the order
#: of summation (the product form against the recurrent one, the kernels'
#: sublane sums, online softmax by page), through four layers and, in the
#: recurrence, through every token. A dropped gate, norm, tap or multiplier
#: is 1e-2 and more (tests/chipbench/test_granite_reference.py)
TOL = 3e-4


def build(seed=0, **kw):
    """Mamba-2, attention, Mamba-2, Mamba-2 at head_dim 128 (hidden 256 over
    2 heads), 8 Mamba heads of 64 over a state of 128 (E = 512), 8 experts
    top-3: the kernels are the real ones, interpreted."""
    kw = dict(dict(hidden_size=256, num_attention_heads=2,
                   num_key_value_heads=1, mamba_n_heads=8,
                   attention_multiplier=1 / 64), **kw)
    cfg = GraniteConfig.tiny(dtype=jnp.float32, **kw)
    model = GraniteForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(seed),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return cfg, model, params


def family():
    from chipbench.harness import Registry
    return Registry().module("families", "granite")


def as_file(cfg):
    """``cfg`` as a configuration file's keys."""
    fam = family()
    d = {k: getattr(cfg, k) for k in fam.MODEL_KEYS}
    first, count = cfg.held
    d.update(num_local_experts=count, deployment={"held_first": first},
             published={"num_local_experts": cfg.num_local_experts})
    return d


def reference(cfg, params, ids, **kw):
    from chipbench.reference import granite_ref
    fam, d = family(), as_file(cfg)
    return granite_ref.forward_logits(fam.reference_weights(params, d),
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
    tokens, 40 fused decode steps (the attention layer's context crosses
    pages at 112, 128 and 144), a forced token through the ragged pass; the
    reference then runs over the prompt and the engine's own tokens."""
    cfg, model, params = built
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 256, 100).astype(np.int32)
    eng = engine_for(model, params)
    got = {"packed": eng.put([1], [prompt[:27]])[0],
           "paged": eng.put([1], [prompt[27:96]])[0]}
    for i in range(96, 100):
        got[f"single_{i}"] = eng.put([1], [prompt[i:i + 1]])[0]
    toks = eng.decode_pipeline([1]).run(40)[0]
    last = np.asarray([7], np.int32)
    got["after_40_fused"] = eng.put([1], [last])[0]
    ids = np.concatenate([prompt, toks, last])
    state = eng.sequence_state(1)
    want, want_state = reference(cfg, params, ids, with_state=True)
    rows = {"packed": 26, "paged": 95, "after_40_fused": len(ids) - 1,
            **{f"single_{i}": i for i in range(96, 100)}}
    return (eng, got, np.asarray(want), rows, toks, state,
            np.asarray(want_state))


@pytest.mark.parametrize("phase", ["packed", "paged", "single_96",
                                   "single_97", "single_98", "single_99",
                                   "after_40_fused"])
def test_engine_logits_match_the_reference(served, phase):
    _, got, want, rows, *_ = served
    assert close(got[phase], want[rows[phase]])


def test_fused_steps_choose_the_reference_tokens(served):
    _, _, want, _, toks, *_ = served
    greedy = np.argmax(want[99:99 + 40], axis=-1)
    assert [int(t) for t in toks] == [int(t) for t in greedy]


def test_state_after_the_run_is_the_reference_state(served):
    *_, state, want_state = served
    assert state.shape == (3, 128, 512) and state.dtype == np.float32
    assert want_state.shape == (3, 8, 64, 128)
    assert close(state, as_pool(want_state), 2e-5)


def test_the_flax_forward_is_the_reference(built):
    cfg, model, params = built
    ids = np.random.default_rng(11).integers(0, 256, 40).astype(np.int32)
    want = np.asarray(reference(cfg, params, ids))
    got = np.asarray(model.apply({"params": params}, ids[None]))[0]
    assert close(got, want, 1e-4)


#: a 70-token prompt put in these pieces; a pass holds two chunk slots of 16
SPLITS = {"one put: three passes, the last of one short slot": [70],
          "a slot's worth, then the rest": [16, 54],
          "a chunk shorter than its slot, then paged passes": [5, 65],
          "three tokens: fewer than the convolution's taps": [3, 67],
          "short chunks between passes": [33, 4, 33],
          "the last piece one token": [69, 1]}


@pytest.fixture(scope="module")
def unsplit(built):
    cfg, model, params = built
    ids = np.random.default_rng(1).integers(0, 256, 70).astype(np.int32)
    eng = engine_for(model, params)
    logits = eng.put([1], [ids])[0]
    return eng, ids, logits, eng.sequence_state(1), \
        np.asarray(eng.kv.kv.conv).reshape(3, 5, -1)[
            :, eng.scheduler.seqs[1].state_slot]


@pytest.mark.parametrize("case", list(SPLITS))
def test_any_split_of_a_prompt_gives_the_state_one_put_gives(unsplit, case):
    eng, ids, logits, state, tail = unsplit
    uid, at = 2, 0
    for n in SPLITS[case]:
        got = eng.put([uid], [ids[at:at + n]])[0]
        at += n
    slot = eng.scheduler.seqs[uid].state_slot
    assert close(eng.sequence_state(uid), state, 2e-5)
    got_tail = np.asarray(eng.kv.kv.conv).reshape(3, 5, -1)[:, slot]
    assert close(got_tail, tail, 1e-5)
    assert close(got, logits)
    eng.flush([uid])


def test_one_put_state_is_the_reference_state(unsplit, built):
    cfg, _, params = built
    _, ids, logits, state, _ = unsplit
    want, want_state = reference(cfg, params, ids, with_state=True)
    assert close(logits, np.asarray(want)[-1])
    assert close(state, as_pool(want_state), 2e-5)


def test_slots_moved_by_slices_give_what_gather_and_scatter_give(
        unsplit, built, monkeypatch):
    """A state slot of 1 MiB or more moves by one dynamic slice a row
    (``_SLOT_SLICE_BYTES``; the published 4 MiB do, this model's 16 KiB do
    not): with the rule at 0 a prompt put in three parts (a state read from
    the pool, handed from slot to slot, stored) leaves what one put left."""
    _, model, params = built
    _, ids, logits, state, _ = unsplit
    tiny = _tiny("granite")
    by_gather = program_text.lowered(*tiny, "serve_prefill_packed")
    monkeypatch.setattr(rm, "_SLOT_SLICE_BYTES", 0)
    assert program_text.lowered(*tiny, "serve_prefill_packed") != by_gather
    eng = engine_for(model, params)
    for at, n in ((0, 20), (20, 37), (57, 13)):
        got = eng.put([1], [ids[at:at + n]])[0]
    assert close(eng.sequence_state(1), state, 2e-5)
    assert close(got, logits)


def test_a_freed_slot_reused_gives_the_new_sequence_its_reference(built):
    """What a freed slot still holds is never read: the next sequence to
    take it starts from zero."""
    cfg, model, params = built
    rng = np.random.default_rng(2)
    first, second = (rng.integers(0, 256, n).astype(np.int32)
                     for n in (50, 41))
    eng = engine_for(model, params)
    eng.put([1], [first])
    eng.decode_pipeline([1]).run(5)
    slot = eng.scheduler.seqs[1].state_slot
    assert np.abs(eng.sequence_state(1)).max() > 0
    eng.flush([1])
    assert eng.state_slots() == (0, 1, 4)
    got = eng.put([2], [second[:40]])[0]
    assert eng.scheduler.seqs[2].state_slot == slot
    want = np.asarray(reference(cfg, params, second))
    assert close(got, want[39])
    assert close(eng.put([2], [second[40:]])[0], want[40])


def test_decode_rows_reordered_between_runs_keep_their_states(built):
    cfg, model, params = built
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 256, n).astype(np.int32) for n in (20, 33, 9)]
    eng = engine_for(model, params)
    alone = []
    for p in prompts:
        eng.put([9], [p])
        alone.append([int(t) for t in eng.decode_pipeline([9]).run(10)[0]])
        eng.flush([9])
    eng.put([1, 2, 3], prompts)
    a = eng.decode_pipeline([1, 2, 3]).run(4)
    b = eng.decode_pipeline([3, 1]).run(3)         # 2 sits out, rows swap
    c = eng.decode_pipeline([2, 3, 1]).run(3)
    got = {1: list(a[0]) + list(b[1]) + list(c[2]),
           3: list(a[2]) + list(b[0]) + list(c[1]),
           2: list(a[1]) + list(c[0])}
    assert [int(t) for t in got[1]] == alone[0]
    assert [int(t) for t in got[3]] == alone[2]
    assert [int(t) for t in got[2]] == alone[1][:7]


@pytest.mark.parametrize("loop", ["side buffer", "general"])
def test_loop_and_pipeline_give_the_same_tokens(built, loop, monkeypatch):
    """The decode step chained by the pipeline against the per-token loop
    (``sample_next``/``put``: the ragged pass, which carries the state its
    own way) and the reference, in both of the step's forms."""
    if loop == "general":
        monkeypatch.setattr(rm, "side_buffer_fits", lambda *a, **kw: False)
    cfg, model, params = built
    p = np.random.default_rng(4).integers(0, 256, 30).astype(np.int32)
    eng = engine_for(model, params)
    eng.put([1], [p])
    eng.put([2], [p])
    looped = []
    for _ in range(6):
        looped.append(int(eng.sample_next([1])[0]))
        eng.put([1], [np.asarray(looped[-1:], np.int32)])
    piped = eng.decode_pipeline([2]).run(6)[0]
    assert looped == list(piped)
    want = np.asarray(reference(cfg, params, np.concatenate([p, piped])))
    assert [int(t) for t in piped] == [
        int(t) for t in np.argmax(want[29:35], axis=-1)]


# --------------------------------------------------------------------------- #
# the pools, the spec, what is counted
# --------------------------------------------------------------------------- #

def test_pages_are_the_attention_layer_and_states_the_mamba_layers(served):
    eng = served[0]
    spec = eng.spec
    assert [k.mamba for k in spec.layer_kinds] == [True, False, True, True]
    assert all(k.moe and not k.rope and k.window is None
               for k in spec.layer_kinds)
    assert ms.num_page_layers(spec) == 1 and ms.num_state_layers(spec) == 3
    kv = eng.kv.kv
    assert isinstance(kv, StatefulKV)
    assert kv.pages.shape[0] == 1
    assert kv.ssm.shape == (3, 5, 128, 512) and kv.ssm.dtype == jnp.float32
    # x, B and C convolved together: 512 + 2 x 128 channels, padded to 1,024
    assert kv.conv.shape == (3, 5, 3 * 8, 1024 // 8)
    assert eng.state_config.conv_dim == 768
    assert eng.state_config.bytes_per_slot() == 3 * 4 * (128 * 512 + 3 * 1024)
    assert spec.mamba == {"kind": "mamba2", "d_inner": 512, "n_heads": 8,
                          "d_head": 64, "n_groups": 1, "d_state": 128,
                          "d_conv": 4, "chunk": 256}
    assert (spec.embed_scale, spec.residual_scale, spec.logits_scale,
            spec.attn_scale) == (12.0, 0.22, 1 / 16, 1 / 64)
    assert "score_func" not in spec.moe and "held" not in spec.moe
    text = ms.describe_layer_kinds(spec)
    assert text.count("Mamba state-space mixer (no pages), MoE FFN") == 2
    assert "layers 1-1: full, no positions, MoE FFN" in text
    assert tracer.totals["serve/state/bytes_per_sequence"] \
        == eng.state_config.bytes_per_slot()
    assert tracer.totals["serve/state/kind"] == 2


def test_adapter_stacks_a_tree_per_run(built):
    cfg, _, params = built
    spec, weights = adapters.adapt_granite(params, cfg)
    assert [n for _, _, n in ms.layer_runs(spec)] == [1, 1, 2]
    stacks = weights["layers"]
    assert "mamba" in stacks[0] and "wq" not in stacks[0]
    assert "wq" in stacks[1] and "mamba" not in stacks[1]
    m = stacks[2]["mamba"]
    assert m["in_proj"].shape == (2, 256, 512 + 768 + 8)
    assert m["conv_w"].shape == (2, 4, 768) and m["A_log"].shape == (2, 8)
    assert m["norm"].shape == (2, 512)
    assert stacks[2]["moe"]["w_gate"].shape == (2, 8, 256, 64)
    assert stacks[2]["moe"]["shared"]["w_up"].shape == (2, 256, 128)
    assert "lm_head" not in weights and spec.tied_lm_head
    assert cfg.layer_types == (MAMBA, ATTENTION, MAMBA, MAMBA)


def test_the_published_preset_counts_the_issue_s_parameters():
    cfg = GraniteConfig.granite_4_0_h_small(
        num_hidden_layers=10, layer_types=tuple(
            ATTENTION if i == 5 else MAMBA for i in range(10)),
        experts_held=(0, 36), dtype=jnp.bfloat16)
    shapes = jax.eval_shape(
        lambda k: GraniteForCausalLM(cfg).init(
            k, jnp.zeros((1, 8), jnp.int32))["params"], jax.random.PRNGKey(0))
    count = sum(int(np.prod(a.shape))
                for a in jax.tree_util.tree_leaves(shapes))
    assert count == 4_962_732_672            # 9.24 GiB in bfloat16
    full = GraniteConfig.granite_4_0_h_small()
    assert full.layer_types.count(ATTENTION) == 4
    assert [i for i, t in enumerate(full.layer_types) if t == ATTENTION] \
        == [5, 15, 25, 35]


# --------------------------------------------------------------------------- #
# one chip's share of the experts, behind the softmax router
# --------------------------------------------------------------------------- #

def _moe_layer(seed=5, T=24, hid=256, E=8, F=64):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s) / np.sqrt(s[-2]),
                               jnp.float32)
    w = {"router": f(hid, E), "w_gate": f(E, hid, F), "w_up": f(E, hid, F),
         "w_down": f(E, F, hid),
         "shared": {"w_gate": f(hid, 2 * F), "w_up": f(hid, 2 * F),
                    "w_down": f(2 * F, hid)}}
    x = jnp.asarray(rng.standard_normal((T, hid)), jnp.float32)
    return x, w


def _share(w, first, count):
    cut = {k: w[k][first:first + count] for k in ("w_gate", "w_up", "w_down")}
    return {**w, **cut}


def test_the_shares_add_up():
    """The routed parts of share (0, 4) and of share (4, 4), plus the shared
    MLP once, equal the uncut MoE layer — and the reference's."""
    from chipbench.reference import granite_ref
    x, w = _moe_layer()
    routing = {"num_experts": 8, "top_k": 3}
    whole = rm._moe_ffn(x, w, 3, jnp.float32, routing=routing)
    shared = rm._swiglu(x, w["shared"])
    parts = [rm._moe_ffn(x, _share(w, first, 4), 3, jnp.float32,
                         routing={**routing, "held": (first, 4)}) - shared
             for first in (0, 4)]
    assert close(parts[0] + parts[1] + shared, whole, 1e-5)
    assert float(jnp.max(jnp.abs(parts[0]))) > 0 < float(
        jnp.max(jnp.abs(parts[1])))
    hp = {"top_k": 3, "held": None}
    want, _ = granite_ref.sparse_mixture(x, w, hp)
    assert close(whole, want, 1e-5)
    for first, part in zip((0, 4), parts):
        held, _ = granite_ref.sparse_mixture(
            x, _share(w, first, 4), dict(hp, held=(first, 4)))
        assert close(part + shared, held, 1e-5)


def test_the_softmax_router_with_held_weighs_as_the_uncut_router():
    """``held`` does not touch the router: the same ids, and the softmax over
    ALL the chosen, so a held expert's weight is the uncut router's (not a
    softmax over the held choices: the reference's fault flag shows the
    difference)."""
    from chipbench.reference import granite_ref
    x, w = _moe_layer(6)
    gates, ids = rm.moe_route(x, w, 3, {"num_experts": 8, "top_k": 3})
    gates_h, ids_h = rm.moe_route(x, w, 3, {"num_experts": 8, "top_k": 3,
                                            "held": (4, 4)})
    assert np.array_equal(np.asarray(ids), np.asarray(ids_h))
    assert np.array_equal(np.asarray(gates), np.asarray(gates_h))
    assert close(gates.sum(-1), np.ones(len(x)), 1e-6)
    dense = np.zeros((len(x), 8), np.float32)
    np.put_along_axis(dense, np.asarray(ids), np.asarray(gates), axis=1)
    want, margin, is_held = granite_ref.route(x, w, {"top_k": 3,
                                                     "held": (4, 4)})
    assert close(dense, want, 1e-6)
    assert list(np.asarray(is_held)) == [False] * 4 + [True] * 4
    assert float(jnp.min(margin)) >= 0
    wrong, _, _ = granite_ref.route(x, w, {"top_k": 3, "held": (4, 4),
                                           "softmax_over_held": True})
    assert not close(np.asarray(wrong)[:, 4:], dense[:, 4:], 1e-2)


@pytest.fixture(scope="module")
def held_engine():
    cfg, model, params = build(seed=1, experts_held=(2, 4))
    return cfg, model, params, engine_for(model, params)


def test_an_engine_with_a_share_of_the_experts_is_the_reference_s(held_engine):
    cfg, model, params, eng = held_engine
    assert eng.spec.moe == {"num_experts": 8, "top_k": 3, "held": (2, 4)}
    assert tracer.totals["serve/moe/held_experts"] == 4
    stack = eng.weights["layers"][2]["moe"]
    assert stack["w_gate"].shape[1] == 4 and stack["router"].shape[-1] == 8
    ids = np.random.default_rng(7).integers(0, 256, 60).astype(np.int32)
    got = eng.put([1], [ids[:45]])[0]
    want = np.asarray(reference(cfg, params, ids))
    assert close(got, want[44])
    assert close(eng.put([1], [ids[45:]])[0], want[59])
    toks = eng.decode_pipeline([1]).run(6)[0]
    full = np.asarray(reference(cfg, params, np.concatenate([ids, toks])))
    assert [int(t) for t in toks] == [
        int(t) for t in np.argmax(full[59:65], axis=-1)]


def test_the_family_holds_the_engine_to_the_file(held_engine):
    cfg, model, params, eng = held_engine
    fam, d = family(), as_file(cfg)
    d["engine"] = {"kv_cache": {"block_size": 16}}
    assert fam.experts(d) == (8, (2, 4))
    assert fam.check_engine(d, eng) == ""
    assert fam.state_layout(d)["bytes_per_sequence"] \
        == eng.state_config.bytes_per_slot()
    assert "multipliers" in fam.check_engine(
        dict(d, residual_multiplier=1.0), eng)
    assert "holds" in fam.check_engine(
        dict(d, deployment={"held_first": 0}), eng)
    x = jnp.asarray(np.random.default_rng(8).standard_normal((64, 256)),
                    jnp.bfloat16)
    share = fam.held_touched_share(eng, x, 4)
    assert 0.5 < share <= 1.0


# --------------------------------------------------------------------------- #
# the multipliers are plain spec fields: neutral values leave a program as
# it is without them
# --------------------------------------------------------------------------- #

def _tiny(fam):
    """(spec, weights, pools) of a family at toy widths."""
    if fam == "granite":
        cfg, model, _ = build()
        return program_text.tiny(fam, (cfg, model, adapters.adapt_granite))
    return program_text.tiny(fam)


@pytest.mark.parametrize("program", ["serve_decode_step", "serve_paged_pass",
                                     "serve_prefill_packed"])
@pytest.mark.parametrize("fam", ["jamba", "mixtral", "afmoe"])
def test_neutral_multipliers_lower_to_the_program_without_them(fam, program):
    """Mixtral's, Trinity's (afmoe) and Jamba's programs with all four
    multipliers at their neutral values are, as lowered text, the programs
    with none (``None``: what their adapters leave): the new fields add no
    operation to a family that does not set them."""
    spec, weights, kv = _tiny(fam)
    assert (spec.embed_scale, spec.residual_scale, spec.logits_scale,
            spec.attn_scale) == (None,) * 4
    neutral = dataclasses.replace(
        spec, embed_scale=1.0, residual_scale=1.0, logits_scale=1.0,
        attn_scale=spec.head_dim ** -0.5)
    assert program_text.lowered(neutral, weights, kv, program) \
        == program_text.lowered(spec, weights, kv, program)


@pytest.mark.parametrize("field", ["embed_scale", "residual_scale",
                                   "logits_scale", "attn_scale"])
def test_each_multiplier_is_in_the_granite_programs(field):
    spec, weights, kv = _tiny("granite")
    neutral = {"attn_scale": spec.head_dim ** -0.5}.get(field, 1.0)
    without = dataclasses.replace(spec, **{field: neutral})
    assert program_text.lowered(without, weights, kv, "serve_paged_pass") \
        != program_text.lowered(spec, weights, kv, "serve_paged_pass")


# --------------------------------------------------------------------------- #
# refusals: what needs a snapshot of the state
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("feature,says", [
    ("prefix_cache", "prefix_cache.enabled"),
    ("spec_decode", "spec_decode.enabled"),
    ("lora", "multi-tenant LoRA")])
def test_engine_build_refuses(built, feature, says):
    _, model, params = built
    with pytest.raises(NotImplementedError, match=says) as e:
        engine_for(model, params, **{feature: {"enabled": True}})
    if feature != "lora":
        assert "snapshot of the state at a block boundary" in str(e.value)


def test_tensor_parallel_beside_mamba2_is_refused(built):
    from deepspeed_tpu.inference.v2.attention import AttentionKernelSpec
    from deepspeed_tpu.inference.v2.config_v2 import (
        RaggedInferenceEngineConfig)
    cfg, _, params = built
    spec, _ = adapters.adapt_granite(params, cfg)
    spec = dataclasses.replace(spec, num_kv_heads=2)    # whole heads a shard
    conf = RaggedInferenceEngineConfig.load(
        {**ENGINE, "tensor_parallel": 2})
    with pytest.raises(NotImplementedError,
                       match="tensor_parallel > 1 are not wired for a model "
                             "with state-space"):
        AttentionKernelSpec.validate_engine_build(spec, conf)


def test_page_movers_are_refused(served):
    eng = served[0]
    with pytest.raises(NotImplementedError, match="export_kv"):
        eng.export_kv(1)
    with pytest.raises(NotImplementedError, match="preemption='offload'"):
        eng.serving_frontend(config={"preemption": "offload"})
    with pytest.raises(NotImplementedError, match="speculative verify step"):
        rm.build_verify_step(eng.spec, 3)


def test_groups_that_do_not_divide_the_heads_are_refused():
    GraniteConfig.tiny(mamba_n_groups=2)        # 4 heads in 2 groups: built
    with pytest.raises(ValueError, match="mamba_n_groups"):
        GraniteConfig.tiny(mamba_n_groups=3)
    with pytest.raises(ValueError, match="experts_held"):
        GraniteConfig.tiny(experts_held=(6, 4))


def test_a_bfloat16_state_pool_shows_in_the_state_the_programs_leave(built):
    """What the benchmark's check on the chip holds the state's precision
    by: with the pool in float32 the state the engine's programs leave is
    the reference's to 2e-5; over a pool that holds it in bfloat16 the same
    programs leave one as far from it as the reference's control does."""
    cfg, model, params = built
    eng = engine_for(model, params)
    kv = eng.kv.kv
    eng.kv.update(StatefulKV(kv.pages, kv.ssm.astype(jnp.bfloat16), kv.conv))
    prompt = np.random.default_rng(5).integers(0, 256, 70).astype(np.int32)
    eng.put([1], [prompt])
    toks = eng.decode_pipeline([1]).run(32)[0]
    ids = np.concatenate([prompt, toks])
    state = eng.sequence_state(1)
    want = as_pool(reference(cfg, params, ids, with_state=True)[1])
    control = as_pool(reference(cfg, params, ids, with_state=True,
                                state_dtype=jnp.bfloat16)[1])
    assert eng.kv.kv.ssm.dtype == jnp.bfloat16
    assert not close(state, want, 1e-3) and not close(control, want, 1e-3)
    assert close(state, want, 5e-2)
