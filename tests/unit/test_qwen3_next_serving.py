"""Qwen3-Next (``qwen3_next``) through InferenceEngineV2: Gated DeltaNet
layers — a delta-rule state a sequence in the state pool's slots — beside
gated attention with a quarter of each head rotated, every feed-forward
routed experts plus a shared expert behind a sigmoid gate. Against the plain
reference ``chipbench/reference/qwen3_next_ref.py`` through the packed pass,
the paged passes, single tokens through the cache and the fused decode step,
with rows joining and leaving; the state itself; what the adapter takes
apart; what the spec says of pools and kinds."""

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
from deepspeed_tpu.models.qwen3_next import (Qwen3NextConfig,  # noqa: E402
                                             Qwen3NextForCausalLM)

#: 2 chunk slots of 16 rows a pass (32 tokens), pages of 16, 4 decode rows
ENGINE = {"dtype": "float32",
          "state_manager": {"max_context": 256, "max_tracked_sequences": 4,
                            "max_ragged_sequence_count": 4,
                            "max_ragged_batch_size": 4 + 2 * 16,
                            "prefill_chunk_size": 16},
          "kv_cache": {"block_size": 16, "num_blocks": 64}}
#: float32 engine against the float32 reference: what is left is the order
#: of summation (the chunked scan's products against the recurrence token by
#: token, the paged kernels' online softmax) — 1e-5 to 1e-4 here; a dropped
#: gate, norm, tap, rotation or the delta's correction is 1e-2 and more
#: (tests/chipbench/test_qwen3_next_reference.py shows each)
TOL = 5e-4
#: the state a sequence leaves against the reference's, rms over rms
TOL_STATE = 1e-4


def build(seed=0, **kw):
    """Two periods (6 Gated DeltaNet layers, 2 attention) at toy widths: one
    key head of 128 serving two value heads of 128 (the kernels are the real
    ones, interpreted), 4 query heads over 2 KV heads of 32 with 8 values
    rotated, 8 experts top-3. Every norm's weight is moved off its initial
    value, so that ``1 + w`` and ``w`` differ."""
    cfg = Qwen3NextConfig.tiny(dtype=jnp.float32, **kw)
    model = Qwen3NextForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(seed),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 1000))

    def shake(path, leaf):
        names = [getattr(p, "key", "") for p in path]
        if any("norm" in n for n in names):
            return leaf + 0.2 * jax.random.normal(next(keys), leaf.shape)
        return leaf

    return cfg, model, jax.tree_util.tree_map_with_path(shake, params)


def family():
    from chipbench.harness import Registry
    return Registry().module("families", "qwen3_next")


def as_file(cfg):
    """``cfg`` as a configuration file's keys."""
    fam = family()
    d = {k: getattr(cfg, k) for k in fam.MODEL_KEYS}
    first, count = cfg.held
    d.update(num_experts=count, deployment={"held_first": first},
             published={"num_experts": cfg.num_experts})
    return d


def reference(cfg, params, ids, **kw):
    from chipbench.reference import qwen3_next_ref
    fam, d = family(), as_file(cfg)
    return qwen3_next_ref.forward_logits(fam.reference_weights(params, d),
                                         np.asarray(ids), fam.reference_hp(d),
                                         **kw)


def engine_for(model, params, **over):
    return InferenceEngineV2(model=model, model_parameters=params,
                             config={**ENGINE, **over})


def close(got, want, tol=TOL):
    return np.max(np.abs(np.asarray(got) - np.asarray(want))) \
        <= tol * np.max(np.abs(np.asarray(want)))


def as_pool(states):
    """The reference's states ``[Ld, Hv, P, N]`` as the pool lays them out."""
    s = np.asarray(states)
    return np.swapaxes(s.reshape(s.shape[0], -1, s.shape[-1]), 1, 2)


def state_err(got, want):
    return float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)))


@pytest.fixture(scope="module")
def built():
    return build()


@pytest.fixture(scope="module")
def served(built):
    """One engine run of one sequence: a packed pass (two slots, the second
    short), paged passes (state handed from pass to pass), four single
    tokens, 24 fused decode steps (the attention layers' context crosses a
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


def test_fused_steps_choose_the_references_tokens(served):
    """Greedy tokens of the 24 fused steps: the reference's argmax at each
    position, given the engine's own tokens before it (logits are what the
    other tests compare; this one ties the fused path's tokens to them)."""
    _, _, want, _, toks, *_ = served
    assert (np.argmax(want[99:99 + 24], axis=-1) == toks).all()


@pytest.mark.parametrize("layer", range(6))
def test_the_state_is_the_references(served, layer):
    """After chunked scans, single steps and 24 fused steps, each delta
    layer's state in the pool is the sequential recurrence's."""
    *_, state, want_state = served
    assert state.shape == (6, 128, 256)
    assert state_err(state[layer], as_pool(want_state)[layer]) < TOL_STATE


def test_rows_join_and_leave(built):
    """Three sequences of different lengths decode side by side; one is
    flushed and a fourth joins in its slot while the others go on: each
    one's logits stay those of the reference run on that sequence alone."""
    cfg, model, params = built
    rng = np.random.default_rng(3)
    prompts = {u: rng.integers(0, 256, n).astype(np.int32)
               for u, n in ((1, 40), (2, 9), (3, 21), (4, 33))}
    eng = engine_for(model, params)
    eng.put([1, 2, 3], [prompts[u] for u in (1, 2, 3)])
    toks = {u: list(t) for u, t in zip(
        (1, 2, 3), eng.decode_pipeline([1, 2, 3]).run(5))}
    freed = eng.scheduler.seqs[2].state_slot
    eng.flush([2])
    eng.put([4], [prompts[4]])
    assert eng.scheduler.seqs[4].state_slot == freed
    more = eng.decode_pipeline([3, 4, 1]).run(6)
    toks[4] = []
    for u, t in zip((3, 4, 1), more):
        toks[u].extend(t)
    probe = np.asarray([11], np.int32)
    last = dict(zip((1, 3, 4), eng.put([1, 3, 4], [probe] * 3)))
    for u in (1, 3, 4):
        ids = np.concatenate([prompts[u], toks[u], probe]).astype(np.int32)
        want, S = reference(cfg, params, ids, with_state=True)
        assert close(last[u], np.asarray(want)[-1]), u
        assert state_err(eng.sequence_state(u)[0],
                         as_pool(S)[0]) < TOL_STATE, u


def test_held_experts_give_their_share(built):
    """Experts 4-7 of 8 held: the engine's logits are the reference's given
    the same share (the shares' sum is the uncut layer:
    tests/chipbench/test_qwen3_next_reference.py)."""
    cfg, model, params = build(experts_held=(4, 4))
    ids = np.random.default_rng(1).integers(0, 256, 40).astype(np.int32)
    eng = engine_for(model, params)
    assert eng.spec.moe["held"] == (4, 4)
    got = eng.put([1], [ids])[0]
    want = np.asarray(reference(cfg, params, ids))[-1]
    assert close(got, want)


@pytest.mark.parametrize("routing", ["as_drawn", "one_held_choice_a_token"])
def test_a_small_held_share_takes_the_compact_path(routing):
    """Experts 2-3 of 16 held: the two prefill passes have a bound under
    their choices (32 of the paged pass's 36 x 3) and their MoE layers work
    on slabs of that many sorted rows; the logits are the reference's given
    the same share. With every router bent so that each token sends exactly
    one of its three choices to a held expert (expert 2 scores ``x . u``,
    expert 3 ``-x . u``, the fourteen others 0), the packed pass's 32 rows
    hold 32 held choices against its bound of 24, so each of its 8 layers
    takes a second turn: ``serve/moe/held_overflow_turns`` counts them, and
    the logits are still the reference's (nothing is dropped). The paged
    passes' padding (24 and 2 of their 32 chunk rows, 4 decode rows) asks no
    expert, so their 8 and 30 held choices stay under their bound of 32."""
    from deepspeed_tpu.monitor.trace import tracer
    cfg, model, params = build(num_experts=16, experts_held=(2, 2))
    if routing == "one_held_choice_a_token":
        u = jax.random.normal(jax.random.PRNGKey(5), (cfg.hidden_size,))

        def bend(path, leaf):
            names = [getattr(p, "key", "") for p in path]
            if names[-2:] == ["gate", "kernel"] and leaf.shape[-1] == 16:
                return jnp.zeros_like(leaf).at[:, 2].set(u).at[:, 3].set(-u)
            return leaf

        params = jax.tree_util.tree_map_with_path(bend, params)
    ids = np.random.default_rng(2).integers(0, 256, 70).astype(np.int32)
    eng = engine_for(model, params)
    assert eng.spec.moe["held"] == (2, 2)
    assert tracer.totals["serve/moe/held_rows_bound"] == 32
    before = tracer.totals["serve/moe/held_overflow_turns"]
    got = eng.put([1], [ids[:40]])[0]           # a packed pass, a paged pass
    got2 = eng.put([1], [ids[40:]])[0]          # one paged pass
    want = np.asarray(reference(cfg, params, ids))
    assert close(got, want[39]) and close(got2, want[-1])
    over = tracer.totals["serve/moe/held_overflow_turns"] - before
    # the packed pass's 8 MoE layers, a second turn each; none as drawn
    assert over == (8 if routing == "one_held_choice_a_token" else 0)


def test_the_adapter_takes_the_fused_layouts_apart(built):
    cfg, _, params = built
    spec, weights = adapters.adapt_qwen3_next(params, cfg)
    assert [type(k).__name__ for k in spec.layer_kinds] == \
        ["DeltaKind"] * 3 + ["LayerKind"] + ["DeltaKind"] * 3 + ["LayerKind"]
    assert spec.norm_plus_one and spec.rotary_dim == 8
    assert spec.mamba["kind"] == "gdn" and spec.mamba["conv_dim"] == 512
    assert ms.num_state_layers(spec) == 6 and ms.num_page_layers(spec) == 2
    # one unit of four kinds, twice: a tuple of four stacked trees
    assert [(len(u), n) for u, _, n in ms.layer_units(spec)] == [(4, 2)]
    (unit,) = weights["layers"]
    delta, attn = unit[0], unit[3]
    # [q | k | v | z] over all heads; one key head here, so the fused
    # kernel's own order
    fused = params["layers_0"]["linear_attn"]["in_proj_qkvz"]["kernel"]
    assert (np.asarray(delta["gdn"]["in_proj"][0]) == np.asarray(fused)).all()
    qg = np.asarray(params["layers_3"]["self_attn"]["q_proj"]["kernel"]
                    ).reshape(128, 4, 64)
    wq = np.asarray(attn["wq"][0]).reshape(128, 4, 32)
    assert (np.asarray(attn["wg"][0]).reshape(128, 4, 32)
            == qg[..., 32:]).all()
    # the rotated values interleaved: value 0, value 4, value 1, value 5, ..
    assert (wq[..., 0] == qg[..., 0]).all() and (wq[..., 1] == qg[..., 4]).all()
    assert (wq[..., 2] == qg[..., 1]).all() and (wq[..., 8:] == qg[..., 8:32]).all()


def test_the_period_is_one_scan_of_four_bodies():
    """Three delta layers and an attention layer, three times: ONE unit of
    four kinds (four bodies to trace), not six runs; a single period stays
    two runs; and the units give what one scan a layer gives."""
    D, A = ms.DeltaKind(True), ms.LayerKind(None, True, True)
    assert [(p, r) for _, p, r in ms._unit_cuts((D, D, D, A) * 3)] == [(4, 3)]
    assert [(p, r) for _, p, r in ms._unit_cuts((D, D, D, A))] == [
        (1, 3), (1, 1)]


def test_unit_scans_give_what_one_layer_scans_give(built, monkeypatch):
    cfg, model, params = built
    prompt = np.random.default_rng(5).integers(0, 256, 50).astype(np.int32)

    def run():
        eng = engine_for(model, params)
        out = [eng.put([1], [prompt[:20]])[0], eng.put([1], [prompt[20:]])[0]]
        return eng, out, eng.decode_pipeline([1]).run(6)[0]

    eng, out, toks = run()
    assert len(ms.layer_units(eng.spec)) == 1
    monkeypatch.setattr(ms, "_unit_cuts",
                        lambda kinds: [(i, 1, 1) for i in range(len(kinds))])
    eng1, out1, toks1 = run()
    assert len(ms.layer_units(eng1.spec)) == 8
    assert all(close(a, b, 1e-5) for a, b in zip(out, out1))
    assert (toks == toks1).all()


def test_two_key_heads_are_regrouped():
    """Two key heads serving four value heads: the fused kernels' columns
    (a key head's q, k, v, z together) are regrouped to [q | k | v | z] over
    all heads, and the engine still follows the reference."""
    cfg, model, params = build(linear_num_key_heads=2,
                               linear_num_value_heads=4, num_hidden_layers=4)
    ids = np.random.default_rng(2).integers(0, 256, 45).astype(np.int32)
    eng = engine_for(model, params)
    got = [eng.put([1], [ids[:30]])[0], eng.put([1], [ids[30:]])[0]]
    toks = eng.decode_pipeline([1]).run(4)[0]
    want = np.asarray(reference(cfg, params, np.concatenate([ids, toks])))
    assert close(got[0], want[29]) and close(got[1], want[44])
    assert (np.argmax(want[44:48], axis=-1) == toks).all()


def test_what_is_refused_beside_the_state(built):
    """Snapshots of the state are refused as beside Mamba layers: a prefix
    cache, speculation, LoRA."""
    cfg, model, params = built
    for over in ({"prefix_cache": {"enabled": True}},
                 {"spec_decode": {"enabled": True}}):
        with pytest.raises(NotImplementedError, match="state"):
            engine_for(model, params, **over)


def test_the_engine_reports_its_kind(built):
    cfg, model, params = built
    eng = engine_for(model, params)
    text = ms.describe_layer_kinds(eng.spec)
    assert "Gated DeltaNet" in text and "rotary" in text
    sc = eng.state_config
    assert (sc.num_layers, sc.d_inner, sc.d_state, sc.conv_dim) == \
        (6, 256, 128, 512)
    assert sc.conv_width == 1024        # padded to whole tiles a tap
    fam = family()
    assert fam.check_engine(as_file(cfg), eng) == ""
