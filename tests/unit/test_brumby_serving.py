"""Brumby (``brumby``) through InferenceEngineV2: power retention in every
layer — a gated degree-2 linear-attention state and its normaliser a
sequence in the state pool's slots, q and k normed and rotated in front of
it — and NO layer that holds pages. Against the plain reference
``chipbench/reference/brumby_ref.py`` (the attention form, all pairs) through
the packed pass, the paged passes, single tokens through the pool and the
fused decode step, with rows joining and leaving; the state itself and its
control; a slot another sequence has just freed; an engine that allocates no
page but the scratch page and funds no block, behind the frontend too; what
is refused beside a state; what the spec says of pools and kinds."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from deepspeed_tpu.inference.v2 import model_spec as ms, ragged_model as rm  # noqa: E402
from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2  # noqa: E402
from deepspeed_tpu.models.brumby import (BrumbyConfig,  # noqa: E402
                                         BrumbyForCausalLM)
from deepspeed_tpu.ops.pallas import power_retention as pr  # noqa: E402

#: 2 chunk slots of 16 rows a pass (32 tokens), 4 decode rows
ENGINE = {"dtype": "float32",
          "state_manager": {"max_context": 256, "max_tracked_sequences": 4,
                            "max_ragged_sequence_count": 4,
                            "max_ragged_batch_size": 4 + 2 * 16,
                            "prefill_chunk_size": 16},
          "kv_cache": {"block_size": 16, "num_blocks": 64}}
#: float32 engine against the float32 reference: what is left is the order
#: of summation (the chunked scan against all pairs, the state's read at 16
#: bits) — 1e-5 here; a dropped gate, norm, rotation or normaliser is 1e-2
#: and more
TOL = 2e-4
#: the state a sequence leaves against the reference's, rms over rms
TOL_STATE = 1e-5


def build(seed=0, **kw):
    """Two layers at toy widths: 4 query heads over 2 KV heads of 16 (a
    state of 40 x 144 a layer; the kernels are the real ones, interpreted),
    chunks of 8. Every norm's gain is moved off one."""
    cfg = BrumbyConfig.tiny(dtype=jnp.float32, **kw)
    model = BrumbyForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(seed),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 1000))

    def shake(path, leaf):
        if any("norm" in getattr(p, "key", "") for p in path):
            return leaf + 0.2 * jax.random.normal(next(keys), leaf.shape)
        return leaf

    return cfg, model, jax.tree_util.tree_map_with_path(shake, params)


def family():
    from chipbench.harness import Registry
    return Registry().module("families", "brumby")


def as_file(cfg):
    """``cfg`` as a configuration file's keys."""
    d = {k: getattr(cfg, k) for k in family().MODEL_KEYS}
    d["assumed_numbers"] = {
        "power": cfg.power, "retention_eps": cfg.retention_eps,
        "chunk_size": cfg.chunk_size,
        "gate_init": [list(r) for r in cfg.gate_init]}
    return d


def reference(cfg, params, ids, **kw):
    from chipbench.reference import brumby_ref
    fam, d = family(), as_file(cfg)
    return brumby_ref.forward_logits(fam.reference_weights(params, d),
                                     np.asarray(ids), fam.reference_hp(d),
                                     **kw)


def engine_for(model, params, **over):
    return InferenceEngineV2(model=model, model_parameters=params,
                             config={**ENGINE, **over})


def close(got, want, tol=TOL):
    return np.max(np.abs(np.asarray(got) - np.asarray(want))) \
        <= tol * np.max(np.abs(np.asarray(want)))


def state_err(got, want):
    return float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)))


@pytest.fixture(scope="module")
def built():
    return build()


@pytest.fixture(scope="module")
def served(built):
    """One engine run of one sequence, in the slot another has just freed:
    a packed pass (two slots, the second short), paged passes (state handed
    from pass to pass through the pool), four single tokens, 24 fused decode
    steps, a forced token through the ragged pass; the reference then runs
    over the prompt and the engine's own tokens."""
    cfg, model, params = built
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 256, 100).astype(np.int32)
    eng = engine_for(model, params)
    eng.put([9], [rng.integers(0, 256, 40).astype(np.int32)])
    eng.decode_pipeline([9]).run(5)
    slot = eng.scheduler.seqs[9].state_slot
    eng.flush([9])
    got = {"packed": eng.put([1], [prompt[:27]])[0]}
    assert eng.scheduler.seqs[1].state_slot == slot
    got["paged"] = eng.put([1], [prompt[27:96]])[0]
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
    return (eng, got, np.asarray(reference(cfg, params, ids)), rows, toks,
            state, np.asarray(want_state), np.asarray(want))


@pytest.mark.parametrize("row", ["packed", "paged", "single_96", "single_97",
                                 "single_98", "single_99", "after_24_fused"])
def test_logits_are_the_references_full_forward(served, row):
    """Prefill then decode through the pool = the attention form over every
    pair of the whole sequence."""
    _, got, want, rows = served[:4]
    assert close(got[row], want[rows[row]])


def test_fused_steps_give_the_references_greedy_tokens(served):
    want, toks = served[2], served[4]
    assert list(np.argmax(want[99:99 + 24], axis=-1)) == list(toks)


def test_the_state_is_the_references_and_a_bfloat16_state_is_not(served,
                                                                 built):
    """Layer by layer: the state the programs left (in a slot that held
    another sequence's) is the reference's state form over the same tokens —
    whose logits are its attention form's — and the control, the same with
    ``S`` and ``z`` rounded to bfloat16 after every token, is not."""
    cfg, _, params = built
    state, want_state, want_logits = served[5], served[6], served[7]
    got = np.swapaxes(state, 1, 2)              # [L, D, N] as the driver's
    assert got.shape == want_state.shape == (2, 144, 40)
    for l in range(2):
        assert state_err(got[l], want_state[l]) < TOL_STATE
    assert close(want_logits, served[2], 1e-4)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 256, 100).astype(np.int32)
    seq = np.concatenate([prompt, served[4], [7]])
    _, ctl = reference(cfg, params, seq, with_state=True,
                       state_dtype=jnp.bfloat16)
    assert state_err(np.asarray(ctl)[0], want_state[0]) > 50 * TOL_STATE


def test_packed_and_paged_passes_agree(built):
    """The same 30 tokens as one packed pass (two slots) and as paged
    passes (a first chunk, then the rest through the pool): the same
    logits and the same state."""
    _, model, params = built
    rng = np.random.default_rng(4)
    prompt = rng.integers(0, 256, 30).astype(np.int32)
    eng = engine_for(model, params)
    packed = eng.put([1], [prompt])[0]
    eng.put([2], [prompt[:5]])
    paged = eng.put([2], [prompt[5:]])[0]
    assert close(paged, packed, 1e-5)
    assert state_err(eng.sequence_state(2), eng.sequence_state(1)) < 5e-5


def test_rows_join_and_leave_the_fused_step(built):
    """Three sequences of different lengths decode side by side; one is
    flushed and a fourth takes its slot: every stream is what that sequence
    decodes alone."""
    _, model, params = built
    rng = np.random.default_rng(2)
    prompts = {u: rng.integers(0, 256, n).astype(np.int32)
               for u, n in ((1, 9), (2, 33), (3, 20), (4, 14))}
    eng = engine_for(model, params)
    alone = {}
    for u, p in prompts.items():
        eng.put([u], [p])
        alone[u] = list(eng.decode_pipeline([u]).run(12)[0])
        eng.flush([u])
    eng.put([1, 2, 3], [prompts[u] for u in (1, 2, 3)])
    first = eng.decode_pipeline([1, 2, 3]).run(6)
    assert [list(first[i]) for i in range(3)] == [alone[u][:6]
                                                  for u in (1, 2, 3)]
    slot = eng.scheduler.seqs[2].state_slot
    eng.flush([2])
    eng.put([4], [prompts[4]])
    assert eng.scheduler.seqs[4].state_slot == slot
    rest = eng.decode_pipeline([1, 4, 3]).run(6)
    assert list(rest[0]) == alone[1][6:] and list(rest[2]) == alone[3][6:]
    assert list(rest[1]) == alone[4][:6]


def test_no_page_but_the_scratch_page_and_no_block_funded(served):
    """A model in which no layer holds pages: the pool is one layer of one
    page, the allocator hands out nothing, a sequence's block table stays
    empty whatever it has seen, and ``kv_cache.num_blocks`` (64 in the
    engine's config) is not read."""
    eng = served[0]
    assert ms.num_page_layers(eng.spec) == 0
    assert ms.num_state_layers(eng.spec) == 2
    assert eng.kv.kv.pages.shape[:2] == (1, 1) and eng.scratch_block == 0
    assert eng.allocator.total_blocks == 0 and eng.scheduler.pageless
    seq = eng.scheduler.seqs[1]
    assert seq.seen_tokens == 125 and seq.blocks == []
    assert eng.scheduler.blocks_needed([1], 64) == 0
    assert eng.scheduler.query(1, 100)[0] == 100
    assert eng.kv.kv.conv.size == 0             # no convolution tail
    sc = eng.state_config
    assert (sc.d_state, sc.d_inner, sc.d_conv) == (
        pr.state_rows(2, 16), pr.state_cols(16), 1)
    assert sc.bytes_per_slot() == 2 * 4 * 40 * 144
    assert eng.state_slots() == (1, 1, 4)
    assert family().check_engine(as_file(BrumbyConfig.tiny()), eng) == ""


def test_the_frontend_admits_by_slots_alone(built):
    """Behind ``ServingFrontend``: six requests through four tracked
    sequences with no page to fund, each stream what its prompt decodes
    alone; every slot given back."""
    _, model, params = built
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 256, n).astype(np.int32)
               for n in (40, 7, 90, 18, 33, 61)]
    eng = engine_for(model, params)
    alone = []
    for p in prompts:
        eng.put([1], [p])
        alone.append([int(t) for t in eng.decode_pipeline([1]).run(10)[0]])
        eng.flush([1])
    eng = engine_for(model, params, serving={
        "preemption": "none", "decode_slice": 4, "idle_wait_s": 0.005,
        "classes": [{"name": "c", "priority": 1, "ttft_slo_ms": 1e6,
                     "tbt_slo_ms": 1e6}]})
    fe = eng.serving_frontend()
    handles = [fe.submit(p, priority="c", max_new_tokens=10) for p in prompts]
    for _ in range(600):
        if all(h.finished for h in handles):
            break
        fe.step()
    assert [h.tokens for h in handles] == alone
    # what bounds a request is the context (positions), not a page count
    with pytest.raises(ValueError, match="max_context"):
        fe.submit(np.zeros(250, np.int32), priority="c", max_new_tokens=10)
    fe.close()
    assert eng.state_slots()[0] == 0 and eng.allocator.free_blocks == 0


def test_what_is_refused_beside_a_state(built, served):
    _, model, params = built
    for over, match in (({"prefix_cache": {"enabled": True}}, "prefix"),
                        ({"spec_decode": {"enabled": True}}, "spec"),
                        ({"lora": {"enabled": True}}, "LoRA|lora")):
        with pytest.raises(NotImplementedError, match=match):
            engine_for(model, params, **over)
    eng = served[0]
    with pytest.raises(NotImplementedError, match="export_kv"):
        eng.export_kv(1)
    with pytest.raises(NotImplementedError, match="preemption='offload'"):
        eng.serving_frontend(config={"preemption": "offload"})
    with pytest.raises(NotImplementedError, match="speculative verify step"):
        rm.build_verify_step(eng.spec, 3)


def test_the_spec_says_a_rotated_state_layer(served):
    """To the pools a state layer, to the layer loop one that rotates: the
    kind, the pool it addresses, the one scanned unit, the set-up line."""
    spec = served[0].spec
    kind = ms.PowerKind()
    assert (kind.mamba, kind.rope, kind.moe, kind.window, kind.tail) == (
        True, True, False, None, False)
    assert ms._holds(kind) == "state"
    assert ms._holds(ms.DeltaKind()) == "state" and not ms.DeltaKind().rope
    assert spec.layer_kinds is None and spec.mamba["kind"] == "pr"
    assert spec.rope_theta == 10000.0 and spec.mamba["d_conv"] == 1
    assert [(len(s), l0, n) for s, l0, n in ms.layer_units(spec)] \
        == [(1, 0, 2)]
    assert ms.describe_layer_kinds(spec) == (
        "layers 0-1: power-retention mixer (rotary; no pages), dense FFN")
    # a model of mixed kinds keeps the rotation for this kind's layers
    mixed = ms._run_spec(ms.RaggedModelSpec(
        family="x", num_layers=2, hidden_size=8, num_heads=1, num_kv_heads=1,
        head_dim=8, vocab_size=8, mamba={"kind": "pr"},
        layer_kinds=(kind, ms.MambaKind())), kind)
    assert mixed.rope_theta is not None and mixed.mamba is not None
