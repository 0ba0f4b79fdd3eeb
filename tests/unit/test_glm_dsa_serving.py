"""GLM-5 through InferenceEngineV2: latent attention over a learned per-token
selection — an indexer a layer, an index-key pool beside the latent pages,
attention over the top-k chosen — against the plain reference
``chipbench/reference/glm_dsa_ref.py``, logits and not tokens: prefill then
decode through both pools, through the packed pass (where the engine has
one), paged chunk passes, single tokens through the cache (ragged decode)
and the fused decode step, two sequences of which one is shorter than the
selection; the two pools; and what is refused beside them."""

import functools
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
    adapters, engine_v2, model_spec as ms, ragged_mla)
from deepspeed_tpu.inference.v2.attention import INDEX_POOL_MSG  # noqa: E402
from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2  # noqa: E402
from deepspeed_tpu.inference.v2.ragged.kv_cache import KVCacheConfig  # noqa: E402
from deepspeed_tpu.models.glm_dsa import GlmDsaConfig, GlmDsaForCausalLM  # noqa: E402
from deepspeed_tpu.monitor.trace import tracer  # noqa: E402

#: 2 chunk slots of 16 rows a pass (32 tokens), pages of 16, 4 decode rows
ENGINE = {"dtype": "float32",
          "state_manager": {"max_context": 256, "max_tracked_sequences": 4,
                            "max_ragged_sequence_count": 4,
                            "max_ragged_batch_size": 4 + 2 * 16,
                            "prefill_chunk_size": 16},
          "kv_cache": {"block_size": 16, "num_blocks": 64}}
#: float32 engine against the float32 reference: what is left is the order
#: of summation. A query that chose another token would read 1e-2 and more
TOL = 3e-4
#: the selection keeps 24 tokens (a packed pass of 32 could hold more: the
#: engine's prompts take the paged pass) or 32 (the packed pass runs)
TOPKS = {"paged_only": 24, "packed": 32}


@functools.lru_cache(maxsize=None)
def build(topk=24, held=None, seed=0, **kw):
    """(config, model, parameters), made once a set of arguments: a test
    hands them to an engine, which keeps a copy of its own."""
    cfg = GlmDsaConfig.tiny(dtype=jnp.float32, index_topk=topk,
                            experts_held=held, **kw)
    model = GlmDsaForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(seed),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return cfg, model, params


def family():
    from chipbench.harness import Registry
    return Registry().module("families", "glm_dsa")


def file_keys(cfg):
    """``cfg`` as a configuration file spells it."""
    fam = family()
    d = {k: getattr(cfg, k) for k in fam.MODEL_KEYS}
    first, count = cfg.held
    return dict(d, n_routed_experts=count,
                rope_parameters={"rope_theta": cfg.rope_theta,
                                 "rope_type": "default"},
                published={"n_routed_experts": cfg.n_routed_experts},
                deployment={"held_first": first})


def reference(cfg, params, ids, **hp):
    from chipbench.reference import glm_dsa_ref
    fam, d = family(), file_keys(cfg)
    return glm_dsa_ref.forward_logits(
        fam.reference_weights(params, d), jnp.asarray(ids),
        dict(fam.reference_hp(d), **hp))


def engine_for(model, params, **over):
    return InferenceEngineV2(model=model, model_parameters=params,
                             config={**ENGINE, **over})


def err(got, want):
    return float(np.max(np.abs(np.asarray(got) - np.asarray(want)))
                 / np.max(np.abs(np.asarray(want))))


def last_logits(eng, uid):
    eng._materialize([uid])
    return eng._last_logits[uid]


IDS = np.random.default_rng(1).integers(0, 256, size=96).astype(np.int32)
SHORT = np.random.default_rng(2).integers(0, 256, size=40).astype(np.int32)
FUSED_FROM, FUSED = 40, 12


@pytest.fixture(scope="module", params=list(TOPKS))
def served(request):
    """One engine a selection size. Sequence 1: 32 tokens from position 0
    (the packed pass where the engine has one), 28 more through paged chunk
    passes, four single tokens (ragged decode) — positions 24 on select.
    Sequence 2, beside it in the same passes, is SHORT of the selection all
    along (20 tokens). Then the fused decode step from a prompt of 40 on its
    own greedy tokens, across the page boundary at 48, as one run and as
    two, with the short sequence a second live row."""
    topk = TOPKS[request.param]
    cfg, model, params = build(topk, held=(4, 4))
    eng = engine_for(model, params)
    assert eng.packed_prefill == (request.param == "packed")
    first = eng.put([1, 2], [IDS[:32], SHORT[:12]])
    got = {"first_32": (first[0], IDS, 31), "short_12": (first[1], SHORT, 11)}
    second = eng.put([1, 2], [IDS[32:60], SHORT[12:16]])
    got["paged_60"] = (second[0], IDS, 59)
    got["short_16"] = (second[1], SHORT, 15)
    for i in range(60, 64):
        rows = eng.put([1, 2], [IDS[i:i + 1], SHORT[i - 44:i - 43]])
        got[f"single_{i}"] = (rows[0], IDS, i)
        got[f"short_{i - 44}"] = (rows[1], SHORT, i - 44)
    want = {id(IDS): np.asarray(reference(cfg, params, IDS[:64])),
            id(SHORT): np.asarray(reference(cfg, params, SHORT[:20]))}
    eng.flush([1, 2])
    out = {k: (np.asarray(v), want[id(ids)][row])
           for k, (v, ids, row) in got.items()}
    # a dense reference over the same tokens: what a fallback would give
    out["dense_60"] = (np.asarray(second[0]), np.asarray(
        reference(cfg, params, IDS[:64], fault="dense"))[59])
    # (the engine with a packed pass runs the one-run form alone: what the
    # two-run form adds is the pipeline's, not the pass's)
    for name, uid, runs in (("fused", 3, (FUSED,)),
                            ("fused_two_runs", 5, (5, FUSED - 5))
                            )[:1 if request.param == "packed" else 2]:
        eng.put([uid, uid + 1], [IDS[:FUSED_FROM], SHORT[:8]])
        toks = np.concatenate([np.asarray(eng.decode_pipeline(
            [uid, uid + 1]).run(n), np.int32) for n in runs], axis=1)
        for u, prompt, tag in ((uid, IDS[:FUSED_FROM], ""),
                               (uid + 1, SHORT[:8], "_short")):
            logits = last_logits(eng, u)
            seq = np.concatenate([prompt, toks[u - uid]])
            ref = np.asarray(reference(cfg, params, seq))
            out[name + tag] = (logits, ref[len(seq) - 1])
            out[name + tag + "_tokens"] = (toks[u - uid], np.argmax(
                ref[len(prompt) - 1:len(seq) - 1], axis=-1))
        eng.flush([uid, uid + 1])
    return out


PHASES = ["first_32", "paged_60", "single_60", "single_61", "single_62",
          "single_63", "short_12", "short_16", "short_19", "fused",
          "fused_short", "fused_two_runs", "fused_two_runs_short"]


@pytest.mark.parametrize("phase", PHASES)
def test_engine_logits_match_the_reference(served, phase):
    if phase not in served:
        pytest.skip("the two-run form is run on the engine without a packed "
                    "pass")
    got, want = served[phase]
    assert np.isfinite(got).all() and err(got, want) <= TOL, err(got, want)


def test_the_engine_is_not_dense_attention(served):
    """At position 59 a query sees 60 tokens and keeps 24 or 32: attention
    over all of them reads far from the engine."""
    got, dense = served["dense_60"]
    assert err(got, dense) > 30 * TOL


@pytest.mark.parametrize("loop", ["fused", "fused_two_runs"])
def test_decode_through_a_page_boundary_chooses_the_reference_tokens(
        served, loop):
    if loop + "_tokens" not in served:
        pytest.skip("run on the engine without a packed pass")
    for tag in ("", "_short"):
        got, want = served[loop + tag + "_tokens"]
        assert list(got) == list(want)


# --------------------------------------------------------------------------- #
# the pools
# --------------------------------------------------------------------------- #

def test_two_pools_one_budget_at_the_published_widths():
    """A token costs a layer 640 + 128 bfloat16 values = 1,536 B, funded
    from one budget under one page id."""
    cfg = GlmDsaConfig.glm_5()
    spec = ms.RaggedModelSpec(
        family="glm_dsa", num_layers=5, hidden_size=6144, num_heads=64,
        num_kv_heads=64, head_dim=256, vocab_size=19360,
        mla={"q_lora_rank": cfg.q_lora_rank, "kv_lora_rank": cfg.kv_lora_rank,
             "qk_nope_head_dim": cfg.qk_nope_head_dim,
             "qk_rope_head_dim": cfg.qk_rope_head_dim,
             "v_head_dim": cfg.v_head_dim,
             "index": {"heads": 32, "head_dim": 128, "topk": 2048,
                       "rope_dim": 64, "eps": 1e-6}})
    assert (ms.latent_width(spec), ms.index_width(spec)) == (640, 128)
    kv = KVCacheConfig(5, 64, 256, 128, 10, jnp.bfloat16, latent_dim=640,
                       index_dim=128)
    assert kv.bytes_per_block() == 5 * 128 * 1536
    sized = KVCacheConfig.from_memory_budget(
        5, 0, 0, 10 * kv.bytes_per_block() + 5, 128, jnp.bfloat16,
        latent_dim=640, index_dim=128)
    assert sized.num_blocks == 10 and sized.index_dim == 128
    # the family's layout funds the same bytes through latent_dim alone
    layout = family().page_layout({"kv_lora_rank": 512, "index_head_dim": 128,
                                   "qk_rope_head_dim": 64,
                                   "num_hidden_layers": 5})
    assert layout["latent_dim"] == 768
    assert KVCacheConfig.from_memory_budget(
        5, 0, 0, 10 * kv.bytes_per_block() + 5, 128, jnp.bfloat16,
        latent_dim=768).num_blocks == 10


def test_engine_holds_both_pools_and_says_what_a_token_costs(served):
    del served
    _, model, params = build()
    eng = engine_for(model, params)
    lat, idx = eng.kv.kv
    assert lat.shape == (4, 65, 16, 128) and idx.shape == (4, 65, 16, 128)
    assert lat.nbytes + idx.nbytes == eng.kv.config.bytes_per_block() * 65
    assert tracer.totals["serve/latent/bytes_per_token"] == 128 * 4
    assert tracer.totals["serve/index/bytes_per_token"] == 128 * 4
    assert tracer.totals["serve/index/topk"] == 24
    assert tracer.totals["serve/index/pool_bytes"] == idx.nbytes


def test_the_selections_walks_and_blocks_are_counted():
    """A paged pass and fused decode steps hand back their ``dsa_select``
    calls' walks and blocks beside their results, and the drain adds them to
    ``serve/dsa/select_sweeps`` / ``select_blocks`` by name: a block walks
    its tiles at least twice (the bounds and the threshold) and no more
    often than a sweep a bit and a cut did; warm-up's scratch rows are
    counted out."""
    names = ("serve/dsa/select_sweeps", "serve/dsa/select_blocks")
    _, model, params = build()
    eng = engine_for(model, params)
    eng.warmup()
    zero = [tracer.totals[n] for n in names]
    eng.put([1], [IDS[:40]])
    eng.decode_pipeline([1]).run(3)
    # (a drain reads what has finished and waits for nothing: wait here)
    jax.block_until_ready([c for _, c in engine_v2._held_turns_pending])
    last_logits(eng, 1)
    sweeps, blocks = [tracer.totals[n] - z for n, z in zip(names, zero)]
    # 4 layers; a chunk slot's 16 rows are one block, a step's rows one: a
    # pass of 32 tokens, one of 8, three steps
    assert blocks == (2 + 1 + 3) * 4, blocks
    assert 2 * blocks <= sweeps <= (1 + 32 + 2 + 31) * blocks, (sweeps,
                                                                 blocks)
    eng.flush([1])


def test_adapter_reads_the_indexer_and_joyai_has_none():
    cfg, _, params = build(held=(4, 4))
    spec, weights = adapters.adapt_glm_dsa(params, cfg)
    assert spec.family == "glm_dsa" and spec.mla["index"] == {
        "heads": 4, "head_dim": 32, "topk": 24, "rope_dim": 16, "eps": 1e-6}
    dense, sparse = weights["layers"]
    assert dense["index"]["wq"].shape == (1, 48, 4 * 32)
    assert sparse["index"]["wk"].shape == (3, 64, 32)
    assert sparse["index"]["ww"].shape == (3, 64, 4)
    assert sparse["index"]["k_bias"].shape == (3, 32)
    from deepspeed_tpu.models.joyai import JoyaiConfig, JoyaiForCausalLM
    jcfg = JoyaiConfig.tiny(dtype=jnp.float32)
    jparams = JoyaiForCausalLM(jcfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    jspec, jweights = adapters.adapt_joyai(jparams, jcfg)
    assert "index" not in jspec.mla and "index" not in jweights["layers"][1]


def test_decode_step_reads_the_index_pool_and_gathers_the_selection():
    """The lowered fused decode step of a selecting model: both selection
    kernels and the attention over gathered rows are in it, the dense latent
    kernel is not, and the rows it attends are a gather of ``topk`` rows a
    sequence — not the pages."""
    cfg, model, params = build()
    eng = engine_for(model, params)
    spec, weights = eng.spec, eng.weights
    fwd = ragged_mla.build_decode_step(spec, False, 0)
    S, MB = 4, 16
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    text = jax.jit(fwd).lower(
        weights, eng.kv.kv, i32(S), i32(S), i32(S, MB), i32(S),
        jax.ShapeDtypeStruct((2,), jnp.uint32)).as_text(debug_info=True)
    for name in ("dsa_index_decode", "dsa_select", "dsa_attend_decode",
                 "mla_row_write"):
        assert name in text, name
    assert "mla_decode" not in text and "mla_chunk" not in text
    # the gathered rows: [S, topk, W] out of the flat latent rows
    assert f"tensor<{S}x{cfg.index_topk}x128xf32>" in text


# --------------------------------------------------------------------------- #
# what is refused
# --------------------------------------------------------------------------- #

REFUSED = {
    "prefix_cache": {"prefix_cache": {"enabled": True}},
    "spec_decode": {"spec_decode": {"enabled": True, "k": 3}},
    "tensor_parallel": {"tensor_parallel": 2},
    "kv_quant": {"kv_quant": {"enabled": True}},
    "decode_splits": {"attention": {"decode_splits": 2}},
    "lora": {"lora": {"enabled": True}},
}


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_refused_beside_the_index_pool(what):
    _, model, params = build()
    with pytest.raises(NotImplementedError) as e:
        engine_for(model, params, **REFUSED[what])
    assert "latent attention" in str(e.value)


def test_offload_export_and_verify_are_refused():
    _, model, params = build()
    eng = engine_for(model, params)
    with pytest.raises(NotImplementedError, match="index key"):
        eng.serving_frontend({"preemption": "offload"})
    eng.put([7], [IDS[:20]])
    with pytest.raises(NotImplementedError, match="index key"):
        eng.export_kv(7)
    with pytest.raises(NotImplementedError, match="index key"):
        eng.import_kv(8, IDS[:20], np.zeros((2, 4, 16, 128), np.float32),
                      np.zeros((256,), np.float32))
    with pytest.raises(NotImplementedError, match="index key"):
        ragged_mla.build_verify(eng.spec, 3)
    assert "selects inside latent attention" in INDEX_POOL_MSG


def test_packed_pass_refuses_to_hold_more_than_the_selection():
    """Built for a selection of 24 and handed a pass of 32 rows, the packed
    program stops at trace time; the engine never builds it."""
    _, model, params = build(24)
    eng = engine_for(model, params)
    assert not eng.packed_prefill
    fwd = ragged_mla.build_packed_prefill(eng.spec)
    b = {"chunk_ntok": jnp.zeros((2,), jnp.int32),
         "chunk_tokens": jnp.zeros((32,), jnp.int32)}
    with pytest.raises(AssertionError, match="selects nothing"):
        fwd(eng.weights, eng.kv.kv, b)


def _one_position_twice(chosen_positions):
    def faulty(keep, topk):
        chosen = chosen_positions(keep, topk)
        return chosen.at[:, 0].set(chosen[:, 1])
    return faulty


#: a fault in the wiring between the kernels, as ``monkeypatch.setattr``
#: arguments: a threshold off by one, a position gathered twice, the fused
#: step's own key not scored
WIRING_FAULTS = {
    "sound": None,
    "kept": (ragged_mla, "_kept", lambda kept: (
        lambda topk, seen: kept(topk - 1, seen))),
    "gather": (ragged_mla.sparse_mla, "chosen_positions",
               _one_position_twice),
    "own": (ragged_mla, "select_decode", lambda select: (
        lambda spec, q, w, k_own, *a: select(
            spec, q, w, None if k_own is None else 0 * k_own, *a))),
}


@pytest.mark.parametrize("fault", list(WIRING_FAULTS))
def test_the_cells_selection_check_runs_the_programs_own_selection(
        fault, monkeypatch):
    """``families/glm_dsa.py::selection_readings`` — what decides the new
    cell's ``correct`` beside the logits — goes through ``select_chunk`` and
    ``select_decode`` as the paged pass and the fused decode step call them:
    sound, it agrees with the reference; a fault in their wiring is
    caught."""
    from chipbench.reference import glm_dsa_ref
    cfg, model, params = build(24, held=(4, 4))
    eng = engine_for(model, params)
    if WIRING_FAULTS[fault]:
        where, name, make = WIRING_FAULTS[fault]
        monkeypatch.setattr(where, name, make(getattr(where, name)))
    got = family().selection_readings(
        eng, glm_dsa_ref, family().reference_hp(file_keys(cfg)),
        {"index_contexts": [48, 96], "tol_index": 0.02,
         "index_control_dtype": "float8_e4m3fn"}, np.random.default_rng(5))
    assert got["control"] > 0 and got["kept"] == 2 * 16 * 24
    assert (got["differ"] + got["miscounted"] > 0) == (fault != "sound"), got


# --------------------------------------------------------------------------- #
# a paged pass that holds ONE sequence attends expanded
# --------------------------------------------------------------------------- #

def _both_forms(eng, uids, tokens):
    """``eng.put`` with every paged pass run TWICE through the engine's one
    program: first on copies of the pools with the unused last column of
    every later slot's block table changed — the same pass to every kernel
    (no slot reads that far), and no longer one sequence's to the program —
    then as it is. ``(logits, the absorbed twin's chunk logits a pass, the
    batches)``."""
    batches, twins = [], []
    run, prog = eng._run_pass, eng._pass_rungs[1]

    def recording():
        batch = run()
        batches.extend([] if batch is None else [batch])
        return batch

    def twice(weights, kv, arrays):
        bt = np.array(arrays["chunk_block_tables"])
        bt[1:, -1] += 1
        twins.append(np.asarray(prog(
            weights, jax.tree_util.tree_map(jnp.copy, kv),
            dict(arrays, chunk_block_tables=bt))[0]))
        return prog(weights, kv, arrays)

    eng._run_pass, eng._pass_rungs[1] = recording, twice
    try:
        return ([np.asarray(x) for x in eng.put(uids, tokens)], twins,
                batches)
    finally:
        eng._run_pass, eng._pass_rungs[1] = run, prog


def test_one_sequence_pass_attends_expanded_and_agrees_with_absorbed():
    """A sequence alone in both slots from position 0, again over 32 cached
    tokens with its last slot part full, two sequences a pass, a sequence
    alone in ONE slot: the program's predicate and the host's counter say
    the same of every batch, and the logits of each pass agree with the same
    pass's absorbed form (``_both_forms``) to float32's summation order —
    exactly, where the pass was absorbed to begin with."""
    _, model, params = build(24, held=(4, 4))
    eng = engine_for(model, params)
    assert not eng.packed_prefill
    puts = [([1], [IDS[:32]]), ([1], [IDS[32:60]]),
            ([1, 2], [IDS[60:70], SHORT[:12]]), ([2], [SHORT[12:20]])]
    want = [True, True, False, False]
    for (uids, tokens), expanded in zip(puts, want):
        before = dict(tracer.totals)
        logits, twins, batches = _both_forms(eng, uids, tokens)
        assert len(batches) == len(twins) == 1
        b = batches[0]
        assert bool(ragged_mla.one_sequence(
            jnp.asarray(b.chunk_ntok), jnp.asarray(b.chunk_q0),
            jnp.asarray(b.chunk_block_tables), 16)) == expanded, uids
        gained = lambda k: tracer.totals[k] - before.get(k, 0.0)
        assert gained("serve/mla/paged_passes") == 1
        assert gained("serve/mla/expanded_passes") == expanded, uids
        last = [np.flatnonzero(np.asarray(b.slot_uid) == u)[-1] for u in uids]
        for mine, slot in zip(logits, last):
            assert np.isfinite(mine).all()
            e = err(mine, twins[0][slot])
            assert (0 < e <= TOL) if expanded else e == 0, (uids, e)


@pytest.mark.parametrize("case,ntok,q0,tables,want", [
    ("one_sequence", [16, 16, 5, 0], [32, 48, 64, 0], [3, 3, 3, 0], True),
    ("one_slot", [16, 0, 0, 0], [32, 0, 0, 0], [3, 0, 0, 0], False),
    ("another_table", [16, 16, 5, 0], [32, 48, 64, 0], [3, 3, 4, 0], False),
    ("not_consecutive", [16, 16, 0, 0], [32, 64, 0, 0], [3, 3, 0, 0], False),
    ("a_gap", [16, 0, 16, 0], [32, 0, 64, 0], [3, 0, 3, 0], False),
    ("all_empty", [0, 0, 0, 0], [0, 0, 0, 0], [0, 0, 0, 0], False),
])
def test_one_sequence_is_read_from_the_slots(case, ntok, q0, tables, want):
    bt = np.asarray(tables, np.int32)[:, None] * 10 + np.arange(6)
    assert bool(ragged_mla.one_sequence(
        jnp.asarray(ntok, jnp.int32), jnp.asarray(q0, jnp.int32),
        jnp.asarray(bt, jnp.int32), 16)) == want, case
