"""Jamba (AI21 Jamba2) through InferenceEngineV2: Mamba state-space layers
with a per-sequence state pool beside the paged KV of the attention layers,
against the plain reference ``chipbench/reference/jamba_ref.py`` — through the
packed pass, the paged passes, single tokens through the cache and the fused
decode step; over splits of a prompt into chunk slots and passes, a reused
state slot, reordered decode rows; and what is refused beside such layers."""

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
from deepspeed_tpu.inference.v2.ragged.state_pool import (  # noqa: E402
    StatefulKV, StateSlotAllocator)
from deepspeed_tpu.models.jamba import (ATTENTION, MAMBA, JambaConfig,  # noqa: E402
                                        JambaForCausalLM)
from deepspeed_tpu.monitor.trace import tracer  # noqa: E402

#: 2 chunk slots of 16 rows a pass (32 tokens), pages of 16, 4 decode rows
ENGINE = {"dtype": "float32",
          "state_manager": {"max_context": 256, "max_tracked_sequences": 4,
                            "max_ragged_sequence_count": 4,
                            "max_ragged_batch_size": 4 + 2 * 16,
                            "prefill_chunk_size": 16},
          "kv_cache": {"block_size": 16, "num_blocks": 64}}
#: float32 engine against the float32 reference: what is left is the order
#: of summation (the kernels' sublane sums, online softmax by page), a few
#: float32 ulps through four layers and, in the recurrence, through every
#: token. A dropped norm, gate or convolution tap is 1e-2 and more
#: (tests/chipbench/test_jamba_reference.py)
TOL = 2e-4


def build(seed=0, **kw):
    """Mamba, attention, Mamba, Mamba at head_dim 128 and E = 512, so that
    the kernels are the real ones, interpreted."""
    kw = dict(dict(hidden_size=256, num_attention_heads=2, mamba_dt_rank=16),
              **kw)
    cfg = JambaConfig.tiny(dtype=jnp.float32, **kw)
    model = JambaForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(seed),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return cfg, model, params


def family():
    from chipbench.harness import Registry
    return Registry().module("families", "jamba")


def reference(cfg, params, ids, **kw):
    from chipbench.reference import jamba_ref
    fam = family()
    d = {k: getattr(cfg, k) for k in fam.MODEL_KEYS}
    return jamba_ref.forward_logits(fam.reference_weights(params, d),
                                    jnp.asarray(ids), fam.reference_hp(d),
                                    **kw)


def engine_for(model, params, **over):
    return InferenceEngineV2(model=model, model_parameters=params,
                             config={**ENGINE, **over})


def close(got, want, tol=TOL):
    return np.max(np.abs(np.asarray(got) - np.asarray(want))) \
        <= tol * np.max(np.abs(np.asarray(want)))


@pytest.fixture(scope="module")
def built():
    return build()


@pytest.fixture(scope="module")
def served(built):
    """One engine run of one sequence: a packed pass (two slots, the second
    short), paged passes (three, state handed from pass to pass), four
    single tokens, 64 fused decode steps, then a forced token through the
    ragged pass; the reference then runs over the prompt and the engine's
    own tokens."""
    cfg, model, params = built
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 256, 100).astype(np.int32)
    eng = engine_for(model, params)
    got = {"packed": eng.put([1], [prompt[:27]])[0],
           "paged": eng.put([1], [prompt[27:96]])[0]}
    for i in range(96, 100):
        got[f"single_{i}"] = eng.put([1], [prompt[i:i + 1]])[0]
    toks = eng.decode_pipeline([1]).run(64)[0]
    last = np.asarray([7], np.int32)
    got["after_64_fused"] = eng.put([1], [last])[0]
    ids = np.concatenate([prompt, toks, last])
    state = eng.sequence_state(1)
    want, want_state = reference(cfg, params, ids, with_state=True)
    rows = {"packed": 26, "paged": 95, "after_64_fused": len(ids) - 1,
            **{f"single_{i}": i for i in range(96, 100)}}
    return (eng, got, np.asarray(want), rows, toks, state,
            np.asarray(want_state))


@pytest.mark.parametrize("phase", ["packed", "paged", "single_96",
                                   "single_97", "single_98", "single_99",
                                   "after_64_fused"])
def test_engine_logits_match_the_reference(served, phase):
    _, got, want, rows, *_ = served
    assert close(got[phase], want[rows[phase]])


def test_fused_steps_choose_the_reference_tokens(served):
    _, _, want, _, toks, *_ = served
    greedy = np.argmax(want[99:99 + 64], axis=-1)
    assert [int(t) for t in toks] == [int(t) for t in greedy]


def test_state_after_the_run_is_the_reference_state(served):
    *_, state, want_state = served
    assert state.shape == (3, 16, 512) and state.dtype == np.float32
    assert close(state, np.swapaxes(want_state, 1, 2), 1e-5)


#: a 70-token prompt put in these pieces; a pass holds two chunk slots of 16
SPLITS = {"one put: three passes, the last of one short slot": [70],
          "a slot's worth, then the rest": [16, 54],
          "one prompt over both slots of a pass, then again": [32, 38],
          "a chunk shorter than its slot, then paged passes": [5, 65],
          "three tokens: fewer than the convolution's taps": [3, 67],
          "a single token first": [1, 69],
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
    assert close(eng.sequence_state(uid), state, 1e-5)
    got_tail = np.asarray(eng.kv.kv.conv).reshape(3, 5, -1)[:, slot]
    assert close(got_tail, tail, 1e-5)
    assert close(got, logits)
    eng.flush([uid])


def test_one_put_state_is_the_reference_state(unsplit, built):
    cfg, _, params = built
    _, ids, logits, state, _ = unsplit
    want, want_state = reference(cfg, params, ids, with_state=True)
    assert close(logits, np.asarray(want)[-1])
    assert close(state, np.swapaxes(np.asarray(want_state), 1, 2), 1e-5)


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
        alone.append([int(t) for t in eng.decode_pipeline([9]).run(12)[0]])
        eng.flush([9])
    eng.put([1, 2, 3], prompts)
    a = eng.decode_pipeline([1, 2, 3]).run(5)
    b = eng.decode_pipeline([3, 1]).run(4)         # 2 sits out, rows swap
    c = eng.decode_pipeline([2, 3, 1]).run(3)
    got = {1: list(a[0]) + list(b[1]) + list(c[2]),
           3: list(a[2]) + list(b[0]) + list(c[1]),
           2: list(a[1]) + list(c[0])}
    assert [int(t) for t in got[1]] == alone[0]
    assert [int(t) for t in got[3]] == alone[2]
    assert [int(t) for t in got[2]] == alone[1][:8]


@pytest.mark.parametrize("loop", ["side buffer", "general"])
def test_loop_and_pipeline_give_the_same_tokens(built, loop, monkeypatch):
    """The decode step chained by the pipeline against the per-token loop
    (``sample_next``/``put``: the ragged pass, which carries the state its
    own way), in both of the step's forms: turned away from the side buffer
    it takes the in-layer write, which is handed the rows' state slots the
    same way."""
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


# --------------------------------------------------------------------------- #
# the pools, the slots, what is counted
# --------------------------------------------------------------------------- #

def test_pages_are_the_attention_layers_and_states_the_mamba_layers(served):
    eng = served[0]
    assert [k.mamba for k in eng.spec.layer_kinds] == [True, False, True, True]
    assert ms.num_page_layers(eng.spec) == 1
    assert ms.num_state_layers(eng.spec) == 3
    assert ms._pool_bases(eng.spec) == [0, 0, 1]
    kv = eng.kv.kv
    assert isinstance(kv, StatefulKV)
    assert kv.pages.shape[0] == eng.kv.config.num_layers == 1
    assert kv.ssm.shape == (3, 5, 16, 512) and kv.ssm.dtype == jnp.float32
    assert kv.conv.shape == (3, 5, 3 * 8, 512 // 8)
    assert kv.conv.dtype == jnp.float32
    assert eng.state_config.bytes_per_slot() == 3 * (16 * 512 * 4
                                                     + 3 * 512 * 4)
    text = ms.describe_layer_kinds(eng.spec)
    assert text.count("Mamba state-space mixer (no pages)") == 2
    assert "layers 1-1: full, no positions, dense FFN" in text
    # tokens x layers of the pages: one layer holds them, not four
    dead, resident = eng.kv_window_dead_tokens()
    assert (dead, resident) == (0, eng.scheduler.seqs[1].seen_tokens)


def test_slots_are_taken_at_admission_and_freed_at_flush(built):
    _, model, params = built
    eng = engine_for(model, params)
    before = dict(tracer.totals)
    gained = lambda k: tracer.totals.get(k, 0) - before.get(k, 0)
    p = np.arange(5, dtype=np.int32)
    eng.put([1, 2, 3], [p, p, p])
    assert eng.state_slots() == (3, 3, 4)
    assert len({eng.scheduler.seqs[u].state_slot for u in (1, 2, 3)}) == 3
    eng.flush([2])
    assert eng.state_slots() == (2, 3, 4)
    eng.flush([1, 3])
    assert eng.state_slots() == (0, 3, 4)
    assert gained("serve/state_slots/taken") == 3
    assert gained("serve/state_slots/freed") == 3
    alloc = StateSlotAllocator(2)
    alloc.take(), alloc.take()
    with pytest.raises(RuntimeError, match="state slots are taken"):
        alloc.take()


def test_adapter_stacks_a_tree_per_run_and_layer_types_follow_the_period():
    cfg = JambaConfig.jamba2_3b()
    types = cfg.layer_types
    assert len(types) == 28 and types.count(ATTENTION) == 2
    assert [i for i, t in enumerate(types) if t == ATTENTION] == [7, 21]
    assert cfg.head_dim == 128 and cfg.mamba_d_inner == 5120
    with pytest.raises(ValueError, match="num_experts"):
        JambaConfig(num_experts=16)
    cfg, model, params = build()
    spec, weights = adapters.adapt_model("jamba", params, cfg)
    assert [(n, rs.mamba is not None) for rs, _, n in ms.layer_runs(spec)] \
        == [(1, True), (1, False), (2, True)]
    assert spec.tied_lm_head and spec.rope_theta is None
    assert spec.mamba == {"d_inner": 512, "d_state": 16, "dt_rank": 16,
                          "d_conv": 4}
    stacks = weights["layers"]
    assert "mamba" in stacks[0] and "wq" not in stacks[0]
    assert "wq" in stacks[1] and "mamba" not in stacks[1]
    assert stacks[2]["mamba"]["A_log"].shape == (2, 16, 512)
    assert "lm_head" not in weights


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


def test_page_movers_are_refused(served, built):
    eng = served[0]
    with pytest.raises(NotImplementedError, match="export_kv"):
        eng.export_kv(1)
    with pytest.raises(NotImplementedError, match="import_kv"):
        eng.import_kv(77, [1, 2, 3], np.zeros((1,)), np.zeros((1,)))
    with pytest.raises(NotImplementedError, match="preemption='offload'"):
        eng.serving_frontend(config={"preemption": "offload"})
    with pytest.raises(NotImplementedError, match="speculative verify step"):
        rm.build_verify_step(eng.spec, 3)


def test_recompute_preemption_prefills_again_from_a_zeroed_slot(built):
    """``preemption: recompute`` is served: the victim is flushed (its slot
    freed), and readmission prefills prompt + generated tokens again into
    whatever slot it then takes, from zero."""
    _, model, params = built
    classes = [{"name": n, "priority": p, "ttft_slo_ms": 1e6,
                "tbt_slo_ms": 1e6} for n, p in (("hi", 2), ("lo", 0))]
    eng = engine_for(
        model, params,
        state_manager={**ENGINE["state_manager"], "max_context": 176,
                       "max_ragged_batch_size": 4 + 2 * 32,
                       "prefill_chunk_size": 32},
        kv_cache={"block_size": 16, "num_blocks": 10},
        serving={"decode_slice": 4, "idle_wait_s": 0.005,
                 "classes": classes, "preemption": "recompute"})
    rng = np.random.RandomState(0)
    p_lo, p_hi = (rng.randint(0, 256, size=(n,)).astype(np.int32)
                  for n in (24, 112))
    eng.put([5], [p_lo])
    want = [int(t) for t in eng.decode_pipeline([5]).run(40)[0]]
    eng.flush([5])
    fe = eng.serving_frontend()
    h_lo = fe.submit(p_lo, priority="lo", max_new_tokens=40)
    for _ in range(5):
        fe.step()
    h_hi = fe.submit(p_hi, priority="hi", max_new_tokens=8)
    for _ in range(400):
        if h_lo.finished and h_hi.finished:
            break
        fe.step()
    assert fe.stats.recompute_preemptions >= 1
    assert h_lo.tokens == want and len(h_hi.tokens) == 8
    fe.close()
    assert eng.state_slots()[0] == 0


# --------------------------------------------------------------------------- #
# a model without such layers: its programs carry no state
# --------------------------------------------------------------------------- #

def _llama_programs(head_dim=16):
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    cfg = LlamaConfig.tiny(vocab_size=128, max_position_embeddings=256,
                           hidden_size=4 * head_dim)
    model = LlamaForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        {"input_ids": jnp.zeros((1, 8), jnp.int32)})["params"]
    spec, weights = adapters.adapt_model("llama", params, cfg)
    S, MB = 4, 4
    kv = jnp.zeros((spec.num_layers, 9, 2, spec.num_kv_heads, 16,
                    spec.head_dim), jnp.float32)
    i32 = lambda *s: jnp.zeros(s, jnp.int32)
    step = (i32(S), i32(S), i32(S, MB), jnp.ones((S,), jnp.int32),
            jax.random.PRNGKey(0), jnp.float32(1.0))
    batch = {"chunk_tokens": i32(32), "chunk_positions": i32(32),
             "chunk_ntok": i32(2), "chunk_block_tables": i32(2, MB),
             "chunk_q0": i32(2), "chunk_ctx_lens": i32(2),
             "decode_tokens": i32(S), "decode_positions": i32(S),
             "decode_block_tables": i32(S, MB), "decode_ctx_lens": i32(S),
             "kv_dest": i32(32 + S), "row_seg": i32(32), "page_ids": i32(4),
             "page_rows": i32(4), "page_fill": i32(4)}
    pick = lambda keys: {k: batch[k] for k in keys}
    return spec, weights, kv, {
        # heads 16 wide take the in-layer write, 128 wide the side buffer
        "serve_decode_step": (rm.build_decode_step(spec), step),
        "serve_paged_pass": (rm.build_ragged_forward(spec),
                             (pick(rm.PAGED_PASS_KEYS),)),
        "serve_prefill_packed": (rm.build_prefill_forward(spec),
                                 (pick(rm.PREFILL_PASS_KEYS),)),
    }


@pytest.mark.parametrize("program,head_dim", [
    ("serve_decode_step", 16), ("serve_decode_step", 128),
    ("serve_paged_pass", 16), ("serve_prefill_packed", 16)])
def test_programs_of_a_model_without_mamba_layers_carry_no_state(program,
                                                                 head_dim):
    """They take the bare page pool and their descriptors, and return the
    bare page pool: no argument and no result is a state pool or a slot."""
    spec, weights, kv, programs = _llama_programs(head_dim)
    assert rm.side_buffer_fits(spec, 1, False, None) == (head_dim == 128)
    assert spec.mamba is None and ms.num_state_layers(spec) == 0
    assert ms._pool_bases(spec) == [0]
    fwd, args = programs[program]
    jaxpr = jax.make_jaxpr(fwd)(weights, kv, *args)
    n_in = len(jax.tree_util.tree_leaves((weights, kv, args)))
    assert len(jaxpr.jaxpr.invars) == n_in
    out = jax.eval_shape(fwd, weights, kv, *args)
    new_kv = out[-1]
    assert not isinstance(new_kv, (tuple, StatefulKV))
    assert new_kv.shape == kv.shape and new_kv.dtype == kv.dtype
    shapes = {tuple(v.aval.shape) for e in jaxpr.jaxpr.eqns
              for v in e.outvars if hasattr(v.aval, "shape")}
    assert not any(len(s) == 4 and s[-2] == 16 and s[-1] % 128 == 0
                   for s in shapes if s != kv.shape[-4:])
    if "decode" in program:
        with pytest.raises((AssertionError, ValueError, TypeError)):
            jax.eval_shape(fwd, weights, kv, *args, jnp.zeros((4,), jnp.int32))


def test_a_bfloat16_state_pool_shows_in_the_state_the_programs_leave(built):
    """What the benchmark's check on the chip holds the state's precision
    by: the state the engine's own programs leave after a prompt and fused
    decode steps. With the pool in float32 it is the reference's to 1e-5
    (``test_state_after_the_run_is_the_reference_state``); the same programs
    over a pool that holds ``h`` in bfloat16 leave one as far from it as the
    reference's control does (its state rounded after every token)."""
    cfg, model, params = built
    eng = engine_for(model, params)
    kv = eng.kv.kv
    eng.kv.update(StatefulKV(kv.pages, kv.ssm.astype(jnp.bfloat16), kv.conv))
    prompt = np.random.default_rng(5).integers(0, 256, 70).astype(np.int32)
    eng.put([1], [prompt])
    toks = eng.decode_pipeline([1]).run(48)[0]
    ids = np.concatenate([prompt, toks])
    state = np.swapaxes(eng.sequence_state(1), 1, 2)
    want = np.asarray(reference(cfg, params, ids, with_state=True)[1])
    control = np.asarray(reference(cfg, params, ids, with_state=True,
                                   state_dtype=jnp.bfloat16)[1])
    assert eng.kv.kv.ssm.dtype == jnp.bfloat16
    assert not close(state, want, 1e-3) and not close(control, want, 1e-3)
    assert close(state, want, 5e-2)
