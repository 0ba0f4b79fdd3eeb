"""Generation by diffusion over blocks (inference/v2/blocks/ + ragged_model.
build_block_step + the chunk kernel's block rule + the scheduler's cut).

The family under test is sdar_moe at its ``tiny`` preset, float32. What the
system generates is held to the plain reference of the benchmark
(``chipbench/reference/sdar_ref.py``: a cache-free whole-sequence forward at
every pass); docs/SERVING.md "Block-diffusion generation" describes the
design under test.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.families import sdar as family
from chipbench.reference import sdar_ref
from deepspeed_tpu.inference.v2 import adapters, ragged_model as rm
from deepspeed_tpu.inference.v2.attention import BLOCK_DIFFUSION_MSG
from deepspeed_tpu.inference.v2.blocks import BlockDecodePipeline
from deepspeed_tpu.inference.v2.config_v2 import BlockDecodeConfig
from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
from deepspeed_tpu.inference.v2.pipeline import DecodePipeline
from deepspeed_tpu.inference.v2.spec import SpecDecodePipeline
from deepspeed_tpu.models.sdar import SdarMoeConfig, SdarMoeForCausalLM
from deepspeed_tpu.ops.pallas import paged_attention as pa

B = 4
CFG = SdarMoeConfig.tiny(dtype=jnp.float32)
FILE = {k: getattr(CFG, k) for k in family.MODEL_KEYS}
MASK = int(CFG.mask_token_id)


@pytest.fixture(scope="module")
def mp():
    model = SdarMoeForCausalLM(CFG)
    params = model.init(jax.random.PRNGKey(1),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


@pytest.fixture(scope="module")
def reference(mp):
    """(weights under the reference's names, hp)."""
    return (family.reference_weights(mp[1], FILE),
            family.reference_hp(FILE))


def _engine(mp, warmup=False, **over):
    conf = {"dtype": jnp.float32,
            "state_manager": {"max_tracked_sequences": 8,
                              "max_ragged_sequence_count": 4,
                              "max_ragged_batch_size": 4 + 2 * 16,
                              "max_context": 128, "prefill_chunk_size": 16},
            "kv_cache": {"block_size": 16},
            "block_decode": {"denoising_steps": 2}}
    for k, v in over.items():
        conf[k] = {**conf.get(k, {}), **v} if isinstance(v, dict) else v
    if warmup:
        conf["compile"] = {"warmup": True}
    return InferenceEngineV2(model=mp[0], model_parameters=mp[1], config=conf)


@pytest.fixture(scope="module")
def static_engine(mp):
    return _engine(mp, warmup=True)


@pytest.fixture(scope="module")
def dynamic_engine(mp):
    return _engine(mp, block_decode={
        "denoising_steps": 2, "remasking": "low_confidence_dynamic",
        "confidence_threshold": 0.02})


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(0, 200, size=n).astype(
        np.int32)


def _held_to_reference(got, prompt, n, reference, rule=None, steps=2):
    """The system's tokens are the reference's, or differ first where the
    reference's own choice turns on rounding (its two best confidences, or
    its two best logits, within 1e-4)."""
    weights, hp = reference
    trace = []
    want = sdar_ref.generate(weights, prompt, n, hp, steps, rule, trace=trace)
    if list(got) == want:
        return
    first = next(i for i, (a, b) in enumerate(zip(got, want)) if a != b)
    close = any(
        at <= len(prompt) - len(prompt) % B + first < at + B and sorted(
            c for c in conf if c > 0)[-2:][0] > max(conf) - 1e-4
        for at, _, _, conf, _ in trace)
    assert close, (first, list(got), want)


# --------------------------------------------------------------------------- #
# the kernels' block rule
# --------------------------------------------------------------------------- #

def _chunk_case(seed=0, slots=3, rows=8, heads=4, kv_heads=2, d=16, bs=8,
                pages=6):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((slots, rows, heads, d)), jnp.float32)
    kv = jnp.asarray(rng.standard_normal((slots * pages + 1, 2, kv_heads, bs,
                                          d)), jnp.float32)
    tables = jnp.asarray(rng.permutation(slots * pages).reshape(
        slots, pages), jnp.int32)
    starts = jnp.asarray([0, 16, 24][:slots], jnp.int32)
    return q, kv, tables, starts, starts + rows


def _dense(q, kv, tables, starts, ctx, block):
    """The block rule written out: key s is visible to query t iff
    ``s // block <= t // block`` (and s < ctx)."""
    out = []
    for sl in range(q.shape[0]):
        k_seq, v_seq = pa._gather_seq(kv, tables[sl][None],
                                      q.shape[2] // kv.shape[2])
        k_seq, v_seq = k_seq[0], v_seq[0]
        t = int(starts[sl]) + np.arange(q.shape[1])
        s = np.arange(k_seq.shape[0])
        seen = (s[None] // block <= t[:, None] // block) & (s[None] < int(
            ctx[sl]))
        sc = jnp.einsum("qhd,khd->hqk", q[sl], k_seq) / q.shape[-1] ** 0.5
        p = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hqk,khd->qhd", p, v_seq))
    return jnp.stack(out)


@pytest.mark.parametrize("block", [1, 2, 4, 8])
@pytest.mark.parametrize("twin", ["kernel", "reference"])
def test_chunk_twins_follow_the_block_rule(twin, block):
    """Both chunk-kernel twins against the dense mask, prompt-chunk slots at
    contexts 0, 16 and 24 (a page of 8: the walk's last page follows the
    block's last row)."""
    args = _chunk_case()
    fn = pa.paged_chunk_attention_batched if twin == "kernel" \
        else pa.paged_chunk_attention_batched_reference
    got = fn(*args, causal_block=block)
    np.testing.assert_allclose(np.asarray(got), np.asarray(
        _dense(*args, block)), atol=2e-5, rtol=2e-5)


def test_block_one_is_todays_causal_result_bit_for_bit():
    args = _chunk_case(seed=3)
    today = pa.paged_chunk_attention_batched(*args)
    assert np.array_equal(np.asarray(today), np.asarray(
        pa.paged_chunk_attention_batched(*args, causal_block=1)))
    # .. and traces to the same program: the rule at 1 adds no operation
    text = lambda **kw: jax.jit(lambda *a: pa.paged_chunk_attention_batched(
        *a, **kw)).lower(*args).as_text()
    assert text() == text(causal_block=1)
    assert text() != text(causal_block=4)


def test_a_block_that_is_no_power_of_two_is_refused():
    with pytest.raises(ValueError, match="power of two"):
        pa.paged_chunk_attention_batched(*_chunk_case(), causal_block=3)


def test_the_block_rule_reads_its_own_rows_past_the_first():
    """One slot per sequence, ``B`` query rows at the context's end: every
    row sees all ``B`` (what the block step asks of the kernel)."""
    q, kv, tables, _, _ = _chunk_case(rows=4)
    starts = jnp.asarray([8, 20, 0], jnp.int32)
    got = pa.paged_chunk_attention_batched(q, kv, tables, starts, starts + 4,
                                           causal_block=4)
    want = _dense(q, kv, tables, starts, starts + 4, 4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=2e-5)
    causal = pa.paged_chunk_attention_batched(q, kv, tables, starts,
                                              starts + 4)
    assert not np.allclose(np.asarray(got), np.asarray(causal), atol=1e-3)


# --------------------------------------------------------------------------- #
# configuration and refusals
# --------------------------------------------------------------------------- #

def test_block_decode_config_validation():
    assert BlockDecodeConfig(denoising_steps=3).transfer_schedule(8) == (3, 3,
                                                                         2)
    assert BlockDecodeConfig(denoising_steps=2).transfer_schedule(4) == (2, 2)
    with pytest.raises(ValueError, match="denoising_steps"):
        BlockDecodeConfig(denoising_steps=0)
    with pytest.raises(ValueError, match="remasking"):
        BlockDecodeConfig(remasking="random")
    with pytest.raises(ValueError, match="confidence_threshold"):
        BlockDecodeConfig(confidence_threshold=1.5)
    with pytest.raises(ValueError, match="exceeds the block"):
        BlockDecodeConfig(denoising_steps=8).transfer_schedule(4)


@pytest.mark.parametrize("what,over", [
    ("spec_decode.enabled", {"spec_decode": {"enabled": True}}),
    ("prefix_cache.enabled", {"prefix_cache": {"enabled": True}}),
    ("kv_quant.enabled", {"kv_quant": {"enabled": True}}),
    ("tensor_parallel > 1", {"tensor_parallel": 2}),
    ("lora.enabled", {"lora": {"enabled": True}}),
    ("attention.decode_splits > 1", {"attention": {"decode_splits": 2}}),
])
def test_what_is_not_built_beside_blocks_is_refused_by_name(mp, what, over):
    with pytest.raises(NotImplementedError) as e:
        _engine(mp, **over)
    assert what in str(e.value)
    assert BLOCK_DIFFUSION_MSG.split("{what}")[1] in str(e.value)


def test_a_window_beside_blocks_is_refused(mp, monkeypatch):
    from deepspeed_tpu.inference.v2.attention import AttentionKernelSpec
    from deepspeed_tpu.inference.v2.config_v2 import (
        RaggedInferenceEngineConfig)
    spec, _ = adapters.ADAPTERS["sdar_moe"](mp[1], CFG)
    spec.window = 64
    with pytest.raises(NotImplementedError, match="a sliding window"):
        AttentionKernelSpec.validate_engine_build(
            spec, RaggedInferenceEngineConfig.load(
                {"kv_cache": {"block_size": 16}}))


def test_a_slot_or_page_that_splits_a_block_is_refused(mp):
    with pytest.raises(ValueError, match="multiples of causal_block"):
        _engine(mp, state_manager={"prefill_chunk_size": 6,
                                   "max_ragged_batch_size": 4 + 2 * 6})


def test_offload_export_and_sampling_are_refused(mp, static_engine):
    e = static_engine
    with pytest.raises(NotImplementedError, match="preemption='offload'"):
        e.serving_frontend({"preemption": "offload"})
    e.put([90], [_prompt(8)])
    with pytest.raises(NotImplementedError, match="export_kv"):
        e.export_kv(90)
    e.flush([90])
    with pytest.raises(NotImplementedError, match="import_kv"):
        e.import_kv(91, [1, 2], np.zeros((0,)), np.zeros((4,)))
    with pytest.raises(NotImplementedError, match="sampling is not wired"):
        e.decode_pipeline([], do_sample=True)
    with pytest.raises(NotImplementedError, match="sampling is not wired"):
        e.generate([_prompt(8)], max_new_tokens=4, do_sample=True)


def test_the_block_step_is_for_such_a_spec_only(mp):
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    cfg = LlamaConfig.tiny()
    p = LlamaForCausalLM(cfg).init(jax.random.PRNGKey(0),
                                   jnp.zeros((1, 8), jnp.int32))["params"]
    spec, _ = adapters.ADAPTERS["llama"](p, cfg)
    assert spec.causal_block == 1 and spec.mask_token_id is None
    with pytest.raises(ValueError, match="diffusion over blocks"):
        rm.build_block_step(spec)


def test_a_pass_of_such_a_model_gives_no_decode_logits(static_engine):
    """A paged pass of a model that generates by blocks holds no decode row
    (the block step is its only decode): its second result has no row, where
    every other family's has one a tracked row — 78 MB a pass ENQUEUED at 128
    rows of 151,936 columns."""
    from deepspeed_tpu.inference.v2.ragged.ragged_batch import RaggedBatch
    e = static_engine
    host = RaggedBatch(num_slots=2, slot_size=16, max_sequences=4,
                       max_blocks=8).device_arrays()
    batch = {k: host[k] for k in rm.PAGED_PASS_KEYS}
    chunk, decode, _ = jax.eval_shape(rm.build_ragged_forward(e.spec),
                                      e.weights, e.kv.kv, batch)
    V = CFG.vocab_size
    assert chunk.shape == (2, V) and decode.shape == (0, V)
    import dataclasses
    causal = dataclasses.replace(e.spec, causal_block=1, mask_token_id=None)
    _, decode, _ = jax.eval_shape(rm.build_ragged_forward(causal), e.weights,
                                  e.kv.kv, batch)
    assert decode.shape == (4, V)


@pytest.mark.parametrize("held", ["host", "device"])
def test_the_layers_are_stacked_a_leaf_at_a_time(mp, held, monkeypatch):
    """``adapt_sdar`` waits for each leaf's stack before it begins the next
    (the device then never holds every layer twice); the numbers are
    ``_stack``'s, wherever the layers were handed over."""
    from deepspeed_tpu.inference.v2.adapters import _stacks
    waited = []
    ready = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: waited.append(x.shape) or ready(x))
    params = jax.device_get(mp[1]) if held == "host" else mp[1]
    _, weights = adapters.ADAPTERS["sdar_moe"](params, CFG)
    leaves = jax.tree_util.tree_leaves(weights["layers"])
    assert waited == [x.shape for x in leaves]
    assert all(isinstance(x, jax.Array) for x in leaves)
    layers = [jax.tree_util.tree_map(lambda x, i=i: x[i], weights["layers"])
              for i in range(CFG.num_hidden_layers)]
    for a, b in zip(leaves, jax.tree_util.tree_leaves(_stacks._stack(layers))):
        assert a.shape[0] == CFG.num_hidden_layers
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------------------------------- #
# the scheduler's cut
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("P", [3, 8, 9, 19])
def test_a_prompt_is_prefilled_in_whole_blocks(static_engine, P):
    e = static_engine
    prompt = _prompt(P, seed=P)
    e.scheduler.add_tokens(50, prompt)
    seq = e.scheduler.seqs[50]
    assert len(seq.pending) == P - P % B
    assert np.array_equal(seq.block_open, prompt[P - P % B:])
    while e.scheduler.has_pending():
        batch = e._run_pass()
        assert not batch.pure_prefill or not e.packed_prefill
        assert all(int(n) % B == 0 for n in batch.chunk_ntok)
        assert all(int(q) % B == 0 for q in batch.chunk_q0)
    assert seq.seen_tokens == P - P % B
    with pytest.raises(ValueError, match="takes a sequence's prompt once"):
        e.scheduler.add_tokens(50, prompt)
    e.flush([50])
    assert e.free_blocks == e.allocator.total_blocks


def test_the_packed_pass_is_off_and_the_warm_grid_is_block_steps(
        static_engine):
    e = static_engine
    assert not e.packed_prefill and e._pass_prefill is None
    assert sorted(e._block_progs._d) == [1, 2, 4]
    assert not e._step_progs._d and not e._verify_progs._d
    before = e.compiles
    e.generate([_prompt(9), _prompt(5, 1), _prompt(16, 2)], max_new_tokens=6)
    assert e.compiles == before          # in-grid traffic builds nothing


# --------------------------------------------------------------------------- #
# against the reference
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("P", [8, 9, 19, 3])
def test_generate_is_the_references_static(static_engine, reference, P):
    prompt = _prompt(P, seed=P)
    out = static_engine.generate([prompt], max_new_tokens=13)[0]
    assert out[:P] == prompt.tolist() and len(out) == P + 13
    _held_to_reference(out[P:], prompt, 13, reference)
    assert static_engine.free_blocks == static_engine.allocator.total_blocks


@pytest.mark.parametrize("P", [8, 9, 19, 3])
def test_generate_is_the_references_dynamic(dynamic_engine, reference, P):
    prompt = _prompt(P, seed=P)
    out = dynamic_engine.generate([prompt], max_new_tokens=13)[0]
    _held_to_reference(out[P:], prompt, 13, reference,
                       rule={"threshold": 0.02})
    assert dynamic_engine.free_blocks == dynamic_engine.allocator.total_blocks


def test_the_dynamic_rule_finishes_blocks_sooner(mp, static_engine):
    """At a threshold every confidence passes, a block is one denoise pass
    and a commit: fewer row-passes than the static schedule's three."""
    from deepspeed_tpu.monitor.trace import tracer
    eager = _engine(mp, block_decode={
        "denoising_steps": 2, "remasking": "low_confidence_dynamic",
        "confidence_threshold": 0.0})
    count = lambda: tracer.totals.get("serve/block/row_passes", 0.0)
    c0 = count()
    eager.generate([_prompt(8)], max_new_tokens=16)
    c1 = count()
    static_engine.generate([_prompt(8)], max_new_tokens=16)
    assert c1 - c0 == 4 * 2 and count() - c1 == 4 * 3


def test_paged_and_block_step_logits_are_the_references(static_engine,
                                                        reference):
    """The paged passes' logits at the last prefilled row, and the block
    step's at the block's rows pass by pass, against ``forward_logits`` over
    the whole sequence as the engine held it."""
    e = static_engine
    weights, hp = reference
    prompt = _prompt(37, seed=5)      # 36 prefilled in passes of 32 and 4
    got = e.put([7], [prompt])[0]
    seq = [int(t) for t in prompt[:36]]
    want = sdar_ref.forward_logits(weights, np.asarray(seq, np.int32), hp,
                                   rows=[35])[0]
    np.testing.assert_allclose(got, np.asarray(want), atol=2e-4, rtol=2e-4)
    pipe = e.decode_pipeline([7])
    # (a block row starts from its block: the prompt's logits are let go)
    assert 7 not in e._last_ref and 7 not in e._last_logits
    pipe.watch = [7]
    # one run of seven passes: past the first, a pass runs on the block the
    # pass before left on the device, and is drained a pass late
    pipe.run(7)
    assert [r["step"] for r in pipe.watched] == list(range(7))
    block = None          # what the pass before left; None: the host's next
    for rec in pipe.watched:
        assert rec["fresh"] == (block is None)
        if block is None:
            block = rec["fresh_ids"]
        at = rec["ctx"]
        assert at == len(seq)
        want = sdar_ref.forward_logits(
            weights, np.asarray(seq + block.tolist(), np.int32), hp,
            rows=np.arange(at, at + B))
        np.testing.assert_allclose(np.asarray(rec["logits"]),
                                   np.asarray(want), atol=2e-4, rtol=2e-4)
        if MASK in block:
            block = rec["after"]
        else:                   # that was the block's commit
            assert rec["n_take"] == 0 and (rec["after"] == block).all()
            seq += block.tolist()
            block = None
    assert len(seq) == 36 + 2 * B
    pipe.retire([7])
    e.flush([7])


def test_a_pass_of_mixed_phases_equals_the_rows_alone(static_engine):
    """Rows in their first block (opened by 0, 1 and 3 prompt tokens), in a
    denoise pass and at their commit share passes; each generates what it
    generates alone."""
    e = static_engine
    prompts = [_prompt(8, 1), _prompt(9, 2), _prompt(19, 3), _prompt(3, 4)]
    alone = [e.generate([p], max_new_tokens=11)[0] for p in prompts]
    # admitted a pass apart, so the rows are out of phase with each other
    uids = [20, 21, 22, 23]
    pipe = e.decode_pipeline([])
    outs = {u: [] for u in uids}
    for u, p in zip(uids, prompts):
        e.put([u], [p]) if len(p) >= B else e.scheduler.add_tokens(u, p)
        pipe.admit([u], budgets=[11])
        live = list(pipe.uids)
        for u2, toks in zip(live, pipe.run(1)):
            outs[u2] += toks
    while pipe.uids:
        live = list(pipe.uids)
        for u2, toks in zip(live, pipe.run(3)):
            outs[u2] += toks
    for u, p, want in zip(uids, prompts, alone):
        assert outs[u] == want[len(p):], u
    e.flush(uids)
    assert e.free_blocks == e.allocator.total_blocks


# --------------------------------------------------------------------------- #
# the pipeline's contract
# --------------------------------------------------------------------------- #

def test_the_three_pipelines_say_what_on_tokens_gets():
    assert DecodePipeline.token_batches is False
    assert SpecDecodePipeline.token_batches is True
    assert BlockDecodePipeline.token_batches is True


def test_on_tokens_gets_committed_blocks_and_may_retire(static_engine):
    e = static_engine
    e.put([30, 31], [_prompt(8, 6), _prompt(10, 7)])
    pipe = e.decode_pipeline([30, 31])
    seen = {30: [], 31: []}
    steps = []

    def on_tokens(j, uids, toks):
        assert uids == [30, 31] and len(toks) == 2
        steps.append([len(t) for t in toks])
        for u, t in zip(uids, toks):
            seen[u] += [int(x) for x in t]
        return [30] if len(seen[30]) >= 8 else None

    outs = pipe.run(9, on_tokens=on_tokens)
    # uid 30: blocks of 4 at passes 2, 5, 8; uid 31 (2 prompt tokens open its
    # first block: one denoise pass and the commit) 2 tokens at pass 1, then 4
    assert [s[0] for s in steps] == [0, 0, 4, 0, 0, 4, 0, 0, 0]
    assert [s[1] for s in steps] == [0, 2, 0, 0, 4, 0, 0, 4, 0]
    assert outs[0] == seen[30] and outs[1] == seen[31]
    assert pipe.uids == [31]            # 30 left at the end of the run
    assert e.scheduler.seqs[31].seen_tokens == 8 + 3 * B
    e.flush([30])
    pipe.retire([31])
    e.flush([31])
    assert e.free_blocks == e.allocator.total_blocks


def test_a_raising_callback_settles_state(static_engine):
    e = static_engine
    e.put([40], [_prompt(8, 8)])
    pipe = e.decode_pipeline([40])

    def boom(j, uids, toks):
        if any(len(t) for t in toks):
            raise RuntimeError("client went away")

    with pytest.raises(RuntimeError, match="client went away"):
        pipe.run(6, on_tokens=boom)
    assert pipe.uids == []
    assert e.scheduler.seqs[40].seen_tokens == 12      # the drained commit
    e.flush([40])
    assert e.free_blocks == e.allocator.total_blocks


def test_admit_validation(static_engine):
    e = static_engine
    pipe = e.decode_pipeline([])
    with pytest.raises(ValueError, match="not in steady decode state"):
        pipe.admit([77])
    e.put([60], [_prompt(8)])
    pipe.admit([60])
    with pytest.raises(ValueError, match="already in the pipeline"):
        pipe.admit([60])
    with pytest.raises(ValueError, match="budgets must align"):
        pipe.admit([60], budgets=[1, 2])
    pipe.retire([60])
    e.flush([60])


def test_budgets_cut_the_last_block_and_count_it(static_engine):
    from deepspeed_tpu.monitor.trace import tracer
    e = static_engine
    t = lambda name: tracer.totals.get(f"serve/block/{name}", 0.0)
    before = {n: t(n) for n in ("row_passes", "commit_row_passes",
                                "tokens_committed", "overhang_dropped")}
    e.put([61], [_prompt(10, 9)])        # 2 prompt tokens open the block
    pipe = e.decode_pipeline([])
    pipe.admit([61], budgets=[7])
    outs = pipe.run(12)
    assert len(outs[0]) == 7 and pipe.uids == []
    gained = {n: t(n) - v for n, v in before.items()}
    # blocks: 2 + 4 + (1 of 4): 2 + 3 + 3 row-passes, 3 of them commits; the
    # 2 left-over prompt tokens are not counted, the overhang of 3 is
    assert gained == {"row_passes": 8, "commit_row_passes": 3,
                      "tokens_committed": 7, "overhang_dropped": 3}
    e.flush([61])


def test_traced_passes_carry_what_their_rows_held(static_engine):
    from deepspeed_tpu.monitor.trace import tracer
    e = static_engine
    e.put([62, 63], [_prompt(16, 10), _prompt(8, 11)])
    pipe = e.decode_pipeline([62, 63])
    tracer.reset()
    tracer.configure(enabled=True, ring_size=256)
    try:
        pipe.run(3)
        spans = [r for r in tracer.iter_records()
                 if r[1] == "serve/block/step"]
        assert tracer.summary()["serve/block/dispatch"][0] == 3
    finally:
        tracer.reset()
    assert len(spans) == 3
    first, last = spans[0][5], spans[-1][5]
    assert first == dict(step=0, rows=2, masked=8, commits=0, ctx_tokens=24,
                         pages=3)
    assert last["commits"] == 2 and last["masked"] == 0
    pipe.retire([62, 63])
    e.flush([62, 63])


# --------------------------------------------------------------------------- #
# the frontend
# --------------------------------------------------------------------------- #

def test_the_frontend_generates_by_blocks(static_engine):
    e = static_engine
    prompts = [_prompt(9, 12), _prompt(16, 13), _prompt(3, 14)]
    want = [e.generate([p], max_new_tokens=10)[0][len(p):] for p in prompts]
    fe = e.serving_frontend({"preemption": "none", "decode_slice": 3})
    assert fe.admission.slice_tokens == e.block_reserve_tokens(3) == B * 3
    handles = [fe.submit(p, max_new_tokens=10) for p in prompts]
    for _ in range(200):
        if not fe.step():
            break
    for h, w in zip(handles, want):
        assert h.status == "finished" and list(h.tokens) == w
        # a request's first token is its first COMMIT; a block's tokens
        # arrive together (0 ms between siblings)
        assert h.ttft_ms is not None and len(h.tbt_ms) == 9
        assert sum(1 for g in h.tbt_ms if g == 0.0) >= 6
    fe.close()
    assert e.free_blocks == e.allocator.total_blocks
