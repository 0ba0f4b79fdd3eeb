"""Sliding-window serving (Mistral/Qwen2) in the v2 ragged path.

Parity role: the reference serves windowed models natively in v2
(``inference/v2/model_implementations/mistral``); round-3 verdict item 3
asked for a window mask in the paged kernels + page-ring reuse so windowed
models serve beyond the window with bounded KV, with logits parity against
the dense windowed path (models/llama.py sliding_window attention).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas.paged_attention import (
    paged_chunk_attention_batched, paged_chunk_attention_batched_reference,
    paged_decode_attention, paged_decode_attention_reference,
    paged_decode_attention_step, paged_decode_attention_step_reference)


def _mk(key, *shape, k=0):
    return jax.random.normal(jax.random.fold_in(key, k), shape, jnp.float32)


@pytest.mark.parametrize("window", [8, 20, 1000])
def test_windowed_paged_decode_matches_reference(window):
    key = jax.random.PRNGKey(0)
    NB, bs, Hkv, D, S, H = 24, 8, 2, 128, 3, 4
    kv = _mk(key, NB, 2, Hkv, bs, D, k=1)
    q = _mk(key, S, H, D, k=3)
    bts = jnp.asarray(np.arange(S * 8).reshape(S, 8) % NB, jnp.int32)
    cls_ = jnp.asarray([5, 33, 61], jnp.int32)
    o = paged_decode_attention(q, kv, bts, cls_, window=window)
    o_ref = paged_decode_attention_reference(q, kv, bts, cls_,
                                             window=window)
    assert float(jnp.max(jnp.abs(o - o_ref))) < 2e-2


def test_windowed_decode_step_matches_reference():
    key = jax.random.PRNGKey(1)
    NB, bs, Hkv, D, S, H, W = 24, 8, 2, 128, 3, 4, 20
    kv = _mk(key, NB, 2, Hkv, bs, D, k=1)
    q = _mk(key, S, H, D, k=3)
    kn, vn = _mk(key, S, Hkv, D, k=4), _mk(key, S, Hkv, D, k=5)
    bts = jnp.asarray(np.arange(S * 8).reshape(S, 8) % NB, jnp.int32)
    cls_ = jnp.asarray([5, 33, 61], jnp.int32)
    o, kvf = paged_decode_attention_step(q, kn, vn, kv, bts, cls_,
                                         window=W)
    o_r, kvr = paged_decode_attention_step_reference(
        q, kn, vn, kv, bts, cls_, window=W)
    assert float(jnp.max(jnp.abs(o - o_r))) < 2e-2
    assert float(jnp.max(jnp.abs(kvf - kvr))) == 0.0


def test_windowed_chunk_attention_matches_reference():
    key = jax.random.PRNGKey(2)
    NB, bs, Hkv, D, H, W = 24, 8, 2, 128, 4, 20
    kv = _mk(key, NB, 2, Hkv, bs, D, k=1)
    C, NC = 16, 2
    qc = _mk(key, NC, C, H, D, k=6)
    btc = jnp.asarray(np.arange(NC * 8).reshape(NC, 8) % NB, jnp.int32)
    q0s = jnp.asarray([24, 40], jnp.int32)
    ctxs = jnp.asarray([40, 56], jnp.int32)
    oc = paged_chunk_attention_batched(qc, kv, btc, q0s, ctxs, window=W)
    oc_r = paged_chunk_attention_batched_reference(qc, kv, btc, q0s,
                                                   ctxs, window=W)
    assert float(jnp.max(jnp.abs(oc - oc_r))) < 2e-2


# --------------------------------------------------------------------------- #
# engine level: serve a windowed model beyond its window, parity vs the dense
# windowed forward (models/llama.py), ring-bounded physical KV
# --------------------------------------------------------------------------- #

def _windowed_engine(window=16, max_context=96):
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    cfg = LlamaConfig(vocab_size=128, hidden_size=64, intermediate_size=128,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=2, max_position_embeddings=256,
                      sliding_window=window, dtype=jnp.float32)
    model = LlamaForCausalLM(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(1),
                                 {"input_ids": jnp.zeros((1, 8), jnp.int32)}
                                 )["params"]
    engine = InferenceEngineV2(
        model=model, model_parameters=params,
        config={"state_manager": {"max_tracked_sequences": 2,
                                  "max_ragged_sequence_count": 2,
                                  "max_ragged_batch_size": 40,
                                  "prefill_chunk_size": 8,
                                  "max_context": max_context},
                "kv_cache": {"block_size": 8}, "dtype": jnp.float32})
    return engine, model, params


def test_windowed_engine_prefill_parity_across_boundary(eight_devices):
    engine, model, params = _windowed_engine()
    assert engine.spec.window == 16
    rng = np.random.RandomState(3)
    prompt = rng.randint(0, 128, size=(40,)).astype(np.int32)  # 40 > window
    logits_v2 = np.asarray(engine.put([1], [prompt])[0], np.float32)
    logits_v1 = np.asarray(model.apply(
        {"params": params}, prompt[None],
        method=type(model).forward_logits)[0, -1], np.float32)
    rel = np.max(np.abs(logits_v2 - logits_v1)) / \
        max(1.0, np.max(np.abs(logits_v1)))
    assert rel < 5e-2, rel


def test_windowed_engine_decode_parity_and_ring_bound(eight_devices):
    engine, model, params = _windowed_engine()
    rng = np.random.RandomState(4)
    prompt = rng.randint(0, 128, size=(40,)).astype(np.int32)
    engine.put([1], [prompt])
    from tests.unit.test_multistep_decode import dense_greedy
    ids = engine.decode_pipeline([1]).run(30)   # ctx 40 -> 70: window slides
    seq = engine.scheduler.seqs[1]
    assert len(set(seq.blocks)) <= engine.scheduler.ring_pages
    ref_ids = dense_greedy(model, params, [prompt], 30)
    assert np.mean(ref_ids[0] == ids[0]) >= 0.9


def test_window_at_or_above_max_context_is_dropped(eight_devices):
    # max_context <= window: full attention is exactly equivalent; the spec
    # drops the window so the kernels skip the masks
    engine, _, _ = _windowed_engine(window=96, max_context=96)
    assert engine.spec.window is None
    assert engine.scheduler.ring_pages is None


def test_ring_frees_each_physical_page_once(eight_devices):
    engine, _, _ = _windowed_engine()
    rng = np.random.RandomState(5)
    engine.put([1], [rng.randint(0, 128, size=(40,)).astype(np.int32)])
    engine.decode_pipeline([1]).run(30)
    free_before = engine.allocator.free_blocks
    used = len(set(engine.scheduler.seqs[1].blocks))
    engine.flush([1])
    assert engine.allocator.free_blocks == free_before + used


def test_window_one_chunk_boundary_finalizes():
    """window=1 with ctx-1 on a chunk boundary: the first-real-chunk clamp
    must keep one chunk running so finalize writes the output (round-4
    review finding — previously returned uninitialized garbage)."""
    key = jax.random.PRNGKey(7)
    NB, bs, Hkv, D, S, H = 24, 8, 2, 128, 3, 4
    kv = _mk(key, NB, 2, Hkv, bs, D, k=1)
    q = _mk(key, S, H, D, k=3)
    kn, vn = _mk(key, S, Hkv, D, k=4), _mk(key, S, Hkv, D, k=5)
    bts = jnp.asarray(np.arange(S * 9).reshape(S, 9) % NB, jnp.int32)
    for W in (1, 2):
        for ctx in (65, 64, 17):
            cls_ = jnp.asarray([ctx, ctx - 1, max(ctx - 2, 1)], jnp.int32)
            o, _ = paged_decode_attention_step(q, kn, vn, kv, bts,
                                               cls_, window=W)
            o_r, _ = paged_decode_attention_step_reference(
                q, kn, vn, kv, bts, cls_, window=W)
            assert float(jnp.max(jnp.abs(o - o_r))) < 2e-2, (W, ctx)


@pytest.mark.parametrize("burst", [24, 8])
def test_windowed_flush_on_the_ring_leaves_the_per_step_loops_bytes(
        burst, monkeypatch, eight_devices):
    """A windowed model whose heads are 128 wide serves through the decode
    step's side buffer (``ring_covers(2)`` holds, and the ring covers each
    run's reservation): two sequences past their window, so every write
    lands on a ring page that once held an older logical page. After 24
    tokens — one pipeline run, or three runs of 8, each reserving anew on the
    ring — the pool holds, byte for byte, what the step's other form (each
    layer's kernel writing its rows) leaves there. One layer: its K/V rows
    depend on no attention output, so the two forms must agree exactly."""
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    cfg = LlamaConfig(vocab_size=128, hidden_size=256, intermediate_size=256,
                      num_hidden_layers=1, num_attention_heads=2,
                      num_key_value_heads=2, max_position_embeddings=256,
                      sliding_window=16, dtype=jnp.float32)
    model = LlamaForCausalLM(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(3),
                                 {"input_ids": jnp.zeros((1, 8), jnp.int32)}
                                 )["params"]
    rng = np.random.RandomState(6)
    prompts = [rng.randint(0, 128, size=(n,)).astype(np.int32)
               for n in (40, 33)]

    def serve():
        engine = InferenceEngineV2(
            model=model, model_parameters=params,
            config={"state_manager": {"max_tracked_sequences": 2,
                                      "max_ragged_sequence_count": 2,
                                      "max_ragged_batch_size": 40,
                                      "prefill_chunk_size": 8,
                                      "max_context": 96},
                    "kv_cache": {"block_size": 8}, "dtype": jnp.float32})
        assert engine.spec.window == 16 and engine.spec.head_dim == 128
        assert engine.scheduler.ring_covers(2)
        engine.put([1, 2], list(prompts))
        pipe = engine.decode_pipeline([1, 2])
        ids = np.concatenate([pipe.run(burst) for _ in range(24 // burst)],
                             axis=1)
        for u in (1, 2):       # the ring wrapped: fewer pages than logical
            seq = engine.scheduler.seqs[u]
            assert len(set(seq.blocks)) <= engine.scheduler.ring_pages
            assert len(set(seq.blocks)) < -(-seq.seen_tokens // 8)
        return ids, np.asarray(engine.kv.kv)[:, :engine.scratch_block]

    from deepspeed_tpu.inference.v2 import ragged_model
    flushes = []
    flush = ragged_model.paged_kv_row_write
    monkeypatch.setattr(
        ragged_model, "paged_kv_row_write",
        lambda *a, **kw: (flushes.append(a[5]), flush(*a, **kw))[1])
    got_ids, got = serve()
    assert set(flushes) == {1}
    traced = len(flushes)
    # the other form: each layer's kernel writes its rows, nothing flushes
    monkeypatch.setattr(ragged_model, "side_buffer_fits",
                        lambda *a, **kw: False)
    want_ids, want = serve()
    assert len(flushes) == traced
    np.testing.assert_array_equal(got_ids, want_ids)
    np.testing.assert_array_equal(got, want)
    assert np.count_nonzero(got) > 0
