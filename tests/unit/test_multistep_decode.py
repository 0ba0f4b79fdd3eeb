"""The fused decode step, chained by the pipeline, must reproduce the
per-token serving loop exactly.

Greedy decode over the v2 engine twice from the same prompt state: once via
the standard one-pass-per-token loop (sample_next + put), once via
``decode_pipeline(uids).run(n)`` — n dispatches of the one decode-step
program.  Token streams and the engine's continuation state (next sample
after the run) must match. (The file's name is from when one program ran
the n steps; PR 45 deleted that program and moved its tests here.)
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM


def _build_engine(seed=0):
    import jax
    cfg = LlamaConfig.tiny(vocab_size=128, max_position_embeddings=128)
    model = LlamaForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(seed),
                        {"input_ids": jnp.zeros((1, 8), jnp.int32)})["params"]
    engine = InferenceEngineV2(
        model=model, model_parameters=params,
        config={"dtype": jnp.float32,
                "state_manager": {"max_tracked_sequences": 4,
                                  "max_ragged_sequence_count": 4,
                                  "max_ragged_batch_size": 32,
                                  "max_context": 128},
                "kv_cache": {"block_size": 16}})
    return engine


PROMPTS = [np.array([3, 14, 15, 92, 6], np.int32),
           np.array([27, 18, 28, 18], np.int32),
           np.array([31, 41, 59, 26, 53, 58], np.int32)]
N_STEPS = 7


def _loop_decode(engine, uids, n):
    outs = [[] for _ in uids]
    for _ in range(n):
        ids = engine.sample_next(uids)
        for i, t in enumerate(ids):
            outs[i].append(int(t))
        engine.put(uids, [np.asarray([t], np.int32) for t in ids])
    return outs


def dense_greedy(model, params, prompts, n):
    """The dense model's greedy continuation of each prompt, ``[len(prompts),
    n]``: ONE jitted ``forward_logits`` at one padded length for all rows
    and steps, the logits read at ``len - 1`` (attention is causal, so what
    lies after a row's tokens is invisible to them)."""
    lens = np.asarray([len(p) for p in prompts])
    buf = np.zeros((len(prompts), int(lens.max()) + n), np.int32)
    for row, p in zip(buf, prompts):
        row[:len(p)] = p
    fwd = jax.jit(lambda ids: model.apply(
        {"params": params}, ids, method=type(model).forward_logits))
    rows = np.arange(len(prompts))
    for step in range(n):
        logits = np.asarray(fwd(buf))
        buf[rows, lens + step] = np.argmax(
            logits[rows, lens + step - 1], axis=-1)
    return np.stack([buf[i, l:l + n] for i, l in enumerate(lens)])


def test_decode_steps_matches_loop():
    uids = [0, 1, 2]
    e1 = _build_engine()
    e1.put(uids, PROMPTS)
    ref = _loop_decode(e1, uids, N_STEPS)
    ref_next = e1.sample_next(uids)

    e2 = _build_engine()
    e2.put(uids, PROMPTS)
    got = e2.decode_pipeline(uids).run(N_STEPS)
    assert got.shape == (3, N_STEPS)
    for i in range(3):
        assert list(got[i]) == ref[i], (i, list(got[i]), ref[i])
    # continuation state: the next sampled token must agree too
    got_next = e2.sample_next(uids)
    assert list(got_next) == list(ref_next)


def test_decode_steps_then_put_continues():
    uids = [0, 1]
    e = _build_engine()
    e.put(uids, PROMPTS[:2])
    first = e.decode_pipeline(uids).run(3)
    assert first.shape == (2, 3)
    nxt = e.sample_next(uids)
    # feed the sampled token through the normal path; engine state must accept it
    logits = e.put(uids, [np.asarray([t], np.int32) for t in nxt])
    assert logits.shape[0] == 2
    second = e.decode_pipeline(uids).run(2)
    assert second.shape == (2, 2)
    # lengths consistent: prompt + 3 + 1 + 2 tokens seen
    for u, p in zip(uids, PROMPTS[:2]):
        assert e.scheduler.seqs[u].seen_tokens == len(p) + 3 + 1 + 2


def test_decode_steps_across_block_boundary():
    """Generation crossing a KV block boundary (block_size=16) must stay
    consistent with the loop path."""
    uids = [0]
    prompt = [np.arange(12, dtype=np.int32)]
    e1 = _build_engine(seed=1)
    e1.put(uids, prompt)
    ref = _loop_decode(e1, uids, 10)     # crosses 16-token boundary
    e2 = _build_engine(seed=1)
    e2.put(uids, prompt)
    got = e2.decode_pipeline(uids).run(10)
    assert list(got[0]) == ref[0]


def test_v2_engine_qwen2_bias_logits():
    """Qwen2's q/k/v biases must survive the ragged adapter (regression: the
    adapter used to copy only kernels, silently dropping biases)."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    from deepspeed_tpu.module_inject import convert_hf_model

    torch.manual_seed(0)
    hf_cfg = transformers.Qwen2Config(vocab_size=97, hidden_size=32,
                                      intermediate_size=64,
                                      num_hidden_layers=2,
                                      num_attention_heads=4,
                                      num_key_value_heads=2,
                                      max_position_embeddings=64,
                                      use_sliding_window=False,
                                      attention_dropout=0.0)
    hf = transformers.Qwen2ForCausalLM(hf_cfg)
    hf.eval()
    module, cfg, variables = convert_hf_model(hf, dtype=jnp.float32)
    engine = InferenceEngineV2(
        model=module, model_parameters=variables["params"], family="llama",
        config={"dtype": jnp.float32,
                "state_manager": {"max_tracked_sequences": 2,
                                  "max_ragged_sequence_count": 2,
                                  "max_ragged_batch_size": 32,
                                  "max_context": 64},
                "kv_cache": {"block_size": 16}})
    ids = np.random.RandomState(0).randint(0, 97, size=(1, 10)).astype(np.int32)
    got = engine.put([0], [ids[0]])[0]        # last-token logits
    with torch.no_grad():
        ref = hf(input_ids=torch.tensor(ids, dtype=torch.long)) \
            .logits[0, -1].float().numpy()
    np.testing.assert_allclose(got, ref, atol=2e-3, rtol=1e-3)


def test_sidebuf_multistep_matches_dense_model(eight_devices):
    """The decode step's side-buffer form (head_dim % 128 == 0), chained by
    the pipeline, must match the dense model's greedy continuation exactly,
    across page boundaries and with per-sequence context lengths."""
    cfg = LlamaConfig(vocab_size=128, hidden_size=256, intermediate_size=256,
                      num_hidden_layers=2, num_attention_heads=2,
                      num_key_value_heads=2, max_position_embeddings=128,
                      dtype=jnp.float32)
    assert cfg.head_dim == 128
    model = LlamaForCausalLM(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 {"input_ids": jnp.zeros((1, 8), jnp.int32)}
                                 )["params"]
    eng = InferenceEngineV2(
        model=model, model_parameters=params,
        config={"state_manager": {"max_tracked_sequences": 3,
                                  "max_ragged_sequence_count": 3,
                                  "max_ragged_batch_size": 80,
                                  "prefill_chunk_size": 16,
                                  "max_context": 128},
                "kv_cache": {"block_size": 8}, "dtype": jnp.float32})
    rng = np.random.RandomState(0)
    lens = [9, 16, 23]                       # straddle the 8-token pages
    prompts = [rng.randint(0, 128, size=(n,)).astype(np.int32) for n in lens]
    uids = [1, 2, 3]
    eng.put(uids, list(prompts))
    ids = eng.decode_pipeline(uids).run(20)  # crosses 2-3 page boundaries
    # and the written pools must let a SECOND run continue correctly
    ids2 = eng.decode_pipeline(uids).run(6)
    want = dense_greedy(model, params, prompts, 26)
    np.testing.assert_array_equal(ids, want[:, :20])
    np.testing.assert_array_equal(ids2, want[:, 20:])


# --------------------------------------------------------------------------- #
# the K/V row write after the layers: rows, not pages
# --------------------------------------------------------------------------- #

_L, _NB, _HKV, _BS, _D, _MB = 2, 8, 2, 128, 128, 6
_SCRATCH = _NB - 1


def _flush_case(case, C):
    """(block_tables [S, MB], prefix [S], rows whose side values are equal)."""
    bt = np.stack([np.arange(_MB), np.arange(_MB)[::-1]]).astype(np.int32)
    if case == "page_edge":
        # the step at slot 127 of a page, the next in slot 0 of a new one
        return bt, np.array([_BS - 1, 3 * _BS - 1], np.int32), ()
    if case == "pad_rows":
        # a bucket of 4 with 2 live rows: the pad rows point wholly at the
        # scratch page, at position 0, and carry the same token
        pad = np.full((2, _MB), _SCRATCH, np.int32)
        return (np.concatenate([bt, pad]),
                np.array([5, _BS + 9, 0, 0], np.int32), (2, 3))
    if case == "ring":
        # a windowed sequence: logical pages wrap onto a ring of 3 physical
        ring = np.stack([np.arange(_MB) % 3, 3 + np.arange(_MB) % 3])
        return (ring.astype(np.int32),
                np.array([3 * _BS - 3, 5 * _BS - C], np.int32), ())
    if case == "past_table":
        # positions beyond the block table drop, the others land
        return bt, np.array([_MB * _BS - 3, _MB * _BS], np.int32), ()
    raise AssertionError(case)


def _reference_row_write(pool, scales, side_k, side_v, bt, prefix, C):
    """Row by row, in numpy: step j of sequence s goes to slot (prefix + j)
    % bs of page bt[s, (prefix + j) // bs] in every layer, K and V, head by
    head; nothing else changes. int8 pools take each row's quantized values
    and its scale at the tile offset kv*Hkv*bs + h*bs + slot."""
    from deepspeed_tpu.ops.pallas.paged_attention import kv_quantize_rows
    pool = pool.copy()
    sides = [np.asarray(side_k), np.asarray(side_v)]
    if scales is not None:
        scales = scales.copy()
        flat = scales.reshape(_L, _NB, -1)
        # jitted, as every writer of the pool is: XLA divides by the
        # constant 127 its own way there, one ulp off the eager result
        quant = [jax.jit(kv_quantize_rows)(jnp.asarray(x)) for x in sides]
        sides = [np.asarray(q) for q, _ in quant]
        side_scales = [np.asarray(s) for _, s in quant]
    for l in range(_L):
        for s in range(bt.shape[0]):
            for j in range(C):
                pos = int(prefix[s]) + j
                if pos // _BS >= bt.shape[1]:
                    continue
                page, slot = bt[s, pos // _BS], pos % _BS
                for kv in range(2):
                    for h in range(_HKV):
                        pool[l, page, kv, h, slot] = \
                            sides[kv][l, s, j * _HKV + h].astype(pool.dtype)
                        if scales is not None:
                            flat[l, page, (kv * _HKV + h) * _BS + slot] = \
                                side_scales[kv][l, s, j * _HKV + h]
    return pool, scales


@pytest.mark.parametrize("pool_dtype", ["bfloat16", "int8"])
@pytest.mark.parametrize("C", [1, 8])
@pytest.mark.parametrize("case", ["page_edge", "pad_rows", "ring",
                                  "past_table"])
def test_kv_flush_writes_rows_like_a_row_by_row_reference(case, C,
                                                          pool_dtype):
    """Pool bytes after a decode step (C = 1) and after a slab of 8 steps
    (the kernel keeps its step axis: ROADMAP D4b), twice in a row so the
    second write continues where the first stopped, equal a row-by-row
    reference write: same values, same dtype, every other byte of the pool
    (and of an int8 pool's scale tiles) untouched."""
    import ml_dtypes
    from deepspeed_tpu.ops.pallas.paged_attention import (
        _scale_tile_rows, paged_kv_row_write)
    kvq = pool_dtype == "int8"
    rng = np.random.RandomState(7)
    bt, prefix, twins = _flush_case(case, C)
    S = bt.shape[0]
    shape = (_L, _NB, 2, _HKV, _BS, _D)
    if kvq:
        pool = rng.randint(-127, 128, size=shape).astype(np.int8)
        r8 = _scale_tile_rows(_HKV, _BS)
        scales = rng.rand(_L, _NB, r8, 128).astype(np.float32)
        side_dtype = np.float32
    else:
        pool = rng.randn(*shape).astype(ml_dtypes.bfloat16)
        scales, side_dtype = None, ml_dtypes.bfloat16
    flush = jax.jit(paged_kv_row_write, static_argnums=(5,))
    for _ in range(2):
        side = [rng.randn(_L, S, C * _HKV, _D).astype(side_dtype)
                for _ in range(2)]
        for x in side:
            for s in twins[1:]:
                x[:, s] = x[:, twins[0]]
        want, want_sc = _reference_row_write(pool, scales, *side, bt, prefix,
                                             C)
        got = flush(jnp.asarray(pool), jnp.asarray(side[0]),
                    jnp.asarray(side[1]), jnp.asarray(bt),
                    jnp.asarray(prefix), C,
                    None if scales is None else jnp.asarray(scales))
        if kvq:
            got, got_sc = got
            np.testing.assert_array_equal(np.asarray(got_sc), want_sc)
            scales = want_sc
        assert got.dtype == pool.dtype and got.shape == shape
        np.testing.assert_array_equal(np.asarray(got), want)
        assert not np.array_equal(want, pool) or case == "past_table"
        pool, prefix = want, prefix + C
