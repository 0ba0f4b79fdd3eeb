"""Colocated rollout tests (``runtime/colocated.py`` + the swap wiring).

The contract under test: the WeightBridge's one jitted reshard program
reproduces the universal-checkpoint train->serve path byte-for-byte
(without the host/disk round-trip), swaps rebind the live serving
engine's weights with ZERO new compiles and byte-identical generation
vs a freshly built engine, the prefix cache self-invalidates by weight
version (a post-swap hit on stale KV is refused and re-prefilled), and
the frontend quiesces in-flight decode at a run boundary exactly like
preemption. docs/TRAINING.md + docs/SERVING.md "Colocated rollout"
describe the design."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.checkpoint import ds_to_universal, load_universal
from deepspeed_tpu.checkpoint.state import unflatten_into
from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
from deepspeed_tpu.inference.v2.prefix_cache import RadixPrefixCache
from deepspeed_tpu.inference.v2.ragged.blocked_allocator import BlockedAllocator
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHead
from deepspeed_tpu.runtime.colocated import RolloutLoop, WeightBridge

VOCAB = 128
BS = 8


def _model():
    return GPT2LMHead(GPT2Config.tiny(vocab_size=VOCAB))


def _init_params(model, seed=0):
    batch = {"input_ids": np.zeros((2, 16), np.int32)}
    return model.init(jax.random.PRNGKey(seed), batch)["params"]


def _batch(bs, seed=0, seqlen=16):
    rng = np.random.default_rng(seed)
    return {"input_ids": rng.integers(0, VOCAB, (bs, seqlen)).astype(np.int32)}


def _train_engine(model, params, steps=2, mesh=None, extra=None):
    cfg = {
        "train_batch_size": 8, "gradient_accumulation_steps": 1,
        "steps_per_print": 0,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}},
        "zero_optimization": {"stage": 1},
        "mesh": mesh or {},
    }
    if extra:
        cfg.update(extra)
    engine, *_ = deepspeed_tpu.initialize(model=model,
                                          model_parameters=params, config=cfg)
    for i in range(steps):
        engine.train_batch(_batch(8, seed=100 + i))
    return engine


def _serve_engine(model, params, prefix_cache=False, warmup=False,
                  serving=None):
    econf = {"dtype": jnp.float32,
             "state_manager": {"max_tracked_sequences": 8,
                               "max_ragged_sequence_count": 4,
                               "max_ragged_batch_size": 96,
                               "max_context": 176,
                               "prefill_chunk_size": 32},
             "kv_cache": {"block_size": 16, "num_blocks": 16}}
    if prefix_cache:
        econf["prefix_cache"] = {"enabled": True}
    if warmup:
        econf["compile"] = {"warmup": True}
    if serving is not None:
        econf["serving"] = serving
    return InferenceEngineV2(model=model, model_parameters=params,
                             config=econf)


def _universal_weights(eng, model, tmp_path, econf_kw=None):
    """The disk path the bridge replaces: checkpoint -> universal ->
    fresh engine from the host master tree. Returns that engine."""
    eng.save_checkpoint(str(tmp_path / "ck"), tag="t")
    ds_to_universal(str(tmp_path / "ck"), str(tmp_path / "uni"), tag="t")
    master, _, _ = load_universal(str(tmp_path / "uni"))
    host = unflatten_into(_init_params(model), master)
    return _serve_engine(model, host, **(econf_kw or {}))


def _leaves_byte_equal(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    return all(np.asarray(x).tobytes() == np.asarray(y).tobytes()
               for x, y in zip(la, lb))


# --------------------------------------------------------------------------- #
# reshard byte-equality vs the universal-checkpoint path
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("mesh", [{"data": 1, "fsdp": 8},
                                  {"data": 2, "fsdp": 4}],
                         ids=["fsdp8", "mesh2x4"])
def test_reshard_matches_universal_sharded(eight_devices, tmp_path, mesh):
    model = _model()
    params = _init_params(model)
    eng = _train_engine(model, params, steps=2, mesh=mesh)
    serve = _serve_engine(model, params)
    bridge = serve.weight_bridge(eng)
    new_w = bridge.sync()
    ref = _universal_weights(eng, model, tmp_path)
    assert _leaves_byte_equal(new_w, ref.weights)
    assert bridge.compiles == 1
    # the manifest speaks universal-checkpoint names
    names = bridge.manifest()
    assert "h_0/attn/c_attn/kernel" in names


def test_reshard_matches_universal_offload(tmp_path):
    """Host-master (cpu-offload) engines sync from the merged device
    params — the post-update view the offload flow maintains."""
    model = _model()
    params = _init_params(model)
    eng = _train_engine(model, params, steps=2, extra={
        "zero_optimization": {"stage": 1,
                              "offload_optimizer": {"device": "cpu"}}})
    serve = _serve_engine(model, params)
    new_w = serve.weight_bridge(eng).sync()
    ref = _universal_weights(eng, model, tmp_path)
    assert _leaves_byte_equal(new_w, ref.weights)


def test_bridge_refuses_quantized_serve_engine():
    model = _model()
    params = _init_params(model)
    eng = _train_engine(model, params, steps=0)
    econf = {"dtype": jnp.float32,
             "state_manager": {"max_tracked_sequences": 8,
                               "max_ragged_sequence_count": 4,
                               "max_ragged_batch_size": 96,
                               "max_context": 176,
                               "prefill_chunk_size": 32},
             "kv_cache": {"block_size": 16, "num_blocks": 16},
             "quantization": {"weight_bits": 8}}
    serve = InferenceEngineV2(model=model, model_parameters=params,
                              config=econf)
    with pytest.raises(NotImplementedError, match="quantized"):
        WeightBridge(eng, serve)


def test_rollout_source_refuses_quantized_train_weights():
    model = _model()
    params = _init_params(model)
    eng = _train_engine(model, params, steps=0)
    eng.quantized_weights = True
    with pytest.raises(NotImplementedError, match="quantized"):
        eng.rollout_source_params()


# --------------------------------------------------------------------------- #
# in-place swap: zero compiles, byte-identical generation
# --------------------------------------------------------------------------- #

def test_swap_zero_compiles_byte_identical_generation(tmp_path):
    model = _model()
    params = _init_params(model)
    eng = _train_engine(model, params, steps=2)
    serve = _serve_engine(model, params)
    bridge = serve.weight_bridge(eng)
    prompt = list(range(1, 12))

    serve.generate([prompt], max_new_tokens=8)        # warm the ladders
    c0, b0 = serve.compiles, bridge.compiles
    kv_free0 = serve.allocator.free_blocks

    for i in range(3):                                # >=3 consecutive swaps
        eng.train_batch(_batch(8, seed=200 + i))
        serve.swap_weights(bridge.sync())
    assert serve.compiles == c0                        # ZERO new compiles
    assert bridge.compiles - b0 <= 1                   # first sync builds once
    assert serve.weight_version == 3

    out = serve.generate([prompt], max_new_tokens=8)
    fresh = InferenceEngineV2(
        model=model,
        model_parameters=jax.tree_util.tree_map(
            np.asarray, eng.rollout_source_params()),
        config={"dtype": jnp.float32,
                "state_manager": {"max_tracked_sequences": 8,
                                  "max_ragged_sequence_count": 4,
                                  "max_ragged_batch_size": 96,
                                  "max_context": 176,
                                  "prefill_chunk_size": 32},
                "kv_cache": {"block_size": 16, "num_blocks": 16}})
    assert out == fresh.generate([prompt], max_new_tokens=8)
    assert _leaves_byte_equal(serve.weights, fresh.weights)
    assert serve.allocator.free_blocks == kv_free0     # KV pool at baseline


def test_swap_refused_with_live_sequences_and_bad_trees():
    model = _model()
    params = _init_params(model)
    serve = _serve_engine(model, params)
    same = jax.tree_util.tree_map(lambda x: x, serve.weights)

    serve.scheduler.add_tokens(7, np.arange(1, 20, dtype=np.int32))
    with pytest.raises(RuntimeError, match="live sequence"):
        serve.swap_weights(same)
    serve.scheduler.flush(7)
    assert serve.weight_version == 0                   # refusal changed nothing

    bad = jax.tree_util.tree_map(lambda x: x, serve.weights)
    bad["embed"] = jnp.zeros((3, 3), jnp.float32)
    with pytest.raises(ValueError):
        serve.swap_weights(bad)
    with pytest.raises(ValueError, match="version"):
        serve.swap_weights(same, version=0)            # must be monotone
    assert serve.weight_version == 0
    assert serve.swap_weights(same) == 1               # clean swap still works


# --------------------------------------------------------------------------- #
# prefix cache: weight-version flush + stale-stamp refusal (satellite)
# --------------------------------------------------------------------------- #

class TestPrefixCacheWeightVersion:

    def _cache(self, nb=32):
        alloc = BlockedAllocator(nb)
        return RadixPrefixCache(alloc, BS), alloc

    def test_flush_on_version_bump(self):
        cache, alloc = self._cache()
        toks = np.arange(24)
        blocks = alloc.allocate(3).tolist()
        cache.release(toks, blocks)
        m = cache.match(toks)
        assert m.n_cached == 16
        alloc.free(m.blocks)                           # drop the match refs
        freed = cache.set_weight_version(1)
        assert freed == 3 and cache.cached_blocks == 0
        assert cache.match(toks).n_cached == 0         # stale KV is gone
        assert cache.set_weight_version(1) == 0        # idempotent

    def test_stale_stamped_nodes_refused_and_not_extended(self):
        """Even if stale nodes survive (pinned across a flush attempt),
        matching refuses them and insert never files fresh pages under
        them — the re-prefill path repairs the tree instead."""
        cache, alloc = self._cache()
        toks = np.arange(24)
        blocks = alloc.allocate(3).tolist()
        cache.release(toks, blocks)
        cache.weight_version = 1                       # simulate pinned skip
        assert cache.match_len(toks) == 0
        assert cache.match(toks).n_cached == 0
        blocks2 = alloc.allocate(3).tolist()
        freed = cache.release(toks, blocks2)           # insert under stale root
        assert sorted(freed) == sorted(blocks2)        # refused, pages freed

    def test_flush_with_pinned_pages_raises(self):
        cache, alloc = self._cache()
        toks = np.arange(16)
        blocks = alloc.allocate(2).tolist()
        cache.release(toks, blocks)
        m = cache.match(toks)                          # live match ref pins
        with pytest.raises(RuntimeError, match="quiesce"):
            cache.set_weight_version(1)
        alloc.free(m.blocks)
        cache.set_weight_version(1)

    def test_post_swap_hit_refused_and_reprefilled(self):
        """Engine-level regression: a prompt cached pre-swap must MISS
        after the swap (stale KV refused), re-prefill under the new
        weights, and then hit again — with byte-identical output
        throughout (same weight values swapped in)."""
        model = _model()
        params = _init_params(model)
        serve = _serve_engine(model, params, prefix_cache=True)
        prompt = list(range(1, 40))

        ref = serve.generate([prompt], max_new_tokens=6)
        hits0 = serve.prefix_cache.stats.hits
        assert serve.generate([prompt], max_new_tokens=6) == ref
        assert serve.prefix_cache.stats.hits > hits0   # second run hit

        same = jax.tree_util.tree_map(lambda x: x, serve.weights)
        serve.swap_weights(same)
        assert serve.prefix_cache.weight_version == serve.weight_version
        assert serve.prefix_cache.cached_blocks == 0   # flushed
        hits1 = serve.prefix_cache.stats.hits
        assert serve.generate([prompt], max_new_tokens=6) == ref
        assert serve.prefix_cache.stats.hits == hits1  # re-prefill, no hit
        assert serve.generate([prompt], max_new_tokens=6) == ref
        assert serve.prefix_cache.stats.hits > hits1   # re-primed


# --------------------------------------------------------------------------- #
# frontend swap: run-boundary quiesce, recompute-preempt resume
# --------------------------------------------------------------------------- #

def test_frontend_swap_quiesces_inflight_decode():
    model = _model()
    params = _init_params(model)
    serve = _serve_engine(model, params,
                          serving={"decode_slice": 2, "idle_wait_s": 0.005})
    ref = serve.generate([list(range(1, 12))], max_new_tokens=10)[0]
    serve.flush(list(serve.scheduler.seqs))

    fe = serve.serving_frontend()                      # synchronous (no thread)
    h = fe.submit(list(range(1, 12)), max_new_tokens=10)
    for _ in range(8):                                 # into mid-decode
        fe.step()
        if h.status == "decoding" and len(h.tokens) >= 2:
            break
    assert h.status == "decoding" and not h.finished

    same = jax.tree_util.tree_map(lambda x: x, serve.weights)
    fe.swap_weights(same)                              # inline: no loop thread
    assert serve.weight_version == 1
    assert h.status == "preempted"                     # quiesced, not killed
    assert fe.stats.recompute_preemptions == 1

    for _ in range(64):
        fe.step()
        if h.finished:
            break
    assert h.status == "finished"
    assert h.tokens == ref[11:]                        # stream byte-identical
    fe.close()


# --------------------------------------------------------------------------- #
# LoRA swap-pool drain
# --------------------------------------------------------------------------- #

def test_lora_drain_swap_settles_pool_byte_safely():
    from deepspeed_tpu.inference.v2.lora import (LoraAdapterRegistry,
                                                 LoraPagePool)
    from deepspeed_tpu.inference.v2.model_spec import RaggedModelSpec
    spec = RaggedModelSpec(family="llama", num_layers=2, hidden_size=8,
                           num_heads=2, num_kv_heads=2, head_dim=4,
                           vocab_size=64, dtype=jnp.float32)
    pool = LoraPagePool(spec, ("q", "v"), 4)
    reg = LoraAdapterRegistry(pool, swap_buffers=8, max_rank=4)
    for i in range(3):
        g = np.random.RandomState(i)
        reg.register(f"a{i}",
                     g.standard_normal((2, pool.elements)).astype(np.float32))
    master0 = reg._adapters["a0"].master.copy()
    reg.acquire(1, "a0"); reg.release(1)
    reg.acquire(2, "a1"); reg.release(2)
    reg.acquire(3, "a2"); reg.release(3)               # evicts LRU a0
    assert reg._adapters["a0"].state == "evicted"
    assert reg.swap.outstanding > 0                    # the "flake": pinned

    drained = reg.drain_swap()
    assert drained > 0 and reg.swap.outstanding == 0   # baseline settles
    assert reg._adapters["a0"].state == "registered"
    assert reg.drain_swap() == 0                       # idempotent

    reg.acquire(4, "a0")                               # re-faults from master
    back = pool.fetch_pages(reg._adapters["a0"].page_ids)
    assert back.tobytes() == master0.tobytes()         # byte-safe
    reg.release(4)


# --------------------------------------------------------------------------- #
# the full loop
# --------------------------------------------------------------------------- #

def test_rollout_loop_interleaves_train_and_generate():
    model = _model()
    params = _init_params(model)
    eng = _train_engine(model, params, steps=0)
    serve = _serve_engine(model, params, prefix_cache=True,
                          serving={"decode_slice": 4, "idle_wait_s": 0.005})
    fe = serve.serving_frontend()

    def prompt_fn(rnd):
        r = np.random.default_rng(rnd)
        return [r.integers(1, VOCAB, size=8).tolist() for _ in range(3)]

    def collate(rollouts):
        rows = [(p + t + [0] * 16)[:16] for p, t in rollouts]
        return {"input_ids":
                np.asarray(rows, np.int32).repeat(3, axis=0)[:8]}

    loop = RolloutLoop(eng, fe, prompt_fn=prompt_fn, collate_fn=collate,
                       steps_per_round=1, max_new_tokens=4,
                       request_timeout=60.0)
    rollouts, generate = [], loop._generate

    def recording_generate(rnd):
        rollouts.append(generate(rnd))
        return rollouts[-1]

    loop._generate = recording_generate
    try:
        losses = loop.run(3)
    finally:
        loop.close()
        fe.close()
    # the same policy updates with a serving engine rebuilt from the host
    # copy of the weights at every update: the rollouts agree token for token
    eng2 = _train_engine(model, params, steps=0)
    for rnd in range(3):
        fresh = _serve_engine(model, jax.tree_util.tree_map(
            np.asarray, eng2.rollout_source_params()))
        prompts = prompt_fn(rnd)
        full = fresh.generate(prompts, max_new_tokens=4)
        naive = [(p, list(f[len(p):])) for p, f in zip(prompts, full)]
        assert naive == [(p, list(t)) for p, t in rollouts[rnd]]
        eng2.train_batch(collate(naive))
    assert len(losses) == 3 and all(np.isfinite(l).all() for l in losses)
    assert eng.global_steps == 3
    assert serve.weight_version == 4                   # align + 3 rounds
    st = loop.stats
    assert st.rounds == 4 and st.requests == 9 and st.tokens == 36
    names = [n for n, _, _ in st.events(0)]
    assert "train/rollout/sync_ms_per_round" in names
    assert st.weight_version == 4
