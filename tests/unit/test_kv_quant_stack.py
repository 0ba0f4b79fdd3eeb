"""int8 KV pages as a first-class pool layout for the whole v2 serving stack.

The PR that added these tests collapsed the engine's three int8 refusals
(prefix cache, spec decode, page fabric/offload) into capability flags on
ONE attention-kernel interface (``inference/v2/attention.py``); what these
tests pin is the byte-tier of the gate taxonomy (docs/SERVING.md
"Quantized KV"): quantized-vs-quantized streams stay byte-identical across
cache-on/off, spec-on/off, preempt-offload-restore and cross-engine
migration, the scale-tile fabric invariant (a page's f32 scale tile moves
with its int8 bytes through COW, offload, export/import), and the two
SURVIVING build-time refusals' exact error messages (capability drift must
fail loudly, not silently).
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.inference.v2.pipeline import DecodePipeline
from deepspeed_tpu.inference.v2.ragged.kv_cache import (BlockedKVCache,
                                                        KVCacheConfig)


def _params(seed=0):
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    cfg = LlamaConfig(vocab_size=256, hidden_size=512, intermediate_size=256,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=2, max_position_embeddings=512,
                      dtype=jnp.float32)
    model = LlamaForCausalLM(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed),
                                 {"input_ids": jnp.zeros((1, 8), jnp.int32)}
                                 )["params"]
    return model, params


def _engine(model, params, kvq=True, prefix_cache=False, spec_k=0,
            num_blocks=None, **extra):
    """head_dim-128, Hkv*block_size = 128 engine (the relaxed kv_quant
    alignment gate: block_size 64 x 2 kv heads)."""
    econf = {"state_manager": {"max_tracked_sequences": 4,
                               "max_ragged_sequence_count": 4,
                               "max_ragged_batch_size": 64,
                               "prefill_chunk_size": 16, "max_context": 256},
             "kv_cache": {"block_size": 64, "num_blocks": num_blocks},
             "dtype": jnp.float32}
    if kvq:
        econf["kv_quant"] = {"enabled": True}
    if prefix_cache:
        econf["prefix_cache"] = {"enabled": True}
    if spec_k:
        econf["spec_decode"] = {"enabled": True, "k": spec_k}
    econf.update(extra)
    return InferenceEngineV2(model=model, model_parameters=params,
                             config=econf)


def _force_paged(engine):
    """Hold the kernel path constant (packed-vs-paged prefill variance is
    per-path, pre-existing, and orthogonal — a cache hit always continues
    through the paged path, a cold prompt takes the packed one): every pass
    through the paged forward."""
    orig = engine.scheduler.schedule_pass

    def no_fast_path():
        b = orig()
        if b is not None:
            b.pure_prefill = False
        return b

    engine.scheduler.schedule_pass = no_fast_path


def _unforce_paged(engine):
    try:
        del engine.scheduler.schedule_pass
    except AttributeError:
        pass


def _serve(engine, uid, prompt, gen):
    engine._put_nofetch([uid], [np.asarray(prompt, np.int32)])
    out = DecodePipeline(engine, [uid]).run(gen)
    engine.flush([uid])
    return [int(t) for t in out[0]]


# --------------------------------------------------------------------------- #
# the two surviving build-time refusals: pinned error messages
# --------------------------------------------------------------------------- #

def test_kv_quant_tp_refusal_message_pinned(eight_devices):
    model, params = _params()
    with pytest.raises(NotImplementedError,
                       match=r"kv_quant with tensor_parallel > 1 is not "
                             r"wired"):
        InferenceEngineV2(model=model, model_parameters=params,
                          config={"tensor_parallel": 2,
                                  "kv_quant": {"enabled": True}})


def test_spec_window_refusal_message_pinned(eight_devices):
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    cfg = LlamaConfig(vocab_size=256, hidden_size=512, intermediate_size=256,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=2, max_position_embeddings=512,
                      sliding_window=24, dtype=jnp.float32)
    model = LlamaForCausalLM(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 {"input_ids": jnp.zeros((1, 8), jnp.int32)}
                                 )["params"]
    with pytest.raises(NotImplementedError,
                       match=r"spec_decode with a sliding-window model is "
                             r"not wired \(the page ring aliases the verify "
                             r"step's k\+1-ahead write span\)"):
        InferenceEngineV2(model=model, model_parameters=params,
                          config={"spec_decode": {"enabled": True, "k": 3},
                                  "state_manager": {"max_context": 256}})


def test_kv_quant_alignment_gate(eight_devices):
    # the RELAXED gate: num_kv_heads * block_size % 128 (not block_size
    # alone) — Hkv=2 x bs=64 passes; bs=8 fails with the documented error
    model, params = _params()
    with pytest.raises(ValueError, match="num_kv_heads \\* block_size"):
        _engine(model, params, kvq=True,
                kv_cache={"block_size": 8, "num_blocks": None})


# --------------------------------------------------------------------------- #
# the scale-tile fabric invariant
# --------------------------------------------------------------------------- #

def test_copy_page_copies_scale_tile(eight_devices):
    """COW adoption (prefix cache) must move a page's int8 bytes AND its
    f32 scale tile together — the former refusal's stated reason, now a
    tested invariant."""
    cfg = KVCacheConfig(num_layers=2, num_kv_heads=2, head_dim=128,
                        block_size=64, num_blocks=4, quantized=True)
    cache = BlockedKVCache(cfg)
    vals, scales = cache.kv
    rng = np.random.RandomState(0)
    v_src = rng.randint(-127, 128, size=vals[:, 1].shape).astype(np.int8)
    s_src = rng.rand(*scales[:, 1].shape).astype(np.float32)
    cache.kv = (vals.at[:, 1].set(jnp.asarray(v_src)),
                scales.at[:, 1].set(jnp.asarray(s_src)))
    cache.copy_page(1, 3)
    vals2, scales2 = cache.kv
    assert np.array_equal(np.asarray(vals2[:, 3]), v_src)
    assert np.array_equal(np.asarray(scales2[:, 3]), s_src)
    # the source is untouched
    assert np.array_equal(np.asarray(vals2[:, 1]), v_src)
    assert np.array_equal(np.asarray(scales2[:, 1]), s_src)


def test_page_fabric_roundtrip_and_payload_spec(eight_devices):
    """fetch_pages/put_pages round-trip int8 pools byte-exactly through
    the packed value+scale-tile payload; the payload's size is
    bytes_per_block (one source of size truth for offload accounting and
    handoff validation)."""
    model, params = _params()
    eng = _engine(model, params, kvq=True)
    shape, dtype = eng.page_payload_spec
    assert dtype == np.uint8
    assert shape == (eng.kv.config.bytes_per_block(),)
    rng = np.random.RandomState(1)
    eng._put_nofetch([5], [rng.randint(0, 256, size=(70,)).astype(np.int32)])
    blocks = list(eng.scheduler.seqs[5].blocks)
    assert len(blocks) >= 2            # spans a full + a partial page
    pages = eng.fetch_pages(blocks)
    assert pages.shape == (len(blocks),) + shape and pages.dtype == np.uint8
    assert pages.any()                 # real content, not zeros
    # clobber the device pages, then restore from the host payload
    eng.put_pages(np.zeros_like(pages), blocks)
    assert not eng.fetch_pages(blocks).any()
    eng.put_pages(pages, blocks)
    assert np.array_equal(eng.fetch_pages(blocks), pages)
    eng.flush([5])


def test_import_rejects_mismatched_payload(eight_devices):
    model, params = _params()
    eng = _engine(model, params, kvq=True)
    bad = np.zeros((1, 16), np.uint8)
    with pytest.raises(ValueError, match="does not match"):
        eng.import_kv(77, [1, 2, 3], bad, np.zeros((256,), np.float32))


# --------------------------------------------------------------------------- #
# byte-tier gates: the quantized stream is identical across every path
# --------------------------------------------------------------------------- #

def test_int8_prefix_cache_streams_and_cow(eight_devices):
    """Cache-on int8 serving: a shared prefix re-served through the radix
    tree (full-block reuse + COW adoption of the partial tail page, scale
    tiles included) streams byte-identically to the cache-off serve of the
    same prompt on the same engine."""
    model, params = _params()
    eng = _engine(model, params, kvq=True, prefix_cache=True)
    _force_paged(eng)
    try:
        rng = np.random.RandomState(2)
        prefix = rng.randint(0, 256, size=(96,))     # 1 full + 1 partial page
        tails = [rng.randint(0, 256, size=(8,)) for _ in range(2)]
        cold = [_serve(eng, 100 + i, np.concatenate([prefix, t]), 10)
                for i, t in enumerate(tails)]
        st = eng.prefix_cache.stats
        assert st.hits >= 1            # the second serve reused the prefix
        # re-serve both (warm tree now): pure cache-path streams
        warm = [_serve(eng, 200 + i, np.concatenate([prefix, t]), 10)
                for i, t in enumerate(tails)]
        assert warm == cold
    finally:
        _unforce_paged(eng)


def test_int8_spec_streams_identical_and_rollback(eight_devices):
    """Spec-on int8 == spec-off int8, byte for byte (the verify step's
    quantize-on-write attends the same pool values sequential decode
    does), with allocator blocks back to baseline after reject-heavy
    runs."""
    from deepspeed_tpu.inference.v2.spec import SpecDecodePipeline
    model, params = _params()
    eng = _engine(model, params, kvq=True, spec_k=3)
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, 256, size=(20,)).astype(np.int32)
               for _ in range(2)]
    free0 = eng.free_blocks
    eng._put_nofetch([1, 2], [p.copy() for p in prompts])
    ref = DecodePipeline(eng, [1, 2]).run(12).tolist()
    eng.flush([1, 2])
    assert eng.free_blocks == free0
    eng._put_nofetch([3, 4], [p.copy() for p in prompts])
    sp = SpecDecodePipeline(eng, [3, 4])
    outs = [[], []]
    while sp.uids and min(len(o) for o in outs) < 12:
        got = sp.run(2)
        for i, g in enumerate(got):
            outs[i].extend(int(t) for t in g)
    eng.flush([3, 4])
    assert [o[:12] for o in outs] == ref
    assert eng.free_blocks == free0


def test_int8_offload_restore_stream_identical(eight_devices):
    """Preempt-offload-restore on an int8 pool: the victim's packed
    value+scale pages round-trip pinned host buffers and the resumed
    stream is byte-identical to an uninterrupted run."""
    from deepspeed_tpu.inference.v2.serving.kv_offload import KVOffloadManager
    model, params = _params()
    eng = _engine(model, params, kvq=True)
    rng = np.random.RandomState(4)
    prompt = rng.randint(0, 256, size=(40,)).astype(np.int32)
    ref = _serve(eng, 10, prompt.copy(), 16)
    free0 = eng.free_blocks
    # interrupted: 6 tokens, offload the whole private tail, restore, resume
    eng._put_nofetch([11], [prompt.copy()])
    pipe = DecodePipeline(eng, [11])
    head = [int(t) for t in pipe.run(6)[0]]
    pipe.retire([11])
    mgr = KVOffloadManager(eng)
    kept, tail = eng.scheduler.private_tail(11)
    assert kept == 0 and len(tail) >= 1
    moved = mgr.offload(11, kept, tail)
    assert moved == len(tail) * eng.kv.config.bytes_per_block()
    restored = mgr.restore(11)
    assert restored == moved
    tail_out = DecodePipeline(eng, [11]).run(10)
    eng.flush([11])
    assert head + [int(t) for t in tail_out[0]] == ref
    assert eng.free_blocks == free0


def test_int8_frontend_preempt_cycle_compiles_nothing(eight_devices):
    """The same round trip driven by the product's own loop on a warmed
    engine: two batch requests decode on a pool too small for a third; an
    interactive arrival makes admission offload one of them (packed
    value+scale pages) and restore it later. All three streams equal direct
    plain-pipeline runs, nothing compiles after warm-up (the page-op grid is
    part of it), and pool and host buffers end where they began."""
    model, params = _params()
    eng = _engine(model, params, kvq=True, num_blocks=8,
                  compile={"warmup": True})
    _force_paged(eng)
    try:
        rng = np.random.RandomState(7)
        free0 = eng.free_blocks
        fe = eng.serving_frontend(config={"classes": [
            {"name": "interactive", "priority": 2,
             "ttft_slo_ms": 1e6, "tbt_slo_ms": 1e6},
            {"name": "batch", "priority": 0,
             "ttft_slo_ms": 1e6, "tbt_slo_ms": 1e6}],
            "decode_slice": 4, "spec": False, "preemption": "offload"})
        c0 = eng.compiles
        lows = [fe.submit(rng.randint(0, 256, size=(150,)).astype(np.int32),
                          priority="batch", max_new_tokens=60)
                for _ in range(2)]
        for _ in range(80):              # a victim must be DECODING
            fe.step()
            if any(len(h.tokens) >= 4 for h in lows):
                break
        hi = fe.submit(rng.randint(0, 256, size=(128,)).astype(np.int32),
                       priority="interactive", max_new_tokens=8)
        hs = lows + [hi]
        for _ in range(900):
            if all(h.finished for h in hs):
                break
            fe.step()
        assert all(h.status == "finished" for h in hs)
        assert fe.stats.preemptions >= 1 and fe.stats.restores >= 1
        assert fe.stats.offload_bytes > 0
        assert eng.compiles == c0
        fe.close()
        assert fe.offload.pool.outstanding == 0
        for i, h in enumerate(hs):
            assert _serve(eng, 300 + i, h.prompt, len(h.tokens)) == h.tokens
        assert eng.compiles == c0
        assert eng.free_blocks == free0
    finally:
        _unforce_paged(eng)


def test_int8_cross_engine_handoff_and_salvage(eight_devices):
    """The page fabric between ENGINES (disagg handoff / failover
    salvage): int8 pages exported from engine A import byte-exactly into
    engine B's fresh block ids and the stream continues identically —
    including the failover path where A's offload RECORD (pinned host
    buffers) is the payload."""
    from deepspeed_tpu.inference.v2.serving.kv_offload import KVOffloadManager
    model, params = _params()
    ea = _engine(model, params, kvq=True)
    eb = _engine(model, params, kvq=True)
    rng = np.random.RandomState(5)
    prompt = rng.randint(0, 256, size=(40,)).astype(np.int32)
    ref = _serve(eb, 20, prompt.copy(), 12)
    # disagg-style: prefill on A, export, import on B, decode on B
    ea._put_nofetch([21], [prompt.copy()])
    pages, logits = ea.export_kv(21)
    freeb0 = eb.free_blocks
    eb.import_kv(21, prompt.tolist(), pages, logits)
    out = DecodePipeline(eb, [21]).run(12)
    eb.flush([21])
    assert [int(t) for t in out[0]] == ref
    assert eb.free_blocks == freeb0
    # failover salvage: A decodes 5 tokens, preempt-offloads the WHOLE KV,
    # the record becomes B's import payload (history = prompt + emitted)
    ea._put_nofetch([22], [prompt.copy()])
    pipe = DecodePipeline(ea, [22])
    head = [int(t) for t in pipe.run(5)[0]]
    pipe.retire([22])
    mgr = KVOffloadManager(ea)
    kept, tail = ea.scheduler.private_tail(22)
    mgr.offload(22, kept, tail)
    assert mgr.salvageable(22)
    pages, logits, _ = mgr.export_record(22)
    ea.flush([22])
    history = prompt.tolist() + head
    eb.import_kv(22, history, pages, logits)
    out = DecodePipeline(eb, [22]).run(7)
    eb.flush([22])
    assert head + [int(t) for t in out[0]] == ref
    assert eb.free_blocks == freeb0


# --------------------------------------------------------------------------- #
# observability + lint coverage
# --------------------------------------------------------------------------- #

def test_kv_pool_gauges(eight_devices):
    """serve/frontend/kv/* gauges: dtype bits, bytes/token and capacity
    make the int8 pool's doubling observable; int8 bytes/token is strictly
    below the fp32 pool's at the same layout."""
    model, params = _params()
    vals = {}
    for kvq in (False, True):
        eng = _engine(model, params, kvq=kvq, num_blocks=8)
        fe = eng.serving_frontend(config={"decode_slice": 2,
                                          "preemption": "offload"})
        ev = {name: v for name, v, _ in fe.stats.events()}
        vals[kvq] = ev
        fe.close()
        assert ev["serve/frontend/kv/pool_dtype_bits"] == (8 if kvq else 32)
        assert ev["serve/frontend/kv/pool_tokens"] == 8 * 64
        assert ev["serve/frontend/kv/resident_seq_headroom"] == \
            (8 * 64) // 256
        assert ev["serve/frontend/kv/bytes_per_token"] == \
            eng.kv.config.bytes_per_block() / 64
    assert vals[True]["serve/frontend/kv/bytes_per_token"] \
        < 0.5 * vals[False]["serve/frontend/kv/bytes_per_token"]


def test_kv_headroom_counts_whole_blocks():
    """A max_context-length sequence's last PARTIAL block consumes a whole
    block: with block_size=64, max_context=160 and 5 free blocks, only one
    more sequence fits (ceil(160/64)=3 blocks each) — free-token division
    ((5*64)//160 = 2) would overstate the operator-facing headroom gauge."""
    from deepspeed_tpu.monitor.serving import FrontendStats
    st = FrontendStats(class_names=["standard"])
    st.set_kv_pool(dtype_bits=8, bytes_per_token=1152.0,
                   pool_tokens=8 * 64, max_context=160, block_size=64)
    st.kv_free_blocks = 5
    ev = {name: v for name, v, _ in st.events()}
    assert ev["serve/frontend/kv/resident_seq_headroom"] == 1


def test_serving_spec_opt_out(eight_devices):
    """ServingConfig.spec=False pins a frontend on a spec-enabled engine
    to the plain pipeline (the bit-exact byte-gate discipline the
    --kv-dtype replay uses; docs/SERVING.md gate taxonomy) — and the
    stream it serves is byte-identical to a direct DecodePipeline run."""
    model, params = _params()
    eng = _engine(model, params, kvq=True, spec_k=3, num_blocks=8)
    rng = np.random.RandomState(6)
    prompt = rng.randint(0, 256, size=(20,)).astype(np.int32)
    ref = _serve(eng, 30, prompt.copy(), 8)
    fe = eng.serving_frontend(config={"decode_slice": 2, "spec": False})
    assert fe._spec is False
    fe.start()
    h = fe.submit(prompt, priority="standard", max_new_tokens=8)
    assert h.result(timeout=60.0) == ref
    fe.close()
    fe2 = eng.serving_frontend(config={"decode_slice": 2})
    assert fe2._spec is True          # default: the engine's spec pipeline
    fe2.close()


def test_admission_funds_plain_rate_under_spec_opt_out(eight_devices):
    """slice_tokens matches the pipeline the frontend ACTUALLY runs: a
    spec=False frontend on a spec-enabled engine funds decode_slice + 1
    per row (the plain DecodePipeline's reservation), not the spec rate
    decode_slice * (k + 1) + 1 — funding at the spec rate over-reserved
    ~(k+1)x and preempted/shed requests the pool could serve."""
    model, params = _params()
    eng = _engine(model, params, kvq=True, spec_k=3, num_blocks=8)
    fe_plain = eng.serving_frontend(config={"decode_slice": 4,
                                            "spec": False})
    assert fe_plain.admission.slice_tokens == 4 + 1
    fe_plain.close()
    fe_spec = eng.serving_frontend(config={"decode_slice": 4})
    assert fe_spec.admission.slice_tokens == 4 * (3 + 1) + 1
    fe_spec.close()


def test_jaxlint_hot_paths_cover_attention_module():
    """The new dispatch module rides the serving hot path: JL007/JL008
    hot_paths must cover it (prefix match against the shipped config)."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, ".jaxlint.json")) as f:
        cfg = json.load(f)
    target = "deepspeed_tpu/inference/v2/attention.py"
    for rule in ("JL007", "JL008"):
        hot = cfg["rules"][rule]["options"]["hot_paths"]
        assert any(target.startswith(p) for p in hot), (rule, hot)
