"""Multi-tenant LoRA serving (inference/v2/lora/ + the serving wiring):
the paged adapter pool's byte-exact host round trip, registry lifecycle /
refcount / LRU semantics, cancel-while-faulting rollback, the grouped
decode matmul's mixed-tenant byte-equality against per-adapter sequential
runs on one warmed engine, zero-compile adapter churn, and the frontend
integration (tenant classes, acquire/release around preemption, the
recompute refusal). docs/SERVING.md "Multi-tenant LoRA" describes the
design under test."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
from deepspeed_tpu.inference.v2.lora import LoraAdapterRegistry, LoraPagePool
from deepspeed_tpu.inference.v2.pipeline import DecodePipeline
from deepspeed_tpu.inference.v2.model_spec import RaggedModelSpec
from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from deepspeed_tpu.module_inject.lora import load_lora_adapter
from deepspeed_tpu.utils import fault_injection as fi

# --------------------------------------------------------------------------- #
# pool + registry units (no engine: a bare spec is enough for page layout)
# --------------------------------------------------------------------------- #

_SPEC = RaggedModelSpec(family="llama", num_layers=2, hidden_size=8,
                        num_heads=2, num_kv_heads=2, head_dim=4,
                        vocab_size=64, dtype=jnp.float32)


def _registry(pool_pages=4, ranks=(2, 2, 2), max_rank=4):
    """Adapters ``a0, a1, ...`` with seeded random masters over a small
    pool (sum(ranks) > pool_pages is the interesting regime)."""
    pool = LoraPagePool(_SPEC, ("q", "v"), pool_pages)
    reg = LoraAdapterRegistry(pool, swap_buffers=8, max_rank=max_rank)
    for i, r in enumerate(ranks):
        g = np.random.RandomState(i)
        reg.register(f"a{i}",
                     g.standard_normal((r, pool.elements)).astype(np.float32))
    return reg


def test_pool_page_roundtrip_byte_exact():
    pool = LoraPagePool(_SPEC, ("q", "v"), 8)
    rows = np.random.RandomState(0).standard_normal(
        (3, pool.elements)).astype(np.float32)
    ids = pool.alloc(3)
    pool.put_pages(rows, ids)
    back = pool.fetch_pages(ids)
    assert back.tobytes() == np.asarray(rows, pool.dtype).tobytes()
    # the zero page really is zeros (the inert-delta sentinel)
    assert not pool.fetch_pages([pool.zero_page]).any()
    pool.free(ids)
    assert pool.free_pages == 8


def test_pool_alloc_overcommit_refused():
    pool = LoraPagePool(_SPEC, ("q", "v"), 2)
    with pytest.raises(RuntimeError, match="pool exhausted"):
        pool.alloc(3)


def test_registry_lru_eviction_and_byte_exact_restore():
    reg = _registry(pool_pages=4, ranks=(2, 2, 2))
    master0 = reg._adapters["a0"].master.copy()
    reg.acquire(1, "a0")
    reg.release(1)
    reg.acquire(2, "a1")
    reg.release(2)                       # pool full: a0 + a1 resident, idle
    assert reg.pool.free_pages == 0
    reg.acquire(3, "a2")                 # faults in by evicting LRU = a0
    assert not reg.is_resident("a0") and reg.is_resident("a2")
    assert reg.stats.adapters["a0"].evictions == 1
    reg.release(3)
    # restore: the pinned-buffer scatter-back is byte-exact with the master
    reg.acquire(4, "a0")
    back = reg.pool.fetch_pages(reg._adapters["a0"].page_ids)
    assert back.tobytes() == master0.tobytes()
    assert reg.stats.adapters["a0"].faults == 2      # cold + restore
    reg.release(4)
    reg.close()                          # returns pages AND pinned buffers
    assert reg.pool.free_pages == 4
    assert reg.swap.outstanding == 0


def test_refcount_gates_eviction_and_can_admit_releasing():
    reg = _registry(pool_pages=4, ranks=(2, 2, 2))
    reg.acquire(1, "a0")
    reg.acquire(2, "a1")                 # pool full, every page pinned
    with pytest.raises(RuntimeError, match="cannot evict"):
        reg.evict("a0")
    assert not reg.can_admit("a2")
    with pytest.raises(RuntimeError, match="pool pressure"):
        reg.acquire(3, "a2")
    # the failed acquire rolled its binding back
    assert reg.binding(3) is None and reg.refcount("a2") == 0
    # the planner's simulation: releasing uid 1 would make a0 evictable
    assert reg.can_admit("a2", releasing=[1])
    reg.release(1)
    reg.acquire(3, "a2")                 # now funds by evicting idle a0
    assert not reg.is_resident("a0")
    with pytest.raises(KeyError, match="unknown LoRA adapter"):
        reg.acquire(9, "nope")
    reg.release(2)
    reg.release(3)


def test_cancel_while_faulting_rolls_back_to_baseline():
    reg = _registry(pool_pages=4, ranks=(2, 2))
    free0 = reg.pool.free_pages
    fi.install(fi.parse_plan("serve.lora_fault:at=1"))
    try:
        with pytest.raises(fi.InjectedFault):
            reg.acquire(1, "a0")
    finally:
        fi.clear()
    # rollback: pages freed, binding undone, refcount at baseline
    assert reg.pool.free_pages == free0
    assert reg.refcount("a0") == 0 and reg.binding(1) is None
    assert not reg.is_resident("a0")
    reg.acquire(1, "a0")                 # clean retry succeeds
    assert reg.is_resident("a0")
    reg.release(1)


# --------------------------------------------------------------------------- #
# the grouped decode matmul on one warmed engine
# --------------------------------------------------------------------------- #

_LORA = {"enabled": True, "pool_pages": 6, "max_rank": 4,
         "targets": ("q", "v"), "swap_buffers": 8}


def _model_and_params(seed=0):
    cfg = LlamaConfig.tiny(vocab_size=128, max_position_embeddings=256)
    model = LlamaForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(seed),
                        {"input_ids": jnp.zeros((1, 8), jnp.int32)})["params"]
    return model, params


def _build_engine(model_params=None, warmup=False, lora=_LORA, num_blocks=12):
    model, params = model_params or _model_and_params()
    econf = {"dtype": jnp.float32,
             "state_manager": {"max_tracked_sequences": 8,
                               "max_ragged_sequence_count": 4,
                               "max_ragged_batch_size": 96,
                               "max_context": 176,
                               "prefill_chunk_size": 32},
             "kv_cache": {"block_size": 16, "num_blocks": num_blocks}}
    if lora:
        econf["lora"] = dict(lora)
    if warmup:
        econf["compile"] = {"warmup": True, "warmup_buckets": [1, 2, 4]}
    return InferenceEngineV2(model=model, model_parameters=params,
                             config=econf)


def _adapter_state(engine, rank, seed, scale=0.2):
    """A seeded random adapter; 0.2 scale is large against the random-init
    base weights, so adapter streams visibly diverge from base streams."""
    spec = engine.spec
    douts = {"q": spec.num_heads * spec.head_dim,
             "v": spec.num_kv_heads * spec.head_dim}
    g = np.random.RandomState(seed)
    state = {"alpha": float(rank)}
    for t in engine.config.lora.targets:
        state[t] = {"A": (g.standard_normal((spec.hidden_size, rank))
                          * scale).astype(np.float32),
                    "B": (g.standard_normal((rank, douts[t]))
                          * scale).astype(np.float32)}
    return state


@pytest.fixture(scope="module")
def model_params():
    return _model_and_params()


@pytest.fixture(scope="module")
def warm_engine(model_params):
    """One warmed LoRA engine shared by the decode tests (the (bucket,
    rank-bucket) ladder is the expensive part on this box)."""
    e = _build_engine(model_params, warmup=True)
    load_lora_adapter(e, "t-a", _adapter_state(e, 2, seed=7))
    load_lora_adapter(e, "t-b", _adapter_state(e, 3, seed=8))
    return e


def _serve_direct(engine, uid, prompt, n, adapter=None):
    """One request through the bare pipeline under an adapter binding —
    the per-adapter sequential reference (the bench's oracle)."""
    if adapter is not None:
        engine.lora.acquire(uid, adapter)
    try:
        engine._put_nofetch([uid], [np.asarray(prompt, np.int32)])
        out = DecodePipeline(engine, [uid]).run(n)
        engine.flush([uid])
    finally:
        if adapter is not None:
            engine.lora.release(uid)
    return [int(t) for t in out[0]]


def _prompt(rng, n):
    return rng.randint(0, 128, size=(n,)).astype(np.int32)


def test_mixed_ragged_decode_matches_per_adapter_sequential(warm_engine):
    """The tentpole acceptance criterion: a ragged batch mixing two
    adapters and a base row decodes byte-identically to per-adapter
    sequential runs on the same warmed engine, with zero compiles."""
    e = warm_engine
    rng = np.random.RandomState(0)
    prompts = [_prompt(rng, n) for n in (12, 9, 17, 7)]
    binds = ["t-a", None, "t-b", "t-a"]
    N = 6
    c0 = e.compiles
    refs = [_serve_direct(e, 900 + i, p, N, adapter=a)
            for i, (p, a) in enumerate(zip(prompts, binds))]
    # the deltas are real: the adapter stream diverges from base
    assert refs[0] != _serve_direct(e, 950, prompts[0], N)
    uids = [10, 11, 12, 13]
    for u, a in zip(uids, binds):
        if a is not None:
            e.lora.acquire(u, a)
    try:
        e._put_nofetch(uids, prompts)
        out = DecodePipeline(e, uids).run(N)
        e.flush(uids)
    finally:
        for u, a in zip(uids, binds):
            if a is not None:
                e.lora.release(u)
    assert [[int(t) for t in row] for row in out] == refs
    assert e.compiles == c0      # warmed (bucket, rank-bucket) grid held
    assert all(e.lora.refcount(n) == 0 for n in e.lora.names)


def test_evicted_adapter_restores_byte_exact_stream(warm_engine):
    e = warm_engine
    rng = np.random.RandomState(1)
    p = _prompt(rng, 10)
    ref = _serve_direct(e, 920, p, 8, adapter="t-a")
    e.lora.evict("t-a")
    assert not e.lora.is_resident("t-a")
    c0 = e.compiles
    got = _serve_direct(e, 921, p, 8, adapter="t-a")   # faults back in
    assert got == ref
    assert e.compiles == c0      # pool movers pre-warmed too
    assert e.lora.is_resident("t-a")


def test_adapter_churn_never_compiles(warm_engine):
    """Registering / fault-in / serving / unregistering an adapter
    mid-steady-state stays inside the warmed program grid (rank_bucket is
    engine-stable: pow2 of max registered rank)."""
    e = warm_engine
    c0 = e.compiles
    assert e.lora.rank_bucket == 4
    load_lora_adapter(e, "t-c", _adapter_state(e, 4, seed=9))
    assert e.lora.rank_bucket == 4
    rng = np.random.RandomState(2)
    _serve_direct(e, 930, _prompt(rng, 8), 5, adapter="t-c")
    e.lora.unregister("t-c")
    assert e.compiles == c0


def test_rank0_adapter_is_inert_and_pageless(warm_engine):
    e = warm_engine
    load_lora_adapter(e, "t-zero", {})
    assert e.lora.rank("t-zero") == 0 and e.lora.is_resident("t-zero")
    rng = np.random.RandomState(3)
    p = _prompt(rng, 9)
    free0 = e.lora.pool.free_pages
    base = _serve_direct(e, 940, p, 6)
    got = _serve_direct(e, 941, p, 6, adapter="t-zero")
    assert got == base                       # zero-page rows: exact no-op
    assert e.lora.pool.free_pages == free0   # rank-0 owns no pages
    e.lora.unregister("t-zero")


# --------------------------------------------------------------------------- #
# frontend + admission integration
# --------------------------------------------------------------------------- #

# relaxed SLOs: correctness tests must not shed on a slow CI box
def _serving_cfg(**kw):
    classes = kw.pop("classes", [
        {"name": "premium", "priority": 2, "ttft_slo_ms": 1e6,
         "tbt_slo_ms": 1e6},
        {"name": "standard", "priority": 1, "ttft_slo_ms": 1e6,
         "tbt_slo_ms": 1e6}])
    return dict({"classes": classes, "decode_slice": 4,
                 "idle_wait_s": 0.001, "spec": False}, **kw)


def _step_until(fe, cond, n=400):
    for _ in range(n):
        if cond():
            return True
        fe.step()
    return cond()


def test_frontend_lora_streams_and_tenant_classes(warm_engine):
    e = warm_engine
    rng = np.random.RandomState(4)
    prompts = [_prompt(rng, n) for n in (14, 8, 11)]
    binds = ["t-a", "t-b", None]
    N = 6
    refs = [_serve_direct(e, 960 + i, p, N, adapter=a)
            for i, (p, a) in enumerate(zip(prompts, binds))]
    c0 = e.compiles
    fe = e.serving_frontend(
        config=_serving_cfg(tenant_classes={"t-a": "premium"}))
    hs = [fe.submit(p, max_new_tokens=N, adapter=a)
          for p, a in zip(prompts, binds)]
    assert hs[0].cls.name == "premium"    # tenant_classes mapping
    assert hs[1].cls.name == "standard"   # unmapped tenant: the default
    # explicit priority stays the override
    h_ov = fe.submit(prompts[0], priority="standard", max_new_tokens=2,
                     adapter="t-a")
    assert h_ov.cls.name == "standard"
    assert _step_until(fe, lambda: all(h.finished for h in hs + [h_ov]))
    for h, ref in zip(hs, refs):
        assert h.status == "finished"
        assert h.result(5) == ref
    assert e.compiles == c0
    # bindings released at finalize; residency stays LRU-cached
    assert all(e.lora.refcount(n) == 0 for n in e.lora.names)
    fe.close()


def test_frontend_tenant_churn_past_pool_capacity(warm_engine, tmp_path):
    """More registered adapters (9 pages) than the pool holds (6): a mixed
    queue makes admission fault cold adapters in and evict idle ones while
    one ragged batch mixes tenants. Every stream equals its per-adapter
    direct run, nothing compiles, the faults leave ``serve/lora`` spans, and
    KV pool, adapter pool and pinned buffers end at their baselines."""
    from deepspeed_tpu.monitor.trace import tracer
    e = warm_engine
    load_lora_adapter(e, "t-c", _adapter_state(e, 4, seed=9))
    tracer.reset()
    tracer.configure(trace_dir=str(tmp_path), enabled=True)
    try:
        rng = np.random.RandomState(5)
        binds = ["t-a", "t-c", "t-b", None, "t-c", "t-a", "t-b", "t-c"]
        prompts = [_prompt(rng, int(rng.randint(7, 18))) for _ in binds]
        N = 6
        refs = [_serve_direct(e, 970 + i, p, N, adapter=a)
                for i, (p, a) in enumerate(zip(prompts, binds))]
        kv_free0 = e.allocator.free_blocks
        c0 = e.compiles
        st = e.lora.stats.adapters
        faults0 = sum(c.faults for c in st.values())
        evict0 = sum(c.evictions for c in st.values())
        fe = e.serving_frontend(config=_serving_cfg())
        hs = [fe.submit(p, max_new_tokens=N, adapter=a)
              for p, a in zip(prompts, binds)]
        assert _step_until(fe, lambda: all(h.finished for h in hs), n=2000)
        for h, ref in zip(hs, refs):
            assert h.status == "finished" and h.result(5) == ref
        fe.close()
        assert e.compiles == c0
        assert sum(c.faults for c in st.values()) > faults0
        assert sum(c.evictions for c in st.values()) > evict0
        assert "serve/lora/fault" in tracer.summary()
        # an adapter that sits evicted holds pinned buffers until settled
        e.lora.drain_swap()
        reg = e.lora
        resident = sum(reg.rank(n) for n in reg.names if reg.is_resident(n))
        assert reg.pool.free_pages + resident == reg.pool.num_pages
        assert all(reg.refcount(n) == 0 for n in reg.names)
        assert reg.swap.outstanding == 0
        assert e.allocator.free_blocks == kv_free0
    finally:
        tracer.reset()
        e.lora.unregister("t-c")


def test_frontend_lora_refusals(warm_engine, model_params):
    e = warm_engine
    fe = e.serving_frontend(config=_serving_cfg())
    with pytest.raises(KeyError, match="unknown LoRA adapter"):
        fe.submit(np.arange(4, dtype=np.int32), adapter="nope")
    fe.close()
    # recompute restore would re-prefill decode-written KV base-only — a
    # silently byte-divergent stream, refused at construction
    with pytest.raises(NotImplementedError, match="recompute"):
        e.serving_frontend(config=_serving_cfg(preemption="recompute"))
    plain = _build_engine(model_params, lora=None)
    fp = plain.serving_frontend(config=_serving_cfg())
    with pytest.raises(RuntimeError, match="serves no LoRA adapters"):
        fp.submit(np.arange(4, dtype=np.int32), adapter="t-a")
    fp.close()


def test_preempt_restore_releases_and_reacquires_adapter(model_params):
    """Offload preemption drops the victim's adapter binding (its pages
    become evictable while parked) and reacquires on restore; the resumed
    stream is byte-exact with an uninterrupted reference."""
    e = _build_engine(model_params, num_blocks=10)
    load_lora_adapter(e, "t-a", _adapter_state(e, 2, seed=7))
    rng = np.random.RandomState(5)
    p_lo, p_hi = _prompt(rng, 24), _prompt(rng, 112)
    ref = _serve_direct(e, 970, p_lo, 40, adapter="t-a")
    classes = [{"name": "hi", "priority": 2, "ttft_slo_ms": 1e6,
                "tbt_slo_ms": 1e6},
               {"name": "lo", "priority": 0, "ttft_slo_ms": 1e6,
                "tbt_slo_ms": 1e6}]
    fe = e.serving_frontend(config=_serving_cfg(classes=classes))
    h_lo = fe.submit(p_lo, priority="lo", max_new_tokens=40, adapter="t-a")
    for _ in range(5):
        fe.step()
    assert h_lo.status == "decoding"
    assert e.lora.refcount("t-a") == 1
    h_hi = fe.submit(p_hi, priority="hi", max_new_tokens=8)
    assert _step_until(fe, lambda: h_lo.status == "preempted", 30)
    assert e.lora.refcount("t-a") == 0    # binding dropped while parked
    assert _step_until(fe, lambda: h_lo.finished and h_hi.finished)
    assert h_lo.status == "finished"
    assert h_lo.result(5) == ref
    assert e.lora.refcount("t-a") == 0
    fe.close()


def test_registry_metadata_reads_survive_a_mutating_engine_thread():
    """Regression (threadlint TL003): ``names``/``can_admit``/``rank`` are
    called from CLIENT threads (frontend submit validation) and the
    router's adapter-state probe while the ENGINE thread mutates the
    adapter map — unguarded, the readers iterated ``_adapters`` /
    ``_bindings`` mid-resize (``RuntimeError: dictionary changed size
    during iteration``) or saw half-updated metadata. The ``_meta`` lock
    now guards map shape + metadata for both sides; this stress drives a
    register/unregister churn loop against a hot reader and requires zero
    errors on either side."""
    import threading
    import time

    reg = _registry(ranks=(2,))
    stop = threading.Event()
    errs = []

    def engine_mutator():
        i = 0
        try:
            while not stop.is_set():
                name = f"churn{i % 16}"
                reg.register(name, None)    # rank-0: pure metadata churn
                reg.acquire(30_000 + i, name)
                reg.release(30_000 + i)
                reg.unregister(name)
                i += 1
        except BaseException as exc:        # surfaced to the assert below
            errs.append(exc)

    t = threading.Thread(target=engine_mutator, name="dstpu-engine-fake")
    t.start()
    deadline = time.monotonic() + 1.0
    try:
        while time.monotonic() < deadline and not errs:
            assert "a0" in reg.names
            assert reg.can_admit("a0")
            assert reg.rank("a0") == 2
            assert reg.refcount("a0") == 0
    except BaseException as exc:
        errs.append(exc)
    finally:
        stop.set()
        t.join(10.0)
    assert not errs, errs
