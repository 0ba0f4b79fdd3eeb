"""The async double-buffered serving hot path (inference/v2/pipeline.py) and
its supporting machinery: bucketed decode batches, the compile counter + AOT
warmup grid, the persistent compile cache wiring, and the pipeline monitor
fields. docs/SERVING.md describes the design under test."""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from deepspeed_tpu.utils.caching import next_pow2


def _model_and_params(seed=0):
    cfg = LlamaConfig.tiny(vocab_size=128, max_position_embeddings=128)
    model = LlamaForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(seed),
                        {"input_ids": jnp.zeros((1, 8), jnp.int32)})["params"]
    return model, params


def _build_engine(seed=0, compile_cfg=None, model_params=None):
    model, params = model_params or _model_and_params(seed)
    econf = {"dtype": jnp.float32,
             "state_manager": {"max_tracked_sequences": 4,
                               "max_ragged_sequence_count": 4,
                               "max_ragged_batch_size": 32,
                               "max_context": 128},
             "kv_cache": {"block_size": 16}}
    if compile_cfg is not None:
        econf["compile"] = compile_cfg
    return InferenceEngineV2(model=model, model_parameters=params,
                             config=econf)


PROMPTS = [np.array([3, 14, 15, 92, 6], np.int32),
           np.array([27, 18, 28, 18], np.int32),
           np.array([31, 41, 59, 26, 53, 58], np.int32)]


def _loop_decode(engine, uids, n):
    outs = [[] for _ in uids]
    for _ in range(n):
        ids = engine.sample_next(uids)
        for i, t in enumerate(ids):
            outs[i].append(int(t))
        engine.put(uids, [np.asarray([t], np.int32) for t in ids])
    return outs


@pytest.fixture(scope="module")
def warm_engine():
    """One warmed engine shared by the read-mostly tests (compiles are the
    expensive part on this box; tests that need fresh state build their own)."""
    return _build_engine(
        compile_cfg={"warmup": True, "warmup_buckets": [1, 2, 4]})


# --------------------------------------------------------------------------- #
# correctness: pipeline == per-token loop (greedy, with pads)
# --------------------------------------------------------------------------- #

def test_pipeline_matches_loop_with_pad_rows(warm_engine):
    """3 live rows -> bucket 4: one pad row decodes into the scratch page.
    Greedy streams and continuation state must match the per-token loop
    byte for byte (row independence under padding)."""
    N = 7
    e1 = _build_engine()
    e1.put([0, 1, 2], PROMPTS)
    ref = _loop_decode(e1, [0, 1, 2], N)
    ref_next = list(e1.sample_next([0, 1, 2]))

    e2 = warm_engine
    e2.put([0, 1, 2], PROMPTS)
    c0 = e2.compiles
    pipe = e2.decode_pipeline([0, 1, 2])
    got = pipe.run(N)
    assert got.shape == (3, N)
    assert [list(r) for r in got] == ref
    assert list(e2.sample_next([0, 1, 2])) == ref_next
    # in-grid serving after warmup: ZERO new programs (acceptance criterion)
    assert e2.compiles == c0
    e2.flush([0, 1, 2])


def test_warmup_covers_put_and_a_pipeline_run(warm_engine):
    """put() prefill + continuation passes and an in-grid pipeline run
    (buckets from the warmup config) build nothing new."""
    e = warm_engine
    c0 = e.compiles
    e.put([5, 6, 7], PROMPTS)
    got = e.decode_pipeline([5, 6, 7]).run(3)  # bucket 4 pre-warmed
    assert got.shape == (3, 3)
    assert e.compiles == c0
    e.flush([5, 6, 7])


def test_warmup_traces_under_the_roomy_frame(warm_engine, monkeypatch):
    """Every program of the grid is traced somewhere above ONE frame of
    ``compile_cache.with_stack_room`` (the depth of the stack under a trace
    sets its speed where frames are kept in small chunks: PERF.md, PR 45)."""
    import sys
    seen = []

    def _warmup(buckets, spec_ks):
        f, names = sys._getframe(), []
        while f is not None:
            names.append((f.f_code.co_name, f.f_code.co_nlocals))
            f = f.f_back
        seen.append(names)
        return 0

    monkeypatch.setattr(warm_engine, "_warmup", _warmup)
    assert warm_engine.warmup(buckets=[1]) == 0
    assert [n for n, k in seen[0] if k >= 1 << 16] == ["roomy"]


# --------------------------------------------------------------------------- #
# bucketing: key rounding + executable reuse across live counts
# --------------------------------------------------------------------------- #

def test_decode_steps_key_rounds_to_bucket():
    e = _build_engine()
    e.put([0, 1, 2], PROMPTS)
    e.decode_pipeline([0, 1, 2]).run(2)        # S=3 -> bucket 4
    c_after_first = e.compiles
    # key carries the BUCKET (then sampling, rank bucket and split rung)
    assert (4, False, 0, 0, 1) in e._step_progs
    e.put([3], [np.array([9, 9, 9], np.int32)])
    e.decode_pipeline([0, 1, 2, 3]).run(2)     # S=4 -> same bucket, same prog
    assert e.compiles == c_after_first
    assert len(e._step_progs) == 1
    # a sequence retiring below the bucket boundary compiles the next bucket
    e.flush([2, 3])
    e.decode_pipeline([0, 1]).run(2)           # S=2 -> bucket 2: one build
    assert e.compiles == c_after_first + 1
    e.flush([0, 1])


def test_pipeline_retire_between_runs_reuses_grid(warm_engine):
    e = warm_engine
    e.put([0, 1, 2], PROMPTS)
    pipe = e.decode_pipeline([0, 1, 2])
    c0 = e.compiles
    pipe.run(3)                                # bucket 4 (warm)
    pipe.retire([1])
    e.flush([1])
    got = pipe.run(4)                          # 2 live -> bucket 2 (warm)
    assert got.shape == (2, 4)
    assert e.compiles == c0
    e.flush([0, 2])


def test_decode_batch_pad_rows_are_scratch():
    e = _build_engine()
    e.put([0, 1, 2], PROMPTS)
    db = e.scheduler.decode_batch([0, 1, 2], 4, e.scratch_block)
    assert db.bucket == 4 and db.live == 3
    # pad row: scratch-only block table, position 0, ctx 1
    assert (db.block_tables[3] == e.scratch_block).all()
    assert db.positions[3] == 0 and db.ctx_lens[3] == 1
    # real rows: the sequences' own tables and positions
    for i, u in enumerate([0, 1, 2]):
        seq = e.scheduler.seqs[u]
        assert db.positions[i] == seq.seen_tokens
        assert db.ctx_lens[i] == seq.seen_tokens + 1
        assert db.block_tables[i, 0] == seq.blocks[0]
    # the scratch page sits outside the allocator's pool on purpose
    assert e.scratch_block == e.allocator.total_blocks
    assert e.kv.config.num_blocks == e.allocator.total_blocks + 1
    e.flush([0, 1, 2])
    assert e.free_blocks == e.allocator.total_blocks


# --------------------------------------------------------------------------- #
# mid-run retirement (the one-step-late drain's stop semantics)
# --------------------------------------------------------------------------- #

def test_pipeline_on_tokens_retirement(warm_engine):
    e = warm_engine
    e.put([0, 1, 2], PROMPTS)
    ref = {}
    eref = _build_engine()
    eref.put([0, 1, 2], PROMPTS)
    for u, row in zip([0, 1, 2], eref.decode_pipeline([0, 1, 2]).run(6)):
        ref[u] = list(row)

    retired_at = {}

    def on_tokens(step, uids, row):
        assert len(row) == len(uids)
        if step == 2:                      # observed token 2 -> retire uid 1
            retired_at[1] = step
            return [1]
        return None

    pipe = e.decode_pipeline([0, 1, 2])
    got = pipe.run(6, on_tokens=on_tokens)
    assert pipe.uids == [0, 2]
    # survivors' streams are untouched by the retirement (row independence)
    assert list(got[0]) == ref[0] and list(got[2]) == ref[2]
    # the retired row recorded exactly step+1 tokens into its history
    assert e.scheduler.seqs[1].seen_tokens == len(PROMPTS[1]) + 3
    # its prefix up to retirement matches too (drained before the stop)
    assert list(got[1][:3]) == ref[1][:3]
    # continuation refs are dropped: the uid must be flushed / re-put
    assert 1 not in e._last_ref and 1 not in e._last_logits
    e.flush([0, 1, 2])
    assert e.free_blocks == e.allocator.total_blocks


def test_pipeline_on_tokens_exception_settles_state(warm_engine):
    """An escaping callback must not desynchronize sequence history from the
    KV already written: drained tokens become history, refs drop, the uids
    leave the pipeline, and a flush fully recovers the pool."""
    e = warm_engine
    e.put([0, 1], PROMPTS[:2])
    pipe = e.decode_pipeline([0, 1])

    def boom(step, uids, row):
        if step == 1:
            raise RuntimeError("client hung up")

    with pytest.raises(RuntimeError, match="client hung up"):
        pipe.run(6, on_tokens=boom)
    assert pipe.uids == []
    for u in (0, 1):   # tokens 0 and 1 were drained before the raise
        assert e.scheduler.seqs[u].seen_tokens == len(PROMPTS[u]) + 2
        assert u not in e._last_ref and u not in e._last_logits
    e.flush([0, 1])
    assert e.free_blocks == e.allocator.total_blocks


# --------------------------------------------------------------------------- #
# monitor: per-step pipeline timings + the fetch-bytes invariant
# --------------------------------------------------------------------------- #

class _CaptureMonitor:
    def __init__(self):
        self.events = []

    def write_events(self, event_list):
        self.events.extend(event_list)


def test_pipeline_stats_and_monitor_fields(warm_engine):
    e = warm_engine
    e.put([0, 1], PROMPTS[:2])
    e.pipeline_stats.reset()
    pipe = e.decode_pipeline([0, 1])
    pipe.run(5)
    st = e.pipeline_stats
    assert st.steps == 5 and st.tokens == 10
    # THE tentpole invariant: the per-step device->host transfer is one int32
    # token row per bucket slot — not a logits block
    assert st.fetch_bytes_per_step == 4.0 * next_pow2(2)
    assert st.last_fetch_bytes == 4 * next_pow2(2)
    assert len(st.step_wall_ms) == 5 and all(w > 0 for w in st.step_wall_ms)
    mon = _CaptureMonitor()
    e.write_monitor_events(mon, step=3)
    names = {n for n, _, _ in mon.events}
    for field in ("dispatch_ms_per_step", "host_build_ms_per_step",
                  "fetch_drain_ms_per_step", "bubble_ms_per_step",
                  "fetch_bytes_per_step", "steps", "tokens"):
        assert f"inference/v2/pipeline/{field}" in names
    assert all(s == 3 for _, _, s in mon.events)
    e.flush([0, 1])


# --------------------------------------------------------------------------- #
# persistent compile cache (utils/compile_cache.py, config_v2.CompileConfig)
# --------------------------------------------------------------------------- #

def _point_jax_at(monkeypatch, directory):
    """What a driver does before the process starts: place the cache through
    JAX_COMPILATION_CACHE_DIR. jax reads the variable at import, so a test
    that sets it late mirrors it into the config itself."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", directory)
    jax.config.update("jax_compilation_cache_dir", directory)
    cc.reset_cache()


@pytest.fixture
def restore_cache_config():
    from jax.experimental.compilation_cache import compilation_cache as cc
    prior_dir = jax.config.jax_compilation_cache_dir
    prior_min = jax.config.jax_persistent_cache_min_compile_time_secs
    yield
    cc.reset_cache()
    jax.config.update("jax_compilation_cache_dir", prior_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", prior_min)


def test_compile_cache_placed_from_outside(monkeypatch, tmp_path,
                                           restore_cache_config):
    from deepspeed_tpu.inference.v2.config_v2 import CompileConfig
    from deepspeed_tpu.utils.compile_cache import (host_fingerprint,
                                                   setup_compile_cache)
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    # unset: the fixed path under the checkout, host-keyed on the CPU
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert setup_compile_cache() == os.path.join(
        repo, ".jax_cache", f"cpu-{host_fingerprint()}")
    # set: that directory untouched by code — no sub-directory, no override
    placed = str(tmp_path / "placed")
    _point_jax_at(monkeypatch, placed)
    assert setup_compile_cache() == placed
    assert jax.config.jax_compilation_cache_dir == placed
    # set too late for jax to have read it: refused, not silently cold
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "late"))
    with pytest.raises(RuntimeError, match="before jax is imported"):
        setup_compile_cache()
    # no config field can move the cache
    with pytest.raises(TypeError):
        CompileConfig(cache_dir=placed)
    # non-pow2 buckets normalize to the grid (same rounding as warmup())
    assert CompileConfig(warmup_buckets=[3, 4, 6]).warmup_buckets == [4, 8]
    with pytest.raises(ValueError):
        CompileConfig(warmup_buckets=[0])


def test_second_engine_hits_persistent_cache(monkeypatch, tmp_path,
                                             restore_cache_config):
    """Engine #1 (warmup on, fresh cache dir) populates the persistent cache;
    engine #2 with the same config must reload every program — no new cache
    entries written (file count is the compile witness XLA gives us)."""
    cache_root = str(tmp_path / "ccache")
    cfg = {"min_compile_time_secs": 0.0, "warmup": True,
           "warmup_buckets": [1]}

    def count_entries():
        # executables only: jax's lru_cache backend also touches "-atime"
        # bookkeeping files on cache HITS, which must not count as compiles
        return len([p for p in glob.glob(os.path.join(cache_root, "**"),
                                         recursive=True)
                    if os.path.isfile(p) and not p.endswith("-atime")])

    # model init once, OUTSIDE the cached window: its programs compile before
    # the cache is re-pointed, so a per-engine init would write its entries
    # only on the second pass and fake a miss
    mp = _model_and_params()
    _point_jax_at(monkeypatch, cache_root)
    e1 = _build_engine(compile_cfg=cfg, model_params=mp)
    e1.put([0], [PROMPTS[0]])
    e1.decode_pipeline([0]).run(2)
    jax.effects_barrier()
    n1 = count_entries()
    assert n1 > 0, "warmup wrote nothing to the persistent cache"
    del e1
    e2 = _build_engine(compile_cfg=cfg, model_params=mp)
    e2.put([0], [PROMPTS[0]])
    e2.decode_pipeline([0]).run(2)
    jax.effects_barrier()
    assert count_entries() == n1, \
        "second engine construction recompiled instead of hitting the cache"


def test_pipeline_traced_run_byte_identical_with_serve_spans(warm_engine):
    """Span tracing ON must not change a single token or add a compile, and
    must leave serve/decode/* spans whose per-step count matches the stats
    (docs/OBSERVABILITY.md — one set of perf pairs feeds both)."""
    from deepspeed_tpu.monitor.trace import tracer
    N = 6
    e = warm_engine
    e.put([0, 1, 2], PROMPTS)
    pipe = e.decode_pipeline([0, 1, 2])
    ref = pipe.run(N)
    e.flush([0, 1, 2])

    tracer.reset()
    tracer.configure(enabled=True, ring_size=1024)
    try:
        e.put([0, 1, 2], PROMPTS)
        c0 = e.compiles
        e.pipeline_stats.reset()
        pipe = e.decode_pipeline([0, 1, 2])
        got = pipe.run(N)
        assert e.compiles == c0                       # no traced recompiles
        assert np.array_equal(got, ref)               # byte-identical stream
        summary = tracer.summary()
        assert summary["serve/decode/step"][0] == e.pipeline_stats.steps == N
        assert summary["serve/decode/dispatch"][0] == N
        # the drain spans attribute the policed fetch_to_host by name
        assert "serve/drain/fetch_to_host" in summary
        e.flush([0, 1, 2])
    finally:
        tracer.reset()


def _trace_check(path):
    """The real ``scripts/trace_check.py`` (docs/OBSERVABILITY.md, "Validating
    traces") over an exported timeline."""
    import pathlib
    import subprocess
    import sys
    r = subprocess.run(
        [sys.executable, "scripts/trace_check.py", str(path),
         "--require", "serve/decode"], capture_output=True, text=True,
        cwd=str(pathlib.Path(__file__).resolve().parents[2]))
    assert r.returncode == 0, r.stdout + r.stderr


def _step_records(tracer):
    return sorted((r[5] for r in tracer.iter_records()
                   if r[1] == "serve/decode/step"), key=lambda a: a["step"])


def test_decode_step_records_say_what_the_live_rows_held(warm_engine,
                                                         tmp_path):
    """Every ``serve/decode/step`` record carries, for the rows still live at
    that step, the sum of the contexts the step's program was handed (a row:
    the tokens the scheduler held at the run's start, the ones decoded since
    and the one fed now) and the whole pages that hold them — through a
    retirement in the middle of the run: the retired row stops counting at
    the step ``live`` drops. Plain ints, and the exported timeline is valid."""
    from deepspeed_tpu.monitor.trace import tracer
    N, GONE_AT = 13, 4
    e = warm_engine
    bs = e.kv.config.block_size
    tracer.reset()
    tracer.configure(trace_dir=str(tmp_path), enabled=True, ring_size=1024)
    try:
        e.put([0, 1, 2], PROMPTS)
        seen = [e.scheduler.seqs[u].seen_tokens for u in (0, 1, 2)]
        assert seen == [len(p) for p in PROMPTS]
        pipe = e.decode_pipeline([0, 1, 2])
        pipe.run(N, on_tokens=lambda j, uids, row: [1] if j == GONE_AT
                 else None)
        assert pipe.uids == [0, 2]
        steps = _step_records(tracer)
        assert [a["step"] for a in steps] == list(range(N))
        crossed = False
        for j, a in enumerate(steps):
            rows = [0, 1, 2] if j <= GONE_AT else [0, 2]
            ctx = [seen[i] + j + 1 for i in rows]
            assert a["live"] == len(rows)
            assert a["ctx"] == sum(ctx)
            assert a["pages"] == sum(-(-c // bs) for c in ctx)
            crossed = crossed or a["pages"] > len(rows)
            assert "ctx_window" not in a         # no windowed layer here
            assert all(type(v) is int for v in a.values())
        assert crossed                            # a row took a second page
        _trace_check(tracer.export())
        e.flush([0, 1, 2])
    finally:
        tracer.reset()


def test_decode_step_records_clip_to_the_window_and_the_ring(tmp_path):
    """On a windowed model the record also carries ``ctx_window``, the tokens
    a windowed layer's query still sees (``min(ctx, window)`` a row, summed),
    and a row's pages stop at the page ring."""
    from deepspeed_tpu.monitor.trace import tracer
    cfg = LlamaConfig.tiny(vocab_size=128, max_position_embeddings=128,
                           sliding_window=8)
    model = LlamaForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(3),
                        {"input_ids": jnp.zeros((1, 8), jnp.int32)})["params"]
    e = _build_engine(model_params=(model, params))
    ring, bs = e.scheduler.ring_pages, e.kv.config.block_size
    assert e._windowed_layers == [(8, cfg.num_hidden_layers)] and ring
    prompts = [np.arange(ring * bs + 3, dtype=np.int32) % 128, PROMPTS[0]]
    N = 5
    tracer.reset()
    tracer.configure(enabled=True, ring_size=1024)
    try:
        e.put([0, 1], prompts)
        e.decode_pipeline([0, 1]).run(N)
        steps = _step_records(tracer)
        assert len(steps) == N
        for j, a in enumerate(steps):
            ctx = [len(p) + j + 1 for p in prompts]
            assert a["ctx"] == sum(ctx)
            assert a["ctx_window"] == sum(min(c, 8) for c in ctx)
            assert a["pages"] == sum(min(-(-c // bs), ring) for c in ctx)
            assert a["pages"] < sum(-(-c // bs) for c in ctx)
            assert all(type(v) is int for v in a.values())
        # the short row crossed the window inside the run
        assert steps[0]["ctx_window"] < steps[-1]["ctx_window"] == 16
    finally:
        tracer.reset()


def test_untraced_steps_compute_nothing_of_what_they_held(warm_engine,
                                                          monkeypatch):
    """With tracing off the step loop does not run the reductions."""
    from deepspeed_tpu.inference.v2 import pipeline
    from deepspeed_tpu.monitor.trace import tracer

    def boom(*a, **kw):
        raise AssertionError("rows_held ran with tracing off")

    monkeypatch.setattr(pipeline, "rows_held", boom)
    assert not tracer.enabled
    e = warm_engine
    e.put([0, 1, 2], PROMPTS)
    out = e.decode_pipeline([0, 1, 2]).run(3)
    assert out.shape == (3, 3)
    e.flush([0, 1, 2])


# --------------------------------------------------------------------------- #
# generate() routed through the pipeline (the one-off API shares the hot path)
# --------------------------------------------------------------------------- #

def _old_loop_generate(e, prompts, n, eos=None):
    """The pre-PR per-token sample_next/put loop generate() used to drive —
    the byte-equality reference for the pipeline-routed steady state."""
    uids = list(range(len(prompts)))
    outs = [list(map(int, p)) for p in prompts]
    e.put(uids, prompts)
    live = set(uids)
    for step in range(n):
        batch = sorted(live)
        toks = e.sample_next(batch)
        nxt = {}
        for u, t in zip(batch, toks):
            t = int(t)
            outs[u].append(t)
            if eos is not None and t == eos:
                live.discard(u)
                e.flush([u])
            else:
                nxt[u] = t
        if not nxt or step == n - 1:
            break
        e._put_nofetch(sorted(nxt), [np.asarray([nxt[u]], np.int32)
                                     for u in sorted(nxt)])
    e.flush(sorted(live))
    return outs


def test_generate_matches_old_per_token_loop(warm_engine):
    """generate() now drives decode_pipeline; greedy output must stay byte-
    identical to the old per-token loop, with and without EOS early-exit,
    and release every block."""
    ref_engine = _build_engine()
    ref = _old_loop_generate(ref_engine, PROMPTS, 9)
    e = warm_engine
    free0 = e.free_blocks
    got = e.generate(PROMPTS, max_new_tokens=9)
    assert got == ref
    assert e.free_blocks == free0

    eos = ref[0][len(PROMPTS[0]) + 3]          # stop seq 0 after 4 tokens
    ref_eos = _old_loop_generate(_build_engine(), PROMPTS, 9, eos=eos)
    got_eos = e.generate(PROMPTS, max_new_tokens=9, eos_token_id=eos)
    assert got_eos == ref_eos
    assert e.free_blocks == free0


def test_generate_zero_new_compiles_in_grid(warm_engine):
    """A warmed engine's generate() (pipeline-routed) builds nothing new for
    in-grid batch sizes."""
    e = warm_engine
    c0 = e.compiles
    e.generate(PROMPTS, max_new_tokens=5)
    assert e.compiles == c0


# --------------------------------------------------------------------------- #
# the decode step's K/V write: pool bytes, against the per-step-write loop
# --------------------------------------------------------------------------- #

def _one_layer_engine(kvq: bool):
    """head_dim 128 (the side buffer's gate), ONE layer: a layer's K/V rows
    depend on no attention output, so the decode step's two forms (the side
    buffer, the in-layer write) must leave the same BYTES in the pool."""
    cfg = LlamaConfig(vocab_size=128, hidden_size=256, intermediate_size=256,
                      num_hidden_layers=1, num_attention_heads=2,
                      num_key_value_heads=2, max_position_embeddings=512,
                      dtype=jnp.float32)
    model = LlamaForCausalLM(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(2),
                                 {"input_ids": jnp.zeros((1, 8), jnp.int32)}
                                 )["params"]
    econf = {"dtype": jnp.float32,
             "state_manager": {"max_tracked_sequences": 4,
                               "max_ragged_sequence_count": 4,
                               "max_ragged_batch_size": 160,
                               "prefill_chunk_size": 80,
                               "max_context": 256},
             "kv_cache": {"block_size": 64}}
    if kvq:
        econf["kv_quant"] = {"enabled": True}
    return InferenceEngineV2(model=model, model_parameters=params,
                             config=econf)


def _pool_bytes(engine):
    kv = engine.kv.kv
    leaves = kv if isinstance(kv, tuple) else (kv,)
    # the scratch page is the pad rows' and belongs to no sequence
    return [np.asarray(x)[:, :engine.scratch_block] for x in leaves]


@pytest.mark.parametrize("kvq", [False, True], ids=["f32_pool", "int8_pool"])
def test_step_and_burst_leave_the_per_step_loops_bytes_in_the_pool(
        kvq, monkeypatch):
    """Three live rows in a bucket of four (one pad row), one of them at
    slot 63 of its page so the next step opens a new page: two pipeline runs
    of one step, then a run of 8. The pool (and an int8 pool's scale tiles)
    must hold what the step's other form — each layer's kernel writing its
    rows — leaves there."""
    rng = np.random.RandomState(11)
    prompts = [rng.randint(0, 128, size=(n,)).astype(np.int32)
               for n in (63, 17, 70)]
    uids = [0, 1, 2]

    def serve(engine):
        engine.put(uids, list(prompts))
        pipe = engine.decode_pipeline(uids)
        runs = [pipe.run(n) for n in (1, 1, 8)]
        return np.concatenate(runs, axis=1), _pool_bytes(engine)

    from deepspeed_tpu.inference.v2 import ragged_model
    flushes = []
    flush = ragged_model.paged_kv_row_write
    monkeypatch.setattr(
        ragged_model, "paged_kv_row_write",
        lambda *a, **kw: (flushes.append(a[5]), flush(*a, **kw))[1])
    got_ids, got = serve(_one_layer_engine(kvq))
    assert set(flushes) == {1}              # at the program's tracing
    traced = len(flushes)
    # the other form: each layer's kernel writes its rows, nothing flushes
    monkeypatch.setattr(ragged_model, "side_buffer_fits",
                        lambda *a, **kw: False)
    want_ids, want = serve(_one_layer_engine(kvq))
    assert len(flushes) == traced
    np.testing.assert_array_equal(got_ids, want_ids)
    assert len(got) == (2 if kvq else 1)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(g, w)
    assert np.count_nonzero(got[0]) > 0
