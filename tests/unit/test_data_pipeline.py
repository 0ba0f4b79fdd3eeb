"""Data pipeline tests (parity: ``tests/unit/runtime/test_data_efficiency.py``
and indexed-dataset tests), plus the training input pipeline: dataloader
semantics, the PrefetchLoader producer, and the sync-vs-pipelined engine
equality gates (docs/TRAINING.md)."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.data import (CurriculumScheduler, DeepSpeedDataSampler,
                                MMapIndexedDataset, make_builder, make_dataset,
                                RandomLTDScheduler, gather_tokens,
                                random_ltd_indices, scatter_tokens,
                                slice_attention_mask)
from deepspeed_tpu.runtime.data_pipeline import (PrefetchLoader, StagedBatch,
                                                 as_host_tree, inject_pld,
                                                 needs_truncation,
                                                 truncate_to_seqlen)
from deepspeed_tpu.runtime.dataloader import (DeepSpeedTPUDataLoader,
                                              RepeatingLoader)


# ---------------------------- curriculum ---------------------------------- #

def _sched(**over):
    cfg = {"min_difficulty": 8, "max_difficulty": 64,
           "schedule_type": "fixed_linear",
           "schedule_config": {"total_curriculum_step": 100,
                               "difficulty_step": 8}}
    cfg.update(over)
    return CurriculumScheduler(cfg)


def test_fixed_linear_schedule():
    s = _sched()
    assert s.get_difficulty(0) == 8
    assert s.get_difficulty(100) == 64
    assert s.get_difficulty(1000) == 64
    mid = s.get_difficulty(50)
    assert 8 <= mid <= 64 and mid % 8 == 0
    # monotone non-decreasing
    vals = [s.get_difficulty(t) for t in range(0, 101, 10)]
    assert vals == sorted(vals)


def test_fixed_root_schedule():
    s = _sched(schedule_type="fixed_root",
               schedule_config={"total_curriculum_step": 100,
                                "difficulty_step": 8, "root_degree": 2})
    # sqrt schedule ramps faster early than linear
    assert s.get_difficulty(25) >= _sched().get_difficulty(25)
    assert s.get_difficulty(100) == 64


def test_fixed_discrete_schedule():
    s = _sched(schedule_type="fixed_discrete",
               schedule_config={"difficulty": [8, 16, 64],
                                "max_step": [10, 20]})
    assert s.get_difficulty(5) == 8
    assert s.get_difficulty(15) == 16
    assert s.get_difficulty(25) == 64


def test_curriculum_state_roundtrip():
    s = _sched()
    s.update_difficulty(50)
    st = s.get_state()
    s2 = _sched()
    s2.set_state(st)
    assert s2.current_difficulty == s.current_difficulty


# ---------------------------- indexed dataset ----------------------------- #

def test_indexed_dataset_roundtrip(tmp_path):
    prefix = str(tmp_path / "corpus")
    b = make_builder(prefix, dtype=np.int32)
    seqs = [np.arange(5), np.arange(100, 103), np.arange(7)]
    for s in seqs:
        b.add_item(s)
    b.end_document()
    b.finalize()
    ds = make_dataset(prefix)
    assert len(ds) == 3
    for i, s in enumerate(seqs):
        np.testing.assert_array_equal(ds[i], s.astype(np.int32))
    np.testing.assert_array_equal(ds.get(1, offset=1, length=2), [101, 102])
    with pytest.raises(IndexError):
        ds.get(0, offset=3, length=5)


def test_indexed_dataset_bad_magic(tmp_path):
    prefix = str(tmp_path / "bad")
    with open(prefix + ".idx", "wb") as f:
        f.write(b"WRONGMAG" + b"\0" * 32)
    with open(prefix + ".bin", "wb") as f:
        f.write(b"")
    with pytest.raises(ValueError, match="magic"):
        MMapIndexedDataset(prefix)


# ---------------------------- data sampler -------------------------------- #

def test_sampler_partitions_ranks():
    n, mbs, dp = 64, 4, 2
    samplers = [DeepSpeedDataSampler(n, mbs, data_parallel_rank=r,
                                     data_parallel_size=dp, seed=7)
                for r in range(dp)]
    seen = [set(), set()]
    for r, s in enumerate(samplers):
        for mb in s:
            assert len(mb) == mbs
            seen[r].update(mb)
    assert not (seen[0] & seen[1])  # disjoint across ranks
    assert len(seen[0] | seen[1]) == n


def test_sampler_resume():
    s = DeepSpeedDataSampler(32, 2, gradient_accumulation_steps=2, seed=3)
    it = iter(s)
    first = [next(it), next(it)]  # one global batch consumed
    state = s.state_dict()
    s2 = DeepSpeedDataSampler(32, 2, gradient_accumulation_steps=2, seed=3)
    s2.load_state_dict(state)
    resumed = list(s2)
    full = list(DeepSpeedDataSampler(32, 2, gradient_accumulation_steps=2, seed=3))
    assert resumed == full[2:]


def test_sampler_curriculum_defers_hard_samples():
    n = 32
    difficulties = np.arange(n)  # sample i has difficulty i
    cur = CurriculumScheduler({"min_difficulty": 8, "max_difficulty": 32,
                               "schedule_type": "fixed_linear",
                               "schedule_config": {"total_curriculum_step": 100,
                                                   "difficulty_step": 8}})
    s = DeepSpeedDataSampler(n, 4, difficulties=difficulties, curriculum=cur,
                             seed=0)
    first_batch = next(iter(s))
    assert all(difficulties[i] <= 8 for i in first_batch)


# ---------------------------- random-LTD ---------------------------------- #

def test_random_ltd_gather_scatter():
    rng = jax.random.PRNGKey(0)
    x = jnp.arange(2 * 8 * 4, dtype=jnp.float32).reshape(2, 8, 4)
    idx = random_ltd_indices(rng, 8, 3)
    assert idx.shape == (3,)
    assert bool(jnp.all(idx[:-1] < idx[1:]))  # sorted
    small = gather_tokens(x, idx)
    assert small.shape == (2, 3, 4)
    full = scatter_tokens(small, idx, 8)
    assert full.shape == x.shape
    np.testing.assert_allclose(gather_tokens(full, idx), small)
    kept = np.zeros(8, bool)
    kept[np.asarray(idx)] = True
    assert bool(jnp.all(full[:, ~kept] == 0))


def test_random_ltd_mask_slice():
    mask = jnp.arange(36, dtype=jnp.float32).reshape(6, 6)
    idx = jnp.array([1, 4])
    m = slice_attention_mask(mask, idx)
    np.testing.assert_array_equal(np.asarray(m),
                                  [[mask[1, 1], mask[1, 4]],
                                   [mask[4, 1], mask[4, 4]]])


def test_random_ltd_scheduler():
    s = RandomLTDScheduler(seq_len=128, start=32, total_steps=100, step_size=16)
    assert s.get_keep(0) == 32
    assert s.get_keep(100) == 128
    assert s.get_keep(50) % 16 == 0
    vals = [s.get_keep(t) for t in range(0, 101, 10)]
    assert vals == sorted(vals)


# ---------------------------- dataloader ---------------------------------- #

def test_loader_drop_last_length_math():
    data = list(range(10))
    assert len(DeepSpeedTPUDataLoader(data, batch_size=4)) == 2
    assert len(DeepSpeedTPUDataLoader(data, batch_size=4, drop_last=False)) == 3
    batches = list(DeepSpeedTPUDataLoader(data, batch_size=4, shuffle=False,
                                          drop_last=False))
    assert [len(b) for b in batches] == [4, 4, 2]
    batches = list(DeepSpeedTPUDataLoader(data, batch_size=4, shuffle=False))
    assert [len(b) for b in batches] == [4, 4]


def test_loader_collates_dicts_and_tuples():
    dict_data = [{"a": np.full((3,), i), "b": np.int32(i)} for i in range(4)]
    (batch,) = list(DeepSpeedTPUDataLoader(dict_data, batch_size=4,
                                           shuffle=False))
    assert set(batch) == {"a", "b"}
    assert batch["a"].shape == (4, 3) and batch["b"].shape == (4,)
    np.testing.assert_array_equal(batch["b"], [0, 1, 2, 3])

    tup_data = [(np.full((2,), i), np.full((1,), -i)) for i in range(4)]
    (batch,) = list(DeepSpeedTPUDataLoader(tup_data, batch_size=4,
                                           shuffle=False))
    assert isinstance(batch, tuple) and len(batch) == 2
    assert batch[0].shape == (4, 2) and batch[1].shape == (4, 1)


def test_loader_epoch_reshuffle_deterministic():
    """Shuffle order is a pure function of (seed, epoch): same-epoch loaders
    agree, different epochs differ, and set_epoch reproduces either."""
    data = [np.int32(i) for i in range(16)]

    def order(seed, epoch):
        ld = DeepSpeedTPUDataLoader(data, batch_size=4, seed=seed)
        ld.set_epoch(epoch)
        return [b.tolist() for b in ld]

    assert order(7, 0) == order(7, 0)
    assert order(7, 0) != order(7, 1)
    assert order(7, 1) == order(7, 1)
    assert order(7, 0) != order(8, 0)


def test_repeating_loader_epoch_autobump_reshuffles():
    """RepeatingLoader restarts with epoch+1 => the second pass is the
    epoch-1 shuffle, deterministically (seed+epoch), not a repeat."""
    data = [np.int32(i) for i in range(16)]
    ld = DeepSpeedTPUDataLoader(data, batch_size=4, seed=3)
    rep = iter(RepeatingLoader(ld))
    first = [next(rep).tolist() for _ in range(4)]
    second = [next(rep).tolist() for _ in range(4)]
    assert ld.epoch == 1
    assert first != second
    # both epochs visit the whole dataset
    assert sorted(sum(first, [])) == sorted(sum(second, [])) == list(range(16))
    # and a fresh run replays the identical two epochs
    rep2 = iter(RepeatingLoader(DeepSpeedTPUDataLoader(data, batch_size=4,
                                                       seed=3)))
    assert [next(rep2).tolist() for _ in range(4)] == first
    assert [next(rep2).tolist() for _ in range(4)] == second


# ---------------------------- staging helpers ------------------------------ #

def test_truncate_to_seqlen_views_not_copies():
    batch = {"ids": np.arange(32).reshape(4, 8), "meta": np.arange(4)}
    out = truncate_to_seqlen(batch, 4)
    assert out["ids"].shape == (4, 4)
    assert out["meta"].shape == (4,)
    # a view, not a copy
    assert out["ids"].base is not None
    assert np.shares_memory(out["ids"], batch["ids"])
    # off-boundary: no leaf exceeds -> tree returned with untouched leaves
    out2 = truncate_to_seqlen(batch, 8)
    assert out2["ids"] is batch["ids"]
    assert not needs_truncation(batch, 8)
    assert needs_truncation(batch, 7)


def test_inject_pld_step_keyed_determinism():
    base = jax.random.PRNGKey(0)
    b = {"input_ids": np.zeros((4, 2), np.int32)}
    one = inject_pld(dict(b), 4, 0.9, jax.random.fold_in(base, 5))
    two = inject_pld(dict(b), 4, 0.9, jax.random.fold_in(base, 5))
    other = inject_pld(dict(b), 4, 0.9, jax.random.fold_in(base, 6))
    np.testing.assert_array_equal(one["pld_rng"], two["pld_rng"])
    assert not np.array_equal(one["pld_rng"], other["pld_rng"])
    assert one["pld_theta"].shape == (4,)
    assert one["pld_theta"].dtype == np.float32


# ---------------------------- PrefetchLoader ------------------------------- #

def test_prefetch_loader_preserves_order_and_steps():
    items = [{"x": np.full((2,), i)} for i in range(8)]
    seen_steps = []

    def prepare(batch, step):
        seen_steps.append(step)
        return StagedBatch(batch, step)

    pl = PrefetchLoader(items, prepare=prepare, prefetch=2, start_step=10)
    out = list(pl)
    assert [int(s.tree["x"][0]) for s in out] == list(range(8))
    assert [s.step for s in out] == list(range(10, 18))
    assert seen_steps == list(range(10, 18))
    pl.close()


def test_prefetch_loader_sync_fallback_matches():
    items = [np.int32(i) for i in range(6)]
    prep = lambda b, s: (int(b), s)
    sync = list(PrefetchLoader(items, prepare=prep, prefetch=0))
    threaded = list(PrefetchLoader(items, prepare=prep, prefetch=3))
    assert sync == threaded == [(i, i) for i in range(6)]


def test_prefetch_loader_bounded_queue():
    """The producer stages at most ``prefetch`` batches ahead."""
    produced = []

    def gen():
        for i in range(100):
            produced.append(i)
            yield i

    pl = PrefetchLoader(gen(), prefetch=2)
    first = next(pl)
    time.sleep(0.3)   # give the producer every chance to overrun
    # 1 consumed + 2 queued + at most 1 in-flight in prepare
    assert first == 0
    assert len(produced) <= 4
    assert pl.depth <= 2
    pl.close()


def test_prefetch_loader_propagates_loader_exception():
    def gen():
        yield 1
        yield 2
        raise RuntimeError("corrupt shard")

    pl = PrefetchLoader(gen(), prefetch=2)
    assert next(pl) == 1
    assert next(pl) == 2
    with pytest.raises(RuntimeError, match="corrupt shard"):
        next(pl)
    # the loader is closed after the error surfaces
    with pytest.raises(StopIteration):
        next(pl)


def test_prefetch_loader_propagates_prepare_exception():
    def prepare(batch, step):
        if step == 1:
            raise ValueError("bad stage")
        return batch

    pl = PrefetchLoader([1, 2, 3], prepare=prepare, prefetch=1)
    assert next(pl) == 1
    with pytest.raises(ValueError, match="bad stage"):
        next(pl)


def test_prefetch_loader_close_joins_producer():
    def slow_gen():
        for i in range(1000):
            time.sleep(0.005)
            yield i

    pl = PrefetchLoader(slow_gen(), prefetch=2)
    next(pl)
    producer = pl._thread
    assert producer is not None and producer.is_alive()
    pl.close()
    assert not producer.is_alive()
    with pytest.raises(StopIteration):
        next(pl)
    pl.close()   # idempotent


def test_prefetch_loader_finite_loader_stops():
    pl = PrefetchLoader([1, 2], prefetch=2)
    assert list(pl) == [1, 2]
    with pytest.raises(StopIteration):
        next(pl)


# ---------------------- engine: pipelined step loop ------------------------ #

def _tiny_engine(data=None, prefetch=2, extra=None, seed_params=True):
    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHead

    model = GPT2LMHead(GPT2Config.tiny(vocab_size=64))
    params = model.init(jax.random.PRNGKey(0),
                        {"input_ids": np.zeros((2, 8), np.int32)})["params"]
    cfg = {"train_batch_size": 8, "steps_per_print": 0,
           "train_pipeline": {"prefetch": prefetch},
           "optimizer": {"type": "adamw", "params": {"lr": 1e-3}}}
    if extra:
        cfg.update(extra)
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=params if seed_params else None,
        training_data=data, config=cfg)
    return engine


def _lm_data(n=32, seqlen=8, vocab=64, seed=0):
    rng = np.random.default_rng(seed)
    return [{"input_ids": rng.integers(0, vocab, size=(seqlen,))
             .astype(np.int32)} for _ in range(n)]


def test_train_steps_pipelined_matches_sync_loop():
    """The tentpole gate in-suite: prefetch staging + deferred drain must not
    change the loss stream by a single bit vs fully synchronous staging."""
    data = _lm_data()
    e_sync = _tiny_engine(data, prefetch=0)
    e_pipe = _tiny_engine(data, prefetch=2)
    losses_sync = e_sync.train_steps(6)
    losses_pipe = e_pipe.train_steps(6)
    np.testing.assert_array_equal(losses_sync, losses_pipe)
    assert e_pipe.global_steps == 6
    assert e_pipe._prefetch_loader is not None
    assert e_pipe.train_stats.prefetched_steps >= 5  # first may stage inline
    c0 = e_pipe.compiles             # warm: staging never builds a program
    e_pipe.train_steps(3)
    assert e_pipe.compiles == c0
    e_pipe.destroy()
    assert e_pipe._prefetch_loader is None
    e_sync.destroy()


def test_deferred_drain_one_step_late_and_flush():
    data = _lm_data()
    engine = _tiny_engine(data, prefetch=0)
    engine.train_batch()
    # metrics of the just-dispatched step stay in flight...
    assert len(engine._pending_metrics) == 1
    engine.train_batch()
    assert len(engine._pending_metrics) == 1  # step 1 drained one step late
    engine.drain_metrics()
    assert len(engine._pending_metrics) == 0
    engine.destroy()


def test_wall_clock_breakdown_drains_every_step():
    data = _lm_data()
    engine = _tiny_engine(data, prefetch=0,
                          extra={"wall_clock_breakdown": True})
    engine.train_batch()
    assert len(engine._pending_metrics) == 0  # fully synchronous semantics
    engine.destroy()


def test_checkpoint_load_resets_prefetch_iterator(tmp_path):
    data = _lm_data()
    engine = _tiny_engine(data, prefetch=2)
    engine.train_steps(2)
    assert engine._prefetch_loader is not None
    engine.save_checkpoint(str(tmp_path))
    engine.load_checkpoint(str(tmp_path))
    # staged batches were keyed to the pre-load step counter: gone
    assert engine._prefetch_loader is None
    assert engine._data_iterator is None
    # and training resumes cleanly, rebuilding the pipeline
    engine.train_steps(2)
    assert engine.global_steps == 4
    engine.destroy()


def test_curriculum_bucket_cache_tracks_schedule():
    """The off-boundary fast path must not pin a stale seqlen: the staged
    width has to follow the schedule across bucket boundaries."""
    data = _lm_data(seqlen=16)
    extra = {"curriculum_learning": {
        "enabled": True, "min_difficulty": 8, "max_difficulty": 16,
        "schedule_type": "fixed_linear",
        "schedule_config": {"total_curriculum_step": 4, "difficulty_step": 8}}}
    engine = _tiny_engine(data, prefetch=0, extra=extra)
    widths = []
    orig = engine._shard_global_batch

    def spy(batch):
        widths.append(jax.tree_util.tree_leaves(batch)[0].shape[1])
        return orig(batch)

    engine._shard_global_batch = spy
    for _ in range(6):
        engine.train_batch()
    assert widths[0] == 8 and widths[-1] == 16
    assert engine.curriculum_scheduler.current_difficulty == 16
    # off-boundary steps hit the cached no-op/slice decision
    assert engine._curr_seqlen_state == (16, 16, False)
    engine.destroy()


def test_curriculum_cache_keys_on_widest_leaf():
    """Regression (PR-4 review): the no-op cache must key on the widest
    rank>=2 leaf, not the first — a 1-D first leaf (sorted dict order) with
    varying input width must still truncate."""
    data = _lm_data(seqlen=16)
    extra = {"curriculum_learning": {
        "enabled": True, "min_difficulty": 8, "max_difficulty": 8,
        "schedule_type": "fixed_linear",
        "schedule_config": {"total_curriculum_step": 1, "difficulty_step": 8}}}
    engine = _tiny_engine(prefetch=0, extra=extra)
    # "aux" sorts before "input_ids": the first tree leaf is rank-1
    narrow = {"aux": np.zeros((8,), np.float32),
              "input_ids": np.zeros((8, 8), np.int32)}
    wide = {"aux": np.zeros((8,), np.float32),
            "input_ids": np.ones((8, 24), np.int32)}
    s1 = engine._prepare_batch(narrow, 0)   # seeds the cache with need=False
    s2 = engine._prepare_batch(wide, 1)     # wider input MUST still truncate
    assert s1.tree["input_ids"].shape[-1] == 8
    assert s2.tree["input_ids"].shape[-1] == 8
    engine.destroy()


def test_train_stats_wall_window_bounded():
    from deepspeed_tpu.monitor.training import WALL_WINDOW, TrainPipelineStats
    st = TrainPipelineStats()
    for _ in range(WALL_WINDOW + 100):
        st.record_step(0.0, 0.0, 0.0, 0.0, 0.001)
    assert len(st.step_wall_ms) == WALL_WINDOW
    assert st.steps == WALL_WINDOW + 100


def test_mixed_explicit_and_pipelined_steps_stay_schedule_exact():
    """Regression (PR-4 review): an explicit train_batch() between argless
    pipelined steps moves the step counter outside the producer's keying —
    the engine must restage mismatched batches so the loss stream still
    matches a fully synchronous engine fed the same sequence."""
    data = _lm_data()
    rng = np.random.default_rng(9)
    explicit = {"input_ids": rng.integers(0, 64, size=(8, 8)).astype(np.int32)}
    extra = {"progressive_layer_drop": {"enabled": True, "theta": 0.5,
                                        "gamma": 0.1}}   # step-keyed staging

    def run(prefetch):
        e = _tiny_engine(data, prefetch=prefetch, extra=extra)
        losses = [float(e.train_batch()) for _ in range(2)]
        losses.append(float(e.train_batch(explicit)))
        losses += [float(e.train_batch()) for _ in range(3)]
        e.drain_metrics()
        e.destroy()
        return losses

    np.testing.assert_array_equal(run(0), run(2))


def test_engine_curriculum_seqlen(tmp_path):
    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHead

    model = GPT2LMHead(GPT2Config.tiny())
    cfg = {"train_batch_size": 8,
           "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
           "curriculum_learning": {"enabled": True, "min_difficulty": 8,
                                   "max_difficulty": 16,
                                   "schedule_type": "fixed_linear",
                                   "schedule_config": {"total_curriculum_step": 4,
                                                       "difficulty_step": 8}}}
    engine, *_ = deepspeed_tpu.initialize(model=model, config=cfg)
    batch = {"input_ids": np.zeros((8, 16), np.int32)}
    engine.train_batch(batch)  # step 0: seqlen 8
    assert engine.curriculum_scheduler.current_difficulty == 8
    for _ in range(4):
        engine.train_batch(batch)
    assert engine.curriculum_scheduler.current_difficulty == 16
