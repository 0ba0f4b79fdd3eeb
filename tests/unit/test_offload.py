"""ZeRO-Offload / ZeRO-Infinity swap subsystem tests.

Parity model: reference ``tests/unit/runtime/zero`` offload tests (cpu_offload
stage1/2, NVMe swap) — host-stepped training must track the device-stepped run,
checkpoints must round-trip, and the swapper must preserve bytes through
swap-out/swap-in cycles.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.runtime.swap_tensor import (OptimizerStateSwapper,
                                               PipelinedOptimizerSwapper,
                                               SwapBufferPool)


def _host_offload(leaves, **cfg_kw):
    """A HostOffloadOptimizer over the given fp32 leaves (cpu mode unless
    device= says otherwise)."""
    from deepspeed_tpu.config import OffloadDeviceEnum, OffloadOptimizerConfig
    from deepspeed_tpu.ops.adam import FusedAdam
    from deepspeed_tpu.runtime.zero.offload import HostOffloadOptimizer
    cfg_kw.setdefault("device", OffloadDeviceEnum.cpu)
    cfg = OffloadOptimizerConfig(**cfg_kw)
    return HostOffloadOptimizer(FusedAdam(lr=1e-2, weight_decay=0.01),
                                {k: np.asarray(v, np.float32)
                                 for k, v in leaves.items()}, cfg)


# --------------------------------------------------------------------------- #
# swapper units
# --------------------------------------------------------------------------- #

def test_buffer_pool_reuse():
    pool = SwapBufferPool(max_buffers=4)
    b1 = pool.get(1000)
    assert b1.nbytes >= 1000 and b1.nbytes % 4096 == 0
    pool.put(b1)
    b2 = pool.get(1000)
    assert b2 is b1  # reused, not reallocated
    v = pool.view(b2, (10, 25), np.float32)
    assert v.shape == (10, 25) and v.dtype == np.float32


def test_optimizer_swapper_roundtrip(tmp_path):
    sw = OptimizerStateSwapper(str(tmp_path / "swap"))
    a = np.random.rand(257).astype(np.float32)
    b = np.random.rand(8, 33).astype(np.float32)
    sw.register("exp_avg/a", a)
    sw.register("exp_avg/b", b)
    views = sw.swap_in(["exp_avg/a", "exp_avg/b"])
    np.testing.assert_array_equal(views["exp_avg/a"], a)
    views["exp_avg/a"] += 1.0
    sw.swap_out()
    got = sw.swap_in(["exp_avg/a"])
    np.testing.assert_allclose(got["exp_avg/a"], a + 1.0)
    sw.swap_out()
    all_t = sw.read_all()
    np.testing.assert_array_equal(all_t["exp_avg/b"], b)
    sw.close()


@pytest.mark.parametrize("pipeline", [False, True])
def test_pipelined_swapper_groups(tmp_path, pipeline):
    sw = PipelinedOptimizerSwapper(str(tmp_path / "swap"),
                                   pipeline_read=pipeline, pipeline_write=pipeline)
    arrays = {f"t{i}": np.full(100 + i, float(i), np.float32) for i in range(6)}
    for k, v in arrays.items():
        sw.register(k, v)
    groups = [["t0", "t1"], ["t2", "t3"], ["t4", "t5"]]
    seen = []

    def step(views):
        for name, v in views.items():
            v += 10.0
            seen.append(name)

    sw.run(groups, step)
    assert seen == [n for g in groups for n in g]
    final = sw.read_all()
    for i in range(6):
        np.testing.assert_allclose(final[f"t{i}"], arrays[f"t{i}"] + 10.0)
    sw.close()


# --------------------------------------------------------------------------- #
# swapper failure paths: errors surface, buffers return to the pool
# --------------------------------------------------------------------------- #

def _registered_pipelined(tmp_path, n=6, **kw):
    kw.setdefault("pipeline_read", True)
    kw.setdefault("pipeline_write", True)
    sw = PipelinedOptimizerSwapper(str(tmp_path / "swap"), **kw)
    for i in range(n):
        sw.register(f"t{i}", np.full(100 + i, float(i), np.float32))
    return sw, [[f"t{2 * i}", f"t{2 * i + 1}"] for i in range(n // 2)]


def test_swap_in_submit_failure_releases_buffers(tmp_path):
    sw = OptimizerStateSwapper(str(tmp_path / "swap"))
    sw.register("a", np.zeros(64, np.float32))
    sw.register("b", np.zeros(64, np.float32))
    calls = {"n": 0}

    def failing_pread(view, path):
        calls["n"] += 1
        return 0 if calls["n"] == 1 else -5   # second submit fails

    sw.handle.async_pread = failing_pread
    with pytest.raises(OSError):
        sw.swap_in(["a", "b"])
    # the first submit's buffer (and the failed one's) went back to the pool
    assert sw.pool.outstanding == 0 and not sw._views
    sw.close()


def test_swap_in_wait_failure_releases_buffers(tmp_path):
    sw = OptimizerStateSwapper(str(tmp_path / "swap"))
    sw.register("a", np.zeros(64, np.float32))
    sw.handle.wait = lambda: -9
    with pytest.raises(OSError):
        sw.swap_in(["a"])
    assert sw.pool.outstanding == 0
    sw.close()


def test_pipelined_run_read_failure_surfaces(tmp_path):
    sw, groups = _registered_pipelined(tmp_path)
    sw._read_handle.async_pread = lambda view, path: -5
    with pytest.raises(OSError):
        sw.run(groups, lambda views: None)
    assert sw.pool.outstanding == 0 and not sw._views
    sw.close()


def test_pipelined_run_write_failure_surfaces(tmp_path):
    sw, groups = _registered_pipelined(tmp_path)
    sw._write_handle.async_pwrite = lambda view, path: -7
    stepped = []
    with pytest.raises(OSError):
        sw.run(groups, lambda views: stepped.append(sorted(views)))
    assert stepped  # the failure came from the write stage, after a step
    assert sw.pool.outstanding == 0 and not sw._views
    sw.close()


def test_pipelined_run_stepfn_abort_returns_buffers(tmp_path):
    # an exception out of step_fn mid-pipeline (with group g+1's reads
    # already in flight and g-1's writes draining) must propagate AND leave
    # the pool at zero outstanding
    sw, groups = _registered_pipelined(tmp_path)
    count = {"n": 0}

    def step(views):
        count["n"] += 1
        if count["n"] == 2:
            raise RuntimeError("boom mid-pipeline")
        for v in views.values():
            v += 1.0

    with pytest.raises(RuntimeError, match="boom"):
        sw.run(groups, step)
    assert sw.pool.outstanding == 0 and not sw._views
    # the swapper is reusable after the abort
    seen = []
    sw.run(groups, lambda views: seen.extend(sorted(views)))
    assert seen == [n for g in groups for n in g]
    assert sw.pool.outstanding == 0
    sw.close()


# --------------------------------------------------------------------------- #
# pipelined host step: grouping, chunked kernel, byte equality
# --------------------------------------------------------------------------- #

def test_leaf_groups_sizing_and_nvme_expansion():
    leaves = {f"l{i}": np.zeros(37 + i, np.float32) for i in range(5)}
    off = _host_offload(leaves, group_size=2)
    groups = off.leaf_groups()
    assert [len(g) for g in groups] == [2, 2, 1]
    assert [n for g in groups for n in g] == list(leaves)
    # _nvme_groups expands the SAME chunks into master+moment swap names
    swap_groups = off._nvme_groups()
    assert [len(g) for g in swap_groups] == [6, 6, 3]   # adam: 3 names/leaf
    assert swap_groups[0][:3] == ["master/l0", "exp_avg/l0", "exp_avg_sq/l0"]
    off.close()
    # group_size=0 falls back to buffer_count (the NVMe sub-group sizing)
    off2 = _host_offload(leaves, buffer_count=3)
    assert [len(g) for g in off2.leaf_groups()] == [3, 2]
    off2.close()


def test_step_groups_matches_serial_step_bytes(monkeypatch):
    """The pipelined walk (worker pool + forced leaf chunking) must be
    bit-identical to the serial ``step`` — the kernels are elementwise."""
    from deepspeed_tpu.runtime.zero import offload as off_mod
    rng = np.random.default_rng(1)
    leaves = {f"l{i}": rng.standard_normal(137 + 31 * i).astype(np.float32)
              for i in range(5)}
    a = _host_offload(leaves)                          # serial baseline
    b = _host_offload(leaves, host_workers=3, group_size=2)
    monkeypatch.setattr(off_mod, "_CHUNK_ELEMS", 32)   # force many chunks
    phases = []
    for step in range(3):
        g = {k: (rng.standard_normal(v.shape) * 0.1).astype(np.float32)
             for k, v in leaves.items()}
        a.step({k: v.copy() for k, v in g.items()}, lr=1e-2)
        done = {}
        b.step_groups(
            lambda gi: {k: g[k].copy() for k in b.leaf_groups()[gi]},
            lr=1e-2,
            on_group_done=lambda gi, m: done.update(m),
            record=lambda phase, s: phases.append(phase))
        assert set(done) == set(leaves)   # every leaf reported upstream
    assert a.step_num == b.step_num == 3
    for k in leaves:
        np.testing.assert_array_equal(a.master[k], b.master[k])
        for sk in ("exp_avg", "exp_avg_sq"):
            np.testing.assert_array_equal(a.moments[sk][k], b.moments[sk][k])
    assert "fetch" in phases and "kernel" in phases
    a.close()
    b.close()
    assert b._kernel_pool is None   # close() tears the worker pool down


def test_delayed_update_config_alias():
    from deepspeed_tpu.config import DeepSpeedTPUConfig
    c = DeepSpeedTPUConfig.load({"zero_optimization": {"offload_optimizer": {
        "device": "cpu", "delayed_update": True}}})
    assert c.zero_optimization.offload_optimizer.delayed_param_update


# --------------------------------------------------------------------------- #
# engine integration
# --------------------------------------------------------------------------- #

def _model_and_batches(seed=0, steps=6):
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHead
    model = GPT2LMHead(GPT2Config(vocab_size=64, n_positions=16, n_embd=32,
                                  n_layer=2, n_head=2, dtype=jnp.float32))
    rng = np.random.default_rng(seed)
    batches = [{"input_ids": rng.integers(0, 64, (8, 16)).astype(np.int32)}
               for _ in range(steps)]
    return model, batches


def _config(offload=None, stage=1):
    cfg = {
        "train_batch_size": 8,
        "train_micro_batch_size_per_gpu": 1,
        "zero_optimization": {"stage": stage},
        "mesh": {"data": -1},
        "optimizer": {"type": "adamw", "params": {"lr": 1e-2, "weight_decay": 0.01}},
    }
    if offload:
        cfg["zero_optimization"]["offload_optimizer"] = offload
    return cfg


def _run(model, batches, cfg):
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=cfg)
    losses = [float(engine.train_batch(b)) for b in batches]
    return engine, losses


def test_cpu_offload_matches_device_step():
    model, batches = _model_and_batches()
    _, base_losses = _run(model, batches, _config())
    eng, off_losses = _run(model, batches, _config(offload={"device": "cpu"}))
    assert eng._offload is not None and not eng._offload.nvme
    # same math on host (native kernel or numpy) vs device fp32 — tight match
    np.testing.assert_allclose(off_losses, base_losses, rtol=2e-3, atol=2e-3)
    assert off_losses[-1] < off_losses[0]
    eng.destroy()


def test_nvme_offload_trains_and_swaps(tmp_path):
    model, batches = _model_and_batches()
    _, base_losses = _run(model, batches, _config())
    eng, off_losses = _run(model, batches, _config(offload={
        "device": "nvme", "nvme_path": str(tmp_path), "buffer_count": 3,
        "pipeline_read": True, "pipeline_write": True}))
    assert eng._offload.nvme
    assert eng._offload.swapper.element_count() > 0
    np.testing.assert_allclose(off_losses, base_losses, rtol=2e-3, atol=2e-3)
    eng.destroy()


def test_delayed_param_update_trains_and_drains():
    """ZeRO-Offload DPU: host step N overlaps device step N+1 (host-flow
    leaves one step stale). Training still converges; after the final drain
    every pending update has landed (checkpoint state == sync-mode layout)."""
    model, batches = _model_and_batches(steps=8)
    _, base_losses = _run(model, batches, _config(offload={"device": "cpu"}))
    eng, dpu_losses = _run(model, batches, _config(offload={
        "device": "cpu", "delayed_param_update": True}))
    assert eng._offload_pending is not None     # overlap actually in flight
    # close to the sync trajectory (one-step staleness, not divergence) and
    # clearly training
    assert dpu_losses[-1] < dpu_losses[0]
    np.testing.assert_allclose(dpu_losses[-1], base_losses[-1], rtol=0.05)
    # drain + checkpoint view must include the delayed update
    st = eng._offload_ckpt_state()
    assert eng._offload_pending is None
    host_master, _ = eng._offload.state_leaves()
    for k, v in host_master.items():
        np.testing.assert_array_equal(st["master"][k], v)
    eng.destroy()
    assert eng._offload_executor is None


def test_twin_flow_ratio_splits_leaves():
    from deepspeed_tpu.runtime.zero.offload import partition_leaves
    leaves = {"a": np.zeros(100), "b": np.zeros(1000), "c": np.zeros(10)}
    host, dev = partition_leaves(leaves, 0.2)
    assert set(host) | set(dev) == set(leaves) and host and dev
    # smallest leaves offload first
    assert "c" in host and "b" in dev
    model, batches = _model_and_batches()
    _, base_losses = _run(model, batches, _config())
    eng, off_losses = _run(model, batches,
                           _config(offload={"device": "cpu", "ratio": 0.5}))
    assert eng._offload_dev_names and eng._offload_host_names
    np.testing.assert_allclose(off_losses, base_losses, rtol=2e-3, atol=2e-3)
    eng.destroy()


def test_offload_checkpoint_interchange(tmp_path):
    """Offload-mode checkpoints load into a non-offload engine and vice versa
    (flat-key layout identical — the dp-resize/elastic story of SURVEY §5.4)."""
    model, batches = _model_and_batches()
    eng_off, _ = _run(model, batches[:3], _config(offload={"device": "cpu"}))
    eng_off.save_checkpoint(str(tmp_path / "ck"), tag="t1")

    # load into plain engine
    eng_plain, _ = _run(model, batches[:1], _config())
    eng_plain.load_checkpoint(str(tmp_path / "ck"), tag="t1")
    # continue training both; losses must match
    l_off = [float(eng_off.train_batch(b)) for b in batches[3:]]
    l_plain = [float(eng_plain.train_batch(b)) for b in batches[3:]]
    np.testing.assert_allclose(l_off, l_plain, rtol=2e-3, atol=2e-3)

    # and plain checkpoint loads into an offload engine
    eng_plain.save_checkpoint(str(tmp_path / "ck2"), tag="t2")
    eng_off2, _ = _run(model, batches[:1], _config(offload={"device": "cpu"}))
    eng_off2.load_checkpoint(str(tmp_path / "ck2"), tag="t2")
    assert eng_off2.global_steps == eng_plain.global_steps
    l3 = [float(eng_off2.train_batch(b)) for b in batches[3:]]
    l_plain2 = [float(eng_plain.train_batch(b)) for b in batches[3:]]
    np.testing.assert_allclose(l3, l_plain2, rtol=2e-3, atol=2e-3)


def test_overlap_step_matches_serial_engine_bytes(tmp_path):
    """The SAME device program runs under both orchestrations (overlap_step
    is host-side only), and the host kernels are elementwise — so the loss
    stream and the final masters must be byte-identical between the pre-PR
    serial step, the cpu pipeline, and the nvme pipeline."""
    model, batches = _model_and_batches()
    eng_s, l_s = _run(model, batches, _config(offload={
        "device": "cpu", "overlap_step": False, "buffer_count": 3}))
    eng_p, l_p = _run(model, batches, _config(offload={
        "device": "cpu", "buffer_count": 3}))
    eng_n, l_n = _run(model, batches, _config(offload={
        "device": "nvme", "nvme_path": str(tmp_path), "buffer_count": 3,
        "pipeline_read": True, "pipeline_write": True}))
    assert l_s == l_p == l_n
    m_s, _ = eng_s._offload.state_leaves()
    m_p, _ = eng_p._offload.state_leaves()
    m_n, _ = eng_n._offload.state_leaves()
    for k in m_s:
        np.testing.assert_array_equal(m_s[k], m_p[k])
        np.testing.assert_array_equal(m_s[k], m_n[k])
    for e in (eng_s, eng_p, eng_n):
        c0 = e.compiles              # warm: neither orchestration compiles
        e.train_batch(batches[0])
        assert e.compiles == c0
        e.destroy()


def test_offload_engine_groups_align_with_optimizer():
    model, batches = _model_and_batches(steps=1)
    eng, _ = _run(model, batches, _config(offload={"device": "cpu",
                                                   "group_size": 4}))
    assert eng._offload_groups == eng._offload.leaf_groups()
    assert len(eng._offload_group_meta) == len(eng._offload_groups)
    for names, meta in zip(eng._offload_groups, eng._offload_group_meta):
        assert [m[0] for m in meta] == names
        off = 0
        for _, o, n, shape in meta:   # offsets tile the group flat exactly
            assert o == off and n == int(np.prod(shape))
            off += n
    eng.destroy()


def test_offload_ckpt_state_batches_drains(monkeypatch):
    """Regression: the checkpoint view used one fetch_to_host PER LEAF for
    the device-flow masters (a full link round trip each); it must be a
    bounded number of tree-level drains."""
    model, batches = _model_and_batches(steps=2)
    eng, _ = _run(model, batches,
                  _config(offload={"device": "cpu", "ratio": 0.5}))
    assert len(eng._offload_dev_names) > 2   # per-leaf would exceed the bound
    import deepspeed_tpu.runtime.engine as engine_mod
    real = engine_mod.fetch_to_host
    calls = []

    def counting(tree):
        calls.append(tree)
        return real(tree)

    monkeypatch.setattr(engine_mod, "fetch_to_host", counting)
    st = eng._offload_ckpt_state()
    assert set(st["master"]) == set(eng._offload_dev_names) | \
        set(eng._offload_host_names)
    assert len(calls) <= 2   # one for the master dict, one for the opt tree
    eng.destroy()


def test_offload_pipeline_stats_recorded():
    from deepspeed_tpu.monitor import OffloadPipelineStats
    model, batches = _model_and_batches(steps=3)
    eng, _ = _run(model, batches, _config(offload={"device": "cpu",
                                                   "buffer_count": 3}))
    st = eng.offload_stats
    assert isinstance(st, OffloadPipelineStats)
    n_groups = len(eng._offload_groups)
    assert st.steps == len(batches)
    assert st.groups == st.steps * n_groups
    assert st.kernel_ms > 0.0
    names = [e[0] for e in st.events(0)]
    assert "train/offload/kernel_ms_per_group" in names
    assert "train/offload/swap_ms_per_step" in names
    st.reset()
    assert st.steps == 0 and st.kernel_ms == 0.0
    eng.destroy()


def test_offload_worker_pools_torn_down_on_destroy():
    model, batches = _model_and_batches(steps=2)
    eng, _ = _run(model, batches, _config(offload={"device": "cpu"}))
    off = eng._offload
    eng.destroy()
    assert eng._offload_upload_pool is None
    assert off._kernel_pool is None


def test_offload_rejects_unsupported_optimizer():
    import optax
    model, batches = _model_and_batches()
    cfg = _config(offload={"device": "cpu"})
    cfg.pop("optimizer")
    with pytest.raises(ValueError, match="offload_optimizer does not support"):
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=model, config=cfg, optimizer=optax.sgd(1e-2))
        engine.train_batch(batches[0])
