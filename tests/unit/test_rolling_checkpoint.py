"""Rolling-checkpoint tests (ISSUE 6 tentpole): cadence, commit ordering,
backpressure, retention, shutdown flush, and resume-from-newest-complete.
"""

import csv
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.checkpoint.state import (find_resume_tag, read_latest_tag,
                                            tag_problem)
from deepspeed_tpu.config import ConfigError


def _mlp_engine(save_dir, every=2, keep_last=2, max_pending=1, extra=None,
                writers=2):
    import jax.numpy as jnp

    def model(params, b):
        pred = jnp.tanh(b["x"] @ params["w"])
        return jnp.mean((pred - b["y"]) ** 2)

    rng = np.random.default_rng(0)
    params = {"w": rng.standard_normal((16, 4)).astype(np.float32) * 0.1}
    cfg = {"train_batch_size": 8, "steps_per_print": 0,
           "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
           "checkpoint": {"engine": "async", "writers": writers,
                          "rolling": {"every_n_steps": every,
                                      "save_dir": str(save_dir),
                                      "keep_last": keep_last,
                                      "max_pending": max_pending}}}
    if extra:
        cfg.update(extra)
    engine, *_ = deepspeed_tpu.initialize(model=model, model_parameters=params,
                                          config=cfg)
    return engine


def _batch(step):
    rng = np.random.default_rng(100 + step)
    return {"x": rng.standard_normal((8, 16)).astype(np.float32),
            "y": rng.standard_normal((8, 4)).astype(np.float32)}


def test_rolling_cadence_and_latest_ordering(tmp_path):
    eng = _mlp_engine(tmp_path, every=2, keep_last=8)
    for step in range(5):
        eng.train_batch(_batch(step))
    eng._rolling.flush()
    # saves at steps 2 and 4; each complete with a manifest; latest = newest
    for tag in ("rolling_step2", "rolling_step4"):
        assert tag_problem(str(tmp_path), tag, verify=True) is None
    assert read_latest_tag(str(tmp_path)) == "rolling_step4"
    assert eng._rolling.saves == 2
    # a resumed engine picks the newest complete tag and continues at step 4
    eng2 = _mlp_engine(tmp_path / "other", every=0)
    eng2.train_batch(_batch(0))
    eng2.load_checkpoint(str(tmp_path))
    assert eng2.global_steps == 4
    eng.destroy()
    eng2.destroy()


def test_rolling_resumed_stream_matches_uninterrupted(tmp_path):
    """The property the whole subsystem exists for, in-process: losses after
    a resume from a rolling tag equal the uninterrupted run's."""
    eng = _mlp_engine(tmp_path / "a", every=3, keep_last=8)
    uninterrupted = [float(eng.train_batch(_batch(s))) for s in range(6)]
    eng.destroy()

    eng2 = _mlp_engine(tmp_path / "b", every=3, keep_last=8)
    eng2.train_batch(_batch(0))   # initialise jits
    eng2.load_checkpoint(str(tmp_path / "a"), tag="rolling_step3",
                         verify=True)
    resumed = [float(eng2.train_batch(_batch(s))) for s in range(3, 6)]
    assert resumed == uninterrupted[3:]
    eng2.destroy()


def test_rolling_retention_prunes_but_never_latest(tmp_path):
    eng = _mlp_engine(tmp_path, every=1, keep_last=2)
    for step in range(5):
        eng.train_batch(_batch(step))
    eng._rolling.flush()
    tags = sorted(d for d in os.listdir(str(tmp_path))
                  if d.startswith("rolling_step"))
    # keep_last=2 -> newest two survive; latest points at the newest
    assert tags == ["rolling_step4", "rolling_step5"]
    assert read_latest_tag(str(tmp_path)) == "rolling_step5"
    assert eng.ckpt_stats.pruned == 3
    eng.destroy()


def test_rolling_user_tags_never_pruned(tmp_path):
    eng = _mlp_engine(tmp_path, every=1, keep_last=1)
    eng.train_batch(_batch(0))
    eng.save_checkpoint(str(tmp_path), tag="user_milestone")
    for step in range(1, 4):
        eng.train_batch(_batch(step))
    eng._rolling.flush()
    assert os.path.isdir(str(tmp_path / "user_milestone"))   # retention skips
    assert tag_problem(str(tmp_path), "user_milestone") is None
    eng.destroy()


def test_rolling_backpressure_bounds_writer_lag(tmp_path, monkeypatch):
    """With a committer slower than the cadence, at most ``max_pending``
    snapshots may be queued-but-uncommitted; the next save BLOCKS (charged to
    backpressure) instead of growing the queue."""
    from deepspeed_tpu.checkpoint import rolling as rolling_mod

    real_commit = rolling_mod.commit_checkpoint
    gate = threading.Event()
    committed = []

    def slow_commit(*a, **k):
        gate.wait(5.0)
        committed.append(a[2])
        return real_commit(*a, **k)

    monkeypatch.setattr(rolling_mod, "commit_checkpoint", slow_commit)
    eng = _mlp_engine(tmp_path, every=1, keep_last=8, max_pending=1)
    eng.train_batch(_batch(0))   # save 1 queues; committer blocks on gate

    t = threading.Thread(target=lambda: eng.train_batch(_batch(1)))
    t.start()
    # save 2 must be BLOCKED in backpressure (queue full), not queued deeper
    time.sleep(0.3)
    assert t.is_alive()
    assert eng._rolling._jobs.qsize() <= 1
    gate.set()
    t.join(10.0)
    assert not t.is_alive()
    eng._rolling.flush()
    assert committed == ["rolling_step1", "rolling_step2"]   # FIFO tag order
    assert eng.ckpt_stats.backpressure_ms > 0.0
    eng.destroy()


def test_rolling_commit_failure_surfaces_at_next_save(tmp_path, monkeypatch):
    from deepspeed_tpu.checkpoint import rolling as rolling_mod

    def exploding_commit(*a, **k):
        raise OSError(28, "disk full")

    monkeypatch.setattr(rolling_mod, "commit_checkpoint", exploding_commit)
    eng = _mlp_engine(tmp_path, every=1)
    eng.train_batch(_batch(0))       # save 1: commit fails on the committer
    eng._rolling._jobs.join()        # let the failure land
    with pytest.raises(OSError, match="disk full"):
        eng.train_batch(_batch(1))   # surfaces at the NEXT save — never lost
    monkeypatch.undo()
    eng.destroy()


def test_destroy_surfaces_commit_error_after_full_teardown(tmp_path,
                                                           monkeypatch):
    """A commit error pending at destroy() must surface — but only AFTER the
    rest of the teardown ran (writers closed, committer stopped): a raising
    close must not leak a live committer that can still flip `latest`."""
    from deepspeed_tpu.checkpoint import rolling as rolling_mod

    def exploding_commit(*a, **k):
        raise OSError(28, "disk full")

    monkeypatch.setattr(rolling_mod, "commit_checkpoint", exploding_commit)
    eng = _mlp_engine(tmp_path, every=1)
    eng.train_batch(_batch(0))       # save 1: commit fails on the committer
    eng._rolling._jobs.join()
    rolling = eng._rolling
    with pytest.raises(OSError, match="disk full"):
        eng.destroy()
    assert rolling._committer is None            # committer actually stopped
    assert eng._ckpt_engine._closed              # teardown past the raise ran
    eng.destroy()                                # idempotent, no re-raise


def test_destroy_flushes_inflight_rolling_writes(tmp_path, monkeypatch):
    """engine.destroy() with a SLOW writer: in-flight rolling writers must
    finish and commit before the checkpoint engine closes (the satellite's
    regression case)."""
    from deepspeed_tpu.checkpoint import engine as ckpt_engine_mod

    real = ckpt_engine_mod._atomic_savez

    def slow_savez(path, state_dict):
        time.sleep(0.2)
        real(path, state_dict)

    monkeypatch.setattr(ckpt_engine_mod, "_atomic_savez", slow_savez)
    eng = _mlp_engine(tmp_path, every=1)
    eng.train_batch(_batch(0))
    eng.destroy()   # must block on the slow writers, then commit
    assert tag_problem(str(tmp_path), "rolling_step1", verify=True) is None
    assert read_latest_tag(str(tmp_path)) == "rolling_step1"


def test_async_engine_atexit_flush_is_registered(tmp_path):
    """The async engine's atexit hook is the destroy()-never-ran safety net;
    close() unregisters it (no double flush, no leak)."""
    import atexit
    from unittest import mock
    from deepspeed_tpu.checkpoint.engine import AsyncCheckpointEngine

    with mock.patch.object(atexit, "register") as reg, \
            mock.patch.object(atexit, "unregister") as unreg:
        eng = AsyncCheckpointEngine()
        reg.assert_called_once_with(eng._atexit_flush)
        eng.save({"a": np.zeros(4, np.float32)}, str(tmp_path / "x.npz"))
        eng.close()
        unreg.assert_called_once_with(eng._atexit_flush)
    assert os.path.exists(str(tmp_path / "x.npz"))   # close drained the write
    # _atexit_flush itself never raises, even after close
    eng._atexit_flush()


def test_rolling_config_requires_save_dir():
    import jax.numpy as jnp
    with pytest.raises(ConfigError, match="save_dir"):
        _mlp_engine("", every=2)


def test_rolling_disabled_by_default(tmp_path):
    eng = _mlp_engine(tmp_path, every=0)
    eng.train_batch(_batch(0))
    assert eng._rolling is None
    assert not any(d.startswith("rolling") for d in os.listdir(str(tmp_path)))
    eng.destroy()


def test_ckpt_stats_emitted_at_print_boundary(tmp_path):
    """``train/ckpt/*`` events land beside TrainPipelineStats at print
    boundaries (the monitor satellite)."""
    eng = _mlp_engine(
        tmp_path / "ck", every=1,
        extra={"steps_per_print": 1,
               "csv_monitor": {"enabled": True,
                               "output_path": str(tmp_path / "mon"),
                               "job_name": "ckpt_job"}})
    eng.train_batch(_batch(0))
    eng.train_batch(_batch(1))
    eng.drain_metrics()
    eng._rolling.flush()
    eng.train_batch(_batch(2))
    eng.drain_metrics()
    snap_file = os.path.join(str(tmp_path / "mon"), "ckpt_job",
                             "train_ckpt_snapshot_ms_per_save.csv")
    assert os.path.exists(snap_file)
    with open(snap_file) as f:
        rows = list(csv.reader(f))
    assert len(rows) >= 2
    assert float(rows[1][1]) >= 0.0
    saves_file = os.path.join(str(tmp_path / "mon"), "ckpt_job",
                              "train_ckpt_saves.csv")
    with open(saves_file) as f:
        rows = list(csv.reader(f))
    assert float(rows[-1][1]) >= 1.0
    eng.destroy()


def test_ckpt_stats_counters_and_events():
    from deepspeed_tpu.monitor import CheckpointStats
    st = CheckpointStats()
    st.record_save(snapshot_s=0.002, backpressure_s=0.001, queue_depth=3)
    st.record_commit(commit_s=0.004, pruned=2)
    st.record_save(snapshot_s=0.004)
    st.retries = 5
    ev = {name: val for name, val, _ in st.events(7)}
    assert ev["train/ckpt/saves"] == 2.0
    assert ev["train/ckpt/snapshot_ms_per_save"] == pytest.approx(3.0)
    assert ev["train/ckpt/commit_ms_per_save"] == pytest.approx(2.0)
    assert ev["train/ckpt/backpressure_ms_per_save"] == pytest.approx(0.5)
    assert ev["train/ckpt/writer_queue_depth"] == pytest.approx(1.5)
    assert ev["train/ckpt/retries"] == 5.0
    assert ev["train/ckpt/pruned_tags"] == 2.0
    st.reset()
    assert st.saves == 0 and st.snapshot_ms == 0.0 and st.retries == 0


def test_latest_never_rolls_backwards_past_user_save(tmp_path):
    """A queued rolling commit finishing AFTER an inline user save must not
    flip ``latest`` back to the older rolling tag (the committer's flips are
    monotonic); un-numbered user tags always win the flip."""
    from deepspeed_tpu.checkpoint.state import write_latest_tag
    # direct semantics: monotonic flip refuses to go backwards...
    write_latest_tag(str(tmp_path), "global_step7")
    write_latest_tag(str(tmp_path), "rolling_step6", monotonic=True)
    assert read_latest_tag(str(tmp_path)) == "global_step7"
    # ...but moves forward, and non-monotonic (user) flips always land
    write_latest_tag(str(tmp_path), "rolling_step9", monotonic=True)
    assert read_latest_tag(str(tmp_path)) == "rolling_step9"
    write_latest_tag(str(tmp_path), "best_model")
    assert read_latest_tag(str(tmp_path)) == "best_model"

    # end to end: a user save at step 2 lands while rolling_step1's commit is
    # stuck in the queue; when the committer catches up, latest must still
    # name the newer user tag
    import threading as _th
    from deepspeed_tpu.checkpoint import rolling as rolling_mod
    real_commit = rolling_mod.commit_checkpoint
    gate = _th.Event()

    def slow_commit(*a, **k):
        gate.wait(5.0)
        return real_commit(*a, **k)

    import unittest.mock as mock
    with mock.patch.object(rolling_mod, "commit_checkpoint", slow_commit):
        eng = _mlp_engine(tmp_path / "run", every=1, max_pending=2)
        eng.train_batch(_batch(0))            # rolling_step1 queued, stuck
        eng.train_batch(_batch(1))            # step 2...
        eng.save_checkpoint(str(tmp_path / "run"), tag="global_step2")
        assert read_latest_tag(str(tmp_path / "run")) == "global_step2"
        gate.set()
        eng._rolling.flush()
    # rolling_step1 committed late — complete, but latest never rolled back
    # to it (a same-step tag may legitimately win the flip; both hold the
    # state after step 2)
    assert tag_problem(str(tmp_path / "run"), "rolling_step1") is None
    assert read_latest_tag(str(tmp_path / "run")) in ("global_step2",
                                                      "rolling_step2")
    eng.destroy()


def test_failed_enqueue_hands_the_backpressure_permit_back(tmp_path):
    """Regression (threadlint TL004): the backpressure permit transfers to
    the committer WITH the queued job, so ``save()`` never releases it on
    success — but a ``_jobs.put`` that raises used to leak the permit, and
    with ``max_pending=1`` the NEXT save wedged forever on acquire. The
    fix hands the permit back on any enqueue failure."""
    eng = _mlp_engine(tmp_path, every=100, max_pending=1)
    eng.train_batch(_batch(0))
    rc = eng._rolling
    rc.flush()                       # committer idle, full permit budget

    real_put = rc._jobs.put

    def boom(*a, **k):
        raise RuntimeError("queue closed under save")

    rc._jobs.put = boom
    try:
        with pytest.raises(RuntimeError, match="queue closed under save"):
            rc.save()
    finally:
        rc._jobs.put = real_put
    # pre-fix: the permit was gone -> this acquire fails (and a real
    # caller's next save() blocked forever on the backpressure gate)
    assert rc._pending.acquire(blocking=False)
    rc._pending.release()
    # and the subsystem is still fully usable after the failed enqueue
    rc.save()
    rc.flush()
    eng.destroy()


# --------------------------------------------------------------------------- #
# a real death and a resume onto another device count (docs/ELASTICITY.md):
# subprocess workers, so the kill (os._exit through DSTPU_FAULTS) runs no
# atexit and no finally
# --------------------------------------------------------------------------- #

#: the final global batch does not depend on the world size, so a resume on
#: M != N devices trains on the same global batch at every step
_ELASTIC = {"enabled": True, "max_train_batch_size": 32,
            "micro_batch_sizes": [4, 8], "min_gpus": 1, "max_gpus": 8,
            "version": 0.2}
_EVERY, _KILL_STEP, _TOTAL, _PREFIX = 3, 8, 12, "rolling_step"
_WORLD, _RESUME_WORLD = 4, 2


def _elastic_worker(save_dir, out, total_steps, resume_universal="",
                    load_dir="", resume_tag=""):
    """One training run in THIS process: data parallel over however many
    devices XLA_FLAGS forced, rolling checkpoints on a cadence, optionally
    resumed from a universal checkpoint (the other-world path) or a regular
    tag (the verified-load control). Writes a JSON report to ``out``."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.checkpoint.universal import load_universal_into_engine
    from deepspeed_tpu.elasticity import compute_elastic_config
    from deepspeed_tpu.utils.compile_cache import setup_compile_cache

    setup_compile_cache()
    world = jax.device_count()
    final_batch, _valid, micro = compute_elastic_config(
        {"elasticity": _ELASTIC}, world_size=world, return_microbatch=True)

    def model(params, b):
        h = jnp.tanh(jnp.mean(b["x"], axis=1) @ params["w1"])
        return jnp.mean((h @ params["w2"] - b["y"]) ** 2)

    rng = np.random.default_rng(0)
    params = {"w1": rng.standard_normal((32, 16)).astype(np.float32) * 0.05,
              "w2": rng.standard_normal((16, 8)).astype(np.float32) * 0.05}
    cfg = {"train_batch_size": final_batch,
           "train_micro_batch_size_per_gpu": micro,
           "mesh": {"data": -1}, "steps_per_print": 0,
           "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
           "checkpoint": {"engine": "async", "writers": 2,
                          "verify_load": True,
                          "rolling": {"every_n_steps": _EVERY,
                                      "save_dir": save_dir,
                                      "keep_last": 8, "max_pending": 2}}}
    engine, *_ = deepspeed_tpu.initialize(model=model, model_parameters=params,
                                          config=cfg)
    if resume_universal:
        load_universal_into_engine(engine, resume_universal)
    elif resume_tag:
        engine.load_checkpoint(load_dir, tag=resume_tag, verify=True)
    start = engine.global_steps

    def batch(step):             # keyed by the step alone: every world size
        g = np.random.default_rng(10_000 + step)   # and resume sees the same
        return {"x": g.standard_normal((final_batch, 4, 32)).astype(np.float32),
                "y": g.standard_normal((final_batch, 8)).astype(np.float32)}

    losses, warm = {}, None
    for step in range(start, total_steps):
        losses[str(step + 1)] = float(engine.train_batch(batch(step)))
        if step == start:        # the first (re)started step may compile
            warm = engine.compiles
    report = {"world": world, "global_batch": final_batch,
              "start_step": start, "losses": losses,
              "compiles_after_warmup":
                  0 if warm is None else engine.compiles - warm}
    engine.destroy()             # flushes rolling commits, closes the writers
    with open(out, "w") as f:
        json.dump(report, f)


def _spawn_elastic_worker(devices, save_dir, out, faults="", **resume):
    env = dict(os.environ)
    env.pop("DSTPU_FAULTS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
    if faults:
        env["DSTPU_FAULTS"] = faults
        env["DSTPU_TRACE"] = str(save_dir) + "_trace"
    args = dict(save_dir=str(save_dir), out=str(out), total_steps=_TOTAL,
                **{k: str(v) for k, v in resume.items()})
    p = subprocess.run([sys.executable, os.path.abspath(__file__),
                        json.dumps(args)], env=env, capture_output=True,
                       text=True, timeout=600)
    report = None
    if os.path.exists(out):
        with open(out) as f:
            report = json.load(f)
    return p, report


@pytest.fixture(scope="module")
def uninterrupted_run(tmp_path_factory):
    """The whole run at the original world size, never killed."""
    td = tmp_path_factory.mktemp("elastic_ref")
    p, report = _spawn_elastic_worker(_WORLD, td / "ck", td / "ref.json")
    assert p.returncode == 0, p.stderr[-2000:]
    return report


@pytest.mark.parametrize("death", ["between_steps", "inside_checkpoint_write"])
def test_killed_run_resumes_on_another_device_count(tmp_path, death,
                                                    uninterrupted_run):
    """A run on 4 devices dies (a) between steps, at a step that is not a
    cadence point, or (b) inside a rolling tag's file write; the newest
    COMPLETE tag — for (b) the one before the torn tag, which stays on
    disk and is detected — resumes on 2 devices through the universal
    format. The resumed loss stream is byte-identical to a verified load of
    the same tag on 2 devices, stays within float tolerance of the
    uninterrupted 4-device run (reduction order differs across device
    counts), the global batch is the same on both worlds, and nothing
    compiles after the first resumed step. The killed process leaves the
    tracer's flight-recorder dump behind."""
    from deepspeed_tpu.checkpoint.universal import ds_to_universal
    from deepspeed_tpu.utils.fault_injection import KILL_EXIT_CODE
    # the stall paces every step 250 ms (the kill is listed first and wins at
    # its hit): these tiny steps outrun the background committer, and a kill
    # before the previous cadence tag committed leaves nothing to resume
    pace = "step.kill:every=1:action=stall:delay_s=0.25"
    plan = {"between_steps": f"step.kill:at={_KILL_STEP}:action=kill;{pace}",
            # hit 3 = the second cadence save's first file
            "inside_checkpoint_write":
                f"ckpt.writer:at=3:action=kill;{pace}"}[death]
    save_dir = tmp_path / "killed"
    p, report = _spawn_elastic_worker(_WORLD, save_dir, tmp_path / "a.json",
                                      faults=plan)
    assert p.returncode == KILL_EXIT_CODE, p.stderr[-2000:]
    assert report is None                      # it never reached its end
    # os._exit runs no atexit: the flight recorder's dump, written before
    # the kill, is the only timeline such a death leaves
    with open(tmp_path / "killed_trace" / "trace_crash.json") as f:
        dump = json.load(f)
    assert any(ev.get("name", "").startswith("train/")
               for ev in dump["traceEvents"])

    tag = find_resume_tag(str(save_dir))
    assert tag is not None and tag.startswith(_PREFIX)
    assert tag_problem(str(save_dir), tag) is None
    k = int(tag[len(_PREFIX):])
    assert 0 < k < _KILL_STEP and k % _EVERY == 0
    if death == "inside_checkpoint_write":
        torn = f"{_PREFIX}{2 * _EVERY}"
        assert (save_dir / torn).is_dir()      # still on disk, not chosen
        assert tag_problem(str(save_dir), torn) is not None
        assert k == _EVERY

    uni = ds_to_universal(str(save_dir), str(tmp_path / "uni"), tag=tag)
    pb, b = _spawn_elastic_worker(_RESUME_WORLD, tmp_path / "b_ck",
                                  tmp_path / "b.json", resume_universal=uni)
    pc, c = _spawn_elastic_worker(_RESUME_WORLD, tmp_path / "c_ck",
                                  tmp_path / "c.json", load_dir=save_dir,
                                  resume_tag=tag)
    assert pb.returncode == 0 and pc.returncode == 0, \
        (pb.stderr + pc.stderr)[-2000:]
    ref = uninterrupted_run
    assert b["world"] == _RESUME_WORLD and ref["world"] == _WORLD
    assert b["global_batch"] == ref["global_batch"]
    assert b["start_step"] == k and c["start_step"] == k
    assert b["losses"] == c["losses"] and len(b["losses"]) == _TOTAL - k
    assert b["compiles_after_warmup"] == 0 == c["compiles_after_warmup"]
    steps = sorted(b["losses"], key=int)
    np.testing.assert_allclose([b["losses"][s] for s in steps],
                               [ref["losses"][s] for s in steps],
                               rtol=5e-4, atol=1e-6)


if __name__ == "__main__":
    _elastic_worker(**json.loads(sys.argv[1]))
