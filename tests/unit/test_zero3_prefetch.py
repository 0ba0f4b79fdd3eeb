"""ZeRO-3 collective schedule tests (runtime/zero/prefetch.py).

Parity: reference ``tests/unit/runtime/zero`` prefetch/coordinator coverage —
here the schedule is compiled into the jitted step, so the tests assert on
(a) the plan (what gets gathered, wave packing), (b) byte-identical loss
streams vs the serial schedule (scheduling must never change math), and
(c) the stamp ledger the in-jit taps feed (issue order, residency bounds,
reverse-order backward re-gather).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHead
from deepspeed_tpu.runtime.zero import prefetch

VOCAB = 128


def make_batch(bs, seqlen=16, seed=0):
    rng = np.random.default_rng(seed)
    return {"input_ids": rng.integers(0, VOCAB, size=(bs, seqlen)).astype(np.int32)}


def make_engine(depth, n_layer=4, persist=0, remat=False, bucket=100_000,
                extra=None, n_embd=64):
    """persist=None leaves the config's default persistence threshold."""
    model = GPT2LMHead(GPT2Config.tiny(vocab_size=VOCAB, n_layer=n_layer,
                                       remat=remat, n_embd=n_embd))
    params = model.init(jax.random.PRNGKey(0), make_batch(2))["params"]
    z = {"stage": 3}
    if persist is not None:
        z["stage3_param_persistence_threshold"] = persist
    if depth is not None:
        z.update({"stage3_prefetch_depth": depth,
                  "allgather_bucket_size": bucket,
                  "reduce_bucket_size": bucket})
    cfg = {"train_batch_size": 8, "steps_per_print": 0,
           "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
           "zero_optimization": z, "mesh": {"fsdp": 8}}
    if extra:
        cfg.update(extra)
    engine, *_ = deepspeed_tpu.initialize(model=model, model_parameters=params,
                                          config=cfg)
    return engine


def run_losses(engine, steps=3):
    out = [float(engine.train_batch(make_batch(8, seed=100 + i)))
           for i in range(steps)]
    engine.drain_metrics()
    return out


def stream_bytes(losses):
    return [np.float32(l).tobytes() for l in losses]


def test_depth_changes_placement_never_math(eight_devices):
    """Byte-identical per-step loss streams across prefetch depths: the
    schedule moves collectives, the math is untouched, and a warm schedule
    compiles nothing."""
    base = stream_bytes(run_losses(make_engine(0)))
    for depth in (1, 2):
        engine = make_engine(depth)
        assert stream_bytes(run_losses(engine)) == base
        c0 = engine.compiles         # warm: the schedule is one program
        run_losses(engine, steps=2)
        assert engine.compiles == c0
    # the implicit (XLA-scheduled) path uses a different grad-reduction
    # order: equal to fp32 tolerance, NOT guaranteed byte-equal
    implicit = run_losses(make_engine(None))
    np.testing.assert_allclose(
        implicit, [np.frombuffer(b, np.float32)[0] for b in base], rtol=1e-5)


def test_layer_count_less_than_depth(eight_devices):
    """depth > n_waves must clamp, not crash or deadlock."""
    shallow = make_engine(5, n_layer=2)
    assert shallow._zero3_plan is not None
    assert shallow._zero3_plan.depth == 5
    base = stream_bytes(run_losses(make_engine(0, n_layer=2)))
    assert stream_bytes(run_losses(shallow)) == base


def test_persistence_threshold_params_never_gathered(eight_devices):
    """Leaves under stage3_param_persistence_threshold stay replicated: the
    plan never schedules them (no gather, no reduce-scatter) and accounts
    them as persistent bytes."""
    engine = make_engine(1, persist=5000)
    plan = engine._zero3_plan
    assert plan is not None
    assert plan.persistent_bytes > 0
    for wave in plan.waves:
        for lp in wave.leaves:
            # tiny gpt2: LayerNorm scale/bias are 64 floats = 256B < 5000
            assert "ln_1" not in lp.path and "ln_2" not in lp.path, lp
            assert lp.nbytes > 5000
    # threshold above every param: nothing gatherable -> no plan, implicit path
    none_engine = make_engine(1, persist=10**9)
    assert none_engine._zero3_plan is None
    assert np.isfinite(run_losses(none_engine, steps=1)[0])
    # and scheduling with the threshold active stays byte-equal to serial
    assert stream_bytes(run_losses(engine)) == \
        stream_bytes(run_losses(make_engine(0, persist=5000)))


def test_remat_byte_equal_across_depths(eight_devices):
    """Prefetch under activation checkpointing: the wave recompute composes
    with remat=True and stays byte-equal across depths."""
    base = stream_bytes(run_losses(make_engine(0, remat=True)))
    assert stream_bytes(run_losses(make_engine(1, remat=True))) == base


def test_scheduled_path_drops_xla_bucket_flags(eight_devices):
    """The explicit schedule retires the XLA combiner-threshold hints: bucket
    sizes bound the compiled waves/buckets directly, and the combiner
    re-fusing them would fight the barriers (partition.py deprecation note).
    The implicit path keeps them."""
    scheduled = make_engine(1)
    assert scheduled._zero3_plan is not None
    opts = scheduled._compiler_options(backend="tpu") or {}
    assert not any("combine_threshold" in k for k in opts)
    implicit = make_engine(None)
    assert implicit._zero3_plan is None
    opts = implicit._compiler_options(backend="tpu")
    assert any("combine_threshold" in k for k in opts)


def test_config_validation(eight_devices):
    from deepspeed_tpu.config import ConfigError, DeepSpeedTPUConfig
    with pytest.raises(ConfigError):
        DeepSpeedTPUConfig.from_dict({"train_batch_size": 8,
                                      "zero_optimization": {
                                          "stage": 3,
                                          "stage3_prefetch_depth": -1}})
    with pytest.raises(ConfigError):
        DeepSpeedTPUConfig.from_dict({"train_batch_size": 8,
                                      "zero_optimization": {
                                          "stage": 2,
                                          "stage3_prefetch_depth": 1}})


def test_default_persistence_threshold_probe_not_masked(eight_devices):
    """Under the config's DEFAULT stage3_param_persistence_threshold (100k,
    not the 0 most tests use) each gpt2 layer's path-sorted first leaf
    (attn/c_attn/bias) is persistent and bypasses the gather — the walk's
    completion probe must index by wave.leaves (always a gathered leaf), or
    the pin silently depends on the untouched original param and forces
    nothing. Asserts the masking precondition and byte-equality vs serial
    (when the pin completes on the device is read from a device trace)."""
    engine = make_engine(2, persist=None, n_embd=192)
    plan = engine._zero3_plan
    assert plan is not None and plan.persistent_bytes > 0
    first_paths = {prefetch._leaf_paths(
        engine.state["master"][layer])[0][0]
        for wave in plan.waves for layer in wave.layers}
    gathered_paths = {lp.path for wave in plan.waves for lp in wave.leaves}
    # the masking precondition: tree-order first leaves are all persistent
    assert first_paths and not (first_paths & gathered_paths)
    for wave in plan.waves:
        assert wave.leaves[0].nbytes > 100_000   # what the probe now pins
    # byte-equality on fresh engines
    assert stream_bytes(run_losses(make_engine(2, persist=None, n_embd=192))) \
        == stream_bytes(run_losses(make_engine(0, persist=None, n_embd=192)))


def test_ambient_plan_never_leaks_across_engines(eight_devices):
    """The 'stage3_prefetch_depth=None keeps the implicit path bit-for-bit
    untouched' contract: an unscheduled engine's traces must never see a plan
    a scheduled engine armed earlier on this thread, and destroy() disarms."""
    sched = make_engine(1, n_layer=2)
    run_losses(sched, steps=1)
    assert prefetch.current_plan() is sched._zero3_plan
    implicit = make_engine(None, n_layer=2)
    run_losses(implicit, steps=1)
    assert prefetch.current_plan() is None
    assert float(implicit.eval_loss(make_batch(8))) > 0
    assert prefetch.current_plan() is None
    run_losses(sched, steps=1)
    assert prefetch.current_plan() is sched._zero3_plan
    sched.destroy()
    assert prefetch.current_plan() is None


def test_plan_wave_packing(eight_devices):
    """allgather_bucket_size is a real schedule knob: small bucket -> one
    wave per layer; huge bucket -> one wave for the whole stack."""
    per_layer = make_engine(1, bucket=100_000)._zero3_plan
    assert per_layer.n_waves == 4
    fused = make_engine(1, bucket=1 << 30)._zero3_plan
    assert fused.n_waves == 1
    assert sum(len(w.layers) for w in fused.waves) == 4
