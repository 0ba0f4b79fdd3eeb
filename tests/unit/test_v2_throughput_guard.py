"""Serving-loop regression guard (VERDICT r2 #9).

No benchmark polices serving speed on hardware yet (ROADMAP S0), and a
scheduler/engine regression that, say, doubles the host work per pass would
still pass the functional suite. This smoke
asserts the per-pass rate of the two hot loops on the virtual CPU mesh stays
within a GENEROUS bound (>2x headroom over measured-at-commit rates, so env
noise doesn't flake it while a structural regression trips it).
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest


@pytest.fixture(scope="module")
def tiny_engine():
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    cfg = LlamaConfig.tiny(dtype=jnp.float32)
    model = LlamaForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        {"input_ids": jnp.zeros((1, 8), jnp.int32)})["params"]
    return InferenceEngineV2(
        model=model, model_parameters=params,
        config={"state_manager": {"max_tracked_sequences": 8,
                                  "max_ragged_sequence_count": 4,
                                  "max_ragged_batch_size": 20,
                                  "prefill_chunk_size": 8,
                                  # budget for the retrying measurers below:
                                  # up to 1 warm + 3 attempts x 3 reps of
                                  # 8-token decode_steps per sequence
                                  "max_context": 128},
                "kv_cache": {"block_size": 8, "num_blocks": 96},
                "dtype": jnp.float32})


def _best_rate(measure, attempts=3):
    """max over attempts of max(wall rate, cpu-time rate), also returning the
    best wall rate so callers can assert a (much lower) blocking-regression
    floor on it.

    The cpu-time rate (work / process CPU seconds) is immune to OTHER
    processes loading the box — on the CPU backend the XLA compute runs in
    this process, so a structural regression (10x more host work per pass)
    still tanks it, while a concurrently-running build/bench on this 1-core
    host only stretches wall time. Attempts absorb one-off scheduler stalls.
    CPU rate alone is blind to pure *blocking* regressions (a sleep or lock
    wait burns no CPU), so callers also get the best WALL rate back — they
    assert the main floor on the combined rate and a 50x-lower floor on wall.
    """
    best, best_wall = 0.0, 0.0
    for _ in range(attempts):
        work, wall, cpu = measure()
        wall_rate = work / wall if wall > 0 else 0.0
        best_wall = max(best_wall, wall_rate)
        best = max(best, wall_rate, work / cpu if cpu > 0 else 0.0)
    return best, best_wall


def test_ragged_pass_rate(tiny_engine):
    """put()-driven ragged passes (host descriptor build + jitted pass)."""
    eng = tiny_engine
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, 256, size=(6,)).astype(np.int32) for _ in range(4)]
    uids = [10, 11, 12, 13]
    eng.put(uids, prompts)                      # compile + warm

    def measure():
        n = 10
        t0, c0 = time.time(), time.process_time()
        for i in range(n):
            eng.put(uids, [np.asarray([i % 250], np.int32)] * 4)  # 1 pass each
        return n, time.time() - t0, time.process_time() - c0

    rate, wall_rate = _best_rate(measure)
    eng.flush(uids)
    # measured ~50-80 passes/s warm on the 1-core CI host; 8/s means the
    # serving loop got ~10x slower — a structural regression, not noise.
    # The wall floor catches blocking (no-CPU) regressions like stray sleeps.
    assert rate > 8.0, f"ragged pass rate collapsed: {rate:.1f}/s"
    assert wall_rate > 0.2, f"ragged pass wall rate collapsed: {wall_rate:.2f}/s"


def test_fused_multistep_rate(tiny_engine):
    """decode_steps() fused loop: per-generated-token device+host rate."""
    eng = tiny_engine
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, 256, size=(6,)).astype(np.int32) for _ in range(4)]
    uids = [20, 21, 22, 23]
    eng.put(uids, prompts)
    eng.decode_steps(uids, 8)                   # compile + warm

    def measure():
        reps = 3
        t0, c0 = time.time(), time.process_time()
        for _ in range(reps):
            eng.decode_steps(uids, 8)
        return reps * 8 * len(uids), time.time() - t0, time.process_time() - c0

    tok_rate, wall_rate = _best_rate(measure)
    eng.flush(uids)
    # measured ~500-1500 tok/s warm on the 1-core CI host; 50/s is a 10x+
    # structural regression; the wall floor catches blocking regressions
    assert tok_rate > 50.0, f"fused decode rate collapsed: {tok_rate:.0f} tok/s"
    assert wall_rate > 1.0, f"fused decode wall rate collapsed: {wall_rate:.1f} tok/s"
