"""Colocated rollout bench (docs/TRAINING.md "Colocated rollout", BENCH_r19).

Three legs over ONE colocated train+serve pair (tiny GPT-2 on CPU for the
smoke; real sizes on accelerator hardware):

- ``sync``: the WeightBridge's device-resident reshard vs the universal
  checkpoint round-trip it replaces (save_checkpoint -> ds_to_universal ->
  load_universal -> host unflatten -> re-upload -> the SAME serving-layout
  program). Identical source, identical output layout, byte-equality
  gated — the measured delta is exactly the host/disk legs the bridge
  deletes. Full mode gates the >=5x speedup; smoke gates correctness only.
- ``swap``: >=3 consecutive train->sync->swap cycles into a WARMED engine,
  gating zero new compiles, byte-identical post-swap greedy streams vs a
  freshly built engine on the same weights, and the KV allocator back at
  baseline.
- ``interleave``: the full RolloutLoop (frontend generates rollouts that
  feed the next train batch) vs the naive rebuild-the-engine-per-update
  loop, byte-identical rollouts gated; full mode also gates the steps/s
  advantage.

Every leg prints one JSON line; non-smoke runs aggregate into
``BENCH_r19.json``. The bridge/loop stamps emit the ``train/rollout/*``
trace lanes scripts/trace_check.py requires in the bench smoke.
"""

import argparse
import json
import os
import statistics
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

VOCAB = 128


def _median(xs):
    return statistics.median(xs)


def build_pair(prefix_cache=True):
    import jax.numpy as jnp
    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHead
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2

    model = GPT2LMHead(GPT2Config.tiny(vocab_size=VOCAB))
    import jax
    params = model.init(jax.random.PRNGKey(0),
                        {"input_ids": np.zeros((2, 16), np.int32)})["params"]
    cfg = {"train_batch_size": 8, "gradient_accumulation_steps": 1,
           "steps_per_print": 0,
           "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}},
           "zero_optimization": {"stage": 1}, "mesh": {}}
    engine, *_ = deepspeed_tpu.initialize(model=model,
                                          model_parameters=params, config=cfg)
    econf = {"dtype": jnp.float32,
             "state_manager": {"max_tracked_sequences": 8,
                               "max_ragged_sequence_count": 4,
                               "max_ragged_batch_size": 96,
                               "max_context": 176,
                               "prefill_chunk_size": 32},
             "kv_cache": {"block_size": 16, "num_blocks": 16},
             "serving": {"decode_slice": 4, "idle_wait_s": 0.005}}
    if prefix_cache:
        econf["prefix_cache"] = {"enabled": True}
    serve = InferenceEngineV2(model=model, model_parameters=params,
                              config=econf)
    return engine, serve, model, params


def _train_step(engine, seed):
    rng = np.random.default_rng(seed)
    engine.train_batch({"input_ids":
                        rng.integers(0, VOCAB, (8, 16)).astype(np.int32)})


def _leaves_bytes_equal(a, b):
    import jax
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.asarray(x).tobytes() == np.asarray(y).tobytes()
        for x, y in zip(la, lb))


def run_sync_leg(smoke, reps):
    """Bridge sync vs the universal-checkpoint round-trip, same program on
    both sides — the measured delta is the host/disk legs."""
    import jax
    from deepspeed_tpu.checkpoint import ds_to_universal, load_universal
    from deepspeed_tpu.checkpoint.state import unflatten_into
    from deepspeed_tpu.inference.v2.ragged_model import adapt_model
    from deepspeed_tpu.utils.tree import tree_cast

    engine, serve, model, params = build_pair(prefix_cache=False)
    _train_step(engine, 1)
    bridge = serve.weight_bridge(engine, donate=False)
    bridge.sync()                                    # build (untimed, once)

    dtype = serve.config.dtype
    max_ctx = serve.config.state_manager.max_context
    to_serve = jax.jit(
        lambda p: adapt_model(serve.family, tree_cast(p, dtype),
                              serve.model_config, max_context=max_ctx)[1],
        out_shardings=jax.tree_util.tree_map(lambda a: a.sharding,
                                             serve.weights))

    sync_s, disk_s = [], []
    equal = True
    with tempfile.TemporaryDirectory() as tmp:
        # warm the baseline program too: neither side pays compiles in the
        # timed region
        host0 = jax.tree_util.tree_map(np.asarray,
                                       engine.rollout_source_params())
        jax.block_until_ready(to_serve(jax.device_put(host0)))
        for r in range(reps):
            _train_step(engine, 10 + r)
            t0 = time.perf_counter()
            w_sync = bridge.sync()
            t1 = time.perf_counter()
            sync_s.append(t1 - t0)

            ck = os.path.join(tmp, f"ck{r}")
            uni = os.path.join(tmp, f"uni{r}")
            t0 = time.perf_counter()
            engine.save_checkpoint(ck, tag="b")
            ds_to_universal(ck, uni, tag="b")
            master, _, _ = load_universal(uni)
            host = unflatten_into(
                jax.tree_util.tree_map(np.asarray, params), master)
            w_disk = to_serve(jax.device_put(host))
            jax.block_until_ready(w_disk)
            t1 = time.perf_counter()
            disk_s.append(t1 - t0)
            equal = equal and _leaves_bytes_equal(w_sync, w_disk)

    speedup = _median(disk_s) / max(_median(sync_s), 1e-9)
    out = {"leg": "sync", "reps": reps, "bytes": bridge.nbytes,
           "sync_ms_median": 1e3 * _median(sync_s),
           "universal_roundtrip_ms_median": 1e3 * _median(disk_s),
           "speedup": speedup, "weights_byte_equal": equal,
           "bridge_compiles": bridge.compiles, "smoke": smoke}
    # smoke: byte-equality only (2-core CI wall times are noise); the >=5x
    # bar is the full-size gate (BENCH_r19)
    out["ok"] = equal and (smoke or speedup >= 5.0)
    return out


def run_swap_leg(smoke, n_swaps=3):
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2

    engine, serve, model, params = build_pair(prefix_cache=False)
    bridge = serve.weight_bridge(engine)
    prompt = list(range(1, 12))
    serve.generate([prompt], max_new_tokens=8)       # warm the ladders
    kv_free0 = serve.allocator.free_blocks
    c0 = serve.compiles

    for i in range(n_swaps):
        _train_step(engine, 20 + i)
        serve.swap_weights(bridge.sync())
    out_tokens = serve.generate([prompt], max_new_tokens=8)
    compiles = serve.compiles - c0

    fresh = InferenceEngineV2(
        model=model,
        model_parameters=jax.tree_util.tree_map(
            np.asarray, engine.rollout_source_params()),
        config={"dtype": jnp.float32,
                "state_manager": {"max_tracked_sequences": 8,
                                  "max_ragged_sequence_count": 4,
                                  "max_ragged_batch_size": 96,
                                  "max_context": 176,
                                  "prefill_chunk_size": 32},
                "kv_cache": {"block_size": 16, "num_blocks": 16}})
    ref_tokens = fresh.generate([prompt], max_new_tokens=8)

    out = {"leg": "swap", "swaps": n_swaps,
           "weight_version": serve.weight_version,
           "compiles_after_warmup": compiles,
           "streams_equal": out_tokens == ref_tokens,
           "weights_byte_equal": _leaves_bytes_equal(serve.weights,
                                                     fresh.weights),
           "kv_allocator_at_baseline":
               serve.allocator.free_blocks == kv_free0,
           "smoke": smoke}
    out["ok"] = (compiles == 0 and out["streams_equal"]
                 and out["weights_byte_equal"]
                 and out["kv_allocator_at_baseline"])
    return out


def run_interleave_leg(smoke, rounds):
    """RolloutLoop vs rebuild-the-serving-engine-per-update, identical
    seeded prompts; the naive loop re-pays engine construction + compile
    ladders every policy update."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.runtime.colocated import RolloutLoop

    n_prompts, gen = 3, 4

    def prompts_for(rnd):
        r = np.random.default_rng(1000 + rnd)
        return [r.integers(1, VOCAB, size=8).tolist()
                for _ in range(n_prompts)]

    def collate(rollouts):
        rows = [(p + t + [0] * 16)[:16] for p, t in rollouts]
        return {"input_ids":
                np.asarray(rows, np.int32).repeat(3, axis=0)[:8]}

    # --- colocated -------------------------------------------------------
    engine, serve, model, params = build_pair()
    fe = serve.serving_frontend()
    # run() numbers rounds from 0 on every call; key the seeded prompts by
    # a global update counter instead so the warm round consumes update 0
    # and the timed rounds line up with the naive loop's updates 1..N
    update = {"n": 0}

    def prompts_for_loop(_rnd):
        n = update["n"]
        update["n"] += 1
        return prompts_for(n)

    loop = RolloutLoop(engine, fe, prompt_fn=prompts_for_loop,
                       collate_fn=collate, steps_per_round=1,
                       max_new_tokens=gen, request_timeout=120.0)
    co_rollouts = {}
    orig_gen = loop._generate

    def _capture(rnd):
        n = update["n"]
        out = orig_gen(rnd)
        co_rollouts[n] = [t for _, t in out]
        return out
    loop._generate = _capture
    loop.run(1, align=True)                          # warm every ladder
    t0 = time.perf_counter()
    loop.run(rounds, align=False)
    co_s = time.perf_counter() - t0
    stats = loop.stats
    loop.close()
    fe.close()

    # --- naive: rebuild the serving engine every update ------------------
    engine2, serve2, model2, _ = build_pair()
    econf = {"dtype": jnp.float32,
             "state_manager": {"max_tracked_sequences": 8,
                               "max_ragged_sequence_count": 4,
                               "max_ragged_batch_size": 96,
                               "max_context": 176,
                               "prefill_chunk_size": 32},
             "kv_cache": {"block_size": 16, "num_blocks": 16}}

    def naive_round(rnd):
        host = jax.tree_util.tree_map(np.asarray,
                                      engine2.rollout_source_params())
        eng = InferenceEngineV2(model=model2, model_parameters=host,
                                config=econf)
        prompts = prompts_for(rnd)
        full = eng.generate(prompts, max_new_tokens=gen)
        # generate() returns prompt+continuation; the frontend streams only
        # the continuation — train on the same rows the colocated loop does
        outs = [f[len(p):] for p, f in zip(prompts, full)]
        engine2.train_batch(collate(list(zip(prompts, outs))))
        return outs

    naive_round(0)                                   # align + warm parity
    na_rollouts = {}
    t0 = time.perf_counter()
    for rnd in range(1, rounds + 1):
        na_rollouts[rnd] = naive_round(rnd)
    na_s = time.perf_counter() - t0

    # both loops saw the same seeded prompts at the same policy version,
    # so the greedy rollouts must agree byte-for-byte
    rollouts_equal = all(co_rollouts.get(r) == na_rollouts.get(r)
                         for r in range(1, rounds + 1))
    speedup = na_s / max(co_s, 1e-9)
    out = {"leg": "interleave", "rounds": rounds,
           "colocated_s": co_s, "naive_rebuild_s": na_s,
           "rounds_per_s_colocated": rounds / max(co_s, 1e-9),
           "rounds_per_s_naive": rounds / max(na_s, 1e-9),
           "speedup": speedup, "rollouts_byte_equal": rollouts_equal,
           "sync_ms_per_round": stats.sync_ms / max(1, stats.rounds),
           "swap_ms_per_round": stats.swap_ms / max(1, stats.rounds),
           "generate_ms_per_round":
               stats.generate_ms / max(1, stats.rounds),
           "smoke": smoke}
    out["ok"] = rollouts_equal and (smoke or speedup >= 1.0)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="correctness gates only, tiny sizes (CI)")
    ap.add_argument("--reps", type=int, default=0)
    ap.add_argument("--rounds", type=int, default=0)
    ap.add_argument("--out", default="BENCH_r19.json")
    args = ap.parse_args()
    reps = args.reps or (2 if args.smoke else 5)
    rounds = args.rounds or (2 if args.smoke else 4)

    from deepspeed_tpu.utils.compile_cache import setup_compile_cache
    setup_compile_cache()

    ok = True
    results = {}
    for name, fn in (("sync", lambda: run_sync_leg(args.smoke, reps)),
                     ("swap", lambda: run_swap_leg(args.smoke)),
                     ("interleave",
                      lambda: run_interleave_leg(args.smoke, rounds))):
        out = fn()
        results[name] = out
        print(json.dumps(out), flush=True)
        ok = ok and out["ok"]
    if not args.smoke:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=2)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
