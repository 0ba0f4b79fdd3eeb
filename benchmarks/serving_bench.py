"""Continuous-batching serving benchmark (FastGen system-level analog).

Parity role: the reference's FastGen throughput-latency evaluation
(``blogs/deepspeed-fastgen/README.md`` §B — sweep client load, measure
effective tokens/sec and per-token latency under CONTINUOUS batching, where
prompt prefills are admitted while other sequences decode). This harness
drives the engine the way a serving frontend does:

  a steady arrival stream of prompts -> admit when can_schedule() ->
  one scheduler pass per iteration (mixed chunk+decode batches) ->
  sample on device -> retire sequences at their generation budget.

Prints one JSON line per load point:
  {"arrival_rate": r, "gen_tokens_per_sec": ..., "total_tokens_per_sec": ...,
   "mean_tbt_ms": ..., "p95_tbt_ms": ..., "mixed_pass_fraction": ...}

Usage:
  python benchmarks/serving_bench.py [--seqs 32] [--prompt 128] [--gen 64]
                                     [--rates 2,6] [--duration 20]

On CPU (tests/CI) the model is tiny; on TPU the 0.55B bench config is used.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

# runnable as `python benchmarks/serving_bench.py` from a bare checkout
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_engine(on_tpu: bool, seqs: int, prompt: int, gen: int,
                 burst: int = 8, int8: bool = False,
                 prefix_cache: bool = False, warmup: bool = False,
                 warmup_bursts: bool = True, spec_k: int = 0,
                 ctx_slack: int = 0, extra_config=None):
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    if on_tpu:
        layers, hidden, heads, vocab = 12, 1536, 12, 32000
    else:
        layers, hidden, heads, vocab = 2, 64, 4, 256
    # slack covers the waste margin (4*burst) + one burst overshoot
    ctx = prompt + gen + 6 * burst + ctx_slack
    cfg = LlamaConfig(vocab_size=vocab, hidden_size=hidden,
                      intermediate_size=hidden * 4, num_hidden_layers=layers,
                      num_attention_heads=heads, num_key_value_heads=heads,
                      max_position_embeddings=ctx,
                      dtype=jnp.bfloat16 if on_tpu else jnp.float32)
    model = LlamaForCausalLM(cfg)
    import contextlib

    @contextlib.contextmanager
    def no_pallas():  # init's forward values never affect the params
        old = os.environ.get("DSTPU_DISABLE_PALLAS")
        os.environ["DSTPU_DISABLE_PALLAS"] = "1"
        try:
            yield
        finally:
            if old is None:
                os.environ.pop("DSTPU_DISABLE_PALLAS", None)
            else:
                os.environ["DSTPU_DISABLE_PALLAS"] = old

    with no_pallas():
        params = jax.jit(model.init)(
            jax.random.PRNGKey(0),
            {"input_ids": jnp.zeros((1, 8), jnp.int32)})["params"]
    econf = {"state_manager": {
        "max_tracked_sequences": seqs,
        "max_ragged_sequence_count": seqs,
        # chunk capacity for a handful of concurrent prefills per pass
        "max_ragged_batch_size": 4 * prompt + seqs,
        "prefill_chunk_size": prompt,
        "max_context": ctx}}
    if int8:
        # weight-only int8 serving (the v2 mixed-GEMM analog): decode is
        # weight-read bound, int8 halves the stream
        econf["quantization"] = {"weight_bits": 8}
    if prefix_cache:
        econf["prefix_cache"] = {"enabled": True}
    if spec_k:
        # speculative decoding (inference/v2/spec/): warmup() then covers
        # the (bucket, k) verify grid beside the plain decode grid
        econf["spec_decode"] = {"enabled": True, "k": spec_k}
    if warmup:
        # AOT-warm the whole decode bucket grid (and, for legs that run
        # fused bursts, the burst length) so the timed legs never observe an
        # XLA compile; the multistep scan programs are the slowest compiles
        # in the set, so legs that never burst skip them
        econf["compile"] = {"warmup": True,
                            "warmup_decode_steps": [burst] if warmup_bursts
                            else []}
    if extra_config:
        econf.update(extra_config)
    engine = InferenceEngineV2(model=model, model_parameters=params,
                               config=econf)
    return engine, vocab


def run_load_point(engine, vocab: int, rate: float, seqs: int, prompt: int,
                   gen: int, duration: float, rng: np.random.RandomState,
                   burst: int = 8, mode: str = "burst"):
    """Drive the serving loop at ``rate`` prompt arrivals/sec for ``duration``
    seconds.

    Policy (iteration-level scheduling, RTT-amortised): owed arrivals are
    admitted and prefilled through mixed scheduler passes; between admissions
    ALL live sequences advance through fused ``decode_steps`` bursts (one
    host<->device round trip per ``burst`` tokens). The decode set is kept at a FIXED
    size once saturated: retired sequences are replaced by owed arrivals in
    the same iteration, so the fused-decode program never recompiles; when no
    arrival is owed, a retired slot generates into waste until one is (the
    waste is reported).

    ``mode="mixed"`` (VERDICT r4 weak #3 — the burst leg never exercised
    SplitFuse COMPOSITION): every iteration advances all live sequences by
    ONE token THROUGH SCHEDULER PASSES — their decode rows share each pass
    with any newly admitted prompts' chunks, the chunk+decode composition
    the FastGen scheduler was built for (reference blogs/deepspeed-fastgen
    §B Dynamic SplitFuse) — so ``mixed_pass_fraction`` measures real
    composed passes. Costs one host round trip per token (no fused burst),
    so the artifact reports both legs side by side.
    """
    next_uid = 10_000
    arrivals = 0
    active = {}           # uid -> generated-token count (may exceed goal: waste)
    # per-sequence generation target. In 'mixed' mode targets STAGGER
    # (uniform in [gen/2, 3*gen/2]) so retirements — and therefore
    # admissions — spread across iterations instead of the whole set
    # retiring in lockstep; a rotation then composes its prompt chunks with
    # the other sequences' decode rows in the same pass, which is the
    # SplitFuse mixing this leg measures. 'burst' keeps a fixed gen for
    # round-over-round comparability.
    goal = {}
    dummies = set()       # slot-keeping sequences; all their tokens are waste
    tbts = []
    gen_tokens = 0
    wasted_tokens = 0
    prompt_tokens = 0
    passes = mixed_passes = 0
    decode_bursts = 0
    # a retired slot may generate at most this much waste before it is rotated
    # onto a fresh (dummy) sequence — bounds KV growth under the ctx budget
    waste_margin = 4 * burst

    def admit(n, dummy=False):
        nonlocal next_uid, arrivals, prompt_tokens
        admitted = 0
        for _ in range(n):
            if len(active) >= seqs:
                break
            uid, next_uid = next_uid, next_uid + 1
            if not engine.can_schedule([uid], [prompt]):
                break
            toks = rng.randint(0, vocab, size=(prompt,)).astype(np.int32)
            engine.scheduler.add_tokens(uid, toks)
            active[uid] = 0
            goal[uid] = (int(rng.randint(max(1, gen // 2),
                                         gen + gen // 2 + 1))
                         if mode == "mixed" else gen)
            if dummy:
                dummies.add(uid)
            else:
                arrivals += 1
                prompt_tokens += prompt
            admitted += 1
        return admitted

    def run_passes():
        """Drain pending prompt chunks through engine passes (mixed when
        decode feeds coexist), counting pass composition."""
        nonlocal passes, mixed_passes
        while engine.scheduler.has_pending():
            orig = engine.scheduler.schedule_pass
            seen = {}

            def counting():
                b = orig()
                if b is not None:
                    seen["mixed"] = bool(b.chunk_uids and b.decode_uids)
                return b

            engine.scheduler.schedule_pass = counting
            try:
                engine._run_pass()
            finally:
                engine.scheduler.schedule_pass = orig
            if seen:
                passes += 1
                mixed_passes += int(seen.get("mixed", False))

    admit(seqs)           # fill to the cap; rate governs REPLACEMENTS
    run_passes()
    t0 = time.time()
    while time.time() - t0 < duration:
        owed = int((time.time() - t0) * rate) - arrivals + seqs
        retired = [u for u, g in active.items() if g >= goal[u]]
        # rotate retired slots: onto real arrivals when owed, else onto dummy
        # slot-keepers once they exceed the waste margin (bounds ctx usage)
        rotate = (retired[:max(owed, 0)] +
                  [u for u in retired[max(owed, 0):]
                   if active[u] >= goal[u] + waste_margin])
        if rotate:
            for u in rotate:
                engine.flush([u])
                dummies.discard(u)
                del active[u]
                del goal[u]
            n_real = admit(min(max(owed, 0), len(rotate)))
            admit(len(rotate) - n_real, dummy=True)
            if mode != "mixed":
                run_passes()   # prefill the replacements

        uids = list(active)
        if not uids:
            time.sleep(0.001)
            continue
        if mode == "mixed":
            # one token per sequence through COMPOSED scheduler passes: the
            # decode rows ride the same pass as any pending prompt chunks
            # (including this iteration's admissions, deliberately left
            # undrained above)
            ready = [u for u in uids
                     if len(engine.scheduler.seqs[u].pending) == 0]
            if not ready:
                run_passes()
                continue
            tb0 = time.time()
            nxt = engine.sample_next(ready)
            # add_tokens directly (NOT _put_nofetch, which drains passes
            # internally and would bypass the composition counter)
            for u, t in zip(ready, nxt):
                engine.scheduler.add_tokens(u, np.asarray([t], np.int32))
            run_passes()
            tb = time.time() - tb0
            step = 1
        else:
            tb0 = time.time()
            engine.decode_steps(uids, burst)
            tb = time.time() - tb0
            decode_bursts += 1
            step = burst
            ready = uids
        for u in ready:
            waste = u in dummies or active[u] >= goal[u]
            active[u] += step
            if waste:
                wasted_tokens += step
            else:
                counted = min(step, goal[u] - (active[u] - step))
                gen_tokens += counted
                wasted_tokens += step - counted   # gen-boundary overshoot
                tbts.extend([tb / step] * counted)

    dt = time.time() - t0
    for u in list(active):
        engine.flush([u])
    total = gen_tokens + prompt_tokens
    return {
        "mode": mode,
        "arrival_rate": rate,
        "concurrency_cap": seqs,
        "gen_tokens_per_sec": round(gen_tokens / dt, 1),
        "total_tokens_per_sec": round(total / dt, 1),
        "mean_tbt_ms": round(1e3 * float(np.mean(tbts)), 2) if tbts else None,
        "p95_tbt_ms": (round(1e3 * float(np.percentile(tbts, 95)), 2)
                       if tbts else None),
        "completed": arrivals - len(active),
        "passes": passes,
        "mixed_pass_fraction": round(mixed_passes / passes, 3) if passes else 0,
        "decode_bursts": decode_bursts,
        "wasted_token_fraction": round(wasted_tokens / max(1, gen_tokens +
                                                           wasted_tokens), 3),
    }


def run_shared_prefix(on_tpu: bool, n_requests: int, prefix_len: int,
                      tail_len: int, gen: int, seed: int = 0):
    """Shared-prefix workload (prefix-cache leg): ``n_requests`` prompts share
    one long system prompt and differ only in a short tail — the traffic shape
    automatic prefix caching (SGLang RadixAttention / vLLM APC) targets.
    Requests are served sequentially on a cache-on and a cache-off engine
    (identical weights; params are seeded deterministically) and the leg
    reports computed prefill tokens, cache hit rate, and — the correctness
    gate — whether greedy outputs are EXACTLY equal between the two.

    Both engines run with the packed-prefill fast path disabled (every pass
    through the paged forward): a cache hit turns a from-zero prefill into a
    continuation, which ALWAYS takes the paged path, while a cache-off engine
    takes the packed path — and the two attention implementations carry a
    benign per-path numerical variance (~3e-2 on this random-init bench model
    at 288 tokens, measured against the dense v1 engine: both paths sit the
    same distance from dense). Holding the kernel path constant makes the
    equality gate test exactly what the cache changes: which KV pages back
    the computation."""
    prompt_len = prefix_len + tail_len

    def serve(prefix_cache: bool):
        engine, vocab = build_engine(on_tpu, seqs=4, prompt=prompt_len,
                                     gen=gen, prefix_cache=prefix_cache)
        orig = engine.scheduler.schedule_pass

        def no_fast_path():
            b = orig()
            if b is not None:
                b.pure_prefill = False
            return b

        engine.scheduler.schedule_pass = no_fast_path
        rng = np.random.RandomState(seed)
        prefix = rng.randint(0, vocab, size=(prefix_len,)).astype(np.int32)
        outs = []
        t0 = time.time()
        try:
            for i in range(n_requests):
                tail = rng.randint(0, vocab, size=(tail_len,)).astype(np.int32)
                prompt = np.concatenate([prefix, tail])
                uid = 5000 + i
                engine._put_nofetch([uid], [prompt])
                toks = []
                for j in range(gen):
                    t = int(engine.sample_next([uid])[0])  # greedy, on device
                    toks.append(t)
                    if j < gen - 1:
                        engine._put_nofetch([uid], [np.asarray([t], np.int32)])
                engine.flush([uid])
                outs.append(toks)
        finally:
            # drop the instance attr (lookup falls back to the class method):
            # the wrapper's closure holds a bound method of the scheduler — a
            # reference cycle that would keep this engine's device KV pool
            # alive past `del eng_off` until a gc pass
            del engine.scheduler.schedule_pass
        wall = time.time() - t0
        return engine, outs, wall

    eng_off, outs_off, wall_off = serve(False)
    # pull the counter and DROP the cache-off engine before building the
    # cache-on one: two engines (weights + full KV pool each) alive at once
    # would double device memory for the whole second leg
    off_prefill = eng_off.scheduler.prefill_tokens_completed
    del eng_off
    eng_on, outs_on, wall_on = serve(True)
    on_prefill = eng_on.scheduler.prefill_tokens_completed
    st = eng_on.prefix_cache.stats
    return {
        "leg": "shared_prefix",
        "requests": n_requests,
        "prefix_tokens": prefix_len,
        "tail_tokens": tail_len,
        "gen": gen,
        "prefill_tokens_cache_off": off_prefill,
        "prefill_tokens_cache_on": on_prefill,
        "prefill_reduction": round(1.0 - on_prefill / max(1, off_prefill), 3),
        "cache_hit_rate": round(st.hit_rate, 3),
        "tokens_saved": st.tokens_saved,
        "evictions": st.evictions,
        "cow_copies": st.cow_copies,
        "outputs_equal": outs_on == outs_off,
        "wall_s_cache_off": round(wall_off, 2),
        "wall_s_cache_on": round(wall_on, 2),
    }


def run_steady_state(on_tpu: bool, seqs: int, prompt: int, gen: int,
                     seed: int = 0):
    """Steady-state decode leg: the same fixed decode set generates ``gen``
    tokens through (a) the per-token serving loop the engine shipped with
    before the pipeline — blocking on-device-sample fetch + full scheduler
    pass per token — and (b) the async double-buffered ``DecodePipeline``
    (fused on-device sampling, bucketed descriptors, one-step-late drain).

    The correctness gate: greedy token streams must be BYTE-IDENTICAL
    between the two loops (same forward math, different orchestration), and
    the pipeline's per-step host transfer must be exactly one int32 row per
    bucket slot (the monitor's fetch-bytes field). Reported: tokens/sec per
    loop, the speedup, p50/p99 per-token latency, and the pipeline's
    per-step phase breakdown. Both loops run a short untimed round first so
    the timed rounds are compile-free (asserted via the engine's compile
    counter).
    """
    from deepspeed_tpu.utils.caching import next_pow2
    # no fused bursts in this leg: warm only the passes + the step-prog grid
    engine, vocab = build_engine(on_tpu, seqs=seqs, prompt=prompt, gen=gen,
                                 warmup=True, warmup_bursts=False)
    rng = np.random.RandomState(seed)
    prompts = [rng.randint(0, vocab, size=(prompt,)).astype(np.int32)
               for _ in range(seqs)]
    uid_base = [20_000]

    def prefill():
        uid_base[0] += seqs
        uids = list(range(uid_base[0], uid_base[0] + seqs))
        engine._put_nofetch(uids, prompts)
        return uids

    def sync_leg(n):
        """Pre-PR loop: per token, one blocking token-row fetch, scheduler
        bookkeeping, a full ragged-pass descriptor build, one pass."""
        uids = prefill()
        outs = [[] for _ in uids]
        lat = []
        t0 = time.time()
        for j in range(n):
            tb = time.time()
            toks = engine.sample_next(uids)   # blocks: sample + row fetch
            for i, t in enumerate(toks):
                outs[i].append(int(t))
            if j < n - 1:                     # last token's pass is unread
                engine._put_nofetch(uids, [np.asarray([t], np.int32)
                                           for t in toks])
            lat.append(time.time() - tb)
        wall = time.time() - t0
        engine.flush(uids)
        return outs, wall, [1e3 * x for x in lat]

    def pipe_leg(n):
        uids = prefill()
        pipe = engine.decode_pipeline(uids)
        st = engine.pipeline_stats
        st.reset()
        t0 = time.time()
        out = pipe.run(n)                     # fully drained on return
        wall = time.time() - t0
        engine.flush(uids)
        return [list(map(int, row)) for row in out], wall, list(st.step_wall_ms)

    # untimed rounds: compile/warm everything either loop touches
    sync_leg(min(4, gen))
    pipe_leg(min(4, gen))
    c0 = engine.compiles
    outs_sync, wall_sync, lat_sync = sync_leg(gen)
    outs_pipe, wall_pipe, lat_pipe = pipe_leg(gen)
    compiles = engine.compiles - c0
    st = engine.pipeline_stats
    bucket = next_pow2(seqs)
    tok = seqs * gen
    n = max(1, st.steps)
    return {
        "leg": "steady_state",
        "seqs": seqs,
        "prompt": prompt,
        "gen": gen,
        "bucket": bucket,
        "sync_tokens_per_sec": round(tok / wall_sync, 1),
        "pipelined_tokens_per_sec": round(tok / wall_pipe, 1),
        "speedup": round(wall_sync / wall_pipe, 2),
        "sync_p50_tbt_ms": round(float(np.percentile(lat_sync, 50)), 3),
        "sync_p99_tbt_ms": round(float(np.percentile(lat_sync, 99)), 3),
        "pipe_p50_tbt_ms": round(float(np.percentile(lat_pipe, 50)), 3),
        "pipe_p99_tbt_ms": round(float(np.percentile(lat_pipe, 99)), 3),
        "outputs_equal": outs_pipe == outs_sync,
        # the tentpole invariant: one int32 row per bucket slot per step
        "fetch_bytes_per_step": st.fetch_bytes_per_step,
        "fetch_is_token_row": st.fetch_bytes_per_step == 4.0 * bucket,
        "dispatch_ms_per_step": round(st.dispatch_ms / n, 3),
        "host_build_ms_per_step": round(st.host_build_ms / n, 3),
        "fetch_drain_ms_per_step": round(st.fetch_drain_ms / n, 3),
        "bubble_ms_per_step": round(st.bubble_ms / n, 3),
        "compiles_during_timed_runs": compiles,
    }


def _spec_select_prompts(engine, vocab: int, seqs: int, prompt: int,
                         rng: np.random.RandomState, candidates: int = 16,
                         probe_steps: int = 10):
    """Seeded search for REPETITIVE-regime prompts: tiled short phrases
    whose greedy continuation (on this random-init bench model) settles
    into loops the n-gram proposer can ride — the CPU-box analog of the
    templated/boilerplate traffic speculative decoding targets on a real
    model (a random-init model has no natural templated register, so the
    bench selects for the regime instead of pretending one exists). The
    probe runs SHORT spec bursts on the warmed grid and keeps the prompts
    with the most emitted tokens per verify step; selection is seeded and
    UNTIMED, and the byte-equality gate downstream is independent of it."""
    from deepspeed_tpu.inference.v2.spec import SpecDecodePipeline
    scored = []
    uid = 60_000
    for c0 in range(0, candidates, seqs):
        uids, prompts = [], []
        for _ in range(min(seqs, candidates - c0)):
            phrase = rng.randint(0, vocab,
                                 size=(int(rng.randint(3, 8)),)).astype(np.int32)
            p = np.tile(phrase, -(-prompt // len(phrase)))[:prompt]
            uids.append(uid)
            prompts.append(p)
            uid += 1
        engine._put_nofetch(uids, prompts)
        pipe = SpecDecodePipeline(engine, uids)
        head = pipe.run(probe_steps)
        # score the LOOP REGIME (the probe's tail): early steps measure the
        # cold ramp every prompt pays once, not how hard the loop sustains
        tail = pipe.run(probe_steps)
        engine.flush(uids)
        for p, toks in zip(prompts, tail):
            scored.append((len(toks), p))
        del head
    scored.sort(key=lambda x: -x[0])
    return [p for _, p in scored[:seqs]]


def run_spec(on_tpu: bool, smoke: bool, seqs: int = 4, prompt: int = 48,
             gen: int = 128, k: int = 15, reps: int = 3, seed: int = 0):
    """The speculative-decoding leg (docs/SERVING.md "Speculative
    decoding"): the SAME warmed engine generates ``gen`` greedy tokens per
    sequence through (a) the spec-off ``DecodePipeline`` (the PR 3
    baseline) and (b) the draft-and-verify ``SpecDecodePipeline``, over two
    workloads:

    - ``repetitive``: prompts selected (seeded, untimed) so greedy
      continuations loop — the templated-text regime prompt-lookup
      drafting targets; gates tok/s ratio >= the acceptance bar.
    - ``natural``: random prompts — low acceptance by construction on a
      random-init model; reported for the acceptance-economics curve, no
      speed bar (adaptive k backoff keeps the cost near 1x).

    Gates (every rep): byte-identical greedy streams spec-on vs spec-off,
    zero engine compiles in timed phases (the (bucket, k) verify grid rides
    warmup), and allocator free blocks back to baseline after every leg
    (reject-heavy runs exercise ``rollback_reserved``). Legs alternate
    off/on per rep; the ratio gate compares medians across reps."""
    from deepspeed_tpu.inference.v2.pipeline import DecodePipeline
    from deepspeed_tpu.inference.v2.spec import SpecDecodePipeline
    if smoke:
        gen, reps = min(gen, 32), 1
    # ctx slack must cover the WORST-case speculative reservation: the
    # selection probe's two back-to-back 10-step runs (a perfectly-looping
    # candidate — the exact regime the probe selects for — emits
    # 10*(k+1) in run one and run two still reserves 10*(k+1)+1 up
    # front), plus the timed legs' 8-step chunks
    engine, vocab = build_engine(on_tpu, seqs=seqs, prompt=prompt, gen=gen,
                                 warmup=True, warmup_bursts=False,
                                 spec_k=k,
                                 ctx_slack=(2 * 10 + 8) * (k + 1) + 16)
    rng = np.random.RandomState(seed)
    natural = [rng.randint(0, vocab, size=(prompt,)).astype(np.int32)
               for _ in range(seqs)]
    repetitive = _spec_select_prompts(engine, vocab, seqs, prompt, rng,
                                      candidates=seqs if smoke else 4 * seqs)
    uid_base = [80_000]

    def prefill(prompts):
        uid_base[0] += seqs
        uids = list(range(uid_base[0], uid_base[0] + seqs))
        engine._put_nofetch(uids, prompts)
        return uids

    def off_leg(prompts):
        uids = prefill(prompts)
        pipe = DecodePipeline(engine, uids)
        t0 = time.time()
        out = pipe.run(gen)
        wall = time.time() - t0
        engine.flush(uids)
        return [list(map(int, row)) for row in out], wall

    def spec_leg(prompts):
        uids = prefill(prompts)
        engine.spec_stats.reset()
        pipe = SpecDecodePipeline(engine, uids)
        outs = {u: [] for u in uids}

        def cb(j, run_uids, toks):
            stop = []
            for i, u in enumerate(run_uids):
                if len(outs[u]) >= gen:
                    continue
                outs[u].extend(int(t) for t in toks[i])
                if len(outs[u]) >= gen:
                    stop.append(u)
            return stop

        t0 = time.time()
        while pipe.uids:
            pipe.run(8, on_tokens=cb)
        wall = time.time() - t0
        engine.flush(uids)
        return [outs[u][:gen] for u in uids], wall

    ok = True
    results = []
    for leg, prompts in (("repetitive", repetitive), ("natural", natural)):
        # untimed warm pass for each loop shape
        off_leg(prompts)
        spec_leg(prompts)
        rep_out = []
        for r in range(reps):
            free0 = engine.free_blocks
            c0 = engine.compiles
            ref, wall_off = off_leg(prompts)
            got, wall_on = spec_leg(prompts)
            st = engine.spec_stats
            out = {
                "leg": "spec", "workload": leg, "rep": r,
                "seqs": seqs, "prompt": prompt, "gen": gen, "k": k,
                "spec_off_tok_s": round(seqs * gen / wall_off, 1),
                "spec_on_tok_s": round(seqs * gen / wall_on, 1),
                "ratio": round(wall_off / wall_on, 3),
                "acceptance_rate": round(st.acceptance_rate, 3),
                "tokens_per_step": round(st.tokens_per_step, 2),
                "draft_ms_per_step": round(st.draft_ms / max(1, st.steps), 3),
                "outputs_equal": got == ref,
                "compiles_during_timed": engine.compiles - c0,
                "free_blocks_at_baseline": engine.free_blocks == free0,
            }
            rep_out.append(out)
            print(json.dumps(out), flush=True)
            if not out["outputs_equal"] or out["compiles_during_timed"] != 0 \
                    or not out["free_blocks_at_baseline"]:
                ok = False
        results.append((leg, rep_out))
    med = {leg: float(np.median([x["ratio"] for x in outs]))
           for leg, outs in results}
    # the acceptance bar: repetitive-text decode tok/s over the spec-off
    # pipeline (ROADMAP 1.8x on TPU; 1.5x floor on the 2-core CPU box where
    # the drained verify step shares two cores with the host loop). Smoke
    # gates correctness only — at smoke sizes throughput is noise.
    bar = 1.0 if smoke else 1.5
    gate = med["repetitive"] >= bar if not smoke else True
    print(json.dumps({"gate": "spec_decode_speedup", "ok": bool(gate),
                      "median_ratio": med, "bar": bar, "reps": reps}),
          flush=True)
    return ok and gate


def build_frontend_engine(on_tpu: bool, pool_blocks: int, ctx: int,
                          rows: int = 4, block_size: int = 16,
                          prefix_cache: bool = False, lora: dict = None):
    """A warmed engine sized so the frontend workload SATURATES the KV pool
    (the regime preemption policy differentiates in): a deliberately small
    page pool, the full pow2 decode grid pre-compiled. ``prefix_cache``
    turns the radix tree on (the --router leg's routing substrate);
    ``lora`` enables the adapter pool (the --lora leg — warmup then also
    pre-compiles the (bucket, rank-bucket) program ladder)."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    if on_tpu:
        layers, hidden, heads, vocab = 12, 1536, 12, 32000
    else:
        layers, hidden, heads, vocab = 2, 64, 4, 256
    cfg = LlamaConfig(vocab_size=vocab, hidden_size=hidden,
                      intermediate_size=hidden * 4, num_hidden_layers=layers,
                      num_attention_heads=heads, num_key_value_heads=heads,
                      max_position_embeddings=ctx,
                      dtype=jnp.bfloat16 if on_tpu else jnp.float32)
    model = LlamaForCausalLM(cfg)
    params = jax.jit(model.init)(
        jax.random.PRNGKey(0),
        {"input_ids": jnp.zeros((1, 8), jnp.int32)})["params"]
    econf = {"state_manager": {"max_tracked_sequences": 4 * rows,
                               "max_ragged_sequence_count": rows,
                               "max_ragged_batch_size": 128 + rows,
                               "prefill_chunk_size": 32,
                               "max_context": ctx},
             "kv_cache": {"block_size": block_size,
                          "num_blocks": pool_blocks},
             "compile": {"warmup": True}}
    if prefix_cache:
        econf["prefix_cache"] = {"enabled": True}
    if lora:
        econf["lora"] = dict(lora, enabled=True)
    if not on_tpu:
        econf["dtype"] = jnp.float32
    engine = InferenceEngineV2(model=model, model_parameters=params,
                               config=econf)
    return engine, vocab


def _frontend_classes():
    # interactive outranks batch; its SLOs are meaningful on this box, batch
    # SLOs are loose (batch work tolerates preemption — that is the point)
    return [{"name": "interactive", "priority": 2,
             "ttft_slo_ms": 2500.0, "tbt_slo_ms": 400.0},
            {"name": "batch", "priority": 0,
             "ttft_slo_ms": 60000.0, "tbt_slo_ms": 20000.0}]


def _serve_plain(engine, uid, prompt, gen):
    """Direct PLAIN-pipeline reference serve — explicitly DecodePipeline,
    NOT the spec-aware ``engine.decode_pipeline`` factory: the
    bit-identical-programs side of the byte gates that serve their
    frontends with ``serving.spec = False`` (run_kv_dtype's gate
    taxonomy)."""
    from deepspeed_tpu.inference.v2.pipeline import DecodePipeline
    engine._put_nofetch([uid], [np.asarray(prompt, np.int32)])
    out = DecodePipeline(engine, [uid]).run(gen)
    engine.flush([uid])
    return [int(t) for t in out[0]]


def _forced_preempt_cycle(engine, frontend, vocab, rng, *, low_prompt=24,
                          low_new=48, grow_iters=40, grown=None,
                          hi_prompt=96, finish_iters=300, byte_check=False):
    """One deterministic preempt-offload-restore cycle, step()-driven (no
    thread): two batch requests decode until ``grown`` says their KV
    growth has pressured the pool (default: too few free blocks for an
    interactive arrival), which then preempts one. ``byte_check=True``
    additionally replays all three streams through direct DecodePipeline
    runs — the --kv-dtype leg's gate that the packed value+scale payload
    round trip preserved the stream. Returns (ok, detail)."""
    if grown is None:
        def grown(lows):
            return engine.scheduler.available_blocks < 8
    lows = [frontend.submit(rng.randint(0, vocab,
                                        size=(low_prompt,)).astype(np.int32),
                            priority="batch", max_new_tokens=low_new)
            for _ in range(2)]
    for _ in range(grow_iters):              # let batch KV grow into the pool
        frontend.step()
        if grown(lows):
            break
    h_hi = frontend.submit(rng.randint(0, vocab,
                                       size=(hi_prompt,)).astype(np.int32),
                           priority="interactive", max_new_tokens=8)
    for _ in range(finish_iters):
        if h_hi.finished and all(h.finished for h in lows):
            break
        frontend.step()
    ok = (h_hi.status == "finished"
          and all(h.status == "finished" for h in lows)
          and frontend.stats.preemptions >= 1
          and frontend.stats.restores >= 1
          and frontend.stats.offload_bytes > 0)
    detail = {"preemptions": frontend.stats.preemptions,
              "restores": frontend.stats.restores,
              "offload_bytes": frontend.stats.offload_bytes,
              "lo_tokens": [len(h.tokens) for h in lows],
              "hi_tokens": len(h_hi.tokens)}
    if byte_check:
        equal = 0
        for i, h in enumerate(lows + [h_hi]):
            equal += _serve_plain(engine, 88_000 + i, h.prompt,
                                  len(h.tokens)) == h.tokens
        ok = ok and equal == 3
        detail["streams_equal"] = equal
        detail["streams_checked"] = 3
    return ok, detail


def run_frontend(on_tpu: bool, smoke: bool, rate: float, duration: float,
                 seed: int = 0, reps: int = 3):
    """The SLO-aware frontend leg (docs/SERVING.md "Frontend"): a seeded
    Poisson mixed-priority workload replayed identically against each
    preemption policy on ONE warmed engine, gating

      - byte-equality: every completed stream == a direct decode_pipeline
        run of the same prompt (offload + reject-only modes; recompute
        victims legitimately re-prefill through a different kernel path),
      - zero engine compiles during every timed phase (the pow2 grid +
        warmed page round-trip absorb admission, preemption and restore),
      - one forced preempt-offload-restore cycle (deterministic, pre-replay),
      - goodput-under-SLO: median over ``reps`` replays, offload >=
        recompute and >= reject-only (full runs only; the default rate
        clearly OVERSUBSCRIBES the pool — token demand ~1.7x measured
        capacity — so every rep runs in the triage regime preemption policy
        exists for, and requests unfinished at the drain deadline are
        cancelled, scoring zero).

    Smoke runs the offload mode only, one rep (<60 s on a 2-core CPU box)."""
    from deepspeed_tpu.inference.v2.serving import (PoissonLoadGen,
                                                    WorkloadComponent,
                                                    goodput_report, replay)
    engine, vocab = build_frontend_engine(on_tpu, pool_blocks=14, ctx=160)
    mix = [WorkloadComponent("interactive", 4.0, [16, 32], [8, 16, 24]),
           WorkloadComponent("batch", 1.0, [48], [96])]
    arrivals = PoissonLoadGen(rate=rate, mix=mix, vocab=vocab,
                              seed=seed).arrivals(duration=duration)
    modes = ["offload"] if smoke else ["offload", "recompute", "none"]
    if smoke:
        reps = 1
    results = {m: [] for m in modes}
    forced = None
    ok = True
    # reps interleave the modes (off/rec/none, off/rec/none, ...) so slow
    # drift on a shared box lands on every mode, not one — the same
    # alternation discipline the trace-overhead bench uses
    for r in range(reps):
        for mode in modes:
            serving = {"classes": _frontend_classes(), "decode_slice": 4,
                       "preemption": mode, "idle_wait_s": 0.002}
            fe = engine.serving_frontend(config=serving)
            c0 = engine.compiles
            if mode == "offload" and r == 0:
                rng = np.random.RandomState(seed + 1)
                f_ok, forced = _forced_preempt_cycle(engine, fe, vocab, rng)
                forced["ok"] = f_ok
            t0 = time.time()
            fe.start()
            handles = replay(fe, arrivals)
            fe.drain(timeout=2.5 * duration)
            wall = time.time() - t0
            fe.close()           # past-deadline stragglers cancel: 0 goodput
            compiles = engine.compiles - c0
            rep = goodput_report(handles, wall)
            # byte-equality: finished streams vs direct pipeline runs of the
            # same prompts on the same engine (preempt-offloaded included)
            finished = [h for h in handles if h.status == "finished"]
            check = finished[:24] if smoke else finished[:48]
            preempted_checked = equal = skipped = 0
            for h in check:
                if mode == "recompute" and h.preemptions:
                    skipped += 1
                    continue
                engine._put_nofetch([77_000 + h.uid], [h.prompt])
                out = engine.decode_pipeline(
                    [77_000 + h.uid]).run(len(h.tokens))
                engine.flush([77_000 + h.uid])
                if [int(t) for t in out[0]] == h.tokens:
                    equal += 1
                    preempted_checked += bool(h.preemptions)
            checked = len(check) - skipped
            out = {
                "leg": "frontend", "mode": mode, "rep": r, "rate": rate,
                "duration": duration, "arrivals": len(arrivals),
                "preemptions": fe.stats.preemptions,
                "recompute_preemptions": fe.stats.recompute_preemptions,
                "restores": fe.stats.restores,
                "offload_bytes": fe.stats.offload_bytes,
                "forced_cycle": forced if (mode == "offload" and r == 0)
                else None,
                "streams_checked": checked,
                "streams_equal": equal,
                "preempted_streams_checked": preempted_checked,
                "outputs_equal": equal == checked,
                "compiles_during_timed": compiles,
                **rep,
            }
            results[mode].append(out)
            print(json.dumps(out), flush=True)
            if mode != "recompute" and not out["outputs_equal"]:
                ok = False
            if compiles != 0:
                ok = False
    if not forced["ok"]:
        print(json.dumps({"gate": "forced_preempt_offload_restore",
                          "ok": False}), flush=True)
        ok = False
    if not smoke:
        med = {m: float(np.median([x["goodput_tokens_per_sec"]
                                   for x in results[m]])) for m in modes}
        gate = med["offload"] >= med["recompute"] \
            and med["offload"] >= med["none"]
        print(json.dumps({"gate": "goodput_under_slo", "ok": gate,
                          "median_goodput": med, "reps": reps}), flush=True)
        ok = ok and gate
    return ok


def _register_bench_adapters(engine, ranks):
    """Register one seeded random adapter per entry of ``ranks`` (names
    ``ad0, ad1, ...``); deltas are small (~2% weight scale) so streams stay
    well-formed but DO diverge from base decodes."""
    from deepspeed_tpu.module_inject.lora import load_lora_adapter
    spec = engine.spec
    din = spec.hidden_size
    douts = {"q": spec.num_heads * spec.head_dim,
             "k": spec.num_kv_heads * spec.head_dim,
             "v": spec.num_kv_heads * spec.head_dim,
             "o": spec.hidden_size}
    names = []
    for i, r in enumerate(ranks):
        g = np.random.RandomState(1000 + i)
        state = {"alpha": float(r)}
        for t in engine.config.lora.targets:
            state[t] = {
                "A": (g.standard_normal((din, r)) * 0.02).astype(np.float32),
                "B": (g.standard_normal((r, douts[t])) * 0.02).astype(
                    np.float32)}
        name = f"ad{i}"
        load_lora_adapter(engine, name, state)
        names.append(name)
    return names


def _serve_lora_plain(engine, uid, prompt, gen, adapter):
    """Direct plain-pipeline reference serve under an adapter binding —
    the byte-equality oracle for the --lora leg's mixed-tenant streams."""
    from deepspeed_tpu.inference.v2.pipeline import DecodePipeline
    if adapter is not None:
        engine.lora.acquire(uid, adapter)
    try:
        engine._put_nofetch([uid], [np.asarray(prompt, np.int32)])
        out = DecodePipeline(engine, [uid]).run(gen)
        engine.flush([uid])
    finally:
        if adapter is not None:
            engine.lora.release(uid)
    return [int(t) for t in out[0]]


def _lora_pool_baseline(engine):
    """(ok, detail): adapter pool consistency at idle — every refcount 0,
    free + resident pages account for the whole pool, no pinned swap
    buffers outstanding."""
    reg = engine.lora
    resident = sum(reg.rank(n) for n in reg.names if reg.is_resident(n))
    free = reg.pool.free_pages
    detail = {"free_pages": free, "resident_pages": resident,
              "pool_pages": reg.pool.num_pages,
              "refcounts": {n: reg.refcount(n) for n in reg.names},
              "swap_outstanding": reg.swap.outstanding}
    ok = (free + resident == reg.pool.num_pages
          and all(v == 0 for v in detail["refcounts"].values())
          and reg.swap.outstanding == 0)
    return ok, detail


def run_lora(on_tpu: bool, smoke: bool, rate: float, duration: float,
             seed: int = 0, reps: int = 3):
    """The multi-tenant LoRA leg (BENCH_r18; docs/SERVING.md "Multi-tenant
    LoRA"): a seeded Poisson mix where arrivals draw tenants from MORE
    registered adapters than the adapter pool holds at once — admission
    faults cold adapters in and LRU-evicts idle ones while one ragged
    decode batch mixes tenants. Gates, every rep:

      - byte-equality: finished mixed-batch streams == direct per-adapter
        DecodePipeline runs on the same warmed engine,
      - zero engine compiles during every timed phase (the warmed
        (bucket, rank-bucket) ladder absorbs adapter churn),
      - allocator AND adapter pool at baseline after drain (refcounts 0,
        free + resident pages == pool, no pinned buffers outstanding),

    and (full runs) goodput-under-SLO >= 1.5x a NAIVE one-adapter-at-a-time
    baseline: the same arrivals grouped by adapter and each group served
    sequentially to drain (group-relative arrival stamps — generous to the
    baseline, which never pays cross-tenant queueing), on the same engine.
    Spec decode stays OFF: one variable (grouped adapter matmul) per leg."""
    import dataclasses
    from deepspeed_tpu.inference.v2.serving import (PoissonLoadGen,
                                                    WorkloadComponent,
                                                    goodput_report, replay)
    engine, vocab = build_frontend_engine(
        on_tpu, pool_blocks=20, ctx=160,
        lora={"pool_pages": 8, "max_rank": 4, "swap_buffers": 16})
    # 4 adapters totalling 13 pages against an 8-page pool: at most two of
    # the rank-4 tenants are resident with a third's pages in flight, so a
    # saturating mix MUST evict/restore to serve everyone
    adapters = _register_bench_adapters(engine, ranks=[4, 4, 3, 2])
    mix = [WorkloadComponent("interactive", 3.0, [16, 32], [8, 16],
                             adapter_id=adapters),
           WorkloadComponent("interactive", 1.0, [16], [8]),   # base tenant
           WorkloadComponent("batch", 1.0, [32], [24],
                             adapter_id=adapters[0])]
    arrivals = PoissonLoadGen(rate=rate, mix=mix, vocab=vocab,
                              seed=seed).arrivals(duration=duration)
    serving = {"classes": _frontend_classes(), "decode_slice": 4,
               "preemption": "offload", "idle_wait_s": 0.002,
               "spec": False}
    if smoke:
        reps = 1
    ok = True
    mixed_good, naive_good = [], []
    for r in range(reps):
        kv_free0 = engine.allocator.free_blocks
        # -- mixed multi-tenant replay (the subsystem under test) ---------
        fe = engine.serving_frontend(config=serving)
        c0 = engine.compiles
        t0 = time.time()
        fe.start()
        handles = replay(fe, arrivals)
        fe.drain(timeout=2.5 * duration + 20)
        wall = time.time() - t0
        fe.close()
        compiles_mixed = engine.compiles - c0
        rep = goodput_report(handles, wall)
        faults = engine.lora.stats
        # byte-equality: mixed-batch streams vs direct per-adapter serves
        finished = [(h, a) for h, a in zip(handles, arrivals)
                    if h.status == "finished" and h.tokens]
        check = finished[:16] if smoke else finished[:32]
        c1 = engine.compiles
        equal = 0
        for i, (h, a) in enumerate(check):
            got = _serve_lora_plain(engine, 91_000 + i, h.prompt,
                                    len(h.tokens), a.adapter)
            equal += got == h.tokens
        compiles_ref = engine.compiles - c1
        # settle the swap pool before the baseline: adapters that happen to
        # sit EVICTED here legitimately hold pinned buffers, which the leak
        # check would misread as outstanding (the --lora --smoke flake)
        engine.lora.drain_swap()
        pool_ok, pool_detail = _lora_pool_baseline(engine)
        kv_ok = engine.allocator.free_blocks == kv_free0
        out = {
            "leg": "lora", "mode": "mixed", "rep": r, "rate": rate,
            "duration": duration, "arrivals": len(arrivals),
            "adapters": len(adapters),
            "adapter_pool_pages": engine.lora.pool.num_pages,
            "adapter_faults": sum(c.faults
                                  for c in faults.adapters.values()),
            "adapter_evictions": sum(c.evictions
                                     for c in faults.adapters.values()),
            "adapter_hit_fraction": round(faults.hit_fraction, 3),
            "streams_checked": len(check), "streams_equal": equal,
            "outputs_equal": equal == len(check),
            "compiles_during_timed": compiles_mixed + compiles_ref,
            "allocator_at_baseline": kv_ok,
            "adapter_pool_at_baseline": pool_ok,
            "adapter_pool": pool_detail,
            **rep,
        }
        print(json.dumps(out), flush=True)
        mixed_good.append(rep["goodput_tokens_per_sec"])
        ok = ok and out["outputs_equal"] and kv_ok and pool_ok \
            and out["compiles_during_timed"] == 0
        # -- naive one-adapter-at-a-time baseline -------------------------
        groups = {}
        for a in arrivals:
            groups.setdefault(a.adapter, []).append(a)
        naive_wall = 0.0
        naive_tokens = 0
        compiles_naive = 0
        for key in sorted(groups, key=lambda k: groups[k][0].t):
            grp = [dataclasses.replace(a, t=a.t - groups[key][0].t)
                   for a in groups[key]]
            fe = engine.serving_frontend(config=serving)
            c0 = engine.compiles
            t0 = time.time()
            fe.start()
            hs = replay(fe, grp)
            fe.drain(timeout=2.5 * duration + 20)
            naive_wall += time.time() - t0
            fe.close()
            compiles_naive += engine.compiles - c0
            naive_tokens += goodput_report(hs, 1.0)["good_tokens"]
        naive = round(naive_tokens / naive_wall, 1)
        out = {"leg": "lora", "mode": "naive_sequential", "rep": r,
               "groups": len(groups), "wall_s": round(naive_wall, 2),
               "goodput_tokens_per_sec": naive,
               "compiles_during_timed": compiles_naive}
        print(json.dumps(out), flush=True)
        naive_good.append(naive)
        ok = ok and compiles_naive == 0
    if not smoke:
        med_m = float(np.median(mixed_good))
        med_n = float(np.median(naive_good))
        gate = med_m >= 1.5 * med_n
        print(json.dumps({"gate": "lora_goodput_vs_naive", "ok": gate,
                          "median_mixed": med_m, "median_naive": med_n,
                          "required_ratio": 1.5,
                          "ratio": round(med_m / max(med_n, 1e-9), 2)}),
              flush=True)
        ok = ok and gate
    return ok


def _kv_dtype_layout(on_tpu: bool):
    """(layers, hidden, heads, kv_heads, vocab) for the --kv-dtype leg."""
    if on_tpu:
        return 12, 1536, 12, 12, 32000
    return 2, 256, 2, 2, 256


def _kv_dtype_bpb(on_tpu: bool, kvq: bool) -> int:
    """bytes_per_block at the leg's pool layout — sizes the shared byte
    budget and the capacity thresholds from the SAME math the engine
    pools use, so the leg works on both the CPU (fp32) and TPU (bf16)
    model shapes."""
    import jax.numpy as jnp
    from deepspeed_tpu.inference.v2.ragged.kv_cache import KVCacheConfig
    layers, hidden, heads, kvh, _ = _kv_dtype_layout(on_tpu)
    return KVCacheConfig(num_layers=layers, num_kv_heads=kvh,
                         head_dim=hidden // heads, block_size=64,
                         num_blocks=1,
                         dtype=jnp.bfloat16 if on_tpu else jnp.float32,
                         quantized=kvq).bytes_per_block()


def build_kv_dtype_engine(on_tpu: bool, kvq: bool, budget_bytes: int,
                          rows: int = 4, ctx: int = 256, spec_k: int = 3,
                          num_blocks: int = None):
    """A warmed engine for the --kv-dtype leg: head_dim-128 model (the
    int8 alignment gate), prefix cache AND spec decode ON — the full
    production composition the former build-time refusals forbade — and
    the KV pool sized from ONE shared HBM byte budget, so the int8 pool's
    extra blocks ARE the capacity win the goodput gate measures."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.inference.v2.ragged.kv_cache import KVCacheConfig
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    # CPU layout: hidden/intermediate/H*D all <= 256 ON PURPOSE — XLA CPU
    # runs M=1 matmuls through a GEMV kernel whose reduction order differs
    # from the M>=2 GEMM path once K reaches 512 (measured: row 0 of a
    # [1,512]x[512,512] f32 dot differs from the same row inside a [4,512]
    # batch by ~6e-5), so a solo-rerun reference can never byte-match a
    # dynamically-batched serving stream at that width — every reduction
    # dim stays <= 256 so the leg's byte gates compare bit-identical math
    # (head_dim stays 128 for the int8 gate)
    layers, hidden, heads, kvh, vocab = _kv_dtype_layout(on_tpu)
    block_size = 64                       # kvh * 64 lane-aligns both configs
    cfg = LlamaConfig(vocab_size=vocab, hidden_size=hidden,
                      intermediate_size=hidden, num_hidden_layers=layers,
                      num_attention_heads=heads, num_key_value_heads=kvh,
                      max_position_embeddings=ctx,
                      dtype=jnp.bfloat16 if on_tpu else jnp.float32)
    model = LlamaForCausalLM(cfg)
    params = jax.jit(model.init)(
        jax.random.PRNGKey(0),
        {"input_ids": jnp.zeros((1, 8), jnp.int32)})["params"]
    probe = KVCacheConfig(num_layers=layers, num_kv_heads=kvh,
                          head_dim=hidden // heads, block_size=block_size,
                          num_blocks=1,
                          dtype=jnp.bfloat16 if on_tpu else jnp.float32,
                          quantized=kvq)
    if num_blocks is None:
        num_blocks = max(4, budget_bytes // probe.bytes_per_block())
    econf = {"state_manager": {"max_tracked_sequences": 4 * rows,
                               "max_ragged_sequence_count": rows,
                               "max_ragged_batch_size": 128 + rows,
                               "prefill_chunk_size": 32,
                               "max_context": ctx},
             "kv_cache": {"block_size": block_size,
                          "num_blocks": num_blocks},
             "prefix_cache": {"enabled": True},
             "spec_decode": {"enabled": True, "k": spec_k},
             "compile": {"warmup": True}}
    if kvq:
        econf["kv_quant"] = {"enabled": True}
    if not on_tpu:
        econf["dtype"] = jnp.float32
    engine = InferenceEngineV2(model=model, model_parameters=params,
                               config=econf)
    return engine, vocab, num_blocks


def run_kv_dtype(on_tpu: bool, smoke: bool, rate: float, duration: float,
                 seed: int = 0, reps: int = 3):
    """The --kv-dtype int8 leg (docs/SERVING.md "Quantized KV"): the SAME
    seeded Poisson workload against an fp32 (bf16 on TPU) pool and an int8
    pool sized from ONE byte budget, both engines with prefix cache AND
    spec decode enabled (the composition this PR unlocked), gating

      - BYTE tier (int8 engine): cache-hit re-serves byte-identical to the
        cold serve (radix reuse + COW scale-tile adoption), spec-on ==
        spec-off streams, one forced preempt-offload-restore cycle with
        the restored stream checked, and every checked frontend stream ==
        a direct decode_pipeline run of the same prompt;
      - zero engine compiles during every timed phase (warmup covers the
        decode grid, the (bucket, k) verify grid and the packed page-op
        round trip);
      - the capacity win: kv bytes/token measurably below the fp pool
        (the monitor gauge — the HBM-stream claim at this layout) and the
        int8 pool holding more blocks at the same byte budget — >= 2x vs
        the CPU fp32 pool, >= 1.7x vs the TPU bf16 pool (half-width
        elements cap the win at <2x once scale tiles ride on top);
      - the RESIDENCY gate (full runs, every rep, compute-independent):
        the same replay that forces the fp pool to preempt-churn (it
        cannot hold the workload's KV working set at this byte budget)
        runs with ZERO preemptions on the int8 pool — the ~3.5x block
        density holding the working set resident is the capacity fact
        the goodput conversion rests on;
      - goodput-under-SLO medians are REPORTED on CPU and GATED
        (int8 >= fp) on TPU only: this 2-core interpret-mode box is
        compute-bound, so walls measure interpret dequant overhead and
        spec-draft scheduling noise, not the HBM-bound serving regime
        (measured here: every run completes every request within SLO and
        goodput differences are pure wall noise) — the regime the int8
        decode kernel's 1.27x and the resident-capacity doubling convert
        in is the TPU one the gate targets.

    int8-vs-fp streams are NOT compared byte-wise — quantization changes
    numerics by design; the cross-dtype tier is the prefill-logits rtol
    gate (documented in docs/SERVING.md). The timed replays serve with
    ``serving.spec = False`` (the plain pipeline) so every stream
    byte-check compares bit-identical programs and isolates
    ORCHESTRATION (admission/preemption/restore/cache): spec-on vs
    spec-off greedy streams agree only up to cross-kernel float noise
    (~1e-4/token argmax flips on this random-init model — measured; the
    gate-taxonomy line docs/SERVING.md draws), so the spec x int8
    composition is byte-gated at its own deterministic scale (the
    spec_stream_equal gate here + tests/unit/test_kv_quant_stack.py +
    the --spec leg) rather than across thousands of replay tokens."""
    from deepspeed_tpu.inference.v2.serving import (PoissonLoadGen,
                                                    WorkloadComponent,
                                                    goodput_report, replay)
    # the shared budget: 6 fp blocks at the platform's pool layout (CPU
    # fp32: ~1.5 MB; TPU bf16: ~27 MB) — small enough that the batch
    # mixture's KV lifetime SATURATES the fp pool (constant preempt/
    # offload churn) while the denser int8 pool (~3.5x on fp32, ~1.9x on
    # bf16) holds the whole working set resident: the capacity regime the
    # goodput gate measures
    budget = 6 * _kv_dtype_bpb(on_tpu, kvq=False)
    engines = {}
    blocks = {}
    for name, kvq in (("fp", False), ("int8", True)):
        e, vocab, nb = build_kv_dtype_engine(on_tpu, kvq, budget)
        _force_paged(e)
        engines[name], blocks[name] = e, nb
    ok = True
    rng = np.random.RandomState(seed)
    bpt = {n: e.kv.config.bytes_per_block() / e.kv.config.block_size
           for n, e in engines.items()}

    # ---- cross-dtype rtol tier: prefill logits ------------------------ #
    toks = [rng.randint(0, vocab, size=(24,)).astype(np.int32)
            for _ in range(2)]
    lf = np.asarray(engines["fp"].put([1, 2], [t.copy() for t in toks]),
                    np.float32)
    lq = np.asarray(engines["int8"].put([1, 2], [t.copy() for t in toks]),
                    np.float32)
    for e in engines.values():
        e.flush([1, 2])
    rtol_gate = float(np.max(np.abs(lf - lq))) < 0.05 * float(np.max(np.abs(lf)))

    # ---- byte tier on the int8 engine --------------------------------- #
    eq = engines["int8"]

    prefix = rng.randint(0, vocab, size=(96,))
    tail = rng.randint(0, vocab, size=(8,))
    prompt = np.concatenate([prefix, tail]).astype(np.int32)
    cold = _serve_plain(eq, 900, prompt, 12)
    hits0 = eq.prefix_cache.stats.hits
    warm = _serve_plain(eq, 901, prompt, 12)
    cache_gate = warm == cold and eq.prefix_cache.stats.hits > hits0

    from deepspeed_tpu.inference.v2.spec import SpecDecodePipeline
    p2 = rng.randint(0, vocab, size=(20,)).astype(np.int32)
    ref = _serve_plain(eq, 902, p2, 12)
    eq._put_nofetch([903], [p2.copy()])
    sp = SpecDecodePipeline(eq, [903])
    got = []
    while sp.uids and len(got) < 12:
        for row in sp.run(2):
            got.extend(int(t) for t in row)
    eq.flush([903])
    spec_gate = got[:12] == ref

    # ---- forced preempt-offload-restore on a POOL-SATURATED int8 engine
    # (the main int8 engine's whole point is that it does NOT saturate):
    # a quarter-budget pool forces admission to offload a decoding batch
    # victim's packed value+scale pages and restore them byte-exactly,
    # with zero compiles (warmup covers the page-op grid)
    ef, _, _ = build_kv_dtype_engine(on_tpu, True, budget // 4)
    _force_paged(ef)
    fe_f = ef.serving_frontend(config={"classes": [
        {"name": "interactive", "priority": 2,
         "ttft_slo_ms": 60000.0, "tbt_slo_ms": 20000.0},
        {"name": "batch", "priority": 0,
         "ttft_slo_ms": 60000.0, "tbt_slo_ms": 20000.0}],
        "decode_slice": 4, "spec": False, "idle_wait_s": 0.002})
    cf0 = ef.compiles
    f_ok, forced = _forced_preempt_cycle(
        ef, fe_f, vocab, np.random.RandomState(seed + 1),
        low_prompt=150, low_new=60, grow_iters=80,
        # a batch victim must be DECODING when the interactive lands
        grown=lambda lows: any(len(h.tokens) >= 4 for h in lows),
        hi_prompt=128, finish_iters=900, byte_check=True)
    forced["ok"] = f_ok
    forced["compiles"] = ef.compiles - cf0
    fe_f.close()
    _unforce_paged(ef)
    del ef
    if forced["compiles"] != 0:
        forced["ok"] = f_ok = False

    # ---- Poisson replays: same arrivals, each pool -------------------- #
    # SLOs sized to this box's triage window: loose enough that shedding
    # and goodput track CAPACITY (the pools' difference), not interpret-
    # mode prefill latency; the batch mixture's KV lifetime (~3 blocks of
    # the 6-block fp pool each) is what saturates the fp side
    classes = [{"name": "interactive", "priority": 2,
                "ttft_slo_ms": 30000.0, "tbt_slo_ms": 5000.0},
               {"name": "batch", "priority": 0,
                "ttft_slo_ms": 120000.0, "tbt_slo_ms": 30000.0}]
    # spec=False: the replay's byte-checks compare BIT-IDENTICAL programs
    # (plain pipeline both sides — leg docstring); the spec x int8 gates
    # live above at their deterministic scale
    serving = {"classes": classes, "decode_slice": 4, "spec": False,
               "idle_wait_s": 0.002}
    mix = [WorkloadComponent("interactive", 3.0, [16, 24], [8, 12],
                             prefix_len=64),
           WorkloadComponent("batch", 2.0, [48], [160])]
    arrivals = PoissonLoadGen(rate=rate, mix=mix, vocab=vocab,
                              seed=seed).arrivals(duration=duration)
    if smoke:
        reps = 1
    results = {n: [] for n in engines}
    for r in range(reps):
        for name, e in engines.items():
            # each replay starts with a COLD radix tree (the router leg's
            # discipline): reps stay comparable and the byte-checks below
            # re-derive the same cache state the replay built
            _clear_prefix_caches([e])
            fe = e.serving_frontend(config=serving)
            c0 = e.compiles
            t0 = time.time()
            fe.start()
            handles = replay(fe, arrivals)
            fe.drain(timeout=3.0 * duration + 15.0)
            wall = time.time() - t0
            fe.close()
            compiles = e.compiles - c0
            rep = goodput_report(handles, wall)
            finished = [h for h in handles if h.status == "finished"]
            check = finished[:12] if smoke else finished[:32]
            equal = 0
            for i, h in enumerate(check):
                # plain pipeline both sides: bit-identical programs, the
                # comparison isolates orchestration (leg docstring)
                out = _serve_plain(e, 77_000 + 100 * r + i, h.prompt,
                                   len(h.tokens))
                equal += out == h.tokens
            ev = {k: v for k, v, _ in fe.stats.events()}
            out = {
                "leg": "kv_dtype", "pool": name, "rep": r, "rate": rate,
                "duration": duration, "arrivals": len(arrivals),
                "pool_blocks": blocks[name],
                "kv_bytes_per_token": bpt[name],
                "pool_dtype_bits": ev["serve/frontend/kv/pool_dtype_bits"],
                "preemptions": fe.stats.preemptions,
                "restores": fe.stats.restores,
                "streams_checked": len(check), "streams_equal": equal,
                "outputs_equal": equal == len(check),
                "compiles_during_timed": compiles,
                "forced_cycle": forced if (name == "int8" and r == 0)
                else None,
                **rep,
            }
            results[name].append(out)
            print(json.dumps(out), flush=True)
            if not out["outputs_equal"] or compiles != 0:
                ok = False
    for e in engines.values():
        _unforce_paged(e)

    # dtype-aware thresholds: int8 value bytes are 1/4 of an fp32 pool's
    # but only 1/2 of a bf16 pool's, and the padded f32 scale tiles ride
    # on top — a bf16 pool can NEVER meet the fp32-calibrated 2x/0.5x
    # bar (value bytes alone are exactly half), so the TPU leg gates at
    # the density its element width actually affords
    if on_tpu:
        min_blocks, max_bpt_frac = int(1.7 * blocks["fp"]), 0.58
    else:
        min_blocks, max_bpt_frac = 2 * blocks["fp"], 0.5
    capacity_gate = (blocks["int8"] >= min_blocks
                     and bpt["int8"] < max_bpt_frac * bpt["fp"])
    print(json.dumps({"gate": "kv_dtype_byte_tier", "ok": bool(
        cache_gate and spec_gate and forced["ok"]),
        "cache_hit_stream_equal": bool(cache_gate),
        "spec_stream_equal": bool(spec_gate),
        "forced_preempt_cycle": forced}), flush=True)
    print(json.dumps({"gate": "kv_dtype_rtol_tier", "ok": bool(rtol_gate),
                      "rtol": 0.05}), flush=True)
    print(json.dumps({"gate": "kv_dtype_capacity", "ok": bool(capacity_gate),
                      "pool_blocks": blocks,
                      "kv_bytes_per_token": bpt}), flush=True)
    ok = ok and cache_gate and spec_gate and forced["ok"] and rtol_gate \
        and capacity_gate
    if not smoke:
        # the RESIDENCY gate (compute-independent capacity fact): the fp
        # pool cannot hold this workload's KV working set at the shared
        # byte budget — it preempt-churns every rep — while the int8
        # pool's ~3.5x block density holds it RESIDENT (zero preemptions)
        fp_pressured = all(x["preemptions"] >= 1 for x in results["fp"])
        int8_resident = all(x["preemptions"] == 0 for x in results["int8"])
        gate = fp_pressured and int8_resident
        print(json.dumps({"gate": "kv_dtype_residency", "ok": bool(gate),
                          "fp_preemptions": [x["preemptions"]
                                             for x in results["fp"]],
                          "int8_preemptions": [x["preemptions"]
                                               for x in results["int8"]]}),
              flush=True)
        ok = ok and gate
        # goodput-under-SLO: gated in the HBM-bound regime (TPU) only; on
        # CPU interpret the walls measure dequant/scheduling artifacts of
        # the harness, not the serving stack (see the leg docstring)
        med = {n: float(np.median([x["goodput_tokens_per_sec"]
                                   for x in results[n]])) for n in engines}
        xgate = med["int8"] >= med["fp"]
        print(json.dumps({"gate": "kv_dtype_goodput_vs_fp",
                          "ok": bool(xgate) if on_tpu else None,
                          "gated": bool(on_tpu),
                          "median_goodput": med, "reps": reps}), flush=True)
        if on_tpu:
            ok = ok and xgate
    return ok


def _force_paged(engine):
    """Disable the packed pure-prefill fast path on one engine: a prefix-
    cache hit turns a from-zero prefill into a continuation, which ALWAYS
    takes the paged path, while a cold prompt takes the packed path — and
    the two kernels carry a benign per-path numerical variance (see
    run_shared_prefix). Holding the kernel path constant across every
    replica AND the direct-reference runs makes the router's byte-equality
    gate test exactly what routing changes: WHERE requests run and which KV
    pages back them."""
    orig = engine.scheduler.schedule_pass

    def no_fast_path():
        b = orig()
        if b is not None:
            b.pure_prefill = False
        return b

    engine.scheduler.schedule_pass = no_fast_path


def _unforce_paged(engine):
    # drop the instance attr (lookup falls back to the class method): the
    # wrapper's closure holds a bound method of the scheduler — a reference
    # cycle that would keep the engine's device KV pool alive until gc
    try:
        del engine.scheduler.schedule_pass
    except AttributeError:
        pass


def _clear_prefix_caches(engines):
    """Evict every cached page (all sequences are flushed between replays,
    so the whole tree is refcount-1) — each policy replay starts COLD, and
    the eviction deltas empty any registered router index."""
    for e in engines:
        pc = e.prefix_cache
        while pc is not None and pc.cached_blocks:
            if pc.evict(pc.cached_blocks) == 0:
                break


def _attribution_gate(handles):
    """SLO-miss attribution gate (docs/OBSERVABILITY.md): every finished
    request's phase ledger must TILE arrival..last-emission — its stints
    sum to the client-measured latency (TTFT + Σ TBT) within the shared
    ``attribution_epsilon`` (the SAME tolerance the serve/slo
    attr_consistent stat applies). Gated over ALL finished requests (a
    superset of the SLO-missed ones the acceptance bar names). Returns
    (checked, bad_records)."""
    from deepspeed_tpu.inference.v2.serving.frontend import \
        attribution_epsilon
    checked = 0
    bad = []
    for h in handles:
        if h.status != "finished":
            continue
        attr = h.attribution()
        client = attr["client_s"]
        if client is None:
            continue
        checked += 1
        if abs(attr["total_s"] - client) > attribution_epsilon(client):
            bad.append({"uid": h.uid, "migrated": h.migrated,
                        "client_s": round(client, 4),
                        "ledger_s": round(attr["total_s"], 4),
                        "phases": {k: round(v, 4)
                                   for k, v in attr["phases"].items()}})
    return checked, bad


def _migrated_chain_gate(handles):
    """Failover chain gate: every migrated FINISHED request must carry a
    ``migration`` stint on its ledger, and (tracing on) its flow chain —
    spans sharing its trace_id — must span >= 2 lanes including the
    health lane's migrate span: the hops survive the replica death.
    Returns (migrated_finished, ok_count, bad_uids)."""
    from deepspeed_tpu.monitor.trace import tracer as _tr
    migrated = [h for h in handles if h.status == "finished" and h.migrated]
    if not migrated:
        return 0, 0, []
    by_tid = {}
    if _tr.enabled:
        for kind, name, _t0, _t1, lane, args in _tr.iter_records():
            if kind == "X" and args and "trace_id" in args:
                by_tid.setdefault(args["trace_id"], set()).add((lane, name))
    ok = 0
    bad = []
    for h in migrated:
        good = any(p == "migration" for p, _, _ in h.timeline())
        if good and _tr.enabled:
            recs = by_tid.get(h.trace_id, set())
            good = (len({lane for lane, _ in recs}) >= 2
                    and any(n == "serve/health/migrate" for _, n in recs))
        if good:
            ok += 1
        else:
            bad.append(h.uid)
    return len(migrated), ok, bad


def _check_router_streams(engine, handles, limit, uid_base):
    """Byte-equality: finished router streams vs direct decode_pipeline
    runs of the same prompts on ``engine`` (same weights on every replica,
    forced-paged kernel path on both sides)."""
    finished = [h for h in handles if h.status == "finished"]
    check = finished[:limit]
    equal = 0
    for i, h in enumerate(check):
        uid = uid_base + i
        engine._put_nofetch([uid], [h.prompt])
        out = engine.decode_pipeline([uid]).run(len(h.tokens))
        engine.flush([uid])
        equal += [int(t) for t in out[0]] == h.tokens
    return len(check), equal


def run_router(on_tpu: bool, smoke: bool, seed: int = 0, reps: int = 3):
    """The multi-replica router leg (docs/SERVING.md "Multi-replica &
    disaggregation"), BENCH_r13. Two replicas of one model (identical
    weights, independent KV pools) behind a ``ServingRouter``; every
    timed replay is a seeded Poisson shared-prefix mixture, modes
    interleaved per rep, prefix caches evicted cold between replays.

    Leg A (routing): cache-aware vs round-robin placement on the SAME
    arrival stream, gating

      - computed prefill tokens: cache-aware <= 0.7x round-robin (the
        cluster pays each shared prefix ~once instead of once per replica),
      - goodput-under-SLO: cache-aware >= round-robin (medians over reps),
      - byte-equality: checked completed streams == direct single-frontend
        decode_pipeline runs of the same prompts,
      - zero engine compiles on EVERY replica during every timed replay.

    Leg B (disaggregation): 1 prefill + 1 decode replica vs the same two
    replicas colocated, same workload, gating >= 1 prefill->decode handoff
    per rep (KV byte-exactness is pinned below the router by
    tests/unit/test_serving_router.py and implied by the stream gate here)
    and decode TBT p95 <= the colocated leg's (medians over reps) — the
    interference-removal claim disaggregation exists for.

    Smoke: one rep each at tiny sizes, correctness gates only."""
    from deepspeed_tpu.inference.v2.serving import (PoissonLoadGen,
                                                    ServingCluster,
                                                    ServingRouter,
                                                    WorkloadComponent,
                                                    goodput_report, replay)
    classes = [{"name": "interactive", "priority": 2,
                "ttft_slo_ms": 4000.0, "tbt_slo_ms": 600.0},
               {"name": "batch", "priority": 0,
                "ttft_slo_ms": 60000.0, "tbt_slo_ms": 20000.0}]
    serving = {"classes": classes, "decode_slice": 4, "idle_wait_s": 0.002}
    engines = []
    for _ in range(2):
        # pool sized so CONCENTRATED caching fits (4 rows x 12 blocks live
        # + ~5 prefixes x 9 blocks cached) but caching every prefix on
        # every replica does NOT: round-robin duplicates all 8 prefixes per
        # replica (72 blocks) and pays evictions for it — the
        # cluster-cache-capacity half of the cache-aware argument
        e, vocab = build_frontend_engine(on_tpu, pool_blocks=112, ctx=192,
                                         prefix_cache=True)
        _force_paged(e)
        engines.append(e)
    if smoke:
        reps = 1
    ok = True
    results = {}

    def replay_once(router_cfg, roles, arrivals, duration):
        _clear_prefix_caches(engines)
        cluster = ServingCluster(engines, serving=serving, roles=roles)
        rt = ServingRouter(cluster, router_cfg)
        prefill0 = [e.scheduler.prefill_tokens_completed for e in engines]
        c0 = [e.compiles for e in engines]
        t0 = time.time()
        rt.start()
        handles = replay(rt, arrivals)
        rt.drain(timeout=3.0 * duration + 10.0)
        wall = time.time() - t0
        rt.close()           # past-deadline stragglers cancel: 0 goodput
        compiles = [e.compiles - c for e, c in zip(engines, c0)]
        prefill = sum(e.scheduler.prefill_tokens_completed - p
                      for e, p in zip(engines, prefill0))
        tbts = [g for h in handles if h.status == "finished"
                for g in h.tbt_ms]
        return {
            "handles": handles, "wall": wall, "compiles": compiles,
            "prefill_tokens": prefill,
            "tbt_p95_ms": (round(float(np.percentile(
                np.asarray(tbts, np.float64), 95)), 2) if tbts else None),
            "routed": dict(rt.stats.routed),
            "cache_hit_blocks": rt.stats.cache_hit_blocks,
            "rebalances": rt.stats.rebalances,
            "handoffs": rt.stats.handoffs,
            "handoff_bytes": rt.stats.handoff_bytes,
            "report": goodput_report(handles, wall),
        }

    # ---- leg A: cache-aware vs round-robin routing ------------------- #
    # 8 equal shared-prefix components: enough groups that hash affinity
    # spreads them across 2 replicas, so stickiness does not congest one
    # side. balance=16 lets a group SPILL once its sticky replica runs ~8
    # requests deeper than the other (the cold side then pays the prefix
    # once and the group balances warm-vs-warm) — the stickiness/balance
    # tradeoff the knob exists for.
    rate, duration = (8.0, 3.0) if smoke else (6.0, 9.0)
    mix = [WorkloadComponent("interactive" if i < 6 else "batch",
                             1.0, [4], [8, 16] if i < 6 else [24],
                             prefix_len=144) for i in range(8)]
    arrivals = PoissonLoadGen(rate=rate, mix=mix, vocab=vocab,
                              seed=seed).arrivals(duration=duration)
    policies = ["cache_aware"] if smoke else ["cache_aware", "round_robin"]
    routing = {p: [] for p in policies}
    # one untimed warm replay (a short slice of the stream): absorbs every
    # first-serving lazy cost so rep 0 measures what reps 1-2 measure
    warm = arrivals[:min(8, len(arrivals))]
    replay_once({"policy": "round_robin"}, ["serve", "serve"], warm, 1.0)
    for r in range(reps):
        for policy in policies:
            res = replay_once({"policy": policy, "balance": 16.0},
                              ["serve", "serve"], arrivals, duration)
            checked, equal = _check_router_streams(
                engines[0], res["handles"], 12 if smoke else 32, 170_000)
            a_checked, a_bad = _attribution_gate(res["handles"])
            out = {
                "leg": "router", "mode": policy, "rep": r, "rate": rate,
                "duration": duration, "arrivals": len(arrivals),
                "prefill_tokens": res["prefill_tokens"],
                "routed": res["routed"],
                "cache_hit_blocks": res["cache_hit_blocks"],
                "rebalances": res["rebalances"],
                "streams_checked": checked, "streams_equal": equal,
                "outputs_equal": equal == checked,
                "attribution_checked": a_checked,
                "attribution_bad": a_bad[:4],
                "attribution_ok": a_checked > 0 and not a_bad,
                "compiles_during_timed": res["compiles"],
                **res["report"],
            }
            routing[policy].append(out)
            print(json.dumps(out), flush=True)
            if not out["outputs_equal"] or any(c != 0 for c in
                                               res["compiles"]) \
                    or not out["attribution_ok"]:
                ok = False
    results["routing"] = routing

    # ---- leg B: disaggregated vs colocated --------------------------- #
    rate, duration = (5.0, 2.5) if smoke else (8.0, 6.0)
    mix = [WorkloadComponent("interactive", 3.0, [96], [12, 16]),
           WorkloadComponent("batch", 1.0, [96], [24])]
    arrivals = PoissonLoadGen(rate=rate, mix=mix, vocab=vocab,
                              seed=seed + 1).arrivals(duration=duration)
    topos = {"disaggregated": (["prefill", "decode"],
                               {"topology": "disaggregated"}),
             "colocated": (["serve", "serve"],
                           {"policy": "round_robin"})}
    disagg = {t: [] for t in topos}
    for r in range(reps):
        for topo, (roles, cfg) in topos.items():
            res = replay_once(cfg, roles, arrivals, duration)
            # the decode engine under disaggregation is engines[1]; direct
            # references run there so prefill+decode share one engine
            checked, equal = _check_router_streams(
                engines[1], res["handles"], 8 if smoke else 24, 180_000)
            a_checked, a_bad = _attribution_gate(res["handles"])
            out = {
                "leg": "router_disagg", "mode": topo, "rep": r,
                "rate": rate, "duration": duration,
                "arrivals": len(arrivals),
                "handoffs": res["handoffs"],
                "handoff_bytes": res["handoff_bytes"],
                "tbt_p95_ms": res["tbt_p95_ms"],
                "streams_checked": checked, "streams_equal": equal,
                "outputs_equal": equal == checked,
                "attribution_checked": a_checked,
                "attribution_bad": a_bad[:4],
                "attribution_ok": a_checked > 0 and not a_bad,
                "compiles_during_timed": res["compiles"],
                **res["report"],
            }
            disagg[topo].append(out)
            print(json.dumps(out), flush=True)
            if not out["outputs_equal"] or any(c != 0 for c in
                                               res["compiles"]) \
                    or not out["attribution_ok"]:
                ok = False
            if topo == "disaggregated" and res["handoffs"] < 1:
                ok = False
    results["disagg"] = disagg

    for e in engines:
        _unforce_paged(e)

    # ---- gates -------------------------------------------------------- #
    if not smoke:
        med_prefill = {p: float(np.median([x["prefill_tokens"]
                                           for x in routing[p]]))
                       for p in policies}
        med_goodput = {p: float(np.median([x["goodput_tokens_per_sec"]
                                           for x in routing[p]]))
                       for p in policies}
        reduction = 1.0 - med_prefill["cache_aware"] \
            / max(1.0, med_prefill["round_robin"])
        gate_prefill = reduction >= 0.30
        gate_goodput = (med_goodput["cache_aware"]
                        >= med_goodput["round_robin"])
        print(json.dumps({"gate": "cache_aware_prefill_reduction",
                          "ok": bool(gate_prefill),
                          "reduction": round(reduction, 3),
                          "median_prefill_tokens": med_prefill,
                          "bar": 0.30}), flush=True)
        print(json.dumps({"gate": "cache_aware_goodput",
                          "ok": bool(gate_goodput),
                          "median_goodput": med_goodput}), flush=True)
        med_tbt = {t: float(np.median([x["tbt_p95_ms"] for x in disagg[t]
                                       if x["tbt_p95_ms"] is not None]))
                   for t in topos}
        gate_tbt = med_tbt["disaggregated"] <= med_tbt["colocated"]
        print(json.dumps({"gate": "disagg_decode_tbt",
                          "ok": bool(gate_tbt),
                          "median_tbt_p95_ms": med_tbt}), flush=True)
        ok = ok and gate_prefill and gate_goodput and gate_tbt
    handoff_reps = [x["handoffs"] for x in disagg["disaggregated"]]
    print(json.dumps({"gate": "prefill_decode_handoff",
                      "ok": all(h >= 1 for h in handoff_reps),
                      "handoffs_per_rep": handoff_reps}), flush=True)
    return ok


def _check_chaos_streams(engine, handles, limit, uid_base):
    """Byte-equality under chaos: finished streams (MIGRATED ones first —
    they are the point) vs direct decode_pipeline runs of the same prompts
    on a forced-paged engine. Returns (checked, equal, migrated_checked)."""
    finished = [h for h in handles if h.status == "finished"]
    finished.sort(key=lambda h: -h.migrated)
    check = finished[:limit]
    equal = migrated = 0
    for i, h in enumerate(check):
        uid = uid_base + i
        engine._put_nofetch([uid], [h.prompt])
        out = engine.decode_pipeline([uid]).run(len(h.tokens))
        engine.flush([uid])
        if [int(t) for t in out[0]] == h.tokens:
            equal += 1
            migrated += bool(h.migrated)
    return len(check), equal, migrated


def locksan_gate(leg: str) -> bool:
    """Runtime lock-order gate for legs run under ``DSTPU_LOCKSAN=1``
    (docs/THREADLINT.md): ZERO observed acquisition cycles, and every edge
    the sanitizer recorded must be predicted by threadlint's static lock
    graph (static >= observed — the analyzer is never blind to an ordering
    the runtime actually took). No-op (and passing) when the sanitizer is
    not armed, so the legs behave identically outside the smoke harness.
    Blocking-under-lock events are REPORTED but don't flip the gate — the
    static rule (TL002) owns that class, with annotations for the
    deliberate handoffs."""
    from deepspeed_tpu.utils import locksan
    if not locksan.enabled():
        return True
    from deepspeed_tpu.tools.threadlint.config import (ThreadLintConfig,
                                                       find_config)
    from deepspeed_tpu.tools.threadlint.model import static_lock_graph
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg_path = find_config(root)
    config = ThreadLintConfig.load(cfg_path) if cfg_path         else ThreadLintConfig()
    static = set(static_lock_graph([os.path.join(root, "deepspeed_tpu")],
                                   config))
    rep = locksan.report()
    unexplained = sorted(locksan.check_static(static))
    out = {"locksan_leg": leg,
           "observed_edges": sorted(locksan.edges()),
           "cycles": rep["cycles"],
           "unexplained_edges": unexplained,
           "blocking_under_lock": rep["blocking"]}
    print(json.dumps(out), flush=True)
    return not rep["cycles"] and not unexplained


def run_chaos(on_tpu: bool, smoke: bool, seed: int = 0, reps: int = 3):
    """The fault-tolerance leg (docs/SERVING.md "Failure semantics"),
    BENCH_r14: N colocated replicas behind a health-monitored
    ``ServingRouter`` replay a seeded Poisson workload while fault
    injection KILLS one replica's serving loop (``serve.engine_step.<r>``
    action=raise) and STALLS another's (action=stall past the down
    deadline) mid-run. The monitor detects (liveness + progress-stall),
    fences, migrates every in-flight stream, and auto-rejoins each replica
    once its thread exits — re-warming off the hot path.

    Gates, every rep:

      - every checked non-shed stream byte-identical to an uninterrupted
        direct decode_pipeline reference (forced-paged kernel discipline on
        every engine AND the references, so migration re-prefill is
        bit-equal — the gate tests exactly what failover changes: WHERE
        the stream ran);
      - both injected faults fired AND were detected (>=1 liveness down,
        >=1 stall down), >=1 request migrated, the faulted replicas
        rejoined and ended HEALTHY;
      - ZERO engine compiles on every replica across the chaos replay —
        including each rejoin's re-warm;
      - allocator free blocks back to baseline on every replica after the
        replay (survivors AND rejoined corpses);
      - with ``DSTPU_TRACE`` set, the injected raise leaves a
        flight-recorder crash dump (``trace_check --expect-crash`` in
        bench_smoke validates it).

    Full runs additionally gate goodput-under-SLO against an N-1-replica
    NO-FAULT floor replayed on the same engines (median over reps):
    losing-then-healing one replica must degrade gracefully toward the
    floor, not collapse. Smoke: 2 replicas, one kill + one stall, one rep,
    correctness gates only (<60 s warm)."""
    from deepspeed_tpu.inference.v2.serving import (PoissonLoadGen,
                                                    ServingCluster,
                                                    ServingRouter,
                                                    WorkloadComponent,
                                                    goodput_report, replay)
    from deepspeed_tpu.utils import fault_injection as fi
    n_replicas = 2 if smoke else 3
    engines = []
    for _ in range(n_replicas):
        e, vocab = build_frontend_engine(on_tpu, pool_blocks=20, ctx=192)
        _force_paged(e)
        engines.append(e)
    health = {"enabled": True, "interval_s": 0.02,
              "suspect_after_s": 0.4, "down_after_s": 1.0,
              "fence_join_s": 0.5, "auto_rejoin": True}
    # SLOs sized to this box's detection + migration window (the
    # tight-interactive triage regime is the --frontend leg's subject;
    # here goodput must track CAPACITY so the N-1 floor comparison
    # measures graceful degradation, not SLO-accounting artifacts)
    classes = [{"name": "interactive", "priority": 2,
                "ttft_slo_ms": 5000.0, "tbt_slo_ms": 1500.0},
               {"name": "batch", "priority": 0,
                "ttft_slo_ms": 60000.0, "tbt_slo_ms": 20000.0}]
    serving = {"classes": classes, "decode_slice": 4,
               "idle_wait_s": 0.002}
    rate, duration = (8.0, 3.5) if smoke else (20.0, 12.0)
    mix = [WorkloadComponent("interactive", 4.0, [16, 32], [8, 16, 24]),
           WorkloadComponent("batch", 1.0, [48], [64])]
    arrivals = PoissonLoadGen(rate=rate, mix=mix, vocab=vocab,
                              seed=seed).arrivals(duration=duration)
    if smoke:
        reps = 1
    # one kill + one stall, aimed at distinct replicas mid-run; `at` counts
    # the TARGET replica's own loop iterations (replica-scoped sites), so
    # both fire early enough to leave room for detection + rejoin
    stall_s = 1.5 if smoke else 2.0
    plan = (f"serve.engine_step.r0:at=25:action=raise;"
            f"serve.engine_step.r1:at=60:action=stall:delay_s={stall_s}")

    def replay_once(engine_set, faults):
        frees = [e.free_blocks for e in engine_set]
        cluster = ServingCluster(engine_set, serving=serving)
        rt = ServingRouter(cluster, {"policy": "round_robin",
                                     "health": health})
        c0 = [e.compiles for e in engine_set]
        if faults:
            fi.install(fi.parse_plan(faults, seed=seed))
        try:
            t0 = time.time()
            rt.start()
            handles = replay(rt, arrivals)
            rt.drain(timeout=3.0 * duration + 20.0)
            rt.health.wait_all_healthy(30.0)
            wall = time.time() - t0
            fired = list(fi.active().fired) if faults else []
        finally:
            fi.clear()
        hs = rt.health.stats
        rt.close()           # past-deadline stragglers cancel: 0 goodput
        return {
            "handles": handles, "wall": wall, "fired": fired,
            "compiles": [e.compiles - c for e, c in zip(engine_set, c0)],
            "free_ok": [e.free_blocks == f
                        for e, f in zip(engine_set, frees)],
            "health": hs, "all_healthy": rt.health.all_healthy(),
            "report": goodput_report(handles, wall),
        }

    # untimed warm replay: absorbs every first-serving lazy cost so the
    # zero-compile gate tests the chaos machinery, not cold starts
    replay_once(engines, None)

    ok = True
    chaos_reps, floor_reps = [], []
    trace_dir = os.environ.get("DSTPU_TRACE", "")
    for r in range(reps):
        res = replay_once(engines, plan)
        hs = res["health"]
        checked, equal, migrated_checked = _check_chaos_streams(
            engines[-1], res["handles"], 16 if smoke else 40, 200_000)
        a_checked, a_bad = _attribution_gate(res["handles"])
        m_total, m_ok, m_bad = _migrated_chain_gate(res["handles"])
        crash_dump = (os.path.exists(os.path.join(
            trace_dir, "trace_crash.json")) if trace_dir else None)
        out = {
            "leg": "chaos", "rep": r, "replicas": n_replicas,
            "rate": rate, "duration": duration, "arrivals": len(arrivals),
            "faults_fired": [f"{site}@{hit}:{act}"
                             for site, hit, act in res["fired"]],
            "liveness_downs": hs.liveness_downs,
            "stall_downs": hs.stall_downs,
            "migrations": hs.migrations,
            "salvaged": hs.salvaged,
            "reprefilled": hs.reprefilled,
            "migration_sheds": hs.migration_sheds,
            "rejoins": hs.rejoins,
            "detect_p95_ms": (round(float(np.percentile(
                np.asarray(hs.detect_ms, np.float64), 95)), 1)
                if hs.detect_ms else None),
            "all_healthy_after": res["all_healthy"],
            "streams_checked": checked, "streams_equal": equal,
            "migrated_streams_checked": migrated_checked,
            "outputs_equal": equal == checked,
            "attribution_checked": a_checked,
            "attribution_bad": a_bad[:4],
            "attribution_ok": a_checked > 0 and not a_bad,
            "migrated_finished": m_total,
            "migrated_chains_ok": m_ok,
            "migrated_chains_bad": m_bad[:8],
            "compiles_during_timed": res["compiles"],
            "allocator_at_baseline": res["free_ok"],
            "flight_recorder_dump": crash_dump,
            **res["report"],
        }
        chaos_reps.append(out)
        print(json.dumps(out), flush=True)
        if not out["outputs_equal"] or any(c != 0 for c in res["compiles"]) \
                or not all(res["free_ok"]) or not res["all_healthy"] \
                or hs.liveness_downs < 1 or hs.stall_downs < 1 \
                or hs.migrations < 1 or hs.rejoins < 2 \
                or not out["attribution_ok"] or m_ok < m_total:
            ok = False
        if crash_dump is False:
            ok = False
        if not smoke:
            floor = replay_once(engines[:-1], None)
            fout = {"leg": "chaos_floor", "rep": r,
                    "replicas": n_replicas - 1,
                    "compiles_during_timed": floor["compiles"],
                    **floor["report"]}
            floor_reps.append(fout)
            print(json.dumps(fout), flush=True)
            if any(c != 0 for c in floor["compiles"]):
                ok = False
    if not smoke:
        med_chaos = float(np.median([x["goodput_tokens_per_sec"]
                                     for x in chaos_reps]))
        med_floor = float(np.median([x["goodput_tokens_per_sec"]
                                     for x in floor_reps]))
        gate = med_chaos >= 0.7 * med_floor and med_chaos > 0
        print(json.dumps({"gate": "chaos_goodput_floor", "ok": bool(gate),
                          "median_goodput_chaos": med_chaos,
                          "median_goodput_n_minus_1_floor": med_floor,
                          "bar": "chaos >= 0.7 x floor"}), flush=True)
        ok = ok and gate
    return ok


def run_serving_trace_overhead(on_tpu: bool, smoke: bool, seed: int = 0,
                               reps: int = 5):
    """Serving-side tracer/attribution overhead leg (the
    ``train_bench.py --trace-overhead`` discipline applied to the router
    stack), BENCH_r16. The SAME seeded burst workload (every arrival
    submitted immediately — the wall time is serving work, not open-loop
    sleeps) replays against a 2-replica cache-aware router with flow
    tracing + phase attribution ON vs OFF, orders ALTERNATED per rep.

    Gates, every rep:

      - byte-identical streams: each request finished on both sides
        produced the same tokens (tracing/attribution must not perturb
        placement-independent greedy serving);
      - zero engine compiles on every replica in every timed replay;
      - attribution consistency on the ON side (ledger sums to the
        client-measured latency per finished request).

    Full runs additionally gate: median per-rep wall ratio (ON/OFF)
    <= 1.02 — flow tracing plus the ledger costs at most 2% of serving
    wall. Smoke: one rep, correctness gates only."""
    from deepspeed_tpu.inference.v2.serving import (PoissonLoadGen,
                                                    ServingCluster,
                                                    ServingRouter,
                                                    WorkloadComponent,
                                                    replay)
    from deepspeed_tpu.monitor.trace import tracer as _tr
    classes = [{"name": "interactive", "priority": 2,
                "ttft_slo_ms": 60000.0, "tbt_slo_ms": 20000.0},
               {"name": "batch", "priority": 0,
                "ttft_slo_ms": 60000.0, "tbt_slo_ms": 20000.0}]
    engines = []
    for _ in range(2):
        e, vocab = build_frontend_engine(on_tpu, pool_blocks=112, ctx=192,
                                         prefix_cache=True)
        _force_paged(e)
        engines.append(e)
    n_arrivals = 16 if smoke else 48
    mix = [WorkloadComponent("interactive", 3.0, [16, 32], [8, 16],
                             prefix_len=64),
           WorkloadComponent("batch", 1.0, [32], [24])]
    arrivals = PoissonLoadGen(rate=8.0, mix=mix, vocab=vocab,
                              seed=seed).arrivals(n=n_arrivals)
    if smoke:
        reps = 1

    def replay_once(attribution: bool):
        _clear_prefix_caches(engines)
        serving = {"classes": classes, "decode_slice": 4,
                   "idle_wait_s": 0.002, "attribution": attribution}
        cluster = ServingCluster(engines, serving=serving)
        rt = ServingRouter(cluster, {"policy": "cache_aware",
                                     "balance": 16.0})
        c0 = [e.compiles for e in engines]
        t0 = time.perf_counter()
        rt.start()
        handles = replay(rt, arrivals, speed=1e9)   # burst: no pacing sleeps
        rt.drain(timeout=120.0)
        wall = time.perf_counter() - t0
        rt.close()
        return {"handles": handles, "wall": wall,
                "compiles": [e.compiles - c for e, c in zip(engines, c0)]}

    was_enabled = _tr.enabled        # $DSTPU_TRACE may have armed it
    _tr.enabled = False
    replay_once(False)               # untimed warm: lazy costs absorbed
    ok = True
    ratios = []
    reps_out = []
    for r in range(reps):
        order = ("on", "off") if r % 2 == 0 else ("off", "on")
        res = {}
        for side in order:
            if side == "on":
                _tr.configure(enabled=True)
            else:
                _tr.enabled = False
            res[side] = replay_once(attribution=(side == "on"))
            _tr.enabled = False
        checked = equal = 0
        for a, b in zip(res["on"]["handles"], res["off"]["handles"]):
            if a.status == "finished" and b.status == "finished":
                checked += 1
                equal += a.tokens == b.tokens
        a_checked, a_bad = _attribution_gate(res["on"]["handles"])
        ratio = res["on"]["wall"] / res["off"]["wall"]
        ratios.append(ratio)
        out = {
            "leg": "serving_trace_overhead", "rep": r, "order": list(order),
            "arrivals": len(arrivals),
            "wall_on_s": round(res["on"]["wall"], 4),
            "wall_off_s": round(res["off"]["wall"], 4),
            "ratio": round(ratio, 4),
            "streams_checked": checked, "streams_equal": equal,
            "outputs_equal": checked == equal and checked >= int(
                0.9 * len(arrivals)),
            "attribution_checked": a_checked,
            "attribution_ok": a_checked > 0 and not a_bad,
            "compiles_during_timed": [res[s]["compiles"] for s in order],
        }
        reps_out.append(out)
        print(json.dumps(out), flush=True)
        if not out["outputs_equal"] or not out["attribution_ok"] \
                or any(c != 0 for side in ("on", "off")
                       for c in res[side]["compiles"]):
            ok = False
    _tr.enabled = was_enabled
    for e in engines:
        _unforce_paged(e)
    med = float(np.median(ratios))
    gate = {"gate": "serving_trace_overhead",
            "median_ratio": round(med, 4), "ratios_per_rep":
            [round(x, 4) for x in ratios], "bar": 1.02,
            "enforced": not smoke,
            "ok": bool(smoke or med <= 1.02)}
    print(json.dumps(gate), flush=True)
    if not smoke:
        ok = ok and med <= 1.02
    return ok


def _splitk_op_microbench(on_tpu: bool, splits: int, iters: int = 30):
    """Op-level split-K point: the paged decode attention op alone, split=1
    vs split=S, on the path this box actually runs (TPU: Pallas kernel;
    CPU: the page-granular XLA scan — split=1 walks all NC pages
    sequentially, split=S walks ceil(NC/S) wider steps, so the win is the
    scan-iteration overhead the splits amortise). Small batch x long ctx x
    the bench model's head_dim — the regime the engine leg serves."""
    import jax
    import jax.numpy as jnp
    from functools import partial
    from deepspeed_tpu.ops.pallas.paged_splitk import (
        paged_decode_attention_xla)
    S, H, HKV, D, bs, NC = 4, 4, 2, 16, 16, 64     # ctx 1024/seq
    rng = np.random.RandomState(0)
    kv = jnp.asarray(rng.randn(S * NC + 1, 2, HKV, bs, D)
                     .astype(np.float32))
    q = jnp.asarray(rng.randn(S, H, D).astype(np.float32))
    bt = jnp.asarray(np.arange(S * NC).reshape(S, NC) + 1, jnp.int32)
    ctx = jnp.full((S,), NC * bs, jnp.int32)

    def timed(n_splits):
        # the XLA fallback at both points: the ONLY difference between the
        # legs is the split count, so the ratio is pure split-K (comparing
        # against the chunk-serial Pallas kernel here would conflate the
        # win with CPU interpret-mode overhead)
        f = jax.jit(partial(paged_decode_attention_xla,
                            n_splits=n_splits))
        f(q, kv, bt, ctx).block_until_ready()      # compile outside timing
        t0 = time.perf_counter()
        for _ in range(iters):
            out = f(q, kv, bt, ctx)
        out.block_until_ready()
        return (time.perf_counter() - t0) / iters

    t1, ts = timed(1), timed(splits)
    return {"op_ctx": NC * bs, "op_seqs": S, "op_head_dim": D,
            "op_split1_us": round(1e6 * t1, 1),
            "op_splitS_us": round(1e6 * ts, 1),
            "op_speedup": round(t1 / ts, 2)}


def run_long_context(on_tpu: bool, smoke: bool, seqs=None, prompt=None,
                     gen=None, splits: int = 4, reps: int = 3):
    """Flash-decoding long-context leg (docs/SERVING.md "Attention
    kernels"), BENCH_r17: few sequences x long context — the split-K
    regime, where grid parallelism over sequences alone leaves the chip
    (or, on CPU, the scan) serial over each row's pages. ONE warmed engine
    with the pow2 split ladder ``[1..splits]`` serves the same seeded
    prompts through the DecodePipeline twice per rep: pinned to the
    chunk-serial split=1 program (``attn_rung_override``) and under auto
    rung selection (climbs the ladder as live ctx crosses
    ``min_ctx_per_split`` multiples).

    Gates: (a) token streams IDENTICAL between split=1 and the ladder —
    same forward math, different grid decomposition (the op-level LSE-merge
    equality tests put the two paths within float rtol; greedy argmax over
    the bench model's logits is byte-stable across that); (b) zero timed
    compiles — every rung program came out of warmup(); (c) allocator back
    to baseline each rep; (d) the auto leg actually climbed the ladder
    (merged_steps > 0; otherwise the comparison is vacuous); (e) full runs
    only: the op-level point shows >= 1.3x split=S over split=1 on the
    measurable fallback path (CPU box: the XLA scan)."""
    seqs = seqs if seqs is not None else (2 if smoke else 3)
    prompt = prompt if prompt is not None else (96 if smoke else 384)
    gen = gen if gen is not None else (8 if smoke else 32)
    min_ctx = 16 if smoke else 64
    reps = 1 if smoke else reps
    engine, vocab = build_engine(
        on_tpu, seqs=seqs, prompt=prompt, gen=gen,
        warmup=True, warmup_bursts=False,
        extra_config={
            # small pages: the long ctx becomes MANY pages per row, the
            # regime where chunk-serial decode is scan-bound
            "kv_cache": {"block_size": 16},
            "attention": {"decode_splits": splits,
                          "min_ctx_per_split": min_ctx}})
    _force_paged(engine)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, vocab, size=(prompt,)).astype(np.int32)
               for _ in range(seqs)]
    uid_base = [60_000]

    def serve(rung):
        """One timed decode run at a pinned rung (None = auto ladder)."""
        engine.attn_rung_override = rung
        uid_base[0] += seqs
        uids = list(range(uid_base[0], uid_base[0] + seqs))
        engine._put_nofetch(uids, prompts)
        pipe = engine.decode_pipeline(uids)
        t0 = time.time()
        out = pipe.run(gen)
        wall = time.time() - t0
        engine.flush(uids)
        engine.attn_rung_override = None
        return [list(map(int, row)) for row in out], wall

    # untimed: compile-free from here (warmup covered every rung)
    serve(1)
    serve(None)
    free0 = engine.free_blocks
    c0 = engine.compiles
    ok = True
    ladder = engine.attn_split_ladder
    for rep in range(reps):
        ref, wall1 = serve(1)
        engine.attn_stats.reset()
        got, walls = serve(None)
        s = engine.attn_stats
        out = {
            "leg": "long_context", "rep": rep, "seqs": seqs,
            "prompt": prompt, "gen": gen, "ladder": ladder,
            "min_ctx_per_split": min_ctx,
            "split1_tok_s": round(seqs * gen / wall1, 1),
            "ladder_tok_s": round(seqs * gen / walls, 1),
            "engine_speedup": round(wall1 / walls, 2),
            "outputs_equal": got == ref,
            "ladder_engaged": s.merged_steps > 0,
            "splits_per_select": round(s.splits_per_select, 2),
            "max_live_ctx": s.max_live_ctx,
            "compiles_during_timed_runs": engine.compiles - c0,
            "allocator_at_baseline": engine.free_blocks == free0,
        }
        print(json.dumps(out), flush=True)
        ok = ok and out["outputs_equal"] and out["ladder_engaged"] \
            and out["compiles_during_timed_runs"] == 0 \
            and out["allocator_at_baseline"]
    op = _splitk_op_microbench(on_tpu, splits,
                               iters=(10 if smoke else 30))
    gate_op = smoke or op["op_speedup"] >= 1.3
    print(json.dumps({"gate": "splitk_long_context",
                      "ok": bool(ok and gate_op), **op}), flush=True)
    return bool(ok and gate_op)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seqs", type=int, default=None,
                    help="concurrent sequences (default: 32; --spec leg: 4)")
    ap.add_argument("--prompt", type=int, default=None,
                    help="prompt tokens (default: 128; --spec leg: 48)")
    ap.add_argument("--gen", type=int, default=None,
                    help="greedy tokens per sequence (default: 64; the "
                         "--spec leg defaults to 128 so the loop regime "
                         "n-gram drafting rides can establish)")
    ap.add_argument("--rates", default="2,6")
    ap.add_argument("--duration", type=float, default=20.0)
    ap.add_argument("--int8", action="store_true",
                    help="weight-only int8 serving (quantization.weight_bits=8)")
    ap.add_argument("--modes", default="burst",
                    help="comma list of 'burst' (fused decode bursts) and/or "
                         "'mixed' (SplitFuse chunk+decode composition "
                         "through scheduler passes)")
    ap.add_argument("--burst", type=int, default=16,
                    help="fused decode tokens per host round trip (bigger "
                         "bursts trade admission latency for fewer round "
                         "trips)")
    ap.add_argument("--shared-prefix", action="store_true",
                    help="run the shared-prefix (prefix-cache) leg instead of "
                         "the load sweep: N requests sharing a long system "
                         "prompt, cache-on vs cache-off")
    ap.add_argument("--steady-state", action="store_true",
                    help="run the steady-state decode leg instead of the load "
                         "sweep: a fixed decode set through the pre-pipeline "
                         "per-token loop vs the async double-buffered "
                         "DecodePipeline, with a byte-identical-greedy gate")
    ap.add_argument("--frontend", action="store_true",
                    help="run the SLO-aware frontend leg: a seeded Poisson "
                         "mixed-priority workload against each preemption "
                         "policy (offload / recompute / reject-only) on one "
                         "warmed engine, gating byte-equality, zero timed "
                         "compiles and goodput-under-SLO")
    ap.add_argument("--router", action="store_true",
                    help="run the multi-replica router leg: 2 replicas "
                         "behind a ServingRouter on seeded shared-prefix "
                         "Poisson traffic — cache-aware vs round-robin "
                         "routing (prefill-token reduction + goodput), "
                         "disaggregated vs colocated prefill/decode "
                         "(handoffs + decode TBT), gating stream "
                         "byte-equality vs direct single-frontend runs and "
                         "zero steady-state compiles per replica")
    ap.add_argument("--chaos", action="store_true",
                    help="run the fault-tolerance leg: N replicas behind a "
                         "health-monitored router replay a seeded Poisson "
                         "workload while injected faults kill one serving "
                         "loop and stall another — gating byte-identical "
                         "non-shed streams vs uninterrupted references, "
                         "detection of both failure modes, zero compiles "
                         "incl. rejoin re-warm, allocator baseline on every "
                         "replica, and (full) goodput >= 0.7x an "
                         "N-1-replica no-fault floor")
    ap.add_argument("--lora", action="store_true",
                    help="run the multi-tenant LoRA leg: a seeded Poisson "
                         "mix drawing tenants from more registered adapters "
                         "than the adapter pool holds, served through the "
                         "grouped LoRA decode matmul — gating byte-identical "
                         "streams vs direct per-adapter runs, zero timed "
                         "compiles across adapter churn, allocator + adapter "
                         "pool at baseline every rep, and (full) goodput >= "
                         "1.5x a naive one-adapter-at-a-time baseline")
    ap.add_argument("--trace-overhead", action="store_true",
                    help="run the serving tracer/attribution overhead leg: "
                         "the same seeded burst router workload with flow "
                         "tracing + phase attribution ON vs OFF (orders "
                         "alternated per rep), gating byte-identical "
                         "streams, zero timed compiles, attribution "
                         "consistency, and (full) median overhead <= 2%")
    ap.add_argument("--spec", action="store_true",
                    help="run the speculative-decoding leg: spec-off "
                         "DecodePipeline vs draft-and-verify "
                         "SpecDecodePipeline on one warmed engine over "
                         "repetitive-text and natural-text workloads, "
                         "gating byte-identical greedy streams, zero timed "
                         "compiles across the (bucket, k) grid, allocator "
                         "baseline after reject-heavy runs, and the "
                         "repetitive-leg tok/s ratio")
    ap.add_argument("--long-context", action="store_true",
                    help="run the flash-decoding long-context leg: few "
                         "sequences x long ctx on ONE warmed engine with "
                         "the pow2 split ladder — split=1 (chunk-serial) "
                         "vs auto rung selection, gating identical token "
                         "streams, zero timed compiles, allocator "
                         "baseline, ladder engagement, and (full) the "
                         "op-level split-K point >= 1.3x on the "
                         "measurable fallback path (BENCH_r17)")
    ap.add_argument("--splits", type=int, default=4,
                    help="long-context leg: top rung of the pow2 split "
                         "ladder")
    ap.add_argument("--spec-k", type=int, default=15,
                    help="spec leg: max draft tokens per verify step (the "
                         "ladder dispatches pow2-minus-1 rungs up to it; "
                         "k+1 a power of two keeps the chunk kernel's "
                         "q-block whole)")
    ap.add_argument("--kv-dtype", default=None, choices=["int8"],
                    help="with --frontend: run the quantized-KV leg instead "
                         "— the same seeded Poisson workload against an "
                         "fp (bf16/f32) pool and an int8 pool sized from "
                         "ONE byte budget, both with prefix cache AND spec "
                         "decode on, gating byte-identical quantized "
                         "streams across cache/spec/preempt paths, zero "
                         "timed compiles, the bytes/token drop, and "
                         "goodput-under-SLO int8 >= fp (docs/SERVING.md "
                         "'Quantized KV')")
    ap.add_argument("--smoke", action="store_true",
                    help="frontend/spec legs: tiny sizes, correctness "
                         "gates only (<60 s; no throughput comparison)")
    ap.add_argument("--rate", type=float, default=None,
                    help="frontend leg: Poisson arrivals/sec (default: an "
                         "oversubscribing 36/s full, 10/s smoke)")
    ap.add_argument("--reps", type=int, default=None,
                    help="replays per mode/rep count (default: 3; the "
                         "trace-overhead leg defaults to 5, its smoke to 1)")
    ap.add_argument("--requests", type=int, default=16,
                    help="shared-prefix leg: number of requests")
    ap.add_argument("--prefix", type=int, default=256,
                    help="shared-prefix leg: shared system-prompt tokens")
    ap.add_argument("--tail", type=int, default=32,
                    help="shared-prefix leg: unique tail tokens per request")
    args = ap.parse_args()

    import jax
    on_tpu = jax.default_backend() not in ("cpu",)
    from deepspeed_tpu.utils.compile_cache import setup_compile_cache
    setup_compile_cache()
    # one shared default for every leg's rep count; the trace-overhead
    # leg overrides to its own 5-rep default below
    reps = args.reps if args.reps is not None else 3
    if args.spec:
        ok = run_spec(on_tpu, args.smoke, k=args.spec_k,
                      seqs=args.seqs if args.seqs is not None else 4,
                      prompt=args.prompt if args.prompt is not None else 48,
                      gen=args.gen if args.gen is not None else 128,
                      reps=reps)
        sys.exit(0 if ok else 1)
    if args.long_context:
        ok = run_long_context(on_tpu, args.smoke, seqs=args.seqs,
                              prompt=args.prompt, gen=args.gen,
                              splits=args.splits, reps=reps)
        sys.exit(0 if ok else 1)
    if args.gen is None:
        args.gen = 64
    if args.seqs is None:
        args.seqs = 32
    if args.prompt is None:
        args.prompt = 128
    if args.trace_overhead:
        ok = run_serving_trace_overhead(
            on_tpu, args.smoke,
            reps=args.reps if args.reps is not None else 5)
        sys.exit(0 if ok else 1)
    if args.lora:
        rate = args.rate or (8.0 if args.smoke else 16.0)
        dur = 3.0 if args.smoke else min(args.duration, 10.0)
        ok = run_lora(on_tpu, args.smoke, rate=rate, duration=dur, reps=reps)
        sys.exit(0 if ok else 1)
    if args.chaos:
        ok = run_chaos(on_tpu, args.smoke, reps=reps)
        ok = locksan_gate("chaos") and ok
        sys.exit(0 if ok else 1)
    if args.router:
        ok = run_router(on_tpu, args.smoke, reps=reps)
        ok = locksan_gate("router") and ok
        sys.exit(0 if ok else 1)
    if args.frontend:
        if args.kv_dtype == "int8":
            rate = args.rate or (8.0 if args.smoke else 14.0)
            dur = 3.0 if args.smoke else min(args.duration, 8.0)
            ok = run_kv_dtype(on_tpu, args.smoke, rate=rate, duration=dur,
                              reps=reps)
            sys.exit(0 if ok else 1)
        rate = args.rate or (10.0 if args.smoke else 36.0)
        dur = 4.0 if args.smoke else min(args.duration, 15.0)
        ok = run_frontend(on_tpu, args.smoke, rate=rate, duration=dur,
                          reps=reps)
        sys.exit(0 if ok else 1)
    if args.shared_prefix:
        out = run_shared_prefix(on_tpu, args.requests, args.prefix, args.tail,
                                gen=min(args.gen, 16))
        print(json.dumps(out), flush=True)
        if not out["outputs_equal"]:
            # the leg's correctness gate: cached-KV reuse must not change
            # greedy outputs — a divergence means corrupted page adoption
            sys.exit(1)
        return
    if args.steady_state:
        out = run_steady_state(on_tpu, args.seqs, args.prompt, args.gen)
        print(json.dumps(out), flush=True)
        if (not out["outputs_equal"] or not out["fetch_is_token_row"]
                or out["compiles_during_timed_runs"] != 0):
            # gates: pipelined orchestration must not change greedy outputs,
            # the per-step transfer must stay one token row, and warm in-grid
            # serving must never compile (a bucket-keying regression shows
            # up here before it shows up as a throughput mystery)
            sys.exit(1)
        return
    engine, vocab = build_engine(on_tpu, args.seqs, args.prompt, args.gen,
                                 burst=args.burst, int8=args.int8)
    rng = np.random.RandomState(0)
    # warm run compiles every pass shape (prefill, mixed, fused burst)
    run_load_point(engine, vocab, rate=50.0, seqs=args.seqs,
                   prompt=args.prompt, gen=max(8, args.gen // 4),
                   duration=8.0 if on_tpu else 2.0, rng=rng, burst=args.burst)
    modes = args.modes.split(",")
    bad = [m for m in modes if m not in ("burst", "mixed")]
    if bad:
        ap.error(f"unknown --modes entries {bad}; valid: burst, mixed")
    for rate in [float(r) for r in args.rates.split(",")]:
        for mode in modes:
            out = run_load_point(engine, vocab, rate, args.seqs, args.prompt,
                                 args.gen, args.duration, rng,
                                 burst=args.burst, mode=mode)
            print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
