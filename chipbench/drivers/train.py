"""Training: ``deepspeed_tpu.initialize(...).train_batch`` fed from an
iterator over seeded batches, steps back to back for the window.

A step is complete when its loss has been fetched. The loop keeps one step
in flight: it dispatches step ``k + 1`` and then fetches the loss of step
``k``. The window opens at one fetch and closes at the first fetch
``--seconds`` or more later, so the rate is whole steps over exactly the
time they took. ``--trace 2``: after the window has closed the loop goes on
for the cell's ``trace_seconds`` under the program's capture.

Correctness, before the window: ``engine.eval_loss`` on the first batch,
with the labels the plain reference itself predicts (its greedy token at
every position), against the reference's loss on the same tokens, labels
and initial weights. With the usual labels (the random ids themselves) the
loss is ``ln V + var/2`` whatever the layers compute, because the final
norm fixes the logits' scale; with the reference's own predictions as labels
it is ``logsumexp - max``, which a wrong layer moves by whole units.
"""

import itertools
import time

import numpy as np

from chipbench import models
from chipbench.harness import BenchError, Context, Outcome, annotate
from chipbench.reduce import stats
from chipbench.reference import decoder_ref
from chipbench.traffic import generator


def reference_check(ctx: Context, engine, batch) -> bool:
    import jax
    import jax.numpy as jnp
    cfg = ctx.config
    hp = models.reference_hp(cfg)
    engine.eval_loss(batch)                 # builds the state: initial weights
    params = engine.get_params()

    @jax.jit
    def predict(p, ids):
        w = models.reference_weights(p, cfg)

        def one(seq):
            logits = decoder_ref.forward_logits(w, seq, hp)[:-1]
            best = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            nll = jax.scipy.special.logsumexp(logits, axis=-1) \
                - jnp.max(logits, axis=-1)
            return jnp.concatenate([seq[:1], best]), jnp.mean(nll)

        labels, nll = jax.lax.map(one, ids)
        return labels, jnp.mean(nll)

    t0 = time.time()
    labels, want = predict(params, jnp.asarray(batch["input_ids"]))
    labels, want = np.asarray(labels), float(want)
    del params
    got = float(engine.eval_loss({"input_ids": batch["input_ids"],
                                  "labels": labels}))
    tol = float(cfg["check"]["tol_loss"])
    ok = bool(np.isfinite(got)) and abs(got - want) <= tol
    ctx.log(f"check: eval_loss {got:.5f} against the reference's {want:.5f} "
            f"on its own greedy labels (tol {tol}; reference took "
            f"{time.time() - t0:.1f} s): {'ok' if ok else 'FAILED'}")
    return ok


def run(ctx: Context) -> Outcome:
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu

    cfg, mix = ctx.config, ctx.traffic
    chips = len(ctx.devices)
    ds_config = cfg["train"]
    global_batch, seq = int(ds_config["train_batch_size"]), int(mix["seq_len"])
    if seq > (cfg.get("sliding_window") or seq):
        raise BenchError("sequences longer than the window are not trained")
    model = models.build_model(cfg, jnp.bfloat16, remat=True)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, config=ds_config, rngs=models.jax_key(ctx.seed))
    batches = generator.train_batches(mix, global_batch, ctx.seed,
                                      cfg["vocab_size"])
    correct = reference_check(ctx, engine, batches[0])

    feed = itertools.cycle(batches)
    t0 = time.time()
    pending = engine.train_batch(data_iter=feed)
    for _ in range(int(mix["warmup_steps"])):
        nxt = engine.train_batch(data_iter=feed)
        first = float(pending)
        pending = nxt
    ctx.log(f"warm-up: {mix['warmup_steps']} steps in {time.time() - t0:.1f} "
            f"s, first loss {first:.4f}")

    losses, ends, traced = [], [], []
    tracer, trace_at = ctx.tracer, 0.4 * ctx.seconds
    tracing_since = None
    t_start = time.perf_counter()
    window_start = time.time()
    while True:
        with annotate("harness dispatch"):
            nxt = engine.train_batch(data_iter=feed)
        with annotate("fetch loss"):
            losses.append(float(pending))
        pending = nxt
        now = time.perf_counter()
        ends.append(now)
        traced.append(tracer is not None and tracer.running)
        if tracer is not None:
            if tracing_since is None and now - t_start >= trace_at:
                tracer.start()
                tracing_since = time.perf_counter()
            elif tracing_since is not None and tracer.path is None \
                    and now - tracing_since >= tracer.seconds:
                tracer.stop()
        if now - t_start >= ctx.seconds:
            break
    t_end = ends[-1]
    if ctx.capture is not None:
        # the window is closed and its numbers come from t_start, ends and
        # losses above; the same loop goes on, now under the capture
        capture, captured = ctx.capture, []
        capture.prime()
        capture.start()
        t_c = last = time.perf_counter()
        while last - t_c < capture.seconds:
            with annotate("harness dispatch"):
                nxt = engine.train_batch(data_iter=feed)
            with annotate("fetch loss"):
                float(pending)
            pending = nxt
            now = time.perf_counter()
            captured.append(1e3 * (now - last))
            last = now
        capture.stop()
        ctx.log(f"--trace 2: {len(captured)} steps under the capture, median "
                f"{stats.median(captured):.2f} ms")
    float(pending)                          # the step in flight, not counted
    if tracer is not None and tracer.path is None:
        tracer.stop()
    steps = len(losses)
    rate = steps * global_batch * seq / (t_end - t_start) / chips
    step_ms = 1e3 * np.diff([t_start] + ends)
    finite = int(np.isfinite(losses).sum())
    peak = max(d.memory_stats()["peak_bytes_in_use"] for d in ctx.devices) \
        if ctx.on_chip else 0
    ctx.log(f"window: {steps} steps of {global_batch} x {seq} tokens in "
            f"{t_end - t_start:.3f} s on {chips} chip(s): {rate:.1f} "
            f"tokens/s/chip; step median {stats.median(step_ms):.2f} ms, p95 "
            f"{stats.percentile(step_ms, 95):.2f} ms; losses "
            f"{losses[0]:.4f} .. {losses[-1]:.4f}, {finite} finite")
    if any(traced):
        ctx.log(f"steps that ended under the profiler: {sum(traced)}, median "
                f"{stats.median([m for m, t in zip(step_ms, traced) if t]):.2f}"
                " ms")
    engine.destroy()
    return Outcome(
        correct=correct and finite == steps, attempted=steps,
        failed=steps - finite, window_start=window_start,
        end_to_end={"train_tok_s": rate},
        counters={"hbm_peak_gib": peak / 2**30,
                  "step_ms_median": stats.median(step_ms),
                  # the rate of an undisturbed step: in a traced run the
                  # window's own rate is lowered by the profiler's start and
                  # stop, which no step of an untraced run pays
                  "tok_s_at_median_step": global_batch * seq / chips
                  / (1e-3 * stats.median(step_ms)),
                  "compiles_in_window": ctx.compiles.between(t_start, t_end)})
