"""Bringing the serving engine up for a cell and holding it to the plain
reference, shared by the serving drivers.

Order matters on a 16 GB chip: the weights are made on the device, the
reference's logits are computed from them, then the weights move to the host
and the engine stacks its own copy from there (two device copies do not
fit; ``chip_smoke.py`` found this out). After the engine is up its logits
after packed prefill, paged-chunk prefill and paged decode, and the tokens of
its fused decode step, are compared with the reference's.
"""

from __future__ import annotations

import gc
import time
from dataclasses import dataclass
from typing import Any, Dict, List

import numpy as np

from chipbench import models
from chipbench.harness import BenchError, Context
from chipbench.reduce import latency, stats
from chipbench.reference import decoder_ref
from chipbench.traffic import generator, replay

#: a position whose routing margin (see decoder_ref.hidden_states) is under
#: this is not compared: router logits have unit scale at random weights and
#: bfloat16 activations move them by about 2^-8 * sqrt(2 * layers) ~ 1e-2,
#: so under 5e-2 the engine may rightly choose another expert
MIN_ROUTING_MARGIN = 5e-2


@dataclass
class Served:
    engine: Any
    vocab: int
    correct: bool
    class_name: str


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-9))


def bring_up(ctx: Context) -> Served:
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.inference.v2.ragged.kv_cache import KVCacheConfig
    from deepspeed_tpu.utils.tree import tree_size_bytes

    cfg, check = ctx.config, ctx.config["check"]
    L, vocab = cfg["num_hidden_layers"], cfg["vocab_size"]
    dev = ctx.devices[0]
    model = models.build_model(cfg, jnp.bfloat16)
    t0 = time.time()
    params = models.init_params(model, ctx.seed, jnp.bfloat16)
    jax.block_until_ready(params)
    weight_bytes = tree_size_bytes(params)
    ctx.log(f"weights: depth {L}, {weight_bytes / 2**30:.2f} GiB bf16, made "
            f"on the device in {time.time() - t0:.1f} s")

    # -- the reference's rows first: one position at the end of the packed
    # half of the prompt, the prompt's last, and K forced decode positions
    t0 = time.time()
    Tp, K = int(check["prompt_tokens"]), int(check["forced_tokens"])
    bs = cfg["engine"]["kv_cache"]["block_size"]
    half = (Tp // 2 // bs) * bs or Tp // 2
    rows = jnp.asarray([half - 1] + list(range(Tp - 1, Tp + K)), jnp.int32)
    hp = models.reference_hp(cfg)
    ref_fn = jax.jit(lambda p, ids: decoder_ref.forward_logits(
        models.reference_weights(p, cfg), ids, hp, rows=rows,
        with_margin=True))
    rng = generator.rng_for(ctx.seed, "check")
    prompt = rng.integers(0, vocab, size=Tp).astype(np.int32)
    ids = np.zeros((Tp + K,), np.int32)
    ids[:Tp] = prompt
    # the forced tokens are the reference's own greedy continuation; causal
    # attention makes a row blind to the padding after it
    for i in range(K):
        ids[Tp + i] = int(jnp.argmax(ref_fn(params, ids)[0][1 + i]))
    ref, margin = (np.asarray(x) for x in ref_fn(params, ids))
    forced = ids[Tp:]
    if not np.isfinite(ref).all():
        raise BenchError("the reference's logits are not finite")
    usable = margin >= MIN_ROUTING_MARGIN
    ctx.log(f"reference: {Tp}-token prompt + {K} forced tokens in "
            f"{time.time() - t0:.1f} s; routing margins "
            f"{np.round(margin, 3).tolist()}, {int(usable.sum())} of "
            f"{len(usable)} rows compared")
    if usable.sum() * 3 < len(usable):
        raise BenchError("fewer than a third of the check rows have a clear "
                         "routing margin; choose another seed")

    # -- the weights move to the host; the engine stacks its copy from there
    t0 = time.time()
    host_params = jax.device_get(params)
    del params, ref_fn
    gc.collect()
    limit = dev.memory_stats()["bytes_limit"] if ctx.on_chip \
        else int(cfg["rehearsal_hbm_bytes"])
    budget = int(limit * cfg["hbm_fill"]) - weight_bytes \
        - int(cfg["hbm_headroom_bytes"])
    num_blocks = KVCacheConfig.from_memory_budget(
        L, cfg["num_key_value_heads"], hp["head_dim"], budget,
        block_size=bs).num_blocks
    engine_cfg = {k: dict(v) for k, v in cfg["engine"].items()}
    engine_cfg["kv_cache"]["num_blocks"] = num_blocks
    engine_cfg["dtype"] = jnp.bfloat16
    t1 = time.time()
    engine = InferenceEngineV2(model=model, model_parameters=host_params,
                               config=engine_cfg)
    del host_params
    gc.collect()
    ctx.log(f"engine: weights to the host in {t1 - t0:.1f} s, engine up in "
            f"{time.time() - t1:.1f} s (warm-up included); {num_blocks} pages "
            f"of {bs} tokens = "
            f"{engine.kv.config.bytes_per_block() * (num_blocks + 1) / 2**30:.2f}"
            f" GiB; window {engine.spec.window}; {engine.compiles} programs")
    if engine.spec.window != cfg.get("sliding_window"):
        raise BenchError(f"the engine runs window {engine.spec.window}, the "
                         f"configuration says {cfg.get('sliding_window')}")

    # -- logits, not tokens, against the reference
    tol = float(check["tol_logits"])
    bad: List[str] = []

    def compare(name: str, got, row: int) -> None:
        if not usable[row]:
            ctx.log(f"check {name}: skipped, routing margin "
                    f"{margin[row]:.3f}")
            return
        got = np.asarray(got, np.float32)
        err = rel_err(got, ref[row])
        ctx.log(f"check {name}: rel err {err:.2e} (tol {tol:.1e})")
        if not (np.isfinite(got).all() and err <= tol):
            bad.append(name)

    uid = 1
    compare("prefill (packed pass)", engine.put([uid], [prompt[:half]])[0], 0)
    compare("prefill (paged chunk pass)",
            engine.put([uid], [prompt[half:]])[0], 1)
    for i in range(K):
        compare(f"decode {i + 1} (ragged pass)",
                engine.put([uid], [forced[i:i + 1]])[0], 2 + i)
    engine.flush([uid])
    # the fused decode step — what traffic runs — samples on the device and
    # gives tokens: each is the reference's greedy token or, at the first
    # that is not (after which the histories differ), within the logits
    # tolerance of the reference's best
    uid = 2
    engine.put([uid], [prompt])
    toks = engine.decode_pipeline([uid]).run(K)[0]
    engine.flush([uid])
    scale = float(np.max(np.abs(ref)))
    for i, (got, want) in enumerate(zip(toks, forced)):
        if not usable[1 + i]:
            break
        if int(got) != int(want):
            gap = float(ref[1 + i].max() - ref[1 + i][int(got)])
            ctx.log(f"check fused step {i + 1}: token {got} for {want}, "
                    f"{gap:.3e} under the reference's best")
            if gap > 2 * tol * scale:
                bad.append(f"fused step {i + 1}")
            break
    if bad:
        ctx.log(f"CHECK FAILED: {bad}")
    return Served(engine=engine, vocab=vocab, correct=not bad,
                  class_name=engine_cfg["serving"]["classes"][0]["name"])


def submitter(frontend, served: Served):
    def submit(prompt, max_new_tokens):
        return frontend.submit(prompt, priority=served.class_name,
                               max_new_tokens=max_new_tokens)
    return submit


def warm_traffic(ctx: Context, served: Served, frontend) -> None:
    """A burst of requests (the mix's ``warmup``: its own prompt lengths,
    short outputs) before anything is timed: it runs the module-level
    programs the engine's warm-up does not build (shape-keyed helpers met
    only under traffic) through every decode bucket, up and down. The same
    requests in every run, whatever the seed."""
    warm = ctx.traffic.get("warmup")
    if not warm:
        return
    n = int(warm["requests"])
    t0 = time.time()
    before = len(ctx.compiles.ended)
    pool = generator.closed_pool(dict(warm, pool_requests=n), 0, served.vocab)
    submit = submitter(frontend, served)
    sent = [replay.Sent(r, 0.0, 0.0, submit(r.prompt, r.max_new_tokens))
            for r in pool]
    if not replay.drain(sent, float(ctx.traffic["drain_s"])):
        raise BenchError("the warm-up burst did not drain")
    ctx.log(f"warm-up burst: {n} requests in {time.time() - t0:.1f} s, "
            f"{len(ctx.compiles.ended) - before} programs compiled or loaded")


class Gauges:
    """What the sender samples between sends, and the pipeline's counters at
    the window's edges."""

    def __init__(self, engine):
        self.engine = engine
        self.total = engine.allocator.total_blocks
        self.min_free = self.total
        self.max_inflight = 0
        self.edges: List[Dict[str, float]] = []

    def sample(self, frontend) -> None:
        self.min_free = min(self.min_free, self.engine.allocator.free_blocks)
        self.max_inflight = max(self.max_inflight, frontend.outstanding)

    def edge(self, frontend) -> None:
        st = self.engine.pipeline_stats
        self.edges.append({
            "t": time.perf_counter(), "steps": st.steps, "rows": st.tokens,
            "host_ms": st.dispatch_ms + st.host_build_ms + st.bubble_ms,
            "drain_ms": st.fetch_drain_ms,
            "outstanding": frontend.outstanding})
        if len(self.edges) == 1:          # the window opens: peak from here
            self.min_free = self.engine.allocator.free_blocks

    def counters(self) -> Dict[str, float]:
        a, b = self.edges[0], self.edges[-1]
        steps = max(1, b["steps"] - a["steps"])
        return {"decode_steps": b["steps"] - a["steps"],
                "decode_rows_mean": (b["rows"] - a["rows"]) / steps,
                "host_ms_per_step": (b["host_ms"] - a["host_ms"]) / steps,
                "drain_ms_per_step": (b["drain_ms"] - a["drain_ms"]) / steps,
                "kv_pages_peak_share": 1.0 - self.min_free / self.total,
                "backlog_start": a["outstanding"],
                "backlog_end": b["outstanding"],
                "max_inflight": self.max_inflight}


def summarize(ctx: Context, served: Served, sent: List[replay.Sent],
              measured: List[replay.Sent], t_w0: float, t_w1: float,
              gauges: Gauges) -> Dict[str, Any]:
    """End-to-end values and counters of one serving window; ``measured``
    are the requests whose latency counts (due inside the window)."""
    done = [s for s in measured if latency.complete(s, served.vocab)]
    ttft = [latency.ttft_ms(s) for s in done]
    gaps = [g for s in done for g in s.handle.tbt_ms]
    late = [latency.lateness_ms(s) for s in measured]
    waits = [w for w in (latency.queue_wait_ms(s) for s in done)
             if w is not None]
    tokens = latency.tokens_between(sent, t_w0, t_w1)
    values = {"serve_tok_s": tokens / (t_w1 - t_w0)}
    counters = gauges.counters()
    counters["gen_late_p95_ms"] = stats.percentile(late, 95)
    if waits:
        counters["queue_wait_p50_ms"] = stats.median(waits)
    counters["compiles_in_window"] = ctx.compiles.between(t_w0, t_w1)
    if ttft and gaps:   # the tails go per layer: too unsteady to bound
        values["itl_p50_ms"] = stats.median(gaps)
        counters["itl_p95_ms"] = stats.percentile(gaps, 95)
        counters["ttft_p50_ms"] = stats.median(ttft)
        counters["ttft_p90_ms"] = stats.percentile(ttft, 90)
    ctx.log(f"window: {len(measured)} requests due, {len(done)} complete; "
            f"{tokens} tokens arrived in {t_w1 - t_w0:.2f} s")
    if ttft and gaps:
        ctx.log(f"ttft from due time: median {stats.median(ttft):.1f} ms, "
                f"p90 {counters['ttft_p90_ms']:.1f} ms over {len(ttft)} "
                f"requests; gaps: median {values['itl_p50_ms']:.2f} ms, p95 "
                f"{counters['itl_p95_ms']:.2f} ms over {len(gaps)} gaps")
    ctx.log(f"generator lateness: median {stats.median(late):.2f} ms, p95 "
            f"{counters['gen_late_p95_ms']:.2f} ms; counters {counters}")
    # where the two tails sit: a percentile on a flat stretch of these is
    # steady, one on a step flips between runs
    detail = {
        "ttft_ms": {q: stats.percentile(ttft, q) for q in (50, 80, 90, 95)},
        "itl_ms": {q: stats.percentile(gaps, q)
                   for q in (50, 90, 92.5, 94, 95, 96, 97.5, 99)}}
    ctx.log(f"percentiles {detail}")
    return {"values": values, "counters": counters, "detail": detail,
            "attempted": len(measured), "failed": len(measured) - len(done)}
