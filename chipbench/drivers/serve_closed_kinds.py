"""Closed-loop serving of a model whose layers are of several kinds (window
and full attention, dense and MoE feed-forward), with a bring-up of its own.

The loop is ``serve_closed.py``'s: ``clients`` callers, ``ramp_s`` before the
window, the window, the drain; ``--trace 2`` runs the clients again under
the capture. What differs is what comes before it, the pool's order and one
gauge:

- the family's part of the bring-up is named by the configuration file:
  ``family`` is a module under ``chipbench/families/`` (model, reference
  weights and settings, cache layout) and that module names the plain
  reference under ``chipbench/reference/``. A next family is files only.
- the check compares many rows, because with many small experts most
  positions have a routing margin that rounding can cross: after the packed
  and the paged-chunk prefill the prompt's last ``single_rows`` tokens go
  through the cache one at a time, then ``forced_tokens`` forced ones, then
  the fused decode step; the prompt is longer than the window several times
  over, so windowed layers drop most of it and full layers see all of it.
  Rows with a small margin are not compared, so the logits cannot tell a
  router computed in a lower precision: the family's ``router_readings``
  holds the program's router by itself to the reference's.
- the pool holds the generator's lengths in the seed's order, dealt so that
  every stretch of it holds the same work (``traffic/balanced.py``): such a
  model is served contexts of very unequal length, a window takes under
  half the pool, and under a permutation of the whole pool the seed would
  choose which of the long prompts the window meets.
- the sender also samples ``engine.kv_window_dead_tokens()``: of the tokens
  the pages hold (x layers), those a windowed layer will never read again.
- off the chip (``ctx.on_chip`` false) the configuration's ``rehearsal``
  block is laid over it: tiny sizes with every kind of layer present.
"""

import copy
import gc
import importlib
import time
from typing import Any, Dict, List

import numpy as np

from chipbench import models, serving
from chipbench.harness import BenchError, Context, Outcome, annotate
from chipbench.reduce import latency
from chipbench.traffic import balanced, generator, replay


def overlay(base: Dict[str, Any], over: Dict[str, Any]) -> Dict[str, Any]:
    """``over`` laid on ``base``: dicts merge key by key, all else replaces."""
    out = dict(base)
    for k, v in over.items():
        out[k] = overlay(out[k], v) if isinstance(v, dict) and isinstance(
            out.get(k), dict) else copy.deepcopy(v)
    return out


def bring_up(ctx: Context) -> serving.Served:
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.inference.v2.ragged.kv_cache import KVCacheConfig
    from deepspeed_tpu.utils.tree import tree_size_bytes

    cfg = ctx.config
    if not ctx.on_chip:
        cfg = ctx.config = overlay(cfg, cfg["rehearsal"])
    check = cfg["check"]
    family = ctx.registry.module("families", cfg["family"])
    reference = importlib.import_module(
        "chipbench.reference." + family.REFERENCE)
    vocab = cfg["vocab_size"]
    dev = ctx.devices[0]
    model = family.build_model(cfg, jnp.bfloat16)
    t0 = time.time()
    params = models.init_params(model, ctx.seed, jnp.bfloat16)
    jax.block_until_ready(params)
    weight_bytes = tree_size_bytes(params)
    ctx.log(f"weights: family {cfg['family']}, depth "
            f"{cfg['num_hidden_layers']}, {weight_bytes / 2**30:.2f} GiB "
            f"bf16, made on the device in {time.time() - t0:.1f} s")

    # -- the reference's rows first: the last position of the packed part,
    # of the paged-chunk part, each of the prompt's last R positions (sent
    # one token at a time) and K forced decode positions
    t0 = time.time()
    Tp, R, K = (int(check[k]) for k in
                ("prompt_tokens", "single_rows", "forced_tokens"))
    bs = cfg["engine"]["kv_cache"]["block_size"]
    half = (Tp // 2 // bs) * bs or Tp // 2
    first_single = Tp - R
    if not half < first_single:
        raise BenchError("the check's prompt is too short for its rows")
    rows = jnp.asarray([half - 1] + list(range(first_single - 1, Tp + K)),
                       jnp.int32)
    hp = family.reference_hp(cfg)
    ref_fn = jax.jit(lambda p, ids: reference.forward_logits(
        family.reference_weights(p, cfg), ids, hp, rows=rows,
        with_margin=True))
    rng = generator.rng_for(ctx.seed, "check")
    ids = rng.integers(0, vocab, size=Tp + K).astype(np.int32)
    # two passes of the reference: the first forced token is its own greedy
    # one (causal attention makes a row blind to what comes after it), the
    # others are the seed's; the second pass then gives every row, and the
    # greedy token after the first forced one, for the fused step
    first_forced = 1 + R
    ids[Tp] = int(jnp.argmax(ref_fn(params, ids)[0][first_forced]))
    ref, margin = (np.asarray(x) for x in ref_fn(params, ids))
    greedy = [int(ids[Tp]), int(np.argmax(ref[first_forced + 1]))]
    prompt, forced = ids[:Tp], ids[Tp:]
    if not np.isfinite(ref).all():
        raise BenchError("the reference's logits are not finite")
    min_margin = float(check["min_routing_margin"])
    usable = margin >= min_margin
    ctx.log(f"reference: {Tp}-token prompt + {K} forced tokens in "
            f"{time.time() - t0:.1f} s; routing margins: median "
            f"{np.median(margin):.2e}, largest {margin.max():.2e}; "
            f"{int(usable.sum())} of {len(usable)} rows at or over "
            f"{min_margin:.1e} are compared")
    if usable.sum() < int(check["min_rows"]):
        raise BenchError(f"fewer than {check['min_rows']} check rows have a "
                         "clear routing margin; choose another seed")

    # -- the weights move to the host; the engine stacks its copy from there
    t0 = time.time()
    host_params = jax.device_get(params)
    del params, ref_fn
    gc.collect()
    limit = dev.memory_stats()["bytes_limit"] if ctx.on_chip \
        else int(cfg["rehearsal_hbm_bytes"])
    budget = int(limit * cfg["hbm_fill"]) - weight_bytes \
        - int(cfg["hbm_headroom_bytes"])
    layers, kv_heads, head_dim = family.kv_layout(cfg)
    num_blocks = KVCacheConfig.from_memory_budget(
        layers, kv_heads, head_dim, budget, block_size=bs).num_blocks
    engine_cfg = {k: dict(v) for k, v in cfg["engine"].items()}
    engine_cfg["kv_cache"]["num_blocks"] = num_blocks
    engine_cfg["dtype"] = jnp.bfloat16
    t1 = time.time()
    engine = InferenceEngineV2(model=model, model_parameters=host_params,
                               config=engine_cfg)
    del host_params
    gc.collect()
    from deepspeed_tpu.inference.v2.ragged_model import describe_layer_kinds
    ctx.log(f"engine: weights to the host in {t1 - t0:.1f} s, engine up in "
            f"{time.time() - t1:.1f} s (warm-up included); {num_blocks} pages "
            f"of {bs} tokens = "
            f"{engine.kv.config.bytes_per_block() * (num_blocks + 1) / 2**30:.2f}"
            f" GiB; {describe_layer_kinds(engine.spec)}; page ring "
            f"{engine.scheduler.ring_pages}; attention rungs "
            f"{list(engine.attn_split_ladder)}; {engine.compiles} programs")
    wrong = family.check_engine(cfg, engine)
    if wrong:
        raise BenchError(wrong)

    # -- logits, not tokens, against the reference
    tol = float(check["tol_logits"])
    bad: List[str] = []
    errs: List[float] = []

    def compare(name: str, got, row: int, say: bool = True) -> None:
        if not usable[row]:
            return
        got = np.asarray(got, np.float32)
        err = serving.rel_err(got, ref[row])
        errs.append(err)
        if say or err > tol:
            ctx.log(f"check {name}: rel err {err:.2e} (tol {tol:.1e}), "
                    f"routing margin {margin[row]:.2e}")
        if not (np.isfinite(got).all() and err <= tol):
            bad.append(name)

    uid = 1
    compare("prefill (packed pass)", engine.put([uid], [prompt[:half]])[0], 0)
    compare("prefill (paged chunk passes)",
            engine.put([uid], [prompt[half:first_single]])[0], 1)
    for i in range(R):
        pos = first_single + i
        compare(f"prompt position {pos} through the cache (ragged pass)",
                engine.put([uid], [prompt[pos:pos + 1]])[0], 2 + i, say=False)
    for i in range(K):
        compare(f"decode {i + 1} (ragged pass)",
                engine.put([uid], [forced[i:i + 1]])[0], 2 + R + i)
    engine.flush([uid])
    ctx.log(f"check: {len(errs)} rows compared, largest rel err "
            f"{max(errs):.2e}, median {float(np.median(errs)):.2e} "
            f"(tol {tol:.1e})")
    # the fused decode step — what traffic runs — samples on the device and
    # gives tokens: each is the reference's greedy token or, at the first
    # that is not (after which the histories differ), within the logits
    # tolerance of the reference's best
    uid = 2
    engine.put([uid], [prompt])
    toks = engine.decode_pipeline([uid]).run(len(greedy))[0]
    engine.flush([uid])
    scale = float(np.max(np.abs(ref)))
    for i, (got, want) in enumerate(zip(toks, greedy)):
        if not usable[first_forced + i]:
            break
        if int(got) != want:
            gap = float(ref[first_forced + i].max()
                        - ref[first_forced + i][int(got)])
            ctx.log(f"check fused step {i + 1}: token {got} for {want}, "
                    f"{gap:.3e} under the reference's best")
            if gap > 2 * tol * scale:
                bad.append(f"fused step {i + 1}")
            break
    # the router by itself, where the logits cannot tell (see the docstring)
    tol_router = float(check["tol_router"])
    x = jnp.asarray(rng.standard_normal(
        (int(check["router_rows"]), cfg["hidden_size"])), jnp.bfloat16)
    router = family.router_readings(engine, reference, hp, x, tol_router)
    ctx.log(f"check router: largest difference in a routing weight "
            f"{router['err']:.2e} (tol {tol_router:.1e}) over "
            f"{router['rows']} token-layers; the control, the reference's "
            f"router in bfloat16, reads {router['control']:.2e}")
    if not router["err"] <= tol_router:
        bad.append("router")
    if not router["control"] > tol_router:
        bad.append("router control (it passes a bfloat16 router)")
    if bad:
        ctx.log(f"CHECK FAILED: {bad[:8]} ({len(bad)} in all)")
    return serving.Served(
        engine=engine, vocab=vocab, correct=not bad,
        class_name=engine_cfg["serving"]["classes"][0]["name"])


class Gauges(serving.Gauges):
    """``serving.Gauges`` and, from the window's start, the share of the
    resident tokens (x layers) that lie below a windowed layer's reach."""

    def __init__(self, engine):
        super().__init__(engine)
        self.dead = self.resident = 0

    def sample(self, frontend) -> None:
        super().sample(frontend)
        if self.edges:
            dead, resident = self.engine.kv_window_dead_tokens()
            self.dead += dead
            self.resident += resident

    def counters(self) -> Dict[str, float]:
        out = super().counters()
        if self.resident:
            out["kv_window_dead_share"] = self.dead / self.resident
        return out


def loop(ctx: Context, served, frontend, mix, pool, seconds: float,
         traced=None) -> dict:
    """``serve_closed.loop`` with this file's gauges."""
    ramp = float(mix["ramp_s"])
    gauges = Gauges(served.engine)
    t0 = time.perf_counter() + 0.05
    window_start = time.time() + 0.05 + ramp
    t_w0, t_w1 = t0 + ramp, t0 + ramp + seconds
    if traced is not None:
        traced.schedule(t_w1 - traced.seconds)
    time.sleep(max(0.0, t0 - time.perf_counter()))
    sent = replay.run_closed(
        serving.submitter(frontend, served), pool, int(mix["clients"]),
        until=t_w1,
        marks=[(t_w0, lambda: gauges.edge(frontend)),
               (t_w1, lambda: gauges.edge(frontend))],
        each=lambda: gauges.sample(frontend), span=annotate)
    if traced is not None:
        traced.join()
    drained = replay.drain(sent, float(mix["drain_s"]))
    ctx.log(f"{mix['clients']} clients sent {len(sent)} requests; "
            f"drained {drained}")
    measured = [s for s in sent
                if (latency.token_times(s.handle) or [t_w0])[-1] >= t_w0
                or not s.handle.finished]
    got = serving.summarize(ctx, served, sent, measured, t_w0, t_w1, gauges)
    got["window_start"] = window_start
    # for people: what the window's prefills cost, request by request (a
    # request's first token comes when its prompt is through, and every
    # live row waits as long)
    inside = [s for s in sent if t_w0 <= s.sent_t < t_w1
              and s.handle.ttft_ms is not None]
    ctx.log(f"prefills in the window: {len(inside)} requests, "
            f"{sum(len(s.request.prompt) for s in inside)} prompt tokens, "
            f"{sum(s.handle.ttft_ms for s in inside) / 1e3:.2f} s to their "
            "first tokens; (s into the window, prompt tokens, ms) "
            + " ".join(f"({s.sent_t - t_w0:.1f},{len(s.request.prompt)},"
                       f"{s.handle.ttft_ms:.0f})" for s in inside))
    return got


def run(ctx: Context) -> Outcome:
    served = bring_up(ctx)
    mix = ctx.traffic
    if not ctx.on_chip:
        mix = ctx.traffic = overlay(mix, ctx.config.get(
            "rehearsal_traffic", {}))
    pool = balanced.closed_pool(mix, ctx.seed, served.vocab)
    with served.engine.serving_frontend() as frontend:
        serving.warm_traffic(ctx, served, frontend)
        got = loop(ctx, served, frontend, mix, pool, float(ctx.seconds),
                   ctx.tracer)
        if ctx.capture is not None:
            ctx.log(f"--trace 2: the clients again for the ramp, then "
                    f"{ctx.capture.seconds} s under the capture (the lines "
                    "up to 'capture:' are of that segment, not of the "
                    "measured window)")
            ctx.capture.prime()
            loop(ctx, served, frontend, mix, pool, ctx.capture.seconds,
                 ctx.capture)
    return Outcome(correct=served.correct and got["failed"] == 0,
                   attempted=got["attempted"], failed=got["failed"],
                   window_start=got["window_start"], end_to_end=got["values"],
                   counters=got["counters"])
