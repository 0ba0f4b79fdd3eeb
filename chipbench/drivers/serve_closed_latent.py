"""Closed-loop serving of a model with latent attention (one latent row a
token a layer in the pool, no key/value pair) that may hold one chip's
share of its experts, with a bring-up of its own.

The loop, the gauges and the capture are ``serve_closed_state.py``'s own
(``clients`` callers over a pool dealt by ``traffic/balanced.py``, ``ramp_s``
before the window, the window, the drain; under ``--trace 2`` the same
clients go on for the cell's ``trace_tail_s`` past the window's end and a
capture of the cell's ``trace_seconds`` is taken there). What differs is the
bring-up:

- the pool is sized from what a token really holds there. The family's
  ``page_layout`` gives the layers and the row's width (``latent_dim``
  values a token a layer), and the page count is
  ``KVCacheConfig.from_memory_budget(..., latent_dim=...)``; no head count or
  head size enters (``serve_closed_kinds.py`` reckons ``2 x kv_heads x
  head_dim`` a token, which a latent row is not).
- the check runs the ENGINE FIRST and the reference after it, over the
  engine's own tokens (``serve_closed_state.py``'s way): the weights and the
  pool fill the device, so the reference goes layer by layer
  (``reference.one_layer`` compiles once a kind of layer) with that layer's
  weights handed up from the host. It is given the same share of the experts
  as the engine (``hp["held"]``), and a row's routing margin counts only
  boundaries that touch a held expert.
- what the engine runs: the prompt through the packed pass (expanded
  attention) and paged chunk passes, its last ``single_rows`` tokens one at a
  time through the cache, ``forced_tokens`` forced ones, every program that
  reads the pool attending in the absorbed form; the single tokens go as
  ``single_streams`` copies of the prompt side by side, each fed its own
  span of the rows, so a pass compares that many rows (a ragged pass of a
  40-layer model costs a tenth of a second however few rows are live). Then
  the fused decode step, what traffic runs and as traffic runs it: that long
  sequence and a short one (``short_prompt_tokens``: what the fused steps
  write is a third of its context, where the long one's thousands of rows
  would drown a fault in it) are two rows among ``fused_neighbours`` other
  live sequences for ``fused_steps`` steps in runs of ``fused_run``, across
  a page boundary each; the logits each run leaves are compared, and after
  the last one forced token goes through a ragged pass (all rows in it),
  whose logits read the rows the fused steps flushed.
- the limits. With 39 MoE layers and random weights nearly every row has, in
  some layer, a held expert within bfloat16's rounding of the selection's
  edge, and one expert chosen otherwise moves a row's logits by a tenth (the
  reference with bfloat16 activations reads the same against itself in
  float32). So of the ragged passes' rows those with a clear routing margin
  are held in the MEDIAN (``tol_logits``) and at the 90th percentile
  (``tol_tail``); the fused path's rows, too few to choose among, in the
  median of each of the two sequences (``tol_tail``); and every row sent by
  a loose limit of its own (``tol_row``). The control
  (``check.control_act_dtype``) is the reference with its activations
  rounded to that type: each of those statistics of it, on the long
  sequence's rows, has to read OVER its limit, or the run is not correct.
  Plus the family's ``router_readings``.
- ``held_touched_share``: of the experts held here, the share a step of the
  engine's decode rows reaches (the engine's own routers on unit-normal
  inputs, off the window); logged, and set as ``serve/moe/
  held_touched_share`` in ``tracer.totals``.
- off the chip (``ctx.on_chip`` false) the configuration's ``rehearsal``
  block is laid over it.
"""

import gc
import importlib
import time
from typing import List

import numpy as np

from chipbench import models, serving
from chipbench.harness import BenchError, Context, Outcome
from chipbench.traffic import balanced, generator


def bring_up(ctx: Context) -> serving.Served:
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.inference.v2.ragged.kv_cache import KVCacheConfig
    from deepspeed_tpu.inference.v2.ragged_model import describe_layer_kinds
    from deepspeed_tpu.monitor.trace import tracer
    from deepspeed_tpu.utils.tree import tree_size_bytes

    overlay = ctx.registry.module("drivers", "serve_closed_kinds").overlay
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
    params = getattr(family, "init_params", models.init_params)(
        model, ctx.seed, jnp.bfloat16)
    jax.block_until_ready(params)
    weight_bytes = tree_size_bytes(params)
    t1 = time.time()
    host_params = jax.device_get(params)
    del params
    gc.collect()
    ctx.log(f"weights: family {cfg['family']}, depth "
            f"{cfg['num_hidden_layers']}, {weight_bytes / 2**30:.2f} GiB "
            f"bf16, made on the device in {t1 - t0:.1f} s and moved to the "
            f"host in {time.time() - t1:.1f} s")

    # -- the account: fill x limit, less weights and the headroom; the rest
    # is pages of latent rows
    bs = cfg["engine"]["kv_cache"]["block_size"]
    limit = dev.memory_stats()["bytes_limit"] if ctx.on_chip \
        else int(cfg["rehearsal_hbm_bytes"])
    budget = int(limit * cfg["hbm_fill"]) - weight_bytes \
        - int(cfg["hbm_headroom_bytes"])
    layout = family.page_layout(cfg)
    num_blocks = KVCacheConfig.from_memory_budget(
        layout["layers"], 0, 0, budget, block_size=bs,
        latent_dim=layout["latent_dim"]).num_blocks
    engine_cfg = {k: dict(v) for k, v in cfg["engine"].items()}
    engine_cfg["kv_cache"]["num_blocks"] = num_blocks
    engine_cfg["dtype"] = jnp.bfloat16
    t1 = time.time()
    engine = InferenceEngineV2(model=model, model_parameters=host_params,
                               config=engine_cfg)
    per_token = engine.kv.config.bytes_per_block() / (layout["layers"] * bs)
    ctx.log(f"engine: up in {time.time() - t1:.1f} s (warm-up included); "
            f"{num_blocks} pages "
            f"of {bs} tokens x {layout['layers']} layers of latent rows "
            f"({layout['row_values']} values in {layout['latent_dim']}, "
            f"{per_token:.0f} B a token a layer) = "
            f"{engine.kv.config.bytes_per_block() * (num_blocks + 1) / 2**30:.2f}"
            f" GiB; {describe_layer_kinds(engine.spec)}; experts held "
            f"{engine.spec.moe.get('held', 'all')} of "
            f"{engine.spec.moe['num_experts']}; {engine.compiles} programs")
    wrong = family.check_engine(cfg, engine)
    if wrong:
        raise BenchError(wrong)

    rng = generator.rng_for(ctx.seed, "check")
    hp = family.reference_hp(cfg)
    bad = run_check(ctx, engine, family, reference, hp, host_params, rng)
    del host_params
    gc.collect()
    # the router by itself, where the logits cannot tell, and how much of
    # the held experts a step of the engine's decode rows reaches
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
    step_rows = cfg["engine"]["state_manager"]["max_ragged_sequence_count"]
    touched = family.held_touched_share(engine, x, step_rows)
    tracer.note("serve/moe/held_touched_share", touched)
    ctx.log(f"held experts touched by a step of {step_rows} rows: "
            f"{100 * touched:.1f}% a MoE layer in the mean ("
            f"{x.shape[0] // step_rows} steps of unit-normal rows through "
            "the engine's routers)")
    if bad:
        ctx.log(f"CHECK FAILED: {bad[:8]} ({len(bad)} in all)")
    return serving.Served(
        engine=engine, vocab=vocab, correct=not bad,
        class_name=engine_cfg["serving"]["classes"][0]["name"])


def fused_logits(engine, uid: int) -> np.ndarray:
    """The logits the last fused decode step left for ``uid`` (what the next
    step samples from), fetched to the host."""
    engine._materialize([uid])
    return engine._last_logits[uid]


def run_check(ctx: Context, engine, family, reference, hp, host_params,
              rng) -> List[str]:
    """The check of the module's docstring on ``engine``; the names of what
    failed."""
    import jax.numpy as jnp

    cfg, check = ctx.config, ctx.config["check"]
    vocab = cfg["vocab_size"]
    Tp, R, K, G, F, run, NB, Ts = (int(check[k]) for k in (
        "prompt_tokens", "single_rows", "forced_tokens", "single_streams",
        "fused_steps", "fused_run", "fused_neighbours",
        "short_prompt_tokens"))
    bs = cfg["engine"]["kv_cache"]["block_size"]
    half = (Tp // 2 // bs) * bs or Tp // 2
    first_single, span = Tp - R, R // G
    if not half < first_single:
        raise BenchError("the check's prompt is too short for its rows")
    if span * G != R or F % run:
        raise BenchError("single_rows is not a multiple of single_streams, "
                         "or fused_steps of fused_run")
    draw = lambda n: rng.integers(0, vocab, size=int(n)).astype(np.int32)
    prompt, forced, short = draw(Tp), draw(K + 1), draw(Ts + 1)

    # -- the engine first. ``got`` holds (name, logits, position) of the long
    # sequence's rows out of ragged passes; ``fused`` the rows of the fused
    # path, by sequence. The prompt's last R positions go through the cache
    # one token at a time, as G streams of the same prompt side by side:
    # stream g is prefilled up to its span of R / G positions and then fed
    # them, all streams a token a pass
    t0 = time.time()
    uids = list(range(1, G + 1))
    starts = [first_single + g * span for g in range(G)]
    long, small = uids[-1], G + 1     # the last stream's span ends the prompt
    got = [("prefill (packed pass, expanded)",
            engine.put(uids[:1], [prompt[:half]])[0], half - 1),
           ("prefill (paged chunk passes, absorbed)",
            engine.put(uids[:1], [prompt[half:first_single]])[0],
            first_single - 1)]
    if G > 1:
        engine.put(uids[1:], [prompt[:s] for s in starts[1:]])
    for i in range(span):
        rows_i = engine.put(uids, [prompt[s + i:s + i + 1] for s in starts])
        got.extend((f"prompt position {s + i} through the cache (ragged "
                    f"pass, stream {g + 1} of {G})", rows_i[g], s + i)
                   for g, s in enumerate(starts))
    for i in range(K):
        got.append((f"decode {i + 1} (ragged pass)",
                    engine.put([long], [forced[i:i + 1]])[0], Tp + i))
    engine.flush(uids[:-1])
    # the fused steps run as traffic runs them: the long sequence and a short
    # one are two rows among NB others that are live
    engine.put([small], [short[:Ts]])
    others = list(range(small + 1, small + 1 + NB))
    lo, hi = check["neighbour_tokens"]
    engine.put(others, [draw(n) for n in rng.integers(lo, hi + 1, size=NB)])
    live = list(others)
    live.insert(NB // 3, long)
    live.insert(2 * NB // 3 + 1, small)
    at = {long: Tp + K, small: Ts}       # the next position of each
    own = {long: [], small: []}
    fused = {long: [], small: []}
    pipe = engine.decode_pipeline(live)
    for j in range(F // run):
        toks = np.asarray(pipe.run(run), np.int32)
        for u in (long, small):
            own[u].extend(toks[live.index(u)])
            at[u] += run
            fused[u].append((f"fused step {(j + 1) * run}",
                             fused_logits(engine, u), at[u] - 1))
    last = engine.put(live, [forced[K:] if u == long else short[Ts:]
                             if u == small else draw(1) for u in live])
    for u in (long, small):
        fused[u].append((f"forced token after {F} fused decode steps "
                         "(ragged pass)", last[live.index(u)], at[u]))
    engine.flush(live)
    ids = {long: np.concatenate([prompt, forced[:K], own[long], forced[K:]]),
           small: np.concatenate([short[:Ts], own[small], short[Ts:]])}
    T = len(ids[long])
    ctx.log(f"check: the engine ran {T} tokens ({half} then "
            f"{first_single - half} in prefill passes, {R} single as {G} "
            f"streams of {span}, {K} forced, {F} fused steps in runs of "
            f"{run} as row {live.index(long)} of {len(live)} live sequences, "
            f"1 forced) and {len(ids[small])} more from a prompt of {Ts} as "
            f"row {live.index(small)} in {time.time() - t0:.1f} s")

    # -- then the reference over those very tokens, layer by layer with the
    # weights handed up from the host: the long sequence in float32 and once
    # more as the control; the short one in float32, padded to the long
    # one's length (causal: what follows it changes nothing before) so that
    # no other program compiles
    t0 = time.time()
    weights = family.reference_weights(host_params, cfg)
    rows = {long: np.r_[half - 1, first_single - 1:T],
            small: np.arange(Ts - 1, len(ids[small]))}
    ids[small] = np.concatenate(
        [ids[small], np.zeros(T - len(ids[small]), np.int32)])

    # (every pass is the control's program, its rounding switched on for
    # the control alone: a kind of layer compiles once, not twice)
    def ref_fn(u, rounding=False, **kw):
        return reference.forward_logits(
            weights, ids[u], hp, rows=jnp.asarray(rows[u], jnp.int32),
            act_dtype=getattr(jnp, check["control_act_dtype"]),
            rounding=rounding, **kw)

    ref_long, margin = (np.asarray(x) for x in ref_fn(long, with_margin=True))
    ref = {long: ref_long, small: np.asarray(ref_fn(small))}
    low = np.asarray(ref_fn(long, rounding=True))
    del weights
    if not all(np.isfinite(r).all() for r in ref.values()):
        raise BenchError("the reference's logits are not finite")
    index = {u: {int(p): n for n, p in enumerate(rows[u])} for u in rows}
    usable = margin >= float(check["min_routing_margin"])
    ctx.log(f"reference: {T} tokens three times (the long sequence in "
            f"float32 and with {check['control_act_dtype']} activations, the "
            f"short one in float32) in {time.time() - t0:.1f} s; routing "
            f"margins (boundaries that touch a held expert): median "
            f"{np.median(margin):.2e}")

    tol, tol_tail, tol_row = (float(check[k]) for k in (
        "tol_logits", "tol_tail", "tol_row"))
    bad: List[str] = []

    def compare(u, name, logits, pos, say=True) -> float:
        n = index[u][pos]
        logits = np.asarray(logits, np.float32)
        err = serving.rel_err(logits, ref[u][n])
        if say or err > tol_row:
            ctx.log(f"check {name}: rel err {err:.2e} (a row's limit "
                    f"{tol_row:.1e})")
        if not (np.isfinite(logits).all() and err <= tol_row):
            bad.append(name)
        return err

    # the ragged passes' rows: those with a clear routing margin, in the
    # median and at the 90th percentile
    errs, ctl = [], []
    for n, (name, logits, pos) in enumerate(got):
        err = compare(long, name, logits, pos, say=n < 2 or n >= len(got) - K)
        if usable[index[long][pos]]:
            errs.append(err)
            ctl.append(serving.rel_err(low[index[long][pos]],
                                       ref[long][index[long][pos]]))
    if len(errs) < int(check["min_rows"]):
        raise BenchError(f"fewer than {check['min_rows']} check rows have a "
                         "clear routing margin; choose another seed")
    stats = lambda v: (float(np.median(v)), float(np.percentile(v, 90)))
    (median, p90), (ctl_median, ctl_p90) = stats(errs), stats(ctl)
    ctx.log(f"check: {len(got)} rows out of ragged passes, {len(errs)} of "
            f"them with a clear margin: their median rel err {median:.2e} "
            f"(tol {tol:.1e}), 90th percentile {p90:.2e} (tol "
            f"{tol_tail:.1e}), largest {max(errs):.2e}; the control reads "
            f"{ctl_median:.2e} and {ctl_p90:.2e} on the same rows")
    if not median <= tol:
        bad.append("the median of the compared rows")
    if not p90 <= tol_tail:
        bad.append("the 90th percentile of the compared rows")
    if not (ctl_median > tol and ctl_p90 > tol_tail):
        bad.append(f"logits control (the reference in "
                   f"{check['control_act_dtype']} passes)")
    # the fused path's rows, margin or not, in the median of each sequence;
    # its tokens are whatever the step chose: how many are the reference's
    # greedy ones is for people
    for u, name in ((long, "long"), (small, "short")):
        mid = float(np.median([compare(u, f"{name} sequence, {what}", logits,
                                       pos, say=False)
                               for what, logits, pos in fused[u]]))
        first = at[u] - F
        greedy = [int(np.argmax(ref[u][index[u][first - 1 + i]]))
                  for i in range(F)]
        same = sum(int(a) == b for a, b in zip(own[u], greedy))
        ctx.log(f"check fused path, {name} sequence (positions {first} to "
                f"{at[u]}): {len(fused[u])} rows, median rel err {mid:.2e} "
                f"(tol {tol_tail:.1e}); {same} of {F} tokens are the "
                "reference's greedy ones")
        if not mid <= tol_tail:
            bad.append(f"the median of the fused path's rows, {name} "
                       "sequence")
    ctl_fused = float(np.median([
        serving.rel_err(low[index[long][pos]], ref[long][index[long][pos]])
        for _, _, pos in fused[long]]))
    ctx.log(f"check fused path: the control reads {ctl_fused:.2e} in the "
            "median of the long sequence's rows")
    if not ctl_fused > tol_tail:
        bad.append("fused path control")
    return bad


def serve(ctx: Context, served: serving.Served) -> Outcome:
    """The closed loop over ``served``: ``serve_closed_state.py``'s."""
    mix = ctx.traffic
    if not ctx.on_chip:
        overlay = ctx.registry.module("drivers", "serve_closed_kinds").overlay
        mix = ctx.traffic = overlay(mix, ctx.config.get(
            "rehearsal_traffic", {}))
    loop = ctx.registry.module("drivers", "serve_closed_state").loop
    pool = balanced.closed_pool(mix, ctx.seed, served.vocab)
    with served.engine.serving_frontend() as frontend:
        serving.warm_traffic(ctx, served, frontend)
        got = loop(ctx, served, frontend, mix, pool, float(ctx.seconds),
                   ctx.tracer, ctx.capture)
    return Outcome(correct=served.correct and got["failed"] == 0,
                   attempted=got["attempted"], failed=got["failed"],
                   window_start=got["window_start"], end_to_end=got["values"],
                   counters=got["counters"])


def run(ctx: Context) -> Outcome:
    return serve(ctx, bring_up(ctx))
