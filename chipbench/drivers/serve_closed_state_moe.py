"""Closed-loop serving of a model whose layers keep a MATRIX state per head
(Mamba-2) beside a few attention layers, every feed-forward a mixture of
experts of which this chip holds a share, with a bring-up of its own.

The loop, the gauges (state slots among them) and the capture in the tail are
``serve_closed_state.py``'s own (``loop``, ``capture_from``; ``layer_errs``
for the states). What differs is the bring-up:

- the weights are made a layer at a time (the family's ``init_params``), and
  the account takes the state pool off first: a sequence's state costs the
  same whatever its length (``family.state_layout``: 4 MiB a Mamba layer and
  the convolution's tail), so ``(tracked + 1) x bytes_per_sequence`` comes
  off the budget before the pages get the rest;
- the check runs the ENGINE FIRST and the reference after it, over the
  engine's own tokens, layer by layer with that layer's weights handed up
  from the host. What the engine runs: another sequence through the state
  slot before (prompt, a few fused steps, flush: what it leaves must not
  show); then the prompt through the packed pass (state handed from chunk
  slot to chunk slot, the product-form scan), paged chunk passes (state
  handed from pass to pass through the pool, the last chunk shorter than its
  slot), ``single_rows`` tokens one at a time through the cache (the
  one-token recurrence, the attention layer across a page boundary),
  ``forced_tokens`` forced ones; then the fused decode step, what traffic
  runs and as traffic runs it: the sequence is one row among
  ``fused_neighbours`` live ones, each in a state slot of its own, for
  ``fused_steps`` steps in runs of ``fused_run``; the logits each run leaves
  are compared, and after the last a forced token goes through a ragged pass
  (all rows in it);
- the logits' limits are ``serve_closed_latent.py``'s, and for its reason:
  with a router in every layer a row may have, in some layer, a HELD expert
  within bfloat16's rounding of the selection's edge (``granite_ref``'s
  margin), and an expert chosen otherwise moves the row. So of the ragged
  passes' rows those with a clear margin are held in the MEDIAN
  (``tol_logits``) and at the 90th percentile (``tol_tail``), the fused
  path's rows in their median (``tol_tail``), every row by a loose limit of
  its own (``tol_row``). The control (``control_act_dtype``) is the reference
  with its activations rounded to that type: each statistic of it has to
  read OVER its limit, or the run is not correct;
- the state's precision is held on the state the timed programs leave
  (``engine.sequence_state`` after the last token), in the FIRST Mamba layer,
  as rms difference over rms state against the reference run with its
  activations rounded where the program's are (``state_unrounded``: which of
  the recurrence's inputs the compiler leaves in float32) and a float32
  state: ``serve_closed_state.py``'s way and for its reason (from the second
  layer on two bfloat16 computations drift by their own rounding, control or
  not; the run logs every layer). The control is that reference with its
  state rounded to ``control_state_dtype`` after every token: it has to come
  out OVER ``tol_state``. The engine's state ``[Lm, N, H * P]`` is compared
  as the reference's ``[Lm, H, P, N]``;
- ``held_touched_share``: of the experts held here, the share a step of the
  engine's decode rows reaches (the engine's own routers on unit-normal
  inputs, off the window); logged, and ``serve/moe/held_touched_share`` in
  ``tracer.totals``;
- off the chip (``ctx.on_chip`` false) the configuration's ``rehearsal``
  block is laid over it.
"""

import gc
import importlib
import time
from typing import List

import numpy as np

from chipbench import serving
from chipbench.harness import BenchError, Context, Outcome
from chipbench.traffic import balanced, generator


def bring_up(ctx: Context) -> serving.Served:
    import jax
    import jax.numpy as jnp

    overlay = ctx.registry.module("drivers", "serve_closed_kinds").overlay
    cfg = ctx.config
    if not ctx.on_chip:
        cfg = ctx.config = overlay(cfg, cfg["rehearsal"])
    family = ctx.registry.module("families", cfg["family"])
    try:
        model = family.build_model(cfg, jnp.bfloat16)
        from deepspeed_tpu.inference.v2.ragged_model import ADAPTERS
        if cfg["family"] not in ADAPTERS:
            raise ImportError(f"no ragged adapter for {cfg['family']!r}")
    except ImportError as e:
        raise BenchError(f"this tree's program cannot serve the "
                         f"{cfg['family']!r} family: {e}") from None
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.inference.v2.ragged.kv_cache import KVCacheConfig
    from deepspeed_tpu.inference.v2.ragged_model import describe_layer_kinds
    from deepspeed_tpu.monitor.trace import tracer
    from deepspeed_tpu.utils.tree import tree_size_bytes

    reference = importlib.import_module(
        "chipbench.reference." + family.REFERENCE)
    dev = ctx.devices[0]
    t0 = time.time()
    params = family.init_params(model, ctx.seed, jnp.bfloat16)
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

    # -- the account: fill x limit, less weights, the state pool and the
    # headroom; the rest is pages
    bs = cfg["engine"]["kv_cache"]["block_size"]
    sm = cfg["engine"]["state_manager"]
    state = family.state_layout(cfg)
    state_bytes = (sm["max_tracked_sequences"] + 1) \
        * state["bytes_per_sequence"]
    limit = dev.memory_stats()["bytes_limit"] if ctx.on_chip \
        else int(cfg["rehearsal_hbm_bytes"])
    budget = int(limit * cfg["hbm_fill"]) - weight_bytes - state_bytes \
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
    ctx.log(f"engine: up in {time.time() - t1:.1f} s (warm-up included); "
            f"{num_blocks} pages of {bs} tokens x {layers} attention layers "
            f"= {engine.kv.config.bytes_per_block() * (num_blocks + 1) / 2**30:.2f}"
            f" GiB; state pool {sm['max_tracked_sequences']} + 1 slots x "
            f"{state['bytes_per_sequence'] / 2**20:.2f} MiB = "
            f"{state_bytes / 2**30:.2f} GiB; "
            f"{describe_layer_kinds(engine.spec)}; experts held "
            f"{engine.spec.moe.get('held', 'all')} of "
            f"{engine.spec.moe['num_experts']}; {engine.compiles} programs")
    wrong = family.check_engine(cfg, engine)
    if wrong:
        raise BenchError(wrong)

    rng = generator.rng_for(ctx.seed, "check")
    bad = run_check(ctx, engine, family, reference, host_params, rng)
    del host_params
    gc.collect()
    step_rows = sm["max_ragged_sequence_count"]
    x = jnp.asarray(rng.standard_normal(
        (int(cfg["check"]["router_rows"]), cfg["hidden_size"])), jnp.bfloat16)
    touched = family.held_touched_share(engine, x, step_rows)
    tracer.note("serve/moe/held_touched_share", touched)
    ctx.log(f"held experts touched by a step of {step_rows} rows: "
            f"{100 * touched:.1f}% a layer in the mean ("
            f"{x.shape[0] // step_rows} steps of unit-normal rows through "
            "the engine's routers)")
    if bad:
        ctx.log(f"CHECK FAILED: {bad[:8]} ({len(bad)} in all)")
    return serving.Served(
        engine=engine, vocab=cfg["vocab_size"], correct=not bad,
        class_name=engine_cfg["serving"]["classes"][0]["name"])


def run_check(ctx: Context, engine, family, reference, host_params,
              rng) -> List[str]:
    """The check of the module's docstring on ``engine``; the names of what
    failed."""
    import jax.numpy as jnp

    state_driver = ctx.registry.module("drivers", "serve_closed_state")
    fused_logits = ctx.registry.module(
        "drivers", "serve_closed_latent").fused_logits
    cfg, check = ctx.config, ctx.config["check"]
    vocab = cfg["vocab_size"]
    Tp, Tk, R, K, F, run, NB = (int(check[k]) for k in (
        "prompt_tokens", "packed_tokens", "single_rows", "forced_tokens",
        "fused_steps", "fused_run", "fused_neighbours"))
    first_single = Tp - R
    if not 0 < Tk < first_single or F % run:
        raise BenchError("the check's prompt is too short for its parts, or "
                         "fused_steps is not a multiple of fused_run")
    draw = lambda n: rng.integers(0, vocab, size=int(n)).astype(np.int32)
    prompt, forced = draw(Tp), draw(K + 1)

    # -- the engine first. Another sequence through the slot before
    t0 = time.time()
    first, uid = 1, 2
    engine.put([first], [draw(Tk // 2)])
    engine.decode_pipeline([first]).run(8)
    slot = engine.scheduler.seqs[first].state_slot
    engine.flush([first])
    got: List = []          # (name, the engine's logits, the reference's row)
    got.append(("prefill (packed pass)",
                engine.put([uid], [prompt[:Tk]])[0], Tk - 1))
    if engine.scheduler.seqs[uid].state_slot != slot:
        raise BenchError("the check's sequence did not take the freed slot")
    got.append(("prefill (paged chunk passes)",
                engine.put([uid], [prompt[Tk:first_single]])[0],
                first_single - 1))
    for i in range(first_single, Tp):
        got.append((f"prompt position {i} through the cache (ragged pass)",
                    engine.put([uid], [prompt[i:i + 1]])[0], i))
    for i in range(K):
        got.append((f"decode {i + 1} (ragged pass)",
                    engine.put([uid], [forced[i:i + 1]])[0], Tp + i))
    others = list(range(uid + 1, uid + 1 + NB))
    lo, hi = check["neighbour_tokens"]
    if others:
        engine.put(others, [draw(n) for n in rng.integers(lo, hi + 1,
                                                          size=NB)])
    row = NB // 2
    live = others[:row] + [uid] + others[row:]
    pipe = engine.decode_pipeline(live)
    own, fused, at = [], [], Tp + K
    for j in range(F // run):
        own.extend(np.asarray(pipe.run(run), np.int32)[row])
        at += run
        fused.append((f"fused step {(j + 1) * run}",
                      fused_logits(engine, uid), at - 1))
    last = engine.put(live, [forced[K:] if u == uid else draw(1)
                             for u in live])[row]
    fused.append((f"forced token after {F} fused decode steps (ragged pass)",
                  last, at))
    ids = np.concatenate([prompt, forced[:K], own, forced[K:]])
    T = len(ids)
    h_engine = engine.sequence_state(uid)                  # [Lm, N, E]
    slots = {engine.scheduler.seqs[u].state_slot for u in live}
    engine.flush(live)
    if len(slots) != len(live):
        raise BenchError("two live sequences share a state slot")
    ctx.log(f"check: the engine ran {T} tokens ({Tk} packed, "
            f"{first_single - Tk} in paged chunk passes, {R} single, {K} "
            f"forced, {F} fused steps in runs of {run} as row {row} of "
            f"{len(live)} live sequences, 1 forced) in "
            f"{time.time() - t0:.1f} s")

    # -- then the reference over those very tokens: in float32 for the
    # logits, and once more as the logits' control; for the state, with its
    # activations rounded where the program's are and the state in float32,
    # and that one's control
    t0 = time.time()
    hp = family.reference_hp(cfg)
    weights = family.reference_weights(host_params, cfg)
    positions = [p for _, _, p in got] + [p for _, _, p in fused]
    rows = jnp.asarray(positions, jnp.int32)
    act = engine.spec.dtype          # where the program rounds: bfloat16
    wide = tuple(check["state_unrounded"])
    (ref, margin, _), (low, _, _), (_, _, h_ref), (_, _, h_ctl) = (
        tuple(None if v is None else np.asarray(v) for v in out)
        for out in reference.forward_variants(weights, ids, hp, [
            {}, {"act_dtype": getattr(jnp, check["control_act_dtype"])},
            {"act_dtype": act, "unrounded": wide, "head": False},
            {"act_dtype": act, "unrounded": wide, "head": False,
             "state_dtype": getattr(jnp, check["control_state_dtype"])}],
            rows=rows))
    del weights
    if not np.isfinite(ref).all():
        raise BenchError("the reference's logits are not finite")
    usable = margin >= float(check["min_routing_margin"])
    ctx.log(f"reference: {T} tokens four times in one walk over the layers "
            f"(float32; "
            f"{check['control_act_dtype']} activations; "
            f"{jnp.dtype(act).name} activations with a float32 state and "
            f"with a {check['control_state_dtype']} state) in "
            f"{time.time() - t0:.1f} s; routing margins (boundaries that "
            f"touch a held expert): median {np.median(margin):.2e}, "
            f"{int(usable.sum())} of {len(margin)} rows at or over "
            f"{check['min_routing_margin']}")

    tol, tol_tail, tol_row, tol_state = (float(check[k]) for k in (
        "tol_logits", "tol_tail", "tol_row", "tol_state"))
    bad: List[str] = []

    def compare(n, name, logits, say) -> float:
        logits = np.asarray(logits, np.float32)
        err = serving.rel_err(logits, ref[n])
        if say or err > tol_row:
            ctx.log(f"check {name}: rel err {err:.2e} (a row's limit "
                    f"{tol_row:.1e}; margin {margin[n]:.1e})")
        if not (np.isfinite(logits).all() and err <= tol_row):
            bad.append(name)
        return err

    control = lambda n: serving.rel_err(low[n], ref[n])
    # the ragged passes' rows: those with a clear routing margin, in the
    # median and at the 90th percentile
    errs, ctl, every = [], [], []
    for n, (name, logits, _) in enumerate(got):
        err = compare(n, name, logits, say=n < 2 or n >= len(got) - K)
        every.append(err)
        if usable[n]:
            errs.append(err)
            ctl.append(control(n))
    if len(errs) < int(check["min_rows"]):
        raise BenchError(f"fewer than {check['min_rows']} check rows have a "
                         "clear routing margin; choose another seed")
    stats = lambda v: (float(np.median(v)), float(np.percentile(v, 90)))
    (median, p90), (ctl_median, ctl_p90) = stats(errs), stats(ctl)
    ctx.log(f"check: {len(got)} rows out of ragged passes, {len(errs)} of "
            f"them with a clear margin: their median rel err {median:.2e} "
            f"(tol {tol:.1e}), 90th percentile {p90:.2e} (tol "
            f"{tol_tail:.1e}), largest {max(errs):.2e} (of all {len(every)} "
            f"rows: median {float(np.median(every)):.2e}, largest "
            f"{max(every):.2e}); the control reads {ctl_median:.2e} and "
            f"{ctl_p90:.2e} on the same rows")
    if not median <= tol:
        bad.append("the median of the compared rows")
    if not p90 <= tol_tail:
        bad.append("the 90th percentile of the compared rows")
    if not (ctl_median > tol and ctl_p90 > tol_tail):
        bad.append(f"logits control (the reference in "
                   f"{check['control_act_dtype']} passes)")
    # the fused path's rows, margin or not, in their median; its tokens are
    # whatever the step chose: how many are the reference's greedy ones is
    # for people
    base = len(got)
    mid = float(np.median([compare(base + n, name, logits, say=False)
                           for n, (name, logits, _) in enumerate(fused)]))
    ctl_fused = float(np.median([control(base + n)
                                 for n in range(len(fused))]))
    ctx.log(f"check fused path (positions {Tp + K} to {at}): {len(fused)} "
            f"rows, median rel err {mid:.2e} (tol {tol_tail:.1e}); the "
            f"control reads {ctl_fused:.2e}")
    if not mid <= tol_tail:
        bad.append("the median of the fused path's rows")
    if not ctl_fused > tol_tail:
        bad.append("fused path control")
    # the state the timed programs left: the module's docstring
    Lm, H, P, N = h_ref.shape
    as_pool = lambda h: np.swapaxes(h.reshape(Lm, H * P, N), 1, 2)
    e_state = state_driver.layer_errs(h_engine, as_pool(h_ref))
    e_ctl = state_driver.layer_errs(as_pool(h_ctl), as_pool(h_ref))
    show = lambda v: " ".join(f"{x:.1e}" for x in v)
    ctx.log(f"check state after the run against the reference with "
            f"{jnp.dtype(act).name} activations: in the first Mamba layer "
            f"the engine's is {e_state[0]:.2e} from it, the control's "
            f"({check['control_state_dtype']} state) {e_ctl[0]:.2e} (tol "
            f"{tol_state:.1e}); for people, every Mamba layer in order: the "
            f"engine {show(e_state)}; the control {show(e_ctl)}")
    if not (np.isfinite(h_engine).all() and e_state[0] <= tol_state):
        bad.append("state after the fused steps")
    if not e_ctl[0] > tol_state:
        bad.append(f"state control (a {check['control_state_dtype']} state "
                   "passes)")
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
