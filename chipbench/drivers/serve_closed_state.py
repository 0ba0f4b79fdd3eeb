"""Closed-loop serving of a model whose layers keep a recurrent state per
sequence (state-space layers beside a few attention layers), with a bring-up
of its own.

The loop is ``serve_closed_kinds.py``'s: ``clients`` callers over a pool dealt
by ``traffic/balanced.py``, ``ramp_s`` before the window, the window, the
drain. What differs:

- the check. An error in a recurrence grows with steps, not with layers, so
  the check runs long: one sequence goes in as a packed pass (state handed
  from chunk slot to chunk slot), then as paged chunk passes (state handed
  from pass to pass through the pool, the last chunk shorter than its slot),
  then ``single_rows`` tokens one at a time, ``forced_tokens`` forced decode
  tokens, then ``fused_steps`` consecutive steps of the fused decode step —
  what traffic runs, and as traffic runs it: the sequence is one row among
  ``fused_neighbours`` other live sequences, each in a state slot of its
  own, so what is compared is the many-row program with real slot indices —
  and one more forced token through the ragged pass (all rows in it), whose
  logits read the state the fused steps left. With random weights the
  largest logit changes on rounding, so the fused steps' tokens are whatever
  the step chose: the reference runs AFTER the engine, over the prompt and
  the engine's own tokens, layer by layer with that layer's weights handed
  up from the host (the engine's copy and the pools fill the device). The
  sequence takes the state slot another sequence has just given back, so
  leftover state shows.
- the state's precision is held on the state those programs left
  (``engine.sequence_state`` after the last token). The logits cannot hold
  it: at these widths bfloat16 activations move them more than a state
  rounded to bfloat16 after every token does (the configuration file's
  ``tol_reason``). Neither can the state against the float32 reference: the
  engine's follows its bfloat16 ``dt``, ``c`` and ``B``. So the reference
  runs once more with its activations rounded where the program's are
  (``act_dtype``; ``state_unrounded``: which of the recurrence's inputs the
  compiler leaves in float32) and a float32 state, and the engine's state in
  the FIRST Mamba layer is held to it (``tol_state``). Only there do the two
  compute from the same inputs, the embeddings; from the second layer on any
  two bfloat16 computations drift apart by bfloat16's own rounding, control
  or not (the run logs every layer). Every Mamba layer is the same scanned
  body calling the same kernels. The control is that reference with its
  state rounded to ``control_state_dtype`` after every token: it has to come
  out OVER ``tol_state``, or the run is not correct.
- the gauges: the sender also samples ``engine.state_slots()``.
- the capture: under ``--trace 2`` the clients are not started a second
  time. 128 prompts prefill for longer than the mix's ``ramp_s``, and a
  second ramp and a second drain of outputs this long cost 80 s of a run
  that has a time limit. The same clients go on past the window's end for
  the cell's ``trace_tail_s`` and the capture is taken there, from a thread:
  the loop is in the state the window measured (decode steps, a prefill pass
  as each request arrives). Up to the window's end the run is a ``--trace
  0`` run; what is sent after it counts in no number.
- off the chip (``ctx.on_chip`` false) the configuration's ``rehearsal``
  block is laid over it: tiny sizes with both kinds of layer present.
"""

import gc
import importlib
import threading
import time
from typing import Dict, List

import numpy as np

from chipbench import models, serving
from chipbench.harness import BenchError, Context, Outcome, annotate
from chipbench.reduce import latency
from chipbench.traffic import balanced, generator, replay


def bring_up(ctx: Context) -> serving.Served:
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.inference.v2.ragged.kv_cache import KVCacheConfig
    from deepspeed_tpu.inference.v2.ragged_model import describe_layer_kinds
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
    params = models.init_params(model, ctx.seed, jnp.bfloat16)
    jax.block_until_ready(params)
    weight_bytes = tree_size_bytes(params)
    host_params = jax.device_get(params)
    del params
    gc.collect()
    ctx.log(f"weights: family {cfg['family']}, depth "
            f"{cfg['num_hidden_layers']}, {weight_bytes / 2**30:.2f} GiB "
            f"bf16, made on the device and moved to the host in "
            f"{time.time() - t0:.1f} s")

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
            f"{describe_layer_kinds(engine.spec)}; {engine.compiles} programs")
    wrong = family.check_engine(cfg, engine)
    if wrong:
        raise BenchError(wrong)

    bad = run_check(ctx, engine, family, reference, host_params,
                    generator.rng_for(ctx.seed, "check"))
    del host_params
    gc.collect()
    return serving.Served(
        engine=engine, vocab=vocab, correct=not bad,
        class_name=engine_cfg["serving"]["classes"][0]["name"])


def layer_errs(got, want) -> np.ndarray:
    """Per Mamba layer of states ``[Lm, E, N]``: the root of the mean squared
    difference over that of ``want`` (a largest difference swings with the
    draw of the tokens; this does not)."""
    n = want.shape[0]
    apart = (np.asarray(got, np.float32) - want).reshape(n, -1)
    return np.sqrt((apart ** 2).mean(axis=1)
                   / ((want.reshape(n, -1) ** 2).mean(axis=1) + 1e-30))


def run_check(ctx: Context, engine, family, reference, host_params,
              rng) -> List[str]:
    """The check of the module's docstring on ``engine``; the names of what
    failed."""
    import jax.numpy as jnp

    cfg, check = ctx.config, ctx.config["check"]
    vocab = cfg["vocab_size"]
    Tp, Tk, R, K, F, NB = (int(check[k]) for k in (
        "prompt_tokens", "packed_tokens", "single_rows", "forced_tokens",
        "fused_steps", "fused_neighbours"))
    first_single = Tp - R
    if not 0 < Tk < first_single:
        raise BenchError("the check's prompt is too short for its parts")
    prompt = rng.integers(0, vocab, size=Tp).astype(np.int32)
    forced = rng.integers(0, vocab, size=K + 1).astype(np.int32)
    draw = lambda n: rng.integers(0, vocab, size=int(n)).astype(np.int32)
    # -- the engine first: its logits at the compared rows, and its tokens.
    # Another sequence through the slot before: prompt, a few fused steps,
    # flush. What it leaves in the slot must not show below
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
    # the fused steps run as traffic runs them: the sequence is one row
    # among NB others that are live, each in a state slot of its own, so the
    # program compared is the many-row bucket's, with real slot indices
    others = list(range(uid + 1, uid + 1 + NB))
    if others:
        engine.put(others, [draw(n) for n in rng.integers(8, 33, size=NB)])
    row = NB // 2
    live = others[:row] + [uid] + others[row:]
    own = np.asarray(engine.decode_pipeline(live).run(F)[row], np.int32)
    last = engine.put(live, [forced[K:] if u == uid else draw(1)
                             for u in live])[row]
    got.append((f"forced token after {F} fused decode steps (ragged pass)",
                last, Tp + K + F))
    ids = np.concatenate([prompt, forced[:K], own, forced[K:]])
    h_engine = np.swapaxes(engine.sequence_state(uid), 1, 2)   # [Lm, E, N]
    slots = {engine.scheduler.seqs[u].state_slot for u in live}
    engine.flush(live)
    if len(slots) != len(live):
        raise BenchError("two live sequences share a state slot")
    ctx.log(f"check: the engine ran {len(ids)} tokens ({Tk} packed, "
            f"{first_single - Tk} in paged chunk passes, {R} single, {K} "
            f"forced, {F} fused steps as row {row} of {len(live)} live "
            f"sequences, 1 forced) in {time.time() - t0:.1f} s")

    # -- then the reference over those very tokens: in float32 for the
    # logits; for the state, with its activations rounded where the program's
    # are (``act_dtype``) and the state in float32, and that one's control
    t0 = time.time()
    hp = family.reference_hp(cfg)
    weights = family.reference_weights(host_params, cfg)
    rows = jnp.asarray([r for _, _, r in got], jnp.int32)
    fused_rows = jnp.arange(Tp + K - 1, Tp + K + F - 1)
    ref = np.asarray(reference.forward_logits(
        weights, ids, hp, rows=jnp.concatenate([rows, fused_rows])))
    act = engine.spec.dtype          # where the program rounds: bfloat16
    low = getattr(jnp, check["control_state_dtype"])
    hp_state = dict(hp, unrounded=tuple(check["state_unrounded"]))
    h_ref, h_ctl = (np.asarray(reference.forward_logits(
        weights, ids, hp_state, rows=rows[-1:], with_state=True,
        act_dtype=act, state_dtype=dtype)[1]) for dtype in (None, low))
    del weights
    if not np.isfinite(ref).all():
        raise BenchError("the reference's logits are not finite")
    ctx.log(f"reference: {len(ids)} tokens three times (float32; "
            f"{jnp.dtype(act).name} activations with a float32 state; "
            f"the control, the same with the state rounded to "
            f"{check['control_state_dtype']} after every token) in "
            f"{time.time() - t0:.1f} s")

    tol = float(check["tol_logits"])
    bad: List[str] = []
    errs = []
    for n, (name, logits, _) in enumerate(got):
        logits = np.asarray(logits, np.float32)
        err = serving.rel_err(logits, ref[n])
        errs.append(err)
        if n < 2 or n >= len(got) - 1 - K or err > tol:
            ctx.log(f"check {name}: rel err {err:.2e} (tol {tol:.1e})")
        if not (np.isfinite(logits).all() and err <= tol):
            bad.append(name)
    # the fused steps give tokens: each is the reference's greedy token for
    # the engine's own history or within the logits tolerance of its best
    greedy = ref[len(got):]
    off = 0
    for i in range(F):
        want = greedy[i]
        gap = float(want.max() - want[int(own[i])])
        if gap > 0:
            off += 1
        if gap > 2 * tol * float(np.max(np.abs(want))):
            bad.append(f"fused step {i + 1}")
            ctx.log(f"check fused step {i + 1}: token {own[i]} is {gap:.3e} "
                    "under the reference's best")
    ctx.log(f"check: {len(errs)} rows compared, largest rel err "
            f"{max(errs):.2e}, median {float(np.median(errs)):.2e} (tol "
            f"{tol:.1e}); {off} of {F} fused tokens are not the reference's "
            f"greedy one (each within 2 x tol of its best unless named above)")
    # the state the timed programs left: the module's docstring
    tol_state = float(check["tol_state"])
    e_state, e_ctl = layer_errs(h_engine, h_ref), layer_errs(h_ctl, h_ref)
    show = lambda v: " ".join(f"{x:.1e}" for x in v)
    ctx.log(f"check state after the run against the reference with "
            f"{jnp.dtype(act).name} activations: in the first Mamba "
            f"layer the engine's is {e_state[0]:.2e} from it, the control's "
            f"({check['control_state_dtype']} state) {e_ctl[0]:.2e} (tol "
            f"{tol_state:.1e}); for people, every Mamba layer in order: the "
            f"engine {show(e_state)}; the control {show(e_ctl)}")
    if not (np.isfinite(h_engine).all() and e_state[0] <= tol_state):
        bad.append("state after the fused steps")
    if not e_ctl[0] > tol_state:
        bad.append(f"state control (a {check['control_state_dtype']} state "
                   "passes)")
    if bad:
        ctx.log(f"CHECK FAILED: {bad[:8]} ({len(bad)} in all)")
    return bad


class Gauges(serving.Gauges):
    """``serving.Gauges`` and, from the window's start, the most state slots
    live at once over the slots there are."""

    def __init__(self, engine):
        super().__init__(engine)
        self.slots_peak = 0

    def sample(self, frontend) -> None:
        if len(self.edges) > 1:     # the window has closed (--trace 2's tail)
            return
        super().sample(frontend)
        if self.edges:
            self.slots_peak = max(self.slots_peak,
                                  self.engine.state_slots()[0])

    def counters(self) -> Dict[str, float]:
        out = super().counters()
        total = self.engine.state_slots()[2]
        if total:
            out["state_slots_peak_share"] = self.slots_peak / total
        return out


def capture_from(capture, start_at: float) -> threading.Thread:
    """``capture`` (``--trace 2``'s) primed and taken from ``start_at``
    (``time.perf_counter``) on, in a thread, so that the sender is not held
    up; join it before the trace is read."""
    def body():
        time.sleep(max(0.0, start_at - time.perf_counter()))
        capture.prime()
        capture.start()
        time.sleep(capture.seconds)
        capture.stop()
    thread = threading.Thread(target=body, name="chipbench-capture",
                              daemon=True)
    thread.start()
    return thread


def loop(ctx: Context, served, frontend, mix, pool, seconds: float,
         traced=None, capture=None) -> dict:
    """``serve_closed_kinds.loop`` with this file's gauges, and with
    ``capture`` taken in a tail after the window (the module's docstring)."""
    ramp = float(mix["ramp_s"])
    gauges = Gauges(served.engine)
    t0 = time.perf_counter() + 0.05
    window_start = time.time() + 0.05 + ramp
    t_w0, t_w1 = t0 + ramp, t0 + ramp + seconds
    until, capturing = t_w1, None
    if traced is not None:
        traced.schedule(t_w1 - traced.seconds)
    if capture is not None:
        until = t_w1 + float(ctx.cell["trace_tail_s"])
        capturing = capture_from(capture, t_w1 + 0.5)
    time.sleep(max(0.0, t0 - time.perf_counter()))
    sent = replay.run_closed(
        serving.submitter(frontend, served), pool, int(mix["clients"]),
        until=until,
        marks=[(t_w0, lambda: gauges.edge(frontend)),
               (t_w1, lambda: gauges.edge(frontend))],
        each=lambda: gauges.sample(frontend), span=annotate)
    if traced is not None:
        traced.join()
    if capturing is not None:
        capturing.join()
    loaded = len(ctx.compiles.ended), ctx.compiles.seconds
    stats = served.engine.pipeline_stats
    steps, t_d = stats.steps, time.perf_counter()
    drained = replay.drain(sent, float(mix["drain_s"]))
    t_d = time.perf_counter() - t_d
    ctx.log(f"{mix['clients']} clients sent {len(sent)} requests; "
            f"drained {drained} in {t_d:.1f} s: {stats.steps - steps} decode "
            f"steps, {len(ctx.compiles.ended) - loaded[0]} programs compiled "
            f"or loaded ({ctx.compiles.seconds - loaded[1]:.1f} s in the "
            "backend)")
    if not drained:     # say what is left, for whoever reads the failure
        ctx.log("not finished: (status, prompt, tokens of asked, sent s "
                "before the window closed, ms to first token) " + " ".join(
                    f"({s.handle.status},{len(s.request.prompt)},"
                    f"{len(s.handle.tokens)}/{s.request.max_new_tokens},"
                    f"{t_w1 - s.sent_t:.1f},{s.handle.ttft_ms})"
                    for s in sent if not s.handle.finished))
    measured = [s for s in sent if s.sent_t < t_w1
                and ((latency.token_times(s.handle) or [t_w0])[-1] >= t_w0
                     or not s.handle.finished)]
    got = serving.summarize(ctx, served, sent, measured, t_w0, t_w1, gauges)
    got["window_start"] = window_start
    inside = [s for s in sent if t_w0 <= s.sent_t < t_w1]
    ctx.log(f"requests sent in the window: {len(inside)}, "
            f"{sum(len(s.request.prompt) for s in inside)} prompt tokens; "
            f"state slots (live, peak, total) {served.engine.state_slots()}")
    return got


def run(ctx: Context) -> Outcome:
    served = bring_up(ctx)
    mix = ctx.traffic
    if not ctx.on_chip:
        overlay = ctx.registry.module("drivers", "serve_closed_kinds").overlay
        mix = ctx.traffic = overlay(mix, ctx.config.get(
            "rehearsal_traffic", {}))
    pool = balanced.closed_pool(mix, ctx.seed, served.vocab)
    with served.engine.serving_frontend() as frontend:
        serving.warm_traffic(ctx, served, frontend)
        got = loop(ctx, served, frontend, mix, pool, float(ctx.seconds),
                   ctx.tracer, ctx.capture)
    return Outcome(correct=served.correct and got["failed"] == 0,
                   attempted=got["attempted"], failed=got["failed"],
                   window_start=got["window_start"], end_to_end=got["values"],
                   counters=got["counters"])
