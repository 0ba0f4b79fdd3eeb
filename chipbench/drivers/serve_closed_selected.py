"""Closed-loop serving of a model that SELECTS inside latent attention (an
indexer a layer, an index-key pool beside the latent pages, attention over
the top-k chosen).

Bring-up, check, loop, gauges and capture are ``serve_closed_latent.py``'s,
called as they are: the family's ``page_layout`` funds both pools a token a
layer, its ``check_engine`` holds the engine's two pools to the
configuration, and the logits of the engine's own programs (packed and paged
passes, single tokens through the cache, the fused decode step) are held to
the reference, which selects too. What this file adds is the selection's
OWN check, after the bring-up: logits barely see a flipped key among 2,048,
so the program's indexer and ``select`` run by themselves on the engine's
own weights over ``check.index_contexts`` (``families/glm_dsa.py::
selection_readings``: chunk slots and decode rows ending at contexts of
4k-32k) against ``reference.index_readings`` on the same inputs. The run is
correct only if the two selections differ in NO position whose score lies
further than ``check.tol_index`` (in units of the spread of the row's
scores) from the row's threshold — and the control, the reference with its
index queries, keys and scores rounded to ``check.index_control_dtype``,
differs in some. Every row also has to keep exactly as many positions as
the reference does.

And the window. An admitted prompt of 8k-32k tokens is prefilled whole, 1-5 s
in which no row decodes, so tokens arrive in bursts of half a second between
silences, and 45 s hold some 18 of them. Two things follow, and ``serve``
answers both (the cell's file says why by its numbers; a third, how much of
a pass's choices the seed's router sends to this chip's experts, is the
family's: ``families/glm_dsa.py::balance``):

- which prompts fall into the window, and which answers end in it, is worth
  a tenth of ``serve_tok_s`` (``balanced.closed_pool`` balances a stretch of
  32, the window takes 18). So every run is dealt the pool in ONE order and
  pairing, the cell's ``pool_order``; the seed draws the token ids and the
  weights, as it does for the warm-up burst of every cell. Every seed is
  then given the same work.
- a burst on this or that side of an edge is a twentieth of one window's
  count, and a run that is half a percent slower than another moves the
  edge at 85 s by a burst's length. So ``serve_tok_s`` is the mean over
  EVERY window of ``--seconds`` that opens in the cell's
  ``window_opens_over_s`` after the ramp (``windows_mean``): the loop runs
  to the last window's end, and a token counts by the share of the windows
  it lies in. The counters beside it are of that whole stretch.
"""

import dataclasses
import importlib
import time

import numpy as np

from chipbench import serving
from chipbench.harness import Context, Outcome
from chipbench.reduce import latency
from chipbench.traffic import balanced, generator


def check_selection(ctx: Context, served):
    cfg = ctx.config
    family = ctx.registry.module("families", cfg["family"])
    reference = importlib.import_module(
        "chipbench.reference." + family.REFERENCE)
    got = family.selection_readings(
        served.engine, reference, family.reference_hp(cfg), cfg["check"],
        generator.rng_for(ctx.seed, "selection"))
    check = cfg["check"]
    ctx.log(f"check selection: {got['rows']} query rows in chunk slots "
            f"ending at contexts {got['contexts']} and a decode row at "
            f"each; of {got['kept']} positions kept {got['flipped']} are "
            f"not the reference's, the furthest of them {got['worst']:.2e} "
            f"of the row's score spread from its threshold (tol "
            f"{check['tol_index']:.1e}: {got['differ']} over it), "
            f"{got['miscounted']} rows keep another number than the "
            f"reference's; the "
            f"control, the reference's indexer in "
            f"{check['index_control_dtype']}, reads {got['control_worst']:.2e}"
            f" ({got['control']} over)")
    bad = []
    if got["differ"] or got["miscounted"]:
        bad.append("selection")
    if not got["control"] > 0:
        bad.append("selection control (a rounded indexer passes)")
    if bad:
        ctx.log(f"CHECK FAILED: {bad}")
    return dataclasses.replace(served, correct=served.correct and not bad)


def dealt(mix, order: int, seed: int, vocab: int):
    """The mix's pool in the order and pairing ``traffic/balanced.py`` deals
    at ``order`` (the cell's ``pool_order``: one for every run), the token
    ids drawn from the run's ``seed`` as the generator draws them."""
    rng = generator.rng_for(seed, "tokens")
    return [generator.Request(
        0.0, rng.integers(0, vocab, size=len(r.prompt)).astype(np.int32),
        r.max_new_tokens) for r in balanced.closed_pool(mix, order, 2)]


class Remembering:
    """A frontend that keeps the handles it gives out; everything else is
    the frontend's own."""

    def __init__(self, frontend):
        self._frontend = frontend
        self.handles = []

    def submit(self, *args, **kwargs):
        self.handles.append(self._frontend.submit(*args, **kwargs))
        return self.handles[-1]

    def __getattr__(self, name):
        return getattr(self._frontend, name)


def windows_mean(ctx: Context, handles, t_w0: float, seconds: float,
                 opens: float) -> float:
    """Tokens a second in a window of ``seconds``, in the mean over EVERY
    window that opens in the ``opens`` seconds from ``t_w0``: a token that
    arrived ``tau`` after ``t_w0`` lies in the windows opened between
    ``tau - seconds`` and ``tau``, of which ``[0, opens]`` holds its share
    (why: the module's docstring). Four single windows go to the log."""
    tau = np.fromiter((t - t_w0 for h in handles
                       for t in latency.token_times(h)), float)
    share = np.clip(np.minimum(opens, tau) - np.maximum(0.0, tau - seconds),
                    0.0, None) / opens
    mean = float(share.sum()) / seconds
    single = [float(((tau >= a) & (tau < a + seconds)).sum()) / seconds
              for a in np.linspace(0.0, opens, 4)]
    ctx.log(f"windows of {seconds:.0f} s opened over {opens:.0f} s: tokens/s "
            f"of those opened at {np.linspace(0.0, opens, 4).round(1)} s "
            f"{np.round(single, 1)}, of all of them in the mean {mean:.1f}")
    return mean


def serve(ctx: Context, served) -> Outcome:
    """``serve_closed_latent.serve`` over the cell's one order of the pool,
    ``serve_tok_s`` from every window of ``--seconds`` that opens in the
    cell's ``window_opens_over_s`` after the ramp (the loop runs to the
    last one's end; its counters are of that whole stretch)."""
    mix = ctx.traffic
    seconds, opens = float(ctx.seconds), float(ctx.cell["window_opens_over_s"])
    if not ctx.on_chip:
        overlay = ctx.registry.module("drivers", "serve_closed_kinds").overlay
        mix = ctx.traffic = overlay(mix, ctx.config.get(
            "rehearsal_traffic", {}))
        opens = min(opens, 1.0)
    loop = ctx.registry.module("drivers", "serve_closed_state").loop
    pool = dealt(mix, int(ctx.cell["pool_order"]), ctx.seed, served.vocab)
    with served.engine.serving_frontend() as frontend:
        serving.warm_traffic(ctx, served, frontend)
        kept = Remembering(frontend)
        got = loop(ctx, served, kept, mix, pool, seconds + opens,
                   ctx.tracer, ctx.capture)
    t_w0 = got["window_start"] - time.time() + time.perf_counter()
    got["values"]["serve_tok_s"] = windows_mean(
        ctx, kept.handles, t_w0, seconds, opens)
    return Outcome(correct=served.correct and got["failed"] == 0,
                   attempted=got["attempted"], failed=got["failed"],
                   window_start=got["window_start"], end_to_end=got["values"],
                   counters=got["counters"])


def run(ctx: Context) -> Outcome:
    latent = ctx.registry.module("drivers", "serve_closed_latent")
    return serve(ctx, check_selection(ctx, latent.bring_up(ctx)))
