"""Closed-loop serving of a model in which NO layer holds pages: every layer
keeps a state slot a sequence (power retention), and the page pool is its
scratch page.

Bring-up, check and capture are ``serve_closed_state.py``'s, called as they
are (the dense model beside a state that file was written for: weights made
in one program, the engine first and the reference after it over the
engine's own tokens, the first layer's state held against the reference with
the program's roundings and against its control). What differs is the
gauges, and with them the loop that builds them: ``serving.Gauges`` reports
the page pool's peak share and divides by the pool's pages, which here are
none. This loop samples what such a model has — state slots, decode rows,
host time a step — and reports no page metric. Every run also logs the
spread of the gates the weights drew (``families/brumby.py::gate_spread``): a
fast gate forgets within tens of tokens and hides an error in how the state
is carried.
"""

import time
from typing import Dict, List

from chipbench import serving
from chipbench.harness import Context, Outcome, annotate
from chipbench.reduce import latency
from chipbench.traffic import balanced, replay


class Gauges:
    """What the sender samples between sends, and the pipeline's counters at
    the window's edges: ``serving.Gauges`` without the pages, and from the
    window's start the most state slots live at once."""

    def __init__(self, engine):
        self.engine = engine
        self.max_inflight = 0
        self.slots_peak = 0
        self.edges: List[Dict[str, float]] = []

    def sample(self, frontend) -> None:
        if len(self.edges) > 1:     # the window has closed (--trace 2's tail)
            return
        self.max_inflight = max(self.max_inflight, frontend.outstanding)
        if self.edges:
            self.slots_peak = max(self.slots_peak,
                                  self.engine.state_slots()[0])

    def edge(self, frontend) -> None:
        st = self.engine.pipeline_stats
        self.edges.append({
            "t": time.perf_counter(), "steps": st.steps, "rows": st.tokens,
            "host_ms": st.dispatch_ms + st.host_build_ms + st.bubble_ms,
            "drain_ms": st.fetch_drain_ms,
            "outstanding": frontend.outstanding})

    def counters(self) -> Dict[str, float]:
        a, b = self.edges[0], self.edges[-1]
        steps = max(1, b["steps"] - a["steps"])
        return {"decode_steps": b["steps"] - a["steps"],
                "decode_rows_mean": (b["rows"] - a["rows"]) / steps,
                "host_ms_per_step": (b["host_ms"] - a["host_ms"]) / steps,
                "drain_ms_per_step": (b["drain_ms"] - a["drain_ms"]) / steps,
                "state_slots_peak_share":
                    self.slots_peak / self.engine.state_slots()[2],
                "backlog_start": a["outstanding"],
                "backlog_end": b["outstanding"],
                "max_inflight": self.max_inflight}


def loop(ctx: Context, served, frontend, mix, pool, seconds: float,
         traced=None, capture=None) -> dict:
    """``serve_closed_state.loop`` with this file's gauges: the window, and
    under ``--trace 2`` the same clients going on for the cell's
    ``trace_tail_s`` past its end with the capture taken there."""
    state = ctx.registry.module("drivers", "serve_closed_state")
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
        capturing = state.capture_from(capture, t_w1 + 0.5)
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
    drained = replay.drain(sent, float(mix["drain_s"]))
    ctx.log(f"{mix['clients']} clients sent {len(sent)} requests; drained "
            f"{drained}; state slots (live, peak, total) "
            f"{served.engine.state_slots()}")
    if not drained:     # say what is left, for whoever reads the failure
        ctx.log("not finished: (status, prompt, tokens of asked) " + " ".join(
            f"({s.handle.status},{len(s.request.prompt)},"
            f"{len(s.handle.tokens)}/{s.request.max_new_tokens})"
            for s in sent if not s.handle.finished))
    measured = [s for s in sent if s.sent_t < t_w1
                and ((latency.token_times(s.handle) or [t_w0])[-1] >= t_w0
                     or not s.handle.finished)]
    got = serving.summarize(ctx, served, sent, measured, t_w0, t_w1, gauges)
    got["window_start"] = window_start
    return got


def run(ctx: Context) -> Outcome:
    state = ctx.registry.module("drivers", "serve_closed_state")
    served = state.bring_up(ctx)
    family = ctx.registry.module("families", ctx.config["family"])
    ctx.log(f"gates: g = sigmoid(b_g) over the layers' KV heads, (least, "
            f"most): {family.gate_spread(served.engine)}")
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
