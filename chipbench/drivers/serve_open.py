"""Open-loop serving: requests arrive on a schedule fixed by the traffic
file and the seed, whatever the server does.

The first ``ramp_s`` seconds of arrivals are sent before the window and not
measured. Requests due inside the window are the ones measured (the same
requests at the same gaps whatever the seed, in another order): they are
drained after it (up to ``drain_s``), and one that is shed, errors or does
not finish counts as failed and has no latency.

``--trace 2``: after the measured window has closed and drained, one more
ramp of the same mix against the same frontend, and from its end the cell's
``trace_seconds`` under the program's capture.
"""

import time

from chipbench import serving
from chipbench.harness import Context, Outcome, annotate
from chipbench.traffic import generator, replay


def measure(ctx: Context, served, frontend, mix, seconds: float,
            traced=None) -> dict:
    """One ramp and window of ``mix`` against a running frontend; ``traced``
    (a ``TraceWindow``) traces the window's last ``traced.seconds``."""
    ramp = float(mix["ramp_s"])
    requests = generator.open_schedule(mix, seconds, ctx.seed, served.vocab)
    gauges = serving.Gauges(served.engine)
    t0 = time.perf_counter() + 0.05
    window_start = time.time() + 0.05 + ramp
    if traced is not None:
        traced.schedule(t0 + ramp + seconds - traced.seconds)
    sent = replay.replay_open(
        serving.submitter(frontend, served), requests, t0,
        marks=[(ramp, lambda: gauges.edge(frontend)),
               (ramp + seconds, lambda: gauges.edge(frontend))],
        each=lambda: gauges.sample(frontend), span=annotate)
    if traced is not None:
        traced.join()
    measured = [s for s in sent if s.request.measured]
    drained = replay.drain(sent, float(mix["drain_s"]))
    ctx.log(f"sent {len(sent)} requests at {mix['arrivals']['rate_per_s']}/s;"
            f" drained {drained}")
    got = serving.summarize(ctx, served, sent, measured, t0 + ramp,
                            t0 + ramp + seconds, gauges)
    got["window_start"] = window_start
    return got


def run(ctx: Context) -> Outcome:
    served = serving.bring_up(ctx)
    with served.engine.serving_frontend() as frontend:
        serving.warm_traffic(ctx, served, frontend)
        got = measure(ctx, served, frontend, ctx.traffic, float(ctx.seconds),
                      ctx.tracer)
        if ctx.capture is not None:
            ctx.log(f"--trace 2: one more ramp, then {ctx.capture.seconds} s "
                    "under the capture (the lines up to 'capture:' are of "
                    "that segment, not of the measured window)")
            ctx.capture.prime()
            measure(ctx, served, frontend, ctx.traffic, ctx.capture.seconds,
                    ctx.capture)
    return Outcome(correct=served.correct and got["failed"] == 0,
                   attempted=got["attempted"], failed=got["failed"],
                   window_start=got["window_start"],
                   end_to_end=got["values"], counters=got["counters"])
