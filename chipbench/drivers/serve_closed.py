"""Closed-loop serving: ``clients`` callers, each sending its next request
when its last has finished, which keeps the replica saturated without a
rate to tune. The loop runs ``ramp_s`` seconds before the window (so the
clients' requests are out of step with each other by its start), then the
window; what is in flight at its end is drained. Measured are the requests
in flight at any time in the window.

``--trace 2``: after that the clients run again, for ``ramp_s`` and the
cell's ``trace_seconds`` more, the last under the program's capture."""

import time

from chipbench import serving
from chipbench.harness import Context, Outcome, annotate
from chipbench.reduce import latency
from chipbench.traffic import generator, replay


def loop(ctx: Context, served, frontend, mix, pool, seconds: float,
         traced=None) -> dict:
    """The clients for ``ramp_s`` and a window of ``seconds``, then the
    drain; ``traced`` (a ``TraceWindow``) traces the window's last
    ``traced.seconds``."""
    ramp = float(mix["ramp_s"])
    gauges = serving.Gauges(served.engine)
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
    return got


def run(ctx: Context) -> Outcome:
    served = serving.bring_up(ctx)
    mix = ctx.traffic
    pool = generator.closed_pool(mix, ctx.seed, served.vocab)
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
