"""Find an open-loop cell's knee once, on the chip: the same engine, the
cell's mix at several rates and, at each rate, in several orders of the same
arrivals and lengths (``--seeds``), one window after another.

    python3 chipbench/sweep.py --workload <cell> --rates 3,3.5 \\
        --seeds 101,102,103 --seconds 30

For each window it prints the requests in flight at its start and end and
their most, the generator's lateness, the latencies with the percentiles
around the two tails, and the tokens per second. A rate holds if in every
order the backlog at the end is no larger than at the start, no more
requests are in flight than the engine has rows, and the generator keeps
up; the knee is the highest rate that holds. The cell's traffic file takes
its rate as a number, and PERF.md the table. Not part of a run of the
benchmark.
"""

import time

_T_PROCESS = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--out", default="chiprun_out/sweep.json")
    args = ap.parse_args(argv)

    from chipbench import harness, serving
    from deepspeed_tpu.utils.compile_cache import setup_compile_cache
    reg = harness.Registry()
    try:
        cell = reg.cell(args.workload)
        devices, peaks = harness.gate_devices(
            int(cell["chips"]), os.path.join(reg.dir, "peaks.json"))
    except harness.BenchError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    setup_compile_cache(min_compile_time_secs=0.0)
    ctx = harness.Context(
        registry=reg, cell=cell, config=reg.config(cell["config"]),
        traffic=reg.traffic(cell["traffic"]), seed=0,
        seconds=args.seconds, devices=devices, peaks=peaks,
        compiles=harness.CompileCounter(), t_process=_T_PROCESS)
    measure = reg.module("drivers", cell["driver"]).measure
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    served = serving.bring_up(ctx)
    rows = []
    with served.engine.serving_frontend() as frontend:
        serving.warm_traffic(ctx, served, frontend)
        for rate in (float(r) for r in args.rates.split(",")):
            mix = dict(ctx.traffic, arrivals=dict(ctx.traffic["arrivals"],
                                                  rate_per_s=rate))
            for seed in (int(x) for x in args.seeds.split(",")):
                ctx.seed = seed         # orders the arrivals; weights stay
                got = measure(ctx, served, frontend, mix, args.seconds)
                row = {"rate_per_s": rate, "seed": seed,
                       "attempted": got["attempted"], "failed": got["failed"],
                       **got["values"], **got["counters"], **got["detail"]}
                rows.append(row)
                print("SWEEP " + json.dumps(row), flush=True)
                with open(args.out, "w") as f:      # kept if the call is cut
                    json.dump(rows, f, indent=1)
    with open(args.out, "w") as f:
        json.dump({"workload": args.workload, "seconds": args.seconds,
                   "seeds": args.seeds, "correct": served.correct,
                   "device": devices[0].device_kind, "rows": rows}, f,
                  indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
