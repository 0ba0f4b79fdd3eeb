"""Measure a cell the way a bound is set: two sets of runs with the same
seeds in both, every run a process of its own, then (optionally) one traced
run; print each metric's median and spread per set.

    python3 chipbench/tools/measure_sets.py --workload <cell> --seconds 45 \\
        --seeds 101,102,103,104,105,106 --traced-seed 107 --out chiprun_out/sets

A spread is the interquartile range over the median, the quartiles as
``statistics.quantiles(values, n=4)`` gives them. This process never touches
jax: a chip belongs to the run it starts. It stops at the first run that
fails. Not part of a run of the benchmark.
"""

import argparse
import json
import os
import statistics
import subprocess
import sys

HERE = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def one_run(workload, seed, seconds, trace, log_path):
    """Run the cell once; its output goes to ``log_path``. Returns the
    result line as a dict, or ``None`` if the run failed."""
    cmd = [sys.executable, os.path.join(HERE, "chipbench", "run.py"),
           "--workload", workload, "--seed", str(seed),
           "--seconds", str(seconds), "--trace", str(trace)]
    with open(log_path, "w") as log:
        rc = subprocess.run(cmd, cwd=HERE, stdout=log,
                            stderr=subprocess.STDOUT).returncode
    with open(log_path) as log:
        lines = log.read().splitlines()
    print(f"rc={rc} {os.path.basename(log_path)}: {lines[-1] if lines else ''}",
          flush=True)
    if rc != 0 or not lines:
        return None
    return json.loads(lines[-1])


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--traced-seed", type=int, default=None)
    ap.add_argument("--out", default="chiprun_out/sets")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    os.makedirs(args.out, exist_ok=True)
    sets = []
    for k in (1, 2):
        runs = []
        for seed in seeds:
            line = one_run(args.workload, seed, args.seconds, 0, os.path.join(
                args.out, f"{args.workload}.set{k}.seed{seed}.log"))
            if line is None or not line["correct"]:
                return 1
            runs.append(line)
        sets.append(runs)
    for name in sets[0][0]["metrics"]:
        for k, runs in enumerate(sets, 1):
            values = [r["metrics"][name]["value"] for r in runs]
            if name == "setup_s":           # the first run of all compiles
                values = values[1:] if k == 1 else values
            print(f"SET {k} {name}: median {statistics.median(values)} "
                  f"spread {spread(values):.5f} values {values}", flush=True)
    if args.traced_seed is not None:
        line = one_run(args.workload, args.traced_seed, args.seconds, 1,
                       os.path.join(args.out, f"{args.workload}.traced.log"))
        if line is None:
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
