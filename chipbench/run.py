"""Run one cell of the benchmark once.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1|2>

A new process each time: it refuses to run without the cell's TPU chips
(exit code 2, no result line, no CPU fallback), makes the weights on the
device from ``--seed``, checks the program's outputs against the plain
reference outside the window, warms the cell's own shapes, measures for
``--seconds`` and prints one JSON object as the last line of its standard
output. Everything for people — medians, counts, the generator's lateness —
is on earlier lines. ``BENCH_RUN`` in the environment is not read.

``--trace 0`` measures and prints the end-to-end metrics; ``--trace 1`` traces
a few seconds inside the window and prints the per-layer metrics;
``--trace 2`` is a ``--trace 0`` run up to the moment the window closes, then
traces a few seconds more of the same traffic in the same process and prints
both sets of metrics in its one last line.
"""

import time

_T_PROCESS = time.time()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1, 2), default=0)
    args = ap.parse_args(argv)
    from chipbench.harness import run_cell
    return run_cell(args.workload, args.seed, args.seconds, args.trace,
                    _T_PROCESS)


if __name__ == "__main__":
    sys.exit(main())
