"""``--trace 2``: the run that measures and then traces in one process. The
harness's last line on the recorded trace and capture (what
``tracer.capture_stop`` returned on a v5e chip, kept beside the trace), the
switch in ``BENCHMARK.json``, and — off the chip, tiny — each cell's own
driver, capture, reduction and readers through ``chipbench.rehearse``."""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import harness  # noqa: E402
from chipbench.harness import BenchError, Registry  # noqa: E402
from tests.chipbench.test_named import SCOPED, kept_capture  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCH["workloads"]]


class _AnyCounter(dict):
    def get(self, key, default=None):
        return 1.0

    def __getitem__(self, key):
        return 1.0


def _context(cell, **kw):
    reg = Registry()
    entry = reg.cell(cell)
    dev = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite",
                          memory_stats=lambda: {"peak_bytes_in_use": 5 << 30})
    peaks = json.load(open(os.path.join(ROOT, "chipbench", "peaks.json")))
    return harness.Context(
        registry=reg, cell=entry, config=reg.config(entry["config"]),
        traffic=reg.traffic(entry["traffic"]), seed=1, seconds=1.0,
        devices=[dev] * entry["chips"], peaks=peaks["TPU v5 lite"],
        compiles=None, t_process=0.0, on_chip=False, **kw)


def test_benchmark_declares_tracing_in_the_run():
    assert BENCH["trace_in_run"] is True
    assert list(BENCH)[:4] == ["command", "paths", "run_seconds",
                               "trace_in_run"]


@pytest.mark.parametrize("cell", CELLS)
def test_trace2_line_holds_both_sets_of_metrics(cell):
    """One last line: the cell's end-to-end metrics from the measured window
    beside every per-layer metric a reader can give, the traced window's busy
    and window seconds, and a breakdown whose kernels are told apart."""
    reg = Registry()
    window = SimpleNamespace(path=SCOPED, capture=kept_capture())
    ctx = _context(cell, capture=window)
    e2e = {m["name"] for m in reg.metrics_of(cell, "end_to_end")}
    per_layer = {m["name"] for m in reg.metrics_of(cell, "per_layer")}
    out = harness.Outcome(correct=True, attempted=3, failed=0,
                          window_start=2.5, counters=_AnyCounter(),
                          end_to_end={n: 1.0 for n in e2e - {"setup_s"}})
    line = harness.result_line(ctx, out, trace=2)
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device", "breakdown"}
    assert e2e <= set(line["metrics"]) <= e2e | per_layer
    assert line["metrics"]["setup_s"] == {"value": 2.5, "unit": "s"}
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes", "busy_s", "window_s"}
    # the recorded step is flash forward and backward: the train cells read
    # their three flash metrics from it, all under 100%
    flash = {n for n in per_layer if n.startswith("flash_")}
    assert flash <= set(line["metrics"])
    assert all(0 < line["metrics"][n]["value"] < 100 for n in flash)
    assert any("@jit(tiny_scoped_step)/jvp(flash_fwd)" in k
               for k, _ in line["breakdown"]["device_ops"])
    assert "host/sleep" in dict(line["breakdown"]["idle_gaps"])
    json.dumps(line)
    # the same view without a capture (a --trace 1 run): the metrics that
    # need one are absent, not wrong
    plain = harness.result_line(
        _context(cell, tracer=SimpleNamespace(path=SCOPED)), out, trace=1)
    assert not set(plain["metrics"]) & (e2e | flash)
    out.end_to_end.clear()
    if e2e - {"setup_s"}:
        with pytest.raises(BenchError):
            harness.result_line(ctx, out, trace=2)


def test_a_run_that_made_no_trace_gives_no_line():
    ctx = _context(CELLS[0], capture=SimpleNamespace(path=None, capture=None))
    out = harness.Outcome(correct=True, attempted=1, failed=0,
                          window_start=1.0, counters=_AnyCounter(),
                          end_to_end={"itl_p50_ms": 1.0})
    with pytest.raises(BenchError):
        harness.result_line(ctx, out, trace=2)


def test_run_takes_trace_2_and_still_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "2"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert not [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_under_trace_2_ends_in_one_well_formed_line(cell):
    """The cell's own driver at tiny widths on the CPU: the measured window,
    then the segment under the program's capture, the reduction and every
    reader. The last line has a result line's form, values withheld; the
    span readers found the program's spans."""
    reg = Registry()
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.rehearse", "--workload", cell,
         "--seconds", "2", "--trace", "2"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    assert len(lines) == 1 and proc.stdout.rstrip().endswith(lines[0])
    line = json.loads(lines[0])
    assert line["rehearsal"] is True and line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["device"] == {"platform": "cpu", "kind": "cpu",
                              "count": reg.cell(cell)["chips"]}
    names = {m["name"] for g in ("end_to_end", "per_layer")
             for m in reg.metrics_of(cell, g)}
    assert set(line["metrics"]) == names
    assert all(set(v) == {"unit", "read"} for v in line["metrics"].values())
    read = {n for n, v in line["metrics"].items() if v["read"]}
    assert {m["name"] for m in reg.metrics_of(cell, "end_to_end")} <= read
    spans = {m["name"] for m in reg.metrics_of(cell, "per_layer")
             if reg.layer_metric(m["name"])["reader"].startswith("span.")}
    # the engine thread's phases are recorded when they end: on a loaded CPU
    # a second may hold no whole one (tests/unit/test_trace_capture.py holds
    # them to tiling a step); a train step always ends inside it
    assert spans - read <= {n for n in spans if "_share." in n}
    assert not os.path.exists(os.path.join(
        ROOT, "chipbench_out", "rehearsal", cell))
