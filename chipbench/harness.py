"""What every cell shares: the registry that finds a cell's files by name,
the device gate, the compile counter, the traced window and the result line.

Nothing here knows a cell, a configuration or a metric by name. A cell is
``BENCHMARK.json``'s entry plus ``workloads/<cell>.json``; its configuration
is ``configs/<config>.json``; its traffic ``traffic/<traffic>.json``; its
driver ``drivers/<driver>.py``; each per-layer metric
``layer_metrics/<metric>.json``, which names a reader under ``readers/``.
"""

from __future__ import annotations

import glob
import importlib.util
import json
import os
import shutil
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: host spans the benchmark writes into the profiler's trace; an idle gap of
#: the device is attributed to one of these or stays unattributed
ANNOTATIONS = ("harness submit", "waiting for arrival", "waiting for clients",
               "fetch loss", "harness dispatch")


class BenchError(Exception):
    """The run cannot give a result (exit code 2, no result line)."""


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


class Registry:
    """Finds everything by name under ``<root>/chipbench``."""

    def __init__(self, root: str = ROOT):
        self.root = root
        self.dir = os.path.join(root, "chipbench")
        self.benchmark = load_json(os.path.join(root, "BENCHMARK.json"))
        self._modules: Dict[str, Any] = {}

    def _data(self, kind: str, name: str) -> Dict[str, Any]:
        path = os.path.join(self.dir, kind, name + ".json")
        if not os.path.isfile(path):
            raise BenchError(f"no {kind} file for {name!r}: {path}")
        return load_json(path)

    def cell(self, name: str) -> Dict[str, Any]:
        entries = [w for w in self.benchmark["workloads"]
                   if w["name"] == name]
        if not entries:
            raise BenchError(f"BENCHMARK.json has no workload {name!r}")
        return {**self._data("workloads", name), **entries[0]}

    def config(self, name: str) -> Dict[str, Any]:
        return self._data("configs", name)

    def traffic(self, name: str) -> Dict[str, Any]:
        return self._data("traffic", name)

    def layer_metric(self, name: str) -> Dict[str, Any]:
        return self._data("layer_metrics", name)

    def metrics_of(self, cell: str, group: str) -> List[Dict[str, Any]]:
        """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
        return [m for m in self.benchmark[group]
                if "workloads" not in m or cell in m["workloads"]]

    def module(self, kind: str, name: str):
        """``<kind>/<name>.py`` of this tree, loaded once."""
        key = f"{kind}/{name}"
        if key not in self._modules:
            path = os.path.join(self.dir, kind, name + ".py")
            if not os.path.isfile(path):
                raise BenchError(f"no {kind} module {name!r}: {path}")
            spec = importlib.util.spec_from_file_location(
                f"chipbench_{kind}_{name}", path)
            module = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(module)
            self._modules[key] = module
        return self._modules[key]

    def driver(self, name: str) -> Callable:
        return self.module("drivers", name).run

    def reader(self, name: str) -> Callable:
        """``trace.idle_share`` is ``idle_share`` of ``readers/trace.py``;
        a bare ``counter`` is ``read`` of ``readers/counter.py``."""
        module, _, fn = name.partition(".")
        return getattr(self.module("readers", module), fn or "read")


# --------------------------------------------------------------------------- #

def gate_devices(chips: int, peaks_path: str):
    """The cell's chips, or no run: platform ``tpu``, a device kind the
    peaks table has, exactly ``chips`` devices."""
    import jax
    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        raise BenchError(f"needs a TPU, found platform {d0.platform!r}")
    peaks = load_json(peaks_path)
    if d0.device_kind not in peaks:
        raise BenchError(f"device kind {d0.device_kind!r} is not in "
                         f"{peaks_path}: add its published peaks")
    if len(devices) != chips:
        raise BenchError(f"the cell needs {chips} chip(s), found "
                         f"{len(devices)}")
    return devices, peaks[d0.device_kind]


class CompileCounter:
    """Backend compiles (a persistent-cache hit counts: it is a program that
    was not ready) with the time each ended, from jax's monitoring events —
    it sees module-level jits that ``engine.compiles`` cannot."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        from jax import monitoring
        self.ended: List[float] = []
        self.seconds = 0.0
        monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event: str, secs: float, **_):
        if event == self.EVENT:
            self.ended.append(time.perf_counter())
            self.seconds += secs

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t in self.ended if t0 <= t < t1)


class TraceWindow:
    """A few seconds of the window under ``jax.profiler``, written inside
    the checkout. ``start``/``stop`` may be called from a helper thread
    (:meth:`schedule`), so that a load generator is not held up."""

    def __init__(self, directory: str, seconds: float):
        self.directory = directory
        self.seconds = seconds
        self.path: Optional[str] = None
        self._thread: Optional[threading.Thread] = None
        self.running = False

    def start(self) -> None:
        import jax
        shutil.rmtree(self.directory, ignore_errors=True)
        os.makedirs(self.directory, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # the harness's spans, not frames
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.directory, profiler_options=options)
        self.running = True

    def stop(self) -> None:
        import jax
        if not self.running:
            return
        self.running = False
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(self.directory, "plugins", "profile",
                                       "*", "*.xplane.pb"))
        if len(found) != 1:
            raise BenchError(f"expected one trace under {self.directory}, "
                             f"found {found}")
        self.path = found[0]

    def schedule(self, start_at: float, clock=time.perf_counter) -> None:
        """Trace ``[start_at, start_at + seconds]`` from a thread."""
        def body():
            time.sleep(max(0.0, start_at - clock()))
            self.start()
            time.sleep(self.seconds)
            self.stop()
        self._thread = threading.Thread(target=body, name="chipbench-trace",
                                        daemon=True)
        self._thread.start()

    def join(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None


class CaptureWindow(TraceWindow):
    """``--trace 2``: a few seconds of the same traffic AFTER the measured
    window has closed, under the program's own capture control
    (``tracer.capture_start`` / ``capture_stop``: the profiler and the
    program's spans over one interval, on one clock). Until :meth:`prime`
    nothing of it has run: the run is a ``--trace 0`` run up to there."""

    def __init__(self, directory: str, seconds: float):
        super().__init__(directory, seconds)
        self.capture = None            # what capture_stop returned

    def prime(self) -> None:
        """Start and stop the profiler once and throw that trace away, so
        that the cost of its first start falls into no number."""
        shutil.rmtree(self.directory, ignore_errors=True)
        primer = TraceWindow(os.path.join(self.directory, "primer"), 0.0)
        primer.start()
        primer.stop()
        shutil.rmtree(primer.directory, ignore_errors=True)

    def start(self) -> None:
        from deepspeed_tpu.monitor.trace import tracer
        tracer.capture_start(self.directory)
        self.running = True

    def stop(self) -> None:
        from deepspeed_tpu.monitor.trace import tracer
        if not self.running:
            return
        self.running = False
        self.capture = tracer.capture_stop()
        self.path = self.capture.trace_path

    def discard(self) -> None:
        shutil.rmtree(self.directory, ignore_errors=True)


@dataclass
class Context:
    """What a driver is given."""
    registry: Registry
    cell: Dict[str, Any]
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    seed: int
    seconds: float
    devices: List[Any]
    peaks: Dict[str, float]
    compiles: CompileCounter
    t_process: float                       # time.time() at process start
    tracer: Optional[TraceWindow] = None   # set in a --trace 1 run
    capture: Optional[CaptureWindow] = None   # set in a --trace 2 run
    on_chip: bool = True                   # False only in the CPU rehearsal

    def log(self, msg: str) -> None:
        """A line for people. The CPU rehearsal prints none: they carry
        times and rates, which a CPU run may not give."""
        if not self.on_chip:
            return
        print(f"[chipbench +{time.time() - self.t_process:7.1f}s] {msg}",
              flush=True)


@dataclass
class Outcome:
    """What a driver gives back."""
    correct: bool
    attempted: int
    failed: int
    window_start: float                    # time.time() when the window began
    end_to_end: Dict[str, float]           # every metric the driver can give
    counters: Dict[str, float] = field(default_factory=dict)


def annotate(name: str):
    import jax
    assert name in ANNOTATIONS, name
    return jax.profiler.TraceAnnotation(name)


def memory_peak_bytes(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats()
        if not stats or "peak_bytes_in_use" not in stats:
            raise BenchError("the backend reports no device memory statistics")
        peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks)


def load_view(ctx: Context, out: Outcome, trace: int,
              values: Dict[str, float]):
    """The reduced trace of a traced run and the view its readers are given.
    In a ``--trace 2`` run the view also holds what ``capture_stop`` returned
    (``capture``: the program's records on the trace's clock, its counters'
    deltas, the trace's path) and the names the program gave its device work
    (``op_names``), and the program's spans join the harness's annotations
    on the host line, so that an idle gap goes to either."""
    from chipbench.reduce import xplane
    traced = ctx.capture if trace == 2 else ctx.tracer
    if traced.path is None:
        raise BenchError("the driver made no trace")
    ctx.log(f"reducing {traced.path} "
            f"({os.path.getsize(traced.path) / 2**20:.1f} MiB)")
    tr = xplane.load(traced.path, ANNOTATIONS)
    view = {"trace": tr, "counters": out.counters, "values": values,
            "peaks": ctx.peaks, "config": ctx.config, "traffic": ctx.traffic,
            "cell": ctx.cell, "chips": len(ctx.devices)}
    if trace == 2:
        from chipbench.reduce import hlo_names
        capture = ctx.capture.capture
        tr.host.extend(xplane.Event(r[1], r[2], r[3] - r[2])
                       for r in capture.records if r[0] == "X")
        tr.host.sort(key=lambda e: e.start_ns)
        view["capture"] = capture
        view["op_names"] = hlo_names.load(traced.path)
        ctx.log(f"capture: host clock to trace clock skew "
                f"{capture.skew_ns * 1e-3:.1f} us over "
                f"{(capture.stop_ns - capture.start_ns) * 1e-9:.3f} s, "
                f"anchors known to {capture.anchor_uncertainty_ns * 1e-3:.1f}"
                f" us; {len(capture.records)} records of the program; "
                f"counters {capture.counters}; HLO of "
                f"{len(view['op_names'])} programs in the trace")
    return tr, view


def result_line(ctx: Context, out: Outcome, trace: int) -> Dict[str, Any]:
    """The one JSON object the run ends with. ``--trace 0`` carries the
    cell's end-to-end metrics, ``--trace 1`` its per-layer metrics, read by
    their readers from the driver's counters and the reduced trace.
    ``--trace 2`` carries both: end-to-end metrics and counters from the
    measured window, which no profiler disturbed, and device-trace and span
    metrics from the capture made after it."""
    reg, name = ctx.registry, ctx.cell["name"]
    d0 = ctx.devices[0]
    device: Dict[str, Any] = {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(ctx.devices),
        "memory_peak_bytes": memory_peak_bytes(ctx.devices)}
    metrics: Dict[str, Dict[str, Any]] = {}
    line: Dict[str, Any] = {"correct": bool(out.correct),
                            "attempted": int(out.attempted),
                            "failed": int(out.failed), "metrics": metrics,
                            "device": device}
    values = dict(out.end_to_end)
    values["setup_s"] = out.window_start - ctx.t_process
    if trace != 1:
        for m in reg.metrics_of(name, "end_to_end"):
            if m["name"] not in values:
                raise BenchError(f"the driver gave no {m['name']!r}")
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    if not trace:
        return line

    from chipbench.reduce import xplane
    tr, view = load_view(ctx, out, trace, values)
    t0, t1 = xplane.window(tr)
    busy = xplane.busy_seconds(tr)
    device["busy_s"] = sum(busy.values()) / len(busy)
    device["window_s"] = (t1 - t0) * 1e-9
    spans = {}
    for ev in tr.host:
        n, sec = spans.get(ev.name, (0, 0.0))
        spans[ev.name] = (n + 1, sec + ev.dur_ns * 1e-9)
    most = sorted(spans.items(), key=lambda kv: -kv[1][1])[:16]
    ctx.log(f"trace: window {device['window_s']:.3f} s, busy "
            f"{device['busy_s']:.3f} s; host spans in it (count, seconds) "
            f"{ {k: (n, round(s, 3)) for k, (n, s) in most} }")
    rows = [r for r in xplane.module_table(tr) if r["mean_ms"] >= 0.05]
    for row in rows[:8]:
        ctx.log(f"trace: {row['module']}: {row['calls']} runs, mean "
                f"{row['mean_ms']:.3f} ms, total {row['total_ms']:.1f} ms")
    for m in reg.metrics_of(name, "per_layer"):
        spec = reg.layer_metric(m["name"])
        value = reg.reader(spec["reader"])(view, **spec.get("args", {}))
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    line["breakdown"] = {"device_ops": xplane.top_ops(tr, 10,
                                                      view.get("op_names")),
                         "idle_gaps": xplane.idle_gaps(tr, 10)}
    return line


def run_cell(workload: str, seed: int, seconds: float, trace: int,
             t_process: float, registry: Optional[Registry] = None) -> int:
    reg = registry or Registry()
    try:
        cell = reg.cell(workload)
        devices, peaks = gate_devices(
            int(cell["chips"]), os.path.join(reg.dir, "peaks.json"))
    except BenchError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    from deepspeed_tpu.utils.compile_cache import setup_compile_cache
    # every program is persisted, however fast it compiled: the second run
    # in a checkout must find all of them
    cache_dir = setup_compile_cache(min_compile_time_secs=0.0)
    trace = int(trace)
    ctx = Context(registry=reg, cell=cell, config=reg.config(cell["config"]),
                  traffic=reg.traffic(cell["traffic"]), seed=seed,
                  seconds=seconds, devices=devices, peaks=peaks,
                  compiles=CompileCounter(), t_process=t_process)
    if trace:
        window = CaptureWindow if trace == 2 else TraceWindow
        traced = window(
            os.path.join(reg.root, "chipbench_out", "trace", workload),
            float(cell.get("trace_seconds", 2.0)))
        if trace == 2:
            ctx.capture = traced
        else:
            ctx.tracer = traced
    ctx.log(f"cell {workload}: config {cell['config']}, traffic "
            f"{cell['traffic']}, driver {cell['driver']}, seed {seed}, "
            f"{seconds} s, trace {int(trace)}; device {devices[0].device_kind}"
            f" x{len(devices)}; compile cache {cache_dir}")
    try:
        out = reg.driver(cell["driver"])(ctx)
        line = result_line(ctx, out, trace)
    except BenchError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    finally:
        if ctx.capture is not None:
            ctx.capture.discard()       # reduced, or of no use
    ctx.log(f"compiles in this process: {len(ctx.compiles.ended)} "
            f"({ctx.compiles.seconds:.1f} s in the backend)")
    print(json.dumps(line), flush=True)
    return 0
