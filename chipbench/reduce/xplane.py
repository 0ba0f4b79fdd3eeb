"""From a profiler trace (``.xplane.pb``) to numbers.

What a trace of ``TPU v5 lite`` under jax 0.9.0 looks like (recorded with
``chipbench/tools/record_tiny_trace.py``; PERF.md section 3):

- one plane per chip, named ``/device:TPU:<n>``; the host is ``/host:CPU``;
- on a device plane the line ``XLA Modules`` holds one event per execution of
  a jitted program, named ``jit_<function>(<fingerprint>)``; the line
  ``XLA Ops`` holds the operations of those programs in execution order,
  each named by its HLO text (``%fusion.1 = bf16[...] fusion(...)``); the
  line ``Async XLA Ops`` holds the spans of asynchronous copies, slices and
  collective-permutes, which overlap the operations and are not counted as
  busy;
- a collective on ``XLA Ops`` is an event with a collective opcode
  (``all-gather``, ``collective-permute-done``, ...) or a ``fusion`` the
  compiler made of one: ``kind=kCustom, calls=%all-reduce-scatter.1``, or an
  instruction named ``%async-collective-start.57`` / ``-done.57`` (the
  cell-4 trace of PR 24: 224 ms of 2391 in such fusions, 35 ms under a
  collective opcode);
- a Pallas (Mosaic) kernel is an ``XLA Ops`` event whose HLO text holds
  ``custom_call_target="tpu_custom_call"``. Nothing in it names the kernel;
- ``jax.profiler.TraceAnnotation`` spans of the benchmark are events on the
  host plane under the name given. Host and device timestamps share an
  origin to within a millisecond or two (the device ran 1.25 ms "before" its
  dispatch in the recorded trace), so a gap is attributed to a host span only
  by overlap, and short gaps mostly stay ``unattributed``.

The functions below the loader work on plain lists of :class:`Event`, so the
tests can check them on events written by hand.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ASYNC_LINE = "Async XLA Ops"
MOSAIC_MARK = 'custom_call_target="tpu_custom_call"'
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_CALLS = re.compile(r"kind=kCustom, calls=%([a-z][a-z\-]*)")
_ASYNC_PAIR = re.compile(r"^%(.*)-(start|done)((?:\.\d+)?)$")
_MODULE = re.compile(r"^(.*?)\((\d+)\)$")


@dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass
class DeviceTrace:
    ops: List[Event] = field(default_factory=list)
    modules: List[Event] = field(default_factory=list)
    async_ops: List[Event] = field(default_factory=list)
    _self_times: Optional[List[Tuple[Event, float]]] = None

    def self_times(self) -> List[Tuple[Event, float]]:
        """:func:`self_times` of ``ops``, worked out once (every reader of a
        traced run asks for it, over some 100,000 operations)."""
        if self._self_times is None:
            self._self_times = self_times(self.ops)
        return self._self_times


@dataclass
class Trace:
    devices: Dict[int, DeviceTrace] = field(default_factory=dict)
    host: List[Event] = field(default_factory=list)


def load(path: str, host_names: Sequence[str] = ()) -> Trace:
    """Read an ``.xplane.pb``. Of the host plane only events whose name is in
    ``host_names`` are kept (the benchmark's own annotations)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    trace = Trace()
    names: Dict[str, str] = {}
    keep = set(host_names)
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = trace.devices.setdefault(int(m.group(1)), DeviceTrace())
            for line in plane.lines:
                if line.name == OPS_LINE:
                    into = dev.ops
                elif line.name == MODULES_LINE:
                    into = dev.modules
                elif line.name == ASYNC_LINE:
                    into = dev.async_ops
                else:
                    continue
                for ev in line.events:
                    name = ev.name
                    name = names.setdefault(name, name)
                    into.append(Event(name, ev.start_ns, ev.duration_ns))
        elif plane.name == HOST_PLANE and keep:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in keep:
                        trace.host.append(
                            Event(ev.name, ev.start_ns, ev.duration_ns))
    for dev in trace.devices.values():
        dev.ops.sort(key=lambda e: (e.start_ns, -e.dur_ns))
        dev.modules.sort(key=lambda e: e.start_ns)
    trace.host.sort(key=lambda e: e.start_ns)
    return trace


# --------------------------------------------------------------------------- #
# names
# --------------------------------------------------------------------------- #

def opcode(name: str) -> str:
    """The HLO opcode of an ``XLA Ops`` event name, ``""`` if it has none."""
    _, _, rhs = name.partition(" = ")
    m = _OPCODE.search(" " + rhs if rhs else name)
    return m.group(1) if m else ""


def instruction(name: str) -> str:
    """``%fusion.1`` of ``%fusion.1 = ...``."""
    return name.partition(" = ")[0].strip()


def is_mosaic(name: str) -> bool:
    return MOSAIC_MARK in name


def is_collective(name: str) -> bool:
    """A collective by its opcode (``-start`` and ``-done`` included), or a
    fusion the compiler made of one (see the top of this file)."""
    code = opcode(name)
    if any(code == c or code == c + "-start" or code == c + "-done"
           for c in COLLECTIVES):
        return True
    if code != "fusion":
        return False
    if instruction(name).startswith("%async-collective-"):
        return True
    m = _CALLS.search(name)
    return bool(m) and any(c in m.group(1) for c in COLLECTIVES)


def module_name(name: str) -> Tuple[str, str]:
    """``("jit_step", "123")`` of ``jit_step(123)``."""
    m = _MODULE.match(name)
    return (m.group(1), m.group(2)) if m else (name, "")


# --------------------------------------------------------------------------- #
# intervals
# --------------------------------------------------------------------------- #

def union(events: Iterable[Event]) -> List[Tuple[float, float]]:
    """Merged, sorted ``(start, end)`` intervals covered by ``events``."""
    spans = sorted((e.start_ns, e.end_ns) for e in events)
    out: List[Tuple[float, float]] = []
    for s, e in spans:
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def covered_ns(spans: Sequence[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in spans)


def self_times(ops: Sequence[Event]) -> List[Tuple[Event, float]]:
    """Each operation with the part of its duration not covered by
    operations nested inside it (a ``while`` or ``call`` spans its body)."""
    order = sorted(ops, key=lambda e: (e.start_ns, -e.dur_ns))
    out: List[List] = []
    stack: List[int] = []
    for ev in order:
        while stack and out[stack[-1]][0].end_ns <= ev.start_ns:
            stack.pop()
        if stack and ev.end_ns <= out[stack[-1]][0].end_ns:
            out[stack[-1]][1] -= ev.dur_ns
        out.append([ev, ev.dur_ns])
        stack.append(len(out) - 1)
    return [(ev, max(0.0, t)) for ev, t in out]


def window(trace: Trace) -> Tuple[float, float]:
    """The traced window on the devices' clock: from the first operation to
    the end of the last, over all chips."""
    starts = [d.ops[0].start_ns for d in trace.devices.values() if d.ops]
    ends = [max(e.end_ns for e in d.ops) for d in trace.devices.values()
            if d.ops]
    if not starts:
        raise ValueError("the trace holds no device operation")
    return min(starts), max(ends)


# --------------------------------------------------------------------------- #
# metrics
# --------------------------------------------------------------------------- #

def busy_seconds(trace: Trace) -> Dict[int, float]:
    return {i: covered_ns(union(d.ops)) * 1e-9
            for i, d in trace.devices.items()}


def idle_share(trace: Trace) -> float:
    """1 - busy / window on the chip that was busiest least (worst chip)."""
    t0, t1 = window(trace)
    busy = busy_seconds(trace)
    return 1.0 - min(busy.values()) / ((t1 - t0) * 1e-9)


def op_share(trace: Trace, pick) -> float:
    """Self time of the operations ``pick(name)`` accepts over busy time,
    summed over chips."""
    picked = busy = 0.0
    for dev in trace.devices.values():
        busy += covered_ns(union(dev.ops))
        picked += sum(t for ev, t in dev.self_times() if pick(ev.name))
    return picked / busy if busy else 0.0


def collective_spans(dev: DeviceTrace) -> List[Tuple[float, float]]:
    """When an asynchronous collective was in flight on this chip: the
    collective events of the async line, and on the operations line the time
    from the end of a collective's ``-start`` to the start of its ``-done``
    (paired by the instruction's name and number). Merged and sorted."""
    spans = [e for e in dev.async_ops if is_collective(e.name)]
    started: Dict[Tuple[str, str], float] = {}
    for ev in dev.ops:
        m = _ASYNC_PAIR.match(instruction(ev.name))
        if not m or not is_collective(ev.name):
            continue
        key = (m.group(1), m.group(3))
        if m.group(2) == "start":
            started[key] = ev.end_ns
        elif key in started:
            begin = started.pop(key)
            spans.append(Event(ev.name, begin, max(0.0, ev.start_ns - begin)))
    return union(spans)


def overlap_ns(a: Sequence[Tuple[float, float]],
               b: Sequence[Tuple[float, float]]) -> float:
    """Nanoseconds covered by both of two sorted lists of disjoint spans."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        total += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def exposed_collective_share(trace: Trace) -> float:
    """Time a chip gave to collectives and to nothing else, over the window,
    on the chip where it is largest: the self time of collective operations
    on the operations line (which is serial: a synchronous collective, a
    fusion made of one, the wait in a ``-done``), plus the time that line
    stood idle while an asynchronous collective was in flight."""
    t0, t1 = window(trace)
    worst = 0.0
    for dev in trace.devices.values():
        exposed = sum(t for ev, t in dev.self_times()
                      if is_collective(ev.name))
        busy = union(dev.ops)
        gaps = [(end, start) for (_, end), (start, _) in zip(busy, busy[1:])]
        exposed += overlap_ns(gaps, collective_spans(dev))
        worst = max(worst, exposed / (t1 - t0))
    return worst


def module_table(trace: Trace) -> List[Dict]:
    """Per jitted program (name and fingerprint): executions, mean and total
    device milliseconds, over all chips; most executions first. A chip's
    first and last execution are left out where it has three or more: the
    trace clips the one that was running when it began and when it ended
    (a 501 ms train step read 375 ms and 4 ms)."""
    rows: Dict[str, List[float]] = {}
    for dev in trace.devices.values():
        whole = dev.modules[1:-1] if len(dev.modules) >= 3 else dev.modules
        for ev in whole:
            rows.setdefault(ev.name, []).append(ev.dur_ns)
    table = [{"module": k, "calls": len(v), "mean_ms": sum(v) / len(v) * 1e-6,
              "total_ms": sum(v) * 1e-6} for k, v in rows.items()]
    table.sort(key=lambda r: (-r["calls"], -r["total_ms"]))
    return table


def module_ms(trace: Trace, min_mean_ms: float = 0.0) -> Optional[float]:
    """Mean device milliseconds of the program executed most often. Programs
    whose mean is under ``min_mean_ms`` (eager helper operations of a
    microsecond) are passed over."""
    table = [r for r in module_table(trace) if r["mean_ms"] >= min_mean_ms]
    return table[0]["mean_ms"] if table else None


def top_ops(trace: Trace, n: int = 10,
            op_names: Optional[Dict[str, Dict[str, str]]] = None) -> List[List]:
    """The ``n`` operations with most self time, named
    ``<program>/<instruction> <opcode>``; seconds summed over chips. With
    ``op_names`` (``hlo_names.load`` of a ``--trace 2`` capture) the name
    ends in ``@`` and the scopes the program gave the operation."""
    total: Dict[str, float] = {}
    for dev in trace.devices.values():
        mods = dev.modules
        k = 0
        for ev, t in dev.self_times():
            while k + 1 < len(mods) and mods[k + 1].start_ns <= ev.start_ns:
                k += 1
            inside = mods and mods[k].start_ns <= ev.start_ns <= mods[k].end_ns
            prog = module_name(mods[k].name)[0] if inside else "?"
            code = "mosaic" if is_mosaic(ev.name) else opcode(ev.name)
            key = f"{prog}/{instruction(ev.name)} {code}"[:120]
            if op_names is not None and inside:
                named = op_names.get(mods[k].name, {}).get(
                    instruction(ev.name).lstrip("%"))
                if named:
                    key += " @" + "/".join(named.split("/")[-3:-1])
            total[key] = total.get(key, 0.0) + t * 1e-9
    rows = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v] for k, v in rows]


def idle_gaps(trace: Trace, n: int = 10) -> List[List]:
    """Idle time of the first chip by what the host was doing: a gap of a
    millisecond or more between operations goes to the host span (the
    benchmark's annotations; in a ``--trace 2`` run the program's spans too)
    that overlaps most of it (at least half; of spans that overlap it alike,
    as nested ones do, the shortest), every other gap to ``unattributed`` by
    length. Seconds, largest first."""
    first = trace.devices[min(trace.devices)]
    spans = union(first.ops)
    total: Dict[str, float] = {}
    for (_, end), (start, _) in zip(spans, spans[1:]):
        gap = start - end
        best, best_ns, best_dur = None, 0.0, 0.0
        if gap >= 1e6:
            for ev in trace.host:
                if ev.start_ns >= start:
                    break
                over = min(start, ev.end_ns) - max(end, ev.start_ns)
                if over > best_ns or (over == best_ns and over > 0.0
                                      and ev.dur_ns < best_dur):
                    best, best_ns, best_dur = ev.name, over, ev.dur_ns
        if best is None or best_ns < 0.5 * gap:
            best = ("unattributed (<0.1 ms)" if gap < 1e5 else
                    "unattributed (0.1-1 ms)" if gap < 1e6 else
                    "unattributed (1-10 ms)" if gap < 1e7 else
                    "unattributed (>10 ms)")
        total[best] = total.get(best, 0.0) + gap * 1e-9
    rows = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v] for k, v in rows]
