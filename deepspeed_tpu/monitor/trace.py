"""Unified span tracing: one Perfetto-exportable timeline across every lane.

PRs 3-6 turned the hot paths into overlapped async pipelines (double-buffered
decode, multi-step train dispatch, fetch/step/upload offload groups, rolling
async checkpoints), but observability stayed flat ``(name, value, step)``
aggregates — you could see that a bubble existed, never *where* it sat
relative to a dispatch, a D2H drain, upload-lane work, or a committer stall.
This module is the timeline: every pipeline lane records **spans** (named
intervals with monotonic-clock endpoints) into a per-thread preallocated ring
buffer, and an exporter writes Chrome-trace/Perfetto JSON where each lane
(step loop, prefetch producer, host-Adam workers, upload lane, AIO swapper,
checkpoint writers, committer) is its own named track — the overlap structure
becomes visually auditable in https://ui.perfetto.dev.

Design constraints (the regimes PRs 3-6 gated must survive tracing ON):

- **zero device syncs**: spans only ever read ``time.perf_counter()``; no
  recording path touches a jax array. jaxlint JL008 statically polices that
  span context managers in hot-path modules never *enclose* a blocking fetch
  outside the policed drain names, so tracing can't quietly reintroduce the
  per-step host sync the async loops removed.
- **no allocation-heavy formatting on the hot path**: a record is one small
  tuple stored into a preallocated slot (``ring[i % cap] = rec``); names are
  interned literals at the call sites; all JSON formatting happens at export
  time, off the steady-state loop.
- **bounded memory**: each thread keeps only the newest ``ring_size`` spans.
  That bound is also the **flight recorder** — after a crash the rings hold
  the final steps' timeline, dumped to ``trace_crash.json`` by the
  fault-injection kill/raise hooks and fatal engine teardown (and the normal
  rings export from an atexit hook), so a preempted or wedged run leaves a
  readable timeline.
- **true no-op when disabled**: ``add()`` is a two-instruction early return
  and ``span()`` hands back a shared no-op context manager; hot-path call
  sites additionally guard on ``tracer.enabled`` so disabled runs don't even
  stamp clocks for the trace.

Two recording APIs, matching two call-site shapes:

- ``tracer.add(name, t0, t1, lane=..., **args)`` — record a COMPLETED span
  from ``perf_counter`` timestamps the call site already took for its stats
  counters. This is the hot-path form: the five ``monitor/`` stat classes
  and the tracer aggregate the *same* measured intervals (one clock, one
  measurement — the stats are per-window aggregations of exactly the spans
  the timeline shows, not a parallel set of hand-rolled timers).
- ``with tracer.span(name, lane=..., **args):`` — context-manager form for
  worker lanes (producers, writers, committers, kernel chunks) where the
  span IS the timing.

``instant(name)`` marks a point event (faults, admissions); ``counter(name,
value)`` records a Perfetto counter track sample (queue depths).

``stage(name)`` is the one always-on interval: a stage of SET-UP, whose wall
time goes to ``totals["setup/<name>_s"]`` whether or not tracing is on (and
to a span on lane ``setup`` when it is), and which names the phase that
``utils/compile_cache.py`` charges jax's traces, lowerings and compiles to
(docs/OBSERVABILITY.md, "Set-up and compiles").

Tracks: by default a span lands on its recording THREAD's track (threads in
this tree are descriptively named: ``dstpu-prefetch``, ``dstpu-hostopt_*``,
``dstpu-offload-upload``, ``ckpt-writer_*``, ``dstpu-ckpt-commit``). A
``lane="train/step"`` argument overrides the track name — used by the main
thread, which multiplexes several logical lanes (dispatch/drain phases,
checkpoint snapshots) that should render as their own rows. Lanes are scoped
per thread (two threads recording the same lane name get two tracks), so B/E
nesting within a track is always well-formed.

Enable via ``DSTPU_TRACE=<dir>`` (arms in ``deepspeed_tpu.initialize`` and
the v2 inference engine) or ``config.monitor.trace`` — docs/OBSERVABILITY.md
walks the taxonomy, the Perfetto workflow and what tracing costs.

**Captures** put these spans and the device's work on one clock.
``tracer.capture_start(dir)`` turns the rings on, starts ``jax.profiler`` and
writes an anchor ``TraceAnnotation`` whose ``perf_counter`` time is known;
``tracer.capture_stop()`` writes a second anchor, stops the profiler, puts
``enabled`` back and returns a :class:`Capture`: the ``.xplane.pb``, the ring
records of the interval with their endpoints on the trace's host clock
(offset from the first anchor, drift from the second), and what the
always-on counters (:meth:`Tracer.bump`) gained. Device work is never timed
with host stamps: it is named (``jax.named_scope``, program names) and read
from the device trace; host work is a span here.
"""

from __future__ import annotations

import atexit
import glob
import json
import os
import re
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, Iterator, List, Optional, Tuple

from deepspeed_tpu.utils.logging import logger
from deepspeed_tpu.utils.threads import make_lock

_ENV_VAR = "DSTPU_TRACE"
_ENV_RING = "DSTPU_TRACE_RING"
_ENV_REQ_LANES = "DSTPU_TRACE_REQ_LANES"

#: default spans retained per thread (the flight-recorder window)
DEFAULT_RING_SIZE = 16384

#: per-request ``serve/req/u<uid>`` lanes exported under their OWN track —
#: beyond this window (newest by last activity), retired requests' lanes are
#: recycled onto a bounded pool of ``serve/req/recycled/<k>`` tracks (the
#: exporter-side mirror of the dead-ring sweep: a long serving run must not
#: grow one timeline row per uid forever)
DEFAULT_REQ_LANE_WINDOW = 64

#: lanes subject to the recycling window
_REQ_LANE_RE = re.compile(r"^serve/req/u\d+$")

# record kinds (Chrome trace phase at export: span -> B/E pair)
_SPAN, _INSTANT, _COUNTER = "X", "i", "C"

#: the top-level stages of set-up and the compile phase each belongs to
#: (:meth:`Tracer.stage`, :meth:`Tracer.phase`)
STAGE_PHASE = {"engine_init": "build", "state_build": "build",
               "warmup": "warmup", "remat_fit": "warmup",
               "first_step": "warmup"}

#: the two ``TraceAnnotation`` events a capture writes into the profiler's
#: trace; their ``perf_counter`` times are known, so they map one clock onto
#: the other
CAPTURE_ANCHOR = "dstpu/capture/anchor"


#: dead threads' rings retained for export/crash dumps (a finished prefetch
#: producer's spans must still reach the timeline) — beyond this, the OLDEST
#: dead rings are pruned at ring registration so thread churn (per-epoch
#: producers, rebuilt writer pools) cannot grow memory without bound
MAX_DEAD_RINGS = 32


class _Ring:
    """One thread's preallocated record ring. Single writer (the owning
    thread), lock-free: ``buf[idx % cap] = rec; idx += 1``. Readers (export)
    snapshot racily — a slot is either an old record or a new one, never a
    torn value (CPython list-slot stores are atomic)."""

    __slots__ = ("buf", "idx", "cap", "thread_name", "thread_id", "thread")

    def __init__(self, cap: int, thread: threading.Thread):
        self.buf: List[Optional[tuple]] = [None] * cap
        self.idx = 0
        self.cap = cap
        self.thread_name = thread.name
        self.thread_id = thread.ident or 0
        self.thread = thread   # liveness probe for dead-ring pruning

    def add(self, rec: tuple) -> None:
        self.buf[self.idx % self.cap] = rec
        self.idx += 1

    def snapshot(self) -> List[tuple]:
        """Records in insertion order, oldest kept first (newest ``cap``)."""
        n = self.idx
        if n <= self.cap:
            return [r for r in self.buf[:n] if r is not None]
        i = n % self.cap
        return [r for r in self.buf[i:] + self.buf[:i] if r is not None]


class _NoopSpan:
    """Shared do-nothing context manager handed out while tracing is
    disabled — zero per-call allocation on the disabled path."""

    __slots__ = ()
    seconds = 0.0

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False


_NOOP = _NoopSpan()


class Span:
    """Context manager recording one interval on exit; ``.seconds`` is valid
    after exit (call sites may feed it to their stats counters)."""

    __slots__ = ("_tracer", "name", "lane", "args", "t0", "t1", "_ann")

    def __init__(self, tracer: "Tracer", name: str, lane: Optional[str],
                 args: Optional[dict]):
        self._tracer = tracer
        self.name = name
        self.lane = lane
        self.args = args
        self.t0 = 0.0
        self.t1 = 0.0
        self._ann = None

    def __enter__(self) -> "Span":
        annotation = self._tracer._annotation
        if annotation is not None:
            # a capture runs: the span shows in the profiler's own viewer too
            self._ann = annotation(self.name)
            self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
            self._ann = None
        self._tracer._record((_SPAN, self.name, self.t0, self.t1, self.lane,
                              self.args))
        return False

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


class Stage:
    """One open stage of set-up (:meth:`Tracer.stage`); ``.seconds`` is what
    it was charged, valid after exit."""

    __slots__ = ("_tracer", "name", "path", "phase", "top", "t0", "carved",
                 "seconds")

    def __init__(self, tracer: "Tracer", name: str):
        self._tracer = tracer
        self.name = name
        self.path = name
        self.phase = "build"
        self.top = True
        self.t0 = 0.0
        self.carved = 0.0      # seconds of top-level stages opened inside
        self.seconds = 0.0

    def __enter__(self) -> "Stage":
        tr = self._tracer
        with tr._totals_lock:
            stack = tr._stages
            self.top = self.name in STAGE_PHASE or not stack
            if self.top:
                self.phase = STAGE_PHASE.get(self.name, "build")
            else:
                self.path = f"{stack[-1].path}/{self.name}"
                self.phase = stack[-1].phase
            stack.append(self)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = time.perf_counter()
        tr = self._tracer
        with tr._totals_lock:
            stack = tr._stages
            if self in stack:       # not after a reset() in between
                del stack[stack.index(self):]
            self.seconds = max(0.0, (t1 - self.t0) - self.carved)  # jaxlint: disable=JL001 -- a stage's body blocks on the device work it started
            if self.top:
                for outer in stack:
                    outer.carved += self.seconds
                if self.name in ("warmup", "first_step"):
                    tr._warmed = True
            key = f"setup/{self.path}_s"
            tr.totals[key] = tr.totals.get(key, 0.0) + self.seconds
        if tr.enabled:
            tr.add("setup/" + self.path, self.t0, t1, lane="setup")
        return False


@dataclass
class Capture:
    """What :meth:`Tracer.capture_stop` returns. Times ending in ``_ns`` are
    on the host clock of the profiler's trace (``ProfileData`` event times)."""
    trace_path: str
    start_ns: float                 # the first anchor
    stop_ns: float                  # the second anchor
    #: ``(kind, name, t0_ns, t1_ns, lane, args, thread_name)`` of every ring
    #: record that overlaps the interval (not clipped to it)
    records: List[tuple] = field(default_factory=list)
    #: what each always-on counter (:meth:`Tracer.bump`) gained
    counters: Dict[str, float] = field(default_factory=dict)
    #: ``trace_ns = start_ns + (perf_s - perf_start) * 1e9 * drift``
    perf_start: float = 0.0
    drift: float = 1.0
    #: how far a mapping by the first anchor alone would have put the second
    #: anchor from where the trace has it
    skew_ns: float = 0.0
    #: half the ``perf_counter`` bracket around each anchor's entry
    anchor_uncertainty_ns: float = 0.0

    def to_ns(self, perf_s: float) -> float:
        return self.start_ns + (perf_s - self.perf_start) * 1e9 * self.drift

    def spans(self, *names: str) -> List[Tuple[str, float, float]]:
        """``(name, t0_ns, t1_ns)`` of the spans called one of ``names``,
        clipped to the captured interval, in start order."""
        out = [(r[1], max(r[2], self.start_ns), min(r[3], self.stop_ns))
               for r in self.records if r[0] == _SPAN and r[1] in names]
        return sorted((s for s in out if s[2] > s[1]), key=lambda s: s[1])


class Tracer:
    """The process-wide tracer (module singleton: :data:`tracer`)."""

    def __init__(self):
        self.enabled = False
        self.trace_dir = ""
        self.ring_size = DEFAULT_RING_SIZE
        self.req_lane_window = DEFAULT_REQ_LANE_WINDOW
        self._rings: List[_Ring] = []
        self._local = threading.local()
        self._reg_lock = make_lock("monitor.trace.registry")
        self._atexit_installed = False
        self._crash_path: Optional[str] = None
        # one simultaneous (perf_counter, unix) pair: trace_merge.py maps
        # every file's perf-based timestamps onto one wall-clock axis with it
        self._clock_sync = (time.perf_counter(), time.time())
        #: always-on cumulative counters (:meth:`bump`); a capture returns
        #: what they gained over its interval
        self.totals: Dict[str, float] = {}
        self._totals_lock = make_lock("monitor.trace.totals")
        #: the open stages of set-up, outermost first (:meth:`stage`), and
        #: whether a ``warmup`` or ``first_step`` stage has closed yet; both
        #: under the totals lock
        self._stages: List[Stage] = []
        self._warmed = False
        self._capture_lock = make_lock("monitor.trace.capture")
        self._capture: Optional[dict] = None      # state of a running capture
        self._captures = 0
        self._annotation = None     # jax.profiler.TraceAnnotation in a capture

    # ------------------------------------------------------------------ #
    # configuration
    # ------------------------------------------------------------------ #

    def configure(self, trace_dir: str = "", enabled: Optional[bool] = None,
                  ring_size: Optional[int] = None,
                  req_lane_window: Optional[int] = None) -> "Tracer":
        """Enable (or reconfigure) tracing. ``trace_dir`` nonempty implies
        enabled and is where the exporter + flight recorder write; an empty
        dir with ``enabled=True`` records rings without an export target
        (tests, in-process overhead measurement). ``req_lane_window`` bounds
        how many per-request ``serve/req/u<uid>`` lanes export under their
        own track (older ones recycle onto a pooled track set)."""
        if trace_dir:
            self.trace_dir = trace_dir
        if ring_size:
            self.ring_size = max(16, int(ring_size))
        if req_lane_window is not None:
            self.req_lane_window = max(0, int(req_lane_window))
        if enabled is None:
            enabled = bool(trace_dir) or self.enabled
        self.enabled = bool(enabled)
        if self.enabled and not self._atexit_installed:
            self._atexit_installed = True
            atexit.register(self._atexit_export)
        return self

    def reset(self) -> None:
        """Drop every ring and disable (tests). Threads re-register their
        rings lazily on the next record."""
        with self._reg_lock:
            self._rings = []
        self._local = threading.local()
        self.enabled = False
        self.trace_dir = ""
        self.ring_size = DEFAULT_RING_SIZE
        self.req_lane_window = DEFAULT_REQ_LANE_WINDOW
        self._crash_path = None
        self._clock_sync = (time.perf_counter(), time.time())
        self._annotation = None
        with self._totals_lock:
            self._stages = []
            self._warmed = False

    # ------------------------------------------------------------------ #
    # recording
    # ------------------------------------------------------------------ #

    def _ring(self) -> _Ring:
        ring = getattr(self._local, "ring", None)
        if ring is None:
            ring = _Ring(self.ring_size, threading.current_thread())
            self._local.ring = ring
            with self._reg_lock:
                # registration is the rare, already-locked path: prune the
                # OLDEST dead rings beyond the retention bound here so
                # thread churn never grows the registry without bound
                dead = [r for r in self._rings if not r.thread.is_alive()]
                if len(dead) > MAX_DEAD_RINGS:
                    drop = set(map(id, dead[:len(dead) - MAX_DEAD_RINGS]))
                    self._rings = [r for r in self._rings
                                   if id(r) not in drop]
                self._rings.append(ring)
        return ring

    def _record(self, rec: tuple) -> None:
        if self.enabled:
            self._ring().add(rec)

    def register_thread(self) -> None:
        """Pre-register the calling thread's ring so later records are
        lock-free appends. A thread's FIRST record otherwise acquires the
        registry lock at whatever call site it happens to land on — callers
        that record under their own locks use this to keep the registry
        acquisition outside them (lock-order hygiene; the locksan scenario tests
        demand every observed acquisition order be statically explained)."""
        if self.enabled:
            self._ring()

    def add(self, name: str, t0: float, t1: float, lane: Optional[str] = None,
            **args: Any) -> None:
        """Record a completed span from ``time.perf_counter()`` endpoints the
        call site already measured (the zero-extra-clock hot-path form)."""
        if not self.enabled:
            return
        self._ring().add((_SPAN, name, t0, t1, lane, args or None))

    def span(self, name: str, lane: Optional[str] = None, **args: Any):
        """Context manager recording ``name`` over the with-body. Returns a
        shared no-op when disabled."""
        if not self.enabled:
            return _NOOP
        return Span(self, name, lane, args or None)

    def instant(self, name: str, lane: Optional[str] = None, **args: Any) -> None:
        if not self.enabled:
            return
        now = time.perf_counter()
        self._ring().add((_INSTANT, name, now, now, lane, args or None))

    def counter(self, name: str, value: float, lane: Optional[str] = None) -> None:
        if not self.enabled:
            return
        now = time.perf_counter()
        self._ring().add((_COUNTER, name, now, now, lane,
                          {"value": float(value)}))

    def bump(self, name: str, value: float = 1.0) -> None:
        """Add to an always-on cumulative counter (compiles, cache loads):
        counted whether or not tracing is on, for sites that run rarely. A
        capture reports what each gained over its interval."""
        with self._totals_lock:
            self.totals[name] = self.totals.get(name, 0.0) + value

    def note(self, name: str, value: float) -> None:
        """Set an always-on value that is chosen, not counted (what an
        engine's checkpointed layers keep): the newest choice stands."""
        with self._totals_lock:
            self.totals[name] = float(value)

    # ------------------------------------------------------------------ #
    # set-up: where a process's start goes, by stage
    # ------------------------------------------------------------------ #

    def stage(self, name: str) -> Stage:
        """Context manager naming the stage of set-up the program is in.
        ALWAYS ON: on exit ``setup/<name>_s`` in :attr:`totals` gains the
        stage's wall time (a stage blocks on the device work it started
        before it closes); under ``enabled`` the interval is also a span on
        lane ``setup``. It closes on an exception like on a return.

        A name of :data:`STAGE_PHASE` is a top-level stage: opened inside
        another stage it is no child of it, and every stage open around it
        is charged NET of it (an engine's ``engine_init`` net of the
        ``warmup`` its constructor runs; a ``first_step`` net of the
        ``state_build`` and ``remat_fit`` inside it), so the top-level
        stages add up to wall time. Any other name is a child of the
        innermost open stage — ``setup/<parent>/<name>_s``, inside the
        parent's seconds — or, with none open, a top-level stage of phase
        ``build``.

        The stack is process-wide, under the totals lock: set-up runs on one
        thread, and what another thread compiles meanwhile (a warm-up that
        waits for a worker) is charged to the stage that is open
        (:meth:`phase`). Two threads that open stages at once would nest
        into each other."""
        return Stage(self, name)

    def phase(self) -> str:
        """The compile phase of this moment (``utils/compile_cache.py``
        charges what jax traces, lowers and compiles to it): ``build`` or
        ``warmup`` by the innermost open stage; with none open ``before``
        until a ``warmup`` or ``first_step`` stage has closed once in the
        process (the caller's own programs), ``traffic`` from then on."""
        with self._totals_lock:
            if self._stages:
                return self._stages[-1].phase
            return "traffic" if self._warmed else "before"

    def setup_events(self, step: int = 0) -> List[Tuple[str, float, int]]:
        """The ``setup/*`` and ``compile/*`` totals as ``(name, value,
        step)`` monitor events: the engines' monitor writes carry them to
        ``MonitorMaster``'s sinks and the ``/metrics`` exporter."""
        with self._totals_lock:
            return [(name, float(value), step)
                    for name, value in sorted(self.totals.items())
                    if name.startswith(("setup/", "compile/"))]

    # ------------------------------------------------------------------ #
    # captures: the rings and the profiler over one interval, on one clock
    # ------------------------------------------------------------------ #

    def _anchor(self) -> Tuple[float, float]:
        """Write one anchor annotation; its ``perf_counter`` time (the middle
        of the bracket around its entry) and half the bracket's width."""
        ann = self._annotation(CAPTURE_ANCHOR)
        a = time.perf_counter()
        ann.__enter__()
        b = time.perf_counter()
        ann.__exit__(None, None, None)
        return 0.5 * (a + b), 0.5 * (b - a)  # jaxlint: disable=JL001 -- brackets a host annotation, no device work

    def capture_start(self, directory: str) -> None:
        """Start a capture: rings on, ``jax.profiler`` tracing into
        ``directory/capture_<n>`` (python tracer off, host tracer level 2),
        first anchor written. May be called from a helper thread, any number
        of times in a process, one capture at a time."""
        import jax
        with self._capture_lock:
            if self._capture is not None:
                raise RuntimeError("a capture is already running")
            # a directory of its own: the profiler names its output by the
            # second, so two captures in one second would share a file
            self._captures += 1
            directory = f"{directory}{os.sep}capture_{self._captures:03d}"
            os.makedirs(directory, exist_ok=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0     # spans, not python frames
            options.host_tracer_level = 2
            jax.profiler.start_trace(directory, profiler_options=options)
            self._annotation = jax.profiler.TraceAnnotation
            with self._totals_lock:
                totals = dict(self.totals)
            state = {"directory": directory, "was_enabled": self.enabled,
                     "totals": totals}
            self.enabled = True
            state["perf_start"], state["bracket"] = self._anchor()
            self._capture = state

    def capture_stop(self) -> Capture:
        """Stop the capture: second anchor, profiler stopped, ``enabled`` put
        back to what it was, and the :class:`Capture` returned."""
        import jax
        with self._capture_lock:
            state = self._capture
            if state is None:
                raise RuntimeError("no capture is running")
            perf_stop, bracket = self._anchor()
            self.enabled = state["was_enabled"]
            self._annotation = None
            self._capture = None
            jax.profiler.stop_trace()
            with self._totals_lock:
                counters = {k: v - state["totals"].get(k, 0.0)
                            for k, v in self.totals.items()}
        new = _xplanes(state["directory"])
        if len(new) != 1:
            raise RuntimeError(f"expected one trace under "
                               f"{state['directory']}, found {new}")
        anchors = _anchor_times_ns(new[0])
        if len(anchors) < 2:
            raise RuntimeError(f"{new[0]} holds {len(anchors)} of the "
                               f"capture's two anchors")
        perf_start = state["perf_start"]
        start_ns, stop_ns = anchors[0], anchors[-1]
        span_ns = (perf_stop - perf_start) * 1e9
        cap = Capture(trace_path=new[0], start_ns=start_ns, stop_ns=stop_ns,
                      counters=counters, perf_start=perf_start,
                      drift=(stop_ns - start_ns) / span_ns if span_ns else 1.0,
                      skew_ns=(stop_ns - start_ns) - span_ns,
                      anchor_uncertainty_ns=1e9 * max(bracket,
                                                      state["bracket"]))
        with self._reg_lock:
            rings = list(self._rings)
        for ring in rings:
            for kind, name, t0, t1, lane, args in ring.snapshot():
                if t1 >= perf_start and t0 <= perf_stop:
                    cap.records.append((kind, name, cap.to_ns(t0),
                                        cap.to_ns(t1), lane, args,
                                        ring.thread_name))
        logger.info(
            f"capture: {1e-9 * (stop_ns - start_ns):.3f} s, "
            f"{len(cap.records)} records; host clock to trace clock: skew "
            f"{cap.skew_ns * 1e-3:.1f} us over the interval (drift "
            f"{cap.drift - 1.0:+.2e}), anchors known to "
            f"{cap.anchor_uncertainty_ns * 1e-3:.1f} us; {new[0]}")
        return cap

    # ------------------------------------------------------------------ #
    # aggregation (the stats classes' view of the same measurements)
    # ------------------------------------------------------------------ #

    def summary(self) -> Dict[str, Tuple[int, float]]:
        """``{span name: (count, total seconds)}`` over everything currently
        retained — the derived-aggregation view the monitor stat classes
        mirror per window (tests cross-check the two against each other)."""
        out: Dict[str, Tuple[int, float]] = {}
        with self._reg_lock:
            rings = list(self._rings)
        for ring in rings:
            for rec in ring.snapshot():
                if rec[0] != _SPAN:
                    continue
                _, name, t0, t1, _, _ = rec
                cnt, tot = out.get(name, (0, 0.0))
                out[name] = (cnt + 1, tot + (t1 - t0))
        return out

    def iter_records(self) -> Iterator[tuple]:
        """Snapshot every retained raw record ``(kind, name, t0, t1, lane,
        args)`` across all rings — tests assert on request flow
        chains (spans sharing a ``trace_id`` arg) without exporting."""
        with self._reg_lock:
            rings = list(self._rings)
        for ring in rings:
            for rec in ring.snapshot():
                yield rec

    # ------------------------------------------------------------------ #
    # export
    # ------------------------------------------------------------------ #

    def _recycle_req_lanes(self, snaps) -> Dict[str, str]:
        """Remap retired ``serve/req/u<uid>`` lanes onto a bounded
        recycled-track pool. Keep/retire is decided over the UNION of all
        rings (a request's lane is written from several threads — engine,
        prefill worker, health; a per-ring window would keep a named track
        in one ring while recycling the same uid in another, splitting one
        request across rows and growing named rows O(window x rings)): the
        newest ``req_lane_window`` lanes (by last recorded activity
        anywhere) keep their name; every older lane is greedily
        interval-packed onto ``serve/req/recycled/<k>`` such that no two
        time-overlapping requests share a slot — per-thread tracks render
        a subset of a slot's lanes, so B/E nesting stays well-formed.
        Mirrors the dead-ring sweep: a long run's timeline stays bounded
        in named rows, not one per uid forever."""
        out: Dict[str, str] = {}
        window = self.req_lane_window
        extents: Dict[str, Tuple[float, float]] = {}
        for _ring, snap in snaps:
            for rec in snap:
                lane = rec[4]
                if not lane or not _REQ_LANE_RE.match(lane):
                    continue
                t0, t1 = rec[2], rec[3]
                if t1 <= t0:           # match the exporter's epsilon E
                    t1 = t0 + 1e-9
                lo, hi = extents.get(lane, (t0, t1))
                extents[lane] = (min(lo, t0), max(hi, t1))
        if len(extents) <= window:
            return out
        by_recent = sorted(extents, key=lambda l: extents[l][1],
                           reverse=True)
        keep = set(by_recent[:window])
        retired = sorted((l for l in extents if l not in keep),
                         key=lambda l: extents[l][0])
        pools: List[float] = []            # last span end per recycled track
        for lane in retired:
            lo, hi = extents[lane]
            slot = None
            for k, end in enumerate(pools):
                if end <= lo:              # equal-ts boundary is safe: the
                    slot = k               # sort ties close E before B
                    break
            if slot is None:
                pools.append(hi)
                slot = len(pools) - 1
            else:
                pools[slot] = max(pools[slot], hi)
            out[lane] = f"serve/req/recycled/{slot}"
        return out

    def _events(self) -> List[dict]:
        """Chrome-trace event list: metadata naming each track, then B/E
        pairs (plus instants/counters), globally sorted so every track's
        stack nests. Tie rules at equal ts: E closes before B opens, longer
        B's open first (outer before inner), and record order breaks the
        remaining ties — zero-duration spans (coarse perf_counter ticks)
        get an epsilon-long E so a span's end can never sort ahead of its
        own begin.

        Spans whose args carry a ``trace_id`` additionally emit Perfetto
        FLOW events (``ph`` s/t/f, one chain per trace_id) binding the
        request's hops — router placement, prefill, KV handoff, decode
        stints, failover migration — into one causal chain across lanes and
        threads (and, through ``scripts/trace_merge.py``, across files)."""
        pid = os.getpid()
        with self._reg_lock:
            rings = list(self._rings)
        snaps = [(ring, ring.snapshot()) for ring in rings]
        lane_map = self._recycle_req_lanes(snaps)
        tids: Dict[Tuple[int, Optional[str]], int] = {}
        meta: List[dict] = [{"ph": "M", "name": "process_name", "pid": pid,
                             "tid": 0, "args": {"name": "deepspeed_tpu"}}]
        body: List[Tuple[float, int, float, int, dict]] = []
        # trace_id -> [(t0, record idx, tid, ts_us)] of its spans
        flows: Dict[Any, List[Tuple[float, int, int, float]]] = {}

        def tid_for(ring: _Ring, lane: Optional[str]) -> int:
            key = (ring.thread_id, lane)
            tid = tids.get(key)
            if tid is None:
                tid = len(tids) + 1
                tids[key] = tid
                meta.append({"ph": "M", "name": "thread_name", "pid": pid,
                             "tid": tid,
                             "args": {"name": lane or ring.thread_name}})
            return tid

        idx = 0
        for ring, snap in snaps:
            for rec in snap:
                kind, name, t0, t1, lane, args = rec
                if lane is not None and lane_map:
                    lane = lane_map.get(lane, lane)
                tid = tid_for(ring, lane)
                ts0 = t0 * 1e6
                if kind == _SPAN and args and "trace_id" in args:
                    flows.setdefault(args["trace_id"], []).append(
                        (t0, idx, tid, ts0))
                if kind == _SPAN:
                    # coarse clocks can stamp t1 == t0; the E must still
                    # land strictly after its own B
                    if t1 <= t0:
                        t1 = t0 + 1e-9
                    dur = t1 - t0
                    b = {"ph": "B", "name": name, "pid": pid, "tid": tid,
                         "ts": ts0}
                    if args:
                        b["args"] = args
                    # equal (ts, dur) B's: LATER record first — a nested CM
                    # records the inner span before the outer, so record
                    # order descending puts the outer's B ahead
                    body.append((ts0, 1, -dur, -idx, b))
                    # equal-ts E's: earlier record first (inner closed first)
                    body.append((t1 * 1e6, 0, 0.0, idx,
                                 {"ph": "E", "name": name, "pid": pid,
                                  "tid": tid, "ts": t1 * 1e6}))
                elif kind == _INSTANT:
                    ev = {"ph": "i", "s": "t", "name": name, "pid": pid,
                          "tid": tid, "ts": ts0}
                    if args:
                        ev["args"] = args
                    body.append((ts0, 2, 0.0, idx, ev))
                else:  # counter
                    body.append((ts0, 2, 0.0, idx,
                                 {"ph": "C", "name": name, "pid": pid,
                                  "tid": tid, "ts": ts0, "args": args or {}}))
                idx += 1
        # flow chains: one s -> t... -> f sequence per trace_id, each event
        # anchored at its hop-span's begin (rank 2: it sorts after the B it
        # binds to). Single-hop ids emit nothing — a chain needs two ends.
        for flow_id, hops in flows.items():
            if len(hops) < 2:
                continue
            hops.sort(key=lambda h: (h[0], h[1]))
            last = len(hops) - 1
            for k, (_t0, ridx, tid, ts0) in enumerate(hops):
                ph = "s" if k == 0 else ("f" if k == last else "t")
                ev = {"ph": ph, "id": int(flow_id), "name": "serve/req",
                      "cat": "flow", "pid": pid, "tid": tid, "ts": ts0}
                if ph == "f":
                    ev["bp"] = "e"     # bind to the enclosing slice
                body.append((ts0, 2, 0.0, ridx, ev))
        body.sort(key=lambda item: item[:4])
        return meta + [ev for _, _, _, _, ev in body]

    def export(self, path: Optional[str] = None) -> Optional[str]:
        """Write the Chrome-trace JSON; returns the path (None when tracing
        is disabled or there is nowhere to write). Idempotent — call at
        teardown and from atexit; later calls overwrite with a superset."""
        if not self.enabled and path is None:
            return None
        if path is None:
            if not self.trace_dir:
                return None
            path = os.path.join(self.trace_dir, f"trace_{os.getpid()}.json")
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        tmp = f"{path}.tmp{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump({"traceEvents": self._events(),
                       "displayTimeUnit": "ms",
                       "clockSync": self._clock_sync_doc()}, f)
        os.replace(tmp, path)
        return path

    def _clock_sync_doc(self) -> dict:
        """One simultaneous (perf_counter, unix) anchor in microseconds —
        ``scripts/trace_merge.py`` uses it to clock-align trace files from
        different processes (each process's perf_counter has its own epoch)
        onto a single merged timeline."""
        perf_s, unix_s = self._clock_sync
        return {"perf_us": perf_s * 1e6, "unix_us": unix_s * 1e6,
                "pid": os.getpid()}

    def crash_dump(self, reason: str = "") -> Optional[str]:
        """Flight-recorder dump: write the retained rings to
        ``trace_crash.json`` in the trace dir. Called on injected kills
        (BEFORE ``os._exit``, which skips atexit), on :class:`InjectedFault`
        raises, and on fatal engine teardown. First reason wins — a cascade
        of secondary failures must not overwrite the original timeline."""
        if not self.enabled or not self.trace_dir:
            return None
        if self._crash_path is not None:
            return self._crash_path
        path = os.path.join(self.trace_dir, "trace_crash.json")
        try:
            events = self._events()
            if reason:
                events.append({"ph": "i", "s": "g", "name": f"crash: {reason}",
                               "pid": os.getpid(), "tid": 0,
                               "ts": time.perf_counter() * 1e6})
            os.makedirs(self.trace_dir, exist_ok=True)
            doc = {"traceEvents": events, "displayTimeUnit": "ms",
                   "clockSync": self._clock_sync_doc()}
            # when the lock-order sanitizer is armed, its acquisition
            # graph/cycle/blocking report rides the same dump: the one
            # postmortem a wedged or crashing run leaves behind
            # (docs/THREADLINT.md)
            from deepspeed_tpu.utils import locksan
            if locksan.enabled():
                doc["locksan"] = locksan.report()
            with open(path, "w") as f:
                json.dump(doc, f)
        except Exception as e:  # a failing dump must never mask the crash
            logger.warning(f"trace crash dump failed: {type(e).__name__}: {e}")
            return None
        self._crash_path = path
        logger.warning(f"flight recorder dumped to {path}"
                       + (f" ({reason})" if reason else ""))
        return path

    def _atexit_export(self) -> None:
        try:
            self.export()
        except Exception as e:  # pragma: no cover - depends on dying disk
            logger.warning(f"trace export at exit failed: "
                           f"{type(e).__name__}: {e}")


def _xplanes(directory: str) -> List[str]:
    return glob.glob(os.path.join(directory, "plugins", "profile", "*",
                                  "*.xplane.pb"))


def _anchor_times_ns(path: str) -> List[float]:
    """Start times of the capture's anchors on the trace's host plane."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out.extend(ev.start_ns for ev in line.events
                       if ev.name == CAPTURE_ANCHOR)
    return sorted(out)


#: the process-wide tracer every instrumentation site records through
tracer = Tracer()


def install_from_env() -> Tracer:
    """Arm the tracer from ``$DSTPU_TRACE`` (a directory; no-op when unset).
    Called by ``deepspeed_tpu.initialize`` and the v2 inference engine so
    subprocess workers trace without touching user code; idempotent — an
    already-configured tracer wins."""
    if tracer.enabled:
        return tracer
    trace_dir = os.environ.get(_ENV_VAR, "").strip()
    if trace_dir:
        ring = int(os.environ.get(_ENV_RING, "0") or 0)
        lanes = os.environ.get(_ENV_REQ_LANES, "").strip()
        tracer.configure(trace_dir=trace_dir,
                         ring_size=ring or None,
                         req_lane_window=int(lanes) if lanes else None)
        logger.info(f"span tracing ARMED from ${_ENV_VAR}: {trace_dir}")
    return tracer
