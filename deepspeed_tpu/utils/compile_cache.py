"""Where this program keeps JAX's persistent compilation cache.

One rule, one helper, called by the test harness, ``chip_smoke.py``, the
benchmark (``chipbench/``) and the serving engine:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads that directory from
  the environment, and this code sets no other — no sub-directory, no
  override from a config. Whoever runs the program places the cache.
- unset: ``<checkout>/.jax_cache``, a fixed path, because the path is part of
  the cache's key and a directory that moves never hits.

The same call installs the process's compile listener
(:func:`install_compile_listener`): an always-on account, from jax's
monitoring events, of what was traced, lowered and compiled (or loaded), by
the phase of set-up the program was in and by the program's name.

A TPU executable does not depend on the host that compiled it. A CPU
executable is compiled ahead of time for the build host's CPU features, and
loading one on a host without them is a SIGILL (XLA's cpu_aot_loader warns
"Compile machine features ... doesn't match"). So where this code picks the
directory and the backend is the CPU, the cache is the per-host
``cpu-<fingerprint>`` sub-directory, which is fixed for a host; a foreign
host warms its own.
"""

from __future__ import annotations

import hashlib
import os
import platform
import re
import threading
import time
from typing import Dict, List, Optional

from deepspeed_tpu.monitor.trace import tracer as _tracer
from deepspeed_tpu.utils.threads import make_lock

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def host_fingerprint() -> str:
    """Stable id for this host's instruction-set surface (machine arch plus
    the sorted /proc/cpuinfo feature flags). Returns "" when the feature
    flags are unreadable — callers must then NOT share host-specific
    binaries, because arch-only keying would put an AVX512 host and a plain
    x86_64 host under the same key (the exact SIGILL this exists to
    prevent)."""
    feats = ""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.split(":")[0].strip() in ("flags", "Features"):
                    feats = " ".join(sorted(line.split(":", 1)[1].split()))
                    break
    except OSError:
        pass
    if not feats:
        return ""
    raw = f"{platform.machine()}|{feats}"
    return hashlib.sha1(raw.encode()).hexdigest()[:12]


#: jax's monitoring events, and the always-on counters they feed
#: (``tracer.totals``; a capture reports what each gained). A program's way
#: to the device is three duration events, each with the program's name
#: (``fun_name``): jax TRACES the function to a jaxpr, LOWERS the jaxpr to an
#: MLIR module, and hands the module to the BACKEND — an event that wraps the
#: persistent cache's lookup too, so a program loaded from the cache counts
#: (it was not ready either) and its seconds are the load's. jax calls the
#: listeners on the thread that worked, at the event's end, with its length.
_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
#: event -> (column of a program's row, counter under ``compile/<phase>/``,
#: span under tracing)
_KINDS = {_TRACE: (1, "trace_s", "compile/trace"),
          _LOWER: (2, "lower_s", "compile/lower"),
          _BACKEND_COMPILE: (3, "backend_s", "compile/backend")}
_MODULE_NAME = re.compile(r"^(?:jit|pmap)\((.*)\)$", re.S)
#: the shortest event that is drawn as a span: a jnp primitive traced inside
#: a larger trace is tens of microseconds and there are thousands a program
#: (counted all the same), which would push a start's first spans out of
#: their ring
SPAN_MIN_S = 1e-3
#: names :func:`programs` keeps; what comes after them is one row
MAX_PROGRAMS = 512
OTHER = "<other>"
_listening = False
_programs: Dict[str, Dict[str, List[float]]] = {}
_programs_lock = make_lock("utils.compile_cache.programs")
_open = threading.local()


def _frames() -> List[float]:
    """This thread's open events, outermost first: for each, the seconds of
    the events that ended inside it."""
    frames = getattr(_open, "frames", None)
    if frames is None:
        frames = _open.frames = []
    return frames


def _on_scalar(event: str, value: float, **_) -> None:
    # jax records an event's start time as a scalar under the event's name
    if event in _KINDS:
        _frames().append(0.0)


def _on_duration(event: str, secs: float, fun_name: str = "", **_) -> None:
    kind = _KINDS.get(event)
    if kind is None:
        if event == _CACHE_RETRIEVAL:
            _tracer.bump("compile/cache_load_s", secs)
        return
    column, counter, span = kind
    # each second once: a jit traced inside another's trace (or an eager
    # operation compiled inside one) is an event of its own inside the
    # outer's interval, and the outer one is charged net of those
    frames = _frames()
    inner = frames.pop() if frames else 0.0
    if frames:
        frames[-1] += secs
    net = max(0.0, secs - inner)
    phase = _tracer.phase()
    # the trace is named as the function is, the module "jit(f)" or "pmap(f)"
    wrapped = _MODULE_NAME.match(fun_name)
    name = wrapped.group(1) if wrapped else fun_name or "<unnamed>"
    _tracer.bump(f"compile/{phase}/{counter}", net)
    if event == _BACKEND_COMPILE:
        _tracer.bump(f"compile/{phase}/programs")
        _tracer.bump("compile/backend_compiles")
        _tracer.bump("compile/backend_compile_s", secs)
    with _programs_lock:
        if name not in _programs and len(_programs) >= MAX_PROGRAMS:
            name = OTHER
        row = _programs.setdefault(name, {}).setdefault(
            phase, [0, 0.0, 0.0, 0.0])
        row[0] += 1
        row[column] += net
    if _tracer.enabled and secs >= SPAN_MIN_S:
        # on the thread that waited, so the span lands inside the stage of
        # set-up, or the step, that built the program
        now = time.perf_counter()
        _tracer.add(span, now - secs, now,  # jaxlint: disable=JL001 -- jax measured secs around the blocking work
                    lane="setup" if phase in ("build", "warmup") else None,
                    program=name)


def _on_event(event: str, **_) -> None:
    if event == _CACHE_HIT:
        _tracer.bump("compile/cache_loads")


def install_compile_listener() -> None:
    """Count traces, lowerings, backend compiles and persistent-cache loads
    for the life of the process (idempotent). jax offers no way to take one
    listener off, so there is one, module-level, and it writes to the
    tracer's counters: ``compile/<phase>/trace_s``, ``lower_s``,
    ``backend_s`` and ``programs`` by the phase of set-up the program is in
    (``tracer.phase()``), the four process-wide totals, and a row a program
    (:func:`programs`)."""
    global _listening
    if _listening:
        return
    from jax import monitoring
    monitoring.register_scalar_listener(_on_scalar)
    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)
    _listening = True


def programs() -> Dict[str, Dict[str, List[float]]]:
    """``{program: {phase: [events, trace_s, lower_s, backend_s]}}`` of
    everything jax traced, lowered or compiled since the listener was
    installed, by the name jax gives (``fun_name``: a jitted function's
    ``__name__``), each second charged once. The first :data:`MAX_PROGRAMS`
    names have a row of their own, the rest share :data:`OTHER`."""
    with _programs_lock:
        return {name: {phase: list(row) for phase, row in phases.items()}
                for name, phases in _programs.items()}


def setup_summary(top: int = 5) -> str:
    """Where this process's set-up went, for the one log line a stage's owner
    prints when its warm-up is over: the stages with their seconds, each
    phase's trace / lower / backend seconds and programs, the persistent
    cache's loads, and the ``top`` programs that cost most."""
    totals = {name: value for name, value, _ in _tracer.setup_events()}
    stages = ", ".join(f"{k[len('setup/'):-2]} {v:.1f}"
                       for k, v in sorted(totals.items())
                       if k.startswith("setup/"))
    phases = []
    for phase in ("before", "build", "warmup", "traffic"):
        n = totals.get(f"compile/{phase}/programs", 0)
        t, l, b = (totals.get(f"compile/{phase}/{c}", 0.0)
                   for c in ("trace_s", "lower_s", "backend_s"))
        if n or t or l or b:
            phases.append(f"{phase} trace {t:.1f} lower {l:.1f} backend "
                          f"{b:.1f} s, {int(n)} programs")
    cost = sorted(((sum(sum(r[1:]) for r in rows.values()), name)
                   for name, rows in programs().items()), reverse=True)
    return (f"set-up by stage, s: {stages or 'none'}; compiles by phase: "
            f"{'; '.join(phases) or 'none'}; cache loads "
            f"{int(totals.get('compile/cache_loads', 0))} in "
            f"{totals.get('compile/cache_load_s', 0.0):.1f} s; costliest "
            f"programs, s: "
            + (", ".join(f"{n} {s:.1f}" for s, n in cost[:top]) or "none"))


_roomy = None


def with_stack_room(fn):
    """``fn()``, called under ONE frame too large for a chunk of CPython's
    frame stack (16 KiB, some thirty frames, given back the moment the frame
    at its start returns): it gets a chunk of its own size, and every frame
    above it lives in what is left. Set-up's traces run in it, because a
    trace is thousands of call chains forty frames deep and a chunk's edge
    inside them costs a map and an unmap each: the same decode step traced
    in 0.22 s or 2.4 s by the depth it was called from (PERF.md, PR 45;
    docs/OBSERVABILITY.md "Set-up and compiles"). Elsewhere a plain call."""
    global _roomy
    if _roomy is None:
        # 65,536 locals that dead code names and nothing ever binds
        names = " = ".join(f"_{i}" for i in range(1 << 16))
        scope: dict = {}
        exec(f"def roomy(fn):\n    if 0:\n        {names} = 0\n"
             "    return fn()\n", scope)
        _roomy = scope["roomy"]
    return _roomy(fn)


def backend_compiles() -> int:
    """Programs compiled or loaded from the cache since the listener was
    installed — module-level jits and eager operations included."""
    return int(_tracer.totals.get("compile/backend_compiles", 0))


def setup_compile_cache(min_compile_time_secs: Optional[float] = None) -> str:
    """Turn the persistent compilation cache on and return its directory.

    Raises where the cache cannot be placed: a run that compiles everything
    cold must not look the same as one that hit.

    ``min_compile_time_secs``: the least compile time JAX persists; ``None``
    leaves the process's current threshold alone."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    install_compile_listener()
    configured = jax.config.jax_compilation_cache_dir
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        if configured != env_dir:
            raise RuntimeError(
                f"JAX_COMPILATION_CACHE_DIR={env_dir!r} but jax is configured "
                f"with jax_compilation_cache_dir={configured!r}: the variable "
                "must be set before jax is imported, and nothing may "
                "override it in code")
        directory = env_dir
    else:
        directory = os.path.join(_CHECKOUT, ".jax_cache")
        if jax.default_backend() == "cpu":
            fp = host_fingerprint()
            if not fp:
                raise RuntimeError(
                    "cannot key a CPU compilation cache: this host's CPU "
                    "feature flags are unreadable (/proc/cpuinfo)")
            directory = os.path.join(directory, f"cpu-{fp}")
        if configured != directory:
            jax.config.update("jax_compilation_cache_dir", directory)
            # jax opens its cache at the FIRST compile and never re-reads the
            # config after that: if anything compiled before this call, the
            # handle is pinned to the old directory (or to "disabled") and
            # every later write silently vanishes. Reset so the next compile
            # opens the directory just set.
            cc.reset_cache()
    if min_compile_time_secs is not None:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          min_compile_time_secs)
    os.makedirs(directory, exist_ok=True)
    return directory
