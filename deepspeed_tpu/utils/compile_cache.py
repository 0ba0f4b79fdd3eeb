"""Where this program keeps JAX's persistent compilation cache.

One rule, one helper, called by the test harness, ``chip_smoke.py``, the
benchmark (``chipbench/``) and the serving engine:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads that directory from
  the environment, and this code sets no other — no sub-directory, no
  override from a config. Whoever runs the program places the cache.
- unset: ``<checkout>/.jax_cache``, a fixed path, because the path is part of
  the cache's key and a directory that moves never hits.

The same call installs the process's compile listener
(:func:`install_compile_listener`): an always-on count, from jax's monitoring
events, of programs that were not ready when they were called.

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
import time
from typing import Optional

from deepspeed_tpu.monitor.trace import tracer as _tracer

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
#: (``tracer.totals``; a capture reports what each gained)
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_listening = False


def _on_duration(event: str, secs: float, **_) -> None:
    if event == _BACKEND_COMPILE:
        # wraps the cache lookup too: a program loaded from the persistent
        # cache counts, it was not ready either
        _tracer.bump("compile/backend_compiles")
        _tracer.bump("compile/backend_compile_s", secs)
        if _tracer.enabled:
            # jax calls listeners on the thread that waited, so the span
            # lands inside the span of the step that recompiled
            now = time.perf_counter()
            _tracer.add("compile/backend", now - secs, now)  # jaxlint: disable=JL001 -- jax measured secs around the blocking compile
    elif event == _CACHE_RETRIEVAL:
        _tracer.bump("compile/cache_load_s", secs)


def _on_event(event: str, **_) -> None:
    if event == _CACHE_HIT:
        _tracer.bump("compile/cache_loads")


def install_compile_listener() -> None:
    """Count backend compiles and persistent-cache loads for the life of the
    process (idempotent). jax offers no way to take one listener off, so
    there is one, module-level, and it writes to the tracer's counters."""
    global _listening
    if _listening:
        return
    from jax import monitoring
    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)
    _listening = True


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
