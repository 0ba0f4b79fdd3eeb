"""Where this program keeps JAX's persistent compilation cache.

One rule, one helper, called by the test harness, ``chip_smoke.py``, the
benchmarks and the serving engine:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads that directory from
  the environment, and this code sets no other — no sub-directory, no
  override from a config. Whoever runs the program places the cache.
- unset: ``<checkout>/.jax_cache``, a fixed path, because the path is part of
  the cache's key and a directory that moves never hits.

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
from typing import Optional

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


def setup_compile_cache(min_compile_time_secs: Optional[float] = None) -> str:
    """Turn the persistent compilation cache on and return its directory.

    Raises where the cache cannot be placed: a run that compiles everything
    cold must not look the same as one that hit.

    ``min_compile_time_secs``: the least compile time JAX persists; ``None``
    leaves the process's current threshold alone."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

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
