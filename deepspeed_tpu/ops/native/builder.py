"""Compile-on-demand loader for the native host module.

The TPU analog of the reference's ``op_builder`` JIT system
(``op_builder/builder.py:108`` ``OpBuilder.load()`` which lazily compiles
``csrc/`` extensions via ``torch.utils.cpp_extension``): here a single C++17
translation unit is compiled with ``g++`` on first use and cached next to the
source, under a name keyed by source hash and host; loading is via ``ctypes``
(no pybind11 in this environment). Every consumer degrades gracefully to a
pure-Python path when no compiler exists, the same way reference builders
report ``is_compatible() == False``.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import tempfile
import threading
from typing import Optional

from deepspeed_tpu.utils.compile_cache import host_fingerprint
from deepspeed_tpu.utils.logging import logger
from deepspeed_tpu.utils.threads import make_lock

_SRC = os.path.join(os.path.dirname(__file__), "csrc", "ds_native.cpp")
_BUILD_DIR = os.environ.get(
    "DS_TPU_NATIVE_BUILD_DIR",
    os.path.join(os.path.dirname(__file__), "_build"))

_lock = make_lock("ops.builder")
_lib: Optional[ctypes.CDLL] = None
_tried = False

_BASE_FLAGS = ["-O3", "-std=c++17", "-shared", "-fPIC", "-pthread"]


def _lib_path() -> str:
    """The library for THIS source on THIS host: the name carries a hash of
    the source and the host's CPU-feature fingerprint. The build uses
    ``-march=native``, and the build directory travels with a copied working
    tree, so a library built on another host (or from other source) must
    never be the one that loads here — it simply has another name. A host
    whose features are unreadable ("" fingerprint) shares nothing: its key
    is the host name."""
    with open(_SRC, "rb") as f:
        src = hashlib.sha1(f.read()).hexdigest()[:12]
    host = host_fingerprint() or f"host-{os.uname().nodename}"
    return os.path.join(_BUILD_DIR, f"libds_native-{src}-{host}.so")


def _compile(lib_path: str) -> bool:
    os.makedirs(_BUILD_DIR, exist_ok=True)
    # Unique temp output so concurrent builds (multi-process launch on a cold
    # cache) never interleave writes; os.replace makes the publish atomic.
    fd, tmp_out = tempfile.mkstemp(suffix=".so", dir=_BUILD_DIR)
    os.close(fd)
    try:
        # Prefer native ISA + OpenMP; retreat flag by flag for portability.
        for extra in (["-march=native", "-fopenmp"], ["-fopenmp"], []):
            cmd = ["g++"] + _BASE_FLAGS + extra + [_SRC, "-o", tmp_out]
            try:
                proc = subprocess.run(cmd, capture_output=True, text=True, timeout=120)
            except (OSError, subprocess.TimeoutExpired) as e:
                logger.warning(f"native build failed to launch g++: {e}")
                return False
            if proc.returncode == 0:
                os.replace(tmp_out, lib_path)
                return True
        logger.warning(f"native build failed:\n{proc.stderr[-2000:]}")
        return False
    finally:
        if os.path.exists(tmp_out):
            os.unlink(tmp_out)


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    c = ctypes
    lib.ds_aio_create.restype = c.c_void_p
    lib.ds_aio_create.argtypes = [c.c_long, c.c_int, c.c_int, c.c_int]
    lib.ds_aio_destroy.argtypes = [c.c_void_p]
    lib.ds_aio_block_size.restype = c.c_long
    lib.ds_aio_block_size.argtypes = [c.c_void_p]
    lib.ds_aio_queue_depth.restype = c.c_int
    lib.ds_aio_queue_depth.argtypes = [c.c_void_p]
    lib.ds_aio_thread_count.restype = c.c_int
    lib.ds_aio_thread_count.argtypes = [c.c_void_p]
    lib.ds_aio_submit.restype = c.c_long
    lib.ds_aio_submit.argtypes = [c.c_void_p, c.c_void_p, c.c_long, c.c_char_p,
                                  c.c_long, c.c_int]
    lib.ds_aio_wait.restype = c.c_int
    lib.ds_aio_wait.argtypes = [c.c_void_p]
    lib.ds_alloc_aligned.restype = c.c_void_p
    lib.ds_alloc_aligned.argtypes = [c.c_long]
    lib.ds_free_aligned.argtypes = [c.c_void_p]

    f = c.c_float
    lib.ds_adam_step.argtypes = [c.c_long] + [c.c_void_p] * 4 + [f] * 5 + [c.c_int, f, f]
    lib.ds_adagrad_step.argtypes = [c.c_long] + [c.c_void_p] * 3 + [f] * 3
    lib.ds_lion_step.argtypes = [c.c_long] + [c.c_void_p] * 3 + [f] * 4
    lib.ds_f32_to_bf16.argtypes = [c.c_long, c.c_void_p, c.c_void_p]
    lib.ds_bf16_to_f32.argtypes = [c.c_long, c.c_void_p, c.c_void_p]
    return lib


def load_native() -> Optional[ctypes.CDLL]:
    """Return the bound CDLL, compiling if needed; None if unavailable."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        try:
            lib_path = _lib_path()
            if not os.path.exists(lib_path) and not _compile(lib_path):
                return None
            _lib = _bind(ctypes.CDLL(lib_path))
        except (OSError, AttributeError) as e:
            # AttributeError: stale cached .so missing a newer symbol — degrade
            # to the Python fallback rather than crashing consumers.
            logger.warning(f"native module load failed: {e}")
            _lib = None
        return _lib


def native_available() -> bool:
    return load_native() is not None
