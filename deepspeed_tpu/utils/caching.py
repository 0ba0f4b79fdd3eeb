"""Small bounded-LRU cache (plus its bucketing helper) shared by long-lived
serving paths.

Compiled XLA executables and host-side layout tables are cached per
(shape/config) key; a serving process that sees many distinct keys must evict
or it leaks executables indefinitely. One helper so every such cache behaves
identically (inference v2 decode-step programs, block-sparse layouts, ...).

:func:`next_pow2` is the canonical shape-bucketing function for those cache
keys: every device program keyed on a *variable* count (live decode rows,
reorder-gather lengths) rounds the count up to a power of two first, so the
reachable program set is log-sized instead of linear in the count.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Callable, Generic, Hashable, TypeVar
from deepspeed_tpu.utils.threads import make_lock

V = TypeVar("V")


def next_pow2(n: int) -> int:
    """Smallest power of two >= ``n``, with ``next_pow2(0) == 1``.

    The serving engine pads every count-keyed device-program dimension to this
    bucket (sampler rows, decode-batch rows, reorder gathers): a serving loop
    whose live-sequence count drifts by one per admission/retirement then
    reuses ~log2 cached executables instead of recompiling per count
    (seconds each). Zero maps to 1 because
    every padded program needs at least one row.
    """
    if n <= 1:
        return 1
    return 1 << (int(n) - 1).bit_length()


class LRUCache(Generic[V]):
    def __init__(self, maxsize: int):
        assert maxsize > 0
        self.maxsize = maxsize
        self._d: "OrderedDict[Hashable, V]" = OrderedDict()
        # Serving engines may be driven from multiple threads. The cache-wide
        # lock only guards the dict; factories (usually multi-second XLA
        # compiles) run under a per-key lock so two threads racing the SAME
        # cold key share one compile while hits and other keys never block
        # behind an in-flight factory.
        self._lock = make_lock("utils.caching.lru")
        self._key_locks: dict = {}

    def get_or_create(self, key: Hashable, factory: Callable[[], V]) -> V:
        with self._lock:
            hit = self._d.get(key)
            if hit is not None:
                self._d.move_to_end(key)
                return hit
            klock = self._key_locks.setdefault(
                key, make_lock("utils.caching.key"))
        with klock:
            with self._lock:  # a racer may have built it while we waited
                hit = self._d.get(key)
            if hit is None:
                hit = factory()
            with self._lock:
                self._d[key] = hit
                self._d.move_to_end(key)
                while len(self._d) > self.maxsize:
                    self._d.popitem(last=False)
                self._key_locks.pop(key, None)
            return hit

    def __len__(self) -> int:
        return len(self._d)

    def __contains__(self, key: Hashable) -> bool:
        return key in self._d
