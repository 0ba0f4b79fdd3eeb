"""Named concurrency primitives + thread-role declarations.

The multi-threaded stack (serving loops, health monitor, prefill workers,
the prefetch producer, the offload upload lane, checkpoint committer/writers,
AIO pools) coordinates over locks whose ORDER and OWNERSHIP discipline is
what threadlint (docs/THREADLINT.md) checks statically and ``utils/locksan``
checks at runtime. Both need stable lock identities, so locks are created
through the factories here with a dotted name::

    self._lock = make_lock("serving.frontend.inflight")

- Normally ``make_lock`` returns a plain ``threading.Lock`` — zero overhead,
  byte-for-byte the behavior the stack always had.
- Under ``DSTPU_LOCKSAN=1`` it returns an order-recording
  :class:`~deepspeed_tpu.utils.locksan.SanLock` proxy carrying the same
  name, so the runtime acquisition graph and the static one share a
  namespace and a test can assert ``static edges >= observed edges``.

Names are lockdep-style CLASSES, not instances: every per-key lock minted by
``utils/caching.py`` shares one name, exactly how lockdep groups locks by
initialization site.

:func:`thread_role` declares which long-lived thread runs a function — the
seed threadlint's role propagation grows from (the decorator only attaches
an attribute; there is no runtime behavior)::

    @thread_role("serve-loop")
    def _loop(self): ...
"""

from __future__ import annotations

import threading

from deepspeed_tpu.utils import locksan

__all__ = ["thread_role", "make_lock", "make_rlock", "make_semaphore",
           "make_condition"]


def thread_role(name: str):
    """Declare that the decorated function is the entry point of the
    ``name`` thread role (e.g. ``"serve-loop"``, ``"health-monitor"``).
    Purely declarative: threadlint seeds its role propagation from it."""
    def deco(fn):
        fn.__thread_role__ = name
        return fn
    return deco


def make_lock(name: str) -> threading.Lock:
    """A ``threading.Lock`` under a stable dotted name. With locksan armed
    (``DSTPU_LOCKSAN=1``) the lock is wrapped in an order-recording proxy."""
    lock = threading.Lock()
    if locksan.enabled():
        return locksan.SanLock(name, lock)
    return lock


def make_rlock(name: str) -> threading.RLock:
    """Reentrant variant of :func:`make_lock`."""
    lock = threading.RLock()
    if locksan.enabled():
        return locksan.SanLock(name, lock, reentrant=True)
    return lock


def make_semaphore(name: str, value: int = 1) -> threading.Semaphore:
    """A counting semaphore under a stable name. Semaphores are WAITED on,
    not lock-ordered (a release may come from another thread), so locksan
    records them only as blocking sites, never as held locks."""
    sem = threading.Semaphore(value)
    if locksan.enabled():
        return locksan.SanSemaphore(name, sem)
    return sem


def make_condition(name: str, lock=None) -> threading.Condition:
    """A condition variable over a (named) lock."""
    cond = threading.Condition(lock)
    return cond
