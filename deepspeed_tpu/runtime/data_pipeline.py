"""Prefetch-to-device training input pipeline.

Why this exists: ``inference/v2/pipeline.py`` made the *serving* decode
path an async pipeline, but the training hot path still paid the same
host-bound tax per step — ``DeepSpeedTPUDataLoader.__iter__`` collates
batches item-by-item on the caller's thread, ``train_batch`` blocks on a
synchronous ``_shard_global_batch`` device_put, and the metric fetch
serialised every step. This module is the t5x-style answer
(prefetch-to-device iterators) for the DeepSpeed-shaped engine: a producer thread pulls host batches from any
loader, applies the host-side staging work (curriculum-seqlen truncation,
progressive-layer-drop injection, the [tb] -> [gas, mb*dp] reshape and
sharded ``device_put``) OFF the critical path, and parks the next N
device-resident global batches in a bounded queue. ``train_batch`` then
dequeues an already-sharded tree and goes straight to dispatch::

    producer:  | pull | collate | truncate/PLD | device_put |  ->  queue(N)
    consumer:          | dequeue | dispatch step k | drain k-1 metrics |

The staging helpers (`as_host_tree`, `truncate_to_seqlen`, `inject_pld`) are
module functions so the engine's synchronous fallback path (``prefetch=0``,
or an explicit ``train_batch(batch)``) runs the EXACT same code the producer
thread runs — the pipelined and sync loops must produce bit-identical loss
streams
(``tests/unit/test_data_pipeline.py::test_train_steps_pipelined_matches_sync_loop``).

This module is deliberately NOT a jaxlint JL007 hot-path module: host-side
``np.asarray`` conversions live here so ``runtime/engine.py`` (which IS
policed) carries exactly one suppressed drain point. docs/TRAINING.md walks
the whole loop.
"""

from __future__ import annotations

import queue
import threading
from dataclasses import dataclass
from typing import Any, Callable, Iterable, Optional

import numpy as np

from deepspeed_tpu.monitor.trace import tracer as _tracer
from deepspeed_tpu.utils.threads import thread_role


def as_host_tree(batch):
    """Materialise every leaf of a batch tree as a numpy array.

    Loader-collated batches are already numpy (no copy); user-passed lists or
    device arrays are converted here — the ONE place the training input path
    touches ``np.asarray`` on arbitrary leaves, kept out of the JL007-policed
    engine module on purpose."""
    return _tree_map(np.asarray, batch)


def _tree_map(fn, tree):
    import jax
    return jax.tree_util.tree_map(fn, tree)


def needs_truncation(batch, seqlen: int) -> bool:
    """True when any rank>=2 leaf is wider than the scheduled seqlen — an
    O(#leaves) shape scan, no data touched."""
    import jax
    return any(len(np.shape(x)) >= 2 and np.shape(x)[1] > seqlen
               for x in jax.tree_util.tree_leaves(batch))


def truncate_to_seqlen(batch, seqlen: int):
    """Curriculum-seqlen truncation: slice rank>=2 leaves to ``[:, :seqlen]``.

    Returns the host tree UNCHANGED (no slicing tree_map) when no leaf
    exceeds the scheduled length — the off-boundary fast path; slices are
    numpy views, so even on-boundary steps copy nothing."""
    host = as_host_tree(batch)
    if not needs_truncation(host, seqlen):
        return host
    return _tree_map(
        lambda x: x[:, :seqlen] if x.ndim >= 2 and x.shape[1] > seqlen else x,
        host)


def inject_pld(batch, leading: int, theta: float, key):
    """Thread PLD theta + per-sample PRNG keys through the batch so the jitted
    step sees them as inputs (no retrace per theta change); models read
    ``batch["pld_theta"]``/``["pld_rng"]``.

    ``key`` must already be step-folded (``fold_in(base, step)``) so sync and
    prefetched staging derive identical randomness for the same global step
    regardless of which thread runs first."""
    if not isinstance(batch, dict):
        return batch
    import jax
    batch = dict(batch)
    batch["pld_theta"] = np.full((leading,), theta, np.float32)
    # tiny (leading, 2) uint32 fetch; off the critical path under prefetch
    batch["pld_rng"] = np.asarray(jax.random.split(key, leading))
    return batch


@dataclass
class StagedBatch:
    """A device-resident sharded global batch, staged for step ``step``.

    ``tree`` is the ``[gas, mb*dp, ...]`` sharded tree ``train_batch``
    dispatches directly; ``raw`` keeps a reference to the ORIGINAL host batch
    (pre-truncation/PLD — the collated numpy tree, so holding it costs
    nothing beyond the queue depth) for the flops profiler and for restaging
    when the engine's step counter moved outside the pipeline (mixed
    explicit/argless usage; see ``train_batch``)."""

    tree: Any
    step: int
    raw: Any = None


class _Item:
    """Queue envelope: exactly one of batch / exc / end is set."""

    __slots__ = ("batch", "exc", "end")

    def __init__(self, batch=None, exc=None, end=False):
        self.batch = batch
        self.exc = exc
        self.end = end


class PrefetchLoader:
    """Background producer staging the next N prepared batches.

    Wraps any iterable of host batches (``DeepSpeedTPUDataLoader``,
    ``RepeatingLoader``, a generator, a plain list). ``prepare(batch, step)``
    is the staging hook — the engine passes ``_prepare_batch``, which
    truncates/injects/shards and returns a :class:`StagedBatch`; ``step``
    counts consumed batches from ``start_step`` so schedule-dependent staging
    (curriculum seqlen, PLD theta) is computed for the step the batch will be
    TRAINED at, not the step it was produced at.

    - ``prefetch >= 1``: a daemon producer thread fills a bounded queue
      (``prefetch=2`` is classic double buffering: one batch in flight on
      device, one staged behind it).
    - ``prefetch = 0``: synchronous fallback — no thread, ``prepare`` runs
      inline on ``__next__`` (same code path, same results, for debugging
      and for platforms where background transfers misbehave).

    Exceptions raised by the loader or by ``prepare`` in the producer are
    re-raised on the consumer thread at the ``__next__`` that would have
    returned the failed batch; a finite loader ends with ``StopIteration``
    as usual. ``close()`` stops the producer without consuming the rest.
    """

    def __init__(self, loader: Iterable, prepare: Optional[Callable] = None,
                 prefetch: int = 2, start_step: int = 0):
        if prefetch < 0:
            raise ValueError(f"prefetch must be >= 0, got {prefetch}")
        self.loader = loader
        self.prepare = prepare or (lambda batch, step: batch)
        self.prefetch = int(prefetch)
        # stepped by the CONSUMER on the prefetch==0 inline path and by
        # the producer thread when prefetching — the paths are mutually
        # exclusive by configuration, never concurrent
        self._next_step = int(start_step)  # threadlint: guarded-by=none
        self._iter = None              # sync-mode iterator
        self._queue: Optional[queue.Queue] = None
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self._closed = False

    # ------------------------------------------------------------------ #
    # iteration
    # ------------------------------------------------------------------ #

    def __iter__(self):
        return self

    def __next__(self):
        if self._closed:
            raise StopIteration
        if self.prefetch == 0:
            if self._iter is None:
                self._iter = iter(self.loader)
            batch = next(self._iter)
            staged = self.prepare(batch, self._next_step)
            self._next_step += 1
            return staged
        self._ensure_started()
        item = self._queue.get()
        if item.end:
            self._closed = True
            raise StopIteration
        if item.exc is not None:
            self.close()
            raise item.exc
        return item.batch

    def __len__(self):
        return len(self.loader)

    def __bool__(self):
        # without this, truthiness falls back to __len__, which explodes when
        # the wrapped loader (e.g. RepeatingLoader) has no length
        return True

    @property
    def depth(self) -> int:
        """Staged batches currently parked in the queue (monitor signal: a
        persistently empty queue means the producer — not the device — is the
        bottleneck)."""
        return self._queue.qsize() if self._queue is not None else 0

    # ------------------------------------------------------------------ #
    # producer
    # ------------------------------------------------------------------ #

    def _ensure_started(self):
        if self._thread is not None:
            return
        self._queue = queue.Queue(maxsize=self.prefetch)
        self._thread = threading.Thread(target=self._produce,
                                        name="dstpu-prefetch", daemon=True)
        self._thread.start()

    @thread_role("dstpu-prefetch")
    def _produce(self):
        try:
            for batch in self.loader:
                if self._stop.is_set():
                    return
                # the producer's staging work on its own timeline track
                # (thread 'dstpu-prefetch'): overlap with the consumer's
                # train/step spans is the whole point of this thread
                with _tracer.span("train/prefetch/stage",
                                  step=self._next_step):
                    staged = self.prepare(batch, self._next_step)
                self._next_step += 1
                if not self._put(_Item(batch=staged)):
                    return
            self._put(_Item(end=True))
        except BaseException as exc:  # propagate to the consumer, don't die
            self._put(_Item(exc=exc))

    def _put(self, item: _Item) -> bool:
        """Bounded put that stays responsive to ``close()``."""
        while not self._stop.is_set():
            try:
                self._queue.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    # ------------------------------------------------------------------ #
    # shutdown
    # ------------------------------------------------------------------ #

    def close(self):
        """Stop the producer and drop staged batches. Idempotent; called by
        ``engine.destroy()`` and on checkpoint load (a restored step counter
        invalidates schedule-dependent staging)."""
        self._closed = True
        self._stop.set()
        thread, self._thread = self._thread, None
        if thread is not None:
            # unblock a producer waiting on a full queue
            while True:
                try:
                    self._queue.get_nowait()
                except queue.Empty:
                    break
            thread.join(timeout=5.0)
