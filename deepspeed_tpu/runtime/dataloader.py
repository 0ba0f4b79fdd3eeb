"""Data loader.

Parity: ``DeepSpeedDataLoader`` (reference ``deepspeed/runtime/dataloader.py``) —
there, a torch DataLoader with a DistributedSampler carving the dataset per dp rank;
here, a single-controller loader yielding **global** batches (leading dim =
train_batch_size) as numpy trees; the engine shards them over (data, fsdp) at
device_put. Per-host input pipelines (one feeder per process) arrive with the
multi-host launcher.

Determinism contract (pinned by tests/unit/test_data_pipeline.py, whose
loss-equality tests rely on it): the shuffle order
is a pure function of ``(seed, epoch)`` — two loaders with the same seed and
epoch yield identical batch streams, and ``RepeatingLoader``'s epoch
auto-bump reshuffles reproducibly. The async step loop builds on this:
``runtime/data_pipeline.PrefetchLoader`` stages these batches device-side
from a producer thread (docs/TRAINING.md), so any nondeterminism here would
surface as sync-vs-pipelined loss divergence.
"""

from __future__ import annotations

from typing import Any, Callable, Iterable, Optional, Sequence

import numpy as np


def _default_collate(items: Sequence[Any]):
    first = items[0]
    if isinstance(first, dict):
        return {k: np.stack([np.asarray(it[k]) for it in items]) for k in first}
    if isinstance(first, (tuple, list)):
        return tuple(np.stack([np.asarray(it[i]) for it in items])
                     for i in range(len(first)))
    return np.stack([np.asarray(it) for it in items])


class DeepSpeedTPUDataLoader:

    def __init__(self, dataset, batch_size: int, collate_fn: Optional[Callable] = None,
                 shuffle: bool = True, seed: int = 42, drop_last: bool = True,
                 curriculum_schedule=None):
        self.dataset = dataset
        self.batch_size = batch_size
        self.collate_fn = collate_fn or _default_collate
        self.shuffle = shuffle
        self.seed = seed
        self.drop_last = drop_last
        self.epoch = 0
        self.curriculum_schedule = curriculum_schedule

    def __len__(self):
        n = len(self.dataset)
        return n // self.batch_size if self.drop_last else (n + self.batch_size - 1) // self.batch_size

    def set_epoch(self, epoch: int):
        self.epoch = epoch

    def __iter__(self):
        n = len(self.dataset)
        idx = np.arange(n)
        if self.shuffle:
            rng = np.random.default_rng(self.seed + self.epoch)
            rng.shuffle(idx)
        end = (n // self.batch_size) * self.batch_size if self.drop_last else n
        for start in range(0, end, self.batch_size):
            sel = idx[start:start + self.batch_size]
            items = [self.dataset[int(i)] for i in sel]
            yield self.collate_fn(items)


class RepeatingLoader:
    """Parity: ``deepspeed.utils.RepeatingLoader`` — wraps a loader to restart on
    StopIteration (used by pipeline train loops)."""

    def __init__(self, loader):
        self.loader = loader
        self.data_iter = iter(self.loader)

    def __iter__(self):
        return self

    def __next__(self):
        try:
            return next(self.data_iter)
        except StopIteration:
            if hasattr(self.loader, "set_epoch"):
                self.loader.set_epoch(getattr(self.loader, "epoch", 0) + 1)
            self.data_iter = iter(self.loader)
            return next(self.data_iter)
