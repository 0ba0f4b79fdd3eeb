"""Per-sequence tracking state.

Parity: ``DSSequenceDescriptor`` (reference
``inference/v2/ragged/sequence_descriptor.py``) — seen tokens, owned KV blocks and
the host-side block table row. The pending (unprocessed) prompt tail also lives
here: the scheduler drains it chunk by chunk (Dynamic SplitFuse).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List

import numpy as np


@dataclass
class DSSequenceDescriptor:
    uid: int
    seen_tokens: int = 0                      # tokens whose KV is in the cache
    blocks: List[int] = field(default_factory=list)
    pending: np.ndarray = field(default_factory=lambda: np.zeros((0,), np.int32))
    in_flight_tokens: int = 0                 # tokens scheduled in the current pass
    # prefix-cache support (scheduler fills these only when a cache is wired):
    # every token the host has seen for this sequence, in order — the radix
    # tree is keyed on token blocks, so releasing KV pages to the cache needs
    # the ids that produced them. Device-generated tokens the host never saw
    # (a pipeline run's) are NOT here; pages beyond the history are freed,
    # not cached. Buffered as a part-list so the per-decode-token append is
    # O(1) (a flat-array concatenate per token is O(n^2) over a generation);
    # ``history()`` flattens on demand.
    history_parts: List[np.ndarray] = field(default_factory=list)
    history_len: int = 0
    # length of the CONTIGUOUS recorded prefix (None = all of history). The
    # fused device decode loop (scheduler.advance) appends tokens the host
    # never records; any tokens recorded AFTER such a gap sit at later
    # positions than their history index, so keying KV pages by them would
    # poison the radix tree with wrong token->page mappings. advance() seals
    # the valid prefix at the pre-gap length.
    history_valid: "int | None" = None
    cached_tokens: int = 0                    # prompt tokens served from cache
    filed_tokens: int = 0                     # tokens already eager-inserted
    # engine-weight version this sequence's KV is being computed under
    # (stamped at admission when a prefix cache is wired): a flush whose
    # stamp trails the cache's current version frees the pages instead of
    # filing old-weight KV into a post-swap tree (runtime/colocated.py)
    weight_version: int = 0
    # slot of the recurrent-state pool (ragged/state_pool.py) this sequence
    # holds from admission to flush; -1 for a model with no such layers
    state_slot: int = -1
    # generation by diffusion over blocks (scheduler.causal_block B > 1): the
    # last ``P mod B`` prompt tokens, which are NOT prefilled — they open the
    # first block the block pipeline denoises (blocks/pipeline.py)
    block_open: np.ndarray = field(
        default_factory=lambda: np.zeros((0,), np.int32))

    @property
    def cur_allocated_blocks(self) -> int:
        return len(self.blocks)

    def kv_blocks_needed(self, new_tokens: int, block_size: int) -> int:
        """Extra blocks required to hold ``new_tokens`` more tokens."""
        total = self.seen_tokens + new_tokens
        needed = -(-total // block_size)      # ceil
        return max(0, needed - len(self.blocks))

    def extend_pending(self, tokens: np.ndarray) -> None:
        self.pending = np.concatenate([self.pending, np.asarray(tokens, np.int32)])

    def record_history(self, tokens: np.ndarray) -> None:
        t = np.asarray(tokens, np.int32)
        self.history_parts.append(t)
        self.history_len += len(t)

    def history(self, n: int | None = None) -> np.ndarray:
        """The recorded token history (first ``n`` tokens). Flattens the part
        buffer in place — called per prompt completion / flush, not per
        token."""
        if len(self.history_parts) != 1:
            self.history_parts = [
                np.concatenate(self.history_parts) if self.history_parts
                else np.zeros((0,), np.int32)]
        h = self.history_parts[0]
        return h if n is None else h[:n]

    def block_table(self, max_blocks: int) -> np.ndarray:
        bt = np.zeros((max_blocks,), np.int32)
        n = len(self.blocks)
        if n > max_blocks:
            raise ValueError(f"sequence {self.uid} needs {n} blocks > "
                             f"max_blocks_per_sequence {max_blocks}")
        bt[:n] = self.blocks
        return bt
