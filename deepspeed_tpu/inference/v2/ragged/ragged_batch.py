"""Host-side pass descriptor arrays.

Parity: ``RaggedBatchWrapper`` (reference ``inference/v2/ragged/ragged_wrapper.py``)
— the per-forward metadata buffers (token ids, inflight descriptors, KV block
tables) assembled on host and shipped to device once per pass. The reference uses
pinned host buffers (``ragged/csrc/fast_host_buffer.cu``); here plain numpy arrays
feed ``jax.device_put`` / jit donation.

Pass layout (static shapes; see ``ragged_model.py`` for how each section is used):

  - **chunk section** (``num_slots`` slots of ``slot_size`` rows): several
    sequences' prompt chunks prefill together in one pass — one chunk per pass
    would serialise N prompts on N pass dispatches (host descriptor build +
    transfer RTT each); Dynamic SplitFuse composes them with the ready decode
    tokens so prefill never stalls token generation.
  - **decode section** (``max_sequences`` rows): one query token per sequence,
    served by the paged flash-decode kernel.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List

import numpy as np


@dataclass
class RaggedBatch:
    # static capacities
    num_slots: int                            # chunk slots per pass
    slot_size: int                            # tokens per slot
    max_sequences: int
    max_blocks: int

    # chunk section (num_slots prompt chunks, slot-major rows). A sequence
    # may span several consecutive slots in one pass: chunk_uids and
    # chunk_is_final are per SEQUENCE (scheduling order); slot_uid is per
    # filled SLOT (the logits row for a finished prompt is its last slot).
    chunk_uids: List[int] = field(default_factory=list)   # per sequence
    slot_uid: List[int] = field(default_factory=list)     # per filled slot
    chunk_tokens: np.ndarray = None           # [NC * Cs] int32
    chunk_positions: np.ndarray = None        # [NC * Cs] int32
    chunk_ntok: np.ndarray = None             # [NC] int32 (0 = empty slot)
    chunk_block_tables: np.ndarray = None     # [NC, MB] int32
    chunk_q0: np.ndarray = None               # [NC] int32
    chunk_ctx_lens: np.ndarray = None         # [NC] int32 (0 = empty slot)
    chunk_is_final: List[bool] = field(default_factory=list)  # per sequence

    # decode section
    decode_uids: List[int] = field(default_factory=list)
    decode_tokens: np.ndarray = None          # [S] int32
    decode_positions: np.ndarray = None       # [S] int32
    decode_block_tables: np.ndarray = None    # [S, MB] int32
    decode_ctx_lens: np.ndarray = None        # [S] int32 (0 => inactive row)

    # flat KV scatter destinations for every new token, chunk rows then decode
    # rows; padding rows hold the cache's OOB sentinel so the write drops them
    kv_dest: np.ndarray = None                # [NC * Cs + S] int32

    # per-chunk-row sequence index (position in chunk_uids; -1 = padding row)
    # for the packed-flash prefill fast path; decode rows are not included
    row_seg: np.ndarray = None                # [NC * Cs] int32
    # True when this pass is prefill-from-zero only (no decode rows, every
    # chunk sequence starts at position 0): attention then needs no paged
    # reads at all and the engine routes to the packed-flash forward
    pure_prefill: bool = False
    # page-granular KV write plan for pure-prefill passes: each written page
    # is one contiguous run of chunk rows (tokens fill pages in order from
    # slot 0), so the pool update is a scatter of whole [bs, D] windows over
    # ~CT/bs page indices instead of CT*Hkv single rows (TPU scatters cost
    # per index — measured 57 ms -> ~6 ms per wave at 32x128 tokens, v5e-1).
    # page_ids: global page index (NB = padding sentinel, dropped);
    # page_rows: chunk-row index of the page's first token; page_fill: tokens
    # written to that page (stale rows past fill are never read — every
    # reader is bounded by ctx_len).
    page_ids: np.ndarray = None               # [PW] int32
    page_rows: np.ndarray = None              # [PW] int32
    page_fill: np.ndarray = None              # [PW] int32

    # recurrent-state slots (ragged/state_pool.py; models with state-space
    # layers). Empty chunk slots and inactive decode rows hold ``dump_slot``.
    # chunk_state_mode says where a chunk slot's state comes from: 0 = zero
    # (the slot holds its sequence's position 0), 1 = the pool (the sequence
    # has context from an earlier pass), 2 = the chunk slot before it (the
    # same sequence's previous ``slot_size`` tokens in this pass). A slot
    # followed by a mode-2 slot writes nothing back; the sequence's last
    # slot of the pass does.
    dump_slot: int = 0
    chunk_state_slot: np.ndarray = None       # [NC] int32
    chunk_state_mode: np.ndarray = None       # [NC] int32
    decode_state_slot: np.ndarray = None      # [S] int32

    def __post_init__(self):
        NC, Cs = self.num_slots, self.slot_size
        S, MB = self.max_sequences, self.max_blocks
        if self.chunk_tokens is None:
            self.chunk_tokens = np.zeros((NC * Cs,), np.int32)
        if self.chunk_positions is None:
            self.chunk_positions = np.zeros((NC * Cs,), np.int32)
        if self.chunk_ntok is None:
            self.chunk_ntok = np.zeros((NC,), np.int32)
        if self.chunk_block_tables is None:
            self.chunk_block_tables = np.zeros((NC, MB), np.int32)
        if self.chunk_q0 is None:
            self.chunk_q0 = np.zeros((NC,), np.int32)
        if self.chunk_ctx_lens is None:
            self.chunk_ctx_lens = np.zeros((NC,), np.int32)
        if self.decode_tokens is None:
            self.decode_tokens = np.zeros((S,), np.int32)
        if self.decode_positions is None:
            self.decode_positions = np.zeros((S,), np.int32)
        if self.decode_block_tables is None:
            self.decode_block_tables = np.zeros((S, MB), np.int32)
        if self.decode_ctx_lens is None:
            self.decode_ctx_lens = np.zeros((S,), np.int32)
        if self.kv_dest is None:
            self.kv_dest = np.zeros((NC * Cs + S,), np.int32)
        if self.row_seg is None:
            self.row_seg = np.full((NC * Cs,), -1, np.int32)
        if self.chunk_state_slot is None:
            self.chunk_state_slot = np.full((NC,), self.dump_slot, np.int32)
        if self.chunk_state_mode is None:
            self.chunk_state_mode = np.zeros((NC,), np.int32)
        if self.decode_state_slot is None:
            self.decode_state_slot = np.full((S,), self.dump_slot, np.int32)
        # page_ids/page_rows/page_fill stay None here: their static size
        # (NC*Cs/bs + NC) needs the cache block size, so the scheduler
        # allocates them (schedule_pass)

    @property
    def current_tokens(self) -> int:
        return int(self.chunk_ntok.sum()) + len(self.decode_uids)

    @property
    def current_sequences(self) -> int:
        return len(self.chunk_uids) + len(self.decode_uids)

    def device_arrays(self) -> Dict[str, Any]:
        """The dict handed to the jitted pass (shapes static across passes)."""
        return {
            "chunk_tokens": self.chunk_tokens,
            "chunk_positions": self.chunk_positions,
            "chunk_ntok": self.chunk_ntok,
            "chunk_block_tables": self.chunk_block_tables,
            "chunk_q0": self.chunk_q0,
            "chunk_ctx_lens": self.chunk_ctx_lens,
            "decode_tokens": self.decode_tokens,
            "decode_positions": self.decode_positions,
            "decode_block_tables": self.decode_block_tables,
            "decode_ctx_lens": self.decode_ctx_lens,
            "kv_dest": self.kv_dest,
            "row_seg": self.row_seg,
            "page_ids": self.page_ids,
            "page_rows": self.page_rows,
            "page_fill": self.page_fill,
            "chunk_state_slot": self.chunk_state_slot,
            "chunk_state_mode": self.chunk_state_mode,
            "decode_state_slot": self.decode_state_slot,
        }


@dataclass
class DecodeBatch:
    """BUCKETED decode-only descriptor set for the fused decode step
    (the double-buffered ``DecodePipeline`` and its speculative twin).

    Row count is padded to ``bucket = next_pow2(len(uids))`` so every device
    program downstream is keyed by the bucket, not the live count: admitting
    or retiring a sequence moves between cached executables instead of
    triggering a recompile (docs/SERVING.md "bucketing grids"). Pad rows are
    inert fake sequences — position 0, context 1, and a block table that is
    ALL the engine's scratch page, so whatever they read is garbage that
    never reaches a real row and whatever they write lands in the scratch
    page no real sequence maps. This relies on decode being row-independent
    (true for the dense ragged models served here; a capacity-constrained
    MoE router would couple rows and need pad-row masking first).

    Advanced per step by :meth:`advance` — the pipeline's "build step N+1"
    stage is exactly these two tiny allocations, which is why the host side
    of a pipelined decode step is ~free once KV blocks are pre-reserved.
    """
    uids: List[int]
    bucket: int
    positions: np.ndarray       # [bucket] int32; pad rows 0
    block_tables: np.ndarray    # [bucket, MB] int32; pad rows all-scratch
    ctx_lens: np.ndarray        # [bucket] int32; pad rows 1
    # [bucket] int32 recurrent-state slots, pad rows the dump slot; None for
    # a model with no state-space layers (run-invariant, like block tables)
    state_slots: "np.ndarray | None" = None

    @property
    def live(self) -> int:
        return len(self.uids)

    def advance(self, n: int = 1) -> None:
        """Advance every row (pad rows included — their writes stay inside
        the scratch page at any position) by ``n`` generated tokens.

        REBINDS the arrays instead of ``+=``: the previous step's dispatch is
        still in flight and jax's CPU backend may alias host numpy buffers
        zero-copy, so an in-place increment can race the async computation
        reading them (observed as nondeterministic token divergence in the
        pipeline tests; jax arrays made from these buffers must be treated
        as frozen once dispatched)."""
        self.positions = self.positions + np.int32(n)
        self.ctx_lens = self.ctx_lens + np.int32(n)

    def advance_rows(self, counts: np.ndarray) -> None:
        """Per-row variable advance (speculative decode: row i emitted
        ``counts[i]`` tokens this step — accepted draft prefix plus the
        bonus token; pad-row entries advance inside the scratch page like
        :meth:`advance`). Same REBIND discipline as ``advance`` — the
        arrays already uploaded for an in-flight dispatch stay frozen."""
        counts = np.asarray(counts, np.int32)
        assert counts.shape == self.positions.shape, \
            (counts.shape, self.positions.shape)
        self.positions = self.positions + counts
        self.ctx_lens = self.ctx_lens + counts
