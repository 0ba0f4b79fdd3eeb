"""Dynamic SplitFuse pass scheduler.

Parity: the FastGen scheduling policy (reference ``blogs/deepspeed-fastgen`` §
"Dynamic SplitFuse", and the ``can_schedule``/``query`` accounting in
``inference/v2/engine_v2.py:153-227``): long prompts are decomposed into chunks
processed across passes; short work is composed so every pass runs near the token
budget. Each pass here = all ready decode tokens (one per active sequence, up to
``max_ragged_sequence_count``) + up to ``num_chunk_slots`` prompt chunks of
``chunk_slot_size`` tokens each — the chunks' matmuls amortise the decode tokens'
bandwidth (the SplitFuse win), and multiple slots per pass keep prefill from
serialising on per-pass dispatch costs; attention splits per section (batched
chunked flash for the slots, paged flash-decode for the rest) in
``ragged_model.py``.
"""

from __future__ import annotations

from typing import Dict, List, Optional, TYPE_CHECKING, Tuple

import numpy as np

from deepspeed_tpu.inference.v2.attention import STATE_SNAPSHOT_MSG
from deepspeed_tpu.inference.v2.config_v2 import DSStateManagerConfig
from deepspeed_tpu.inference.v2.ragged.blocked_allocator import BlockedAllocator
from deepspeed_tpu.inference.v2.ragged.kv_cache import BlockedKVCache
from deepspeed_tpu.inference.v2.ragged.ragged_batch import RaggedBatch
from deepspeed_tpu.inference.v2.ragged.sequence_descriptor import DSSequenceDescriptor
from deepspeed_tpu.inference.v2.ragged.state_pool import StateSlotAllocator
from deepspeed_tpu.monitor.trace import tracer as _tracer

if TYPE_CHECKING:  # avoid an import cycle at runtime
    from deepspeed_tpu.inference.v2.prefix_cache import RadixPrefixCache


class DynamicSplitFuseScheduler:

    def __init__(self, config: DSStateManagerConfig, cache: BlockedKVCache,
                 allocator: BlockedAllocator,
                 prefix_cache: "Optional[RadixPrefixCache]" = None):
        self.config = config
        self.cache = cache
        self.allocator = allocator
        # radix-tree KV reuse (prefix_cache.py): new prompts adopt cached
        # pages at admission, completed sequences release pages back to the
        # tree instead of the free list. None = cache off (reference
        # recompute-everything behaviour). Mutually exclusive with the
        # sliding-window page ring (ring reuse overwrites pages in place, so
        # a cached page's content would rot under a live sharer).
        self.prefix_cache = prefix_cache
        # prompt tokens actually prefilled (post-cache): what a cache hit or
        # a cache-aware route saved is read off this counter
        self.prefill_tokens_completed = 0
        self.seqs: Dict[int, DSSequenceDescriptor] = {}
        bs = cache.config.block_size
        self.max_blocks = -(-config.max_context // bs)
        # sliding-window span (set by the engine from the model spec). With a
        # window, per-sequence physical KV is a PAGE RING of ring_pages
        # blocks: logical page i beyond the ring reuses blocks[i - ring];
        # dead tokens are overwritten in place, so a sequence's KV footprint
        # is bounded by the window however long it runs (the ZeRO-Inference
        # long-context analog of the reference's sliding cache).
        self.window: Optional[int] = None
        # record token history even without a prefix cache (set by the
        # engine when speculative decoding is on: the n-gram proposer drafts
        # from each sequence's prompt history, spec/proposer.py)
        self.record_history_always = False
        # recurrent-state slots (ragged/state_pool.py), set by the engine for
        # a model with state-space layers: one per tracked sequence, taken
        # here at admission, freed at flush
        self.state_slots: "Optional[StateSlotAllocator]" = None
        # set by the engine for a model in which NO layer holds pages (every
        # layer keeps a state slot instead): no block is funded a token, a
        # sequence's block table stays empty (every entry the scratch page),
        # and admission rests on the tracked-sequence count alone
        self.pageless = False
        # set by the engine for a model that generates by diffusion over
        # blocks (``spec.causal_block`` B > 1): a prompt's whole blocks are
        # prefilled, in chunks cut at multiples of B (a block split over two
        # chunk slots could not see its later half: the slot size is a
        # multiple of B, ``validate_engine_build``), and its last ``P mod B``
        # tokens are kept for the block pipeline (``seq.block_open``)
        self.causal_block = 1

    @property
    def _dump_slot(self) -> int:
        return 0 if self.state_slots is None else self.state_slots.total

    @property
    def _pass_take_cap(self) -> int:
        """Max prompt tokens one sequence may take in one pass under a
        window (also bounds the live span the ring must cover)."""
        cfg = self.config
        return min(self.window + self.cache.config.block_size,
                   cfg.num_chunk_slots * cfg.chunk_slot_size)

    @property
    def ring_pages(self) -> Optional[int]:
        """Physical pages per sequence under a window. The live span during
        a pass is [earliest_query - window + 1, write_head]: a chunked
        continuation pass of T tokens still needs ``window`` tokens behind
        its FIRST query row while writing T ahead, so the ring covers
        window + T (+1 page of slack) — not just the window. Aliased logical
        pages are then >= ring*bs > window + T tokens apart: no pass can
        read or scatter-collide with a page it is overwriting."""
        if self.window is None:
            return None
        bs = self.cache.config.block_size
        return -(-(self.window + self._pass_take_cap) // bs) + 1

    def ring_covers(self, n_tokens: int) -> bool:
        """True iff a consumer may freeze page reads while writing
        ``n_tokens`` ahead (the decode step's side buffer: the pool frozen
        through the layers, the step's token written after them — and the
        next step's reserved — so ``ring_covers(2)``): the ring spans window
        + _pass_take_cap live tokens, so a frozen read is safe only when the
        whole write fits in the take the ring was sized for. Without a
        window there is no ring — always True."""
        if self.window is None:
            return True
        return n_tokens <= self._pass_take_cap

    # ------------------------------------------------------------------ #
    # sequence admission (parity: engine_v2.put token intake)
    # ------------------------------------------------------------------ #

    def add_tokens(self, uid: int, tokens: np.ndarray) -> None:
        tokens = np.asarray(tokens, np.int32)
        seq = self.seqs.get(uid)
        known = 0 if seq is None else seq.seen_tokens + len(seq.pending)
        total = known + len(tokens)
        if total > self.config.max_context:
            raise ValueError(f"sequence {uid}: {total} tokens > max_context "
                             f"{self.config.max_context}")
        new_seq = seq is None
        if self.causal_block > 1 and not new_seq:
            raise ValueError(
                f"sequence {uid}: a model that generates by diffusion over "
                "blocks takes a sequence's prompt once (its later tokens are "
                "the block pipeline's)")
        if new_seq:
            if len(self.seqs) >= self.config.max_tracked_sequences:
                raise RuntimeError(
                    f"max_tracked_sequences={self.config.max_tracked_sequences} exceeded")
            seq = self.seqs[uid] = DSSequenceDescriptor(uid=uid)
            if self.state_slots is not None:
                seq.state_slot = self.state_slots.take()
                _tracer.bump("serve/state_slots/taken")
            if self.prefix_cache is not None:
                seq.weight_version = self.prefix_cache.weight_version
        if self._cache_active or self.record_history_always:
            seq.record_history(tokens)
        if self._cache_active:
            if new_seq and len(tokens) > 1:
                # adopt every cached whole-block prefix: matched pages join
                # the block table with ZERO prefill scheduled; only the
                # uncached tail (always >= 1 token, so the last token's
                # logits are computed fresh) goes through SplitFuse
                m = self.prefix_cache.match(tokens)
                if m.n_cached:
                    seq.blocks.extend(m.blocks)
                    seq.seen_tokens = m.n_cached
                    seq.cached_tokens = m.n_cached
                    tokens = tokens[m.n_cached:]
        if self.causal_block > 1:
            whole = len(tokens) - len(tokens) % self.causal_block
            seq.block_open = tokens[whole:]
            tokens = tokens[:whole]
        seq.extend_pending(tokens)

    @property
    def _cache_active(self) -> bool:
        return self.prefix_cache is not None and self.window is None

    def flush(self, uid: int) -> None:
        """Release a sequence's KV blocks (parity: ``engine_v2.flush``). With
        the prefix cache on, pages return to the radix tree — warm for the
        next matching prompt — instead of the free list; eviction reclaims
        them under pool pressure."""
        seq = self.seqs.pop(uid, None)
        if seq is not None and seq.state_slot >= 0:
            # what the slot holds stays there: the next sequence to take it
            # starts from zero (RaggedBatch.chunk_state_mode 0)
            self.state_slots.free(seq.state_slot)
            _tracer.bump("serve/state_slots/freed")
        if seq is None or not seq.blocks:
            return
        # ring reuse repeats physical ids in the logical list — settle each once
        uniq = list(dict.fromkeys(seq.blocks))
        if self._cache_active \
                and seq.weight_version == self.prefix_cache.weight_version:
            known = self._cacheable_tokens(seq)
            self.prefix_cache.release(seq.history(known), uniq)
        else:
            # no cache — or this sequence's KV predates a weight swap
            # (weight_version stamp trails the tree): old-weight pages must
            # never be filed into the post-swap tree, so they free instead
            self.allocator.free(uniq)

    @staticmethod
    def _cacheable_tokens(seq: DSSequenceDescriptor) -> int:
        """Tokens whose (position -> token id) mapping is certain: the
        contiguous recorded-history prefix, capped by what the KV actually
        holds. Pages beyond this are released, never cached."""
        valid = seq.history_len if seq.history_valid is None \
            else seq.history_valid
        return min(valid, seq.seen_tokens)

    # ------------------------------------------------------------------ #
    # capacity queries (parity: engine_v2.query/can_schedule :153-227)
    # ------------------------------------------------------------------ #

    def _new_blocks_needed(self, seq: DSSequenceDescriptor,
                           new_tokens: int) -> int:
        """Fresh allocator blocks required for ``new_tokens`` more tokens —
        under a window, capped by the ring (pages beyond it are reuses)."""
        if self.pageless:
            return 0
        bs = self.cache.config.block_size
        need = seq.kv_blocks_needed(new_tokens, bs)
        ring = self.ring_pages
        if ring is not None:
            need = min(need, max(0, ring - len(seq.blocks)))
        return need

    def _available_blocks(self) -> int:
        """Blocks obtainable right now: the free list plus cached pages held
        only by the radix tree (evicted on demand by ``_alloc``)."""
        free = self.allocator.free_blocks
        if self._cache_active:
            free += self.prefix_cache.evictable_blocks
        return free

    def _alloc(self, num_blocks: int) -> np.ndarray:
        """Allocate, LRU-evicting idle cached pages to cover a shortfall."""
        short = num_blocks - self.allocator.free_blocks
        if short > 0 and self._cache_active:
            self.prefix_cache.evict(short)
        return self.allocator.allocate(num_blocks)

    def query(self, uid: int, max_request_tokens: int) -> Tuple[int, int]:
        """(max new tokens fundable by free blocks, available blocks).
        Accounts for queued-but-unprocessed pending tokens, which will consume
        the same pool; cached-but-idle prefix pages count as available (they
        evict on demand)."""
        seq = self.seqs.get(uid, DSSequenceDescriptor(uid=uid))
        bs = self.cache.config.block_size
        avail = self._available_blocks()
        if self.pageless or (self.ring_pages is not None
                             and len(seq.blocks) >= self.ring_pages):
            # no pages to fund, or the ring complete: any request fits in
            # place (up to max_context)
            return max_request_tokens, avail
        slack = len(seq.blocks) * bs - seq.seen_tokens - len(seq.pending)
        fundable = max(0, slack + avail * bs)
        return min(max_request_tokens, fundable), avail

    def can_schedule(self, uids: List[int], lengths: List[int]) -> bool:
        needed = 0
        for uid, n in zip(uids, lengths):
            seq = self.seqs.get(uid, DSSequenceDescriptor(uid=uid))
            needed += self._new_blocks_needed(seq, len(seq.pending) + n)
        # free list first: the evictable count walks the whole radix tree,
        # only worth it on an actual shortfall
        if needed > self.allocator.free_blocks \
                and needed > self._available_blocks():
            return False
        new = sum(1 for u in uids if u not in self.seqs)
        return len(self.seqs) + new <= self.config.max_tracked_sequences

    def has_pending(self) -> bool:
        return any(len(s.pending) > 0 for s in self.seqs.values())

    @property
    def available_blocks(self) -> int:
        """Blocks obtainable right now (free list + evictable cached pages) —
        the capacity number the serving frontend's admission model plans
        with."""
        return self._available_blocks()

    def blocks_needed(self, uids: List[int], n_tokens: int) -> int:
        """Fresh allocator blocks a fused-decode reservation of ``n_tokens``
        more tokens for every uid would take (``decode_batch``'s per-run
        ``reserve``) — the serving frontend's per-slice funding check."""
        return sum(self._new_blocks_needed(self.seqs[u], n_tokens)
                   for u in uids)

    # ------------------------------------------------------------------ #
    # preempt-offload support (serving frontend; docs/SERVING.md)
    # ------------------------------------------------------------------ #

    def private_tail(self, uid: int) -> Tuple[int, List[int]]:
        """``(kept, tail)``: the maximal *suffix* of ``uid``'s block table
        held by nobody else (allocator refcount 1) — the pages preemption may
        offload. Shared pages (radix-tree references, co-holding sequences)
        are always a prefix here: the tree files/matches whole-block
        prefixes only, and eviction never touches a page a live sequence
        holds — so a shared page's content is stable and the sequence simply
        keeps its references across the preemption."""
        if self.window is not None:
            raise NotImplementedError(
                "preemption with a sliding-window page ring is not wired "
                "(the logical block list aliases physical pages)")
        blocks = self.seqs[uid].blocks
        k = len(blocks)
        while k > 0 and self.allocator.ref_count(blocks[k - 1]) == 1:
            k -= 1
        return k, list(blocks[k:])

    def drop_tail(self, uid: int, kept: int) -> None:
        """Free the blocks beyond ``kept`` and truncate the block table —
        the releasing half of a preempt-offload (page CONTENT must already
        be copied out; ``free`` recycles the ids immediately)."""
        seq = self.seqs[uid]
        self.allocator.free(seq.blocks[kept:])
        del seq.blocks[kept:]

    def adopt_sequence(self, uid: int, tokens: np.ndarray,
                       n_blocks: int) -> List[int]:
        """Create a sequence whose KV was computed ELSEWHERE — the import
        half of a cross-engine prefill->decode handoff (``engine.import_kv``;
        serving/cluster.py). Allocates ``n_blocks`` fresh pages (LRU-evicting
        idle cached pages on a shortfall), records the token history, and
        marks all ``tokens`` as seen — the caller scatters the page CONTENT
        in (``engine.put_pages``) before the sequence decodes. Returns the
        allocated ids in logical order, exactly like ``grow_tail``."""
        if self.window is not None:
            raise NotImplementedError(
                "cross-engine KV adoption with a sliding-window page ring "
                "is not wired (the logical block list aliases physical "
                "pages)")
        if self.state_slots is not None:
            raise NotImplementedError(STATE_SNAPSHOT_MSG.format(
                what="adopting a sequence whose pages were computed elsewhere"
                " (import_kv)"))
        tokens = np.asarray(tokens, np.int32)
        if uid in self.seqs:
            raise ValueError(f"sequence {uid} is already tracked")
        if len(tokens) < 1:
            raise ValueError("adopt_sequence needs at least one token")
        if len(tokens) > self.config.max_context:
            raise ValueError(f"sequence {uid}: {len(tokens)} tokens > "
                             f"max_context {self.config.max_context}")
        bs = self.cache.config.block_size
        if n_blocks * bs < len(tokens):
            raise ValueError(
                f"{n_blocks} pages cannot hold {len(tokens)} tokens at "
                f"block_size {bs}")
        if len(self.seqs) >= self.config.max_tracked_sequences:
            raise RuntimeError(
                f"max_tracked_sequences={self.config.max_tracked_sequences} "
                "exceeded")
        if n_blocks > self.allocator.free_blocks \
                and n_blocks > self._available_blocks():
            raise RuntimeError(
                f"cannot adopt sequence {uid}: needs {n_blocks} KV blocks, "
                f"{self._available_blocks()} obtainable")
        seq = self.seqs[uid] = DSSequenceDescriptor(uid=uid)
        if self._cache_active or self.record_history_always:
            seq.record_history(tokens)
        ids = [int(b) for b in self._alloc(n_blocks)] if n_blocks else []
        seq.blocks.extend(ids)
        seq.seen_tokens = len(tokens)
        return ids

    def grow_tail(self, uid: int, n: int) -> List[int]:
        """Append ``n`` fresh pages to ``uid``'s block table (LRU-evicting
        idle cached pages on a shortfall) and return their ids, in order —
        the restore half: the caller scatters the offloaded page contents
        into these before the sequence decodes again."""
        seq = self.seqs[uid]
        ids = [int(b) for b in self._alloc(n)] if n else []
        seq.blocks.extend(ids)
        return ids

    # ------------------------------------------------------------------ #
    # multi-step decode support (device-fused token loop)
    # ------------------------------------------------------------------ #

    def reserve(self, uid: int, n_tokens: int) -> None:
        """Pre-allocate KV blocks so ``uid`` can append ``n_tokens`` without
        host intervention (the fused N-step decode writes pages directly).
        Enforces the same max_context bound as ``add_tokens``."""
        seq = self.seqs[uid]
        total = seq.seen_tokens + len(seq.pending) + n_tokens
        if total > self.config.max_context:
            raise ValueError(f"sequence {uid}: {total} tokens > max_context "
                             f"{self.config.max_context}")
        self._ensure_blocks(seq, n_tokens)

    def decode_batch(self, uids: List[int], n_reserve: int,
                     scratch_block: int) -> "DecodeBatch":
        """Bucketed decode-only descriptors for the fused decode programs.

        Reserves ``n_reserve`` tokens of KV per sequence UP FRONT (so the
        per-step host work during a pipelined run is just the
        ``DecodeBatch.advance`` increments — the block tables already cover
        the whole run), then packs positions/block-tables/context-lengths
        into arrays padded to ``next_pow2(len(uids))`` rows. Pad rows point
        wholly at ``scratch_block`` (see DecodeBatch for why that is inert).
        """
        from deepspeed_tpu.utils.caching import next_pow2
        for u in uids:
            self.reserve(u, n_reserve)
        bucket = next_pow2(len(uids))
        mb = self.max_blocks
        bt = np.full((bucket, mb), scratch_block, np.int32)
        pos = np.zeros((bucket,), np.int32)
        for i, u in enumerate(uids):
            seq = self.seqs[u]
            bt[i] = seq.block_table(mb)
            pos[i] = seq.seen_tokens
        # pad rows: pos 0 -> ctx 1, attending exactly one (scratch) token
        ctx = pos + 1
        slots = None
        if self.state_slots is not None:
            slots = np.full((bucket,), self._dump_slot, np.int32)
            slots[:len(uids)] = [self.seqs[u].state_slot for u in uids]
        from deepspeed_tpu.inference.v2.ragged.ragged_batch import DecodeBatch
        return DecodeBatch(uids=[int(u) for u in uids], bucket=bucket,
                           positions=pos, block_tables=bt, ctx_lens=ctx,
                           state_slots=slots)

    def advance(self, uid: int, n_tokens: int) -> None:
        """Record ``n_tokens`` device-generated tokens (their KV was written
        by the fused loop; no pending compute remains)."""
        seq = self.seqs[uid]
        assert len(seq.pending) == 0, "advance() with pending host tokens"
        if self._cache_active and seq.history_valid is None:
            # the host never saw these tokens: history recorded after this
            # point is position-shifted, unusable as radix keys — seal the
            # contiguous prefix here (see DSSequenceDescriptor.history_valid)
            seq.history_valid = seq.history_len
        seq.seen_tokens += n_tokens

    def rollback_reserved(self, uid: int) -> List[int]:
        """Block-granular KV rollback: free every reserved-but-unused
        trailing block — pages wholly past ``seen_tokens`` — and truncate
        the block table. Returns the freed ids.

        This is the speculative-decode reject path's reclamation
        (``spec/pipeline.py``): a verify run reserves KV for full acceptance
        up front, and a reject-heavy run leaves whole pages the advanced
        history never reached. Only the FRESH suffix is ever touched:
        prefix-cache-shared pages and COW-adopted tails all hold tokens
        within ``seen_tokens`` (the tree files whole-block history prefixes;
        COW adoption copies a partial page the sequence then fills), so the
        rollback boundary can never cross a shared or content-bearing page
        — enforced by the refcount guard below, not just assumed."""
        if self.window is not None:
            # ring reuse repeats physical ids in the logical list; there is
            # no fresh suffix to roll back (and spec decode refuses windowed
            # models before ever reserving ahead)
            return []
        seq = self.seqs[uid]
        bs = self.cache.config.block_size
        need = -(-seq.seen_tokens // bs)
        tail = [int(b) for b in seq.blocks[need:]]
        if not tail:
            return []
        shared = [b for b in tail if self.allocator.ref_count(b) != 1]
        if shared:
            raise RuntimeError(
                f"rollback of sequence {uid} would free shared block(s) "
                f"{shared} (refcount != 1) — reserved tails must be fresh")
        self.allocator.free(tail)
        del seq.blocks[need:]
        return tail

    # ------------------------------------------------------------------ #
    # pass construction
    # ------------------------------------------------------------------ #

    def _ensure_blocks(self, seq: DSSequenceDescriptor, new_tokens: int) -> None:
        if self.pageless:
            return
        bs = self.cache.config.block_size
        ring = self.ring_pages
        if ring is None:
            need = seq.kv_blocks_needed(new_tokens, bs)
            if need:
                seq.blocks.extend(int(b) for b in self._alloc(need))
            return
        target = -(-(seq.seen_tokens + new_tokens) // bs)   # logical pages
        fresh = min(max(0, target - len(seq.blocks)),
                    max(0, ring - len(seq.blocks)))
        if fresh:
            seq.blocks.extend(int(b) for b in self._alloc(fresh))
        while len(seq.blocks) < target:                      # ring reuse
            seq.blocks.append(seq.blocks[len(seq.blocks) - ring])

    def schedule_pass(self) -> Optional[RaggedBatch]:
        """Build the next pass, or None when no pending work exists."""
        cfg = self.config
        NC, Cs = cfg.num_chunk_slots, cfg.chunk_slot_size
        S, MB = cfg.max_ragged_sequence_count, self.max_blocks
        bs = self.cache.config.block_size
        batch = RaggedBatch(num_slots=NC, slot_size=Cs, max_sequences=S,
                            max_blocks=MB, dump_slot=self._dump_slot)
        kv_dest = np.full((NC * Cs + S,), self.cache.oob_sentinel, np.int32)

        # decode rows: sequences holding exactly one pending token
        decode = [s for s in self.seqs.values()
                  if len(s.pending) == 1 and s.seen_tokens > 0]
        decode = decode[:S]
        for row, seq in enumerate(decode):
            self._ensure_blocks(seq, 1)
            pos = seq.seen_tokens
            batch.decode_uids.append(seq.uid)
            batch.decode_tokens[row] = seq.pending[0]
            batch.decode_positions[row] = pos
            batch.decode_block_tables[row] = seq.block_table(MB)
            batch.decode_ctx_lens[row] = pos + 1
            if seq.state_slot >= 0:
                batch.decode_state_slot[row] = seq.state_slot
            if not self.pageless:
                kv_dest[NC * Cs + row] = self.cache.flat_write_index(
                    seq.blocks[pos // bs], pos % bs)
            seq.in_flight_tokens = 1

        # prompt chunks, up to NC slots: longest pending first (prefer
        # finishing prefills). A sequence may claim SEVERAL consecutive slots
        # in one pass (its chunk KV is scattered before attention runs, so a
        # later slot sees the earlier slots' tokens) — a lone long prompt
        # then prefills at the full slot capacity per pass, not one slot.
        prompts = sorted((s for s in self.seqs.values()
                          if len(s.pending) > 1 or
                          (len(s.pending) == 1 and s.seen_tokens == 0
                           and s.uid not in batch.decode_uids)),
                         key=lambda s: -len(s.pending))
        sl = 0
        from_zero = True   # every chunk sequence starts at position 0?
        # page-granular write plan (pure-prefill fast path; see RaggedBatch)
        PW = NC * Cs // bs + NC
        batch.page_ids = np.full((PW,), self.cache.config.num_blocks, np.int32)
        batch.page_rows = np.zeros((PW,), np.int32)
        batch.page_fill = np.zeros((PW,), np.int32)
        pw = 0
        for seq in prompts:
            if sl >= NC:
                break
            take = min(len(seq.pending), (NC - sl) * Cs)
            if self.window is not None:
                # the ring covers window + _pass_take_cap tokens of live
                # span; taking more in one pass would overwrite pages the
                # pass's own queries still need (the remainder prefills on
                # the next pass)
                take = min(take, self._pass_take_cap)
            self._ensure_blocks(seq, take)
            blocks = np.asarray(seq.blocks, np.int32)
            batch.chunk_uids.append(seq.uid)
            batch.chunk_is_final.append(take == len(seq.pending))
            if seq.seen_tokens > 0:
                from_zero = False
            elif not self.pageless:
                # from position 0, tokens fill pages in order: one plan entry
                # per touched page, rows contiguous from this seq's first row.
                # Under a window, pages wholly dead by the end of the take are
                # skipped — their tokens are never attended again, and writing
                # them could collide with a ring-reused live page in the same
                # scatter.
                r0_seq = sl * Cs
                for p in range(-(-take // bs)):
                    if (self.window is not None
                            and (p + 1) * bs <= take - self.window):
                        continue
                    batch.page_ids[pw] = blocks[p]
                    batch.page_rows[pw] = r0_seq + p * bs
                    batch.page_fill[pw] = min(bs, take - p * bs)
                    pw += 1
            taken = 0
            while taken < take:
                n = min(Cs, take - taken)
                q0 = seq.seen_tokens + taken
                positions = q0 + np.arange(n, dtype=np.int32)
                r0 = sl * Cs
                batch.chunk_tokens[r0:r0 + n] = seq.pending[taken:taken + n]
                batch.chunk_positions[r0:r0 + n] = positions
                batch.chunk_ntok[sl] = n
                batch.chunk_block_tables[sl] = seq.block_table(MB)
                batch.chunk_q0[sl] = q0
                batch.chunk_ctx_lens[sl] = q0 + n
                if seq.state_slot >= 0:
                    batch.chunk_state_slot[sl] = seq.state_slot
                    batch.chunk_state_mode[sl] = (2 if taken else
                                                  1 if q0 else 0)
                batch.row_seg[r0:r0 + n] = len(batch.chunk_uids) - 1
                if not self.pageless:
                    kv_dest[r0:r0 + n] = self.cache.flat_write_index(
                        blocks[positions // bs], positions % bs)
                batch.slot_uid.append(seq.uid)
                taken += n
                sl += 1
            seq.in_flight_tokens = take

        batch.kv_dest = kv_dest
        batch.pure_prefill = (not batch.decode_uids and bool(batch.chunk_uids)
                              and from_zero)
        if batch.current_sequences == 0:
            return None
        # flash_attention_packed's correctness contract (see its docstring:
        # per-sequence rows contiguous-in-order, padding rows seg -1) is
        # PRODUCED here, so it is asserted here: non-padding row_seg values
        # must be non-decreasing and positions within a segment must advance
        # by exactly 1. O(rows) numpy — negligible next to the pass itself.
        live = batch.row_seg >= 0
        segs = batch.row_seg[live]
        if segs.size > 1:
            dseg = np.diff(segs)
            dpos = np.diff(batch.chunk_positions[live])
            if not (np.all(dseg >= 0) and np.all(dpos[dseg == 0] == 1)):
                raise AssertionError(
                    "scheduler produced an interleaved/unordered packed "
                    "batch; flash_attention_packed requires per-sequence "
                    "rows contiguous and position-ordered")
        return batch

    def complete_pass(self, batch: RaggedBatch) -> List[int]:
        """Advance descriptors after the pass ran; returns uids whose *next-token
        logits* this pass produced (final prompt chunks + all decode rows)."""
        finished: List[int] = []
        for uid, is_final in zip(batch.chunk_uids, batch.chunk_is_final):
            seq = self.seqs[uid]
            n = seq.in_flight_tokens
            seq.seen_tokens += n
            seq.pending = seq.pending[n:]
            seq.in_flight_tokens = 0
            self.prefill_tokens_completed += n
            if is_final:
                finished.append(uid)
                if self._cache_active:
                    # eager insert: file the finished prompt's FULL pages into
                    # the radix tree now (tree takes its own references; the
                    # live sequence keeps its own), so later arrivals reuse
                    # them without waiting for this sequence to flush. Partial
                    # tails are only filed at flush — one tree node per page,
                    # so eviction accounting stays exact. The filed_tokens
                    # watermark skips the re-walk when no NEW full page
                    # completed since the last insert (multi-turn put()s).
                    bs = self.cache.config.block_size
                    known = self._cacheable_tokens(seq)
                    full = (known // bs) * bs
                    if full > seq.filed_tokens and seq.weight_version \
                            == self.prefix_cache.weight_version:
                        self.prefix_cache.insert(seq.history(full),
                                                 seq.blocks[:full // bs],
                                                 transfer_refs=False)
                        seq.filed_tokens = full
        for uid in batch.decode_uids:
            seq = self.seqs[uid]
            seq.seen_tokens += 1
            seq.pending = seq.pending[1:]
            seq.in_flight_tokens = 0
            finished.append(uid)
        return finished
