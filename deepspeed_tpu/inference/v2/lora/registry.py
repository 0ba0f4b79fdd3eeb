"""Adapter registry: the lifecycle/refcount half of multi-tenant LoRA.

State machine per adapter (docs/SERVING.md "Multi-tenant LoRA"):

    REGISTERED --fault-in--> RESIDENT --evict--> EVICTED
         \\______________________________________/
                   (restore = fault-in from pinned buffers)

- **REGISTERED**: the validated checkpoint payload lives as a host master
  copy (``[rank, elements]``, pool dtype) — no device pages yet.
- **RESIDENT**: the adapter owns ``rank`` pool pages; its weights are
  gatherable by the decode programs. Residency persists after the last
  in-flight request releases it (an LRU cache, like KV prefix blocks).
- **EVICTED**: pages were fetched device->host into pinned
  ``SwapBufferPool`` buffers and freed — restore scatters the SAME bytes
  back (byte-exact round trip, the KV offload contract), returning the
  buffers to the pool.

Refcounts gate eviction exactly like KV pages: an adapter bound to any
in-flight request can never be evicted, so a decode batch's gather is
always backed. Fault-in under pool pressure evicts idle adapters LRU;
``maybe_fail("serve.lora_fault")`` sits inside the fault-in so a chaos
test can cancel mid-fault (rollback: allocated pages freed, binding
undone, refcounts at baseline).

Each fault-in/evict takes ONE pair of ``perf_counter`` stamps feeding both
the ``serve/lora/{fault,swap}`` tracer spans and the :class:`LoraStats`
counters (the stats-equals-spans discipline, docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import jax
import numpy as np

from deepspeed_tpu.inference.v2.lora.pool import LoraPagePool
from deepspeed_tpu.monitor.serving import LoraStats
from deepspeed_tpu.monitor.trace import tracer as _tracer
from deepspeed_tpu.runtime.swap_tensor.buffer_pool import SwapBufferPool
from deepspeed_tpu.utils.caching import next_pow2
from deepspeed_tpu.utils.fault_injection import maybe_fail as _maybe_fail
from deepspeed_tpu.utils.threads import make_lock

REGISTERED = "registered"
RESIDENT = "resident"
EVICTED = "evicted"


@dataclass
class _Adapter:
    name: str
    rank: int
    master: Optional[np.ndarray]          # [rank, elements] host master
    state: str = REGISTERED
    page_ids: List[int] = field(default_factory=list)
    bufs: List[np.ndarray] = field(default_factory=list)   # pinned (EVICTED)
    refcount: int = 0
    last_used: int = 0                    # LRU clock stamp


class LoraAdapterRegistry:
    """Adapter lifecycle over one :class:`LoraPagePool`.

    ONE mutator thread by design (the frontend's engine thread / a test's
    driver — the same discipline as the scheduler), but the cheap metadata
    readers (``names``/``rank``/``is_resident``/``can_admit``/``binding``)
    are called from CLIENT threads (``frontend.submit`` validation) and the
    router's adapter-state probe, so the maps they iterate are guarded by
    ``serving.lora.registry``. Device work — fault-in scatter, eviction
    fetch, the residency sync — always runs OUTSIDE that lock (threadlint
    TL002): a client thread listing adapters must never wait out a swap.
    The engine exposes this as ``engine.lora``."""

    def __init__(self, pool: LoraPagePool, swap_buffers: int = 16,
                 max_rank: Optional[int] = None,
                 stats: Optional[LoraStats] = None):
        self.pool = pool
        self.max_rank = max_rank
        self.swap = SwapBufferPool(max_buffers=swap_buffers)
        self.stats = stats if stats is not None else LoraStats()
        # guards _adapters/_bindings map SHAPE + adapter metadata fields
        # (state/refcount/rank) for cross-thread readers; device work and
        # payload copies stay outside it
        self._meta = make_lock("serving.lora.registry")
        self._adapters: Dict[str, _Adapter] = {}
        self._bindings: Dict[int, str] = {}   # uid -> adapter name
        self._clock = 0

    # -- registration ----------------------------------------------------- #

    def register(self, name: str, pages: Optional[np.ndarray]) -> None:
        """Register a validated adapter payload (``module_inject.lora``
        packs checkpoints into this page layout).

        ``pages``: ``[rank, elements]`` rank-slice rows in the pool dtype,
        or ``None``/empty for a rank-0 (no-op) adapter — rank-0 adapters
        own no pages, are trivially resident, and never join the rank
        bucket. Duplicate names: an IDENTICAL payload re-registers
        idempotently; a different payload replaces an IDLE adapter
        (device/host state dropped first) and refuses while any request
        holds the old one in flight."""
        rows = None
        rank = 0
        if pages is not None:
            rows = np.asarray(pages, self.pool.dtype)
            if rows.size == 0:
                rows = None
            elif rows.ndim != 2 or rows.shape[1] != self.pool.elements:
                raise ValueError(
                    f"adapter {name!r} payload shape {rows.shape} does not "
                    f"match this pool's page layout (rank, "
                    f"{self.pool.elements}) — pack it with "
                    "module_inject.load_lora_adapter against THIS engine")
            else:
                rank = rows.shape[0]
        if rank > self.pool.num_pages:
            raise ValueError(
                f"adapter {name!r} rank {rank} exceeds the pool "
                f"({self.pool.num_pages} pages) — raise lora.pool_pages or "
                "reduce the adapter rank")
        if self.max_rank is not None and rank > self.max_rank:
            raise ValueError(
                f"adapter {name!r} rank {rank} exceeds lora.max_rank "
                f"({self.max_rank}) — the warmed (bucket, rank-bucket) "
                "program grid stops there, so admitting it would compile "
                "mid-steady-state; raise lora.max_rank (and re-warm)")
        with self._meta:
            old = self._adapters.get(name)
        if old is not None:
            same = (old.rank == rank
                    and (rows is None if old.master is None
                         else (old.master is not None
                               and np.array_equal(old.master, rows))))
            if same:
                return                      # idempotent re-register
            if old.refcount > 0:
                raise ValueError(
                    f"adapter {name!r} is bound to {old.refcount} in-flight "
                    "request(s) — a re-register with a DIFFERENT payload "
                    "must wait until they finish (or use a new name)")
            self.unregister(name)
        with self._meta:
            self._adapters[name] = _Adapter(name=name, rank=rank,
                                            master=rows)
        self.stats.set_resident(name, rank == 0)

    def unregister(self, name: str) -> None:
        """Drop an IDLE adapter entirely (device pages freed, pinned
        buffers returned, master forgotten)."""
        ad = self._get(name)
        if ad.refcount > 0:
            raise ValueError(
                f"adapter {name!r} is bound to {ad.refcount} in-flight "
                "request(s) — cannot unregister")
        if ad.state == RESIDENT and ad.page_ids:
            self.pool.free(ad.page_ids)
        for buf in ad.bufs:
            self.swap.put(buf)
        with self._meta:
            del self._adapters[name]
        self.stats.drop(name)

    def drain_swap(self) -> int:
        """Return every EVICTED adapter's pinned buffers to the swap pool;
        returns the number of buffers drained.

        Byte-safe: the host master rows are retained for the adapter's
        whole lifetime, so a drained adapter just drops back to
        REGISTERED and its next fault-in re-uploads from the master
        instead of the pinned snapshot. Settles the pool to its quiescent
        baseline (``swap.outstanding == 0``) for leak accounting — a
        caller snapshots its pool baseline after this, otherwise whichever
        adapters HAPPEN to sit evicted at snapshot time read as leaked
        buffers."""
        with self._meta:
            evicted = [ad for ad in self._adapters.values()
                       if ad.state == EVICTED]
        drained = 0
        for ad in evicted:
            for buf in ad.bufs:
                self.swap.put(buf)
            drained += len(ad.bufs)
            with self._meta:
                ad.bufs = []
                ad.state = REGISTERED
        return drained

    def _get(self, name: str) -> _Adapter:
        try:
            return self._adapters[name]
        except KeyError:
            raise KeyError(
                f"unknown LoRA adapter {name!r} (registered: "
                f"{sorted(self._adapters)}) — register it via "
                "module_inject.load_lora_adapter first") from None

    # -- introspection (admission / router / engine dispatch) ------------- #

    @property
    def names(self) -> List[str]:
        with self._meta:
            return sorted(self._adapters)

    @property
    def rank_bucket(self) -> int:
        """The pow2 rank bucket EVERY LoRA decode program dispatches at:
        ``next_pow2(max registered rank)``, 0 when only rank-0/no adapters
        exist. Engine-stable after registration (NOT per-batch), so adapter
        churn inside the registered set never changes program signatures —
        the zero-steady-state-compile invariant."""
        with self._meta:
            ranks = [a.rank for a in self._adapters.values() if a.rank > 0]
        return next_pow2(max(ranks)) if ranks else 0

    def rank(self, name: str) -> int:
        with self._meta:
            return self._get(name).rank

    def is_resident(self, name: str) -> bool:
        with self._meta:
            ad = self._get(name)
            return ad.rank == 0 or ad.state == RESIDENT

    def refcount(self, name: str) -> int:
        with self._meta:
            return self._get(name).refcount

    def binding(self, uid: int) -> Optional[str]:
        with self._meta:
            return self._bindings.get(int(uid))

    def can_admit(self, name: str, releasing=()) -> bool:
        """Could ``acquire`` succeed right now without shedding anyone?
        True when resident, rank-0, or free + idle-evictable pages cover
        the rank (the admission loop's pool-pressure signal). ``releasing``
        simulates a set of uids whose bindings are about to drop (the
        planner's already-chosen preempt victims): an adapter becomes
        evictable when those releases would take its refcount to zero."""
        with self._meta:
            ad = self._get(name)
            if ad.rank == 0 or ad.state == RESIDENT:
                return True
            rel = {int(u) for u in releasing}
            held = {}
            for u, n in self._bindings.items():
                if u not in rel:
                    held[n] = held.get(n, 0) + 1
            evictable = sum(a.rank for a in self._adapters.values()
                            if a.state == RESIDENT
                            and held.get(a.name, 0) == 0)
        return self.pool.free_pages + evictable >= ad.rank

    # -- request lifecycle ------------------------------------------------ #

    def acquire(self, uid: int, name: str) -> None:
        """Bind request ``uid`` to adapter ``name`` and make it resident
        (faulting in — evicting idle adapters LRU — as needed). Exception-
        safe: a failure mid-fault (pool pressure, injected
        ``serve.lora_fault``) rolls the binding and refcount back and frees
        any pages allocated, so cancel-while-faulting leaves the registry
        at baseline."""
        uid = int(uid)
        with self._meta:
            assert uid not in self._bindings, \
                f"uid {uid} already bound to {self._bindings[uid]!r}"
            ad = self._get(name)
            hit = ad.rank == 0 or ad.state == RESIDENT
            ad.refcount += 1
            self._bindings[uid] = name
        try:
            self._ensure_resident(ad)     # device work: NOT under _meta
        except BaseException:
            with self._meta:
                ad.refcount -= 1
                del self._bindings[uid]
            raise
        with self._meta:
            self._clock += 1
            ad.last_used = self._clock
        self.stats.record_acquire(name, hit)

    def release(self, uid: int) -> None:
        """Unbind a finished/cancelled/shed request. The adapter STAYS
        resident (LRU-cached) until pool pressure evicts it."""
        uid = int(uid)
        with self._meta:
            name = self._bindings.pop(uid, None)
            if name is None:
                return
            ad = self._adapters[name]
            ad.refcount -= 1
            assert ad.refcount >= 0
        self.stats.record_release(name)

    # -- residency (fault-in / evict) ------------------------------------- #

    def _ensure_resident(self, ad: _Adapter) -> None:
        if ad.rank == 0 or ad.state == RESIDENT:
            return
        t0 = time.perf_counter()
        while self.pool.free_pages < ad.rank:
            victim = self._lru_victim(exclude=ad.name)
            if victim is None:
                raise RuntimeError(
                    f"LoRA pool pressure: adapter {ad.name!r} needs "
                    f"{ad.rank} pages, {self.pool.free_pages} free and "
                    "every resident adapter is bound to in-flight requests "
                    "— admission should defer this request (can_admit)")
            self.evict(victim.name)
        ids = self.pool.alloc(ad.rank)
        try:
            # chaos site: cancel-while-faulting (tests/unit/test_lora_serving.py
            # pins that the rollback restores refcounts + free pages)
            _maybe_fail("serve.lora_fault")
            if ad.state == EVICTED:
                rows = np.stack([self.swap.view(buf, (self.pool.elements,),
                                                self.pool.dtype)
                                 for buf in ad.bufs])
            else:
                rows = ad.master
            self.pool.put_pages(rows, ids)
        except BaseException:
            self.pool.free(ids)
            raise
        with self._meta:
            ad.page_ids = ids
            if ad.state == EVICTED:
                for buf in ad.bufs:
                    self.swap.put(buf)
                ad.bufs = []
            ad.state = RESIDENT
        # sync before the stamp: the fault-in span/counters time the swap-in
        # through device completion, not just the scatter dispatch (this
        # runs in the admission round, never inside a decode slice)
        jax.block_until_ready(self.pool.pool)
        t1 = time.perf_counter()
        nbytes = ad.rank * self.pool.page_nbytes
        # one stamp pair feeds the span AND the counters (stats == spans)
        self.stats.record_fault(ad.name, nbytes, t1 - t0)
        if _tracer.enabled:
            _tracer.add("serve/lora/fault", t0, t1, lane="serve/lora",
                        adapter=ad.name, pages=ad.rank, nbytes=nbytes)

    def _lru_victim(self, exclude: str) -> Optional[_Adapter]:
        best = None
        for a in self._adapters.values():
            if (a.name == exclude or a.state != RESIDENT or a.refcount > 0
                    or a.rank == 0):
                continue
            if best is None or a.last_used < best.last_used:
                best = a
        return best

    def evict(self, name: str) -> None:
        """Device -> pinned host buffers, pages freed (refcount must be 0).
        The restore half is ``acquire``'s fault-in; the round trip is
        byte-exact (the ``fetch_pages``/``put_pages`` contract)."""
        ad = self._get(name)
        if ad.state != RESIDENT or ad.rank == 0:
            return
        if ad.refcount > 0:
            raise RuntimeError(
                f"adapter {name!r} is bound to {ad.refcount} in-flight "
                "request(s) — cannot evict (the refcount gate that keeps "
                "decode gathers backed)")
        t0 = time.perf_counter()
        rows = self.pool.fetch_pages(ad.page_ids)
        bufs = []
        for i in range(ad.rank):
            buf = self.swap.get(self.pool.page_nbytes)
            np.copyto(self.swap.view(buf, (self.pool.elements,),
                                     self.pool.dtype), rows[i])
            bufs.append(buf)
        with self._meta:
            self.pool.free(ad.page_ids)
            ad.page_ids = []
            ad.bufs = bufs
            ad.state = EVICTED
        t1 = time.perf_counter()
        nbytes = ad.rank * self.pool.page_nbytes
        # timed work already drained: fetch_pages ends in fetch_to_host and
        # the buffer fills are host copies
        self.stats.record_evict(name, nbytes, t1 - t0)  # jaxlint: disable=JL001
        if _tracer.enabled:
            _tracer.add("serve/lora/swap", t0, t1, lane="serve/lora",
                        adapter=name, pages=ad.rank, nbytes=nbytes)

    # -- decode dispatch --------------------------------------------------- #

    def page_table(self, uids: Sequence[int], bucket: int,
                   rb: int) -> np.ndarray:
        """The per-batch ``adapter_pt [bucket, rb]`` int32 operand: each
        row's bound adapter's page ids (rank-padded with the zero page);
        unbound rows, rank-0 rows, and bucket-pad rows are all-zero-page
        (exact-zero delta — inert, like scratch-page KV rows)."""
        pt = np.full((bucket, rb), self.pool.zero_page, np.int32)
        for i, uid in enumerate(uids):
            name = self._bindings.get(int(uid))
            if name is None:
                continue
            ad = self._adapters[name]
            if ad.rank == 0:
                continue
            assert ad.state == RESIDENT, \
                f"bound adapter {name!r} not resident (refcount gate broken)"
            pt[i, :ad.rank] = ad.page_ids
        return pt

    def close(self) -> None:
        """Drop everything (engine teardown): frees device pages and
        returns pinned buffers; refuses while requests are in flight."""
        for name in list(self._adapters):
            if self._adapters[name].refcount > 0:
                raise RuntimeError(
                    f"adapter {name!r} still bound at close()")
            self.unregister(name)
