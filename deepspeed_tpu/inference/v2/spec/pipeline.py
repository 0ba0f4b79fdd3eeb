"""Speculative decode pipeline — draft, verify in one ragged forward, accept.

``SpecDecodePipeline`` is the ``DecodePipeline`` analog for speculation: the
same admit/retire/run surface over a fixed live set, the same bucketed
descriptors and warmed program grid, but each step advances every row by a
VARIABLE count — the accepted draft prefix plus one greedy bonus token:

    host:   draft (n-gram match over each row's history) -> upload [S, k]
    device: ONE ragged forward scores all k+1 rows per sequence, writes
            their KV, computes the greedy accept mask + bonus token
    host:   drain ONE int32 [2, S] row (accept counts + bonus tokens),
            reconstruct the emitted tokens from the draft it proposed,
            advance rows, draft the next step

The drain is synchronous per step — speculation trades PR 3's one-step-late
overlap for k-token amortization, because the NEXT draft must extend the
tokens this step actually emitted (the device-resident bonus token and the
accept count are unknowable one step early). The per-step host transfer is
still one small int32 row, and a k-token accept amortises the full-model
HBM stream (the reason decode is slow) over k+1 emitted tokens.

Correctness: greedy speculation is exactness-preserving — the emitted
stream is BYTE-IDENTICAL to the spec-off pipeline (ragged_model.
build_verify_step's induction). Rejection never touches prefix-cache-shared
pages: stale
rejected-token KV sits past the advanced context inside pages the sequence
owns (ctx-bounded readers never see it; the next write overwrites it), and
run-end ``scheduler.rollback_reserved`` frees whole reserved-but-unused
pages back to the refcounted allocator — reject-heavy runs return the pool
to baseline (tests/unit/test_spec_decode.py pins all of it).
"""

from __future__ import annotations

import time
from typing import Callable, Dict, Iterable, List, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference.v2.engine_v2 import fetch_to_host
from deepspeed_tpu.inference.v2.spec.proposer import (DraftProposer,
                                                      NGramProposer)
from deepspeed_tpu.monitor.trace import tracer as _tracer


class _TokenBuf:
    """Amortized-growth int32 token history: appends are element stores
    into a doubling buffer and the proposer reads a zero-copy view — a
    plain Python list re-converted with ``np.asarray`` per step costs an
    O(T) copy per verify step (O(T^2) over a generation) on the
    drain-synchronous host loop the draft budget pays for."""

    __slots__ = ("a", "n")

    def __init__(self, toks):
        t = np.asarray(toks, np.int32).reshape(-1)
        self.a = np.empty((max(64, 2 * len(t)),), np.int32)
        self.a[:len(t)] = t
        self.n = len(t)

    def _grow(self, need: int) -> None:
        if self.n + need > len(self.a):
            a = np.empty((max(2 * len(self.a), self.n + need),), np.int32)
            a[:self.n] = self.a[:self.n]
            self.a = a

    def append(self, t: int) -> None:
        self._grow(1)
        self.a[self.n] = t
        self.n += 1

    def extend(self, toks) -> None:
        t = np.asarray(toks, np.int32).reshape(-1)
        self._grow(len(t))
        self.a[self.n:self.n + len(t)] = t
        self.n += len(t)

    def pop(self) -> None:
        self.n -= 1

    def view(self) -> np.ndarray:
        return self.a[:self.n]


class SpecDecodePipeline:
    """Draft-and-verify decode over a fixed live set of sequences.

    Drive it exactly like ``DecodePipeline`` (``engine.decode_pipeline``
    returns this class when ``config.spec_decode.enabled`` and the request
    is greedy)::

        pipe = engine.decode_pipeline(uids)      # SpecDecodePipeline
        toks = pipe.run(16)      # list of per-row token lists (ragged:
                                 # each step emits 1..k+1 tokens per row)
        pipe.retire(done); engine.flush(done); pipe.admit(new)

    ``spec`` is True and so is ``token_batches``
    (``DecodePipeline.token_batches``: callers branch their ``on_tokens``
    shape on that). Greedy streams are byte-identical to the spec-off pipeline; sampling is
    not supported here (the engine routes sampled pipelines to the plain
    ``DecodePipeline`` with a one-time warning).
    """

    spec = True
    token_batches = True

    def __init__(self, engine, uids: Sequence[int],
                 proposer: Optional[DraftProposer] = None):
        self.engine = engine
        cfg = engine.config.spec_decode
        self.k = int(cfg.k)
        self.adaptive = bool(cfg.adaptive)
        self.proposer = proposer if proposer is not None else NGramProposer(
            min_match=cfg.min_match, max_ngram=cfg.max_ngram)
        self.uids: List[int] = []
        self.stats = engine.spec_stats
        # per-uid host state: token history (prompt + emitted — what the
        # proposer matches over) and the adaptive per-row draft budget
        self._hist: Dict[int, _TokenBuf] = {}
        self._k_eff: Dict[int, int] = {}
        self.admit(uids)

    # ------------------------------------------------------------------ #
    # live-set management (between runs)
    # ------------------------------------------------------------------ #

    def retire(self, uids: Iterable[int]) -> None:
        """Drop sequences from the live set (engine state untouched — flush
        them to release KV; their draft history goes with them)."""
        gone = {int(u) for u in uids}
        self.uids = [u for u in self.uids if u not in gone]
        for u in gone:
            self._hist.pop(u, None)
            self._k_eff.pop(u, None)

    def admit(self, uids: Iterable[int],
              histories: Optional[Sequence[Sequence[int]]] = None) -> None:
        """Add prefilled sequences (after ``engine.put``). ``histories``
        optionally seeds each row's draft history; by default the
        scheduler's recorded history is used (the engine records it whenever
        spec decode is enabled), so prompt-lookup can match into the prompt
        from the first step. A short/empty history only degrades draft
        quality, never correctness."""
        e = self.engine
        uids = [int(u) for u in uids]
        if histories is not None and len(histories) != len(uids):
            raise ValueError("histories must align with uids")
        for i, u in enumerate(uids):
            seq = e.scheduler.seqs.get(u)
            if seq is None or len(seq.pending):
                raise ValueError(f"uid {u} is not in steady decode state")
            if u not in e._last_ref and u not in e._last_logits:
                raise ValueError(f"uid {u} has no last-logits state to "
                                 "sample from (run put() first)")
            if u in self.uids:
                raise ValueError(f"uid {u} already in the pipeline")
            self.uids.append(u)
            self._hist[u] = _TokenBuf(histories[i] if histories is not None
                                      else seq.history())
            self._k_eff[u] = self.k

    # ------------------------------------------------------------------ #
    # the hot loop
    # ------------------------------------------------------------------ #

    def _tune_k(self, u: int, proposed: int, accepted: int) -> None:
        """Per-sequence adaptive draft budget (MIMD): a full accept DOUBLES
        the budget (up to k — a row riding a repetitive span reaches full
        k within log2(k) steps); any reject drops it to accepted + 1,
        keeping a probe of 1 alive so a row re-entering a repetitive span
        is detected without paying for dead full-k drafts meanwhile."""
        if not self.adaptive or proposed < 1:
            return
        if accepted >= proposed:
            self._k_eff[u] = min(self.k, max(2 * self._k_eff[u], 1))
        else:
            self._k_eff[u] = max(1, accepted + 1)

    def run(self, n_steps: int,
            on_tokens: Optional[Callable] = None) -> List[List[int]]:
        """Run ``n_steps`` verify steps; returns each live row's emitted
        tokens (ragged — between ``n_steps`` and ``n_steps * (k + 1)`` per
        row) in ``self.uids`` order at run start.

        ``on_tokens(step, uids, toks)`` is called after each step's
        accept-row drain with ``toks`` a list of int32 arrays — row i's
        tokens emitted THIS step (1..k+1 of them, host-visible
        simultaneously). Its truthy return value is an iterable of uids to
        retire: recording (and drafting) for them stops, their continuation
        refs drop, and they leave the live set — but their device rows run
        to the end of the burst (bucket shapes are static), exactly the
        ``DecodePipeline`` retirement trade. If the callback raises, state
        settles first (histories advanced to the drained spans, reserved
        pages rolled back, refs dropped, all uids leave the pipeline —
        flush or re-``put`` before reuse).
        """
        e = self.engine
        uids = list(self.uids)
        S = len(uids)
        if S == 0 or n_steps <= 0:
            return [[] for _ in range(S)]
        assert not e.scheduler.has_pending(), \
            "spec decode pipeline requires a drained scheduler"
        perf = time.perf_counter
        K1 = self.k + 1
        # reserve for FULL acceptance up front (the verify step writes up to
        # k+1 positions ahead per step with no host intervention); run-end
        # rollback returns whatever rejection left unused
        db = e.scheduler.decode_batch(uids, n_steps * K1 + 1,
                                      e.scratch_block)
        # each step dispatches the SMALLEST (bucket, k) rung covering its
        # longest draft — a mostly-unrepetitive batch pays 2-row verifies,
        # not full-k ones; draft-empty steps (cold history, post-reject
        # backoff) dispatch the PLAIN fused decode step — bit-identical to
        # a verify step's row 0 for full-precision pools, value-identical
        # up to cross-kernel float noise for int8 pools (both attend the
        # quantized pool values; docs/SERVING.md "Quantized KV").
        # Everything here is on the warmed grid:
        # the ladder tops out at exactly self.k (both read config k), the
        # invariant the zero-compile gate rests on.
        ladder = e.spec_k_ladder
        rb = e.lora_rank_bucket
        plain = e._decode_step_prog(db.bucket, False, 0, rb)
        temp = jnp.float32(1.0)
        block_tables = jnp.asarray(db.block_tables)
        # run-invariant LoRA operands, like block_tables (empty at rb=0);
        # verify programs repeat each row's pages over its K+1 token rows
        # in-jit, so the SAME [bucket, rb] table feeds both program kinds
        lora_args = e._lora_operands(uids, db.bucket, rb)
        ids, _ = e._sample_device_padded(uids, False, 1.0, 0)
        assert ids.shape[0] == db.bucket
        if hasattr(ids, "copy_to_host_async"):
            ids.copy_to_host_async()
        # the run's ONE extra drain: the bootstrap row. Step j emits the
        # COMMITTED tokens — the carry (step j-1's bonus; this bootstrap at
        # step 0, stream-identical to DecodePipeline's first drained row)
        # plus the accepted drafts; the bonus becomes step j+1's carry, and
        # the final step's bonus stays un-emitted, re-derived from the
        # logits refs exactly like DecodePipeline's final sampled row.
        carry = fetch_to_host(ids)

        outs: List[List[int]] = [[] for _ in range(S)]
        live = np.ones((S,), bool)
        # tokens whose history/advance is settled (drained steps), per row
        emitted = np.zeros((S,), np.int64)
        recorded = np.zeros((S,), np.int64)
        row_of = {u: i for i, u in enumerate(uids)}
        final_logits = None
        # the carry token continues each row's history — drafts extend it
        for i, u in enumerate(uids):
            self._hist[u].append(int(carry[i]))
        try:
            for j in range(n_steps):
                t0 = perf()
                draft, n_draft = self._draft_step(uids, live, db.bucket)
                t1 = perf()
                kmax = int(n_draft.max())
                if kmax > 0:
                    k_step = next(k_ for k_ in ladder if k_ >= kmax)
                    prog = e._verify_prog(db.bucket, k_step, rb)
                    accept_row, nxt, final_logits, new_kv = prog(
                        e.weights, e.kv.kv, ids,
                        jnp.asarray(draft[:, :k_step]),
                        jnp.asarray(n_draft),
                        db.positions, block_tables, db.ctx_lens,
                        *lora_args)
                else:
                    # nothing to verify anywhere: one plain decode step
                    # (greedy ignores the key; bit-identical to a verify
                    # step's row 0)
                    nxt, final_logits, new_kv = plain(
                        e.weights, e.kv.kv, ids, db.positions,
                        block_tables, db.ctx_lens, e._rng_key, temp,
                        *lora_args)
                    accept_row = None
                e.kv.update(new_kv)
                drain_src = accept_row if accept_row is not None else nxt
                if hasattr(drain_src, "copy_to_host_async"):
                    drain_src.copy_to_host_async()
                t2 = perf()
                # the ONE per-step drain: accept counts + bonus tokens
                # (a fallback step's bonus row with implicit zero accepts)
                host = fetch_to_host(drain_src)
                row = host if accept_row is not None else np.stack(
                    [np.zeros_like(host), host])
                t3 = perf()
                counts = row[0] + 1                  # emitted per device row
                step_tokens = proposed = accepted = 0
                empty = np.zeros((0,), np.int32)
                toks: List[np.ndarray] = [empty] * S
                for i, u in enumerate(uids):
                    a = int(row[0, i])
                    emitted[i] += a + 1
                    if not live[i]:
                        continue
                    # step j's stream tokens: the carry (committed by this
                    # step's row 0) + the accepted drafts; the bonus
                    # row[1, i] becomes the next carry (in history for
                    # drafting, not yet in the stream)
                    tk = np.concatenate(
                        [carry[i:i + 1], draft[i, :a]]).astype(np.int32)
                    toks[i] = tk
                    self._hist[u].extend(draft[i, :a])
                    self._hist[u].append(int(row[1, i]))
                    # rows retired THIS step (below) still record this
                    # step's tokens — same policy as DecodePipeline
                    outs[i].extend(int(t) for t in tk)
                    recorded[i] = emitted[i]
                    step_tokens += a + 1
                    proposed += int(n_draft[i])
                    accepted += a
                    self._tune_k(u, int(n_draft[i]), a)
                carry = row[1]
                tc = tc2 = t3
                if on_tokens is not None:
                    tc = perf()
                    stop = on_tokens(j, uids, toks)
                    tc2 = perf()
                    for u in (stop or ()):
                        i = row_of.get(int(u))
                        if i is not None and live[i]:
                            live[i] = False
                            self._hist.pop(int(u), None)
                            self._k_eff.pop(int(u), None)
                # device rows advance by what the device actually wrote —
                # retired rows included (their positions must keep tracking
                # the KV writes their still-running row performs), pad rows
                # by their own device-reported count (always 1: no draft)
                db.advance_rows(counts)
                ids = nxt
                t4 = perf()
                live_rows = int(live.sum())
                self.stats.record_step(
                    rows=live_rows, proposed=proposed, accepted=accepted,
                    tokens=step_tokens, draft_s=t1 - t0,
                    verify_s=(t3 - t1), fetch_bytes=host.nbytes)
                if _tracer.enabled:
                    _tracer.add("serve/spec/draft", t0, t1,
                                lane="serve/spec", step=j)
                    _tracer.add("serve/spec/dispatch", t1, t2,
                                lane="serve/spec", step=j)
                    _tracer.add("serve/spec/drain", t2, t3,
                                lane="serve/spec", step=j)
                    if on_tokens is not None:
                        _tracer.add("serve/spec/callback", tc, tc2,
                                    lane="serve/spec", step=j)
                    _tracer.add("serve/spec/step", t0, t4,
                                lane="serve/spec", step=j,
                                tokens=step_tokens, accepted=accepted)
        except BaseException:
            # settle like DecodePipeline: drained spans become history,
            # reserved pages roll back, refs drop, all uids leave — flush
            # (or re-put) before reuse
            for i, u in enumerate(uids):
                e.scheduler.advance(u, int(recorded[i]))
                e.scheduler.rollback_reserved(u)
                e._last_ref.pop(u, None)
                e._last_logits.pop(u, None)
                self._hist.pop(u, None)
                self._k_eff.pop(u, None)
            self.uids = []
            raise
        for i, u in enumerate(uids):
            if live[i]:
                e.scheduler.advance(u, int(emitted[i]))
                e._last_ref[u] = (final_logits, i)
                e._last_logits.pop(u, None)
                # drop the trailing un-emitted bonus from the draft history:
                # the next run re-derives it from the refs and re-appends it
                # as its carry (a double entry would skew n-gram matching)
                self._hist[u].pop()
            else:
                # retired mid-run: only the recorded span becomes history;
                # overrun tokens' KV is overwritten by any later decode at
                # the same positions. Refs would point past the recorded
                # span — drop them (flush or re-put).
                e.scheduler.advance(u, int(recorded[i]))
                e._last_ref.pop(u, None)
                e._last_logits.pop(u, None)
            # block-granular rollback: reserved pages the (possibly
            # reject-heavy) run never reached return to the allocator
            e.scheduler.rollback_reserved(u)
        self.uids = [u for i, u in enumerate(uids) if live[i]]
        return outs

    # ------------------------------------------------------------------ #

    def _draft_step(self, uids: List[int], live: np.ndarray, bucket: int):
        """Draft for the live rows only (retired rows stop proposing — their
        device row decays to plain single-token decode)."""
        draft = np.zeros((bucket, self.k), np.int32)
        n_draft = np.zeros((bucket,), np.int32)
        for i, u in enumerate(uids):
            if not live[i]:
                continue
            budget = self._k_eff[u] if self.adaptive else self.k
            if budget < 1:
                continue
            d = self.proposer.propose(self._hist[u].view(), budget)
            if len(d):
                draft[i, :len(d)] = d
                n_draft[i] = len(d)
        return draft, n_draft
