"""Block decode pipeline — the ``DecodePipeline`` analog of a model that
generates by diffusion over blocks (``spec.causal_block`` B > 1; SDAR).

The same admit / retire / run surface over a live set, the same bucketed
descriptors and warmed program grid, but a step is a PASS over every live
row's current block (``ragged_model.build_block_step``), and a pass yields a
row no token — a denoise pass, which fills some of the block's masked
positions on the device — or a whole block — the commit pass, which runs the
block once more with its final tokens, writes the K/V later blocks attend
to, and after which the row's context advances by B:

    device:  [ pass N-1 ]      [ pass N ]        [ pass N+1 ]
    host:         | dispatch N | drain N-1's blocks | build N+1 | dispatch ..

Under the STATIC schedule (``block_decode.remasking``) the host knows every
row's phase, and how many positions its pass fills, without reading the
device: it builds pass N + 1 while pass N runs and drains one small array a
pass late (the blocks' ids, of which it reads the committed rows), as
``DecodePipeline`` does its token row. Under the DYNAMIC rule a block is
done when the device says so: the host reads one int32 row a pass (masks
left a row) before it builds the next, as the spec pipeline reads its accept
row. One program serves rows in every phase, so rows admitted at different
times share a pass.

What the host holds between runs is each row's block as last seen (a run
drains its last pass before it returns), its phase and what is left of its
token budget; what the scheduler holds is the committed context
(``advance(uid, B)`` at a commit, as it is drained). A denoise pass's K/V sit
past the context inside pages the row has reserved: never read by another
row, overwritten by the next pass; run-end ``rollback_reserved`` returns
whole reserved pages the run did not reach.

Spans and counters (docs/OBSERVABILITY.md "Block decode"): a pass is a
``serve/block/step`` span with what its rows held, and the always-on
counters ``serve/block/*`` in ``tracer.totals`` say how many tokens a
row-pass yields.
"""

from __future__ import annotations

import time
from typing import Callable, Dict, Iterable, List, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference.v2.engine_v2 import fetch_to_host
from deepspeed_tpu.monitor.trace import tracer as _tracer

#: "no budget": more tokens than any context holds
_UNBOUNDED = 1 << 40


class _Row:
    """A live row between runs: its block as the host last saw it (the ids
    the next pass starts from), the masks in it, the denoise passes it has
    had, how many of its leading positions are prompt tokens (a first block),
    and the tokens its request still wants."""

    __slots__ = ("ids", "masks", "step", "lead", "budget")

    def __init__(self, ids, masks, lead, budget):
        self.ids, self.masks, self.step = ids, masks, 0
        self.lead, self.budget = lead, budget


class BlockDecodePipeline:
    """Denoise-and-commit decode over a live set of sequences.

    Drive it like the other two pipelines (``engine.decode_pipeline`` returns
    this class for a model with ``spec.causal_block > 1``)::

        pipe = engine.decode_pipeline(uids)      # after engine.put(...)
        toks = pipe.run(12)      # list of per-row token lists: a row's
                                 # blocks committed in these 12 passes
        pipe.retire(done); engine.flush(done); pipe.admit(new)

    ``token_batches`` is True (``DecodePipeline.token_batches`` says what
    that means to ``on_tokens``). Greedy only.
    """

    token_batches = True

    def __init__(self, engine, uids: Sequence[int]):
        self.engine = engine
        spec = engine.spec
        self.B = int(spec.causal_block)
        self.mask_id = int(spec.mask_token_id)
        cfg = engine.config.block_decode
        self.schedule = np.asarray(engine.block_schedule, np.int32)
        self.dynamic = cfg.remasking == "low_confidence_dynamic"
        self.threshold = np.float32(cfg.confidence_threshold if self.dynamic
                                    else np.inf)
        self.uids: List[int] = []
        self.stats = engine.pipeline_stats
        self._rows: Dict[int, _Row] = {}
        # a check's switch: the uids whose every pass is recorded in
        # ``watched`` (:meth:`_watch_pass`); empty, nothing keeps the logits
        self.watch: Sequence[int] = ()
        self.watched: List[Dict] = []
        self.admit(uids)

    # ------------------------------------------------------------------ #
    # live-set management (between runs)
    # ------------------------------------------------------------------ #

    def retire(self, uids: Iterable[int]) -> None:
        """Drop sequences from the live set (engine state untouched — flush
        them to release KV; their open block goes with them)."""
        gone = {int(u) for u in uids}
        self.uids = [u for u in self.uids if u not in gone]
        for u in gone:
            self._rows.pop(u, None)

    def admit(self, uids: Iterable[int],
              budgets: Optional[Sequence[Optional[int]]] = None) -> None:
        """Add prefilled sequences (after ``engine.put`` / the frontend's
        prefill: the scheduler has kept their last ``P mod B`` prompt tokens,
        ``seq.block_open``, which open the first block here). ``budgets``
        optionally gives each row the tokens its request asks for: its last
        block is then cut there (what a block holds past the budget is
        dropped and counted, ``serve/block/overhang_dropped``) and the row
        idles once it is spent, to the end of the run."""
        e = self.engine
        uids = [int(u) for u in uids]
        if budgets is not None and len(budgets) != len(uids):
            raise ValueError("budgets must align with uids")
        for i, u in enumerate(uids):
            seq = e.scheduler.seqs.get(u)
            if seq is None or len(seq.pending):
                raise ValueError(f"uid {u} is not in steady decode state")
            if seq.seen_tokens % self.B:
                raise ValueError(f"uid {u}: context {seq.seen_tokens} is not "
                                 f"a whole number of blocks of {self.B}")
            if u in self.uids:
                raise ValueError(f"uid {u} already in the pipeline")
            # a block row starts from its block, never from logits: what the
            # prompt's last pass left is let go (a reference pins that pass's
            # whole logits on the device: 5 MB a live row at 151,936 columns)
            e._last_ref.pop(u, None)
            e._last_logits.pop(u, None)
            lead = len(seq.block_open)
            ids = np.full((self.B,), self.mask_id, np.int32)
            ids[:lead] = seq.block_open
            budget = None if budgets is None else budgets[i]
            self.uids.append(u)
            self._rows[u] = _Row(ids, self.B - lead, lead,
                                 _UNBOUNDED if budget is None
                                 else max(0, int(budget)))

    def _watch_pass(self, j, row_of, ops, logits) -> List[Dict]:
        """Pass ``j`` of a run as the program was handed it, a record a
        watched row: its context, ``n_take``, whether its block was the
        host's (``fresh``: then ``fresh_ids`` is the block the pass ran on;
        else it ran on the pass before's ``after``, which never left the
        device), the block rows' logits ``[B, V]`` (a slice, still on the
        device) and ``after``, the block the pass left, filled in when the
        pass is drained."""
        fresh, fresh_ids, n_take, ctx = ops
        B = self.B
        rows = [(int(u), row_of[int(u)]) for u in self.watch
                if int(u) in row_of]
        got = [dict(uid=u, step=j, ctx=int(ctx[i]), n_take=int(n_take[i]),
                    fresh=bool(fresh[i]), fresh_ids=fresh_ids[i].copy(),
                    logits=logits[i * B:(i + 1) * B], row=i, after=None)
               for u, i in rows]
        self.watched += got
        return got

    # ------------------------------------------------------------------ #
    # the hot loop
    # ------------------------------------------------------------------ #

    def run(self, n_steps: int,
            on_tokens: Optional[Callable] = None) -> List[List[int]]:
        """Run ``n_steps`` passes; returns each live row's tokens committed
        in them (``self.uids`` order at run start).

        ``on_tokens(step, uids, toks)`` is called as each pass's blocks are
        drained — one pass late under the static schedule — with ``toks`` a
        list of int32 arrays: row i's block if that pass committed it (B
        tokens; fewer for a first block, which opens with prompt tokens, and
        for a last one cut at the row's budget), empty otherwise. Its truthy
        return value is an iterable of uids to retire: recording stops for
        them and they leave the live set at the end of the run, while their
        device rows idle to it (bucket shapes are static) — the
        ``DecodePipeline`` retirement trade. If the callback raises, state
        settles first (commits drained so far are the scheduler's history,
        reserved pages roll back, all uids leave the pipeline — flush
        before reuse)."""
        e = self.engine
        uids = list(self.uids)
        S = len(uids)
        if S == 0 or n_steps <= 0:
            return [[] for _ in range(S)]
        assert not e.scheduler.has_pending(), \
            "block decode pipeline requires a drained scheduler"
        perf = time.perf_counter
        st = self.stats
        del st.step_wall_ms[:]
        B, mask_id = self.B, self.mask_id
        db = e.scheduler.decode_batch(uids, e.block_reserve_tokens(n_steps),
                                      e.scratch_block)
        prog = e._block_step_prog(db.bucket)
        block_tables = jnp.asarray(db.block_tables)
        bs = e.kv.config.block_size
        rows = [self._rows[u] for u in uids]
        # the rows' state as arrays of the bucket's length, a live sequence a
        # row; the bucket's pad rows are idle from the start

        def column(values, dtype):
            out = np.zeros((db.bucket,), dtype)
            out[:S] = values
            return out

        ctx = db.positions                                 # [bucket]
        ids_host = np.zeros((db.bucket, B), np.int32)
        ids_host[:S] = [r.ids for r in rows]
        masks = column([r.masks for r in rows], np.int32)
        step = column([r.step for r in rows], np.int32)
        lead = column([r.lead for r in rows], np.int32)
        budget = column([r.budget for r in rows], np.int64)
        idle = budget <= 0
        fresh = np.ones((db.bucket,), bool)   # every block is the host's
        live = np.ones((S,), bool)       # not stopped by the callback
        empty = np.zeros((0,), np.int32)
        outs: List[List[int]] = [[] for _ in range(S)]
        row_of = {u: i for i, u in enumerate(uids)}
        last_step = len(self.schedule) - 1
        block_ids = e._zero_block(db.bucket)
        full_block = np.full((B,), mask_id, np.int32)

        def plan():
            """Pass j's operands and what it commits, from the rows' state;
            the state moves on to what pass j + 1 starts from (under the
            static schedule: as the device will leave it)."""
            nonlocal ctx, ids_host, masks, step, lead, budget, idle, fresh
            active = ~idle
            commit = active & (masks == 0)
            denoise = active & (masks > 0)
            n_s = self.schedule[np.minimum(step, last_step)]
            n_take = np.where(denoise, n_s if self.dynamic
                              else np.minimum(n_s, masks), 0).astype(np.int32)
            ops = fresh.astype(np.int32), ids_host, n_take, ctx
            held = None
            n_active = int(active.sum())
            if _tracer.enabled:
                c = ctx[active]
                held = dict(rows=n_active,
                            masked=int(masks[active].sum()),
                            commits=int(commit.sum()),
                            ctx_tokens=int(c.sum()),
                            pages=int((-(-(c + B) // bs)).sum()))
            # what a commit gives its request: the block less its leading
            # prompt tokens, cut at the budget
            give = np.where(commit, np.minimum(B - lead, budget), 0)
            committed = [(int(i), int(lead[i]), int(give[i]))
                         for i in np.flatnonzero(commit)]
            _tracer.bump("serve/block/passes")
            _tracer.bump("serve/block/row_passes", float(n_active))
            _tracer.bump("serve/block/commit_row_passes", float(commit.sum()))
            _tracer.bump("serve/block/tokens_committed", float(give.sum()))
            _tracer.bump("serve/block/overhang_dropped", float(
                (np.where(commit, B - lead, 0) - give).sum()))
            e.count_kv_rows(B, n_active * B)     # a live row's block: a run
            # the state pass j + 1 starts from (arrays rebound, never
            # written in place: pass j's dispatch may still read them)
            fresh = commit
            if commit.any():
                ctx = ctx + commit.astype(np.int32) * B
                ids_host = ids_host.copy()
                ids_host[commit] = full_block
            budget = budget - give
            idle = idle | (commit & (budget <= 0))
            lead = np.where(commit, 0, lead)
            if not self.dynamic:
                masks = np.where(commit, B, masks - n_take)
                step = np.where(commit, 0, step + denoise)
            return ops, committed, held, denoise, n_active

        def drain(j, new_ids, committed, seen=()):
            """Pass j's blocks, fetched: its commits become the scheduler's
            history and the callback's tokens."""
            host = fetch_to_host(new_ids)
            for rec in seen:
                rec["after"] = host[rec["row"]].copy()
            toks: List[np.ndarray] = [empty] * S
            for i, cut, n in committed:
                e.scheduler.advance(uids[i], B)
                if live[i]:
                    toks[i] = host[i, cut:cut + n]
                    outs[i].extend(int(t) for t in toks[i])
            tc = tc2 = perf()
            if on_tokens is not None:
                stop = on_tokens(j, uids, toks)
                tc2 = perf()
                for u in (stop or ()):
                    i = row_of.get(int(u))
                    if i is not None and live[i]:
                        live[i] = False
                        idle[i] = True
            return host, tc2 - tc

        late = None                  # the pass not yet drained (static)
        host_ids = None              # the last drained pass's blocks
        try:
            for j in range(n_steps):
                t0 = perf()
                ops, committed, held, denoise, n_active = plan()
                fresh_j, fresh_ids, n_take, ctx_j = ops
                tp = perf()
                new_ids, left, logits, new_kv = prog(
                    e.weights, e.kv.kv, block_ids, fresh_ids, fresh_j,
                    n_take, block_tables, ctx_j, self.threshold)
                e.kv.update(new_kv)
                seen = self._watch_pass(j, row_of, ops, logits) \
                    if self.watch else ()
                del logits
                block_ids = new_ids
                for a in (new_ids, left):
                    if hasattr(a, "copy_to_host_async"):
                        a.copy_to_host_async()
                t1 = perf()
                cb_s, nbytes = 0.0, 0
                if self.dynamic:
                    # the device decides when a block is done: read the masks
                    # it left before the next pass is built
                    left_host = fetch_to_host(left)
                    # (plan() left this pass's commits in ``fresh``)
                    masks = np.where(fresh, B,
                                     np.where(denoise, left_host, masks))
                    step = np.where(fresh, 0, step + denoise)
                    host_ids, cb_s = drain(j, new_ids, committed, seen)
                    nbytes = host_ids.nbytes + left_host.nbytes
                else:
                    if late is not None:
                        host_ids, cb_s = drain(*late)
                        nbytes = host_ids.nbytes
                    late = (j, new_ids, committed, seen)
                t2 = perf()
                # (the callback's time goes to the bubble, as the other
                # pipelines charge it)
                st.record_step(dispatch_s=t1 - tp, drain_s=(t2 - t1) - cb_s,
                               build_s=tp - t0, wall_s=t2 - t0,
                               fetch_bytes=nbytes, live_tokens=n_active)
                if held is not None:
                    _tracer.add("serve/block/build", t0, tp,
                                lane="serve/block", step=j)
                    _tracer.add("serve/block/dispatch", tp, t1,
                                lane="serve/block", step=j)
                    _tracer.add("serve/block/drain", t1, t2,
                                lane="serve/block", step=j)
                    _tracer.add("serve/block/step", t0, t2,
                                lane="serve/block", step=j, **held)
            if late is not None:
                host_ids, _ = drain(*late)
        except BaseException:
            for u in uids:
                e.scheduler.rollback_reserved(u)
                self._rows.pop(u, None)
            self.uids = []
            raise
        # every pass is drained: the rows' blocks come home for the next run
        # (a row that committed in the last pass starts a block of masks)
        ids_host = np.where(fresh[:, None], ids_host, host_ids)
        kept = []
        for i, u in enumerate(uids):
            e.scheduler.rollback_reserved(u)
            if idle[i] or not live[i]:
                self._rows.pop(u, None)
                continue
            r = self._rows[u]
            r.ids, r.masks, r.step = ids_host[i], int(masks[i]), int(step[i])
            r.lead, r.budget = int(lead[i]), int(budget[i])
            kept.append(u)
        self.uids = kept
        return outs
