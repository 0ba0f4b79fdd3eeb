"""Async double-buffered decode pipeline — the v2 steady-state serving loop.

Why this exists: with the prefix cache removing most prefill tokens, what
was left of steady-state serving cost was per-step HOST work, not device
compute. The per-token loop paid, per generated token:
a device dispatch, a BLOCKING logits/token fetch, scheduler bookkeeping, a
full ragged descriptor build, and another dispatch — all serialised. This
pipeline restructures that into two overlapped stages (the TPU-jit analog of
DeepSpeed's fused CUDA sampling + persistent decode loops, and of the
host/device overlap in continuous-batching servers like Orca/NanoFlow):

    device:  [ step N-1 ]  [ step N ]  [ step N+1 ]
    host:          | dispatch N | drain N-1's row | build N+1 | dispatch N+1 |

- **Sampling is fused into the decode program** (``build_decode_step``):
  step N's dispatch consumes step N-1's token row *on device* — no host
  round trip sits between consecutive forward passes, and the only per-step
  device->host transfer is one int32 row (4 bytes/slot, vs the [S, V]
  logits block), started asynchronously right after dispatch and drained
  ONE STEP LATE while the device runs ahead.
- **Descriptors are bucketed** (``DecodeBatch``): rows, block tables and
  position ids are padded to ``next_pow2(live)``, so admission/retirement
  moves between cached executables (pre-compiled by ``engine.warmup()``)
  instead of recompiling; KV blocks are pre-reserved per run, so the
  "build step N+1" stage is two array increments.

Consequence of the one-step-late drain: the host OBSERVES token j while the
device is already computing token j+1. A stop decision made on token j (EOS,
budget) therefore lands after one extra token of device work — that token is
wasted compute in the scratch-of-the-sequence sense, the standard price of
any lookahead/continuation-style serving loop, and the reason ``on_tokens``
retirement stops *recording* rather than the device.

Per-step phase timings land in ``engine.pipeline_stats``
(``monitor/serving.py``) so the overlap is observable; docs/SERVING.md walks
the whole path.
"""

from __future__ import annotations

import time
from typing import Callable, Iterable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference.v2.engine_v2 import fetch_to_host
from deepspeed_tpu.monitor.trace import tracer as _tracer


def rows_held(engine, ctx_lens: np.ndarray, live: np.ndarray) -> dict:
    """What the rows of ``live`` held at one decode step, as arguments of its
    ``serve/decode/step`` record (plain ints: ``tracer.export()`` writes them
    as JSON). ``ctx_lens`` are the context lengths the step's program was
    handed, a live sequence a row. ``ctx``: their sum, in tokens; ``pages``:
    the whole pages that hold those tokens, a row at most the ring where the
    engine runs a page ring; and where the model has windowed layers
    ``ctx_window``: the tokens a windowed layer's query still sees,
    ``min(ctx, window)`` a row — one int, or a tuple in the order of
    ``engine._windowed_layers`` where the windows differ. Called under
    ``tracer.enabled`` only: it is what a reader needs to hold the paged
    kernels' device time against the bytes their rows' contexts are
    (docs/OBSERVABILITY.md)."""
    ctx = ctx_lens[live]
    # (a model in which no layer holds pages holds its contexts in none)
    pages = np.zeros_like(ctx) if engine.scheduler.pageless \
        else -(-ctx // engine.kv.config.block_size)
    ring = engine.scheduler.ring_pages
    if ring is not None:
        pages = np.minimum(pages, ring)
    held = {"ctx": int(ctx.sum()), "pages": int(pages.sum())}
    if engine._windowed_layers:
        clipped = tuple(int(np.minimum(ctx, window).sum())
                        for window, _ in engine._windowed_layers)
        held["ctx_window"] = clipped[0] if len(clipped) == 1 else clipped
    return held


class DecodePipeline:
    """Double-buffered decode over a fixed live set of sequences.

    All ``uids`` must be in steady decode state: known to the scheduler, no
    pending host tokens, last-logits refs available (i.e. after ``put()`` or
    a previous run). Drive it as::

        pipe = engine.decode_pipeline(uids)
        tokens = pipe.run(64)            # [len(uids), 64], greedy
        pipe.retire(done_uids); engine.flush(done_uids)
        pipe.admit(new_uids)             # after engine.put() prefilled them
        tokens2 = pipe.run(64)

    Rows are padded to ``next_pow2(live)`` (``DecodeBatch``: programs are
    keyed by the bucket, pad rows decode into the scratch page) and decode is
    row-independent, so greedy streams are byte-identical under padding and
    to the per-token ``sample_next``/``put`` loop (same forward math; pinned
    by tests/unit/test_decode_pipeline.py). Batch-sampled rows draw from a
    [bucket, V] noise block, so SAMPLED streams are valid draws that depend
    on the bucket (not on which other rows are pads) — a documented trade,
    not a bug.
    """

    #: The pipelines' contract with ``on_tokens(step, uids, row)``, written
    #: once (``SpecDecodePipeline`` and ``BlockDecodePipeline`` set it True):
    #: False — ``row`` is an int32 array, ONE token a live row a step; True —
    #: ``row`` is a list of int32 arrays, row i's token BATCH of this step
    #: (an accepted draft prefix and its bonus token; a committed block; an
    #: empty array where the step gave the row nothing), every token of
    #: which became host-visible at once. Callers (``engine.generate``, the
    #: serving frontend's ``_on_tokens``) branch on it and on nothing else.
    token_batches = False

    def __init__(self, engine, uids: Sequence[int], do_sample: bool = False,
                 temperature: float = 1.0, top_k: int = 0):
        self.engine = engine
        self.uids: List[int] = []
        self.do_sample = bool(do_sample)
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.stats = engine.pipeline_stats
        # same validation as later admissions: fail with a clear error NOW,
        # not as a KeyError deep inside scheduler.reserve at run() time
        self.admit(uids)

    # ------------------------------------------------------------------ #
    # live-set management (between runs)
    # ------------------------------------------------------------------ #

    def retire(self, uids: Iterable[int]) -> None:
        """Drop sequences from the live set (their engine state is untouched
        — flush them to release KV). The next run uses the smaller bucket."""
        gone = {int(u) for u in uids}
        self.uids = [u for u in self.uids if u not in gone]

    def admit(self, uids: Iterable[int]) -> None:
        """Add prefilled sequences (after ``engine.put``) to the live set."""
        e = self.engine
        for u in uids:
            u = int(u)
            seq = e.scheduler.seqs.get(u)
            if seq is None or len(seq.pending):
                raise ValueError(f"uid {u} is not in steady decode state")
            if u not in e._last_ref and u not in e._last_logits:
                raise ValueError(f"uid {u} has no last-logits state to sample "
                                 "from (run put() first)")
            if u in self.uids:
                raise ValueError(f"uid {u} already in the pipeline")
            self.uids.append(u)

    # ------------------------------------------------------------------ #
    # the hot loop
    # ------------------------------------------------------------------ #

    def run(self, n_steps: int,
            on_tokens: Optional[Callable] = None) -> np.ndarray:
        """Generate ``n_steps`` tokens per live sequence; returns the ids
        [live, n_steps] in ``self.uids`` order at run start.

        ``on_tokens(step, uids, row)`` is called as each step's token row is
        DRAINED (observed one step late; ``row`` is int32 [live]). Its return
        value, if truthy, is an iterable of uids to retire: recording for
        them stops (their later entries in the returned array are padding
        noise), their continuation refs are dropped (flush or re-``put``
        them before reuse), and they leave the pipeline's live set. The
        device finishes the in-flight burst regardless — stopping the world
        on a retirement would forfeit the overlap this loop exists for.
        Stop-set uids not live in this run are ignored.

        If the callback raises (or the run is interrupted), the exception
        propagates AFTER state is settled: every row's history is advanced
        to its drained span, continuation refs are dropped, and all uids
        leave the pipeline — flush (or re-``put``) them before reuse.
        """
        e = self.engine
        uids = list(self.uids)
        S = len(uids)
        if S == 0 or n_steps <= 0:
            return np.zeros((S, 0), np.int32)
        assert not e.scheduler.has_pending(), \
            "decode pipeline requires a drained scheduler"
        perf = time.perf_counter
        st = self.stats
        del st.step_wall_ms[:]   # per-run latencies (cumulative fields stay)
        # stage-0 setup: pre-reserve KV for the whole run; bucketed
        # descriptors; grid-warm program; on-device bootstrap sample
        db = e.scheduler.decode_batch(uids, n_steps + 1, e.scratch_block)
        rb = e.lora_rank_bucket
        prog = e._decode_step_prog(db.bucket, self.do_sample, self.top_k, rb)
        e._rng_key, base = jax.random.split(e._rng_key)
        temp = jnp.float32(self.temperature)
        # block tables are invariant for the whole run (KV pre-reserved):
        # commit them to device ONCE instead of re-uploading [bucket, MB]
        # ints with every per-token dispatch
        block_tables = jnp.asarray(db.block_tables)
        # LoRA operands are run-invariant too (adapter bindings are frozen
        # while a request is in flight — the registry's refcount gate): empty
        # at rb=0, so adapter-free engines dispatch the identical program
        lora_args = e._lora_operands(uids, db.bucket, rb)
        # so are the rows' recurrent-state slots (a model with state-space
        # layers; it refuses LoRA, so the two never share the tail)
        lora_args += e._state_operands(db)
        tb = perf() if _tracer.enabled else 0.0
        ids, _ = e._sample_device_padded(uids, self.do_sample,
                                         self.temperature, self.top_k)
        if tb:
            # the sampler that opens a slice: eager helpers keyed by how the
            # rows split over logits arrays, so a compile/backend span here
            # names a helper that was not warm
            _tracer.add("serve/decode/bootstrap", tb, perf(),
                        lane="serve/decode", rows=S)
        assert ids.shape[0] == db.bucket
        if hasattr(ids, "copy_to_host_async"):
            ids.copy_to_host_async()

        out = np.empty((n_steps, S), np.int32)
        live = np.ones((S,), bool)
        recorded = np.full((S,), n_steps, np.int32)
        row_of = {u: i for i, u in enumerate(uids)}
        logits = None
        steps_drained = 0
        try:
            for j in range(n_steps):
                t0 = perf()
                # dispatch step j: consumes the device-resident row `ids`
                # (= token j, sampled by step j-1 / the bootstrap), writes its
                # KV, samples token j+1 — one program, no host round trip
                nxt, logits, new_kv = prog(e.weights, e.kv.kv, ids,
                                           db.positions, block_tables,
                                           db.ctx_lens,
                                           jax.random.fold_in(base, j), temp,
                                           *lora_args)
                e.kv.update(new_kv)
                if hasattr(nxt, "copy_to_host_async"):
                    nxt.copy_to_host_async()  # D2H queued behind step j, free
                t1 = perf()
                # drain stage: token j's row (its transfer started last
                # iteration; blocks only if the device is still on step j-1)
                row = fetch_to_host(ids)
                t2 = perf()
                out[j] = row[:S]
                steps_drained = j + 1
                # rows retired THIS step still had token j drained + recorded
                drained_tokens = int(live.sum())
                cb_s = 0.0
                tc = tc2 = t2
                if on_tokens is not None:
                    tc = perf()
                    stop = on_tokens(j, uids, out[j])
                    tc2 = perf()
                    cb_s = tc2 - tc      # callback cost -> bubble, not build
                    for u in (stop or ()):
                        # uids not in THIS run (already retired, foreign) are
                        # ignored rather than aborting a healthy burst
                        i = row_of.get(int(u))
                        if i is not None and live[i]:
                            live[i] = False
                            recorded[i] = j + 1
                # build stage: step j+1's descriptors (blocks pre-reserved,
                # so this is the whole of it; it rebinds the context lengths
                # step j's program was handed, which its span reports)
                handed = db.ctx_lens
                db.advance(1)
                ids = nxt
                t3 = perf()
                st.record_step(dispatch_s=t1 - t0, drain_s=t2 - t1,
                               build_s=(t3 - t2) - cb_s, wall_s=t3 - t0,
                               fetch_bytes=row.nbytes,
                               live_tokens=drained_tokens)
                if _tracer.enabled:
                    # timeline view of the SAME per-step phase measurements
                    # the stats aggregate (docs/OBSERVABILITY.md): zero-sync,
                    # perf_counter pairs already taken above. The stats
                    # charge callback time to bubble, not build — so the
                    # build span excludes the callback window too (emitted
                    # as its own serve/decode/callback span)
                    _tracer.add("serve/decode/dispatch", t0, t1,
                                lane="serve/decode", step=j)
                    _tracer.add("serve/decode/drain", t1, t2,
                                lane="serve/decode", step=j)
                    if on_tokens is not None:
                        _tracer.add("serve/decode/build", t2, tc,
                                    lane="serve/decode", step=j)
                        _tracer.add("serve/decode/callback", tc, tc2,
                                    lane="serve/decode", step=j)
                        _tracer.add("serve/decode/build", tc2, t3,
                                    lane="serve/decode", step=j)
                    else:
                        _tracer.add("serve/decode/build", t2, t3,
                                    lane="serve/decode", step=j)
                    # with what the rows held whose token j was drained
                    # (recording stops at j + 1 for a row retired this step)
                    _tracer.add("serve/decode/step", t0, t3,
                                lane="serve/decode", step=j,
                                live=drained_tokens,
                                **rows_held(e, handed[:S], recorded > j))
        except BaseException:
            # an escaping on_tokens (or interrupt) must not leave sequence
            # state desynchronized from the KV already written: settle every
            # row's history at its drained span and drop now-stale refs —
            # the uids leave the pipeline and need a flush (or re-put)
            for i, u in enumerate(uids):
                e.scheduler.advance(u, min(int(recorded[i]), steps_drained))
                e._last_ref.pop(u, None)
                e._last_logits.pop(u, None)
            self.uids = []
            raise
        # the final step's sampled row (token n_steps) stays on device,
        # discarded; continuation re-derives it from the final logits refs
        # (greedy: same token)
        for i, u in enumerate(uids):
            if live[i]:
                e.scheduler.advance(u, n_steps)
                e._last_ref[u] = (logits, i)
                e._last_logits.pop(u, None)
            else:
                # mid-run retirement: only the recorded span becomes sequence
                # history; the overrun tokens' KV is overwritten by any later
                # decode at the same positions. Continuation refs would point
                # past the recorded span — drop them (flush or re-put).
                e.scheduler.advance(u, int(recorded[i]))
                e._last_ref.pop(u, None)
                e._last_logits.pop(u, None)
        self.uids = [u for i, u in enumerate(uids) if live[i]]
        return out.T.copy()
