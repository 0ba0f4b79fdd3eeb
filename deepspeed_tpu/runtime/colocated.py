"""Colocated rollout: device-resident train->serve weight reshard.

RLHF-style loops interleave training with generation from the freshly
updated policy. The portable way to move weights between the two engines
is the universal checkpoint (``save_checkpoint`` -> ``ds_to_universal`` ->
``load_universal_into_engine``): every tensor crosses to host numpy, hits
disk, and is re-uploaded — correct, but a full host round-trip per policy
update. When the trainer and the server share the SAME device mesh (the
colocated deployment this module is for), that round-trip is pure waste:
both layouts already live on device, and the train->serve mapping —
cast to the serving dtype, slice/transpose per family, stack layers,
repartition to the serving shardings — is just a program XLA can run
where the data is.

:class:`WeightBridge` compiles that mapping ONCE as a single jitted
program: the training engine's sharded optimizer view in, the serving
engine's exact weight layout (``out_shardings`` taken leaf-by-leaf from
the live serving weights) out. No leaf touches the host — the bridge is
listed in jaxlint's JL007 hot paths with an empty baseline, so any
``device_get``/``np.asarray``/``.item`` creeping in fails lint, not just
review. The serving engine's OLD weights are passed as a donated operand so
XLA may alias the new layout into their buffers.

:class:`RolloutLoop` drives the full cycle on top: train step(s) ->
``sync`` (the bridge program) -> ``swap`` (in-place rebind into the live
serving engine at a run boundary, prefix cache flushed by weight-version,
zero new compiles) -> ``generate`` (the frontend produces the rollouts
that feed the next train batch through the PrefetchLoader staging path).
Every phase is perf-stamped once; the same stamps feed the
``train/rollout/{sync,swap,generate}`` tracer spans and
:class:`~deepspeed_tpu.monitor.training.RolloutStats` (stats-equals-spans,
docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import queue
import time
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax

from deepspeed_tpu.checkpoint.state import flatten_tree
from deepspeed_tpu.inference.v2.adapters import adapt_model
from deepspeed_tpu.monitor.trace import tracer as _tracer
from deepspeed_tpu.monitor.training import RolloutStats
from deepspeed_tpu.runtime.data_pipeline import PrefetchLoader
from deepspeed_tpu.runtime.zero import prefetch as zero3_prefetch
from deepspeed_tpu.utils.logging import log_dist
from deepspeed_tpu.utils.tree import tree_cast

__all__ = ["WeightBridge", "RolloutLoop"]


class WeightBridge:
    """One jitted program from a training engine's parameter tree to a
    serving engine's weight layout.

    The program re-runs the serving engine's own constructor pipeline —
    ``tree_cast`` to the serving dtype, then the family adapter
    (``adapt_model``) that slices/stacks checkpoints into the ragged
    layout — under trace, with ``out_shardings`` pinned to the live
    serving weights' shardings. That reuses the universal checkpoint's
    repartitioning semantics (same source tree ``ds_to_universal`` reads,
    same adapter ``load_universal_into_engine`` replays) with the
    host/disk legs deleted; :meth:`manifest` exposes the same
    ``flatten_tree`` names the universal writer files tensors under.

    ``donate=True`` additionally passes the serving engine's current
    weights as a donated scratch operand so the resharded layout may be
    aliased into their buffers — the steady-state swap then needs no net
    new device memory. Donation requires the serving engine to be
    quiesced FIRST (no live sequences), because once the program runs the
    old weights are forfeit; :meth:`sync` enforces that ordering.
    """

    def __init__(self, train_engine, serve_engine, *, donate: bool = True):
        cfg = serve_engine.config
        if cfg.quantization.weight_bits in (4, 8):
            raise NotImplementedError(
                "colocated weight sync into a weight-quantized serving "
                "engine is not wired: the bridge emits the adapter's "
                "unquantized layout, but this engine serves "
                f"int{cfg.quantization.weight_bits} packed weights — "
                "requantization under trace is future work")
        self.train = train_engine
        self.serve = serve_engine
        self.donate = bool(donate)
        self.compiles = 0
        self.stats = RolloutStats()
        # static: what one sync moves, in the serving layout (for bytes/s
        # against the sync span — no fetch involved, metadata only)
        self.nbytes = sum(int(leaf.nbytes) for leaf in
                          jax.tree_util.tree_leaves(serve_engine.weights))
        self._prog = None

    def manifest(self) -> List[str]:
        """Source tensor names, as the universal checkpoint files them."""
        return sorted(flatten_tree(self.train.rollout_source_params()).keys())

    def _build(self, src):
        serve = self.serve
        dtype = serve.config.dtype
        family = serve.family
        model_config = serve.model_config
        max_ctx = serve.config.state_manager.max_context
        out_shardings = jax.tree_util.tree_map(
            lambda a: a.sharding, serve.weights)

        def _reshard(params, old_weights):
            # donated scratch: XLA may alias the outputs into its buffers
            del old_weights
            p = tree_cast(params, dtype)
            _, w = adapt_model(family, p, model_config, max_context=max_ctx)
            return w

        # fail at build time, with checkpoint-manifest names, rather than
        # deep inside the first dispatch
        shaped = jax.eval_shape(_reshard, src, serve.weights)
        want = flatten_tree(serve.weights)
        got = flatten_tree(shaped)
        bad = [k for k in want
               if k not in got
               or got[k].shape != want[k].shape
               or got[k].dtype != want[k].dtype]
        if bad or set(got) != set(want):
            raise ValueError(
                "train->serve reshard does not reproduce the serving "
                f"layout; mismatched tensors: {sorted(set(bad) | (set(got) ^ set(want)))[:8]}"
                " — the training module and the serving model_config "
                "disagree about the architecture")
        if self.donate:
            return jax.jit(_reshard, donate_argnums=(1,),
                           out_shardings=out_shardings)
        return jax.jit(lambda params: _reshard(params, None),
                       out_shardings=out_shardings)

    def sync(self, *, wait: bool = True):
        """Run the reshard program; returns the serving-layout weight tree.

        The caller owns handing the result to ``swap_weights`` (or use
        :meth:`sync_and_swap`). Traced/dispatched under
        ``zero3_prefetch.cleared()``: the bridge's program is a foreign
        trace to the training engine's ambient ZeRO-3 schedule and must
        not adopt its gather plan.
        """
        serve = self.serve
        if self.donate and serve.scheduler.seqs:
            raise RuntimeError(
                "donating sync with live sequences on the serving engine — "
                "the old weights are forfeit once the program runs, so the "
                "engine must be quiesced (drain or preempt) first; use "
                "ServingFrontend.swap_weights for the full quiesce+swap, "
                "or WeightBridge(donate=False)")
        t0 = time.perf_counter()
        src = self.train.rollout_source_params()
        with zero3_prefetch.cleared():
            if self._prog is None:
                self._prog = self._build(src)
                self.compiles += 1
                log_dist("colocated: reshard program built "
                         f"({self.nbytes / 2**20:.1f} MiB serving layout)",
                         ranks=[0])
            if self.donate:
                new_w = self._prog(src, serve.weights)
            else:
                new_w = self._prog(src)
        if wait:
            jax.block_until_ready(new_w)
        t1 = time.perf_counter()
        if _tracer.enabled:
            _tracer.add("train/rollout/sync", t0, t1, lane="train/rollout",
                        nbytes=self.nbytes, donate=self.donate)
        self.stats.record_sync(t1 - t0, nbytes=self.nbytes)
        return new_w

    def sync_and_swap(self, frontend=None, *, version: Optional[int] = None,
                      timeout: Optional[float] = None) -> int:
        """``sync`` then swap into the live engine; returns the new
        weight version. With a frontend the swap runs on the serving
        thread at a run boundary (in-flight decode quiesced exactly like
        preemption); bare-engine swaps require the engine to be idle."""
        new_w = self.sync()
        fstats = getattr(frontend, "stats", None)
        pre = (fstats.recompute_preemptions, fstats.forced_sheds) \
            if fstats is not None else (0, 0)
        t0 = time.perf_counter()
        if frontend is not None:
            ver = frontend.swap_weights(new_w, version=version,
                                        timeout=timeout)
        else:
            ver = self.serve.swap_weights(new_w, version=version)
        t1 = time.perf_counter()
        post = (fstats.recompute_preemptions, fstats.forced_sheds) \
            if fstats is not None else (0, 0)
        preempted, shed = post[0] - pre[0], post[1] - pre[1]
        if _tracer.enabled:
            _tracer.add("train/rollout/swap", t0, t1, lane="train/rollout",
                        version=ver, preempted=preempted, shed=shed)
        self.stats.record_swap(t1 - t0, version=ver,  # jaxlint: disable=JL001 -- swap is host-side validation + operand rebind, no async dispatch to await
                               preempted=preempted, shed=shed)
        return ver


_CLOSE = object()


class RolloutLoop:
    """Interleaved train+generate driver over one colocated device mesh.

    Per round: the serving frontend generates rollouts from the current
    policy (``generate``), ``collate_fn`` turns them into a host batch
    that feeds the training engine through the same PrefetchLoader staging
    path ordinary data takes, the engine trains ``steps_per_round`` fused
    steps, and the bridge reshards + swaps the updated weights into the
    live frontend (``sync`` + ``swap``) — so the NEXT round generates
    on-policy. The serving engine is never rebuilt: swaps rebind the
    weights operand, the warmed compile ladders survive, and the prefix
    cache self-invalidates by weight version.

    ``prompt_fn(round) -> list of token-id sequences`` supplies the
    prompts; ``collate_fn(rollouts) -> host batch`` maps the finished
    ``(prompt, tokens)`` pairs to whatever tree the training module eats.
    """

    def __init__(self, train_engine, frontend, *,
                 prompt_fn: Callable[[int], Sequence[Sequence[int]]],
                 collate_fn: Callable[[List[Tuple[List[int], List[int]]]], Any],
                 bridge: Optional[WeightBridge] = None,
                 steps_per_round: int = 1,
                 max_new_tokens: int = 16,
                 prefetch: int = 1,
                 request_timeout: float = 120.0):
        self.engine = train_engine
        self.frontend = frontend
        self.bridge = bridge or WeightBridge(train_engine, frontend.engine)
        self.stats = self.bridge.stats
        self.prompt_fn = prompt_fn
        self.collate_fn = collate_fn
        self.steps_per_round = int(steps_per_round)
        self.max_new_tokens = int(max_new_tokens)
        self.request_timeout = float(request_timeout)
        self._queue: "queue.Queue" = queue.Queue()
        self._loader = PrefetchLoader(self._feed(), prefetch=int(prefetch),
                                      prepare=train_engine._prepare_batch,
                                      start_step=train_engine.global_steps)
        self._closed = False

    def _feed(self):
        while True:
            item = self._queue.get()
            if item is _CLOSE:
                return
            yield item

    def _generate(self, rnd: int) -> List[Tuple[List[int], List[int]]]:
        t0 = time.perf_counter()
        prompts = [list(p) for p in self.prompt_fn(rnd)]
        handles = [self.frontend.submit(p, max_new_tokens=self.max_new_tokens)
                   for p in prompts]
        outs = [h.result(timeout=self.request_timeout) for h in handles]
        t1 = time.perf_counter()
        tokens = sum(len(o) for o in outs)
        if _tracer.enabled:
            _tracer.add("train/rollout/generate", t0, t1,
                        lane="train/rollout", requests=len(outs),
                        tokens=tokens)
        self.stats.record_generate(t1 - t0, requests=len(outs), tokens=tokens)  # jaxlint: disable=JL001 -- h.result() blocks until every token materialized
        return list(zip(prompts, outs))

    def run(self, rounds: int, *, align: bool = True) -> List[Any]:
        """Drive ``rounds`` full cycles; returns the per-round loss arrays.

        ``align=True`` first syncs+swaps once before any generation so
        round 0 is already on-policy (the serving engine may have been
        built from stale initial parameters).
        """
        if self._closed:
            raise RuntimeError("rollout loop is closed")
        if self.frontend._thread is None or not self.frontend._thread.is_alive():
            self.frontend.start()
        if align:
            self.bridge.sync_and_swap(self.frontend)
        losses: List[Any] = []
        for rnd in range(int(rounds)):
            rollouts = self._generate(rnd)
            self._queue.put(self.collate_fn(rollouts))
            losses.append(self.engine.train_steps(self.steps_per_round,
                                                  data_iter=self._loader))
            self.bridge.sync_and_swap(self.frontend)
        return losses

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        self._queue.put(_CLOSE)
        self._loader.close()
