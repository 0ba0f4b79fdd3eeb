"""Inference engine v2 — continuous batching over a paged KV cache.

Parity: ``InferenceEngineV2`` (reference ``inference/v2/engine_v2.py:30``):
``put(uids, tokens) -> logits`` (:107), ``query`` (:153), ``can_schedule`` (:179),
``flush``, plus a convenience ``generate`` driving continuous batching the way
MII's serving loop drives the reference engine.

TPU-native structure per pass (one jitted call, static shapes):

    host: DynamicSplitFuseScheduler builds RaggedBatch descriptor arrays
      |                                   (``scheduler.py``)
    device: ragged forward — scan over layers; paged KV write + chunk/decode
      Pallas attention; MoE grouped GEMM      (``ragged_model.py``)
    host: sample / collect last-token logits, advance descriptors

The steady-state decode hot path does NOT run that per-pass loop: it runs
the bucketed fused decode step (one program a token: sampling on device, one
int32 token row per step crossing to host) chained by the async
double-buffered ``DecodePipeline`` (``pipeline.py``); see docs/SERVING.md
for the full picture (bucketing grids, the one-step-late drain, AOT
warmup).

KV pages are donated through the pass (XLA aliases them in HBM — the functional
analog of the reference writing its blocked KV cache in place).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.comm.mesh import (TENSOR_AXIS, MeshTopology, build_topology,
                                     set_topology)
from deepspeed_tpu.config import MeshConfig
from deepspeed_tpu.inference.v2.adapters import adapt_model
from deepspeed_tpu.inference.v2.attention import (BLOCK_DIFFUSION_MSG,
                                                  INDEX_POOL_MSG,
                                                  STATE_SNAPSHOT_MSG,
                                                  AttentionKernelSpec)
from deepspeed_tpu.inference.v2.config_v2 import RaggedInferenceEngineConfig
from deepspeed_tpu.inference.v2.model_spec import (
    describe_layer_kinds, index_width, latent_width, layer_runs,
    num_page_layers, num_state_layers)
from deepspeed_tpu.inference.v2.ragged.blocked_allocator import BlockedAllocator
from deepspeed_tpu.inference.v2.ragged.kv_cache import BlockedKVCache, KVCacheConfig
from deepspeed_tpu.inference.v2.ragged_model import (
    PAGED_PASS_KEYS, PREFILL_PASS_KEYS, STATE_PASS_KEYS, build_block_step,
    build_decode_step, build_prefill_forward, build_ragged_forward, build_verify_step,
    kv_write_run_group, pass_held_rows_bound, quantize_weights_int4,
    quantize_weights_int8)
from deepspeed_tpu.inference.v2.scheduler import DynamicSplitFuseScheduler
from deepspeed_tpu.monitor.trace import install_from_env as _trace_from_env
from deepspeed_tpu.monitor.trace import tracer as _tracer
from deepspeed_tpu.utils.caching import LRUCache, next_pow2
from deepspeed_tpu.utils.compile_cache import backend_compiles as _backend_compiles
from deepspeed_tpu.utils import locksan as _locksan
from deepspeed_tpu.utils.fault_injection import maybe_fail as _maybe_fail
from deepspeed_tpu.utils.logging import log_dist


import collections
import contextlib
import functools
import time as _time


#: what programs returned after their three results and no host has read
#: yet: ``(the always-on counter it adds to, an int32 scalar on the device)``,
#: read by :func:`_count_held_turns` once its program is done
_held_turns_pending: "collections.deque" = collections.deque()

#: the counters programs feed that way: a held share's turns past the first
#: (``ragged_model._stream_turns``), and a selection's walks over its tiles
#: and the blocks of query rows that took them (``ragged_mla._select_counts``)
_PROGRAM_COUNTERS = ("serve/moe/held_overflow_turns",
                     "serve/dsa/select_sweeps", "serve/dsa/select_blocks")


def _count_held_turns() -> None:
    """Add what the finished programs counted to the always-on counters of
    :data:`_PROGRAM_COUNTERS`. Called where the host is fetching a step's
    results anyway (:func:`fetch_to_host`); it reads only scalars whose
    program has finished, oldest first, and waits for none."""
    while _held_turns_pending:
        try:
            name, count = _held_turns_pending.popleft()
        except IndexError:      # another thread took the last
            return
        if not count.is_ready():
            _held_turns_pending.appendleft((name, count))
            return
        _tracer.bump(name, float(np.asarray(count)))  # jaxlint: disable=JL007 -- 4 bytes of a finished pass


class _ThreeResults:
    """A jitted prefill pass or decode step, called for its three results.
    What a program returns after them it has counted — a held share's turns
    past the first (a scalar), a selection's walks and blocks (a dict by
    counter) — and that is left on the device for :func:`_count_held_turns`.
    Everything else (``lower``, the cache's counters) is the program's
    own."""

    def __init__(self, prog):
        self.prog = prog

    def __call__(self, *args):
        first, second, new_kv, *counted = self.prog(*args)
        for c in counted:
            _held_turns_pending.extend(
                c.items() if isinstance(c, dict)
                else [(_PROGRAM_COUNTERS[0], c)])
        return first, second, new_kv

    def __getattr__(self, name):
        return getattr(self.prog, name)


def fetch_to_host(arr) -> np.ndarray:
    """THE device->host drain point for the v2 serving hot path.

    Every blocking fetch of a device array in ``inference/v2`` routes through
    here: the serving loops are engineered so the only thing drained per
    decode step is a bucket-sized int32 token row, and funnelling the drain
    through one function lets jaxlint rule JL007 statically police the hot
    path for stray blocking fetches (an accidental ``np.asarray(logits)``
    re-introduces the [S, V] per-step transfer this engine exists to avoid).

    Under tracing the drain records a ``serve/drain/fetch_to_host`` span, so
    host-sync cost on the serving path is always attributed by name
    (docs/OBSERVABILITY.md).
    """
    if _locksan.enabled():
        # runtime TL002 signal: a drain while sanitized locks are held
        _locksan.note_blocking("fetch_to_host")
    traced = _tracer.enabled
    t0 = _time.perf_counter() if traced else 0.0
    out = np.asarray(arr)  # jaxlint: disable=JL007 -- the intentional drain
    _count_held_turns()
    if traced:
        _tracer.add("serve/drain/fetch_to_host", t0, _time.perf_counter(),
                    lane="serve/drain")
    return out


def _program(fn, name: str, **jit_kwargs):
    """``jax.jit(fn)`` under a name of its own (for a pass or a decode step,
    inside :class:`_ThreeResults`). Every builder's inner function
    is called ``fwd``; under its name the program is ``jit_<name>`` in a
    device trace, the compile log and the cache key (docs/OBSERVABILITY.md,
    "Names on the device's work"). A split-K rung above 1 is part of the
    name (``_sk<r>``)."""
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn, **jit_kwargs)


def _moe_kernel_counts() -> Dict[str, int]:
    """``serve/moe/grouped_kernel/<pallas|xla>`` of ``tracer.totals`` by
    kernel: MoE layer runs traced so far in this process."""
    prefix = "serve/moe/grouped_kernel/"
    return {k[len(prefix):]: int(v) for k, v in dict(_tracer.totals).items()
            if k.startswith(prefix)}


def _rung(sp: int) -> str:
    return "" if int(sp) <= 1 else f"_sk{int(sp)}"


@functools.partial(jax.jit, static_argnums=(3, 4))
def serve_sample_rows(arr, rows, key, do_sample: bool, top_k: int,
                      temperature=1.0):
    """Gather rows + greedy / temperature / top-k sampling, ONE device call.
    arr [P, V] (or [V] with rows=None semantics handled by caller reshaping);
    rows [n] int32."""
    logits = arr[rows]
    if not do_sample:
        return jnp.argmax(logits, axis=-1)
    z = logits.astype(jnp.float32) / jnp.maximum(temperature, 1e-6)
    if top_k > 0:
        kth = jax.lax.top_k(z, top_k)[0][:, -1:]
        z = jnp.where(z < kth, -jnp.inf, z)
    return jax.random.categorical(key, z, axis=-1)


@jax.jit
def serve_place_rows(ids, part, at):
    """``ids`` [B] int32 with ``part`` [p] written at positions ``at`` [p]
    (``at == B``: an entry of the part's padding, dropped)."""
    return ids.at[at].set(part.astype(jnp.int32), mode="drop")


class InferenceEngineV2:

    @property
    def backend_compiles(self) -> int:
        """Programs the PROCESS compiled or loaded from the persistent cache
        since this engine was built (jax's monitoring events, through
        ``utils/compile_cache.py``): module-level jits and eager helper
        operations included, which ``compiles`` — a count of this engine's
        own program builds — cannot see."""
        return _backend_compiles() - self._backend_compiles_base

    def __init__(self,
                 model: Any = None,
                 config: Optional[RaggedInferenceEngineConfig] = None,
                 model_parameters: Any = None,
                 family: Optional[str] = None,
                 mesh_topology: Optional[MeshTopology] = None):
        # serving runs don't pass through deepspeed_tpu.initialize — arm the
        # span tracer from $DSTPU_TRACE here (no-op when unset/armed), before
        # the first stage, so that a traced start's timeline holds all of it
        _trace_from_env()
        # the first stage of set-up (tracer.stage; docs/OBSERVABILITY.md,
        # "Set-up and compiles"): net of the warm-up it may run
        with _tracer.stage("engine_init"):
            self._init(model, config, model_parameters, family, mesh_topology)

    def _init(self, model, config, model_parameters, family, mesh_topology):
        self.config = RaggedInferenceEngineConfig.load(config)
        cfg = self.config
        # persistent XLA compile cache: configured FIRST so every program this
        # constructor (and the optional AOT warmup below) compiles lands in it
        # — a second engine start then reloads instead of recompiling
        from deepspeed_tpu.utils.compile_cache import setup_compile_cache
        setup_compile_cache(cfg.compile.min_compile_time_secs)
        # device programs built by this engine (each is called with exactly
        # one signature, so builds == XLA compiles modulo the persistent
        # cache). Warmup pre-builds the serving grid; a serving loop whose
        # batch sizes stay in-grid must never increment this again.
        self.compiles = 0
        self._backend_compiles_base = _backend_compiles()
        self._setup_logged = False
        tp = cfg.tensor_parallel
        if mesh_topology is not None:
            self.topology = set_topology(mesh_topology)
        else:
            n = len(jax.devices())
            self.topology = set_topology(build_topology(
                MeshConfig(tensor=tp, data=n // tp, fsdp=1)))
        mesh_devices = self.topology.mesh.devices
        if (tp == 1 and mesh_devices.size > 1
                and mesh_devices.flat[0].platform == "tpu"):
            # at tp == 1 the paged kernels are called outside any shard_map,
            # and the SPMD partitioner cannot split a Mosaic kernel: every
            # program of this engine would fail to lower ("Mosaic kernels
            # cannot be automatically partitioned"). The CPU interpreter
            # hides that, so the refusal is for TPU meshes only.
            raise NotImplementedError(
                f"InferenceEngineV2 at tensor_parallel=1 would span all "
                f"{mesh_devices.size} devices of its mesh (a 'data' axis "
                "over replicated weights), and its Pallas kernels cannot be "
                "partitioned over them. Serve one replica per chip: pass "
                "mesh_topology=build_topology(MeshConfig(data=1), "
                "devices=[chip]) for each engine")

        model_config = getattr(model, "config", None)
        if model_config is None:
            raise ValueError("InferenceEngineV2 needs a model with .config")
        if family is None:
            family = _guess_family(model)
        self.family = family
        # adapter inputs, re-run by the colocated WeightBridge
        # (runtime/colocated.py) to trace the train->serve reshard program
        self.model_config = model_config
        # monotone weight-version stamp: bumped by every swap_weights();
        # the prefix cache keys/flushes on it (stale-KV refusal) and the
        # serving frontend tags post-swap streams with it
        self.weight_version = 0
        if model_parameters is None:
            raise ValueError("InferenceEngineV2 needs model_parameters")
        from deepspeed_tpu.utils.tree import tree_cast
        with _tracer.stage("shard_weights"):
            params = tree_cast(model_parameters, cfg.dtype)
            self.spec, weights = adapt_model(
                family, params, model_config,
                max_context=cfg.state_manager.max_context)
            self.spec.dtype = cfg.dtype
            if cfg.quantization.weight_bits in (4, 8):
                if tp > 1:
                    raise NotImplementedError(
                        "weight-only int4/int8 with tensor_parallel > 1 is "
                        "not wired yet (the AutoTP rule walker shards plain "
                        "arrays); run quantized at tp=1 or bf16 under tp")
                weights = (quantize_weights_int8(weights)
                           if cfg.quantization.weight_bits == 8
                           else quantize_weights_int4(weights))
            # blocked on: the stage holds the copy to the device, not only
            # its dispatch
            self.weights = jax.block_until_ready(
                self._shard_weights(weights))

        # KV cache + allocator + scheduler
        sm = cfg.state_manager
        nb = cfg.kv_cache.num_blocks
        if nb is None:
            # pool sized to hold max_tracked_sequences at max_context (CPU tests);
            # on TPU prefer an explicit num_blocks or memory-fraction sizing
            per_seq = -(-sm.max_context // cfg.kv_cache.block_size)
            nb = per_seq * sm.max_tracked_sequences
        # the ONE build-time capability table (inference/v2/attention.py):
        # every surviving (feature x feature) refusal raises here; what
        # does NOT raise composes — int8 KV pages run under the prefix
        # cache, spec decode, preempt-offload and the page fabric
        AttentionKernelSpec.validate_engine_build(self.spec, cfg)
        # the pool carries ONE page beyond the allocator's reach: the scratch
        # page backing bucket-padding rows in the fused decode programs (pad
        # rows read/write only it, so padding a batch to its power-of-two
        # bucket never touches a live sequence's KV). Outside the allocator
        # on purpose — free/total accounting and the prefix cache never see
        # it, and it can never be handed to a sequence.
        # pages exist for the layers that attend; a layer that keeps a state
        # (Mamba, Gated DeltaNet, power retention) holds a slot of the state
        # pool per sequence instead (ragged/state_pool.py). Where NO layer
        # attends (brumby) the page pool is its scratch page alone — one
        # layer of one page, which the programs' padding rows address — the
        # allocator hands out nothing, the scheduler funds no block a token
        # (``scheduler.pageless``), and ``kv_cache.num_blocks`` is not read:
        # what a sequence costs the device is its state slot
        # a learned selection inside latent attention (``adapt_glm_dsa``)
        self.index = (self.spec.mla or {}).get("index")
        pageless = num_page_layers(self.spec) == 0
        if pageless:
            nb = 0
        kv_cfg = KVCacheConfig(
            num_layers=max(1, num_page_layers(self.spec)),
            num_kv_heads=self.spec.num_kv_heads,
            head_dim=self.spec.head_dim,
            block_size=cfg.kv_cache.block_size,
            num_blocks=nb + 1,
            dtype=cfg.dtype,
            quantized=cfg.kv_quant.enabled,
            # latent attention: one row a token a layer, no K/V pair; with
            # an indexer, an index key a token a layer in a pool beside it
            latent_dim=None if self.spec.mla is None
            else latent_width(self.spec),
            index_dim=index_width(self.spec) if self.index else None)
        self.scratch_block = nb
        with _tracer.stage("kv_alloc"):
            self.kv = BlockedKVCache(kv_cfg, self.topology)
            jax.block_until_ready(self.kv.kv)
        self.allocator = BlockedAllocator(nb)
        self.prefix_cache = None
        if cfg.prefix_cache.enabled:
            # (window refusal raised by validate_engine_build above; int8
            # pools compose — copy_page COW-copies the scale tile with the
            # page, tests/unit/test_kv_quant_stack.py)
            from deepspeed_tpu.inference.v2.prefix_cache import RadixPrefixCache
            self.prefix_cache = RadixPrefixCache(
                self.allocator, kv_cfg.block_size,
                max_cached_blocks=cfg.prefix_cache.max_cached_blocks,
                cow_fn=self.kv.copy_page)
        self.scheduler = DynamicSplitFuseScheduler(sm, self.kv, self.allocator,
                                                   prefix_cache=self.prefix_cache)
        self.scheduler.pageless = pageless
        # generation by diffusion over blocks (``adapt_sdar``): the scheduler
        # cuts prompts at multiples of the block, ``decode_pipeline`` is the
        # block pipeline, and the schedule of a block's denoise passes is the
        # configuration's (``block_decode``)
        self.block_schedule: Tuple[int, ...] = ()
        if self.spec.causal_block > 1:
            self.scheduler.causal_block = self.spec.causal_block
            self.block_schedule = cfg.block_decode.transfer_schedule(
                self.spec.causal_block)
            _tracer.note("serve/block/length", self.spec.causal_block)
            _tracer.note("serve/block/steps", len(self.block_schedule))
            for name in ("passes", "row_passes", "commit_row_passes",
                         "tokens_committed", "overhang_dropped"):
                _tracer.bump(f"serve/block/{name}", 0.0)
        # the recurrent-state pools of a model with state-space layers: one
        # slot per tracked sequence (+ the dump slot), riding with the pages
        # as ONE donated pytree through every program
        from deepspeed_tpu.inference.v2.ragged.state_pool import (
            StatefulKV, StatePoolConfig, StateSlotAllocator)
        self.state_config = None
        if self.spec.mamba is not None:
            m = self.spec.mamba
            ssd = m.get("kind") == "mamba2"
            self.state_config = StatePoolConfig(
                num_layers=num_state_layers(self.spec),
                num_slots=sm.max_tracked_sequences, d_inner=m["d_inner"],
                d_state=m["d_state"], d_conv=m["d_conv"],
                # Mamba-2 convolves x, B and C together; a Gated DeltaNet
                # layer q, k and v (its spec says how many channels); power
                # retention nothing (d_conv 1: the tail pool is of zero size)
                conv_dim=m["d_inner"] + 2 * m["n_groups"] * m["d_state"]
                if ssd else m.get("conv_dim"))
        elif self.spec.cca is not None:
            # attention that keeps a convolution tail beside its pages: the
            # same pool with no recurrent state in it (tails only)
            self.state_config = StatePoolConfig.tails_only(
                num_state_layers(self.spec), sm.max_tracked_sequences,
                taps=self.spec.cca["taps"],
                channels=self.spec.cca["tail_channels"])
        if self.state_config is not None:
            self.scheduler.state_slots = StateSlotAllocator(
                sm.max_tracked_sequences)
            with _tracer.stage("kv_alloc"):
                self.kv.kv = jax.block_until_ready(StatefulKV(
                    self.kv.kv, *self.state_config.zeros()))
        # sliding-window serving (Mistral/Qwen2): the scheduler ring-reuses
        # each sequence's pages beyond the window so KV stays bounded. The
        # ring engages only where EVERY layer is windowed: a model of mixed
        # kinds (spec.layer_kinds; afmoe) reports window None here and all
        # its layers hold whole-context pages (one page kind), the windowed
        # ones reading only their last ``window`` tokens
        self.scheduler.window = self.spec.window
        # [(window, how many layers have it)], for kv_window_dead_tokens()
        windows = [rs.window for rs, _, n in layer_runs(self.spec)
                   for _ in range(n) if rs.window is not None]
        self._windowed_layers = [(w, windows.count(w))
                                 for w in sorted(set(windows))]
        if cfg.spec_decode.enabled:
            # (window refusal raised by validate_engine_build above; int8
            # pools compose — build_verify_step quantizes-on-write and the
            # chunk kernel dequantizes in-flight)
            # the n-gram proposer drafts from each sequence's prompt
            # history — record it even without a prefix cache
            self.scheduler.record_history_always = True

        if self.spec.alibi and tp > 1:
            # the paged kernels compute ALiBi slopes from shard-LOCAL head
            # indices; under head-sharded TP every shard would reuse the
            # first shard's half-sized slope schedule (review r5: measured
            # 0.72 max abs err on 8 virtual devices) — refuse until the
            # kernels take a global head offset
            raise NotImplementedError(
                "ALiBi models with tensor_parallel > 1 are not wired in the "
                "ragged engine (shard-local slope schedules would be wrong); "
                "run tp=1 or serve through init_inference")
        fwd = build_ragged_forward(self.spec, mesh=self.topology.mesh, tp=tp)
        self._pass = _ThreeResults(
            _program(fwd, "serve_paged_pass", donate_argnums=(1,)))
        self.compiles += 1
        # flash-decoding split ladder (config.attention; docs/SERVING.md
        # "Attention kernels"): one ragged-pass program per pow2 rung.
        # Rung 1 IS self._pass — the byte-identical chunk-serial program;
        # higher rungs rebuild the pass with split-K attention bound
        # (ops/pallas/paged_splitk.py). The fused decode-step and verify
        # grids grow the same rung axis through their cache keys, and
        # warmup() pre-builds every (grid point x rung) so the
        # admission-driven rung choice (_attn_rung) never compiles on the
        # hot path. decode_splits == 1 (default) leaves all of this inert.
        self._pass_rungs = {1: self._pass}
        for r in self.attn_split_ladder[1:]:
            fwd_r = build_ragged_forward(self.spec, mesh=self.topology.mesh,
                                         tp=tp, n_splits=r)
            self._pass_rungs[r] = _ThreeResults(_program(
                fwd_r, "serve_paged_pass" + _rung(r), donate_argnums=(1,)))
            self.compiles += 1
        # test knob: pin the dispatched rung (None = admission-driven)
        self.attn_rung_override: Optional[int] = None
        self._pass_prefill = None  # built on the first pure-prefill pass
        self._rng = np.random.RandomState(cfg.seed)
        self._rng_key = jax.random.PRNGKey(cfg.seed)
        self._last_logits: Dict[int, np.ndarray] = {}
        # device-resident logits refs: uid -> (device_array, row).
        # Materialised to numpy lazily (put()) or sampled on device without
        # ever shipping the [S, V] tensor to host (sample_next()).
        self._last_ref: Dict[int, Tuple[Any, int]] = {}
        # compiled fused decode-step programs (DecodePipeline), LRU-bounded
        # and keyed by (BUCKET, do_sample, top_k, rank bucket, split rung)
        # where BUCKET = next_pow2(live rows): serving with many batch sizes
        # reuses ~log2 executables. Callers hold the returned program through
        # the call, so eviction can never free an executable mid-flight.
        self._step_progs: LRUCache = LRUCache(maxsize=16)
        # compiled verify-step programs (spec/pipeline.py), keyed by
        # (bucket, k) — the speculation grid warmup() pre-compiles
        self._verify_progs: LRUCache = LRUCache(maxsize=16)
        # compiled block-step programs (blocks/pipeline.py), keyed by bucket
        self._block_progs: LRUCache = LRUCache(maxsize=16)
        self._spec_warned_sampling = False
        # KV page host round-trip programs (gather, scatter) — the serving
        # frontend's preempt-offload path (serving/kv_offload.py); built
        # lazily, warmed by warmup() so a mid-steady-state preemption never
        # observes a compile. _page_buckets tracks the (op, pow2-count)
        # signatures already compiled (the compiles-counter unit here).
        self._page_progs = None
        self._page_buckets: set = set()
        # aggregate double-buffer pipeline timings (monitor/serving.py);
        # write_monitor_events emits them
        from deepspeed_tpu.monitor.serving import (AttnSplitStats,
                                                   PipelineStats,
                                                   SpecDecodeStats)
        self.pipeline_stats = PipelineStats()
        self.spec_stats = SpecDecodeStats()
        # split-ladder rung-selection counters (serve/attn/* events; fed by
        # the same perf stamps as the serve/attn/select trace spans)
        self.attn_stats = AttnSplitStats()
        # multi-tenant LoRA: adapter registry + paged weight pool
        # (inference/v2/lora/; docs/SERVING.md "Multi-tenant LoRA"). The
        # decode/verify program grid grows a rank-bucket axis; the pool's
        # host movers count compiles through the engine counter so the
        # zero-steady-state-compile gate covers adapter churn too.
        self.lora = None
        if cfg.lora.enabled:
            if tp > 1:
                # the grouped-matmul pages pack WHOLE projection columns/rows
                # per rank slice; under head-sharded TP each shard would need
                # its slice of every page — refuse until the pool is sharded
                raise NotImplementedError(
                    "multi-tenant LoRA with tensor_parallel > 1 is not wired "
                    "(adapter pages are unsharded whole-projection slices); "
                    "run lora at tp=1")
            from deepspeed_tpu.inference.v2.lora import (LoraAdapterRegistry,
                                                         LoraPagePool)

            def _count_compile():
                self.compiles += 1

            self.lora = LoraAdapterRegistry(
                LoraPagePool(self.spec, cfg.lora.targets, cfg.lora.pool_pages,
                             compile_hook=_count_compile),
                swap_buffers=cfg.lora.swap_buffers,
                max_rank=cfg.lora.max_rank)
        if self.spec.mla is None and not pageless:
            # the K/V rows the paged passes and block steps write, always on
            # (tracer.totals): those that go as part of a run (a chunk
            # slot's, a block's: ``ragged_model._kv_run_write``) and those
            # scattered one by one (a decode row; every row of a pool the
            # run writer turns away)
            _tracer.bump("serve/kv_write/run_rows", 0.0)
            _tracer.bump("serve/kv_write/single_rows", 0.0)
        ring = self.scheduler.ring_pages
        if self.spec.mla is not None:
            # always-on values (tracer.totals; docs/OBSERVABILITY.md): what a
            # token costs the latent pool a layer, and how many of the
            # router's experts this engine holds
            item = jnp.dtype(kv_cfg.dtype).itemsize
            _tracer.note("serve/latent/bytes_per_token",
                         kv_cfg.latent_dim * item)
        if self.index:
            # what the selection keeps a query, what a token costs the index
            # pool a layer, and that pool's size
            _tracer.note("serve/index/topk", self.index["topk"])
            _tracer.note("serve/index/bytes_per_token",
                         kv_cfg.index_dim * item)
            _tracer.note("serve/index/pool_bytes", self.kv.kv[1].nbytes)
            # the walks ``dsa_select`` took over its tiles and the blocks of
            # query rows that took them, in passes and decode steps: their
            # ratio is how soon the bisection's bounds and its stop let go
            # (ops/pallas/sparse_mla.py, item 2)
            _tracer.bump("serve/dsa/select_sweeps", 0.0)
            _tracer.bump("serve/dsa/select_blocks", 0.0)
            log_dist(f"engine_v2: a selection over the latent pages: "
                     f"{self.index['heads']} index heads of "
                     f"{self.index['head_dim']} keep the top "
                     f"{self.index['topk']} cached tokens a query; pools "
                     f"latent {self.kv.kv[0].nbytes / 2**20:.1f} MiB + index "
                     f"{self.kv.kv[1].nbytes / 2**20:.1f} MiB, "
                     f"{kv_cfg.latent_dim * item} + {kv_cfg.index_dim * item}"
                     " B a token a layer; the packed prefill pass "
                     + ("selects nothing (it cannot hold more than top-k "
                        "tokens)" if self.packed_prefill else
                        "is off (it could hold more than top-k tokens of a "
                        "sequence): prompts take the paged pass"), ranks=[0])
        if self.spec.moe is not None and "held" in self.spec.moe:
            _tracer.note("serve/moe/held_experts", self.spec.moe["held"][1])
            # the sorted rows one turn of the paged pass's MoE layers takes
            # (0: the pass sorts and combines every choice), and the turns
            # its passes and decode steps took past the first, which stays
            # 0 while none sends its held experts more than twice their
            # even share
            _tracer.note("serve/moe/held_rows_bound", pass_held_rows_bound(
                self.spec, self.weights, sm.num_chunk_slots
                * sm.chunk_slot_size + sm.max_ragged_sequence_count) or 0)
            _tracer.bump("serve/moe/held_overflow_turns", 0.0)
        if self.spec.moe is not None:
            # (1: one linear map of the layer's input; 2: an MLP on a state
            # that goes from layer to layer)
            _tracer.note("serve/moe/router_kind",
                         2 if self.spec.moe.get("router") == "mlp" else 1)
        if self.state_config is not None:
            # always-on values: what a tracked sequence costs the state pool
            # over all its layers, and which recurrence fills it
            _tracer.note("serve/state/bytes_per_sequence",
                         self.state_config.bytes_per_slot())
            # (1, 2: the Mamba recurrence; 3: the gated delta rule; 4: no
            # recurrence, convolution tails beside attention's pages; 5:
            # power retention, a state and its normaliser and no tail)
            if self.spec.mamba is None:
                kind, recurrence = 4, "convolution tails (no recurrence)"
                _tracer.note("serve/cca/tail_channels",
                             self.spec.cca["tail_channels"])
            else:
                kind = {"mamba2": 2, "gdn": 3, "pr": 5}.get(
                    self.spec.mamba.get("kind"), 1)
                recurrence = {3: "Gated DeltaNet", 5: "power retention"}.get(
                    kind, f"Mamba-{kind}")
                if kind == 5:
                    # the lanes of a state (the key's expansion), the power,
                    # the chunk of the prompt rows' scan
                    _tracer.note("serve/pr/state_rows",
                                 self.spec.mamba["d_inner"])
                    _tracer.note("serve/pr/power", 2)
                    _tracer.note("serve/pr/chunk", self.spec.mamba["chunk"])
            _tracer.note("serve/state/kind", kind)
        if any(k.block is not None for k in self.spec.layer_kinds or ()):
            # one block a layer: how many layers are each block (what a
            # reader divides a block's share of a step by)
            whats = [k.what for k in self.spec.layer_kinds]
            for what in sorted(set(whats)):
                _tracer.note(f"serve/layers/blocks/{what}", whats.count(what))
        state = "" if self.state_config is None else (
            f"; {recurrence} "
            f"state pool {self.state_config.num_slots}+dump slots x "
            f"{self.state_config.num_layers} layers = "
            f"{self.state_config.total_bytes() / 2**20:.1f} MiB "
            f"({self.state_config.bytes_per_slot() / 2**20:.2f} MiB a "
            "sequence)")
        latent = "" if kv_cfg.latent_dim is None else (
            f" of latent rows ({kv_cfg.latent_dim} values a token, no K/V "
            "pair)")
        log_dist(f"engine_v2: family={family} tp={tp} blocks={nb}+scratch "
                 f"block_size={kv_cfg.block_size} x {kv_cfg.num_layers} page "
                 f"layers{latent} budget={sm.max_ragged_batch_size}{state}"
                 f"; {describe_layer_kinds(self.spec)}; page ring "
                 f"{'off' if ring is None else f'{ring} pages a sequence'}; "
                 f"attention rungs {list(self.attn_split_ladder)}"
                 + (" (full layers; windowed layers stay on rung 1)"
                    if self.spec.layer_kinds is not None
                    and len(self.attn_split_ladder) > 1 else ""),
                 ranks=[0])
        if cfg.compile.warmup:
            self.warmup(buckets=cfg.compile.warmup_buckets)

    # ------------------------------------------------------------------ #

    def _shard_weights(self, weights):
        """TP sharding of the canonical stacked weights via the shared AutoTP
        rule walker (``parallel/tensor_parallel.py``) — one source of truth for
        column/row assignments; non-divisible dims warn and replicate."""
        topo = self.topology
        tp = topo.tp_world_size
        if tp <= 1:
            return jax.device_put(weights, topo.replicated())
        from deepspeed_tpu.parallel.tensor_parallel import (
            RAGGED_STACKED_TP_RULES, derive_tp_specs)
        specs = derive_tp_specs(weights, RAGGED_STACKED_TP_RULES, tp)
        shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(topo.mesh, s), specs,
            is_leaf=lambda s: isinstance(s, P))
        return jax.device_put(weights, shardings)

    # ------------------------------------------------------------------ #
    # in-place weight swap (colocated rollout; runtime/colocated.py)
    # ------------------------------------------------------------------ #

    def swap_weights(self, new_weights: Any,
                     version: Optional[int] = None) -> int:
        """Rebind ``self.weights`` to a new device tree in place — the
        train->serve sync point of the colocated rollout loop.

        Every device program this engine builds (the pass, decode-step and
        verify grids, warmup() included) takes the weight tree as a RUNTIME
        operand (``prog(self.weights, self.kv.kv, ...)``), so a swap whose
        tree matches the old one leaf-for-leaf in structure, shape, dtype
        and sharding reuses every cached executable: ZERO new compiles, the
        pow2/split/rank ladders survive untouched. Anything that does not
        match is refused up front — a silent mismatch would recompile the
        grid mid-steady-state (or serve garbage).

        The caller must have quiesced the engine first: no live sequences
        (KV computed under the old weights must never be decoded under the
        new ones — the ServingFrontend's swap path recompute-preempts
        in-flight requests at a run boundary exactly like preemption).
        The prefix cache is flushed by weight-version stamp, and host-side
        logits snapshots from pre-swap passes are dropped.

        Returns the new ``weight_version``."""
        if self.scheduler.seqs:
            raise RuntimeError(
                f"swap_weights with {len(self.scheduler.seqs)} live "
                "sequence(s) — their KV was computed under the old weights; "
                "quiesce first (frontend swap preempts at a run boundary, "
                "direct drivers flush() every uid)")
        old_leaves, old_def = jax.tree_util.tree_flatten(self.weights)
        new_leaves, new_def = jax.tree_util.tree_flatten(new_weights)
        if new_def != old_def:
            raise ValueError(
                "swap_weights tree structure mismatch — the replacement "
                "tree must come from the same family adapter layout "
                f"(expected {old_def}, got {new_def})")
        paths = [jax.tree_util.keystr(kp) for kp, _ in
                 jax.tree_util.tree_flatten_with_path(self.weights)[0]]
        for path, o, n in zip(paths, old_leaves, new_leaves):
            if o.shape != n.shape or o.dtype != n.dtype:
                raise ValueError(
                    f"swap_weights leaf {path}: expected "
                    f"{o.dtype}{list(o.shape)}, got {n.dtype}{list(n.shape)} "
                    "— a shape/dtype drift would recompile every warmed "
                    "program")
            osh = getattr(o, "sharding", None)
            nsh = getattr(n, "sharding", None)
            if osh is not None and nsh != osh:
                raise ValueError(
                    f"swap_weights leaf {path}: sharding {nsh} != engine "
                    f"layout {osh} — reshard through WeightBridge "
                    "(runtime/colocated.py), whose out_shardings are taken "
                    "from this engine's weights")
        if version is None:
            version = self.weight_version + 1
        elif version <= self.weight_version:
            raise ValueError(
                f"swap_weights version {version} is not newer than the "
                f"current weight_version {self.weight_version} — versions "
                "are monotone (the prefix cache keys staleness on them)")
        self.weights = new_weights
        self.weight_version = version
        if self.prefix_cache is not None:
            # flush-by-version: cached KV pages hold old-weight state; a
            # post-swap match must miss and re-prefill (regression-pinned
            # by tests/unit/test_colocated.py)
            self.prefix_cache.set_weight_version(version)
        # host-side logits snapshots and device row refs from pre-swap
        # passes are old-weight state: drop, never resample from them
        self._last_logits.clear()
        self._last_ref.clear()
        return version

    # ------------------------------------------------------------------ #
    # public API (parity: engine_v2.py put/query/can_schedule/flush)
    # ------------------------------------------------------------------ #

    def put(self, uids: Sequence[int], tokens_list: Sequence[np.ndarray],
            do_checks: bool = True) -> np.ndarray:
        """Schedule these tokens and run passes until all are consumed. Returns
        next-token logits [len(uids), vocab] in the order given."""
        uids = [int(u) for u in uids]
        if do_checks and not self.scheduler.can_schedule(
                uids, [len(t) for t in tokens_list]):
            raise RuntimeError("cannot schedule: insufficient KV blocks or "
                               "sequence slots (check can_schedule first)")
        for uid, toks in zip(uids, tokens_list):
            self.scheduler.add_tokens(uid, np.asarray(toks, np.int32))

        want = set(uids)
        while self.scheduler.has_pending():
            self._run_pass()
        self._materialize(want)
        missing = want - set(self._last_logits)
        if missing:
            raise RuntimeError(f"no logits produced for uids {sorted(missing)}")
        return np.stack([self._last_logits[u] for u in uids])

    def _put_nofetch(self, uids: Sequence[int],
                     tokens_list: Sequence[np.ndarray]) -> None:
        """Like put(), but leaves the logits on device (see sample_next)."""
        uids = [int(u) for u in uids]
        for uid, toks in zip(uids, tokens_list):
            self.scheduler.add_tokens(uid, np.asarray(toks, np.int32))
        while self.scheduler.has_pending():
            self._run_pass()

    def _materialize(self, uids) -> None:
        """Fetch pending device logits to numpy, one transfer per pass array."""
        by_array: Dict[int, Tuple[Any, list]] = {}
        for uid in uids:
            ref = self._last_ref.pop(uid, None)
            if ref is None:
                continue
            arr, row = ref
            by_array.setdefault(id(arr), (arr, []))[1].append((uid, row))
        for arr, pairs in by_array.values():
            host = fetch_to_host(arr)
            for uid, row in pairs:
                self._last_logits[uid] = host[row]

    def sample_next(self, uids: Sequence[int], do_sample: bool = False,
                    temperature: float = 1.0, top_k: int = 0) -> np.ndarray:
        """Sample the next token for each uid ON DEVICE from its last logits,
        fetching only the token ids (4 bytes/seq instead of the [S, V] logits
        tensor — over the host link this is the difference between
        transfer-bound and compute-bound decode)."""
        padded, n = self._sample_device_padded([int(u) for u in uids],
                                               do_sample, temperature, top_k)
        # slice AFTER the host fetch: a device-side [:n] would compile a new
        # tiny executable for every distinct live-sequence count
        return fetch_to_host(padded)[:n]

    def _sample_device(self, uids: Sequence[int], do_sample: bool,
                       temperature: float, top_k: int):
        """Sample next tokens on device, returning a device array aligned with
        ``uids`` (no host fetch). Prefer :meth:`_sample_device_padded` where a
        padded result is acceptable — the exact-length slice here compiles one
        tiny program per distinct ``len(uids)``."""
        padded, n = self._sample_device_padded(uids, do_sample, temperature,
                                               top_k)
        return padded[:n]

    def _sample_device_padded(self, uids: Sequence[int], do_sample: bool,
                              temperature: float, top_k: int):
        """Like :meth:`_sample_device` but returns ``(padded_ids, n)`` where
        ``padded_ids`` has a power-of-two length >= n: every device program in
        here is then keyed by the BUCKET size, so a serving loop whose live
        set shrinks by one each retirement reuses cached executables instead
        of recompiling per count (seconds each)."""
        if not uids:
            return jnp.zeros((1,), jnp.int32), 0
        by_array: Dict[int, Tuple[Any, list]] = {}
        host_rows, host_idx = [], []
        for i, uid in enumerate(uids):
            ref = self._last_ref.get(int(uid))
            if ref is None:
                # logits were materialised to host (a prior put()); re-upload
                host_idx.append(i)
                host_rows.append(self._last_logits[int(uid)])
                continue
            arr, row = ref
            by_array.setdefault(id(arr), (arr, []))[1].append((i, row))
        if host_rows:
            # the re-upload block is BUCKETED too (rows repeat row 0, never
            # referenced): host-rematerialized sources appear whenever a
            # preempt-offloaded sequence is restored (serving/kv_offload.py
            # parks the victim's last logits row on host), and a count-shaped
            # [n, V] upload would compile a fresh serve_sample_rows per distinct
            # restore count — in the middle of the steady state the
            # zero-compile gate polices. pow2 shapes land in the warmed grid.
            pad = next_pow2(len(host_rows)) - len(host_rows)
            arr = jnp.asarray(np.stack(host_rows + [host_rows[0]] * pad))
            by_array[id(arr)] = (arr, [(i, j) for j, i in enumerate(host_idx)])
        # each source array's rows are sampled at a power-of-two count and
        # PLACED at their positions in one bucket-sized row: every program
        # here is keyed by (bucket, part size), both powers of two, so the
        # set is finite and warmup() builds all of it. (Concatenating the
        # parts and gathering them into order was keyed by HOW the live rows
        # split over logits arrays — a new eager program for every new split,
        # 6-9 of them inside a 45 s window of a 128-row replica; PERF.md,
        # PR 31.) Entries past the live rows stay token 0: pad rows, which
        # run against the scratch page.
        n = len(uids)
        bucket = next_pow2(n)
        ids = self._zero_row(bucket)
        for arr, pairs in by_array.values():
            rows = [r for _, r in pairs]
            if do_sample:
                self._rng_key, sub = jax.random.split(self._rng_key)
            else:
                sub = self._rng_key
            # extra rows resample row 0 and are placed nowhere
            n_real = len(rows)
            rows = rows + [rows[0]] * (next_pow2(n_real) - n_real)
            out = serve_sample_rows(arr, np.asarray(rows, np.int32), sub,
                                    bool(do_sample), int(top_k),
                                    float(temperature))
            at = np.full((len(rows),), bucket, np.int32)
            at[:n_real] = [i for i, _ in pairs]
            ids = serve_place_rows(ids, out, at)
        return ids, n

    def _zero_row(self, bucket: int):
        """A token row of zeros committed to the engine's mesh (what
        ``serve_place_rows`` fills; see ``_scratch_step_args`` for why
        committed)."""
        return jax.device_put(np.zeros((bucket,), np.int32),
                              self.topology.replicated())

    def _zero_block(self, bucket: int):
        """``[bucket, causal_block]`` token ids of zeros committed to the
        engine's mesh: what a run's first block step is handed where the
        pass before's blocks would be (every live row's block is then the
        host's; see ``_scratch_step_args`` for why committed)."""
        return jax.device_put(
            np.zeros((bucket, self.spec.causal_block), np.int32),
            self.topology.replicated())

    def _decode_step_prog(self, bucket: int, do_sample: bool, top_k: int,
                          rb: int = 0, sp: Optional[int] = None):
        """The fused single-step decode program (forward + on-device sampling,
        ragged_model.build_decode_step) for one bucket — the DecodePipeline's
        hot program. LRU-cached per (bucket, do_sample, top_k, rb).

        ``rb`` is the LoRA rank bucket (``lora.rank_bucket`` — pow2, engine-
        stable after registration): rb > 0 builds the grouped-matmul variant
        taking the ``(lora_pool, adapter_pt [bucket, rb])`` trailing operands;
        rb = 0 is EXACTLY the pre-LoRA program, so adapter-free engines are
        byte-unchanged. Distinct rb values are distinct keys — a separate jit
        wrapper each — so every compile stays witnessed by the counter (one
        shared jit re-specializing on the page-table shape would compile
        silently).

        ``sp`` is the flash-decoding split rung (None = this step's
        admission-driven :meth:`_attn_rung`); each rung is its own key so
        rung swaps reuse warmed executables."""
        sp = self._attn_rung() if sp is None else int(sp)

        def _build():
            tp = self.topology.tp_world_size
            fwd = build_decode_step(self.spec, mesh=self.topology.mesh,
                                    tp=tp if tp > 1 else 1,
                                    do_sample=do_sample, top_k=top_k,
                                    window_ring_ok=self.scheduler.ring_covers(2),
                                    lora_targets=self._lora_targets(rb),
                                    n_splits=sp)
            self.compiles += 1
            return _ThreeResults(_program(
                fwd, "serve_decode_step" + _rung(sp), donate_argnums=(1,)))

        return self._step_progs.get_or_create(
            (bucket, bool(do_sample), int(top_k), int(rb), sp), _build)

    def _lora_targets(self, rb: int):
        """The ``lora_targets`` builder knob for a rank bucket: the engine's
        configured projection set when rb > 0, None (base program) at rb=0."""
        if rb == 0:
            return None
        assert self.lora is not None, "rank-bucketed program without LoRA"
        return self.config.lora.targets

    @property
    def lora_rank_bucket(self) -> int:
        """The rank bucket current decode dispatch runs at: the registry's
        ``rank_bucket`` (0 when LoRA is off or only rank-0 adapters exist —
        the base programs)."""
        return self.lora.rank_bucket if self.lora is not None else 0

    def _lora_operands(self, uids: Sequence[int], bucket: int,
                       rb: Optional[int] = None) -> tuple:
        """The trailing ``*lora_args`` for a rank-bucketed program: the pool
        array plus the device page table for these rows. Empty at rb=0 so
        callers can splat unconditionally. Built once per pipeline RUN (the
        batch's adapter bindings are frozen for the run, like block tables —
        the in-jit gather is hoisted out of the step scan on that
        invariant)."""
        rb = self.lora_rank_bucket if rb is None else rb
        if rb == 0:
            return ()
        pt = self.lora.page_table(uids, bucket, rb)
        return (self.lora.pool.pool, jnp.asarray(pt))

    def _state_operands(self, db) -> tuple:
        """The trailing operand of a fused decode program of a model with
        state-space layers: the rows' state slots (run-invariant, like the
        block tables). Empty for any other model, so callers splat it."""
        if db.state_slots is None:
            return ()
        return (jnp.asarray(db.state_slots),)

    def state_slots(self) -> Tuple[int, int, int]:
        """``(live, peak, total)`` slots of the recurrent-state pool — the
        gauge beside the page gauges (``allocator.free_blocks``); all zero
        for a model with no state-space layers. It reads the scheduler's
        free list and takes no lock."""
        a = self.scheduler.state_slots
        return (0, 0, 0) if a is None else (a.live, a.peak, a.total)

    def sequence_state(self, uid: int) -> np.ndarray:
        """A tracked sequence's recurrent state ``h`` ``[Lm, N, E]``
        (float32; Mamba-2: channel ``h * P + p`` of head ``h`` on the last
        axis; power retention: the heads' ``S`` and a normaliser a head down
        ``N``, the key's expansion along ``E`` —
        ``ops/pallas/power_retention.py``) fetched to the host, for a check
        that compares it."""
        slot = self.scheduler.seqs[int(uid)].state_slot
        if slot < 0:
            raise ValueError("this model has no state-space layers")
        sc = self.state_config
        if not sc.d_state:
            # tails only (no recurrence): the sequence's convolution tails
            # ``[L, taps, channels]``, oldest token first
            tail = fetch_to_host(self.kv.kv.conv[:, slot])
            return tail.reshape(sc.num_layers, sc.d_conv - 1,
                                sc.conv_width)[..., :sc.conv_dim]
        return fetch_to_host(self.kv.kv.ssm[:, slot])

    @property
    def attn_split_ladder(self) -> List[int]:
        """The pow2 flash-decoding rung grid attention dispatches over:
        ``[1, 2, 4, ..., config.attention.decode_splits]``. Rung 1 is the
        chunk-serial kernel set exactly; each higher rung cuts every
        sequence's page range into that many grid-parallel split-K partials
        (docs/SERVING.md "Attention kernels"). warmup() pre-compiles every
        program grid point at every rung, so the per-step rung choice
        (:meth:`_attn_rung`) swaps cached executables — never compiles."""
        top = self.config.attention.decode_splits
        return [1 << i for i in range(top.bit_length())]

    def _attn_rung(self) -> int:
        """The split rung for THIS step's dispatch: the largest pow2 rung
        such that the longest live context keeps ``min_ctx_per_split``
        tokens per split, clamped to the warmed ladder — short-context
        batches stay on the split=1 chunk-serial program (the merge pass is
        pure overhead there) and the long-context tail climbs the ladder as
        it grows. ``attn_rung_override`` pins the choice (A/B runs on
        one warmed engine). Records the selection through the shared perf
        stamps: one ``perf_counter`` pair feeds both the
        ``serve/attn/select`` trace span and ``attn_stats`` (the
        serve/attn/* monitor events), so timeline and dashboard agree."""
        top = self.config.attention.decode_splits
        if top <= 1:
            return 1
        if self.attn_rung_override is not None:
            return max(1, min(int(self.attn_rung_override), top))
        t0 = _time.perf_counter()
        live = max((s.seen_tokens for s in self.scheduler.seqs.values()),
                   default=0)
        want = max(1, live // self.config.attention.min_ctx_per_split)
        rung = min(top, 1 << (want.bit_length() - 1))
        t1 = _time.perf_counter()
        self.attn_stats.record(rung, live, t1 - t0)  # jaxlint: disable=JL001 -- host-only scheduler scan, nothing dispatched
        if _tracer.enabled:
            _tracer.add("serve/attn/select", t0, t1, lane="serve/attn",
                        rung=rung, live_ctx=live)
        return rung

    @property
    def spec_k_ladder(self) -> List[int]:
        """The draft-length grid speculation dispatches over: pow2-minus-1
        rungs (K+1 a power of two — the chunk kernel's q-block then covers
        each sequence's rows in ONE block instead of collapsing to 1-row
        blocks) up to ``config.spec_decode.k``. Each step runs the SMALLEST
        rung covering its longest draft, so a mostly-unrepetitive batch
        pays 2-row verifies, not full-k ones; warmup() pre-compiles the
        whole (bucket, rung) grid."""
        k = self.config.spec_decode.k
        ks, v = [], 1
        while v < k:
            ks.append(v)
            v = 2 * v + 1
        ks.append(k)
        return sorted(set(ks))

    def _verify_prog(self, bucket: int, k: int, rb: int = 0,
                     sp: Optional[int] = None):
        """The fused speculative verify-step program (draft scoring in ONE
        ragged forward, ragged_model.build_verify_step) for one (bucket, k)
        grid point — the SpecDecodePipeline's hot program. LRU-cached;
        warmup() pre-compiles the whole grid. ``rb`` as in
        :meth:`_decode_step_prog` — rb > 0 verifies WITH each row's adapter
        delta (the K+1 token rows share the sequence's adapter), keeping
        accepted spec tokens byte-identical to plain LoRA decode. ``sp`` as
        in :meth:`_decode_step_prog` — verify rides the SAME split rung as
        decode so spec streams stay on warmed programs across the ladder."""
        sp = self._attn_rung() if sp is None else int(sp)

        def _build():
            tp = self.topology.tp_world_size
            fwd = build_verify_step(self.spec, k, mesh=self.topology.mesh,
                                    tp=tp if tp > 1 else 1,
                                    lora_targets=self._lora_targets(rb),
                                    n_splits=sp)
            self.compiles += 1
            return _program(fwd, "serve_verify_step" + _rung(sp),
                            donate_argnums=(1,))

        return self._verify_progs.get_or_create(
            (bucket, int(k), int(rb), sp), _build)

    def _block_step_prog(self, bucket: int):
        """The block step (``ragged_model.build_block_step``) for one bucket
        — the BlockDecodePipeline's hot program, ``jit_serve_block_step`` in
        a device trace. LRU-cached; warmup() pre-compiles the grid."""
        def _build():
            self.compiles += 1
            return _program(
                build_block_step(self.spec, mesh=self.topology.mesh),
                "serve_block_step", donate_argnums=(1,))

        return self._block_progs.get_or_create(int(bucket), _build)

    def block_reserve_tokens(self, n_passes: int) -> int:
        """KV tokens a run of ``n_passes`` block steps reserves a row up
        front: a block takes a denoise pass and a commit pass at the least,
        so a row commits at most ``n // 2 + 1`` blocks in it, and writes one
        block more past its last commit; run-end ``rollback_reserved``
        returns what it did not reach. The frontend's funding
        (``admission.slice_tokens``) is this number."""
        return self.spec.causal_block * (int(n_passes) // 2 + 2)

    def decode_pipeline(self, uids: Sequence[int], do_sample: bool = False,
                        temperature: float = 1.0, top_k: int = 0):
        """The steady-state decode pipeline over ``uids`` (all must be in
        steady decode state). Default: the async double-buffered
        ``pipeline.DecodePipeline`` — while the device runs step N, the host
        drains step N-1's token row and builds step N+1's descriptors; the
        only per-step transfer is one int32 row.

        With ``config.spec_decode.enabled``, greedy requests get the
        ``spec.SpecDecodePipeline`` instead (draft-and-verify, variable
        per-step advance; callers branch their ``on_tokens`` shape on
        ``pipe.spec``). Speculation is greedy-only for now: ``do_sample``
        cleanly bypasses it with a one-time warning rather than silently
        degrading sampled streams.

        A model that generates by diffusion over blocks
        (``spec.causal_block`` > 1) gets the ``blocks.BlockDecodePipeline``:
        there is no other way to generate from it. Greedy only for now."""
        if self.spec.causal_block > 1:
            if do_sample:
                raise NotImplementedError(
                    "sampling is not wired for generation by diffusion over "
                    "blocks: a denoise pass fills positions by the greedy "
                    "token's confidence (do_sample=True would need a sampled "
                    "token and its probability a position)")
            from deepspeed_tpu.inference.v2.blocks import BlockDecodePipeline
            return BlockDecodePipeline(self, uids)
        if self.config.spec_decode.enabled:
            if do_sample:
                if not self._spec_warned_sampling:
                    self._spec_warned_sampling = True
                    import warnings
                    warnings.warn(
                        "spec_decode is greedy-only for now: "
                        "do_sample=True bypasses speculation and runs the "
                        "plain DecodePipeline (warned once)", stacklevel=2)
            else:
                from deepspeed_tpu.inference.v2.spec import SpecDecodePipeline
                return SpecDecodePipeline(self, uids)
        from deepspeed_tpu.inference.v2.pipeline import DecodePipeline
        return DecodePipeline(self, uids, do_sample=do_sample,
                              temperature=temperature, top_k=top_k)

    # ------------------------------------------------------------------ #
    # AOT warmup (config_v2.CompileConfig)
    # ------------------------------------------------------------------ #

    @property
    def decode_buckets(self) -> List[int]:
        """The full reachable decode bucket grid: powers of two up to the
        scheduler's decode-row capacity."""
        top = next_pow2(self.config.state_manager.max_ragged_sequence_count)
        return [1 << i for i in range(top.bit_length())]

    def warmup(self, buckets: Optional[Sequence[int]] = None,
               spec_ks: Optional[Sequence[int]] = None) -> int:
        """Pre-compile the serving program set so in-grid traffic never
        observes an XLA compile (and, with a persistent compile cache
        configured, so a future engine start reloads everything from disk).

        Covers: the ragged paged pass, the prefill fast path, the fused
        decode-step program for every bucket (greedy — the serving default;
        sampled variants compile on first use), and the module-level
        bootstrap sampler ``serve_sample_rows`` over the logits-source shapes the
        serving loops read (chunk/decode pass outputs, per-bucket fused
        outputs, and pow2-padded host-rematerialized blocks — restore paths
        re-upload through the same bucket grid). Also warms the KV page
        offload/restore round-trip pair.
        Each program is executed once over scratch-page-only descriptors —
        real KV state, scheduler state and logits refs are untouched.

        Explicit ``buckets`` are rounded up to powers of two (the live path
        always rounds, so a non-pow2 bucket would be dead weight).

        Returns the number of ENGINE programs built (what ``self.compiles``
        gained: this engine's own builds, so the bootstrap sampler's
        module-level jits and eager helpers are not in it). The process-wide
        count of what warm-up traced, lowered and compiled or loaded is
        ``compile/warmup/programs`` in ``tracer.totals``, beside
        ``setup/warmup_s`` and a ``setup/warmup/<family>_s`` for each family
        of the grid that this configuration has; the line logged at the end
        (``compile_cache.setup_summary``) gives both with the costliest
        programs by name.

        ``spec_ks``: draft lengths to warm the speculative verify grid for
        — one ``build_verify_step`` program per (bucket, k). ``None``
        defaults to the full ``spec_k_ladder`` when speculation is enabled
        (so a spec-serving engine's steady state — including the spec-off
        comparison legs sharing the engine — adds zero timed compiles).
        """
        from deepspeed_tpu.utils.compile_cache import (setup_summary,
                                                       with_stack_room)
        # set-up's second stage (tracer.stage), a child a family of the grid
        counted = {n: _tracer.totals[n] for n in _PROGRAM_COUNTERS
                   if n in _tracer.totals}
        with _tracer.stage("warmup"):
            built = with_stack_room(lambda: self._warmup(buckets, spec_ks))
        if counted:
            # scratch rows are no traffic: a warmed decode step's rows are
            # all alike, so a router may send every one to a held expert
            _held_turns_pending.clear()
            for name, before in counted.items():
                _tracer.note(name, before)
        if not self._setup_logged:      # once: a rejoin warms again
            self._setup_logged = True
            log_dist(f"engine_v2: {setup_summary()}", ranks=[0])
        return built

    def _warmup(self, buckets, spec_ks) -> int:
        stage = _tracer.stage

        def family(name, members):
            """The stage of one family of the grid: none where this
            configuration has no member of it."""
            return stage(name) if members else contextlib.nullcontext()

        before = self.compiles
        kernels_before = _moe_kernel_counts()
        grid = sorted({next_pow2(int(b)) for b in buckets}) \
            if buckets is not None else self.decode_buckets
        if spec_ks is None:
            spec_ks = self.spec_k_ladder \
                if self.config.spec_decode.enabled else []
        spec_ks = sorted({int(k) for k in spec_ks})
        # LoRA rank rungs: pow2 up to next_pow2(lora.max_rank) — the whole
        # rank-bucket axis of the program grid (registration refuses larger
        # ranks, so live dispatch can never leave the warmed ladder). rb=0
        # (the base programs) is the existing grid below.
        lora_rungs: List[int] = []
        if self.lora is not None:
            top = next_pow2(self.config.lora.max_rank)
            lora_rungs = [1 << i for i in range(top.bit_length())]
        # the flash-decoding split-rung axis (attn_split_ladder): every
        # program grid below is warmed at EVERY rung, so the per-step
        # admission-driven rung choice swaps cached executables — context
        # growth climbing the ladder adds zero steady-state compiles
        attn_rungs = self.attn_split_ladder
        # the warmed set must FIT its LRUs, or warmup evicts programs it just
        # built and the zero-compiles invariant silently breaks on first use
        self._step_progs.maxsize = max(
            self._step_progs.maxsize,
            (len(lora_rungs) + 1) * len(grid) * len(attn_rungs) + 2)
        self._verify_progs.maxsize = max(
            self._verify_progs.maxsize,
            (len(lora_rungs) + 1) * len(spec_ks) * len(grid)
            * len(attn_rungs) + 2)
        with stage("passes"):
            self._warm_passes()
        mb = self.scheduler.max_blocks
        if self.spec.causal_block > 1:
            # generation by blocks: the block step over the decode buckets is
            # the whole decode grid (no one-token step, no sampler: a pass
            # chooses its tokens itself)
            self._block_progs.maxsize = max(self._block_progs.maxsize,
                                            len(grid) + 2)
            with stage("block_grid"):
                for b in grid:
                    new_ids, *_, new_kv = self._block_step_prog(b)(
                        self.weights, self.kv.kv,
                        *self._scratch_block_args(b, mb))
                    self.kv.update(new_kv)
                    jax.block_until_ready(new_ids)
        # (nothing of the one-token grids runs for that family)
        step_grid = [] if self.spec.causal_block > 1 else grid
        with family("decode_grid", step_grid):
            for sp in attn_rungs:
                for b in step_grid:
                    prog = self._decode_step_prog(b, False, 0, sp=sp)
                    args = self._scratch_step_args(b, mb)
                    nxt, _logits, new_kv = prog(self.weights, self.kv.kv,
                                                *args)
                    self.kv.update(new_kv)
                    jax.block_until_ready(nxt)
        # the LoRA (bucket, rank-bucket) grid: every rung runs once over
        # all-pad rows with an all-zero-page table (exact-zero deltas — the
        # same traced shapes live mixed-tenant batches use)
        with family("lora_grid", lora_rungs):
            for rb in lora_rungs:
                for sp in attn_rungs:
                    for b in grid:
                        prog = self._decode_step_prog(b, False, 0, rb, sp=sp)
                        args = self._scratch_step_args(b, mb)
                        lops = self._scratch_lora_args(b, rb)
                        nxt, _logits, new_kv = prog(self.weights, self.kv.kv,
                                                    *args, *lops)
                        self.kv.update(new_kv)
                        jax.block_until_ready(nxt)
        # the speculative (bucket, k) verify grid: every program runs once
        # over all-scratch rows with zero proposed drafts (accept masks and
        # page writes exercise the same traced shapes live traffic uses)
        with family("verify_grid", spec_ks):
            for k in spec_ks:
                for b in grid:
                    for rb in [0] + lora_rungs:
                        for sp in attn_rungs:
                            prog = self._verify_prog(b, k, rb, sp=sp)
                            args = self._scratch_verify_args(b, k, mb)
                            lops = self._scratch_lora_args(b, rb)
                            _acc, nxt, _fl, new_kv = prog(self.weights,
                                                          self.kv.kv,
                                                          *args, *lops)
                            self.kv.update(new_kv)
                            jax.block_until_ready(nxt)
        # the KV page round-trip pair (preempt-offload / page fabric) over
        # its whole bucket grid: rare path, but a preemption DURING the
        # timed steady state must not compile — warm both ops per bucket
        # over the scratch page (content round-trips to itself; int8 pools
        # round-trip their packed values+scale-tile payload the same way)
        # (a model with state-space layers moves no pages to the host: what
        # needs that is refused, see validate_engine_build; nor does one
        # whose pages have index keys in a second pool)
        movers = self.state_config is None and not self.index
        with family("page_movers", movers or self.lora is not None):
            for b in self.page_buckets if movers else ():
                pages = self.fetch_pages([self.scratch_block] * b)
                self.put_pages(pages, [self.scratch_block] * b)
            # the adapter-pool movers over their own rank-sized bucket grid —
            # a mid-steady-state adapter fault/evict must never compile either
            if self.lora is not None:
                self.lora.pool.warm(self.config.lora.max_rank)
        # the greedy bootstrap sampler over every logits-source shape a
        # serving loop can hand it: without this, the FIRST pipeline run
        # after startup pays a small-but-real compile that the engine
        # counter cannot witness (serve_sample_rows is a module-level jit)
        sm = self.config.state_manager
        V = self.spec.vocab_size
        src_rows = {sm.num_chunk_slots, sm.max_ragged_sequence_count} | set(grid)
        # (the logits source committed to the mesh, as a program's output is:
        # see _scratch_step_args)
        with family("sampler", step_grid):
            for nr in src_rows if step_grid else ():
                logits = jax.device_put(jnp.zeros((nr, V), jnp.float32),
                                        self.topology.replicated())
                for b in grid:
                    part = serve_sample_rows(
                        logits, np.zeros((b,), np.int32), self._rng_key,
                        False, 0, 1.0)
                    # ... and its placement into every bucket's row that can
                    # hold it
                    for to in (g for g in grid if g >= b):
                        jax.block_until_ready(serve_place_rows(
                            self._zero_row(to), part,
                            np.full((b,), to, np.int32)))
        built = self.compiles - before
        # which grouped-GEMM kernel the MoE layer runs of those programs took
        # (ragged_model.moe_grouped_kernel; always-on counters in
        # tracer.totals, counted as a program is traced)
        took = {k: n - kernels_before.get(k, 0)
                for k, n in _moe_kernel_counts().items()}
        moe = f"; MoE layer runs by grouped kernel: {took}" if took else ""
        log_dist(f"engine_v2: warmup built {built} programs "
                 f"(buckets={grid}){moe}",
                 ranks=[0])
        return built

    def _scratch_step_args(self, bucket: int, max_blocks: int):
        """All-pad-row inputs for a fused decode program: every row is the
        inert scratch-page fake sequence DecodeBatch pads with. ``ids`` is
        committed to the engine's mesh, as the token row live traffic hands
        a step always is (it is the sampler's or the step before's output):
        an argument's sharding is part of what jit compiles for, so with an
        uncommitted row the warm-up built a second executable beside the one
        traffic runs, and every bucket's first live use compiled its program
        anew — 20-27 s each for a 28-layer model, in the middle of serving
        (PERF.md, PR 31)."""
        ids = jax.device_put(jnp.zeros((bucket,), jnp.int32),
                             self.topology.replicated())
        pos = np.zeros((bucket,), np.int32)
        bt = np.full((bucket, max_blocks), self.scratch_block, np.int32)
        ctx = np.ones((bucket,), np.int32)
        args = ids, pos, bt, ctx, self._rng_key, jnp.float32(1.0)
        if self.state_config is not None:   # every row at the dump slot
            args += (jnp.full((bucket,), self.scheduler._dump_slot,
                              jnp.int32),)
        return args

    def _scratch_lora_args(self, bucket: int, rb: int) -> tuple:
        """All-zero-page LoRA operands for warming a rank-bucketed program
        (every row the null adapter — exact-zero deltas)."""
        if rb == 0:
            return ()
        pt = np.full((bucket, rb), self.lora.pool.zero_page, np.int32)
        return (self.lora.pool.pool, jnp.asarray(pt))

    def _scratch_block_args(self, bucket: int, max_blocks: int):
        """All-pad-row inputs for a block-step program: every row the inert
        scratch-page fake sequence at context 0, no mask in its block, nothing
        to take. ``block_ids`` is committed to the engine's mesh, as the row
        traffic hands a pass is (the pass before's output; see
        ``_scratch_step_args``)."""
        B = self.spec.causal_block
        ids = self._zero_block(bucket)
        zeros = np.zeros((bucket,), np.int32)
        bt = np.full((bucket, max_blocks), self.scratch_block, np.int32)
        return (ids, np.zeros((bucket, B), np.int32), zeros, zeros, bt, zeros,
                np.float32(np.inf))

    def _scratch_verify_args(self, bucket: int, k: int, max_blocks: int):
        """All-pad-row inputs for a verify-step program (spec decode
        warmup): every row the inert scratch-page fake sequence, no drafts
        proposed."""
        ids = jnp.zeros((bucket,), jnp.int32)
        draft = np.zeros((bucket, k), np.int32)
        n_draft = np.zeros((bucket,), np.int32)
        pos = np.zeros((bucket,), np.int32)
        bt = np.full((bucket, max_blocks), self.scratch_block, np.int32)
        ctx = np.ones((bucket,), np.int32)
        return ids, draft, n_draft, pos, bt, ctx

    def _warm_passes(self) -> None:
        """Run the two scheduler-pass programs once on an all-padding batch
        (one scratch-page dummy row each, so the kernels see live work): the
        shapes are fully static, so this is exactly the executable every live
        put()/mixed pass reuses."""
        from deepspeed_tpu.inference.v2.ragged.ragged_batch import RaggedBatch
        sm = self.config.state_manager
        NC, Cs = sm.num_chunk_slots, sm.chunk_slot_size
        S, MB = sm.max_ragged_sequence_count, self.scheduler.max_blocks
        bs = self.kv.config.block_size

        def scratch_batch():
            b = RaggedBatch(num_slots=NC, slot_size=Cs, max_sequences=S,
                            max_blocks=MB,
                            dump_slot=self.scheduler._dump_slot)
            b.kv_dest = np.full((NC * Cs + S,), self.kv.oob_sentinel, np.int32)
            PW = NC * Cs // bs + NC
            b.page_ids = np.full((PW,), self.kv.config.num_blocks, np.int32)
            b.page_rows = np.zeros((PW,), np.int32)
            b.page_fill = np.zeros((PW,), np.int32)
            return b

        # paged/mixed pass: one decode row ticking over in the scratch page
        # — once per split rung (every rung's pass program is reachable
        # from steady state, so every one must be warm)
        b = scratch_batch()
        b.decode_block_tables[0] = self.scratch_block
        b.decode_ctx_lens[0] = 1
        b.kv_dest[NC * Cs] = self.kv.flat_write_index(self.scratch_block, 0)
        arrays = b.device_arrays()
        for pass_fn in self._pass_rungs.values():
            _, _, new_kv = pass_fn(self.weights, self.kv.kv,
                                   self._pass_arrays(arrays, PAGED_PASS_KEYS))
            # direct rebind (not .update()) so JL003 sees the donated pool's
            # reference replaced before the next pass reads it
            self.kv.kv = new_kv
        if not self.packed_prefill:
            return  # (ALiBi; a selection over more than the pass holds)
        # prefill fast path: a one-token prompt prefilling into scratch
        b = scratch_batch()
        b.chunk_ntok[0] = 1
        b.chunk_ctx_lens[0] = 1
        b.chunk_block_tables[0] = self.scratch_block
        b.row_seg[0] = 0
        b.page_ids[0] = self.scratch_block
        b.page_fill[0] = 1
        b.kv_dest[0] = self.kv.flat_write_index(self.scratch_block, 0)
        arrays = b.device_arrays()
        logits, _, new_kv = self._ensure_prefill_pass()(
            self.weights, self.kv.kv,
            self._pass_arrays(arrays, PREFILL_PASS_KEYS))
        self.kv.update(new_kv)
        jax.block_until_ready(logits)

    def _pass_arrays(self, arrays: Dict[str, Any], keys) -> Dict[str, Any]:
        """The descriptors one pass program reads (the two paths are separate
        jit programs; the other's would be dead upload weight), with the
        rows' state slots for a model with state-space layers."""
        if self.state_config is not None:
            keys = keys + STATE_PASS_KEYS
        return {k: arrays[k] for k in keys}

    @property
    def packed_prefill(self) -> bool:
        """Whether a pass of prompts from position 0 takes the packed
        program (expanded attention over the pass's own rows, no page read).
        Not under ALiBi (the packed flash kernel has no position bias), and
        not where a selection keeps fewer tokens than the pass can hold of
        one sequence: the packed program selects nothing."""
        sm = self.config.state_manager
        # (nor under the block rule of generation by diffusion over blocks,
        # which only the paged chunk kernel knows: prompts are a small share
        # of such a model's passes)
        return not self.spec.alibi and self.spec.causal_block == 1 and (
            not self.index or sm.num_chunk_slots * sm.chunk_slot_size
            <= self.index["topk"])

    def _ensure_prefill_pass(self):
        """Build (once) the packed pure-prefill fast-path program — shared by
        the live pass router and warmup so both compile the identical jit."""
        if self._pass_prefill is None:
            self._pass_prefill = _ThreeResults(_program(
                build_prefill_forward(self.spec, mesh=self.topology.mesh,
                                      tp=self.config.tensor_parallel),
                "serve_prefill_packed", donate_argnums=(1,)))
            self.compiles += 1
        return self._pass_prefill

    def count_kv_rows(self, n: int, rows: float, singles: float = 0.0) -> None:
        """``rows`` live rows in runs of ``n`` and ``singles`` rows of their
        own go to the pages in the program being dispatched: counted as the
        program writes them (``kv_write_run_group``: the same question)."""
        if self.spec.mla is not None or self.scheduler.pageless:
            return
        if kv_write_run_group(self.kv.kv, n,
                              self.config.tensor_parallel) is None:
            rows, singles = 0.0, singles + rows
        _tracer.bump("serve/kv_write/run_rows", float(rows))
        _tracer.bump("serve/kv_write/single_rows", float(singles))

    def _run_pass(self):
        """Schedule and dispatch one pass; returns its batch (None when
        nothing was pending) so that a caller can say what the pass held."""
        batch = self.scheduler.schedule_pass()
        if batch is None:
            return None
        if self.state_config is not None:
            # always-on (tracer.totals; a capture reports what each gained):
            # the prompt rows and the chunk slots that hold some, pass by
            # pass — what the passes' state kernels had to do, for a reader
            # that holds their device time against it
            _tracer.bump("serve/pass/passes")
            _tracer.bump("serve/pass/prompt_tokens",
                         float(batch.chunk_ntok.sum()))
            _tracer.bump("serve/pass/live_slots",
                         float((batch.chunk_ntok > 0).sum()))
        arrays = batch.device_arrays()
        # each jitted pass receives only the keys it reads (the two paths are
        # separate jit functions; shipping the other path's descriptors is
        # pure upload waste over a slow link)
        # prefill-from-zero passes need no paged reads: packed-flash fast path
        # (build_prefill_forward) — measured 3-4x wave throughput on v5e-1.
        # ALiBi models take the paged chunk path (the packed flash kernel
        # has no per-head position bias; the paged kernels do)
        if batch.pure_prefill and self.packed_prefill:
            pass_fn = self._ensure_prefill_pass()
            arrays = self._pass_arrays(arrays, PREFILL_PASS_KEYS)
        else:
            # the paged pass of this step's split rung (rung 1: self._pass)
            _count_selecting_pass(self.index, batch)
            self.count_kv_rows(batch.slot_size, batch.chunk_ntok.sum(),
                               len(batch.decode_uids))
            pass_fn = self._pass_rungs.get(self._attn_rung(), self._pass)
            arrays = self._pass_arrays(arrays, PAGED_PASS_KEYS)
        chunk_logits, decode_logits, new_kv = pass_fn(
            self.weights, self.kv.kv, arrays)
        self.kv.update(new_kv)
        finished = self.scheduler.complete_pass(batch)
        for uid in finished:
            if uid in batch.slot_uid:
                # a prompt may span several slots; its next-token logits sit
                # in the LAST slot it filled
                row = len(batch.slot_uid) - 1 - batch.slot_uid[::-1].index(uid)
                self._last_ref[uid] = (chunk_logits, row)
            else:
                self._last_ref[uid] = (decode_logits,
                                       batch.decode_uids.index(uid))
        return batch

    def query(self, uid: int, max_request_tokens: int) -> Tuple[int, int]:
        return self.scheduler.query(uid, max_request_tokens)

    def can_schedule(self, uids: Sequence[int], lengths: Sequence[int]) -> bool:
        return self.scheduler.can_schedule([int(u) for u in uids], list(lengths))

    def flush(self, uids: Sequence[int]) -> None:
        for uid in uids:
            self.scheduler.flush(int(uid))
            self._last_logits.pop(int(uid), None)
            self._last_ref.pop(int(uid), None)

    @property
    def free_blocks(self) -> int:
        return self.allocator.free_blocks

    # ------------------------------------------------------------------ #
    # KV page host round-trip (serving preempt-offload; serving/kv_offload)
    # ------------------------------------------------------------------ #

    def _page_programs(self):
        """(gather, scatter) jits over the whole pool with a TRACED block-id
        VECTOR, padded to a pow2 bucket: offloading a victim's whole tail is
        ONE dispatch + ONE host transfer (and one scatter back on restore),
        not one per page, and the bucket keying means arbitrary tail lengths
        reuse ~log2 executables. Pad slots point at the scratch page — reads
        of it are discarded, writes to it land on the one page no sequence
        can own. Scatter donates the pool (XLA aliases it in HBM, the same
        discipline as the pass programs). The tree_map'd bodies carry an
        int8 pool's (values, scale-tiles) tuple leaf-for-leaf — BOTH leaves
        have the page dim at axis 1, so one dispatch moves a page's bytes
        AND its scale tile together (the scale-tile fabric invariant every
        page mover keeps; docs/SERVING.md "Quantized KV")."""
        if self._page_progs is None:
            @jax.jit
            def serve_kv_page_gather(kv, blocks):
                return jax.tree_util.tree_map(
                    lambda a: _gather_pages(a, blocks), kv)

            @functools.partial(jax.jit, donate_argnums=(0,))
            def serve_kv_page_scatter(kv, pages, blocks):
                return jax.tree_util.tree_map(
                    lambda a, p: a.at[:, blocks].set(jnp.moveaxis(p, 0, 1)),
                    kv, pages)

            self._page_progs = (serve_kv_page_gather, serve_kv_page_scatter)
        return self._page_progs

    @property
    def page_payload_spec(self) -> Tuple[Tuple[int, ...], Any]:
        """(shape, dtype) of ONE page as it travels the host fabric
        (offload buffers, export/import handoffs, failover salvage). Plain
        pools ship the page array itself ([L, 2, H_kv, bs, D], pool
        dtype); int8 pools ship ONE flat byte row per page — the int8
        values followed by the f32 scale tile (``bytes_per_block`` bytes)
        — so every host-side consumer keeps treating a page as one opaque
        copyable slice."""
        cfg = self.kv.config
        if cfg.quantized:
            return (cfg.bytes_per_block(),), np.uint8
        # jnp.dtype, not a numpy-name round trip: bf16 pools carry the
        # ml_dtypes bfloat16 numpy extension dtype
        return cfg.page_shape, jnp.dtype(cfg.dtype)

    def _pack_pages(self, vals: np.ndarray, scales: np.ndarray) -> np.ndarray:
        """(int8 values [n, L, 2, Hkv, bs, D], f32 scale tiles
        [n, L, R8, 128]) -> packed [n, bytes_per_block] uint8 rows."""
        n = vals.shape[0]
        return np.concatenate(
            [np.ascontiguousarray(vals).reshape(n, -1).view(np.uint8),
             np.ascontiguousarray(scales).reshape(n, -1).view(np.uint8)],
            axis=1)

    def _unpack_pages(self, pages: np.ndarray):
        """Inverse of :meth:`_pack_pages`: packed uint8 rows -> (values,
        scale tiles) ready for the tuple-pool scatter."""
        cfg = self.kv.config
        n = pages.shape[0]
        L, Hkv, bs, D = (cfg.num_layers, cfg.num_kv_heads, cfg.block_size,
                         cfg.head_dim)
        vbytes = L * 2 * Hkv * bs * D
        vals = np.ascontiguousarray(pages[:, :vbytes]).view(np.int8)
        scales = np.ascontiguousarray(pages[:, vbytes:]).view(np.float32)
        from deepspeed_tpu.ops.pallas.paged_attention import (
            kv_scale_tiles_shape)
        _, r8, lanes = kv_scale_tiles_shape(1, Hkv, bs)
        return (vals.reshape(n, L, 2, Hkv, bs, D),
                scales.reshape(n, L, r8, lanes))

    def _page_bucket(self, kind: str, n: int) -> int:
        """Pad count for a page-op batch; counts the first use of each
        (op, bucket) signature as a compile (the page jits re-specialize
        per bucket, unlike the one-signature pass programs)."""
        b = next_pow2(n)
        key = (kind, b)
        if key not in self._page_buckets:
            self._page_buckets.add(key)
            self.compiles += 1
        return b

    @property
    def page_buckets(self) -> List[int]:
        """The page-op bucket grid warmup pre-compiles: pow2 up to a whole
        sequence's block-table length (the largest possible private tail)."""
        top = next_pow2(self.scheduler.max_blocks)
        return [1 << i for i in range(top.bit_length())]

    def fetch_pages(self, blocks: Sequence[int]) -> np.ndarray:
        """KV pages fetched to host in one bucketed gather — the offload
        half of the preempt-offload round trip (serving/kv_offload.py) and
        the export half of the page fabric. Plain pools return
        ``[n, L, 2, H_kv, block_size, D]``; int8 pools return packed
        ``[n, bytes_per_block]`` uint8 rows (values + scale tile per page —
        :attr:`page_payload_spec`). Rare path (runs only when admission
        preempts a victim or a handoff exports), drained through the
        policed ``fetch_to_host`` like every other v2 fetch."""
        ids = [int(b) for b in blocks]
        _maybe_fail("serve.kv_fetch")      # chaos site: page-fabric gather
        gather, _ = self._page_programs()
        bucket = self._page_bucket("gather", len(ids))
        idx = np.full((bucket,), self.scratch_block, np.int32)
        idx[:len(ids)] = ids
        res = gather(self.kv.kv, jnp.asarray(idx))
        if self.kv.config.quantized:
            # slice the bucket's scratch pad rows off BEFORE packing —
            # _pack_pages concatenates, and a pow2 bucket can be ~2x n
            vals, scales = res
            return self._pack_pages(fetch_to_host(vals)[:len(ids)],
                                    fetch_to_host(scales)[:len(ids)])
        return fetch_to_host(res)[:len(ids)]

    def put_pages(self, pages: np.ndarray, blocks: Sequence[int]) -> None:
        """Scatter host pages ``[n, ...]`` back into pool slots ``blocks``
        (one bucketed dispatch) — the restore half. Byte-exact with
        ``fetch_pages`` (same dtype both ways; pinned by
        tests/unit/test_serving_frontend.py). Pad slots write zeros into the
        inert scratch page."""
        ids = [int(b) for b in blocks]
        if not ids:
            return
        _maybe_fail("serve.kv_put")        # chaos site: page-fabric scatter
        _, scatter = self._page_programs()
        bucket = self._page_bucket("scatter", len(ids))
        idx = np.full((bucket,), self.scratch_block, np.int32)
        idx[:len(ids)] = ids
        if bucket != len(ids):
            pages = np.concatenate(
                [pages, np.zeros((bucket - len(ids),) + pages.shape[1:],
                                 pages.dtype)])
        if self.kv.config.quantized:
            vals, scales = self._unpack_pages(np.asarray(pages, np.uint8))
            payload = (jnp.asarray(vals), jnp.asarray(scales))
        else:
            payload = jnp.asarray(pages, self.kv.kv.dtype)
        # direct rebind (not kv.update) so JL003 sees the donated pool's
        # reference replaced before the next pass reads it
        self.kv.kv = scatter(self.kv.kv, payload, jnp.asarray(idx))

    def _refuse_beside_index(self, what: str) -> None:
        if self.index:
            raise NotImplementedError(INDEX_POOL_MSG.format(
                what=what + " (handing a sequence's pages to another engine)"))

    def _refuse_beside_blocks(self, what: str) -> None:
        if self.spec.causal_block > 1:
            raise NotImplementedError(BLOCK_DIFFUSION_MSG.format(
                what=what + " (handing a sequence's pages to another engine: "
                "its open block is the pipeline's)"))

    def export_kv(self, uid: int) -> Tuple[np.ndarray, np.ndarray]:
        """``(pages, logits)``: the whole logical KV of a fully-prefilled
        sequence fetched to host in one bucketed gather, plus its last
        logits row — then the sequence is flushed here. The export half of a
        cross-engine prefill->decode handoff (serving/cluster.py): the pair
        is exactly what preempt-offload parks per victim, so ``import_kv``
        on ANOTHER engine restores it the same way preemption restore does
        (pages scattered into fresh pool ids, ``_last_logits`` re-seeded for
        a byte-identical bootstrap sample). With the prefix cache on, the
        flush returns this sequence's pages to the LOCAL radix tree — the
        prefill replica stays warm for the next matching prompt."""
        uid = int(uid)
        self._refuse_beside_index("export_kv")
        self._refuse_beside_blocks("export_kv")
        if self.state_config is not None:
            raise NotImplementedError(STATE_SNAPSHOT_MSG.format(
                what="export_kv (handing a sequence's pages to another "
                "engine)"))
        seq = self.scheduler.seqs.get(uid)
        if seq is None:
            raise KeyError(f"sequence {uid} is not tracked")
        if len(seq.pending):
            raise RuntimeError(f"sequence {uid} still has pending prefill "
                               "tokens — export_kv needs a drained sequence")
        self._materialize([uid])
        logits = self._last_logits.pop(uid)
        pages = self.fetch_pages(list(seq.blocks))
        self.flush([uid])
        return pages, logits

    def import_kv(self, uid: int, tokens: Sequence[int], pages: np.ndarray,
                  logits: np.ndarray) -> List[int]:
        """Adopt a sequence whose KV ``pages`` were computed on ANOTHER
        engine (independent pool, different block ids): allocate fresh pages
        (``scheduler.adopt_sequence``), scatter the content in with the
        bucketed ``put_pages`` (byte-exact — the fabric contract
        tests/unit/test_serving_router.py pins below the router), and
        re-seed the bootstrap logits row exactly like preemption restore.
        The sequence is then in steady decode state: ``decode_pipeline`` can
        admit it directly. Returns the allocated block ids."""
        uid = int(uid)
        self._refuse_beside_index("import_kv")
        self._refuse_beside_blocks("import_kv")
        if self.state_config is not None:
            raise NotImplementedError(STATE_SNAPSHOT_MSG.format(
                what="import_kv (adopting a sequence whose pages were "
                "computed on another engine)"))
        page_shape, page_dtype = self.page_payload_spec
        pages = np.asarray(pages, page_dtype)
        if tuple(pages.shape[1:]) != page_shape:
            raise ValueError(
                f"handoff page shape {tuple(pages.shape[1:])} does not match "
                f"this engine's KV page layout {page_shape} — cross-engine "
                "handoff needs an identical model + block_size")
        ids = self.scheduler.adopt_sequence(uid, tokens, len(pages))
        if ids:
            self.put_pages(pages, ids)
        self._last_logits[uid] = logits
        return ids

    def fetch_page(self, block: int) -> np.ndarray:
        """One KV page (``page_payload_spec``-shaped) to host."""
        return self.fetch_pages([block])[0]

    def put_page(self, page: np.ndarray, block: int) -> None:
        """Scatter one host page back into pool slot ``block``."""
        self.put_pages(page[None], [block])

    def kv_window_dead_tokens(self) -> Tuple[int, int]:
        """``(dead, resident)`` over the live sequences, in tokens x layers:
        ``resident`` counts the tokens whose K/V each layer's pages hold,
        ``dead`` those of them that a windowed layer holds below
        ``ctx - window`` — no query will read them again. It is what one
        page kind costs a model that mixes windowed and full layers
        (``spec.layer_kinds``): every layer keeps whole-context pages. Under
        the page ring (every layer windowed) a sequence holds at most the
        ring, and what is dead is the ring's slack. A gauge for a sender to
        sample between requests; it reads the scheduler's table and takes no
        lock."""
        ring = self.scheduler.ring_pages
        cap = None if ring is None else ring * self.kv.config.block_size
        dead = resident = 0
        for seq in list(self.scheduler.seqs.values()):
            held = seq.seen_tokens if cap is None else min(seq.seen_tokens,
                                                           cap)
            resident += self.kv.config.num_layers * held
            for window, n in self._windowed_layers:
                dead += n * max(0, held - window)
        return dead, resident

    def serving_frontend(self, config=None, uid_base: int = 1 << 20):
        """The persistent SLO-aware serving frontend over this engine
        (``serving/frontend.py``): asyncio-facing ``submit() -> token
        stream``, multi-tenant admission with priority classes, and
        KV offload-preemption. ``config`` overrides ``self.config.serving``;
        ``uid_base`` keeps a cluster's frontends in disjoint uid spaces
        (``serving/cluster.py``)."""
        from deepspeed_tpu.inference.v2.serving import ServingFrontend
        return ServingFrontend(self, config=config, uid_base=uid_base)

    def weight_bridge(self, train_engine, **kwargs):
        """A :class:`~deepspeed_tpu.runtime.colocated.WeightBridge` from a
        colocated training engine into this engine's weight layout — one
        jitted device-resident reshard per policy update, swapped in via
        ``swap_weights`` with zero recompiles (docs/SERVING.md "Colocated
        rollout")."""
        from deepspeed_tpu.runtime.colocated import WeightBridge
        return WeightBridge(train_engine, self, **kwargs)

    # ------------------------------------------------------------------ #
    # prefix-cache support
    # ------------------------------------------------------------------ #

    def write_monitor_events(self, monitor, step: int = 0) -> None:
        """Emit the serving counters through a ``monitor/`` backend
        (``MonitorMaster.write_events`` shape): prefix-cache stats when the
        cache is on, and the decode pipeline's per-step timing/transfer
        breakdown (dispatch / host-build / fetch-drain / bubble, fetch bytes)
        once any ``DecodePipeline`` has run; and the process's ``setup/*``
        and ``compile/*`` totals (docs/OBSERVABILITY.md, "Set-up and
        compiles")."""
        monitor.write_events(_tracer.setup_events(step))
        if self.prefix_cache is not None:
            monitor.write_events(self.prefix_cache.stats.events(step))
        if self.pipeline_stats.steps:
            monitor.write_events(self.pipeline_stats.events(step))
        if self.spec_stats.steps:
            monitor.write_events(self.spec_stats.events(step))
        if self.attn_stats.selects:
            monitor.write_events(self.attn_stats.events(step))
        if self.lora is not None and self.lora.stats.adapters:
            monitor.write_events(self.lora.stats.events(step))

    # ------------------------------------------------------------------ #
    # continuous-batching generation loop (parity role: MII serving loop)
    # ------------------------------------------------------------------ #

    def _sample(self, logits: np.ndarray, do_sample: bool, temperature: float,
                top_k: int) -> int:
        if not do_sample:
            return int(np.argmax(logits))
        z = logits.astype(np.float64) / max(temperature, 1e-6)
        if top_k > 0:
            kth = np.sort(z)[-top_k]
            z = np.where(z < kth, -np.inf, z)
        z = z - z.max()
        p = np.exp(z) / np.exp(z).sum()
        return int(self._rng.choice(len(p), p=p))

    def generate(self,
                 prompts: Sequence[Sequence[int]],
                 max_new_tokens: int = 32,
                 do_sample: bool = False,
                 temperature: float = 1.0,
                 top_k: int = 0,
                 eos_token_id: Optional[int] = None) -> List[List[int]]:
        """Generate continuations for a batch of prompts with continuous
        batching: all sequences advance together; finished ones are flushed
        and their blocks recycled. Returns full token lists (prompt +
        generation).

        Steady-state decode runs through ``decode_pipeline`` — the SAME
        gated hot path the serving frontend drives (fused on-device
        sampling, bucketed descriptors, one-step-late drain; with
        ``spec_decode.enabled`` and greedy requests, the draft-and-verify
        ``SpecDecodePipeline``) — in slice-sized runs, retiring EOS'd (or
        budget-complete) sequences at each drained step. Greedy streams are
        byte-identical to the old per-token ``sample_next``/``put`` loop,
        spec on or off (pinned by tests/unit/test_decode_pipeline.py and
        test_spec_decode.py); sampled streams are valid draws but consume
        RNG per fused step, so they differ from the old loop's draws (and
        depend on the bucket: ``DecodePipeline``'s docstring)."""
        # fresh uid namespace: never collide with caller-owned put() sequences
        uids: List[int] = []
        nxt = 0
        while len(uids) < len(prompts):
            if nxt not in self.scheduler.seqs:
                uids.append(nxt)
            nxt += 1
        idx_of = {u: i for i, u in enumerate(uids)}
        outs: List[List[int]] = [list(map(int, p)) for p in prompts]
        if not self.can_schedule(uids, [len(p) for p in prompts]):
            raise RuntimeError("cannot schedule: insufficient KV blocks or "
                               "sequence slots")
        by_blocks = self.spec.causal_block > 1
        if by_blocks:       # (refuses sampling before anything is scheduled)
            pipe = self.decode_pipeline((), do_sample=do_sample)
        self._put_nofetch(uids, [np.asarray(p, np.int32) for p in prompts])
        if by_blocks:
            # (the block pipeline cuts a row's last block at its budget)
            pipe.admit(uids, budgets=[max_new_tokens] * len(uids))
        else:
            pipe = self.decode_pipeline(uids, do_sample=do_sample,
                                        temperature=temperature, top_k=top_k)
        is_spec = getattr(pipe, "spec", False)
        live = set(uids)
        budget = {u: max_new_tokens for u in uids}

        def on_tokens(j, run_uids, row):
            stop = []
            for i, u in enumerate(run_uids):
                if u not in live:
                    continue        # retired earlier this run: padding noise
                # spec steps emit a variable-length token batch per row;
                # plain steps one token. Tokens past the budget (a spec
                # step's in-step overshoot) are discarded — their KV is
                # stale past the flush below, never read.
                for t in (row[i] if pipe.token_batches else row[i:i + 1]):
                    t = int(t)
                    outs[idx_of[u]].append(t)
                    budget[u] -= 1
                    done = budget[u] <= 0 or (eos_token_id is not None
                                              and t == eos_token_id)
                    if done:
                        live.discard(u)
                        stop.append(u)
                        break
            return stop

        # slice-sized runs bound the post-retirement overshoot (the device
        # finishes each in-flight burst; see DecodePipeline.run) to one
        # slice; a spec step can emit up to k+1 tokens, so its slice is
        # correspondingly shorter
        CHUNK = 32
        K1 = self.config.spec_decode.k + 1
        steps = max(1, CHUNK // K1) if is_spec else CHUNK
        if max_new_tokens <= 0:
            self.flush(pipe.uids)
            return outs
        max_ctx = self.config.state_manager.max_context
        while pipe.uids:
            if is_spec:
                # clamp the verify-run length to the remaining budget AND
                # the rows' max_context headroom (each verify step reserves
                # k+1 tokens up front); when even ONE verify step no longer
                # fits — speculation intrinsically needs k+1 write slots —
                # degrade the tail to the plain pipeline (bit-identical to
                # a verify step's row 0) instead of crashing the stream
                rem = max(budget[u] for u in pipe.uids)
                cap = min((max_ctx - self.scheduler.seqs[u].seen_tokens - 1)
                          // K1 for u in pipe.uids)
                n = min(steps, -(-rem // K1), cap)
                if n < 1:
                    uids_left = list(pipe.uids)
                    pipe.retire(uids_left)
                    from deepspeed_tpu.inference.v2.pipeline import (
                        DecodePipeline)
                    pipe = DecodePipeline(self, uids_left)
                    is_spec = False
                    continue
            elif by_blocks:
                # passes, not tokens: clamp the run to the rows' max_context
                # headroom (a run reserves ``block_reserve_tokens`` up front)
                n = steps
                room = min(max_ctx - self.scheduler.seqs[u].seen_tokens
                           for u in pipe.uids)
                while n >= 1 and self.block_reserve_tokens(n) > room:
                    n -= 1
                if n < 1:
                    raise RuntimeError(
                        "max_context leaves no room for a block step's "
                        "reservation past the longest live sequence")
            else:
                n = min(steps, max(budget[u] for u in pipe.uids))
            before = set(pipe.uids)
            pipe.run(n, on_tokens=on_tokens)
            for u in before - set(pipe.uids):
                self.flush([u])     # retired mid-run: recycle KV blocks now
        self.flush(pipe.uids)
        return outs


def _gather_pages(a, blocks):
    """Pages ``blocks`` of pool leaf ``a`` (page axis 1), page-major on the
    way out: host slices ``[i]`` are contiguous. A loop of one dynamic slice
    a page, for every layout: as ONE gather of whole latent pages (rows 640
    wide) the TPU compiler staged the whole pool — 4.3 GiB of temporaries at
    700 pages, of which the engine's warm-up died on the chip (PR 33) —
    while the loop holds next to nothing for latent and K/V pools alike and
    compiles in a tenth of a second whatever the count
    (``tests/unit/test_chip_compile.py``)."""
    return jax.lax.map(lambda b: jax.lax.dynamic_index_in_dim(
        a, b, axis=1, keepdims=False), blocks)


def _guess_family(model) -> str:
    fam = getattr(getattr(model, "config", None), "family", None)
    if fam:
        return fam
    name = type(model).__name__.lower()
    for fam in ("mixtral", "mistral", "llama", "gpt2", "opt", "falcon", "phi"):
        if fam in name:
            return fam
    raise ValueError(f"cannot infer model family from {type(model).__name__}; "
                     f"pass family=")


def _count_selecting_pass(index, batch) -> None:
    """The paged passes of a model that selects inside latent attention
    (``index``: its ``spec.mla["index"]``), always on in ``tracer.totals``:
    ``serve/mla/paged_passes``, and of them ``serve/mla/expanded_passes`` —
    those whose chunk slots hold ONE sequence in two or more slots, whose
    rows the program attends expanded. The program decides from the arrays
    it is handed (``ragged_mla.one_sequence``); this is the scheduler's side
    of the same rule (a sequence's slots are consecutive, over its table)."""
    if index:
        _tracer.bump("serve/mla/paged_passes")
        _tracer.bump("serve/mla/expanded_passes", float(
            len(batch.chunk_uids) == 1 and (batch.chunk_ntok > 0).sum() >= 2))
