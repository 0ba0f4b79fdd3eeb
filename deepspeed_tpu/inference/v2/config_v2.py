"""Inference v2 engine configuration.

Parity: ``RaggedInferenceEngineConfig`` (reference ``inference/v2/config_v2.py``)
with its ``DSStateManagerConfig`` (``ragged/manager_configs.py``): tracked-sequence
capacity, ragged-batch token budget, and KV memory sizing — plus the TPU additions
(mesh/tp size, page block size).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Optional

import jax.numpy as jnp


@dataclass
class DSStateManagerConfig:
    """Parity: ``DSStateManagerConfig`` (manager_configs.py)."""
    max_tracked_sequences: int = 64          # sequences with live KV state
    max_ragged_sequence_count: int = 32      # decode rows per pass
    max_ragged_batch_size: int = 768         # token budget per pass (chunks + decode)
    max_context: int = 8192                  # per-sequence KV capacity
    prefill_chunk_size: int = 128            # tokens per prompt-chunk slot

    @property
    def chunk_budget(self) -> int:
        return self.max_ragged_batch_size - self.max_ragged_sequence_count

    @property
    def chunk_slot_size(self) -> int:
        """Static tokens per slot. Stays exactly ``prefill_chunk_size`` (a
        user-aligned size, 128 by default): dividing the budget evenly
        instead gives sizes like 147 whose q-block collapses to 1-row MXU
        tiles in the batched prefill kernel."""
        return min(self.prefill_chunk_size, max(1, self.chunk_budget))

    @property
    def num_chunk_slots(self) -> int:
        """Prompt-chunk slots per pass. Multi-slot is the prefill throughput
        lever: one chunk per pass serialises N prompts on N pass dispatches
        (host descriptor build + a dispatch each). The count rounds the
        budget to the NEAREST slot multiple, so realized chunk capacity is
        within half a slot of ``chunk_budget`` — flooring stranded up to a
        slot's worth (96 of 736 tokens at the defaults)."""
        cs = self.chunk_slot_size
        return max(1, (self.chunk_budget + cs // 2) // cs)


@dataclass
class KVCacheSizingConfig:
    block_size: int = 128
    num_blocks: Optional[int] = None         # explicit pool size
    memory_fraction: float = 0.8             # else: fraction of free HBM


@dataclass
class QuantizationConfig:
    """Weight-only quantization for the serving path (parity: the reference's
    v2 quantization config, ``inference/v2/config_v2.py`` QuantizationConfig,
    backing the CUTLASS fp16 x int8 mixed GEMM). ``weight_bits=8`` stores the
    streamed weight matrices int8 in HBM with per-output-column scales and
    dequantizes inside the dot (see ``ragged_model._mm``). None = off."""
    weight_bits: Optional[int] = None

    def __post_init__(self):
        # 4 = PACKED int4 (two per byte along K, 4x under bf16 at rest —
        # reference csrc/quantization/quantize_intX.cu); 8 = int8
        if self.weight_bits not in (None, 4, 8):
            raise ValueError("quantization.weight_bits must be None, 4 or 8, "
                             f"got {self.weight_bits!r}")


@dataclass
class KVQuantConfig:
    """int8 KV pages (parity role: the blocked-flash KV stream +
    ZeRO-Inference's KV quantization strategy, reference README.md:23).
    Pages store int8 values with per-token-head f32 scales (1.6% overhead at
    head_dim 128); the paged kernels dequantize in-flight, halving the
    page-read stream that bounds large-batch GQA decode. A first-class pool
    layout for the WHOLE v2 serving stack: composes with the prefix cache
    (COW copies the scale tile with the page), spec decode (the verify step
    quantizes-on-write), preempt-offload and the cross-engine page fabric
    (packed value+scale-tile payloads, byte-exact round trips) — see
    docs/SERVING.md "Quantized KV" for the layout, the write semantics and
    the byte-vs-rtol gate taxonomy. Requires tp == 1 (the one surviving
    refusal, raised at engine build), head_dim % 128 == 0 and
    num_kv_heads * block_size % 128 == 0."""
    enabled: bool = False
    bits: int = 8

    def __post_init__(self):
        if self.bits != 8:
            raise ValueError(f"kv_quant.bits must be 8, got {self.bits!r}")


@dataclass
class PrefixCacheConfig:
    """Automatic prefix caching (parity role: SGLang RadixAttention / vLLM
    automatic-prefix-caching; see ``inference/v2/prefix_cache.py``). When
    enabled, completed sequences' KV pages are retained in a radix tree keyed
    on token blocks and new prompts reuse every cached whole-block prefix —
    zero prefill is scheduled for the matched span. Off by default: sharing is
    a semantic no-op (outputs stay logit-exact) but the tree holds pool blocks
    that eviction must reclaim under pressure.

    ``max_cached_blocks`` caps how many pool blocks the tree may retain
    (None = bounded only by the pool itself; idle cached blocks are evicted
    LRU whenever an allocation would otherwise fail). ``eviction`` names the
    policy; only ``"lru"`` is implemented."""
    enabled: bool = False
    max_cached_blocks: Optional[int] = None
    eviction: str = "lru"

    def __post_init__(self):
        if self.eviction != "lru":
            raise ValueError(
                f"prefix_cache.eviction must be 'lru', got {self.eviction!r}")
        if self.max_cached_blocks is not None and self.max_cached_blocks < 1:
            raise ValueError("prefix_cache.max_cached_blocks must be >= 1 "
                             f"(or None), got {self.max_cached_blocks}")


@dataclass
class CompileConfig:
    """Persistent compile cache + AOT warmup for the serving hot path.

    Steady-state decode cost on TPU is bounded below by recompiles: every new
    (bucketed) batch shape pays a multi-second XLA compile, and a cold engine
    pays it for every program on its first wave of traffic. Engine
    construction turns JAX's persistent compilation cache on through
    ``utils/compile_cache.py`` — the directory is ``JAX_COMPILATION_CACHE_DIR``
    where that is set and ``<checkout>/.jax_cache`` otherwise, never a field
    of this config — and optionally AOT-warms the whole decode bucket grid at
    startup so serving traffic never observes a compile.

    ``min_compile_time_secs``: the least compile time JAX persists; ``None``
    (default) leaves the process's threshold alone.

    ``warmup``: pre-compile the serving program set at engine construction —
    the ragged paged pass, the prefill fast path, and the fused decode-step
    program for every bucket in ``warmup_buckets``. Warmup runs
    each program once over the engine's scratch KV page, so with a persistent
    cache a *second* engine start skips compilation entirely.

    ``warmup_buckets``: decode-row buckets to pre-compile. ``None`` = the
    full power-of-two grid ``1, 2, 4, ..., next_pow2(max_ragged_sequence_
    count)`` — the whole reachable bucket set, since admission/retirement
    rounds every live count to this grid.
    """
    min_compile_time_secs: Optional[float] = None
    warmup: bool = False
    warmup_buckets: Optional[Any] = None     # list of ints

    def __post_init__(self):
        if self.warmup_buckets is not None:
            if any(not isinstance(b, int) or b < 1
                   for b in self.warmup_buckets):
                raise ValueError("compile.warmup_buckets must be ints >= 1, "
                                 f"got {self.warmup_buckets!r}")
            # normalize to the pow2 grid the live path actually uses — the
            # same rounding engine.warmup() applies to explicit buckets, so
            # both entry points accept the same inputs
            from deepspeed_tpu.utils.caching import next_pow2
            self.warmup_buckets = sorted({next_pow2(b)
                                          for b in self.warmup_buckets})


@dataclass
class SpecDecodeConfig:
    """Speculative decoding for the steady-state decode path
    (``inference/v2/spec/``; docs/SERVING.md "Speculative decoding").

    When enabled, ``engine.decode_pipeline`` returns a
    ``SpecDecodePipeline``: each pipeline step proposes up to ``k`` draft
    tokens per sequence from its own token history (prompt-lookup / n-gram
    matching — no second model), verifies them in ONE ragged forward
    (``ragged_model.build_verify_step``), and emits the accepted prefix plus
    one greedy bonus token. Greedy speculation is exactness-preserving:
    token streams are byte-identical to the spec-off pipeline
    (``tests/unit/test_spec_decode.py::test_spec_stream_matches_plain_pipeline``).

    ``k``: max draft tokens verified per step — the top rung of the
    (bucket, k) warmup grid. Prefer ``k + 1`` a POWER OF TWO (3, 7, 15):
    the chunk kernel's q-block must divide k+1, and an odd k+1 collapses
    it to 1-row blocks with (k+1)x the grid steps (a misaligned k warns
    below). ``min_match`` /
    ``max_ngram``: the proposer matches the longest history suffix of
    length in [min_match, max_ngram] and proposes its continuation; no
    match proposes nothing and the step degenerates to plain decode for
    that row. ``adaptive``: per-sequence MIMD k backoff — any reject drops
    a row's draft budget to accepted + 1 (down to a probe of 1, so
    re-entering a repetitive span is detected), full accepts double it
    back toward k; a traced per-row operand, never a recompile.

    Greedy-only: sampled pipelines bypass speculation with a one-time
    warning. Not wired for sliding-window models (the page ring aliases the
    K+1-ahead write span); int8 KV pages compose — the verify step
    quantizes-on-write like the decode step (docs/SERVING.md
    "Quantized KV")."""
    enabled: bool = False
    k: int = 3
    min_match: int = 2
    max_ngram: int = 4
    adaptive: bool = True

    def __post_init__(self):
        if self.k < 1:
            raise ValueError(f"spec_decode.k must be >= 1, got {self.k}")
        if self.enabled and (self.k + 1) & self.k != 0:
            import warnings
            warnings.warn(
                f"spec_decode.k={self.k}: k + 1 is not a power of two, so "
                "the verify kernel's q-block collapses to 1-row blocks "
                "(measured ~2x slower) — prefer k in 3, 7, 15, ...",
                stacklevel=3)
        if self.min_match < 1:
            raise ValueError("spec_decode.min_match must be >= 1, got "
                             f"{self.min_match}")
        if self.max_ngram < self.min_match:
            raise ValueError(
                f"spec_decode.max_ngram ({self.max_ngram}) must be >= "
                f"min_match ({self.min_match})")


@dataclass
class LoraConfig:
    """Multi-tenant LoRA serving (``inference/v2/lora/``; docs/SERVING.md
    "Multi-tenant LoRA"). One base model plus per-tenant low-rank adapters —
    the S-LoRA/Punica pattern — served from a paged adapter-weight pool
    managed exactly like the KV pool: fixed-size weight pages (one page per
    rank slice), refcounted per in-flight request, LRU-evicted to pinned
    host buffers under pool pressure and restored byte-exactly.

    ``pool_pages``: device pages in the adapter pool. One adapter of rank r
    occupies r pages, so the pool holds ``pool_pages / mean_rank`` adapters
    resident; registering more than fit is the POINT — cold adapters park on
    host and fault back in on demand. Must hold at least one ``max_rank``
    adapter.

    ``max_rank``: the largest adapter rank this engine accepts. Ranks are
    bucketed to powers of two for dispatch: the decode/verify program grid
    is keyed by (bucket, rank-bucket) and ``warmup`` pre-compiles every
    rung, so adapter churn never compiles. The grouped-matmul rank operand
    runs at ``next_pow2(max registered rank)``; smaller adapters pad their
    page tables with the pool's zero page (an exact zero contribution).

    ``targets``: which projections carry deltas — a subset of
    ``("q", "k", "v", "o")``. Deltas apply inside the DECODE and VERIFY
    programs (the serving hot path this subsystem exists for); prefill
    passes run the base model (docs/SERVING.md "Multi-tenant LoRA" states
    the resulting decode-scope semantics).

    ``swap_buffers`` caps the pinned host bounce-buffer pool
    (``runtime/swap_tensor/buffer_pool.py``) evicted adapters park in."""
    enabled: bool = False
    pool_pages: int = 64
    max_rank: int = 16
    targets: Any = ("q", "v")
    swap_buffers: int = 16

    def __post_init__(self):
        self.targets = tuple(self.targets)
        bad = [t for t in self.targets if t not in ("q", "k", "v", "o")]
        if bad:
            raise ValueError(f"lora.targets must be a subset of "
                             f"('q', 'k', 'v', 'o'), got {self.targets!r}")
        if not self.targets:
            raise ValueError("lora.targets must name at least one projection")
        if self.max_rank < 1:
            raise ValueError(f"lora.max_rank must be >= 1, got {self.max_rank}")
        if self.pool_pages < self.max_rank:
            raise ValueError(
                f"lora.pool_pages ({self.pool_pages}) must hold at least one "
                f"max_rank ({self.max_rank}) adapter")
        if self.swap_buffers < 1:
            raise ValueError("lora.swap_buffers must be >= 1, got "
                             f"{self.swap_buffers}")


@dataclass
class PriorityClassConfig:
    """One tenant priority class for the serving frontend
    (``inference/v2/serving/``): a strict-priority level plus the latency
    SLOs admission plans against. ``priority`` is higher-wins; ``ttft_slo_ms``
    bounds time-to-first-token (arrival -> first streamed token) and
    ``tbt_slo_ms`` bounds time-between-tokens — the two numbers
    goodput-under-SLO is gated on (docs/SERVING.md "Frontend")."""
    name: str
    priority: int
    ttft_slo_ms: float = 2000.0
    tbt_slo_ms: float = 250.0

    def __post_init__(self):
        if not self.name:
            raise ValueError("priority class needs a non-empty name")
        if self.ttft_slo_ms <= 0 or self.tbt_slo_ms <= 0:
            raise ValueError(f"class {self.name!r}: SLO targets must be > 0")


def _default_classes():
    return [PriorityClassConfig("interactive", 2, 500.0, 100.0),
            PriorityClassConfig("standard", 1, 2000.0, 250.0),
            PriorityClassConfig("batch", 0, 30000.0, 2000.0)]


@dataclass
class ServingConfig:
    """The SLO-aware serving frontend (``inference/v2/serving/frontend.py``).

    ``classes``: the tenant priority classes (dicts or
    :class:`PriorityClassConfig`), strict priority between classes, FIFO
    within one.

    ``decode_slice``: pipeline steps per ``DecodePipeline.run`` burst — the
    iteration-level continuous-batching grain. Admission, retirement,
    preemption and restore all happen at slice boundaries; a smaller slice
    lowers admission latency, a larger one amortises per-run host work.

    ``preemption`` picks what happens to low-priority victims under KV-pool
    pressure:

    - ``"offload"`` (default): the victim's *private* KV pages (allocator
      refcount 1 — prefix-cache-shared pages are never touched) round-trip
      through pinned host buffers (``runtime/swap_tensor/buffer_pool.py``)
      and are restored byte-identically on readmit; falls back to recompute
      per victim when ``max_offload_bytes`` is exhausted.
    - ``"recompute"``: the victim is flushed and re-prefilled from its
      prompt + generated-so-far tokens on readmit (vLLM's drop-and-recompute
      baseline).
    - ``"none"``: reject-only — no preemption; admission turns conservative
      (a request is admitted only when its full prompt + ``max_new_tokens``
      KV lifetime is fundable up front) and excess load is held, then shed.

    ``shed_factor``: a queued request is shed once
    ``elapsed_queue_delay + predicted_prefill + one_slice >
    ttft_slo_ms * shed_factor`` — it can no longer meet its SLO, so
    admitting it would burn prefill compute on a guaranteed miss.

    ``max_offload_bytes``: host-buffer capacity for offloaded pages (None =
    unbounded); ``offload_buffers`` caps the pinned-buffer pool's free list.
    ``max_queue`` bounds the pending queue (beyond = immediate shed);
    ``idle_wait_s`` is the engine thread's block interval when idle.

    ``spec``: serve greedy requests through the engine's speculative
    pipeline when ``spec_decode.enabled`` (default). ``False`` pins this
    frontend to the plain ``DecodePipeline`` — a per-frontend A/B lever
    (draft-miss overhead vs k-token amortization), and the discipline the
    byte-equality tests use: spec-on and spec-off greedy streams
    agree only up to cross-kernel float noise (~1e-4/token argmax flips on
    a random-init model — docs/SERVING.md "Quantized KV" gate taxonomy),
    so a replay gated bit-exactly against a plain reference serves plain.

    ``tenant_classes``: explicit tenant -> priority-class mapping (tenant
    here = LoRA adapter name, the multi-tenant identity of docs/SERVING.md
    "Multi-tenant LoRA"). Per-request ``priority=`` stays the override, but
    a submit that names an adapter WITHOUT naming a class defaults to the
    tenant's mapped class instead of ``"standard"`` — a mixed queue stops
    misclassifying traffic whose class lives in workload config rather
    than on each request. Every value must name a configured class.
    """
    classes: Any = field(default_factory=_default_classes)
    tenant_classes: Any = field(default_factory=dict)
    decode_slice: int = 8
    spec: bool = True
    preemption: str = "offload"
    max_offload_bytes: Optional[int] = None
    offload_buffers: int = 16
    shed_factor: float = 1.0
    max_queue: int = 1024
    idle_wait_s: float = 0.02

    def __post_init__(self):
        self.classes = [PriorityClassConfig(**c) if isinstance(c, dict) else c
                        for c in self.classes]
        names = [c.name for c in self.classes]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate priority class names: {names}")
        if not self.classes:
            raise ValueError("serving.classes must name at least one class")
        if self.preemption not in ("offload", "recompute", "none"):
            raise ValueError("serving.preemption must be 'offload', "
                             f"'recompute' or 'none', got {self.preemption!r}")
        if self.decode_slice < 1:
            raise ValueError("serving.decode_slice must be >= 1")
        self.tenant_classes = dict(self.tenant_classes)
        for tenant, cls_name in self.tenant_classes.items():
            if cls_name not in names:
                raise ValueError(
                    f"serving.tenant_classes[{tenant!r}] = {cls_name!r} names "
                    f"no configured priority class (configured: {names})")

    def get_class(self, name: str) -> PriorityClassConfig:
        for c in self.classes:
            if c.name == name:
                return c
        raise KeyError(f"unknown priority class {name!r}; configured: "
                       f"{[c.name for c in self.classes]}")

    def class_for(self, priority: Optional[str],
                  tenant: Optional[str] = None) -> PriorityClassConfig:
        """Resolve a request's class: explicit ``priority`` wins, else the
        tenant's ``tenant_classes`` mapping, else ``"standard"``."""
        if priority is not None:
            return self.get_class(priority)
        if tenant is not None and tenant in self.tenant_classes:
            return self.get_class(self.tenant_classes[tenant])
        return self.get_class("standard")


@dataclass
class HealthConfig:
    """Replica failure detection + self-healing for the multi-replica router
    (``inference/v2/serving/health.py``; docs/SERVING.md "Failure
    semantics"). Off by default: a router without health monitoring keeps
    the PR 10 behavior — a dead replica surfaces NAMED at
    ``drain()``/``close()`` instead of being failed over.

    When ``enabled``, a ``dstpu-health`` thread polls every ``interval_s``:
    engine-thread/prefill-worker LIVENESS (a died loop is ``down``
    immediately) plus a PROGRESS heartbeat — the decode-step counter the
    pipeline stats already track (and prefill tokens completed) — so a
    *wedged* replica is detected, not just a dead one. A replica with work
    in flight whose counters stop moving turns ``suspect`` after
    ``suspect_after_s`` and ``down`` after ``down_after_s``; detection
    fences the replica (its loop emits nothing further), migrates every
    in-flight request to a survivor, and — with ``auto_rejoin`` — rebuilds
    a frontend on the engine once its old thread has exited, re-warming the
    pow2 program grids off the hot path (``rejoin_warmup``) before the
    replica re-enters routing.

    ``fence_join_s`` bounds how long failover waits for the failed engine
    thread to exit before migrating anyway (streams stay exact either way:
    migration seals each handle under its emit lock, and a fenced loop
    drops every later emission)."""
    enabled: bool = False
    interval_s: float = 0.05
    suspect_after_s: float = 1.0
    down_after_s: float = 3.0
    fence_join_s: float = 1.0
    auto_rejoin: bool = True
    rejoin_warmup: bool = True

    def __post_init__(self):
        for f in ("interval_s", "suspect_after_s", "down_after_s",
                  "fence_join_s"):
            if getattr(self, f) <= 0:
                raise ValueError(f"health.{f} must be > 0, got "
                                 f"{getattr(self, f)}")
        if self.down_after_s < self.suspect_after_s:
            raise ValueError(
                f"health.down_after_s ({self.down_after_s}) must be >= "
                f"suspect_after_s ({self.suspect_after_s})")


@dataclass
class RouterConfig:
    """The multi-replica serving router (``inference/v2/serving/router.py``;
    docs/SERVING.md "Multi-replica & disaggregation"). Cluster-level — it
    configures a ``ServingRouter`` over N engines, not any single engine.

    ``policy`` picks request placement:

    - ``"cache_aware"`` (default): route to the replica whose radix prefix
      cache holds the longest cached match for the prompt (the
      SGLang-RadixAttention trick at cluster scope, read from a shared
      chain-hash index fed by per-replica insert/evict deltas), scored
      against load: ``score = cached_tokens - balance * outstanding``.
    - ``"round_robin"``: placement ignores caches — the baseline.

    ``balance`` is the stickiness/balance tradeoff knob: how many cached
    prompt tokens one outstanding request on a replica outweighs. ``0`` is
    pure stickiness (hotspot risk); large values degrade to least-loaded.

    ``topology``:

    - ``"colocated"`` (default): every replica runs prefill AND decode.
    - ``"disaggregated"``: dedicated prefill replicas run SplitFuse passes
      and hand finished KV to decode replicas over the page fabric
      (``engine.export_kv``/``import_kv`` — the same bucketed page gather
      preempt-offload rides), eliminating prefill interference on decode
      TBT.

    ``federation``: aggregate per-replica admission state (per-class
    queue-delay EMAs + SLO cost models) into placement — a replica whose
    predicted TTFT already busts the class SLO is skipped while a cold one
    absorbs, and the router sheds up front when EVERY candidate is hot
    (``shed_factor`` scales the SLO bound exactly like
    ``ServingConfig.shed_factor``).

    ``health``: replica failure detection + self-healing
    (:class:`HealthConfig`; docs/SERVING.md "Failure semantics").

    ``handoff_retries`` / ``handoff_timeout_s`` / ``handoff_backoff_s``:
    bounded-retry budget for the disaggregated prefill->decode handoff
    (``utils/resilience.retry_call`` semantics). Each attempt is
    deadline-wrapped (``IOTimeout`` past ``handoff_timeout_s`` — a wedged
    decode replica must not stall the prefill worker unboundedly) and
    re-planned against a DIFFERENT decode replica; a request that exhausts
    the budget is shed with the error NAMED on its handle
    (``RequestHandle.error``), never swallowed."""
    policy: str = "cache_aware"
    balance: float = 32.0
    topology: str = "colocated"
    federation: bool = True
    shed_factor: float = 1.0
    health: Any = field(default_factory=HealthConfig)
    handoff_retries: int = 3
    handoff_timeout_s: Optional[float] = 30.0
    handoff_backoff_s: float = 0.05

    def __post_init__(self):
        if isinstance(self.health, dict):
            self.health = HealthConfig(**self.health)
        if self.handoff_retries < 1:
            raise ValueError("router.handoff_retries must be >= 1, got "
                             f"{self.handoff_retries}")
        if self.handoff_timeout_s is not None and self.handoff_timeout_s <= 0:
            raise ValueError("router.handoff_timeout_s must be > 0 (or "
                             f"None), got {self.handoff_timeout_s}")
        if self.handoff_backoff_s < 0:
            raise ValueError("router.handoff_backoff_s must be >= 0, got "
                             f"{self.handoff_backoff_s}")
        if self.policy not in ("cache_aware", "round_robin"):
            raise ValueError("router.policy must be 'cache_aware' or "
                             f"'round_robin', got {self.policy!r}")
        if self.topology not in ("colocated", "disaggregated"):
            raise ValueError("router.topology must be 'colocated' or "
                             f"'disaggregated', got {self.topology!r}")
        if self.balance < 0:
            raise ValueError(f"router.balance must be >= 0, got {self.balance}")
        if self.shed_factor <= 0:
            raise ValueError("router.shed_factor must be > 0, got "
                             f"{self.shed_factor}")


@dataclass
class AttentionConfig:
    """Flash-decoding split-K knobs (docs/SERVING.md "Attention kernels").

    ``decode_splits``: top rung of the pow2 split ladder. 1 (default) keeps
    the chunk-serial kernels exactly — split-K never dispatches. S > 1 makes
    every paged attention caller (ragged decode pass, fused decode
    step, sidebuf, spec verify) route through the split-K
    dispatchers (``ops/pallas/paged_splitk.py``): each sequence's page range
    is cut into up to S grid-parallel splits emitting ``(acc, lse)``
    partials, merged by one logsumexp-weighted pass. The engine warms ONE
    program per ladder rung ``[1, 2, 4, ..., decode_splits]`` so the
    admission-driven rung choice never compiles on the hot path.

    ``min_ctx_per_split``: rung selection — the engine picks
    ``min(decode_splits, pow2_floor(max_live_ctx / min_ctx_per_split))``
    each step, so short-context batches stay on the split=1 (chunk-serial)
    program where the merge pass is pure overhead, and long tails climb the
    ladder as context grows."""
    decode_splits: int = 1
    min_ctx_per_split: int = 512

    def __post_init__(self):
        if self.decode_splits < 1 or (
                self.decode_splits & (self.decode_splits - 1)) != 0:
            raise ValueError(
                "attention.decode_splits must be a power of two >= 1 (the "
                f"warmed pow2 split ladder), got {self.decode_splits}")
        if self.min_ctx_per_split < 1:
            raise ValueError("attention.min_ctx_per_split must be >= 1, "
                             f"got {self.min_ctx_per_split}")


@dataclass
class BlockDecodeConfig:
    """Generation by diffusion over blocks (``inference/v2/blocks/``;
    docs/SERVING.md "Block-diffusion generation") — read only by an engine
    whose model generates so (``spec.causal_block`` > 1; there is no other
    way to generate from such a model, so nothing here switches it on).

    A block of ``causal_block`` mask tokens takes ``denoising_steps`` denoise
    passes and one commit pass. ``remasking``:

    - ``"low_confidence_static"``: pass ``i`` of a block fills the
      ``num_transfer_tokens(block, steps)[i]`` masked positions of highest
      confidence (``block // steps`` each, the first ``block % steps`` passes
      one more; what is left, if fewer). The schedule needs nothing from the
      device, so the pipeline builds pass N + 1 while pass N runs.
    - ``"low_confidence_dynamic"``: a pass fills every masked position whose
      confidence is over ``confidence_threshold`` if those are at least the
      static count, else the static count — a block may finish in fewer
      passes, and the host reads one int32 row a pass (masks left a row)
      before it builds the next.

    Greedy only for now (``do_sample`` is refused)."""
    denoising_steps: int = 4
    remasking: str = "low_confidence_static"
    confidence_threshold: float = 0.9

    def __post_init__(self):
        if self.denoising_steps < 1:
            raise ValueError("block_decode.denoising_steps must be >= 1, got "
                             f"{self.denoising_steps}")
        if self.remasking not in ("low_confidence_static",
                                  "low_confidence_dynamic"):
            raise ValueError(
                "block_decode.remasking must be 'low_confidence_static' or "
                f"'low_confidence_dynamic', got {self.remasking!r}")
        if not 0.0 <= self.confidence_threshold <= 1.0:
            raise ValueError("block_decode.confidence_threshold must lie in "
                             f"[0, 1], got {self.confidence_threshold}")

    def transfer_schedule(self, block: int):
        """``num_transfer_tokens(block, denoising_steps)``: how many masked
        positions each denoise pass of a block fills, as a tuple."""
        steps = self.denoising_steps
        if steps > block:
            raise ValueError(f"block_decode.denoising_steps={steps} exceeds "
                             f"the block length {block}: a pass would fill "
                             "nothing")
        return tuple(block // steps + (i < block % steps)
                     for i in range(steps))


@dataclass
class RaggedInferenceEngineConfig:
    state_manager: DSStateManagerConfig = field(default_factory=DSStateManagerConfig)
    kv_cache: KVCacheSizingConfig = field(default_factory=KVCacheSizingConfig)
    quantization: QuantizationConfig = field(default_factory=QuantizationConfig)
    kv_quant: KVQuantConfig = field(default_factory=KVQuantConfig)
    prefix_cache: PrefixCacheConfig = field(default_factory=PrefixCacheConfig)
    compile: CompileConfig = field(default_factory=CompileConfig)
    serving: ServingConfig = field(default_factory=ServingConfig)
    spec_decode: SpecDecodeConfig = field(default_factory=SpecDecodeConfig)
    block_decode: BlockDecodeConfig = field(default_factory=BlockDecodeConfig)
    lora: LoraConfig = field(default_factory=LoraConfig)
    attention: AttentionConfig = field(default_factory=AttentionConfig)
    tensor_parallel: int = 1
    dtype: Any = jnp.bfloat16
    seed: int = 0

    @classmethod
    def load(cls, config=None, **overrides) -> "RaggedInferenceEngineConfig":
        if isinstance(config, cls):
            if overrides:
                raise ValueError("pass overrides via a dict config, not on top of "
                                 "an already-built RaggedInferenceEngineConfig")
            cfg = config
        else:
            d = dict(config or {})
            d.update(overrides)
            sm = DSStateManagerConfig(**d.pop("state_manager", {})) \
                if not isinstance(d.get("state_manager"), DSStateManagerConfig) \
                else d.pop("state_manager")
            kv = d.pop("kv_cache", {})
            kv = KVCacheSizingConfig(**kv) if isinstance(kv, dict) else kv
            qz = d.pop("quantization", {})
            qz = QuantizationConfig(**qz) if isinstance(qz, dict) else qz
            kq = d.pop("kv_quant", {})
            kq = KVQuantConfig(**kq) if isinstance(kq, dict) else kq
            pc = d.pop("prefix_cache", {})
            pc = PrefixCacheConfig(**pc) if isinstance(pc, dict) else pc
            co = d.pop("compile", {})
            co = CompileConfig(**co) if isinstance(co, dict) else co
            sv = d.pop("serving", {})
            sv = ServingConfig(**sv) if isinstance(sv, dict) else sv
            sd = d.pop("spec_decode", {})
            sd = SpecDecodeConfig(**sd) if isinstance(sd, dict) else sd
            bd = d.pop("block_decode", {})
            bd = BlockDecodeConfig(**bd) if isinstance(bd, dict) else bd
            lr = d.pop("lora", {})
            lr = LoraConfig(**lr) if isinstance(lr, dict) else lr
            at = d.pop("attention", {})
            at = AttentionConfig(**at) if isinstance(at, dict) else at
            cfg = cls(state_manager=sm, kv_cache=kv, quantization=qz,
                      kv_quant=kq, prefix_cache=pc, compile=co, serving=sv,
                      spec_decode=sd, block_decode=bd, lora=lr, attention=at,
                      **d)
        if cfg.state_manager.chunk_budget <= 0:
            raise ValueError("max_ragged_batch_size must exceed max_ragged_sequence_count")
        return cfg
