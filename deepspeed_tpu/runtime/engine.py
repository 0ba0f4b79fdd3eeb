"""The training engine.

Parity: ``DeepSpeedEngine`` (reference ``deepspeed/runtime/engine.py:175``) — the
object returned by ``initialize`` with ``forward/backward/step/train_batch/
save_checkpoint/load_checkpoint`` and the config property surface. TPU-first
re-design: instead of wrapping an ``nn.Module`` and attaching hooks, the engine owns
a **jitted, sharded train step** closed over the model's apply function:

  - ZeRO stages are sharding policies (``runtime/zero/partition.py``), not hook
    machinery; XLA emits the all-gathers/reduce-scatters the reference schedules by
    hand (stage_1_and_2.py:1004 average_tensor, stage3.py:1183 reduce_and_partition).
  - Mixed precision keeps an fp32 master pytree (sharded over fsdp for stage>=1,
    parity: bf16_optimizer.py:30 / fp16/fused_optimizer.py) and casts to the compute
    dtype each step.
  - Gradient accumulation is a ``lax.scan`` over microbatches inside the step
    (parity: GAS bookkeeping engine.py:1920-2061), with a micro-step path exposing
    the reference's forward()/backward()/step() call discipline.
  - fp16 dynamic loss scaling runs branch-free on device (loss_scaler.py analog).

The steady-state step loop is ASYNC end to end (mirror of the v2 serving
pipeline's one-step-late drain, docs/TRAINING.md): input staging runs in a
``runtime/data_pipeline.PrefetchLoader`` producer thread, ``train_batch``
dispatches the fused step from an already-device-resident sharded batch, and
``_after_step`` is split into a device-side metric enqueue and a host-side
drain that materialises step k-1's floats while step k runs
(``wall_clock_breakdown`` opts the whole loop back into synchronous
execution). This module is a jaxlint JL007 hot path: every blocking
device->host fetch routes through :func:`fetch_to_host`.
"""

from __future__ import annotations

import dataclasses
import os
import time
from collections import deque
from typing import Any, Callable, Dict, Iterable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

import deepspeed_tpu.comm as dist
from deepspeed_tpu.comm.mesh import BATCH_AXES, MeshTopology, build_topology, get_topology, set_topology
from deepspeed_tpu.config import DeepSpeedTPUConfig
from deepspeed_tpu.utils import fault_injection
from deepspeed_tpu.ops import TPUOptimizer, OptaxWrapper, build_optimizer
from deepspeed_tpu.runtime.lr_schedules import build_lr_schedule
from deepspeed_tpu.runtime.loss_scaler import (has_overflow, make_loss_scale_state,
                                               update_loss_scale)
from deepspeed_tpu.runtime.zero.partition import ZeroPartitioner
from deepspeed_tpu.runtime.dataloader import DeepSpeedTPUDataLoader
from deepspeed_tpu.monitor.trace import tracer as _tracer
from deepspeed_tpu.utils import locksan as _locksan
from deepspeed_tpu.utils.logging import log_dist, logger
from deepspeed_tpu.utils.timer import (BACKWARD_GLOBAL_TIMER, FORWARD_GLOBAL_TIMER,
                                       STEP_GLOBAL_TIMER, SynchronizedWallClockTimer,
                                       ThroughputTimer)
from deepspeed_tpu.utils.tree import global_norm, tree_cast


def _last_key(path) -> str:
    from deepspeed_tpu.checkpoint.state import _path_str
    return _path_str(path[-1])


def fetch_to_host(tree):
    """THE device->host drain point for the training engine hot path.

    Every blocking fetch of device data in this module routes through here:
    the step loop is engineered so the only per-step materialisation is the
    deferred metric drain (a handful of scalars, one step late), and
    funnelling all fetches through one function lets jaxlint rule JL007
    statically police the module for stray blocking fetches — an accidental
    ``float(metrics["loss"])`` right after dispatch re-serialises the whole
    loop (the exact regression class the pre-PR ``_after_step`` was). Same
    pattern as ``inference/v2/engine_v2.fetch_to_host``.

    Under tracing the drain records a ``train/drain/fetch_to_host`` span, so
    host-sync cost is ALWAYS attributed on the timeline — whatever code path
    forced the materialisation, the stall shows up here by name.
    """
    if _locksan.enabled():
        # runtime TL002 signal: a drain while sanitized locks are held
        _locksan.note_blocking("fetch_to_host")
    if not _tracer.enabled:
        return jax.device_get(tree)  # jaxlint: disable=JL007 -- the intentional drain
    t0 = time.perf_counter()
    out = jax.device_get(tree)  # jaxlint: disable=JL007 -- the intentional drain
    _tracer.add("train/drain/fetch_to_host", t0, time.perf_counter(),
                lane="train/drain")
    return out


def _bytes_a_device(tree, shardings=None, dtype=None) -> int:
    """Bytes one device holds of a tree of arrays or shapes, each under its
    own sharding (or the matching leaf of ``shardings``), as ``dtype`` if
    given."""
    leaves = jax.tree_util.tree_leaves(tree)
    over = [getattr(x, "sharding", None) for x in leaves] \
        if shardings is None else jax.tree_util.tree_leaves(shardings)
    return sum(
        int(np.prod(sh.shard_shape(x.shape) if sh is not None else x.shape))
        * jnp.dtype(dtype or x.dtype).itemsize for x, sh in zip(leaves, over))


class _FittedStep:
    """The fused step of an engine that chose what its checkpointed layers
    keep (``engine.remat_plan``): compiled ahead of its first call for each
    batch shape, so that the compiled program's ``memory_analysis()`` is
    held against the device's limit before anything runs (the engine's
    ``_compile_fitted``), and called as compiled — one compile a shape, as
    ``jax.jit`` would make."""

    def __init__(self, engine: "DeepSpeedTPUEngine"):
        self._engine = engine
        self._compiled: Dict[Any, Callable] = {}

    def _cache_size(self) -> int:
        return len(self._compiled)

    def lower(self, state, batch):
        return self._engine._jit_fused_step().lower(state, batch)

    def __call__(self, state, batch):
        leaves, treedef = jax.tree_util.tree_flatten(batch)
        key = (treedef, tuple((x.shape, x.dtype) for x in leaves))
        step = self._compiled.get(key)
        if step is None:
            # a stage of set-up, a child ``rung<k>`` for each rung it lowers
            with _tracer.stage("remat_fit"):
                step = self._compiled[key] = self._engine._compile_fitted(
                    state, batch)
        return step(state, batch)


def _extract_apply_fn(model: Any) -> Callable:
    """Accept a flax module (uses ``.apply``), or a callable ``f(params, batch)``.

    The convention mirrors the reference's "engine(batch) returns loss": the model
    maps (params, batch) -> scalar loss, or -> (loss, aux)."""
    if model is None:
        raise ValueError("initialize() requires a model")
    if hasattr(model, "apply") and hasattr(model, "init"):
        def apply_fn(params, batch, rngs=None):
            kwargs = {"rngs": rngs} if rngs else {}
            return model.apply({"params": params}, batch, **kwargs)
        return apply_fn
    if callable(model):
        return lambda params, batch, rngs=None: model(params, batch)
    raise TypeError(f"cannot use {type(model)} as a model: need a flax module or callable")


class DeepSpeedTPUEngine:
    """See module docstring. Construction parity: ``DeepSpeedEngine.__init__``
    (engine.py:178): config wiring, distributed/mesh setup, dtype conversion,
    optimizer + lr scheduler + dataloader configuration, monitors/timers."""

    def __init__(self,
                 args=None,
                 model=None,
                 optimizer: Optional[Any] = None,
                 model_parameters: Optional[Any] = None,
                 training_data=None,
                 lr_scheduler=None,
                 mesh_topology: Optional[MeshTopology] = None,
                 collate_fn=None,
                 config: Optional[DeepSpeedTPUConfig] = None,
                 rngs: Optional[jax.Array] = None,
                 loss_fn: Optional[Callable] = None,
                 tp_rules=None,
                 model_family: Optional[str] = None,
                 param_specs=None):
        self.config = config if isinstance(config, DeepSpeedTPUConfig) else DeepSpeedTPUConfig.load(config)
        # ZeRO++ hpZ / MiCS factorize the fsdp axis into (inter, intra) so
        # secondary-partition gathers ride the intra-node axis
        zc0 = self.config.zero_optimization
        sub = max(zc0.zero_hpz_partition_size,
                  zc0.mics_shard_size if zc0.mics_shard_size > 0 else 1)
        if sub > 1 and self.config.mesh.fsdp_sub == 1 and mesh_topology is None:
            if self.config.mesh.fsdp > 0 and self.config.mesh.fsdp % sub != 0:
                from deepspeed_tpu.config import ConfigError
                raise ConfigError(
                    f"mesh.fsdp={self.config.mesh.fsdp} not divisible by "
                    f"hpz/mics sub-group size {sub}")
            self.config.mesh.fsdp_sub = sub
            if self.config.mesh.fsdp > 0:
                self.config.mesh.fsdp //= sub
        # the engine's mesh is also the ambient (global) topology: model code
        # that reads get_topology() at trace time (pipeline/MoE constraints)
        # must see the same mesh the engine shards over
        self.topology = set_topology(mesh_topology) if mesh_topology is not None \
            else set_topology(build_topology(self.config.mesh))
        self.train_batch_size_, self.micro_batch_size_, self.gas_ = \
            self.config.resolve_batch(self.topology.dp_world_size)
        dist.configure(self.config)
        # Remat policy for every model family built under this engine
        # (parity: _configure_checkpointing engine.py:912 + checkpointing.configure)
        from deepspeed_tpu.runtime import activation_checkpointing
        activation_checkpointing.configure(self.config)

        self.module = model
        self._apply_fn = _extract_apply_fn(model)
        self._loss_fn = loss_fn
        self.compute_dtype = self.config.compute_dtype
        self.mixed_precision = self.compute_dtype != jnp.float32
        self.zero_stage = self.config.zero_optimization.stage
        # tensor parallelism: first-class for training (unlike the reference, which
        # delegates training TP to an external Megatron mpu — SURVEY §2.3)
        self._tp_rules = tp_rules
        self._model_family = model_family
        # explicit per-leaf PartitionSpecs override rule derivation entirely
        # (pipeline stacks, custom layouts); merged with ZeRO axes in the
        # partitioner like TP specs
        self._tp_specs = param_specs
        # compression (parity: compression_training / init_compression wiring)
        self._compression_plan = None
        self.compression_scheduler = None
        if sub > 1 and self.topology.fsdp_sub_size == 1:
            from deepspeed_tpu.config import ConfigError
            raise ConfigError(
                f"hpz/mics sub-group size {sub} configured but the provided mesh "
                "topology has no fsdp_sub axis; factorize fsdp (mesh.fsdp_sub) "
                "or drop mesh_topology so the engine can")
        self.partitioner = ZeroPartitioner(
            self.zero_stage, self.topology,
            persistence_threshold=self.config.zero_optimization.stage3_param_persistence_threshold,
            hpz=self.config.zero_optimization.zero_hpz_partition_size > 1,
            mics=self.config.zero_optimization.mics_shard_size > 0)
        self.quantized_weights = self.config.zero_optimization.zero_quantized_weights

        # -- ZeRO-Offload/Infinity: host/NVMe optimizer step (parity:
        # cpu_offload stage_1_and_2.py:140, stage3 swap_tensor wiring) -----
        off = self.config.zero_optimization.offload_optimizer
        self._offload_cfg = None
        self._offload = None  # HostOffloadOptimizer, built in _init_state
        self._offload_pending = None   # in-flight delayed host update (DPU)
        self._offload_executor = None
        self._offload_upload_pool = None   # upload lane worker (built lazily)
        if off is not None and getattr(off.device, "value", off.device) != "none":
            self._offload_cfg = off
            if self.zero_stage == 0:
                logger.warning("offload_optimizer with zero stage 0: optimizer "
                               "states go to host but grads stay replicated")
            if self.config.zero_optimization.zero_quantized_weights:
                from deepspeed_tpu.config import ConfigError
                raise ConfigError("zero_quantized_weights is not supported "
                                  "together with offload_optimizer")

        # -- optimizer (parity: _configure_optimizer engine.py:1210) -----
        self.client_optimizer = optimizer
        if optimizer is not None:
            if isinstance(optimizer, TPUOptimizer):
                self.optimizer = optimizer
            else:  # assume optax GradientTransformation
                self.optimizer = OptaxWrapper(optimizer)
        elif self.config.optimizer is not None:
            self.optimizer = build_optimizer(self.config.optimizer.type,
                                             self.config.optimizer.params)
        else:
            self.optimizer = build_optimizer("adamw", {"lr": 1e-3})
        base_lr = getattr(self.optimizer, "lr", 1e-3)

        # -- lr schedule (parity: _configure_lr_scheduler engine.py:896) --
        self.lr_scheduler = lr_scheduler
        if lr_scheduler is not None and callable(lr_scheduler):
            self._lr_fn = lr_scheduler
        elif self.config.scheduler is not None and self.config.scheduler.type:
            self._lr_fn = build_lr_schedule(self.config.scheduler.type,
                                            self.config.scheduler.params, base_lr)
        else:
            self._lr_fn = build_lr_schedule(None, {}, base_lr)

        # -- counters (parity: engine.py GAS bookkeeping) ------------------
        self.global_steps = 0
        self.global_samples = 0
        self.micro_steps = 0
        self.skipped_steps = 0
        self._last_metrics: Dict[str, Any] = {}
        # deferred metric drain: (step, samples, device-metrics) entries;
        # _after_step enqueues, _emit_metrics materialises one step late
        self._pending_metrics: deque = deque()

        # -- monitor (parity: MonitorMaster wiring, engine.py:249) ---------
        from deepspeed_tpu.monitor import (CheckpointStats, MonitorMaster,
                                           OffloadPipelineStats,
                                           TrainPipelineStats)
        self.monitor = MonitorMaster(self.config)
        self.train_stats = TrainPipelineStats()
        self.offload_stats = OffloadPipelineStats()
        self.ckpt_stats = CheckpointStats()
        # ZeRO-3 collective schedule (runtime/zero/prefetch.py): built lazily
        # once params exist, armed around every trace of the fused step
        self._zero3_plan = None
        # what the checkpointed layer walk keeps, chosen at the first fused
        # step (_plan_remat); None: nothing chosen, the walk is as configured
        self.remat_plan = None
        # span tracing (docs/OBSERVABILITY.md): config-reachable alongside
        # the DSTPU_TRACE env path initialize() arms
        tc = self.config.monitor.trace
        if tc.enabled or tc.dir:
            _tracer.configure(trace_dir=tc.dir, enabled=True,
                              ring_size=tc.ring_size,
                              req_lane_window=tc.req_lane_window)

        # -- rolling checkpoints (preemption tolerance, docs/ELASTICITY.md):
        # the engine owns the cadence so saves interleave correctly with the
        # deferred metric drain and the offload pipeline's quiesce points
        self._rolling = None
        if self.config.checkpoint.rolling.every_n_steps > 0:
            from deepspeed_tpu.checkpoint.rolling import RollingCheckpointer
            self._rolling = RollingCheckpointer(
                self, self.config.checkpoint.rolling, stats=self.ckpt_stats)

        # -- progressive layer drop (parity: engine hook :1812) ------------
        self.progressive_layer_drop = None
        if self.config.progressive_layer_drop.enabled:
            from deepspeed_tpu.runtime.progressive_layer_drop import \
                ProgressiveLayerDrop
            self.progressive_layer_drop = ProgressiveLayerDrop(
                theta=self.config.progressive_layer_drop.theta,
                gamma=self.config.progressive_layer_drop.gamma)

        # -- curriculum learning (parity: data-pipeline hook engine.py:1823)
        self.curriculum_scheduler = None
        # one-entry cache for the seqlen truncation decision: (scheduled
        # seqlen, incoming leaf width, needs-truncation) — off bucket
        # boundaries the staging path skips the tree walk entirely
        self._curr_seqlen_state: Optional[Tuple[int, int, bool]] = None
        if self.config.curriculum_learning.enabled:
            from deepspeed_tpu.data.curriculum_scheduler import CurriculumScheduler
            self.curriculum_scheduler = CurriculumScheduler(
                self.config.curriculum_learning)

        # -- timers --------------------------------------------------------
        # wall_clock_breakdown opts the whole timer group into device sync
        # (JL001): breakdown numbers measure execution; the default-async
        # timers measure dispatch so steps keep pipelining
        self.timers = SynchronizedWallClockTimer(
            sync=self.config.wall_clock_breakdown)
        self.tput_timer = ThroughputTimer(
            batch_size=self.train_batch_size_,
            steps_per_output=self.config.steps_per_print)

        # -- state ---------------------------------------------------------
        self.state: Optional[Dict[str, Any]] = None
        self._state_shardings = None
        self._rng = rngs if rngs is not None else jax.random.PRNGKey(self.config.seed)
        # PLD randomness is keyed by fold_in(base, step) rather than serial
        # splits so the PrefetchLoader producer (which stages batches AHEAD of
        # the step counter) derives the same stream the sync path would
        self._pld_base_key = None
        if self.progressive_layer_drop is not None:
            self._rng, self._pld_base_key = jax.random.split(self._rng)
        if model_parameters is not None:
            self._build_state(model_parameters)

        # -- jitted steps (built lazily, after state exists) ---------------
        self._first_step_done = False   # train_batch's one branch (set-up)
        self._fused_step = None
        self._micro_step = None
        self._apply_step = None
        self._grad_buffer = None
        self._eval_step = None
        self._data_iterator = None
        self._prefetch_loader = None   # PrefetchLoader owned by the engine
        self._warned_stale_staging = False

        # -- dataloader (parity: deepspeed_io engine.py:1684) --------------
        self.training_dataloader = None
        if training_data is not None:
            self.training_dataloader = self.deepspeed_io(training_data, collate_fn=collate_fn)

    # ------------------------------------------------------------------ #
    # state init
    # ------------------------------------------------------------------ #

    def _build_state(self, model_parameters: Any,
                     init_params: Optional[Callable] = None, init_rng=None):
        """:meth:`_init_state` as a stage of set-up (``tracer.stage``): run
        and blocked on, so ``setup/state_build_s`` holds the build and not
        only its dispatch."""
        with _tracer.stage("state_build"):
            self._init_state(model_parameters, init_params, init_rng)
            jax.block_until_ready(self.state)

    def _init_state(self, model_parameters: Any,
                    init_params: Optional[Callable] = None, init_rng=None):
        """Place master/params/opt-state with their ZeRO shardings.

        Parity: this replaces ``zero.Init`` + ``_configure_distributed_model``
        (partition_parameters.py:734, engine.py:1076): we jit an init function with
        explicit out_shardings so every tensor materialises directly in its
        partitioned layout — no full-model replication transient.

        ``init_params`` (the lazy path, ``_ensure_state``): ``model_parameters``
        is only the abstract tree, and ``init_params(init_rng)`` makes the
        values INSIDE the jitted build: born partitioned, where made eagerly
        they sit whole and in fp32 on one device (4 B/param) beside the state
        being built. The key is the build's ARGUMENT: a constant in its text
        made every seed a program the persistent cache had never seen."""
        topo = self.topology
        # compression plan over the full param tree (parity: init_compression
        # walking the model, compression/compress.py); applied in _current_params
        comp_cfg = getattr(self, "_compression_config", None)
        if (self.config.compression_training or comp_cfg is not None) \
                and self._compression_plan is None:
            from deepspeed_tpu.compression import (CompressionConfig,
                                                   CompressionScheduler,
                                                   compile_compression_plan)
            if comp_cfg is None:
                comp_cfg = CompressionConfig.from_dict(
                    self.config.compression_training)
                self._compression_config = comp_cfg
            self._compression_plan = compile_compression_plan(model_parameters,
                                                              comp_cfg)
            if self.compression_scheduler is None:
                self.compression_scheduler = CompressionScheduler(comp_cfg)
        if self._tp_specs is None and (topo.tp_world_size > 1 or topo.ep_world_size > 1):
            specs = None
            if topo.tp_world_size > 1:
                from deepspeed_tpu.parallel.tensor_parallel import (derive_tp_specs,
                                                                    tp_rules_for)
                rules = (tp_rules_for(self._model_family) if self._tp_rules is None
                         else self._tp_rules)  # [] means "shard nothing"
                specs = derive_tp_specs(model_parameters, rules, topo.tp_world_size)
            if topo.ep_world_size > 1:
                # expert weights shard their leading E dim over 'expert' (parity:
                # expert-parallel groups, utils/groups.py:113); merged with TP specs
                from deepspeed_tpu.parallel.moe import derive_ep_specs
                ep = derive_ep_specs(model_parameters, topo.ep_world_size)
                if specs is None:
                    specs = ep
                else:
                    specs = jax.tree_util.tree_map(
                        lambda t, e: e if tuple(e) != () else t, specs, ep,
                        is_leaf=lambda s: isinstance(s, P))
            self._tp_specs = specs
        master_sh = self.partitioner.master_sharding(model_parameters, self._tp_specs)
        param_sh = self.partitioner.param_sharding(model_parameters, self._tp_specs)
        if self._offload_cfg is not None:
            if init_params is not None:   # the host optimizer reads values
                model_parameters = init_params(init_rng)
            return self._init_state_offload(model_parameters, master_sh, param_sh)
        opt_template = jax.eval_shape(self.optimizer.init,
                                      jax.eval_shape(lambda t: tree_cast(t, jnp.float32),
                                                     model_parameters))
        opt_spec = self.partitioner.opt_state_spec(opt_template, model_parameters,
                                                   self._tp_specs)
        opt_sh = jax.tree_util.tree_map(
            lambda s: NamedSharding(topo.mesh, s), opt_spec,
            is_leaf=lambda s: isinstance(s, P))

        repl = NamedSharding(topo.mesh, P())
        shardings: Dict[str, Any] = {
            "master": master_sh,
            "opt": opt_sh,
            "step": repl,
            "scaler": {k: repl for k in ("scale", "growth_tracker", "hysteresis")},
            "skipped": repl,
        }
        if self.quantized_weights:
            from deepspeed_tpu.runtime.zero.zeropp import quantized_param_shardings
            shardings["params"] = quantized_param_shardings(
                param_sh, model_parameters, topo.mesh)
        elif self.mixed_precision:
            shardings["params"] = param_sh

        fp16 = self.config.fp16
        dynamic = fp16.enabled

        # the program's name in a device trace and the compile log:
        # jit_train_state_build
        def train_state_build(params_in):
            master = tree_cast(params_in, jnp.float32)
            opt = self.optimizer.init(master)
            scaler = make_loss_scale_state(dynamic, fp16.loss_scale,
                                           fp16.initial_scale_power, fp16.hysteresis)
            st = {"master": master, "opt": opt, "step": jnp.zeros((), jnp.int32),
                  "scaler": {k: scaler[k] for k in ("scale", "growth_tracker", "hysteresis")},
                  "skipped": jnp.zeros((), jnp.int32)}
            if self.quantized_weights:
                from deepspeed_tpu.runtime.zero.zeropp import quantize_param_tree
                st["params"] = quantize_param_tree(master, self.compute_dtype)
            elif self.mixed_precision:
                st["params"] = tree_cast(master, self.compute_dtype)
            return st

        donate = (0,) if self.config.donate_model_parameters else ()
        with topo.mesh:
            if init_params is not None:
                def train_state_build_lazy(rng):
                    return train_state_build(init_params(rng))
                self.state = jax.jit(train_state_build_lazy, in_shardings=repl,
                                     out_shardings=shardings)(init_rng)
            else:
                self.state = jax.jit(train_state_build,
                                     out_shardings=shardings,
                                     donate_argnums=donate)(model_parameters)
        self._state_shardings = shardings
        self._scaler_dynamic = bool(dynamic and fp16.loss_scale == 0)
        self._maybe_build_zero3_plan(model_parameters)

    def _maybe_build_zero3_plan(self, model_parameters):
        """Build the ZeRO-3 collective schedule (runtime/zero/prefetch.py)
        once params exist. ``stage3_prefetch_depth=None`` (the default) keeps
        the implicit XLA-scheduled path bit-for-bit untouched. The schedule
        composes with remat but not (yet) with offload, quantized weights, or
        TP-sharded params — those combinations stay on the implicit path."""
        z = self.config.zero_optimization
        if (z.stage3_prefetch_depth is None or z.stage != 3
                or self._offload_cfg is not None or self.quantized_weights
                or self._tp_specs is not None
                or not isinstance(model_parameters, dict)):
            return
        from deepspeed_tpu.runtime.zero import prefetch
        names = prefetch.layer_stack_names(model_parameters)
        if names is None:
            logger.warning(
                "stage3_prefetch_depth=%d set but no layer stack detected in "
                "the param tree: staying on the implicit ZeRO-3 path",
                z.stage3_prefetch_depth)
            return
        specs = self.partitioner.param_spec(model_parameters, self._tp_specs)
        plan = prefetch.build_plan(
            model_parameters, specs, names, depth=z.stage3_prefetch_depth,
            allgather_bucket_size=z.allgather_bucket_size,
            reduce_bucket_size=z.reduce_bucket_size)
        if plan is None:
            logger.warning(
                "stage3_prefetch_depth=%d set but no layer has fsdp-sharded "
                "leaves (all under stage3_param_persistence_threshold?): "
                "staying on the implicit ZeRO-3 path", z.stage3_prefetch_depth)
            return
        self._zero3_plan = plan
        logger.info(
            "zero3 collective schedule: %d waves over %d layers, depth=%d, "
            "%.1f MB gathered/step, %.1f MB persistent",
            plan.n_waves, len(names), plan.depth,
            plan.gather_bytes_per_step / 1e6, plan.persistent_bytes / 1e6)

    # ------------------------------------------------------------------ #
    # ZeRO-Offload state + step (host/NVMe optimizer; parity: cpu_offload +
    # swap_tensor pipelined optimizer swapper)
    # ------------------------------------------------------------------ #

    def _init_state_offload(self, model_parameters, master_sh, param_sh):
        """State layout in offload mode: ``params`` is the full device tree
        (compute dtype, sharded); ``master``/``opt`` are FLAT dicts keyed by
        '/'-joined paths holding only the *device-flow* leaves (twin-flow
        ``ratio`` knob); host-flow leaves live in ``self._offload`` (RAM or
        NVMe via the pipelined swapper). The flat-key scheme matches the
        checkpoint layer, so offload and non-offload checkpoints are
        interchangeable."""
        from deepspeed_tpu.checkpoint.state import flatten_tree
        from deepspeed_tpu.runtime.zero.offload import (HostOffloadOptimizer,
                                                        partition_leaves)
        topo = self.topology
        flat = flatten_tree(model_parameters)
        host_names, dev_names = partition_leaves(flat, self._offload_cfg.ratio)
        self._offload_host_names = host_names
        self._offload_dev_names = dev_names
        self._param_template = jax.eval_shape(lambda t: t, model_parameters)
        flat_master_sh = flatten_tree(master_sh)

        host_master = {k: np.asarray(v, np.float32) for k, v in
                       fetch_to_host({k: flat[k] for k in host_names}).items()}
        self._offload = HostOffloadOptimizer(self.optimizer, host_master,
                                             self._offload_cfg)
        # Grouped flat host-flow layout: grads leave the device as ONE
        # contiguous array PER PIPELINE GROUP and each group's updated master
        # returns as one array — per-leaf transfers pay a full link round
        # trip EACH, while per-group arrays are what lets group g+1's D2H
        # ride the link during group g's kernel.
        # Groups are contiguous chunks of host_names, so the concatenation of
        # all groups is the same byte layout the single-flat scheme used.
        self._offload_groups = self._offload.leaf_groups()
        self._offload_group_meta = []   # per group: [(name, off, n, shape)]
        for names in self._offload_groups:
            meta, off = [], 0
            for k in names:
                n = int(np.prod(np.shape(flat[k])))
                meta.append((k, off, n, np.shape(flat[k])))
                off += n
            self._offload_group_meta.append(meta)

        dev_template = {k: jax.ShapeDtypeStruct(np.shape(flat[k]), jnp.float32)
                        for k in dev_names}
        opt_template = jax.eval_shape(self.optimizer.init, dev_template)
        repl = NamedSharding(topo.mesh, P())

        def opt_leaf_sharding(path, leaf):
            if not np.shape(leaf):
                return repl
            return flat_master_sh.get(_last_key(path), repl)

        opt_sh = jax.tree_util.tree_map_with_path(opt_leaf_sharding, opt_template)
        shardings = {
            "params": param_sh,
            "master": {k: flat_master_sh[k] for k in dev_names},
            "opt": opt_sh,
            "step": repl,
            "scaler": {k: repl for k in ("scale", "growth_tracker", "hysteresis")},
            "skipped": repl,
        }
        fp16 = self.config.fp16

        def build(params_in):
            flat_in = flatten_tree(params_in)
            master_dev = {k: flat_in[k].astype(jnp.float32) for k in dev_names}
            scaler = make_loss_scale_state(fp16.enabled, fp16.loss_scale,
                                           fp16.initial_scale_power, fp16.hysteresis)
            return {"params": tree_cast(params_in, self.compute_dtype),
                    "master": master_dev,
                    "opt": self.optimizer.init(master_dev),
                    "step": jnp.zeros((), jnp.int32),
                    "scaler": {k: scaler[k] for k in ("scale", "growth_tracker",
                                                      "hysteresis")},
                    "skipped": jnp.zeros((), jnp.int32)}

        with topo.mesh:
            self.state = jax.jit(build, out_shardings=shardings)(model_parameters)
        self._state_shardings = shardings
        self._scaler_dynamic = bool(fp16.enabled and fp16.loss_scale == 0)
        self._offload_merge = None
        log_dist(f"offload_optimizer[{self._offload_cfg.device}]: "
                 f"{len(host_names)} host leaves, {len(dev_names)} device leaves",
                 ranks=[0])

    def _build_offload_grad_step(self):
        """Jitted: scan microbatches -> mean grads; update device-flow leaves;
        emit clipped fp32 host-flow grads for the host optimizer."""
        from deepspeed_tpu.checkpoint.state import flatten_tree
        fp16 = self.config.fp16
        clip = self.config.gradient_clipping
        dev_names, host_names = self._offload_dev_names, self._offload_host_names

        def step_fn(state, batch):
            # _current_params applies the compression plan when configured
            params = self._current_params(state)
            scale = state["scaler"]["scale"] if fp16.enabled else jnp.float32(1.0)
            grads, losses = self._accumulate_grads(params, scale, batch)
            flat_g = flatten_tree(grads)
            gnorm = global_norm(flat_g)
            overflow = has_overflow(flat_g) if fp16.enabled else jnp.bool_(False)
            cscale = jnp.minimum(1.0, clip / (gnorm + 1e-6)) if clip > 0 \
                else jnp.float32(1.0)
            lr = self._lr_fn(state["step"])

            dev_g = {k: flat_g[k] * cscale for k in dev_names}
            # host-flow grads as ONE flat array PER PIPELINE GROUP in the
            # COMPUTE dtype: group transfers at half width under bf16 — the
            # reference's ZeRO-Offload ships fp16 grads to the CPU and
            # updates in fp32 there (zero/stage_1_and_2.py cpu_offload); the
            # host kernels upcast to fp32 before stepping. Per-group arrays
            # let the host drain group g while g+1's D2H is still in flight.
            wire = self.compute_dtype
            host_g = tuple(
                jnp.concatenate([(flat_g[k].reshape(-1) * cscale).astype(wire)
                                 for k, _, _, _ in meta])
                for meta in self._offload_group_meta)

            def do_update(operand):
                master, opt = operand
                return self.optimizer.update(dev_g, opt, master, lr=lr)

            new_master, new_opt = jax.lax.cond(
                overflow, lambda o: o, do_update, (state["master"], state["opt"]))
            scaler_full = dict(state["scaler"], dynamic=self._scaler_dynamic)
            new_scaler = update_loss_scale(
                scaler_full, overflow, loss_scale_window=fp16.loss_scale_window,
                hysteresis=fp16.hysteresis, min_loss_scale=fp16.min_loss_scale)
            new_state = {
                "params": params,  # merged after the host step
                "master": new_master,
                "opt": new_opt,
                "step": state["step"] + jnp.where(overflow, 0, 1).astype(jnp.int32),
                "scaler": {k: new_scaler[k] for k in ("scale", "growth_tracker",
                                                      "hysteresis")},
                "skipped": state["skipped"] + overflow.astype(jnp.int32),
            }
            metrics = {"loss": jnp.mean(losses), "grad_norm": gnorm, "lr": lr,
                       "overflow": overflow, "loss_scale": new_scaler["scale"]}
            return new_state, host_g, metrics

        return step_fn

    def _offload_train_step(self, sharded_batch):
        if self._fused_step is None:
            # params pass through to the output state, so donation aliases the
            # old buffers instead of double-allocating device state
            self._fused_step = jax.jit(self._build_offload_grad_step(),
                                       donate_argnums=(0,),
                                       compiler_options=self._compiler_options())
        if self._offload_merge is None:
            self._offload_train_merge_warmup()
        self.state, host_g, metrics = self._fused_step(self.state, sharded_batch)

        if not self._offload_cfg.delayed_param_update:
            overflow = bool(metrics["overflow"]) if self.config.fp16.enabled else False
            if not overflow:
                updated = self._offload_host_step(host_g, metrics)
                self.state["params"] = self._offload_merge(self.state["master"],
                                                           updated)
            return metrics

        # Delayed Param Update (ZeRO-Offload DPU): the fused step above is
        # only DISPATCHED; the worker thread blocks on step N's grads (d2h)
        # and runs the host optimizer while the device already computes step
        # N+1. Step N's host-flow update merges at the START of step N+1, so
        # offloaded leaves apply one step late — step time becomes
        # ~max(device, transfer + host) instead of their sum.
        def host_work(host_g, metrics):
            overflow = (bool(metrics["overflow"])
                        if self.config.fp16.enabled else False)
            if overflow:
                return None
            return self._offload_host_step(host_g, metrics)

        self._drain_offload()  # merge step N-1's host update before N+1 runs
        if self._offload_executor is None:
            from concurrent.futures import ThreadPoolExecutor
            self._offload_executor = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="dstpu-offload")
        self._offload_pending = self._offload_executor.submit(
            host_work, host_g, metrics)
        return metrics

    def _offload_host_step(self, host_g_groups, metrics):
        """Run the host optimizer for one step; returns the per-group updated
        master arrays (tuple matching ``_offload_group_meta``) ready for
        ``_offload_merge``.

        Pipelined (``overlap_step``, the default): every group's grad D2H is
        queued up front, then ``HostOffloadOptimizer.step_groups`` walks the
        groups — group g's kernel runs while g+1's fetch is still on the link
        and g-1's upload (concat + cast + async device_put) drains on a
        dedicated worker thread, with the NVMe swapper double-buffering
        underneath. Serial (``overlap_step: false`` — the pre-PR baseline):
        one blocking drain of all groups, a serial kernel pass, uploads built
        at the end. Identical math either way
        (tests/unit/test_offload.py holds the byte equality)."""
        perf = time.perf_counter
        lr = float(fetch_to_host(metrics["lr"]))
        meta_groups = self._offload_group_meta
        if not meta_groups:
            return ()
        stats = self.offload_stats

        if not self._offload_cfg.overlap_step:
            t0 = perf()
            host_np = [np.asarray(g, np.float32)
                       for g in fetch_to_host(host_g_groups)]
            t1 = perf()
            views = {k: host_np[gi][off:off + n]
                     for gi, meta in enumerate(meta_groups)
                     for k, off, n, _ in meta}
            updated = self._offload.step(views, lr)
            t2 = perf()
            out = self._host_master_group_flats(updated)
            t3 = perf()
            stats.add("fetch", t1 - t0)
            stats.add("kernel", t2 - t1)
            stats.add("upload", t3 - t2)
            stats.record_step(groups=len(meta_groups), depth_sum=0)
            if _tracer.enabled:
                _tracer.add("train/offload/fetch", t0, t1,
                            lane="train/offload")
                _tracer.add("train/offload/kernel", t1, t2,
                            lane="train/offload")
                _tracer.add("train/offload/upload", t2, t3,
                            lane="train/offload")
            return out

        # queue EVERY group's D2H now: the per-group drain below then blocks
        # only on its own transfer, so group g+1's bytes ride the link while
        # group g's kernel runs
        for arr in host_g_groups:
            start = getattr(arr, "copy_to_host_async", None)
            if start is not None:
                start()

        wire = np.dtype(self.compute_dtype)
        repl = NamedSharding(self.topology.mesh, P())
        uploads: list = [None] * len(meta_groups)
        depth_box = {"sum": 0}

        def grad_views_for(gi):
            host_np = np.asarray(fetch_to_host(host_g_groups[gi]), np.float32)
            return {k: host_np[off:off + n] for k, off, n, _ in meta_groups[gi]}

        def upload_group(gi, masters):
            t0 = perf()
            flat = np.concatenate(
                [np.asarray(masters[k], np.float32).reshape(-1)
                 for k, _, _, _ in meta_groups[gi]]).astype(wire)
            dev = jax.device_put(flat, repl)   # async H2D dispatch
            t1 = perf()
            stats.add("upload", t1 - t0)
            _tracer.add("train/offload/upload", t0, t1,
                        lane="train/offload/upload", group=gi)
            return dev

        def on_group_done(gi, masters):
            depth_box["sum"] += sum(1 for f in uploads
                                    if f is not None and not f.done())
            uploads[gi] = self._offload_uploader().submit(
                upload_group, gi, masters)

        self._offload.step_groups(grad_views_for, lr,
                                  on_group_done=on_group_done,
                                  record=stats.add)
        out = tuple(f.result() for f in uploads)
        stats.record_step(groups=len(meta_groups), depth_sum=depth_box["sum"])
        return out

    def _offload_uploader(self):
        """Single-worker executor for the upload lane (concat + cast + async
        device_put of each finished group's master)."""
        if self._offload_upload_pool is None:
            from concurrent.futures import ThreadPoolExecutor
            self._offload_upload_pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="dstpu-offload-upload")
        return self._offload_upload_pool

    def _host_master_group_flats(self, leaves: dict) -> tuple:
        """Per-group flat COMPUTE-dtype host arrays of the given master
        leaves — the host-side input shape ``_offload_merge`` takes (half
        width under bf16; params are cast to the compute dtype there anyway)."""
        wire = np.dtype(self.compute_dtype)
        return tuple(
            np.concatenate([np.asarray(leaves[k], np.float32).reshape(-1)
                            for k, _, _, _ in meta]).astype(wire)
            for meta in self._offload_group_meta)

    def _drain_offload(self):
        """Wait for an in-flight delayed host update and merge it into the
        device params. Called before the next step, checkpoints, and
        destroy() — anything that must observe post-update parameters."""
        pending, self._offload_pending = self._offload_pending, None
        if pending is None:
            return
        updated = pending.result()
        if updated is not None:
            self.state["params"] = self._offload_merge(self.state["master"],
                                                       updated)

    def _offload_ckpt_state(self):
        """Synthetic full-state view for checkpoint save: device-flow leaves
        fetched from device, host-flow leaves read from RAM/NVMe; flat keys make
        the layout identical to non-offload checkpoints."""
        self._drain_offload()   # a delayed (DPU) host step must land first
        # ONE tree-level drain for the device-flow masters (a per-leaf
        # comprehension here paid a full device round trip per leaf)
        dev_master = fetch_to_host(self.state["master"])
        host_master, moments = self._offload.state_leaves()
        full_master = {**dev_master, **host_master}
        dev_opt = fetch_to_host(self.state["opt"])
        full_opt = {}
        for key, val in dev_opt.items():
            if isinstance(val, dict):
                full_opt[key] = {**val, **moments.get(key, {})}
            else:
                full_opt[key] = val
        return {"master": full_master, "opt": full_opt, "step": self.state["step"],
                "scaler": self.state["scaler"], "skipped": self.state["skipped"]}

    def _load_checkpoint_offload(self, load_dir, tag, load_optimizer_states=True,
                                 load_module_only=False, verify=False):
        from deepspeed_tpu.checkpoint import state as ck
        import json
        # a pending DPU host step mutates the same master arrays the load is
        # about to overwrite (and would merge stale values after the load)
        self._drain_offload()
        need_optim = load_optimizer_states and not load_module_only
        # one checksum pass per shard: explicit tags verify at load, a
        # tag=None scan verifies candidates in find_resume_tag (so bit-rot
        # in the newest tag falls back instead of surfacing) and skips the
        # redundant re-verify at load
        scan_verify = verify and tag is None
        tag = ck.resolve_load_tag(load_dir, tag, need_optim=need_optim,
                                  verify=scan_verify)
        verify = verify and not scan_verify
        ckpt_dir = os.path.join(load_dir, tag)
        cke = self._checkpoint_engine()
        model_flat = ck._load_verified(cke, ckpt_dir, ck.MODEL_FILE, verify)
        dev_names, host_names = self._offload_dev_names, self._offload_host_names
        master_sh = self._state_shardings["master"]
        self.state["master"] = {
            k: jax.device_put(model_flat[k], master_sh[k]) for k in dev_names}
        self._offload.load_master_leaves({k: model_flat[k] for k in host_names})
        if load_optimizer_states and not load_module_only:
            optim_flat = ck._load_verified(cke, ckpt_dir, ck.OPTIM_FILE,
                                           verify)
            dev_opt = fetch_to_host(self.state["opt"])
            new_opt, host_moments = {}, {}
            for key, val in dev_opt.items():
                if isinstance(val, dict):
                    new_opt[key] = {
                        k: jax.device_put(optim_flat[f"opt/{key}/{k}"],
                                          self._state_shardings["opt"][key][k])
                        for k in dev_names}
                    host_moments[key] = {k: optim_flat[f"opt/{key}/{k}"]
                                         for k in host_names}
                else:
                    new_opt[key] = jax.device_put(optim_flat[f"opt/{key}"],
                                                  self._state_shardings["opt"][key])
            self.state["opt"] = new_opt
            step_num = int(optim_flat.get("opt/step", optim_flat.get("step", 0)))
            self._offload.load_moment_leaves(host_moments, step_num=step_num)
            for k in ("step", "skipped"):
                self.state[k] = jax.device_put(optim_flat[k].astype(np.int32),
                                               self._state_shardings[k])
            self.state["scaler"] = {
                k: jax.device_put(optim_flat[f"scaler/{k}"],
                                  self._state_shardings["scaler"][k])
                for k in ("scale", "growth_tracker", "hysteresis")}
        # rebuild device params from masters
        if self._offload_merge is None:
            self._offload_train_merge_warmup()
        self.state["params"] = self._offload_merge(
            self.state["master"],
            self._host_master_group_flats(self._offload.master_leaves()))
        client_path = os.path.join(ckpt_dir, ck.CLIENT_FILE)
        client_state = {}
        if os.path.exists(client_path):
            with open(client_path) as f:
                client_state = json.load(f)
        return load_dir, client_state

    def _offload_train_merge_warmup(self):
        from deepspeed_tpu.checkpoint.state import unflatten_into
        param_sh = self._state_shardings["params"]
        template = self._param_template
        dtype = self.compute_dtype
        meta_groups = self._offload_group_meta

        def merge(master_dev, host_group_flats):
            # host master arrives as one flat array PER GROUP (each already
            # uploading while later groups still step); static offsets split
            # them back into leaves
            flat = {k: v.astype(dtype) for k, v in master_dev.items()}
            for meta, gflat in zip(meta_groups, host_group_flats):
                for k, off, n, shape in meta:
                    flat[k] = jax.lax.dynamic_slice_in_dim(
                        gflat, off, n).reshape(shape).astype(dtype)
            return unflatten_into(template, flat)

        self._offload_merge = jax.jit(merge, out_shardings=param_sh)

    # ------------------------------------------------------------------ #
    # loss / grads
    # ------------------------------------------------------------------ #

    def rollout_source_params(self):
        """The device-resident parameter tree the colocated WeightBridge
        reshards from (``runtime/colocated.py``) — the train half of the
        train->serve weight sync, chosen to match the universal-checkpoint
        repartition source byte-for-byte:

        * standard engines: ``state["master"]`` — the fp32 fsdp-sharded
          master, exactly what ``ds_to_universal`` serialises (so the
          bridge's cast->adapt is bitwise the disk path minus disk);
        * cpu-offload engines: ``state["params"]`` after the in-flight
          delayed host step drains — the master is split device/host there,
          and the merged device params ARE the post-update view every
          consumer (next step, checkpoint) reads.

        Both are device trees: nothing here fetches weight bytes to host
        (the JL007-policed invariant). Refuses engine modes whose params
        are not plainly device-resident in the model's own tree layout."""
        if self.quantized_weights:
            raise NotImplementedError(
                "colocated weight sync from a quantized-weight (ZeRO++ qwZ) "
                "engine is not wired — the bridge would have to dequantize "
                "per sync; train unquantized or sync via checkpoint")
        if self._compression_plan is not None and self._compression_plan.leaves:
            raise NotImplementedError(
                "colocated weight sync with an active compression schedule "
                "is not wired (masks are step-keyed); sync via checkpoint")
        if self._offload is not None:
            self._drain_offload()
            return self.state["params"]
        return self.state["master"]

    def _current_params(self, state):
        if "params" in state:
            if self.quantized_weights:
                from deepspeed_tpu.runtime.zero.zeropp import dequantize_param_tree
                params = dequantize_param_tree(state["params"], self.compute_dtype)
            else:
                params = state["params"]
        else:
            params = state["master"]
        if self._compression_plan is not None and self._compression_plan.leaves:
            from deepspeed_tpu.compression import apply_compression
            params = apply_compression(params, self._compression_plan, state["step"])
        return params

    def _loss_of(self, params, batch, rngs=None):
        out = self._apply_fn(params, batch, rngs)
        if self._loss_fn is not None:
            out = self._loss_fn(out, batch)
        if isinstance(out, tuple):
            out = out[0]
        return out

    def _grad_fn(self, params, batch, scale):
        def scaled_loss(p):
            return self._loss_of(p, batch) * scale
        loss, grads = jax.value_and_grad(scaled_loss)(params)
        return loss / scale, grads

    def _constrain_grads(self, grads):
        spec = self.partitioner.grad_spec(grads, self._tp_specs)
        return jax.lax.with_sharding_constraint(
            grads, jax.tree_util.tree_map(
                lambda s: NamedSharding(self.topology.mesh, s), spec,
                is_leaf=lambda s: isinstance(s, P)))

    # ------------------------------------------------------------------ #
    # fused train step (scan over microbatches)
    # ------------------------------------------------------------------ #

    def _accumulate_grads(self, params, scale, batch):
        """Scan microbatches; return (mean fp32 grads, per-microbatch losses).
        Shared by the fused and offload step builders (parity: the GAS loop,
        engine.py:1920-2061)."""
        accum_dtype = self.config.grad_accum_dtype

        def body(acc, mb):
            loss, grads = self._grad_fn(params, mb, scale)
            grads = tree_cast(grads, accum_dtype)
            grads = self._constrain_grads(grads)
            acc = jax.tree_util.tree_map(jnp.add, acc, grads)
            return acc, loss

        acc0 = jax.tree_util.tree_map(
            lambda x: jnp.zeros(x.shape, accum_dtype), params)
        acc0 = self._constrain_grads(acc0)
        grads, losses = jax.lax.scan(body, acc0, batch)
        inv = 1.0 / (self.gas_ * scale)
        grads = jax.tree_util.tree_map(lambda g: (g * inv).astype(jnp.float32), grads)
        return grads, losses

    def _build_fused_step(self):
        from deepspeed_tpu.runtime import activation_checkpointing
        fp16 = self.config.fp16

        def step_fn(state, batch):
            params = self._current_params(state)
            scale = state["scaler"]["scale"] if fp16.enabled else jnp.float32(1.0)
            # read when traced: the rung the engine holds now
            plan = self.remat_plan
            with activation_checkpointing.keeping(
                    None if plan is None else plan.rung,
                    grads_reduced=self.topology.dp_world_size > 1):
                grads, losses = self._accumulate_grads(params, scale, batch)
            new_state, metrics = self._apply_grads(state, grads)
            metrics["loss"] = jnp.mean(losses)
            return new_state, metrics

        return step_fn

    def _jit_fused_step(self):
        return jax.jit(self._build_fused_step(), donate_argnums=(0,),
                       compiler_options=self._compiler_options())

    # ------------------------------------------------------------------ #
    # what the checkpointed layers keep (runtime/activation_checkpointing.py)
    # ------------------------------------------------------------------ #

    def _plan_remat(self, batch_tree):
        """Choose what the model's checkpointed layer walk keeps, from what
        can be observed and is the same in every run: the device's memory
        limit, the bytes of train state and of gradient accumulator a device
        holds, and what each rung keeps of a layer, from one abstract trace
        of the loss on a micro-batch of ``batch_tree`` ([gas, rows, ...]).
        None — and nothing changes — where the device reports no limit (the
        CPU backend), the ``activation_checkpointing`` block says what is
        kept, the explicit ZeRO-3 schedule is armed (its waves recompute to
        free gathered parameters), or no walk of the model asked (no
        ``remat``, or a ``remat_policy`` named)."""
        from deepspeed_tpu.accelerator import get_accelerator
        from deepspeed_tpu.runtime import activation_checkpointing as ac
        if self._zero3_plan is not None or ac.names_what_is_kept(
                self.config.activation_checkpointing):
            return None
        limit = get_accelerator().total_memory()
        if limit <= 0:
            return None
        micro = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), batch_tree)
        with ac.probing() as probe:
            jax.eval_shape(
                lambda state, mb: self._loss_of(self._current_params(state), mb),
                self.state, micro)
        if not probe.layers:
            return None
        params = jax.eval_shape(self._current_params, self.state)
        grads = _bytes_a_device(
            params, self.partitioner._to_sharding(
                self.partitioner.grad_spec(params, self._tp_specs)),
            self.config.grad_accum_dtype)
        resident = _bytes_a_device(self.state)
        # the micro-batch's rows are spread over the data axes; one
        # micro-batch is alive at a time (the accumulation is a scan)
        world = self.topology.dp_world_size
        kept = tuple(-(-k // world) for k in probe.kept_per_layer())
        rung = ac.choose_rung(limit, resident, kept, probe.layers, other=grads)
        return ac.RematPlan(rung=rung, kept_per_layer=kept, layers=probe.layers,
                            limit_bytes=limit, resident_bytes=resident,
                            other_bytes=grads)

    def _make_fused_step(self, batch_tree):
        with _tracer.stage("remat_fit"), _tracer.stage("plan"):
            self.remat_plan = self._plan_remat(batch_tree)
        if self.remat_plan is None:
            return self._jit_fused_step()
        return _FittedStep(self)

    def _compile_fitted(self, state, batch):
        """Compile the fused step at the plan's rung; while the compiled
        program needs more than the device's limit — by its
        ``memory_analysis()``, or because the TPU compiler refuses it
        (RESOURCE_EXHAUSTED) — one rung lower. The last rung (full
        recompute) is what ran before there was a choice: it is returned
        whatever it needs, and what the compiler raises of it is raised."""
        from deepspeed_tpu.runtime import activation_checkpointing as ac
        while True:
            plan = self.remat_plan
            last = plan.rung == len(ac.LADDER) - 1
            # models/llama.py notes its rows when THIS step's gradient is
            # traced; a step with another loss leaves the 0. The flash
            # kernel counts its backward calls the same way, by the path
            # each took (ops/pallas/flash_attention.py::_bwd)
            _tracer.note("train/loss_head/fused", 0)
            _tracer.note("train/flash/bwd_fused", 0)
            _tracer.note("train/flash/bwd_split", 0)
            try:
                with _tracer.stage(f"rung{plan.rung}"):
                    compiled = self._jit_fused_step().lower(
                        state, batch).compile()
            except jax.errors.JaxRuntimeError as e:
                if last or "RESOURCE_EXHAUSTED" not in str(e):
                    raise
                needs = "is refused by the compiler: out of memory"
            else:
                mem = compiled.memory_analysis()
                need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                        - mem.alias_size_in_bytes + mem.temp_size_in_bytes
                        + mem.generated_code_size_in_bytes)
                if last or need <= plan.limit_bytes:
                    fused = _tracer.totals["train/loss_head/fused"]
                    one = _tracer.totals["train/flash/bwd_fused"]
                    two = _tracer.totals["train/flash/bwd_split"]
                    log_dist(f"activation checkpointing: {plan.describe()}; "
                             f"the compiled step needs {need / 2**30:.2f} GiB"
                             + ("" if not fused else
                                f"; the loss head forms its gradient with its "
                                f"value over {fused:.0f} rows a micro-batch "
                                f"(train/loss_head/fused)")
                             + ("" if not one + two else
                                f"; the flash kernel's backward is one call "
                                f"in {one:.0f} of the {one + two:.0f} traced "
                                f"(train/flash/bwd_fused, the rest "
                                f"train/flash/bwd_split)"), ranks=[0])
                    for name, value in (
                            ("rung", plan.rung),
                            ("kept_bytes", plan.kept_bytes),
                            ("kept_bytes_per_layer",
                             plan.kept_per_layer[plan.rung]),
                            ("limit_bytes", plan.limit_bytes),
                            ("resident_bytes", plan.resident_bytes),
                            ("step_bytes", need)):
                        _tracer.note(f"train/remat/{name}", value)
                    return compiled
                needs = (f"needs {need / 2**30:.2f} GiB of a limit of "
                         f"{plan.limit_bytes / 2**30:.2f}")
            logger.warning(
                "activation checkpointing: the step at rung %d (%s) %s; "
                "compiling again one rung lower", plan.rung, plan.what, needs)
            self.remat_plan = dataclasses.replace(plan, rung=plan.rung + 1)

    @jax.named_scope("optimizer")
    def _apply_grads(self, state, grads):
        """Clip, check overflow, optimizer update on the fp32 master, cast
        back. Its operations carry the scope ``optimizer`` in a device trace."""
        cfg = self.config
        fp16 = cfg.fp16
        clip = cfg.gradient_clipping

        gnorm = global_norm(grads)
        overflow = has_overflow(grads) if fp16.enabled else jnp.bool_(False)
        if clip > 0:
            cscale = jnp.minimum(1.0, clip / (gnorm + 1e-6))
            grads = jax.tree_util.tree_map(lambda g: g * cscale, grads)

        lr = self._lr_fn(state["step"])

        def do_update(operand):
            master, opt = operand
            new_master, new_opt = self.optimizer.update(grads, opt, master, lr=lr)
            return new_master, new_opt

        def skip_update(operand):
            return operand

        new_master, new_opt = jax.lax.cond(overflow, skip_update, do_update,
                                           (state["master"], state["opt"]))
        scaler_full = dict(state["scaler"], dynamic=self._scaler_dynamic)
        new_scaler = update_loss_scale(
            scaler_full, overflow, loss_scale_window=fp16.loss_scale_window,
            hysteresis=fp16.hysteresis, min_loss_scale=fp16.min_loss_scale)
        new_state = {
            "master": new_master,
            "opt": new_opt,
            "step": state["step"] + jnp.where(overflow, 0, 1).astype(jnp.int32),
            "scaler": {k: new_scaler[k] for k in ("scale", "growth_tracker", "hysteresis")},
            "skipped": state["skipped"] + overflow.astype(jnp.int32),
        }
        if self.quantized_weights:
            from deepspeed_tpu.runtime.zero.zeropp import quantize_param_tree
            new_state["params"] = jax.lax.with_sharding_constraint(
                quantize_param_tree(new_master, self.compute_dtype),
                self._state_shardings["params"])
        elif self.mixed_precision:
            param_sh = self._state_shardings["params"]
            new_params = jax.lax.with_sharding_constraint(
                tree_cast(new_master, self.compute_dtype), param_sh)
            new_state["params"] = new_params
        metrics = {"grad_norm": gnorm, "lr": lr, "overflow": overflow,
                   "loss_scale": new_scaler["scale"]}
        return new_state, metrics

    # ------------------------------------------------------------------ #
    # public API
    # ------------------------------------------------------------------ #

    def _ensure_state(self, batch):
        if self.state is not None:
            return
        if not (hasattr(self.module, "init") and hasattr(self.module, "apply")):
            raise ValueError("model_parameters required for non-flax models")
        from deepspeed_tpu.runtime.data_pipeline import as_host_tree
        # Lazy init from the first microbatch (parity: zero.Init-style sharded
        # init): only the abstract tree exists here; the values are made
        # inside _init_state's jitted, sharded build. One row per data shard,
        # the least the step itself ever sees: model code that shards its
        # batch over the mesh (attention kernels under shard_map) must be
        # able to trace on it.
        rows = self.topology.dp_world_size
        micro = jax.tree_util.tree_map(lambda x: x[:rows], as_host_tree(batch))
        self._rng, init_rng = jax.random.split(self._rng)

        def init_params(rng):
            return self.module.init(rng, micro)["params"]

        self._build_state(jax.eval_shape(init_params, init_rng), init_params,
                          init_rng)

    def _inject_pld(self, batch, leading: int, step: Optional[int] = None,
                    micro: Optional[int] = None):
        """Thread theta + a per-step key through the batch so the jitted step
        sees them as inputs (no retrace per theta change); models read
        batch["pld_theta"]/["pld_rng"] (parity: engine.py:1812 passing pld
        state into module kwargs). Used by BOTH train_batch and the
        forward/backward facade; keys derive from (step[, micro]) folds so
        prefetched and sync staging draw identical streams."""
        if self.progressive_layer_drop is None or not isinstance(batch, dict):
            return batch
        from deepspeed_tpu.runtime.data_pipeline import inject_pld
        step = self.global_steps if step is None else step
        key = jax.random.fold_in(self._pld_base_key, step)
        if micro is not None:
            key = jax.random.fold_in(key, micro)
        return inject_pld(batch, leading,
                          self.progressive_layer_drop.theta_at(step), key)

    def _scheduled_seqlen(self, step: int) -> Optional[int]:
        """Curriculum seqlen for a global step — a PURE schedule read, safe
        from the PrefetchLoader producer staging future steps."""
        if (self.curriculum_scheduler is None
                or self.config.curriculum_learning.curriculum_type != "seqlen"):
            return None
        return int(self.curriculum_scheduler.get_difficulty(step))

    def _staging_is_stale(self, staged_step: int) -> bool:
        """Would a batch staged for ``staged_step`` differ from one staged
        for the CURRENT step? PLD keys are per-step; curriculum matters only
        when the schedule actually moved between the two steps."""
        if self.progressive_layer_drop is not None:
            return True
        return (self._scheduled_seqlen(staged_step)
                != self._scheduled_seqlen(self.global_steps))

    def _apply_curriculum(self, batch, seqlen: int):
        """Truncate to the scheduled seqlen, bucketed by difficulty_step so
        XLA recompiles once per bucket (parity: curriculum seqlen hook).
        The cache key is the MAX width over every rank>=2 leaf (any one of
        them changing invalidates it), so off bucket boundaries the no-op
        decision skips the truncation tree_map; slices are numpy views, so
        no step ever copies."""
        width = max((int(np.shape(x)[1])
                     for x in jax.tree_util.tree_leaves(batch)
                     if len(np.shape(x)) >= 2), default=0)
        if self._curr_seqlen_state == (seqlen, width, False):
            return batch
        from deepspeed_tpu.runtime.data_pipeline import truncate_to_seqlen
        need = width > seqlen
        self._curr_seqlen_state = (seqlen, width, need)
        return truncate_to_seqlen(batch, seqlen) if need else batch

    def _prepare_batch(self, batch, step: int):
        """Host-side staging for global step ``step``: curriculum truncation,
        PLD injection, and the sharded device placement. Runs on the caller's
        thread (sync mode / explicit batches) or on the PrefetchLoader
        producer — everything schedule-dependent is keyed by ``step``, never
        read from mutable engine counters, so staging ahead is exact."""
        from deepspeed_tpu.runtime.data_pipeline import StagedBatch
        self._ensure_state(batch)
        raw = batch   # pre-schedule view: flops profiling + restage-on-mix
        seqlen = self._scheduled_seqlen(step)
        if seqlen is not None:
            batch = self._apply_curriculum(batch, seqlen)
        batch = self._inject_pld(batch, self.train_batch_size_, step=step)
        return StagedBatch(self._shard_global_batch(batch), step, raw=raw)

    def _shard_global_batch(self, batch):
        """Host-side: reshape [tb, ...] -> [gas, mb*dp, ...] and place sharded."""
        from deepspeed_tpu.runtime.data_pipeline import as_host_tree
        mesh = self.topology.mesh
        sh = NamedSharding(mesh, P(None, BATCH_AXES))

        def place(x):
            if x.shape[0] != self.train_batch_size_:
                raise ValueError(
                    f"batch leading dim {x.shape[0]} != train_batch_size {self.train_batch_size_}")
            x = x.reshape((self.gas_, -1) + x.shape[1:])
            return jax.device_put(x, sh)

        return jax.tree_util.tree_map(place, as_host_tree(batch))

    def _compiler_options(self, backend: Optional[str] = None):
        """ZeRO bucket sizes -> XLA collective-combiner thresholds, applied to
        the jitted step's compile options (parity: ``reduce_bucket_size`` /
        ``allgather_bucket_size``, reference ``runtime/zero/config.py`` — there
        they bound hand-scheduled collective buckets; here they bound XLA's
        collective combining). TPU-only flags: other backends reject them."""
        backend = backend or jax.default_backend()
        if backend != "tpu":
            return None
        z = self.config.zero_optimization
        opts = {}
        if z.stage >= 1 and self._zero3_plan is None:
            # the explicit collective schedule retires these hints: bucket
            # sizes bound the scheduled waves/buckets directly, and leaving
            # XLA's combiner free to re-fuse them would fight the barriers
            # (see runtime/zero/partition.py xla_bucket_flags deprecation note)
            from deepspeed_tpu.runtime.zero.partition import xla_bucket_flags
            opts.update(xla_bucket_flags(z.reduce_bucket_size,
                                         z.allgather_bucket_size))
        # user-pinned compile options win over the derived ones. Python bools
        # must become XLA's lowercase 'true'/'false' — str(True) is 'True',
        # which XLA flag parsing rejects or ignores.
        opts.update({k: (str(v).lower() if isinstance(v, bool) else str(v))
                     for k, v in self.config.xla_compile_options.items()})
        return opts or None

    def _build_data_iterator(self):
        """Iterator over the engine's own dataloader: RepeatingLoader for
        epoch auto-bump, wrapped in a PrefetchLoader staging device-resident
        batches when ``train_pipeline.prefetch > 0``."""
        from deepspeed_tpu.runtime.data_pipeline import PrefetchLoader
        from deepspeed_tpu.runtime.dataloader import RepeatingLoader
        it = RepeatingLoader(self.training_dataloader)
        depth = self.config.train_pipeline.prefetch
        if depth > 0:
            it = PrefetchLoader(it, prepare=self._prepare_batch,
                                prefetch=depth, start_step=self.global_steps)
            self._prefetch_loader = it
        return iter(it)

    def _reset_data_iterator(self):
        """Drop the engine-owned iterator (and stop its producer): staged
        batches are keyed to the step counter, so anything that moves it
        (checkpoint load) invalidates them."""
        if self._prefetch_loader is not None:
            self._prefetch_loader.close()
            self._prefetch_loader = None
        self._data_iterator = None

    def train_batch(self, batch=None, data_iter=None):
        """One full training step over a global batch (parity:
        ``PipelineEngine.train_batch`` pipe/engine.py:321 and the
        forward/backward/step cycle engine.py:1779-2118).

        Returns the mean loss as a DEVICE scalar: ``float()`` it to block.
        The steady-state loop is async (docs/TRAINING.md): the next staged
        batch is dequeued (or staged inline), the fused step is dispatched,
        and ``_after_step`` drains the PREVIOUS step's metrics while this
        one runs. ``wall_clock_breakdown`` restores the fully synchronous
        reference loop."""
        if not self._first_step_done:
            return self._first_train_batch(batch, data_iter)
        from deepspeed_tpu.runtime.data_pipeline import StagedBatch
        # mid-run preemption point: a ``step.kill`` fault plan kills here,
        # modelling a spot-VM SIGTERM landing between (or inside) steps
        fault_injection.maybe_fail("step.kill")
        perf = time.perf_counter
        t0 = perf()
        queue_depth = 0
        if batch is None:
            if data_iter is None:
                if self.training_dataloader is None:
                    raise ValueError("train_batch() needs a batch, a data_iter, or "
                                     "training_data passed to initialize()")
                if self._data_iterator is None:
                    self._data_iterator = self._build_data_iterator()
                data_iter = self._data_iterator
            batch = next(data_iter)
            if self._prefetch_loader is not None and data_iter is self._data_iterator:
                queue_depth = self._prefetch_loader.depth
        prefetched = isinstance(batch, StagedBatch)
        if prefetched and batch.step != self.global_steps \
                and self._staging_is_stale(batch.step):
            # the step counter moved outside the pipeline that staged this
            # batch (an explicit train_batch(batch), the facade, a foreign
            # data_iter): its schedule-keyed staging (curriculum seqlen, PLD
            # theta/rng) is for the wrong step — fall back to the raw view so
            # the inline path below restages it at the CURRENT step. Data
            # order is preserved; only the staging work is redone.
            if not self._warned_stale_staging:
                self._warned_stale_staging = True
                logger.warning(
                    "prefetched batch staged for step %d consumed at step %d "
                    "(mixed explicit/argless train_batch?): restaging inline; "
                    "schedule-dependent staging stays on the caller's thread "
                    "until the pipeline is rebuilt", batch.step,
                    self.global_steps)
            batch = batch.raw
            prefetched = False
        t1 = perf()
        if not prefetched:
            self._ensure_state(batch)
        # keep the host-visible difficulty fresh on every path (tests and
        # callbacks read curriculum_scheduler.current_difficulty)
        if self.curriculum_scheduler is not None:
            self.curriculum_scheduler.update_difficulty(self.global_steps)
        # arm (or clear) the ambient schedule the model walk reads; re-set
        # every step — including to None — so late (re)traces (shape changes,
        # a second engine on this thread) see exactly THIS engine's setting,
        # never a plan left armed by a previous scheduled engine
        from deepspeed_tpu.runtime.zero import prefetch as zero3_prefetch
        zero3_prefetch.configure(self._zero3_plan)
        fp_cfg = self.config.flops_profiler
        if fp_cfg.enabled and self.global_steps + 1 == fp_cfg.profile_step:
            raw = batch.raw if prefetched else batch
            if raw is not None:
                self._run_flops_profile(raw)
        self.tput_timer.start()
        self.timers(STEP_GLOBAL_TIMER).start()
        step_no = self.global_steps   # _after_step bumps it before t4
        staged = batch if prefetched else self._prepare_batch(batch,
                                                              self.global_steps)
        t2 = perf()
        if self._offload is not None:
            metrics = self._offload_train_step(staged.tree)
        else:
            if self._fused_step is None:
                self._fused_step = self._make_fused_step(staged.tree)
            self.state, metrics = self._fused_step(self.state, staged.tree)
        t3 = perf()
        # Only force a device sync for exact timings when the user asked for a
        # wall-clock breakdown (parity: reference timers run under the
        # wall_clock_breakdown flag). An unconditional block_until_ready here
        # serialises dispatch — each step would wait for the device instead
        # of queueing behind the previous one.
        sync = metrics["loss"] if self.config.wall_clock_breakdown else None
        self.timers(STEP_GLOBAL_TIMER).stop(sync_obj=sync)
        self.tput_timer.stop(sync_obj=sync)
        self._after_step(metrics)   # enqueue + one-step-late drain
        t4 = perf()
        self.train_stats.record_step(
            wait_s=(t1 - t0) if prefetched else 0.0,
            build_s=(t2 - t1) + (0.0 if prefetched else (t1 - t0)),
            dispatch_s=t3 - t2, drain_s=t4 - t3, wall_s=t4 - t0,
            queue_depth=queue_depth, prefetched=prefetched)
        if _tracer.enabled:
            # the SAME perf pairs the stats aggregated, as timeline spans
            # (phases nested under one step span on the train/step track).
            # Inline staging counts t0..t1 (batch fetch) into build_s, so
            # the span must cover it too — stats and spans never diverge
            if prefetched:
                _tracer.add("train/step/dequeue_wait", t0, t1,
                            lane="train/step", step=step_no)
            _tracer.add("train/step/host_build", t1 if prefetched else t0,
                        t2, lane="train/step", step=step_no)
            _tracer.add("train/step/dispatch", t2, t3, lane="train/step",
                        step=step_no)
            _tracer.add("train/step/drain", t3, t4, lane="train/step",
                        step=step_no)
            _tracer.add("train/step", t0, t4, lane="train/step", step=step_no,
                        prefetched=prefetched)
            if queue_depth:
                _tracer.counter("train/prefetch/queue_depth", queue_depth,
                                lane="train/step")
        return metrics["loss"]

    def _first_train_batch(self, batch, data_iter):
        """The first step as the last stage of set-up (``tracer.stage``): it
        builds the state if the engine was given none and compiles the step,
        and the stage closes when its loss has been fetched, so
        ``setup/first_step_s`` (net of the ``state_build`` and ``remat_fit``
        inside it) is over before the second step is dispatched. Then the one
        line that says where set-up went."""
        self._first_step_done = True
        with _tracer.stage("first_step"):
            # (not self.train_batch: a subclass wraps it)
            loss = DeepSpeedTPUEngine.train_batch(self, batch, data_iter)
            jax.block_until_ready(loss)
        from deepspeed_tpu.utils.compile_cache import setup_summary
        log_dist(f"engine: {setup_summary()}", ranks=[0])
        return loss

    def train_steps(self, n_steps: int, data_iter=None) -> np.ndarray:
        """Run ``n_steps`` fused steps back-to-back, metrics one step in
        flight throughout (the multi-step dispatch loop), then drain once.

        Returns the per-step loss stream as a float32 ``[n_steps]`` array —
        materialised at the END of the burst, so the loop itself never blocks
        on a metric fetch. Batches come from ``data_iter`` (host batches or a
        PrefetchLoader's staged ones) or the engine's own pipeline."""
        losses = []
        for _ in range(int(n_steps)):
            losses.append(self.train_batch(data_iter=data_iter))
        self.drain_metrics()
        return np.asarray([float(l) for l in losses], np.float32)

    def _run_flops_profile(self, batch):
        """Profile the model forward at ``profile_step`` (parity: flops-profiler
        engine hooks, reference engine.py:1808-1850, 2188-2200)."""
        from deepspeed_tpu.profiling import FlopsProfiler
        from deepspeed_tpu.runtime.data_pipeline import as_host_tree
        fp_cfg = self.config.flops_profiler
        prof = FlopsProfiler(fp_cfg)
        micro = jax.tree_util.tree_map(
            lambda x: x[:max(1, self.micro_batch_size_)], as_host_tree(batch))
        params = self._current_params(self.state)
        if hasattr(self.module, "apply"):
            prof.start_profile(self.module, {"params": params}, micro)
        else:
            prof.start_profile()
        prof.measure(lambda p, b: self._loss_of(p, b), params, micro)
        prof.print_model_profile(profile_step=fp_cfg.profile_step,
                                 module_depth=fp_cfg.module_depth,
                                 top_modules=fp_cfg.top_modules,
                                 detailed=fp_cfg.detailed,
                                 output_file=fp_cfg.output_file)
        if self.monitor.enabled:
            # flops land in the SAME sink as the pipeline stats (train/flops/*)
            # instead of print-only — dashboards see model cost next to the
            # step-loop phase breakdown
            self.monitor.write_events(
                prof.events(step=self.global_samples,
                            top_modules=max(1, fp_cfg.top_modules)))
        prof.end_profile()
        self.flops_profiler = prof

    def _after_step(self, metrics, count_micro_steps: bool = True):
        """Device-side half of the post-step work: counters, schedulers, and
        the metric ENQUEUE. The host-side half (``_emit_metrics``) floats a
        step's metrics ONE STEP LATE — the pre-PR version float()'d here and
        blocked on the just-dispatched step even when nothing was printed.
        ``wall_clock_breakdown`` keeps the reference's synchronous loop by
        draining immediately."""
        self.global_steps += 1
        if self.compression_scheduler is not None:
            self.compression_scheduler.step()
        if self.progressive_layer_drop is not None:
            self.progressive_layer_drop.update_state(self.global_steps)
        self.global_samples += self.train_batch_size_
        if count_micro_steps:
            # facade path counts micro steps in backward(); fused path counts here
            self.micro_steps += self.gas_
        self._last_metrics = metrics
        self._pending_metrics.append(
            (self.global_steps, self.global_samples, metrics))
        self._drain_metric_queue(
            0 if self.config.wall_clock_breakdown else 1)
        if self._rolling is not None:
            # after the counters: a tag named rolling_step{N} holds the state
            # AFTER step N. save() drains the metric queue first (checkpoint
            # boundary) and quiesces the offload pipeline via
            # _offload_ckpt_state before snapshotting host masters.
            self._rolling.maybe_save()

    def drain_metrics(self):
        """Flush every deferred metric entry (blocks on the newest dispatched
        step). Called automatically at checkpoint save/load, ``train_steps``
        exit, and ``destroy()``; call it manually before reading monitor
        output mid-run."""
        self._drain_metric_queue(0)

    def _drain_metric_queue(self, leave: int):
        while len(self._pending_metrics) > leave:
            step, samples, metrics = self._pending_metrics.popleft()
            self._emit_metrics(step, samples, metrics)

    def _emit_metrics(self, step: int, samples: int, metrics):
        """Host-side half of the split ``_after_step``: materialise ONE
        step's metric floats (a single fetch through the drain point) and
        route them to the monitor and the steps_per_print log. When nothing
        consumes them, the entry is dropped without touching the device."""
        every = self.config.steps_per_print
        printing = bool(every and step % every == 0)
        if not (printing or self.monitor.enabled):
            return
        vals = fetch_to_host(metrics)
        if self.monitor.enabled:
            # parity: _write_monitor (engine.py:2259) + loss/lr/scale events
            # (engine.py:1943-1951, 2164-2185); the facade path's step metrics
            # carry no loss
            events = [("Train/Samples/lr", float(vals["lr"]), samples),
                      ("Train/Samples/grad_norm", float(vals["grad_norm"]),
                       samples)]
            if "loss" in vals:
                events.insert(0, ("Train/Samples/train_loss",
                                  float(vals["loss"]), samples))
            if self.config.fp16.enabled:
                events.append(("Train/Samples/loss_scale",
                               float(vals["loss_scale"]), samples))
            self.monitor.write_events(events)
            if printing:
                self.monitor.write_events(self.train_stats.events(samples))
                self.monitor.write_events(_tracer.setup_events(samples))
                if self._offload is not None and self.offload_stats.steps:
                    self.monitor.write_events(
                        self.offload_stats.events(samples))
                if self.ckpt_stats.saves:
                    self.monitor.write_events(self.ckpt_stats.events(samples))
        if printing:
            loss = float(vals["loss"]) if "loss" in vals else float("nan")
            lr = float(vals["lr"])
            log_dist(f"step={step} loss={loss:.4f} lr={lr:.3e} "
                     f"gnorm={float(vals['grad_norm']):.3f}", ranks=[0])
            if self.config.wall_clock_breakdown:
                self.timers.log([FORWARD_GLOBAL_TIMER, BACKWARD_GLOBAL_TIMER,
                                 STEP_GLOBAL_TIMER])

    # -- forward/backward/step facade (reference call discipline) -------- #

    def forward(self, batch):
        """Run one microbatch's fwd+bwd, buffering grads; returns the loss.

        Parity: ``DeepSpeedEngine.forward`` (engine.py:1779) + ``backward``
        (:1920) — in JAX fwd and grad are one computation, so ``forward`` computes
        and buffers the (scaled) gradient and ``backward`` is bookkeeping."""
        from deepspeed_tpu.runtime.data_pipeline import as_host_tree
        self._ensure_state(batch)
        # same contract as train_batch: the micro-step trace sees exactly
        # this engine's schedule setting (None clears a stale ambient plan)
        from deepspeed_tpu.runtime.zero import prefetch as zero3_prefetch
        zero3_prefetch.configure(self._zero3_plan)
        if self._micro_step is None:
            self._build_micro_steps()
        leading = int(np.shape(jax.tree_util.tree_leaves(batch)[0])[0])
        batch = self._inject_pld(batch, leading, micro=self.micro_steps)
        mesh = self.topology.mesh
        sh = NamedSharding(mesh, P(BATCH_AXES))
        mb = jax.tree_util.tree_map(lambda x: jax.device_put(x, sh),
                                    as_host_tree(batch))
        if self._grad_buffer is None:
            self._grad_buffer = self._zero_grad_buffer()
        self.timers(FORWARD_GLOBAL_TIMER).start()
        loss, self._grad_buffer = self._micro_step(self.state, self._grad_buffer, mb)
        self.timers(FORWARD_GLOBAL_TIMER).stop(
            sync_obj=loss if self.config.wall_clock_breakdown else None)
        return loss

    def backward(self, loss=None, **kwargs):
        """Bookkeeping only (the gradient was produced in forward; see above)."""
        self.micro_steps += 1
        return loss

    def is_gradient_accumulation_boundary(self) -> bool:
        """Parity: engine.py:1870."""
        return self.micro_steps % self.gas_ == 0

    def step(self):
        """Apply buffered grads at a GAS boundary (parity: engine.py:2118)."""
        if not self.is_gradient_accumulation_boundary():
            return
        if self._apply_step is None:
            self._build_micro_steps()
        self.timers(STEP_GLOBAL_TIMER).start()
        self.state, metrics = self._apply_step(self.state, self._grad_buffer)
        self.timers(STEP_GLOBAL_TIMER).stop(
            sync_obj=metrics["grad_norm"] if self.config.wall_clock_breakdown
            else None)
        self._grad_buffer = None
        self._after_step(metrics, count_micro_steps=False)

    def _zero_grad_buffer(self):
        accum_dtype = self.config.grad_accum_dtype
        params = self._current_params(self.state)

        def make(x):
            return jnp.zeros(x.shape, accum_dtype)

        with self.topology.mesh:
            buf = jax.jit(lambda t: self._constrain_grads(
                jax.tree_util.tree_map(make, t)))(params)
        return buf

    def _build_micro_steps(self):
        from deepspeed_tpu.runtime import activation_checkpointing
        fp16 = self.config.fp16
        accum_dtype = self.config.grad_accum_dtype
        gas = self.gas_

        def micro(state, buf, mb):
            params = self._current_params(state)
            scale = state["scaler"]["scale"] if fp16.enabled else jnp.float32(1.0)
            # no rung is chosen for this path; a rule is told of the mesh
            with activation_checkpointing.keeping(
                    None, grads_reduced=self.topology.dp_world_size > 1):
                loss, grads = self._grad_fn(params, mb, scale)
            grads = tree_cast(grads, accum_dtype)
            grads = self._constrain_grads(grads)
            buf = jax.tree_util.tree_map(jnp.add, buf, grads)
            return loss, buf

        def apply(state, buf):
            scale = state["scaler"]["scale"] if fp16.enabled else jnp.float32(1.0)
            inv = 1.0 / (gas * scale)
            grads = jax.tree_util.tree_map(lambda g: (g * inv).astype(jnp.float32), buf)
            return self._apply_grads(state, grads)

        self._micro_step = jax.jit(micro, donate_argnums=(1,))
        self._apply_step = jax.jit(apply, donate_argnums=(0, 1))

    # ------------------------------------------------------------------ #
    # dataloader (parity: deepspeed_io engine.py:1684)
    # ------------------------------------------------------------------ #

    def deepspeed_io(self, dataset, batch_size: Optional[int] = None, collate_fn=None,
                     shuffle: bool = True, drop_last: bool = True):
        return DeepSpeedTPUDataLoader(
            dataset,
            batch_size=batch_size or self.train_batch_size_,
            collate_fn=collate_fn,
            shuffle=shuffle,
            seed=self.config.seed,
            drop_last=drop_last)

    # ------------------------------------------------------------------ #
    # checkpointing (parity: engine.py:3028 save_checkpoint / :2679 load)
    # full sharded/universal machinery lives in deepspeed_tpu.checkpoint
    # ------------------------------------------------------------------ #

    def save_checkpoint(self, save_dir: str, tag: Optional[str] = None,
                        client_state: Optional[dict] = None, save_latest: bool = True):
        from deepspeed_tpu.checkpoint.state import save_engine_checkpoint
        self.drain_metrics()   # checkpoint boundary flushes deferred metrics
        tag = tag or f"global_step{self.global_steps}"
        client_state = dict(client_state or {})
        client_state.update({
            "global_steps": self.global_steps,
            "global_samples": self.global_samples,
            "micro_steps": self.micro_steps,
            "skipped_steps": self.get_skipped_steps(),
        })
        state = self._offload_ckpt_state() if self._offload is not None else self.state
        with _tracer.span("ckpt/save", lane="ckpt", tag=tag):
            save_engine_checkpoint(save_dir, tag, state, client_state,
                                   save_latest=save_latest,
                                   ckpt_engine=self._checkpoint_engine(),
                                   stats=self.ckpt_stats)
        return True

    def _checkpoint_engine(self):
        """Configured checkpoint engine, built lazily (parity:
        _configure_checkpointing engine.py:912 picking Torch vs Nebula)."""
        if getattr(self, "_ckpt_engine", None) is None:
            from deepspeed_tpu.checkpoint.engine import build_checkpoint_engine
            ck = self.config.checkpoint
            self._ckpt_engine = build_checkpoint_engine(
                ck.engine,
                config_params={"writers": ck.writers,
                               "writer_retries": ck.writer_retries,
                               "writer_backoff_s": ck.writer_backoff_s})
        return self._ckpt_engine

    def load_checkpoint(self, load_dir: str, tag: Optional[str] = None,
                        load_optimizer_states: bool = True,
                        load_lr_scheduler_states: bool = True,
                        load_module_only: bool = False,
                        verify: Optional[bool] = None):
        """``verify=True`` checksums every loaded shard against the tag's
        manifest (default: ``config.checkpoint.verify_load``). ``tag=None``
        resumes from the newest COMPLETE tag, skipping torn ones."""
        from deepspeed_tpu.checkpoint.state import load_engine_checkpoint
        if self.state is None:
            raise RuntimeError("engine state not initialised; pass model_parameters "
                               "or run a batch before load_checkpoint")
        if verify is None:
            verify = self.config.checkpoint.verify_load
        # flush metrics of the pre-load stream, and drop staged batches: the
        # step counter is about to move, invalidating schedule-keyed staging
        self.drain_metrics()
        self._reset_data_iterator()
        if self.config.checkpoint.load_universal:
            from deepspeed_tpu.checkpoint.universal import load_universal_into_engine
            if tag is not None:
                logger.warning("load_universal: universal checkpoints are "
                               f"untagged directories; ignoring tag={tag!r}")
            client_state = load_universal_into_engine(
                self, load_dir, load_optimizer_states=load_optimizer_states,
                load_module_only=load_module_only)
            return load_dir, client_state
        if self._offload is not None:
            load_dir_, client_state = self._load_checkpoint_offload(
                load_dir, tag, load_optimizer_states=load_optimizer_states,
                load_module_only=load_module_only, verify=verify)
            self.global_steps = int(client_state.get("global_steps", 0))
            self.global_samples = int(client_state.get("global_samples", 0))
            self.micro_steps = int(client_state.get("micro_steps", 0))
            self.skipped_steps = int(client_state.get("skipped_steps", 0))
            return load_dir_, client_state
        params_builder = None
        if self.quantized_weights:
            from deepspeed_tpu.runtime.zero.zeropp import quantize_param_tree
            params_builder = lambda m: quantize_param_tree(m, self.compute_dtype)
        state, client_state = load_engine_checkpoint(
            load_dir, tag, self.state, self._state_shardings,
            load_optimizer_states=load_optimizer_states,
            load_module_only=load_module_only, params_builder=params_builder,
            ckpt_engine=self._checkpoint_engine(), verify=verify)
        self.state = state
        self.global_steps = int(client_state.get("global_steps", 0))
        self.global_samples = int(client_state.get("global_samples", 0))
        self.micro_steps = int(client_state.get("micro_steps", 0))
        self.skipped_steps = int(client_state.get("skipped_steps", 0))
        return load_dir, client_state

    def destroy(self):
        """Release host-side resources (parity: ``DeepSpeedEngine.destroy``):
        the prefetch producer, deferred metrics, the offload optimizer's AIO
        pools/swap files, and monitor writers."""
        # disarm the ambient ZeRO-3 schedule: the documented contract is that
        # stage3_prefetch_depth=None engines are bit-for-bit untouched, so a
        # destroyed engine must never leave its plan for a later engine's
        # trace (train_batch/eval_loss also re-set it defensively each call)
        from deepspeed_tpu.runtime.zero import prefetch as zero3_prefetch
        zero3_prefetch.configure(None)
        self._reset_data_iterator()
        self.drain_metrics()
        rolling_err = None
        if self._rolling is not None:
            # BEFORE the checkpoint engine closes: queued rolling commits
            # need live writer threads to drain against. A surfaced commit
            # error must not abort the rest of the teardown — pools, AIO
            # handles and writers still have to close — so it re-raises
            # only after everything below ran
            try:
                self._rolling.close()
            except BaseException as e:
                rolling_err = e
        if self._offload is not None:
            self._drain_offload()
            if self._offload_executor is not None:
                self._offload_executor.shutdown(wait=True)
                self._offload_executor = None
            if self._offload_upload_pool is not None:
                self._offload_upload_pool.shutdown(wait=True)
                self._offload_upload_pool = None
            self._offload.close()
        if getattr(self, "_ckpt_engine", None) is not None:
            close = getattr(self._ckpt_engine, "close", None)
            if close is not None:
                # a failed bare-save writer surfaces here; like the rolling
                # error it must not abort the remaining teardown or shadow
                # the (earlier, more specific) rolling-commit failure
                try:
                    close()
                except BaseException as e:
                    if rolling_err is None:
                        rolling_err = e
        close = getattr(self.monitor, "close", None)
        if close is not None:
            close()
        if rolling_err is not None:
            # fatal teardown: leave the flight-recorder timeline next to the
            # surfaced error before re-raising (a commit failure's postmortem
            # needs the spans that led up to it)
            _tracer.crash_dump(f"engine destroy: {type(rolling_err).__name__}")
            raise rolling_err
        _tracer.export()

    # ------------------------------------------------------------------ #
    # property surface (parity: engine.py:469-870 accessors)
    # ------------------------------------------------------------------ #

    def train_micro_batch_size_per_gpu(self) -> int:
        return self.micro_batch_size_

    def train_batch_size(self) -> int:
        return self.train_batch_size_

    def gradient_accumulation_steps(self) -> int:
        return self.gas_

    def zero_optimization_stage(self) -> int:
        return self.zero_stage

    def zero_optimization(self) -> bool:
        return self.zero_stage > 0

    def get_lr(self):
        if self.state is None:
            return [float(self._lr_fn(jnp.zeros((), jnp.int32)))]
        return [float(self._lr_fn(self.state["step"]))]

    def get_global_grad_norm(self):
        m = self._last_metrics.get("grad_norm")
        return float(m) if m is not None else None

    def get_skipped_steps(self) -> int:
        """Overflow-skipped step count (device counter; parity: engine skipped_steps)."""
        if self.state is None:
            return self.skipped_steps
        return int(self.state["skipped"])

    @property
    def cur_scale(self):
        if self.state is None:
            return 1.0
        return float(self.state["scaler"]["scale"])

    @property
    def global_rank(self) -> int:
        return dist.get_rank()

    @property
    def world_size(self) -> int:
        return self.topology.world_size

    def get_params(self):
        """Current model params (compute dtype) — the tree users hand to eval fns."""
        if self.state is None:
            return None
        return self._current_params(self.state)

    def module_state_dict(self):
        """Full (unsharded) param pytree on host (parity:
        ``_zero3_consolidated_16bit_state_dict`` engine.py:3440: gather is implicit
        in device_get of a sharded Array)."""
        return fetch_to_host(self.get_params())

    @property
    def compiles(self) -> int:
        """Cumulative XLA program builds across the engine's jitted steps —
        the executable-cache sizes of the fused/micro/apply/eval steps. A
        steady-state loop whose batch shapes are stable must never increment
        this after warmup (curriculum buckets each cost exactly one); the
        training tests assert it."""
        n = 0
        for fn in (self._fused_step, self._micro_step, self._apply_step,
                   self._eval_step, getattr(self, "_offload_merge", None)):
            size = getattr(fn, "_cache_size", None)
            if size is not None:
                n += size()
        return n

    def eval_loss(self, batch) -> float:
        """Forward-only loss on a global batch (no state change)."""
        from deepspeed_tpu.runtime.data_pipeline import as_host_tree
        self._ensure_state(batch)
        params = self._current_params(self.state)
        mesh = self.topology.mesh
        sh = NamedSharding(mesh, P(BATCH_AXES))
        mb = jax.tree_util.tree_map(lambda x: jax.device_put(x, sh),
                                    as_host_tree(batch))
        # always re-set (even to None): the eval trace must see this
        # engine's schedule setting, not a plan another engine left armed
        from deepspeed_tpu.runtime.zero import prefetch as zero3_prefetch
        zero3_prefetch.configure(self._zero3_plan)
        if self._eval_step is None:
            def train_eval_loss(params, batch):
                return self._loss_of(params, batch)
            self._eval_step = jax.jit(train_eval_loss)
        return float(self._eval_step(params, mb))
