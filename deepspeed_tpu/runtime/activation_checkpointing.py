"""Activation checkpointing (rematerialisation) subsystem.

Parity: ``deepspeed/runtime/activation_checkpointing/checkpointing.py`` —
``configure`` (:1070), ``checkpoint``/``CheckpointFunction`` (:484),
``partition_activations`` (:373), CPU checkpointing, and the RNG-state tracker
(``CudaRNGStatesTracker`` :122) that makes dropout deterministic across the
recompute.

TPU-first redesign: the reference re-runs the forward inside ``torch.autograd``
with hand-managed stashing (partitioned buffers across TP ranks, optional copies to
host). Under XLA the same capability is a **remat policy** on ``jax.checkpoint``:

- plain checkpointing            -> ``nothing_saveable`` (recompute everything)
- selective ("save the matmuls") -> ``dots_saveable`` / named saveables
- ``partition_activations``      -> under SPMD, saved residuals simply *keep* their
  ``NamedSharding`` — XLA stores the shard, not a replicated copy, so the
  reference's scatter/gather machinery (checkpointing.py:264,373) has no runtime
  equivalent to build; we select a policy that saves (sharded) layer boundaries.
- ``cpu_checkpointing``          -> host offload of saved residuals
  (``save_and_offload_only_these_names`` / ``offload_dot_with_no_batch_dims``,
  XLA memory space ``pinned_host``).
- RNG determinism                -> JAX PRNG keys are values, so the recompute sees
  the identical key by construction; ``RNGStatesTracker`` exists for API parity
  and for Megatron-style named-seed management.

Models call ``apply_remat(BlockClass, config, static_argnums=...)`` at build time;
user code may also use the reference-shaped ``checkpoint(fn, *args)``.
"""

from __future__ import annotations

import contextlib
from typing import Any, Callable, Dict, Optional, Sequence

import jax
from jax import checkpoint_policies as _cp

from deepspeed_tpu.utils.logging import logger

# --------------------------------------------------------------------------- #
# Policy registry
# --------------------------------------------------------------------------- #

#: Name -> zero-arg factory returning a jax.checkpoint policy (or None = full remat).
#: Mirrors the reference's knob set (checkpointing.py:1070 configure) plus the
#: TPU-idiomatic selective policies the compiler understands.
POLICIES: Dict[str, Callable[[], Optional[Callable]]] = {
    "none": lambda: None,  # full recompute (reference default `checkpoint()`)
    "nothing_saveable": lambda: _cp.nothing_saveable,
    "everything_saveable": lambda: _cp.everything_saveable,
    "dots_saveable": lambda: _cp.dots_saveable,
    "dots_with_no_batch_dims_saveable": lambda: _cp.dots_with_no_batch_dims_saveable,
    # host-offload variants (parity: cpu_checkpointing, checkpointing.py:546-560)
    "offload_dots": lambda: _cp.offload_dot_with_no_batch_dims(
        offload_src="device", offload_dst="pinned_host"),
    # selective: save only per-layer attention outputs (tagged by the zoo
    # models via checkpoint_name "attn_out") — backward skips recomputing the
    # attention kernel, costing only B*T*C per layer of extra residency.
    "attn_out_saveable": lambda: _cp.save_only_these_names("attn_out"),
    "offload_attn_out": lambda: _cp.save_and_offload_only_these_names(
        names_which_can_be_saved=[], names_which_can_be_offloaded=["attn_out"],
        offload_src="device", offload_dst="pinned_host"),
}


def named_saveable_policy(names: Sequence[str], offload: bool = False):
    """Save (or offload) only activations tagged ``jax.ad_checkpoint.checkpoint_name``.

    The TPU analog of the reference's explicit "stash these tensors" list.
    """
    if offload:
        return _cp.save_and_offload_only_these_names(
            names_which_can_be_saved=[],
            names_which_can_be_offloaded=list(names),
            offload_src="device", offload_dst="pinned_host")
    return _cp.save_only_these_names(*names)


def resolve_policy(name_or_policy) -> Optional[Callable]:
    """Accept a registry name, a policy callable, or None."""
    if name_or_policy is None:
        return None
    if callable(name_or_policy):
        return name_or_policy
    try:
        return POLICIES[str(name_or_policy)]()
    except KeyError:
        raise ValueError(
            f"unknown remat policy {name_or_policy!r}; known: {sorted(POLICIES)}")


# --------------------------------------------------------------------------- #
# Module-level configuration (parity: checkpointing.configure / is_configured)
# --------------------------------------------------------------------------- #

class _CheckpointingState:
    def __init__(self):
        self.configured = False
        self.partition_activations = False
        self.cpu_checkpointing = False
        self.contiguous_memory_optimization = False
        self.number_checkpoints: Optional[int] = None
        self.synchronize = False
        self.profile = False
        self.policy: Optional[Callable] = None


_STATE = _CheckpointingState()


def configure(deepspeed_config=None,
              partition_activations: Optional[bool] = None,
              contiguous_checkpointing: Optional[bool] = None,
              num_checkpoints: Optional[int] = None,
              checkpoint_in_cpu: Optional[bool] = None,
              synchronize: Optional[bool] = None,
              profile: Optional[bool] = None) -> None:
    """Parity: ``checkpointing.configure`` (checkpointing.py:1070).

    ``deepspeed_config`` may be a ``DeepSpeedTPUConfig`` (its
    ``activation_checkpointing`` block is read) or an
    ``ActivationCheckpointingConfig``; keyword args override.
    """
    cfg = getattr(deepspeed_config, "activation_checkpointing", deepspeed_config)
    if cfg is not None:
        _STATE.partition_activations = getattr(cfg, "partition_activations", False)
        _STATE.cpu_checkpointing = getattr(cfg, "cpu_checkpointing", False)
        _STATE.contiguous_memory_optimization = getattr(
            cfg, "contiguous_memory_optimization", False)
        _STATE.number_checkpoints = getattr(cfg, "number_checkpoints", None)
        _STATE.synchronize = getattr(cfg, "synchronize_checkpoint_boundary", False)
        _STATE.profile = getattr(cfg, "profile", False)
    if partition_activations is not None:
        _STATE.partition_activations = partition_activations
    if contiguous_checkpointing is not None:
        _STATE.contiguous_memory_optimization = contiguous_checkpointing
    if num_checkpoints is not None:
        _STATE.number_checkpoints = num_checkpoints
    if checkpoint_in_cpu is not None:
        _STATE.cpu_checkpointing = checkpoint_in_cpu
    if synchronize is not None:
        _STATE.synchronize = synchronize
    if profile is not None:
        _STATE.profile = profile

    if _STATE.cpu_checkpointing:
        _STATE.policy = POLICIES["offload_dots"]()
    elif _STATE.partition_activations:
        # saved residuals keep their NamedSharding under SPMD; save the big
        # matmul outputs, recompute pointwise ops.
        _STATE.policy = POLICIES["dots_with_no_batch_dims_saveable"]()
    else:
        _STATE.policy = None
    _STATE.configured = True
    logger.debug("activation checkpointing configured: partition=%s cpu=%s n=%s",
                 _STATE.partition_activations, _STATE.cpu_checkpointing,
                 _STATE.number_checkpoints)


def is_configured() -> bool:
    """Parity: ``checkpointing.is_configured`` (checkpointing.py:1104)."""
    return _STATE.configured


def reset() -> None:
    global _STATE
    _STATE = _CheckpointingState()


def current_policy() -> Optional[Callable]:
    return _STATE.policy if _STATE.configured else None


# --------------------------------------------------------------------------- #
# checkpoint() — the user-facing wrapper (parity: CheckpointFunction :484)
# --------------------------------------------------------------------------- #

def checkpoint(function: Callable, *args, policy=None, static_argnums=(), **kwargs):
    """Recompute ``function(*args)`` in the backward pass.

    Reference shape: ``deepspeed.checkpointing.checkpoint(fn, *args)``
    (checkpointing.py:484 CheckpointFunction.forward). Under jit this is
    ``jax.checkpoint`` with the configured policy; RNG keys in ``args`` flow
    through unchanged, so dropout is deterministic across the recompute without
    the reference's fork/restore of device RNG states (:122).
    """
    pol = resolve_policy(policy) if policy is not None else current_policy()
    fn = jax.checkpoint(function, policy=pol, static_argnums=static_argnums)
    return fn(*args, **kwargs)


def checkpoint_wrapper(function: Callable, policy=None, static_argnums=()):
    """Return a remat-wrapped callable (decorator form)."""
    pol = resolve_policy(policy) if policy is not None else current_policy()
    return jax.checkpoint(function, policy=pol, static_argnums=static_argnums)


def apply_remat(block_cls, remat: bool = True, policy=None, static_argnums=()):
    """Wrap a flax module class in ``nn.remat`` with the configured policy.

    For whole-class wrapping; model layer stacks use
    :func:`apply_checkpointed_layers`, which additionally honours
    ``number_checkpoints`` chunking.
    """
    if not remat:
        return block_cls
    import flax.linen as nn
    pol = resolve_policy(policy) if policy is not None else current_policy()
    return nn.remat(block_cls, policy=pol, static_argnums=static_argnums)


def layer_chunks(n_layers: int) -> list:
    """Chunk boundaries [(start, end), ...] for checkpointed layer application.

    Parity: ``num_checkpoints`` is "the number of activation checkpoints stored
    during the forward" (checkpointing.py:1097) — layers are partitioned into
    that many chunks and only chunk-boundary activations survive; everything
    inside a chunk recomputes in backward. Fewer checkpoints => less memory,
    more recompute. Default (unset): one chunk per layer.
    """
    k = _STATE.number_checkpoints if _STATE.configured and _STATE.number_checkpoints \
        else n_layers
    k = max(1, min(int(k), n_layers))
    per = -(-n_layers // k)  # ceil
    return [(s, min(s + per, n_layers)) for s in range(0, n_layers, per)]


def apply_checkpointed_layers(module, carry, call_layer, n_layers: int,
                              remat: bool = True, policy=None, *,
                              layers=None, layer_args=(), post_layer=None):
    """Apply ``n_layers`` layers with chunked rematerialisation.

    ``call_layer(module, carry, i) -> carry`` applies layer ``i``; layers must be
    reachable through ``module`` (setup-defined submodule lists), the flax lifted
    -transform contract. Model builders use this so the
    ``activation_checkpointing`` config block uniformly drives every family.

    When the engine arms a ZeRO-3 collective schedule
    (``zero_optimization.stage3_prefetch_depth``; ``runtime/zero/prefetch.py``)
    and the model passes its bound layer stack via ``layers``, the walk routes
    through the scheduled wave path instead: tie-pinned bucketed all-gathers
    ``depth`` waves ahead of compute, wave-granular rematerialisation (the
    schedule subsumes this function's chunked remat — gathered params are
    never saved, so recompute is what frees them), reverse-order backward
    re-gathers and reduce-scatter pipelined into each wave's backward.
    ``layer_args`` are extra positional args for every layer call and
    ``post_layer(new_x, prev_x, i)`` wraps each layer's output (progressive
    layer drop). Models whose walk needs flax RNGs or a non-array carry keep
    ``layers=None`` and always take the unscheduled path.
    """
    if layers is not None:
        from deepspeed_tpu.runtime.zero import prefetch
        if prefetch.current_plan() is not None:
            out = prefetch.scheduled_layer_walk(
                list(layers)[:n_layers], carry,
                layer_args=tuple(layer_args), post_layer=post_layer)
            if out is not None:
                return out
    if not remat:
        for i in range(n_layers):
            carry = call_layer(module, carry, i)
        return carry
    import flax.linen as nn
    pol = resolve_policy(policy) if policy is not None else current_policy()

    def chunk(mdl, carry, s, e):
        for i in range(s, e):
            carry = call_layer(mdl, carry, i)
        return carry

    rchunk = nn.remat(chunk, policy=pol, static_argnums=(2, 3))
    for s, e in layer_chunks(n_layers):
        carry = rchunk(module, carry, s, e)
    return carry


# --------------------------------------------------------------------------- #
# RNG state tracker (parity: CudaRNGStatesTracker checkpointing.py:122)
# --------------------------------------------------------------------------- #

class RNGStatesTracker:
    """Named PRNG-key registry with a fork context.

    The reference tracks mutable device RNG *states* and swaps them around the
    recompute; JAX keys are immutable values so determinism is structural. This
    tracker exists for Megatron-style named seeds ("model-parallel-rng") and is
    the hook point for TP-rank seed decorrelation (fold_in of the tp axis index).
    """

    def __init__(self):
        self.states_: Dict[str, jax.Array] = {}

    def reset(self):
        self.states_.clear()

    def get_states(self) -> Dict[str, jax.Array]:
        return dict(self.states_)

    def set_states(self, states: Dict[str, jax.Array]):
        self.states_ = dict(states)

    def add(self, name: str, seed: int):
        if name in self.states_:
            raise ValueError(f"rng state {name} already present")
        self.states_[name] = jax.random.PRNGKey(seed)

    @contextlib.contextmanager
    def fork(self, name: str = "model-parallel-rng"):
        """Yield a fresh subkey for ``name`` and advance the stored key."""
        if name not in self.states_:
            raise KeyError(f"rng state {name} not added")
        key, sub = jax.random.split(self.states_[name])
        self.states_[name] = key
        yield sub


_RNG_TRACKER = RNGStatesTracker()


def get_cuda_rng_tracker() -> RNGStatesTracker:  # reference-shaped name
    return _RNG_TRACKER


def model_parallel_rng_tracker() -> RNGStatesTracker:
    return _RNG_TRACKER


def model_parallel_seed(base_seed: int, tp_rank: int) -> jax.Array:
    """Decorrelated per-TP-rank dropout key (parity:
    ``model_parallel_cuda_manual_seed`` checkpointing.py:222): fold the tp index
    into the base key so ranks drop different units on TP-partitioned
    activations but share the key elsewhere."""
    return jax.random.fold_in(jax.random.PRNGKey(base_seed), tp_rank)
