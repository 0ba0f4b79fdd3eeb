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

**When nobody names a policy.** A model built with ``remat=True`` and no
``remat_policy``, under a config whose ``activation_checkpointing`` block sets
nothing, used to keep only each layer's input and run the layer's forward a
second time in the backward. The train engine now picks, once, the first rung
of :data:`LADDER` that the chip has room for (:func:`choose_rung`): the device's
memory limit against the train state it holds at rest, the gradient
accumulator, and what each rung keeps a layer — read from a trace of one layer
(:func:`kept_bytes`), no compile. The step the engine compiles is the guard:
a program whose ``memory_analysis()`` exceeds the limit, or that the TPU
compiler refuses for memory, is compiled again one rung lower
(``engine._compile_fitted``). Full recompute stays spelled
``remat_policy="none"``; a named
policy, any of ``partition_activations`` / ``cpu_checkpointing`` /
``number_checkpoints``, the explicit ZeRO-3 schedule (its waves recompute to
free gathered parameters) and a device that reports no memory limit (the CPU
backend) keep exactly what they had.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
from jax import checkpoint_policies as _cp
from jax.extend.core import Literal as _Literal

from deepspeed_tpu.utils.logging import logger
from deepspeed_tpu.utils.tree import tree_size_bytes

# --------------------------------------------------------------------------- #
# Policy registry
# --------------------------------------------------------------------------- #

def narrow_dots_saveable(prim, *avals, **params) -> bool:
    """The products with no batch dimension whose output is no wider than
    what they contract over: in a transformer layer q, k, v and the attention
    output projection, and not the MLP's expansion to the intermediate size
    (whose outputs are most of what ``dots_with_no_batch_dims_saveable``
    keeps)."""
    if not _cp.dots_with_no_batch_dims_saveable(prim, *avals, **params) \
            or "dimension_numbers" not in params:
        return False
    (lhs_contract, rhs_contract), _ = params["dimension_numbers"]
    lhs, rhs = avals[:2]
    contracted = math.prod(lhs.shape[d] for d in lhs_contract)
    produced = math.prod(n for d, n in enumerate(rhs.shape)
                         if d not in rhs_contract)
    return produced <= contracted


#: Name -> zero-arg factory returning a jax.checkpoint policy (or None = full remat).
#: Mirrors the reference's knob set (checkpointing.py:1070 configure) plus the
#: TPU-idiomatic selective policies the compiler understands.
POLICIES: Dict[str, Callable[[], Optional[Callable]]] = {
    "none": lambda: None,  # full recompute (reference default `checkpoint()`)
    "nothing_saveable": lambda: _cp.nothing_saveable,
    "everything_saveable": lambda: _cp.everything_saveable,
    "dots_saveable": lambda: _cp.dots_saveable,
    "dots_with_no_batch_dims_saveable": lambda: _cp.dots_with_no_batch_dims_saveable,
    "narrow_dots_saveable": lambda: narrow_dots_saveable,
    # host-offload variants (parity: cpu_checkpointing, checkpointing.py:546-560)
    "offload_dots": lambda: _cp.offload_dot_with_no_batch_dims(
        offload_src="device", offload_dst="pinned_host"),
    # selective: save only per-layer attention outputs (tagged by the zoo
    # models via checkpoint_name "attn_out") — backward skips recomputing the
    # attention kernel, costing only B*T*C per layer of extra residency.
    "attn_out_saveable": lambda: _cp.save_only_these_names("attn_out"),
    # what the flash kernel's backward reads of its forward: the output and
    # the log-sum-exp, named in its forward rule (ops/pallas/flash_attention.py).
    # A custom_vjp is not a dot, so no dot policy keeps them, and "attn_out"
    # (the models' tag, on the reshaped output) does not cover the log-sum-exp
    "flash_residuals_saveable": lambda: _cp.save_only_these_names(
        "flash_out", "flash_lse"),
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
        # set around one trace by an engine (keeping / probing below)
        self.kept: Optional[Callable] = None
        self.grads_reduced = False
        self.probe: Optional["LayerProbe"] = None


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


def names_what_is_kept(cfg) -> bool:
    """Does this ``activation_checkpointing`` block say what a checkpointed
    layer keeps (a policy through ``partition_activations`` or
    ``cpu_checkpointing``, the chunking through ``number_checkpoints``)? An
    engine then leaves the choice alone."""
    return bool(getattr(cfg, "partition_activations", False)
                or getattr(cfg, "cpu_checkpointing", False)
                or getattr(cfg, "number_checkpoints", None))


def current_policy() -> Optional[Callable]:
    """The policy for a caller that names none: the configured block's, else
    what an engine is keeping around this trace (:func:`keeping`), else None
    (full recompute)."""
    if _STATE.configured and names_what_is_kept(_STATE):
        return _STATE.policy
    return _STATE.kept


def policy_for(name_or_policy) -> Optional[Callable]:
    """A caller's own policy (a registry name — ``"none"`` spells full
    recompute — or a callable) if it names one, else :func:`current_policy`."""
    if name_or_policy is not None:
        return resolve_policy(name_or_policy)
    return current_policy()


# --------------------------------------------------------------------------- #
# What a checkpointed layer keeps when nobody names a policy
# --------------------------------------------------------------------------- #

def _attention_kept():
    return _cp.save_from_both_policies(
        POLICIES["attn_out_saveable"](), POLICIES["flash_residuals_saveable"]())


#: (what is kept, factory of the policy), from most kept to least. The
#: backward of rung 0 re-runs only the norms, the rotary embedding and the
#: MLP's elementwise product; of the last, the whole layer.
LADDER: Tuple[Tuple[str, Callable[[], Optional[Callable]]], ...] = (
    ("dots and attention", lambda: _cp.save_from_both_policies(
        POLICIES["dots_with_no_batch_dims_saveable"](), _attention_kept())),
    ("narrow dots and attention", lambda: _cp.save_from_both_policies(
        POLICIES["narrow_dots_saveable"](), _attention_kept())),
    ("attention", _attention_kept),
    ("layer inputs", POLICIES["none"]),
)

#: The share of the device's limit :func:`choose_rung` leaves free: the
#: estimate knows neither how the compiler lays the kept tensors out nor the
#: step's other temporaries (a layer's working set, gathered parameters, the
#: head's logits). A share, so that it scales with the chip; the compiled
#: step's ``memory_analysis()`` is the guard behind it.
MARGIN_SHARE = 0.125


def choose_rung(limit: int, resident: int, kept: Sequence[int], layers: int,
                *, alive: int = 1, other: int = 0) -> int:
    """The first rung of :data:`LADDER` a device has room for.

    ``limit``: the device's memory limit in bytes (0: it reports none, and
    the last rung is returned); ``resident``: bytes of train state it holds
    at rest; ``kept[r]``: bytes rung ``r`` keeps of one layer on this device;
    ``layers``: the layers a device runs; ``alive``: micro-batches whose
    activations are alive at once; ``other``: temporaries the caller can
    name (the gradient accumulator). A pure function of its arguments: the
    same configuration gets the same rung in every run."""
    last = len(kept) - 1
    if limit <= 0:
        return last
    room = int(limit * (1.0 - MARGIN_SHARE)) - resident - other
    for rung in range(last):
        if kept[rung] * layers * alive <= room:
            return rung
    return last


def kept_bytes(layer: Callable, policy: Optional[Callable], *args) -> int:
    """Bytes the forward of ``jax.checkpoint(layer, policy=policy)`` keeps
    for its backward beyond its own arguments and constants: the sum over
    what ``jax.ad_checkpoint.print_saved_residuals`` lists as an output or a
    named value. Traced from ``args`` (arrays, tracers or shapes); nothing
    is compiled."""
    fn = jax.checkpoint(layer, policy=policy)
    jaxpr = jax.make_jaxpr(lambda *a: jax.linearize(fn, *a)[1])(*args).jaxpr
    given = set(jaxpr.invars) | set(jaxpr.constvars)
    # a jit the backward re-runs (``nn.silu`` inside a custom_vjp's forward
    # rule) hands its argument back as a result: the same value under a
    # second name, which the compiled step holds once
    same = {}
    for eqn in jaxpr.eqns:
        if eqn.primitive.name != "jit":
            continue
        inner = eqn.params["jaxpr"].jaxpr
        for out, res in zip(eqn.outvars, inner.outvars):
            if res in inner.invars:
                arg = eqn.invars[inner.invars.index(res)]
                if not isinstance(arg, _Literal):
                    same[out] = same.get(arg, arg)
    kept = {same.get(v, v) for v in jaxpr.outvars if not isinstance(v, _Literal)}
    return tree_size_bytes([v.aval for v in kept - given])


class LayerProbe:
    """What one trace of a model tells of its checkpointed layer walks:
    for each walk, the bytes each rung keeps of one layer (its input
    included, which every rung keeps) and the number of layers."""

    def __init__(self):
        self.walks: List[Tuple[Tuple[int, ...], int]] = []

    @property
    def layers(self) -> int:
        return sum(n for _, n in self.walks)

    def kept_per_layer(self) -> Tuple[int, ...]:
        """Bytes each rung keeps of a layer (the mean over all walks)."""
        return tuple(-(-sum(per_layer[r] * n for per_layer, n in self.walks)
                       // self.layers) for r in range(len(LADDER)))

    def measure(self, module, carry, call_layer, n_layers: int) -> None:
        # a pure function of (variables, carry) for layer 0: the bound
        # module's own variables and rng streams, applied unbound (this
        # trace is thrown away, so drawing from the streams costs nothing)
        unbound, variables = module.unbind()
        rngs = {name: module.make_rng(name) for name in module.scope.rngs}

        def layer(variables, carry):
            return unbound.apply(variables, carry, rngs=rngs,
                                 method=lambda m, c: call_layer(m, c, 0))

        given = tree_size_bytes(carry)
        self.walks.append((tuple(
            given + kept_bytes(layer, build(), variables, carry)
            for _, build in LADDER), n_layers))


@contextlib.contextmanager
def probing():
    """Around one (abstract) trace of a model: every checkpointed walk that
    names no policy reports to the yielded :class:`LayerProbe`."""
    probe, prior = LayerProbe(), _STATE.probe
    _STATE.probe = probe
    try:
        yield probe
    finally:
        _STATE.probe = prior


@contextlib.contextmanager
def keeping(rung: Optional[int], grads_reduced: bool = False):
    """Around one trace of a step: walks that name no policy keep what
    ``LADDER[rung]`` keeps (None: nothing changes), and a differentiation
    rule that asks :func:`gradients_are_reduced` is told ``grads_reduced``:
    whether the step sums its weight gradients across devices before the
    update (so that no optimizer update rides in a layer's products)."""
    prior = (_STATE.kept, _STATE.grads_reduced)
    _STATE.kept = None if rung is None else LADDER[rung][1]()
    _STATE.grads_reduced = grads_reduced
    try:
        yield
    finally:
        _STATE.kept, _STATE.grads_reduced = prior


def gradients_are_reduced() -> bool:
    """What the engine said of the step it is tracing (:func:`keeping`);
    False outside one."""
    return _STATE.grads_reduced


@dataclasses.dataclass(frozen=True)
class RematPlan:
    """What an engine chose for its checkpointed layers, and against what."""
    rung: int
    kept_per_layer: Tuple[int, ...]    # bytes a device keeps of a layer, by rung
    layers: int
    limit_bytes: int
    resident_bytes: int
    other_bytes: int

    @property
    def what(self) -> str:
        return LADDER[self.rung][0]

    @property
    def kept_bytes(self) -> int:
        return self.kept_per_layer[self.rung] * self.layers

    def describe(self) -> str:
        gib = 2.0 ** 30
        return (f"rung {self.rung} ({self.what}): keeps "
                f"{self.kept_bytes / gib:.3f} GiB a device over {self.layers} "
                f"layers ({self.kept_per_layer[self.rung]} B a layer) "
                f"beside {self.resident_bytes / gib:.2f} GiB of state and "
                f"{self.other_bytes / gib:.2f} GiB of gradients, of a limit "
                f"of {self.limit_bytes / gib:.2f} GiB")


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
    pol = policy_for(policy)
    fn = jax.checkpoint(function, policy=pol, static_argnums=static_argnums)
    return fn(*args, **kwargs)


def checkpoint_wrapper(function: Callable, policy=None, static_argnums=()):
    """Return a remat-wrapped callable (decorator form)."""
    pol = policy_for(policy)
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
    pol = policy_for(policy)
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
    With ``policy`` None the walk keeps what :func:`current_policy` says: the
    block's policy, else what an engine is :func:`keeping` around this trace,
    else nothing (full recompute; as a policy name, ``"none"``).

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
    if policy is None and _STATE.probe is not None:
        _STATE.probe.measure(module, carry, call_layer, n_layers)
    pol = policy_for(policy)

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
