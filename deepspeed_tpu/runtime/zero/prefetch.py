"""ZeRO-3 collective schedule: parameter prefetch + pipelined reduce-scatter.

Stage-3 sharding (`ZeroPartitioner`) leaves every gather/reduce placement
decision to XLA: params carry fsdp-sharded specs, the partitioner emits
on-demand all-gathers wherever the scheduler likes, and grad reductions land
after the whole backward. This module builds the *explicit* schedule instead
(parity: DeepSpeed's ``PartitionedParameterCoordinator`` +
``parameter_offload`` prefetch machinery, reference
``runtime/zero/partitioned_param_coordinator.py``):

* the model's layer stack is grouped into **waves** — consecutive layers whose
  fsdp-sharded bytes fit ``allgather_bucket_size`` — and every wave's sharded
  leaves are gathered by ONE bucketed all-gather (ravel → concat → all-gather
  → split), not one collective per tensor;
* wave ``w``'s gather is pinned into a two-sided issue window: a
  ``lax.optimization_barrier`` tie to the activation entering wave
  ``w - prefetch_depth`` is the lower bound (never issued earlier — the hard
  residency bound), and a 1-element probe of a *gathered* leaf barriered into
  wave ``w - 1``'s compute INPUT is the upper bound (always finished one wave
  ahead of use). Completion is forced by dataflow, not best-effort hoisting —
  the program must prefetch even on a serial executor — while the issue
  window spans computes ``w - prefetch_depth .. w - 2``, so at depth >= 2 the
  gather genuinely runs concurrently with intervening waves' compute wherever
  collectives are async (depth 1 double-buffers residency but its window sits
  between two computes: one wave of lookahead leaves no compute to hide
  under);
* the backward re-gathers each wave's params tied to the **incoming
  cotangent** (reverse layer order, inside the backward window) and recomputes
  the wave forward from sharded residuals (wave-granular rematerialisation —
  gathered params are never saved, so full-size buffers die at last use and
  HBM stays at sharded + ``depth + 1`` waves);
* grad reduce-scatter is the **transpose of the bucketed gather**: the wave
  backward differentiates with respect to the *sharded* params, so shard_map
  transposes the bucket's ``all_gather`` into a ``psum_scatter`` over the same
  bucket layout — a true bucketed reduce-scatter pipelined into each wave's
  backward, with ``reduce_bucket_size`` bounding the backward bucket size.

Everything is expressed INSIDE the jitted step — there is no host
orchestration and no extra compiled program; ``prefetch_depth=None`` keeps the
implicit path bit-for-bit untouched.

Scheduling changes placement, never math: gather bucketing is pure data
movement and the transpose reduce-scatter sums the same partials in the same
participant order, so per-step loss streams are byte-identical across depth
0/1/2 and any bucket size
(``tests/unit/test_zero3_prefetch.py::test_depth_changes_placement_never_math``).

Observability: the schedule's collectives are DEVICE work, so they are named
and read from the device trace, never timed with host stamps. Each wave's
forward gather runs under ``jax.named_scope("zero3/gather/w<k>")``, its
backward re-gather under ``zero3/gather_bwd/w<k>`` and the gather's transpose
under ``zero3/reduce_scatter/w<k>``; the scope is part of every operation's
``op_name`` in the compiled program, which a profiler trace carries
(docs/OBSERVABILITY.md "Names on the device's work"). Exposed and hidden
collective time come from that trace (``chipbench/reduce/xplane.py``).

Known lowering honesty: spans and stats name the *logical* collective. On the
forced-host CPU backend the bucketed gather lowers to a real ``all-gather``
and the transpose to a real ``reduce-scatter`` HLO; per-tensor
``with_sharding_constraint`` reductions (the implicit path) instead lower to
``all-reduce + slice`` because XLA:CPU lacks the rewrite pass.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import threading
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.comm.mesh import FSDP_AXES
from deepspeed_tpu.runtime.zero.partition import gathered_spec, sharded_axes_of

__all__ = [
    "Zero3Wave", "Zero3Plan", "build_plan", "configure", "current_plan",
    "scheduled_layer_walk", "layer_stack_names",
]


def layer_stack_names(params: Any) -> Optional[List[str]]:
    """Detect the model's layer stack among top-level param keys.

    Flax scans name repeated submodules ``{prefix}_{i}`` (gpt2 ``h_0..h_N``,
    llama/decoder ``layers_0..N``); the largest contiguous integer-suffixed
    group IS the stack. Returns the keys in model order, or None when no
    group of >= 2 consecutive layers exists (nothing to schedule)."""
    import re
    if not isinstance(params, dict):
        return None
    groups: Dict[str, List[Tuple[int, str]]] = {}
    for k in params:
        m = re.fullmatch(r"(.+?)_(\d+)", str(k))
        if m:
            groups.setdefault(m.group(1), []).append((int(m.group(2)), str(k)))
    if not groups:
        return None
    members = max(groups.values(), key=len)
    members.sort()
    if len(members) < 2 or [i for i, _ in members] != list(range(len(members))):
        return None
    return [k for _, k in members]


# --------------------------------------------------------------------------- #
# Plan
# --------------------------------------------------------------------------- #

@dataclasses.dataclass(frozen=True)
class _LeafPlan:
    """One fsdp-sharded leaf inside a wave bucket."""
    layer: str                 # top-level param key, e.g. "h_3"
    path: Tuple[str, ...]      # path inside the layer's param dict
    spec: Any                  # full PartitionSpec (fsdp + any tp axes)
    out_spec: Any              # spec with fsdp axes stripped (the gathered spec)
    dim: int                   # dimension carrying the fsdp axes
    axes: Tuple[str, ...]      # the fsdp mesh axes sharding `dim`
    nbytes: int                # full (gathered) size in bytes


@dataclasses.dataclass(frozen=True)
class Zero3Wave:
    index: int
    layers: Tuple[str, ...]          # layer names, model order
    leaves: Tuple[_LeafPlan, ...]    # gatherable leaves of those layers
    gather_bytes: int                # sum of leaf nbytes


@dataclasses.dataclass(frozen=True)
class Zero3Plan:
    """Static collective schedule for one model's layer stack."""
    waves: Tuple[Zero3Wave, ...]
    depth: int                       # prefetch lookahead in waves (>= 0)
    layer_wave: Dict[str, int]       # layer name -> wave index
    allgather_bucket_size: int
    reduce_bucket_size: int
    # leaves NOT gathered (replicated / persistence-threshold / tp-only):
    # schedule leaves them alone; recorded for the residency story.
    persistent_bytes: int

    @property
    def n_waves(self) -> int:
        return len(self.waves)

    @property
    def gather_bytes_per_step(self) -> int:
        # forward gather + backward re-gather of every wave
        return 2 * sum(w.gather_bytes for w in self.waves)


def _leaf_paths(tree) -> List[Tuple[Tuple[str, ...], Any]]:
    """Flatten a (nested-dict) param tree to (path, leaf) with string keys."""
    out: List[Tuple[Tuple[str, ...], Any]] = []

    def walk(prefix, node):
        if isinstance(node, dict):
            for k in sorted(node):
                walk(prefix + (str(k),), node[k])
        else:
            out.append((prefix, node))

    walk((), tree)
    return out


def build_plan(params: Any, specs: Any, layer_names: Sequence[str], *,
               depth: int, allgather_bucket_size: int,
               reduce_bucket_size: int, mesh=None) -> Optional[Zero3Plan]:
    """Build the wave schedule from a param tree + aligned spec tree.

    ``layer_names`` are the top-level keys of the model's layer stack in
    model order (e.g. ``["h_0", "h_1", ...]``). Consecutive layers are packed
    into one wave while the wave's gatherable bytes stay within
    ``allgather_bucket_size`` (every wave holds at least one layer, so a
    bucket size smaller than a single layer degrades to per-layer waves).
    Returns None when no layer has a gatherable leaf (nothing to schedule).
    """
    waves: List[Zero3Wave] = []
    cur_layers: List[str] = []
    cur_leaves: List[_LeafPlan] = []
    cur_bytes = 0
    persistent_bytes = 0

    def flush():
        nonlocal cur_layers, cur_leaves, cur_bytes
        if cur_layers:
            waves.append(Zero3Wave(len(waves), tuple(cur_layers),
                                   tuple(cur_leaves), cur_bytes))
            cur_layers, cur_leaves, cur_bytes = [], [], 0

    for name in layer_names:
        lp = params[name]
        ls = specs[name]
        flat_p = _leaf_paths(lp)
        flat_s = dict(_leaf_paths(ls))
        layer_leaves: List[_LeafPlan] = []
        for path, leaf in flat_p:
            spec = flat_s.get(path, P())
            dim_axes = sharded_axes_of(spec, FSDP_AXES)
            if dim_axes is None:
                # replicated or tp-only: persistence threshold / small params —
                # never gathered, never reduced by the schedule
                persistent_bytes += leaf.size * leaf.dtype.itemsize
                continue
            dim, axes = dim_axes
            layer_leaves.append(_LeafPlan(
                layer=name, path=path, spec=spec,
                out_spec=gathered_spec(spec, FSDP_AXES), dim=dim, axes=axes,
                nbytes=int(leaf.size) * leaf.dtype.itemsize))
        lbytes = sum(l.nbytes for l in layer_leaves)
        if cur_layers and cur_bytes + lbytes > allgather_bucket_size:
            flush()
        cur_layers.append(name)
        cur_leaves.extend(layer_leaves)
        cur_bytes += lbytes
    flush()

    if not any(w.leaves for w in waves):
        return None
    layer_wave = {name: w.index for w in waves for name in w.layers}
    return Zero3Plan(waves=tuple(waves), depth=int(depth),
                     layer_wave=layer_wave,
                     allgather_bucket_size=int(allgather_bucket_size),
                     reduce_bucket_size=int(reduce_bucket_size),
                     persistent_bytes=persistent_bytes)


# --------------------------------------------------------------------------- #
# Ambient plan state (mirrors activation_checkpointing.configure/current_policy)
# --------------------------------------------------------------------------- #

class _PrefetchState(threading.local):
    def __init__(self):
        super().__init__()
        self.plan: Optional[Zero3Plan] = None


_STATE = _PrefetchState()


def configure(plan: Optional[Zero3Plan]) -> None:
    """Arm (or clear, with None) the ambient schedule the model walk reads."""
    _STATE.plan = plan


def current_plan() -> Optional[Zero3Plan]:
    return _STATE.plan


@contextlib.contextmanager
def cleared():
    """Trace-hygiene guard for FOREIGN traces on a scheduled engine's
    thread: stash the ambient plan, clear it, restore on exit.

    ``train_batch`` re-arms the plan every step, so anything ELSE that
    traces on the same thread between steps — the colocated WeightBridge's
    train->serve reshard program (``runtime/colocated.py``) is the
    motivating case — would otherwise trace under a plan scheduled for a
    different program's model walk. The reshard touches no model layers, so
    the schedule would not apply today; the guard makes that a guarantee
    instead of a coincidence (the same hygiene rule engine.py documents at its
    per-step ``configure`` call)."""
    prev = _STATE.plan
    _STATE.plan = None
    try:
        yield
    finally:
        _STATE.plan = prev


# --------------------------------------------------------------------------- #
# Bucketed differentiable gather
# --------------------------------------------------------------------------- #

@jax.custom_vjp
def _tied(lv, t):
    out = jax.lax.optimization_barrier(tuple(lv) + (t,))
    return tuple(out[:-1])


def _tied_fwd(lv, t):
    return _tied(lv, t), t


def _tied_bwd(t, ct):
    return tuple(ct), jnp.zeros_like(t)


_tied.defvjp(_tied_fwd, _tied_bwd)


def _tie_barrier(leaves: Sequence[Any], tie):
    """Pin `leaves` behind `tie` with an optimization_barrier, opaque to AD.

    The barrier makes `tie` a data dependency of every leaf, so XLA cannot
    issue the op consuming them before `tie` exists — that placement IS the
    schedule. ``optimization_barrier`` has no differentiation rule, so the
    custom_vjp routes cotangents straight through (identity) and sends `tie`
    a symbolic zero. `tie` is a formal argument, not a closure: closing a
    custom_vjp over a tracer from the surrounding differentiation scope
    leaks it (UnexpectedTracerError under grad-of-walk).
    """
    return _tied(tuple(leaves), tie)


def _bucketize(leaves: Sequence[_LeafPlan], limit: int) -> List[List[int]]:
    """Group leaf indices into buckets of <= limit bytes (>= 1 leaf each),
    keyed by (fsdp axes, dtype-compatible ravel) — one fused collective per
    bucket. Leaves with different fsdp axes cannot share an all-gather."""
    buckets: List[List[int]] = []
    cur: List[int] = []
    cur_bytes = 0
    cur_axes: Optional[Tuple[str, ...]] = None
    for i, lp in enumerate(leaves):
        if cur and (lp.axes != cur_axes or cur_bytes + lp.nbytes > limit):
            buckets.append(cur)
            cur, cur_bytes = [], 0
        cur.append(i)
        cur_axes = lp.axes
        cur_bytes += lp.nbytes
    if cur:
        buckets.append(cur)
    return buckets


def _fused_allgather(*locals_, plans: Sequence[_LeafPlan],
                     n_shards: int, axes: Tuple[str, ...]):
    """shard_map inner: one all-gather for the whole bucket.

    Ravel every local shard into one flat buffer, gather once, then carve each
    leaf back out and reassemble its sharded dimension (shard s owns block s
    of dim `lp.dim`, row-major over the fsdp axes — GSPMD's tile order).
    """
    flat = jnp.concatenate([jnp.ravel(l) for l in locals_])
    full = jax.lax.all_gather(flat, axes)          # (n_shards, bucket_local)
    outs = []
    off = 0
    for l, lp in zip(locals_, plans):
        seg = full[:, off:off + l.size].reshape((n_shards,) + l.shape)
        outs.append(jnp.concatenate(
            [seg[s] for s in range(n_shards)], axis=lp.dim))
        off += l.size
    return tuple(outs)


def _gather_wave(plan: Zero3Plan, wave: Zero3Wave, ptrees: Dict[str, Any],
                 tie, mesh, *, bucket_limit: int, scope: str):
    """Gather a wave's sharded leaves (bucketed, differentiable, tie-pinned)
    under ``jax.named_scope(scope)``: the wave's name in a device trace.

    Returns per-layer param dicts with gathered leaves substituted. The
    transpose of each bucket's all_gather is a psum_scatter over the same
    bucket — differentiating through this function w.r.t. the sharded leaves
    yields the bucketed reduce-scatter of their grads.
    """

    leaves = [ptrees[lp.layer] for lp in wave.leaves]
    for i, lp in enumerate(wave.leaves):
        node = leaves[i]
        for k in lp.path:
            node = node[k]
        leaves[i] = node

    leaves = list(_tie_barrier(leaves, tie))

    gathered: List[Any] = [None] * len(leaves)
    for bucket in _bucketize(wave.leaves, bucket_limit):
        plans = [wave.leaves[i] for i in bucket]
        axes = plans[0].axes
        n_shards = 1
        for a in axes:
            n_shards *= mesh.shape[a]
        fn = jax.shard_map(
            functools.partial(_fused_allgather, plans=plans,
                              n_shards=n_shards, axes=axes),
            mesh=mesh,
            in_specs=tuple(lp.spec for lp in plans),
            out_specs=tuple(lp.out_spec for lp in plans),
            check_vma=False)
        with jax.named_scope(scope):
            outs = fn(*[leaves[i] for i in bucket])
        for i, g in zip(bucket, outs):
            gathered[i] = g

    out = {name: ptrees[name] for name in wave.layers}
    for lp, g in zip(wave.leaves, gathered):
        node = out[lp.layer] = dict(out[lp.layer])
        for k in lp.path[:-1]:
            node[k] = dict(node[k])
            node = node[k]
        node[lp.path[-1]] = g
    return out


# --------------------------------------------------------------------------- #
# The scheduled wave (custom_vjp)
# --------------------------------------------------------------------------- #

def _make_gather_fn(plan: Zero3Plan, wave: Zero3Wave, mesh):
    """custom_vjp gather: fwd = tie-pinned bucketed all-gather of the wave's
    sharded leaves; bwd = the bucketed reduce-scatter (the gather's transpose
    over ``reduce_bucket_size`` buckets), so grads arriving on the gathered
    buffers leave this node already reduced + scattered to the param
    sharding — pipelined into the backward at this wave's position."""
    @jax.custom_vjp
    def gather_fn(ptrees, tie):
        return _gather_wave(plan, wave, ptrees, tie, mesh,
                            bucket_limit=plan.allgather_bucket_size,
                            scope=f"zero3/gather/w{wave.index}")

    def gather_fwd(ptrees, tie):
        return gather_fn(ptrees, tie), (ptrees, tie)

    def gather_bwd(res, ct):
        ptrees, tie = res
        # transpose of the bucketed gather = bucketed psum_scatter: jax.vjp
        # of a fresh gather gives it over reduce_bucket_size buckets; the
        # unused primal all-gather is dead code XLA removes.
        scope = f"zero3/reduce_scatter/w{wave.index}"
        _, vjp_fn = jax.vjp(
            lambda pt: _gather_wave(plan, wave, pt, tie, mesh,
                                    bucket_limit=plan.reduce_bucket_size,
                                    scope=scope), ptrees)
        (gp,) = vjp_fn(ct)
        return gp, jnp.zeros_like(tie)

    gather_fn.defvjp(gather_fwd, gather_bwd)
    return gather_fn


def _make_compute_fn(plan: Zero3Plan, wave: Zero3Wave, mesh,
                     layer_call: Callable[[str, Any, Any], Any]):
    """custom_vjp wave compute: fwd consumes the (prefetched) gathered params
    and saves only SHARDED residuals — the gathered buffers' last use is this
    wave's forward, so XLA's liveness frees them here (the HBM bound). bwd
    re-gathers tied to the incoming cotangent (reverse order, inside the
    backward window), recomputes the wave (wave-granular remat), and routes
    the param grads out through the ``gathered`` input's cotangent — i.e.
    into the gather node's transpose reduce-scatter."""

    def run(gathered, x):
        for name in wave.layers:
            x = layer_call(name, gathered[name], x)
        return x

    @jax.custom_vjp
    def compute_fn(gathered, ptrees, x):
        return run(gathered, x)

    def compute_fwd(gathered, ptrees, x):
        # y's readiness marks the gathered buffers' last forward use:
        # nothing downstream references them (residuals are sharded)
        return run(gathered, x), (ptrees, x)

    def compute_bwd(res, ct):
        ptrees, x = res
        regathered = _gather_wave(plan, wave, ptrees, ct, mesh,
                                  bucket_limit=plan.reduce_bucket_size,
                                  scope=f"zero3/gather_bwd/w{wave.index}")
        _, vjp_fn = jax.vjp(run, regathered, x)
        g_gathered, gx = vjp_fn(ct)
        # param grads leave via g_gathered (the gather node reduce-scatters
        # them); the direct ptrees input only feeds the bwd re-gather
        g_ptrees = jax.tree_util.tree_map(jnp.zeros_like, ptrees)
        return g_gathered, g_ptrees, gx

    compute_fn.defvjp(compute_fwd, compute_bwd)
    return compute_fn


def _gathered_probe_leaf(wave: Zero3Wave, gathered: Dict[str, Any]):
    """1-element probe of the wave's first GATHERED leaf.

    ``gathered`` is a gather node's output (per-layer param dicts); its
    tree-order first leaf may be a persistent param that bypassed the gather,
    so the probe indexes by ``wave.leaves[0]`` — by construction an
    fsdp-sharded leaf the gather substituted."""
    lp = wave.leaves[0]
    node = gathered[lp.layer]
    for k in lp.path:
        node = node[k]
    return jnp.ravel(node)[:1]


def scheduled_layer_walk(layers: Sequence[Any], carry, *,
                         layer_args: Tuple[Any, ...] = (),
                         post_layer: Optional[Callable[[Any, Any, int], Any]] = None):
    """Walk a flax layer stack under the ambient Zero3Plan.

    ``layers`` are the parent's BOUND submodules (e.g. ``self.blocks``);
    each is unbound so the wave can call it as a pure function of its
    (gathered) params. ``layer_args`` are extra positional args passed to
    every layer call; ``post_layer(new_x, prev_x, i)`` wraps each layer's
    output (progressive layer drop). Layers needing flax RNGs (live dropout)
    are not supported — callers gate on deterministic.

    Returns None when the ambient plan does not cover these layers, in which
    case the caller must fall back to the unscheduled walk.
    """
    plan = current_plan()
    if plan is None:
        return None
    names = []
    for m in layers:
        name = getattr(m, "name", None)
        if name is None or name not in plan.layer_wave:
            return None          # plan built for a different model: fall back
        names.append(name)
    if [w for w in sorted({plan.layer_wave[n] for n in names})] != \
            list(range(plan.n_waves)):
        return None

    from deepspeed_tpu.comm.mesh import get_topology
    mesh = get_topology().mesh

    unbound: Dict[str, Any] = {}
    other_vars: Dict[str, Any] = {}
    ptrees: Dict[str, Any] = {}
    index_of: Dict[str, int] = {}
    try:
        for i, m in enumerate(layers):
            mod, variables = m.unbind()
            if "params" not in variables:
                return None      # init pass: params are being created
            ptrees[m.name] = variables["params"]
            other_vars[m.name] = {k: v for k, v in variables.items()
                                  if k != "params"}
            unbound[m.name] = mod
            index_of[m.name] = i
    except Exception:
        return None              # unbound/unbindable context: unscheduled walk

    def layer_call(name: str, pv, x):
        y = unbound[name].apply({"params": pv, **other_vars[name]},
                                x, *layer_args)
        if post_layer is not None:
            y = post_layer(y, x, index_of[name])
        return y

    # Software-pipelined walk: entering wave w, issue gathers up through wave
    # w + depth (tie = the CURRENT carry, i.e. the activation entering wave w
    # — the lower bound on issue), then pin this wave's compute input on a
    # 1-element probe of wave w+1's pending gather. The pin is the upper
    # bound, one wave ahead of use: the compiled program MUST finish gather v
    # before compute v-1 can run, so the prefetch is forced by dataflow, not
    # left to the scheduler's goodwill, even on a serial executor — while
    # gathers deeper in the window (v > w+1) stay unpinned until their own
    # consumer-minus-one compute, free to run concurrently with computes
    # w .. v-2 wherever collectives are async. Pinning every newly issued
    # gather into compute w instead would sandwich each gather between two
    # consecutive computes and forbid any comm/compute concurrency.
    n_w = plan.n_waves
    pending: Dict[int, Any] = {}
    for w, wave in enumerate(plan.waves):
        for v in range(w, min(w + plan.depth, n_w - 1) + 1):
            if v not in pending:
                gf = _make_gather_fn(plan, plan.waves[v], mesh)
                pending[v] = gf(
                    {n: ptrees[n] for n in plan.waves[v].layers}, carry)
        gathered = pending.pop(w)
        if w + 1 in pending:
            # probe a leaf the gather actually produced: wave.leaves holds
            # only fsdp-sharded leaves, so indexing by its first entry can
            # never land on a persistence-threshold leaf that passed through
            # _gather_wave untouched (a probe of one would pin nothing)
            (carry,) = _tie_barrier(
                [carry], _gathered_probe_leaf(plan.waves[w + 1],
                                              pending[w + 1]))
        cf = _make_compute_fn(plan, wave, mesh, layer_call)
        carry = cf(gathered, {n: ptrees[n] for n in wave.layers}, carry)
    return carry
