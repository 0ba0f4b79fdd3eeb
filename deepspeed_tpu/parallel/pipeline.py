"""Pipeline parallelism.

Parity: ``runtime/pipe/`` — ``PipelineModule`` layer partitioning
(``module.py:86``, ``partition_method='parameters'|'uniform'`` :130,370), the
instruction-schedule engine (``engine.py:55``, ``schedule.py``), and P2P activation
exchange (``p2p.py``). TPU-native form: the transformer block stack is a *stacked*
parameter tree with the layer dimension sharded over the 'pipe' mesh axis; a
shard_map microbatch loop moves activations between neighbouring stages with
``lax.ppermute`` (neighbor ICI/DCN hops, exactly the reference's send/recv
pattern), and jax AD differentiates straight through the loop — the backward
schedule falls out of autodiff instead of hand-written BackwardPass instructions.

Schedule: GPipe-style fill/drain over ``n_micro`` microbatches (bubble fraction
(P-1)/(M+P-1)). The 1F1B *memory* optimisation (reference ``schedule.py:189
TrainSchedule`` keeps <= P microbatches of residuals live instead of M) is a
remat boundary here, not a different instruction stream: ``remat_ticks=True``
(the DEFAULT — measured v5e-1, 8x1024-wide blocks, bs 32x512: remat 69 vs
plain 109 ms/step at n_micro=4 and 96 vs 124 ms at n_micro=16; on a
bandwidth-bound chip recomputing a tick from VMEM-resident inputs beats
round-tripping its activations through HBM, so the 1F1B residency trade the
reference schedules for is a net LOSS here and a hand-written 1F1B
instruction stream is not implemented by measurement, not omission)
checkpoints each (stage, microbatch) tick of the scan, so backward stores only
tick inputs and recomputes the local stack serially — stored bytes then SHRINK
as n_micro grows (per-tick inputs get smaller), the 1F1B residency bound.
Measured on the v5e AOT topology (tests/unit/test_pipeline_memory.py, n_micro
in {4, 16}): plain {4: 1110, 16: 748} MB vs remat {4: 245, 16: 52} MB.
The same bound holds in the MULTI-STAGE regime 1F1B exists for — pipe=4
stages, (4, 2) v5e mesh, per-stage residuals (r5:
test_remat_ticks_bounds_memory_at_pipe4) — so stored activations lose both
time (single-chip ticks) and memory (4-stage AOT), and remat_ticks stays
the default on multi-stage evidence rather than single-chip extrapolation.
"""

from __future__ import annotations

from typing import Any, Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.comm.mesh import PIPE_AXIS, get_topology


def partition_balanced(weights: Sequence[float], n_parts: int) -> List[int]:
    """Optimal contiguous partition minimising the max part weight; returns part
    boundaries (len n_parts+1), every part non-empty while layers remain.

    Parity: ``ds_utils.partition_balanced`` used by ``PipelineModule``
    ``partition_method='parameters'`` (module.py:370). DP over prefix sums
    (O(n^2 * parts) — n is a layer count, so trivial)."""
    n = len(weights)
    n_parts = min(n_parts, n) if n else n_parts
    prefix = [0.0]
    for w in weights:
        prefix.append(prefix[-1] + float(w))

    INF = float("inf")
    # cost[p][i]: minimal max-part-weight splitting first i layers into p parts
    cost = [[INF] * (n + 1) for _ in range(n_parts + 1)]
    cut = [[0] * (n + 1) for _ in range(n_parts + 1)]
    cost[0][0] = 0.0
    for p in range(1, n_parts + 1):
        for i in range(p, n + 1):
            for j in range(p - 1, i):
                c = max(cost[p - 1][j], prefix[i] - prefix[j])
                if c < cost[p][i]:
                    cost[p][i] = c
                    cut[p][i] = j
    bounds = [n]
    for p in range(n_parts, 0, -1):
        bounds.append(cut[p][bounds[-1]])
    return bounds[::-1]


def partition_uniform(n_layers: int, n_parts: int) -> List[int]:
    """Parity: ``partition_method='uniform'`` (module.py:130). Balanced integer
    bounds (sizes differ by at most 1, never empty when n_layers >= n_parts)."""
    return [(i * n_layers) // n_parts for i in range(n_parts + 1)]


def _pipeline_ticks(stage, compute, params, micros, carry0,
                    n_micro: int, n_stages: int, axis_name: str,
                    remat_ticks: bool):
    """The shared GPipe fill/drain tick schedule (ONE implementation for the
    homogeneous and heterogeneous pipelines — a schedule fix lands in both).

    ``compute(params, x_mb, recv) -> out`` runs one stage on one microbatch:
    stage 0 reads ``x_mb`` (its input-slice), later stages read ``recv``.
    ``carry0`` fixes the inter-stage activation shape/dtype. Returns the
    [n_micro, ...] buffer of last-stage outputs (garbage on other stages —
    the caller masks + psums)."""
    out_buf = jnp.zeros((n_micro,) + carry0.shape, carry0.dtype)
    recv = carry0
    fwd_perm = [(i, i + 1) for i in range(n_stages - 1)]
    total_ticks = n_micro + n_stages - 1

    def tick(carry, t, params):
        recv, out_buf = carry
        mb_idx = t - stage
        active = jnp.logical_and(mb_idx >= 0, mb_idx < n_micro)
        safe_idx = jnp.clip(mb_idx, 0, n_micro - 1)
        x_mb = lax.dynamic_index_in_dim(micros, safe_idx, 0, keepdims=False)
        out = compute(params, x_mb, recv)
        out = jnp.where(active, out, jnp.zeros_like(out))
        # last stage stores its finished microbatch
        store = jnp.logical_and(active, stage == n_stages - 1)
        cur = lax.dynamic_slice_in_dim(out_buf, safe_idx, 1, 0)
        out_buf = lax.dynamic_update_slice_in_dim(
            out_buf, jnp.where(store, out[None], cur), safe_idx, 0)
        # the final tick's send is never read (the carry's recv dies with
        # the scan) — skip the inter-stage transfer on t == total_ticks-1
        # instead of paying one dead ppermute per step. The predicate is
        # the replicated tick index, so every stage takes the same branch.
        if n_stages > 1:
            recv = lax.cond(t == total_ticks - 1,
                            lambda o: o,
                            lambda o: lax.ppermute(o, axis_name, fwd_perm),
                            out)
        else:
            recv = out
        return (recv, out_buf)

    if remat_ticks:
        tick = jax.checkpoint(tick)

    # lax.scan over ticks (not a Python loop): reverse-mode AD then runs
    # one tick's backward — and, under remat_ticks, one tick's recompute —
    # at a time, which is what actually bounds peak memory. An unrolled
    # loop lets XLA overlap the recomputes and the bound is lost
    # (measured on the v5e AOT topology; see test_pipeline_memory.py).
    (recv, out_buf), _ = lax.scan(
        lambda c, t: (tick(c, t, params), None),
        (recv, out_buf), jnp.arange(total_ticks))
    return out_buf


def gpipe_apply(block_fn: Callable[[Any, jax.Array], jax.Array],
                stacked_params: Any,
                x: jax.Array,
                n_micro: int,
                mesh=None,
                axis_name: str = PIPE_AXIS,
                remat_ticks: bool = True) -> jax.Array:
    """Run a homogeneous block stack as a pipeline.

    ``stacked_params``: pytree whose leaves have leading dim L (total layers),
    sharded over 'pipe' (L/P local layers per stage). ``block_fn(p, x)`` applies
    ONE block. ``x``: [B, S, D] activations; B must divide by n_micro.

    Differentiable end-to-end (jax AD through ppermute); use inside the engine's
    loss like any other function.

    ``remat_ticks=True`` checkpoints each (stage, microbatch) tick: only the
    tick's INPUT activation is stored for backward and the local stack is
    recomputed — peak activation memory stays ~flat in ``n_micro`` instead of
    growing with it (measured: see tests/unit/test_pipeline_memory.py). This is
    the memory shape 1F1B buys the reference (schedule.py:189 TrainSchedule
    keeps <= P microbatches of residuals in flight); on TPU the same bound
    comes from a remat boundary, with recompute traded for the reference's
    schedule complexity.
    """
    mesh = mesh or get_topology().mesh
    n_stages = mesh.shape[axis_name]
    B = x.shape[0]
    assert B % n_micro == 0, f"batch {B} not divisible by n_micro {n_micro}"
    mb = B // n_micro

    def stage_body(local_params, x_full):
        stage = lax.axis_index(axis_name)
        micros = x_full.reshape((n_micro, mb) + x_full.shape[1:])

        # params are an EXPLICIT argument so jax.checkpoint can prune the tick
        # body's residuals (closure captures don't get residual-pruned)
        def compute(params, x_mb, recv):
            inp = jnp.where(stage == 0, x_mb, recv)

            def scan_fn(h, lp):
                return block_fn(lp, h), None
            out, _ = lax.scan(scan_fn, inp, params)
            return out

        carry0 = jnp.zeros((mb,) + x_full.shape[1:], x_full.dtype)
        out_buf = _pipeline_ticks(stage, compute, local_params, micros, carry0,
                                  n_micro, n_stages, axis_name, remat_ticks)
        # share final activations from the last stage with everyone (tiny psum —
        # keeps the output replicated so the loss/head runs outside the pipeline)
        out_full = out_buf.reshape(x_full.shape)
        out_full = lax.psum(
            jnp.where(stage == n_stages - 1, out_full, jnp.zeros_like(out_full)),
            axis_name)
        return out_full

    f = jax.shard_map(
        stage_body, mesh=mesh,
        in_specs=(P(PIPE_AXIS), P()),
        out_specs=P(),
        check_vma=False)
    return f(stacked_params, x)


def hetero_gpipe_apply(stage_fns: Sequence[Callable[[Any, jax.Array, jax.Array], jax.Array]],
                       stage_params: Sequence[Any],
                       x: jax.Array,
                       n_micro: int,
                       mesh=None,
                       axis_name: str = PIPE_AXIS,
                       remat_ticks: bool = True) -> jax.Array:
    """GPipe over HETEROGENEOUS stages (arbitrary per-stage functions/params).

    ``stage_fns[i](params_i, x_mb, recv)`` runs stage i on one microbatch:
    stage 0 reads ``x_mb`` (its slice of the pipeline input — token ids or
    embedded activations), later stages read ``recv`` (the previous stage's
    output, a fixed [mb, ...] float carry). Every stage must emit the SAME
    carry shape; the last stage's outputs are gathered (psum) and returned
    stacked [B, ...].

    TPU-native form of the reference's arbitrary ``LayerSpec`` lists
    (runtime/pipe/module.py:86,130): stages with different structures can't
    ride one stacked-and-sharded array, so each device selects its stage's
    computation with ``lax.switch`` on ``axis_index('pipe')`` — the stage
    params enter replicated across 'pipe' and stay shardable over fsdp /
    tensor axes (at pipe x fsdp the entry gather is exactly ZeRO-3's
    params-for-compute gather).
    """
    mesh = mesh or get_topology().mesh
    n_stages = mesh.shape[axis_name]
    assert len(stage_fns) == n_stages, \
        f"{len(stage_fns)} stage fns for {n_stages} '{axis_name}' devices"
    B = x.shape[0]
    assert B % n_micro == 0, f"batch {B} not divisible by n_micro {n_micro}"
    mb = B // n_micro

    def stage_body(params, x_full, carry0):
        stage = lax.axis_index(axis_name)
        micros = x_full.reshape((n_micro, mb) + x_full.shape[1:])

        def compute(params, x_mb, recv):
            branches = [
                (lambda p, xm, rc, i=i: stage_fns[i](p[i], xm, rc))
                for i in range(n_stages)
            ]
            return lax.switch(stage, branches, params, x_mb, recv)

        out_buf = _pipeline_ticks(stage, compute, params, micros, carry0,
                                  n_micro, n_stages, axis_name, remat_ticks)
        out_full = out_buf.reshape((B,) + carry0.shape[1:])
        out_full = lax.psum(
            jnp.where(stage == n_stages - 1, out_full, jnp.zeros_like(out_full)),
            axis_name)
        return out_full

    f = jax.shard_map(
        stage_body, mesh=mesh,
        in_specs=(P(), P(), P()),
        out_specs=P(),
        check_vma=False)
    # the carry template fixes the inter-stage activation shape/dtype; run
    # stage 0's fn once abstractly to derive it (stage 0 reads x_mb, so its
    # recv argument may be abstractly None here)
    carry_sds = jax.eval_shape(
        lambda p, xm: stage_fns[0](p, xm, None),
        stage_params[0],
        jax.ShapeDtypeStruct((mb,) + x.shape[1:], x.dtype))
    carry0 = jnp.zeros(carry_sds.shape, carry_sds.dtype)
    return f(list(stage_params), x, carry0)


class HeteroPipelineModule:
    """Parity: ``PipelineModule`` with arbitrary ``LayerSpec`` lists
    (runtime/pipe/module.py:86,130,370) — layers of DIFFERENT types
    partitioned into pipeline stages by parameter count.

    ``layers``: a list of flax modules (optionally with an embedding module
    first — it lands on stage 0, the reference's embed-on-first-stage
    layout). Stage boundaries come from :func:`partition_balanced` over each
    layer's actual parameter count ('parameters') or layer index
    ('uniform'). The head typically stays outside (tied to the embedding);
    run the result through the engine like any model.
    """

    def __init__(self, layers: Sequence[Any], n_stages: int, n_micro: int = 1,
                 partition_method: str = "parameters",
                 remat_ticks: bool = True):
        if partition_method not in ("uniform", "parameters"):
            raise NotImplementedError(
                f"partition_method='{partition_method}' not supported "
                "(have: 'uniform', 'parameters')")
        self.layers = list(layers)
        self.n_stages = n_stages
        self.n_micro = n_micro
        self.partition_method = partition_method
        self.remat_ticks = remat_ticks
        self.bounds: Optional[List[int]] = None   # set at init()

    def init(self, rng, sample_x):
        """Init every layer, then cut stage bounds by parameter weight.
        ``sample_x`` feeds layer 0; later layers see the previous output."""
        params = []
        x = sample_x
        for i, layer in enumerate(self.layers):
            rng, sub = jax.random.split(rng)
            p = layer.init(sub, x)["params"]
            params.append(p)
            x = layer.apply({"params": p}, x)
        if self.partition_method == "parameters":
            weights = [sum(int(np.prod(np.shape(leaf)))
                           for leaf in jax.tree_util.tree_leaves(p))
                       for p in params]
            self.bounds = partition_balanced(weights, self.n_stages)
        else:
            self.bounds = partition_uniform(len(self.layers), self.n_stages)
        # per-stage param LISTS (ragged python structure — fine: stages are
        # separate pytrees, not one stacked array; lists, not tuples, because
        # the optimizer's tree-unzip helper treats tuples as leaves)
        return {"params": [
            list(params[self.bounds[i]:self.bounds[i + 1]])
            for i in range(self.n_stages)]}

    def _stage_fns(self):
        bounds = self.bounds
        assert bounds is not None, "call init() (or set .bounds) first"

        def make(i):
            layers = self.layers[bounds[i]:bounds[i + 1]]

            def fn(stage_params, x_mb, recv):
                h = x_mb if i == 0 else recv
                for layer, p in zip(layers, stage_params):
                    h = layer.apply({"params": p}, h)
                return h
            return fn
        return [make(i) for i in range(self.n_stages)]

    def __call__(self, stage_params, x, mesh=None):
        p = stage_params["params"] if "params" in stage_params else stage_params
        return hetero_gpipe_apply(self._stage_fns(), p, x, self.n_micro,
                                  mesh=mesh, remat_ticks=self.remat_ticks)


class PipelineModule:
    """Parity: ``PipelineModule`` (runtime/pipe/module.py:86) for homogeneous
    transformer stacks: embed/head run outside the pipeline region (replicated or
    TP-sharded); the block stack runs through ``gpipe_apply``.

    ``block``: a flax module applied per layer; params are initialised stacked
    [L, ...] via vmap so the leading dim shards over 'pipe'.
    """

    def __init__(self, block, n_layers: int, n_micro: int = 1,
                 partition_method: str = "uniform",
                 remat_ticks: bool = True):
        # For a homogeneous block stack, 'uniform' and 'parameters' coincide
        # (equal per-layer weight): the stacked leading dim shards evenly over
        # 'pipe'. Heterogeneous layer lists go through HeteroPipelineModule,
        # which consumes partition_balanced() over real param counts.
        if partition_method not in ("uniform", "parameters"):
            raise NotImplementedError(
                f"partition_method='{partition_method}' not supported; homogeneous "
                "stacks use 'uniform'/'parameters' (identical here); heterogeneous "
                "layer lists use HeteroPipelineModule")
        self.block = block
        self.n_layers = n_layers
        self.n_micro = n_micro
        self.partition_method = partition_method
        self.remat_ticks = remat_ticks

    def init_stacked(self, rng, sample_x):
        rngs = jax.random.split(rng, self.n_layers)
        return jax.vmap(lambda r: self.block.init(r, sample_x)["params"])(rngs)

    def stacked_param_specs(self, stacked_params):
        return jax.tree_util.tree_map(
            lambda x: P(PIPE_AXIS, *([None] * (np.ndim(x) - 1))), stacked_params)

    def __call__(self, stacked_params, x, mesh=None):
        return gpipe_apply(
            lambda p, h: self.block.apply({"params": p}, h),
            stacked_params, x, self.n_micro, mesh=mesh,
            remat_ticks=self.remat_ticks)


class HeteroPipelineLM:
    """A causal LM over a HETEROGENEOUS layer list, engine-compatible.

    ``layers[0]`` must map token ids -> hidden (the embedding lands on stage
    0 with everything partition_balanced assigns there — the reference's
    ``EmbeddingPipe``-on-first-stage layout, module.py:86); the untied LM
    head stays outside the pipeline (replicated / TP-shardable). Train it
    through ``deepspeed_tpu.initialize`` like any model::

        lm = HeteroPipelineLM(vocab_size=V, layers=[Embed(), Big(), Small()],
                              n_stages=2, n_micro=M)
        params = lm.init(rng, batch)["params"]
        engine, *_ = deepspeed_tpu.initialize(model=lm, model_parameters=params,
                                              config={..., "mesh": {"pipe": P}})
    """

    def __init__(self, vocab_size: int, d_model: int, layers: Sequence[Any],
                 n_stages: int, n_micro: int = 1,
                 partition_method: str = "parameters",
                 init_scale: float = 0.02, remat_ticks: bool = True):
        self.vocab_size = vocab_size
        self.d_model = d_model
        self.pipe = HeteroPipelineModule(layers, n_stages, n_micro,
                                         partition_method=partition_method,
                                         remat_ticks=remat_ticks)
        self.init_scale = init_scale

    def init(self, rng, batch):
        ids = jnp.asarray(batch["input_ids"] if isinstance(batch, dict) else batch)
        k_head, k_stages = jax.random.split(rng)
        stages = self.pipe.init(k_stages, ids[:1])["params"]
        head = self.init_scale * jax.random.normal(
            k_head, (self.vocab_size, self.d_model), jnp.float32)
        return {"params": {"stages": stages, "head": head}}

    def apply(self, variables, batch, rngs=None, mesh=None):
        p = variables["params"] if "params" in variables else variables
        ids = jnp.asarray(batch["input_ids"] if isinstance(batch, dict) else batch)
        labels = batch.get("labels", ids) if isinstance(batch, dict) else ids
        h = self.pipe(p["stages"], ids, mesh=mesh)
        from deepspeed_tpu.models.llama import chunked_causal_lm_loss
        return chunked_causal_lm_loss(h, p["head"], labels)

    def param_specs(self, params):
        """Replicated over 'pipe' (heterogeneous stage trees can't ride one
        sharded axis); leaves remain shardable over fsdp by the engine."""
        p = params["params"] if "params" in params else params
        return jax.tree_util.tree_map(lambda _: P(), p)


class PipelineLM:
    """A complete pipeline-parallel causal LM, engine-compatible.

    Parity: the reference trains a ``PipelineModule`` holding
    ``[EmbeddingPipe, *blocks, LMHead]`` through ``PipelineEngine.train_batch``
    (pipe/engine.py:321). Here the embedding/head live replicated outside the
    pipeline region, the block stack rides :func:`gpipe_apply`, and the CORE
    engine trains it like any model::

        lm = PipelineLM(vocab_size=V, block=MyBlock(), n_layers=L, n_micro=M)
        params = lm.init(rng, batch)["params"]
        engine, *_ = deepspeed_tpu.initialize(
            model=lm, model_parameters=params,
            param_specs=lm.param_specs(params),   # stack shards over 'pipe'
            config={..., "mesh": {"pipe": P, ...}})

    ``init``/``apply`` duck-type a flax module: ``apply(params, batch) ->
    mean next-token loss`` (fused chunked CE, so [B, T, V] never materialises).
    """

    def __init__(self, vocab_size: int, d_model: int, block, n_layers: int,
                 n_micro: int = 1, init_scale: float = 0.02,
                 remat_ticks: bool = True):
        self.vocab_size = vocab_size
        self.d_model = d_model
        self.pipe = PipelineModule(block, n_layers, n_micro,
                                   remat_ticks=remat_ticks)
        self.init_scale = init_scale

    def init(self, rng, batch):
        ids = jnp.asarray(batch["input_ids"] if isinstance(batch, dict) else batch)
        k_wte, k_stack = jax.random.split(rng)
        wte = self.init_scale * jax.random.normal(
            k_wte, (self.vocab_size, self.d_model), jnp.float32)
        sample_x = wte[ids[:1]]
        stacked = self.pipe.init_stacked(k_stack, sample_x)
        return {"params": {"wte": wte, "stack": stacked}}

    def apply(self, variables, batch, rngs=None, mesh=None):
        p = variables["params"] if "params" in variables else variables
        ids = jnp.asarray(batch["input_ids"] if isinstance(batch, dict) else batch)
        labels = batch.get("labels", ids) if isinstance(batch, dict) else ids
        x = p["wte"][ids]  # gather FIRST; dtype follows the engine's cast
        h = self.pipe(p["stack"], x, mesh=mesh)
        from deepspeed_tpu.models.llama import chunked_causal_lm_loss
        return chunked_causal_lm_loss(h, p["wte"], labels)

    def param_specs(self, params):
        """Explicit engine shardings: the stack's leading (layer) dim over
        'pipe'; embedding replicated (pass as ``initialize(param_specs=...)``)."""
        p = params["params"] if "params" in params else params
        return {"wte": P(),
                "stack": self.pipe.stacked_param_specs(p["stack"])}
