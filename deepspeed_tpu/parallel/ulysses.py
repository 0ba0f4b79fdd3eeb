"""DeepSpeed-Ulysses sequence parallelism, TPU-native.

Parity: ``DistributedAttention`` (reference ``deepspeed/sequence/layer.py:60``) with
``_SeqAllToAll`` (:44) / ``single_all_to_all`` (:15): all-to-all #1 converts
sequence-sharded QKV [s/P, h] to head-sharded full-sequence [s, h/P], any local
attention runs, all-to-all #2 converts back. Comm volume O(N·h/P) per link vs
allgather O(N·h) (blogs/deepspeed-ulysses).

Two TPU forms are provided:

- ``ulysses_attention`` — GSPMD form: two ``with_sharding_constraint`` resharding
  annotations around the attention call; XLA lowers the seq<->head resharding to
  exactly the two all-to-alls, scheduled/overlapped by the compiler. This is the
  idiomatic form used by the models.
- ``DistributedAttention`` — explicit shard_map form with ``lax.all_to_all`` for
  call-discipline parity with the reference (usable inside custom shard_map code).
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.comm.mesh import BATCH_AXES, SEQ_AXIS, get_topology


def ulysses_attention(attn_fn: Callable, q: jax.Array, k: jax.Array, v: jax.Array,
                      *args, mesh=None, **kwargs) -> jax.Array:
    """GSPMD Ulysses: q/k/v are logically [B, T, H, D] with T sharded over 'seq';
    constrain to head-sharded for the attention, back to seq-sharded after.

    Works under plain jit: XLA inserts all-to-all pairs on the 'seq' axis.
    """
    mesh = mesh or get_topology().mesh
    seq_sharded = NamedSharding(mesh, P(BATCH_AXES, SEQ_AXIS, None, None))
    head_sharded = NamedSharding(mesh, P(BATCH_AXES, None, SEQ_AXIS, None))

    q, k, v = (lax.with_sharding_constraint(t, head_sharded) for t in (q, k, v))
    out = attn_fn(q, k, v, *args, **kwargs)
    return lax.with_sharding_constraint(out, seq_sharded)


def single_all_to_all(x: jax.Array, scatter_idx: int, gather_idx: int,
                      axis_name: str = SEQ_AXIS) -> jax.Array:
    """Parity: ``single_all_to_all`` (sequence/layer.py:15). For use inside
    shard_map: scatter local dim ``scatter_idx`` across the axis, gather the axis
    into dim ``gather_idx``."""
    return lax.all_to_all(x, axis_name, split_axis=scatter_idx,
                          concat_axis=gather_idx, tiled=True)


def _gqa_repeat(q, k, v):
    """Repeat KV heads up to the query head count (GQA). Kept here (rather
    than importing models.llama.repeat_kv) so the parallel wrappers stay
    model-agnostic; ONE copy for both the Ulysses and ring paths."""
    if k.shape[2] != q.shape[2]:
        rep = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, rep, 2)
        v = jnp.repeat(v, rep, 2)
    return k, v


def sequence_parallel_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                                causal: bool = True,
                                softmax_scale: Optional[float] = None,
                                mesh=None) -> jax.Array:
    """Training-path Ulysses for the model zoo: [B, T, H, D] attention with T
    sharded over the 'seq' mesh axis.

    Uses the explicit shard_map + all-to-all form (``DistributedAttention``)
    rather than GSPMD constraints so the local attention can be the Pallas
    flash kernel — a ``pallas_call`` under plain-jit GSPMD with sharded
    operands has no SPMD rule, while under shard_map each shard calls the
    kernel on its local [B, T, H/P, D] block. Degenerates to the plain
    routed attention when the seq axis is 1.

    Head/seq divisibility by the axis size is required (reference
    ``DistributedAttention`` has the same constraint, sequence/layer.py:60).
    """
    topo = get_topology()
    mesh = mesh or topo.mesh
    P_seq = mesh.shape[SEQ_AXIS]
    from deepspeed_tpu.ops.attention import dot_product_attention

    if P_seq <= 1:
        k, v = _gqa_repeat(q, k, v)
        return dot_product_attention(q, k, v, causal=causal,
                                     softmax_scale=softmax_scale)
    H, Hkv, T = q.shape[2], k.shape[2], q.shape[1]
    if H % P_seq or Hkv % P_seq or T % P_seq:
        raise ValueError(
            f"sequence_parallel_attention needs heads ({H}/{Hkv}) and T ({T}) "
            f"divisible by the seq axis size {P_seq}")

    def _local(q, k, v):
        # GQA: repeat kv heads post-scatter, so the all-to-all moved only
        # Hkv/P heads per link instead of H/P
        k, v = _gqa_repeat(q, k, v)
        return dot_product_attention(q, k, v, causal=causal,
                                     softmax_scale=softmax_scale)

    dist_attn = DistributedAttention(_local)
    fn = jax.shard_map(
        dist_attn, mesh=mesh,
        in_specs=(P(BATCH_AXES, SEQ_AXIS, None, None),) * 3,
        out_specs=P(BATCH_AXES, SEQ_AXIS, None, None),
        check_vma=False)
    return fn(q, k, v)


def context_parallel_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                               causal: bool = True,
                               softmax_scale: Optional[float] = None,
                               mesh=None) -> jax.Array:
    """Ring-attention context parallelism for the model zoo: [B, T, H, D]
    with T sharded over 'seq'; KV blocks rotate the ICI ring via ppermute
    while each shard accumulates online-softmax partials for its local Q
    (parallel/ring.py — the TPU-natural CP strategy; the reference snapshot
    has no CP at all, SURVEY.md §2.3). Unlike Ulysses there is NO head-count
    divisibility requirement — only T must divide by the axis size."""
    topo = get_topology()
    mesh = mesh or topo.mesh
    P_seq = mesh.shape[SEQ_AXIS]
    from deepspeed_tpu.ops.attention import dot_product_attention

    if P_seq <= 1:
        k, v = _gqa_repeat(q, k, v)
        return dot_product_attention(q, k, v, causal=causal,
                                     softmax_scale=softmax_scale)
    if q.shape[1] % P_seq:
        raise ValueError(f"context_parallel_attention needs T ({q.shape[1]}) "
                         f"divisible by the seq axis size {P_seq}")
    from deepspeed_tpu.parallel.ring import ring_attention

    def _local(q, k, v):
        # KV enters (and rotates) the ring at Hkv heads; ring_attention
        # contracts the (Hkv, rep) query grouping against the un-repeated
        # block, so neither ICI nor per-step memory ever sees repeated KV
        return ring_attention(q, k, v, causal=causal,
                              softmax_scale=softmax_scale)

    fn = jax.shard_map(
        _local, mesh=mesh,
        in_specs=(P(BATCH_AXES, SEQ_AXIS, None, None),) * 3,
        out_specs=P(BATCH_AXES, SEQ_AXIS, None, None),
        check_vma=False)
    return fn(q, k, v)


class DistributedAttention:
    """Parity: ``DistributedAttention`` (sequence/layer.py:60).

    Explicit all-to-all wrapper for shard_map code: ``__call__(q, k, v)`` where the
    tensors are the local sequence shards [B, T/P, H, D]; returns the local shard
    of the attention output.
    """

    def __init__(self, local_attention: Callable, axis_name: str = SEQ_AXIS,
                 scatter_idx: int = 2, gather_idx: int = 1):
        self.local_attn = local_attention
        self.axis_name = axis_name
        self.scatter_idx = scatter_idx  # head dim of [B, T, H, D]
        self.gather_idx = gather_idx    # seq dim

    def __call__(self, query, key, value, *args, **kwargs):
        a = self.axis_name
        q = single_all_to_all(query, self.scatter_idx, self.gather_idx, a)
        k = single_all_to_all(key, self.scatter_idx, self.gather_idx, a)
        v = single_all_to_all(value, self.scatter_idx, self.gather_idx, a)
        ctx = self.local_attn(q, k, v, *args, **kwargs)
        # reverse: scatter seq, gather heads
        return single_all_to_all(ctx, self.gather_idx, self.scatter_idx, a)
