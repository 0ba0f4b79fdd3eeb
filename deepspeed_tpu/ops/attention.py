"""Attention dispatch: jnp reference implementation + Pallas flash kernel routing.

Parity role: the reference's fused attention kernels (``csrc/transformer/inference``
softmax/attention ops, blocked flash in ``inference/v2/kernels/ragged_ops``).

Routing: on TPU, sequences >= FLASH_MIN_SEQ take the Pallas flash kernel
(``ops/pallas/flash_attention.py``); shorter sequences, CPU, bias, and packed
segment-ids take the jnp path (XLA's own fusion wins at short T, but it
materializes [T, T] scores — override the threshold via DSTPU_FLASH_MIN_SEQ if
memory, not speed, is the constraint).
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp


def _use_pallas() -> bool:
    return (not os.environ.get("DSTPU_DISABLE_PALLAS")
            and jax.default_backend() == "tpu")


# Threshold re-tuned on the full GPT-2-medium train step (v5e-1, bf16, remat,
# T=1024): flash 24.8k tok/s vs XLA-dense 20.1k at bs=32, and flash's O(T)
# memory admits bs=64 (26.7k) where the dense path OOMs — the earlier small-B
# microbenchmark (B=4: XLA 6.8ms vs flash 9.2ms) was misleading at training
# batch sizes, where the [B,H,T,T] fp32 score tensor is HBM-bound.
# Env override: DSTPU_FLASH_MIN_SEQ (raise it for tiny-batch inference).
FLASH_MIN_SEQ = int(os.environ.get("DSTPU_FLASH_MIN_SEQ", 1024))


def padding_mask_to_bias(mask: jax.Array) -> jax.Array:
    """HF-style [B, S] key mask (1 = attend) -> additive fp32 bias
    [B, 1, 1, S]. Shared by the model zoo and the fused transformer layer."""
    return jnp.where(mask[:, None, None, :] > 0, 0.0,
                     jnp.finfo(jnp.float32).min)


def dot_product_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                          causal: bool = False,
                          bias: Optional[jax.Array] = None,
                          segment_ids: Optional[jax.Array] = None,
                          softmax_scale: Optional[float] = None) -> jax.Array:
    """[B, T, H, D] attention. On TPU, unbiased sequences of at least
    FLASH_MIN_SEQ tokens take the Pallas flash kernel, and a kernel that
    fails to compile fails the step: there is no fallback to the dense
    ``[T, T]`` path, which would hide the failure behind a slower step."""
    if _use_pallas() and bias is None and q.shape[1] >= FLASH_MIN_SEQ:
        return _flash_over_mesh(q, k, v, causal, segment_ids, softmax_scale)
    return reference_attention(q, k, v, causal=causal, bias=bias,
                               segment_ids=segment_ids, softmax_scale=softmax_scale)


def _flash_over_mesh(q, k, v, causal, segment_ids, softmax_scale):
    """The flash kernel, under whatever mesh the caller's program spans.

    The SPMD partitioner cannot split a Mosaic kernel ("Mosaic kernels cannot
    be automatically partitioned"): a program over more than one device must
    call it inside a ``shard_map`` that is manual over every mesh axis.
    Attention is independent across batch rows and heads, so under the
    ambient mesh (the one an engine set) the kernel runs per shard with the
    batch over the data axes and the heads over 'tensor'. Inside a caller's
    own ``shard_map`` (Ulysses, ring) and on one device it is called as is —
    and so is a packed (``segment_ids``) call, which ``flash_attention``
    serves with jnp ops and no kernel."""
    from deepspeed_tpu.comm.mesh import (BATCH_AXES, TENSOR_AXIS,
                                         topology_if_set)
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    local = functools.partial(flash_attention, causal=causal,
                              segment_ids=segment_ids,
                              softmax_scale=softmax_scale)
    topo = topology_if_set()
    if (segment_ids is not None or topo is None or topo.world_size == 1
            or jax.sharding.get_abstract_mesh().manual_axes):
        return local(q, k, v)
    from jax.sharding import PartitionSpec as P
    rows = P(BATCH_AXES, None, TENSOR_AXIS, None)
    return jax.shard_map(local, mesh=topo.mesh, in_specs=(rows,) * 3,
                         out_specs=rows, check_vma=False)(q, k, v)


def reference_attention(q, k, v, causal=False, bias=None, segment_ids=None,
                        softmax_scale=None):
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    scale = softmax_scale if softmax_scale is not None else 1.0 / (D ** 0.5)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if bias is not None:
        scores = scores + bias
    if causal:
        mask = jnp.tril(jnp.ones((Tq, Tk), dtype=bool), k=Tk - Tq)
        scores = jnp.where(mask[None, None], scores, jnp.finfo(jnp.float32).min)
    if segment_ids is not None:
        seg_mask = segment_ids[:, :, None] == segment_ids[:, None, :]
        scores = jnp.where(seg_mask[:, None], scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)
