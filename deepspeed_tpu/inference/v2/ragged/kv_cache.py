"""Blocked (paged) KV cache.

Parity: ``KVCacheManager`` / blocked KV configs (reference
``inference/v2/ragged/kv_cache.py`` + ``inference/v2/ragged/manager_configs.py``).
Pages are device arrays ``[L, num_blocks, H_kv, block_size, D]`` — HEAD-MAJOR
pages, chosen so

  - every pool view in the serving program has (block_size, head_dim) trailing
    dims: no padded sublane tiles for any kv-head count, so the flat-rows <->
    paged reshapes in the layer scan are bitcasts (a head-minor layout makes
    XLA materialise pool-sized copies at e.g. H_kv=12 — see
    ops/pallas/paged_attention.py module docstring);
  - the paged kernels pull whole contiguous pages via scalar-prefetched block
    tables, one DMA per page;
  - the per-token cache write is a flat scatter of H_kv rows at
    ``(block * H_kv + h) * block_size + slot``.

Sharding: KV heads ride the 'tensor' mesh axis when divisible (the reference slices
KV heads across TP ranks in its sharded model implementations); layers/pages are
never sharded — a page must live whole on the chip that attends with it.

The cache arrays are *functional*: each engine pass takes them as donated jit
arguments and returns the updated pages, so XLA aliases them in place in HBM.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.comm.mesh import TENSOR_AXIS, MeshTopology


@dataclass
class KVCacheConfig:
    num_layers: int
    num_kv_heads: int
    head_dim: int
    block_size: int = 128
    num_blocks: int = 256
    dtype: Any = jnp.bfloat16
    # int8 pages with per-token-head f32 scales (see config_v2.KVQuantConfig):
    # the pools become (int8 values, f32 scales) pytrees; every consumer
    # dequantizes in-kernel
    quantized: bool = False
    # latent pages (multi-head latent attention): the values of ONE row a
    # token a layer holds — the compressed latent and the shared rotary key,
    # padded to whole lane tiles (``model_spec.latent_width``). The pool is
    # then [L, NB, bs, latent_dim]: no head axis, no K/V pair, and
    # ``num_kv_heads``/``head_dim`` describe nothing in it
    latent_dim: Optional[int] = None
    # an index key a token a layer beside the latent row (a learned
    # selection inside latent attention: ``model_spec.index_width``): a
    # SECOND pool [L, NB, bs, index_dim] under the same page ids — one
    # allocator, one block table — so that the index scan reads pages of
    # keys and nothing else; the cache is then the pair (latent, index)
    index_dim: Optional[int] = None

    @property
    def max_tokens(self) -> int:
        return self.num_blocks * self.block_size

    def bytes_per_block(self) -> int:
        """Exact at-rest bytes of one pool block across all layers — for
        quantized pools this is ALSO the host page-fabric payload size
        (``engine.page_payload_spec``): int8 values plus the f32 scale tile
        in its padded DMA layout, one source of size truth for offload
        capacity accounting and handoff validation."""
        if self.quantized:
            from deepspeed_tpu.ops.pallas.paged_attention import (
                kv_scale_tiles_shape)
            _, r8, lanes = kv_scale_tiles_shape(1, self.num_kv_heads,
                                                self.block_size)
            values = 2 * self.num_kv_heads * self.block_size * self.head_dim
            return self.num_layers * (values + r8 * lanes * 4)
        itemsize = jnp.dtype(self.dtype).itemsize
        if self.latent_dim is not None:
            return (self.num_layers * self.block_size
                    * (self.latent_dim + (self.index_dim or 0)) * itemsize)
        return (2 * self.num_layers * self.block_size * self.num_kv_heads
                * self.head_dim * itemsize)

    @property
    def page_shape(self) -> Tuple[int, ...]:
        """One page of all layers, as the pool holds it less the page axis."""
        if self.latent_dim is not None:
            return (self.num_layers, self.block_size, self.latent_dim)
        return (self.num_layers, 2, self.num_kv_heads, self.block_size,
                self.head_dim)

    @classmethod
    def from_memory_budget(cls, num_layers: int, num_kv_heads: int, head_dim: int,
                           budget_bytes: int, block_size: int = 128,
                           dtype: Any = jnp.bfloat16,
                           latent_dim: Optional[int] = None,
                           index_dim: Optional[int] = None
                           ) -> "KVCacheConfig":
        """Size the pool from an HBM budget (parity: the reference sizes its pool
        from free GPU memory after model load, ``engine_v2.py`` memory config).
        With ``latent_dim`` the pages are latent rows and the two head
        arguments count for nothing; ``index_dim`` funds an index key a
        token a layer beside each."""
        probe = cls(num_layers, num_kv_heads, head_dim, block_size, 1, dtype,
                    latent_dim=latent_dim, index_dim=index_dim)
        nb = max(1, budget_bytes // probe.bytes_per_block())
        return cls(num_layers, num_kv_heads, head_dim, block_size, int(nb),
                   dtype, latent_dim=latent_dim, index_dim=index_dim)


class BlockedKVCache:
    """Owns the combined page array [L, NB, 2, Hkv, bs, D] (K = index 0,
    V = index 1 — one page per sequence-chunk holds BOTH, because the
    decode kernel is per-DMA-copy bound; see ops/pallas/paged_attention.py)
    and its sharding. With ``config.quantized`` the pool is an (int8
    values, f32 per-token-head scales [L, NB, 2, Hkv, bs]) tuple. With
    ``config.latent_dim`` (latent attention) it is [L, NB, bs, latent_dim]:
    one row a token a layer, the page axis still at 1, so whatever moves
    whole pages by that axis (``copy_page``, the engine's page gather and
    scatter) carries it unchanged. With ``config.index_dim`` too it is the
    pair (latent pages, index-key pages [L, NB, bs, index_dim])."""

    def __init__(self, config: KVCacheConfig, topology: Optional[MeshTopology] = None):
        self.config = config
        self.topology = topology
        self._copy_prog = None      # COW page-copy program (copy_page)
        shape = (config.num_layers, config.num_blocks) + config.page_shape[1:]
        sharding = None
        if topology is not None:
            tp = topology.tp_world_size
            spec = [None] * len(shape)
            if config.latent_dim is not None:
                assert tp == 1 and not config.quantized, \
                    "latent pages are neither sharded nor quantized"
            elif tp > 1 and config.num_kv_heads % tp == 0:
                spec[3] = TENSOR_AXIS
            sharding = NamedSharding(topology.mesh, P(*spec))
        if config.quantized:
            if sharding is not None and topology.tp_world_size > 1:
                raise NotImplementedError(
                    "int8 KV pages with tensor_parallel > 1 are not wired")
            # scales live in the kernels' DMA tile layout AT REST
            # ([L, NB, R8, 128] f32; paged_attention.kv_scale_tiles_shape) so
            # no pass ever pays a pool-sized pad+reshape to convert them
            from deepspeed_tpu.ops.pallas.paged_attention import (
                kv_scale_tiles_shape)
            sshape = (config.num_layers,) + kv_scale_tiles_shape(
                config.num_blocks, config.num_kv_heads, config.block_size)
            self.kv = (_zeros(shape, jnp.int8, None),
                       _zeros(sshape, jnp.float32, None))
        else:
            self.kv = _zeros(shape, config.dtype, sharding)
            if config.index_dim is not None:
                assert config.latent_dim is not None, \
                    "index keys ride beside latent pages only"
                self.kv = (self.kv, _zeros(shape[:-1] + (config.index_dim,),
                                           config.dtype, sharding))
        self.sharding = sharding

    def update(self, kv) -> None:
        """Adopt the pages returned by a jitted pass (donated in, aliased out)."""
        self.kv = kv

    def copy_page(self, src_block: int, dst_block: int) -> None:
        """Device-side copy of one whole page (all layers, K and V) — the
        prefix cache's copy-on-write step when a sequence adopts a
        partially-filled cached page it must keep writing into. One jitted
        program reused for every (src, dst) pair via traced scalar indices.
        The tree_map'd body carries a quantized pool's (values, scale
        tiles) tuple leaf-for-leaf — both leaves have the page dim at axis
        1, so COW adoption copies a page's int8 bytes AND its scale tile
        together, byte-exactly (tests/unit/test_kv_quant_stack.py)."""
        if self._copy_prog is None:
            import functools

            @functools.partial(jax.jit, donate_argnums=(0,))
            def _copy(kv, src, dst):
                return jax.tree_util.tree_map(
                    lambda a: a.at[:, dst].set(a[:, src]), kv)

            self._copy_prog = _copy
        self.kv = self._copy_prog(self.kv, jnp.int32(src_block),
                                  jnp.int32(dst_block))

    def flat_write_index(self, block_id: np.ndarray, slot: np.ndarray) -> np.ndarray:
        """Host-side: flat scatter destination over the fused page dim; padding
        rows use an out-of-bounds sentinel so the scatter drops them."""
        return (np.asarray(block_id, np.int64) * self.config.block_size
                + np.asarray(slot, np.int64)).astype(np.int32)

    @property
    def oob_sentinel(self) -> int:
        return self.config.num_blocks * self.config.block_size


def _zeros(shape: Tuple[int, ...], dtype, sharding):
    if sharding is None:
        return jnp.zeros(shape, dtype)
    return jax.jit(lambda: jnp.zeros(shape, dtype), out_shardings=sharding)()
