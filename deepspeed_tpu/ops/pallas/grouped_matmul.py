"""Grouped matmul for the serving path's MoE experts: sorted rows against
the experts they chose, each touched expert matrix read once where it lies.

``lhs`` ``[M, K]`` holds the assignments sorted by expert, ``rhs`` the whole
expert stack viewed ``[L*E, K, N]`` (every layer's experts; nothing of it is
sliced or copied), ``group_sizes`` ``[E]`` how many rows chose each expert of
layer ``l``. Row ``r`` of group ``g`` gets ``lhs[r] @ rhs[l*E + g]``, float32
accumulation, ``lhs.dtype`` out: what ``jax.lax.ragged_dot`` computes.

XLA:TPU's own kernel for ``ragged_dot`` skips the groups no row chose too,
but reads a touched 3-4 MiB matrix at half the HBM rate (427 GB/s at
Trinity-Mini's shapes, 315 at JoyAI's) and digests a size vector as long as
the whole stack in a metadata kernel before every call; this one reads them
at 700-720 GB/s (the chip table in PERF.md, PR 34). Its grid walks *visits*:
one per (touched group, row tile the group has rows in), in row order. An
empty group has no visit, and its matrix is never fetched. Consecutive
visits of one group (a group longer than a row tile) keep its matrix in
on-chip memory; consecutive visits of one row tile (several small groups in
it) keep the output tile there and each stores only its own rows, as
megablox does (``jax.experimental.pallas.ops.tpu.megablox``). The visit
lists come from ``E`` group sizes in a few small fusions
(:func:`plan_visits`), once a layer for its gate, up and down products, and
reach the kernel as prefetched scalars; the number of visits is the grid's
(dynamic) extent.

Rows of no group (the pad to a whole row tile, and under held experts the
assignments that went to experts held elsewhere) sort last: in a row tile
some group also has rows in they come out 0, in a tile no group visits
they are left undefined — the caller drops or masks them.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas import _backend


# what one rhs block may take of on-chip memory (it is double-buffered): a
# whole 3-4 MiB expert matrix fits, and a larger one is cut along N, then K
RHS_BLOCK_BYTES = 4 << 20


class Visits(NamedTuple):
    """The grid of one layer's grouped matmuls (:func:`plan_visits`)."""
    offsets: jax.Array      # [E + 1] int32: group g's rows are offsets[g:g+2]
    group: jax.Array        # [V] int32: the group a visit computes
    tile: jax.Array         # [V] int32: the row tile it computes it on
    count: jax.Array        # [1] int32: how many visits there are (>= 1)
    tm: int                 # rows a tile


def row_tile(m: int) -> int:
    """Rows a tile for ``m`` assignments (the chip table in PERF.md, PR 34).
    Groups of a decode step hold a few rows each, yet tiles of 64 beat tiles
    of 16 by 2%: fewer groups lie across two tiles, and the matrix unit takes
    as long over 16 rows as over 64 (it is loading the weights). 128 rows, the
    unit's height, once the rows are many (a prefill pass: 983 us against
    1,037 at Trinity's shapes)."""
    return 64 if m <= 1024 else 128


def plan_visits(group_sizes: jax.Array, m: int, tm: int) -> Visits:
    """Which (group, row tile) pairs hold rows, in row order. ``m`` (a
    multiple of ``tm``) is the row count the matmuls will be called with;
    rows past ``group_sizes.sum()`` belong to no group. A group of ``n``
    rows starting at row ``s`` visits tiles ``s // tm .. (s + n - 1) // tm``;
    there are at most ``m // tm + E - 1`` visits. Built from triangular sums
    and comparisons over ``[E, E]`` and ``[V, E]`` — no sort, no scatter, no
    serial cumsum — so it is a few small fusions (6 us a layer on the chip)."""
    assert m % tm == 0, (m, tm)
    E = group_sizes.shape[0]
    sizes = group_sizes.astype(jnp.int32)
    upto = jnp.arange(E)[:, None] >= jnp.arange(E)[None, :]
    ends = jnp.sum(jnp.where(upto, sizes[None, :], 0), axis=1)
    starts = ends - sizes
    first = starts // tm
    n = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    vend = jnp.sum(jnp.where(upto, n[None, :], 0), axis=1)
    vstart = vend - n
    v = jnp.arange(m // tm + E - 1, dtype=jnp.int32)[:, None]
    mine = (v >= vstart[None, :]) & (v < vend[None, :])            # [V, E]
    group = jnp.sum(jnp.where(mine, jnp.arange(E, dtype=jnp.int32), 0), 1)
    tile = jnp.sum(jnp.where(mine, first[None, :] + v - vstart[None, :], 0), 1)
    # a grid of no step is no grid: one visit of group 0 stores nothing
    count = jnp.maximum(vend[-1:], 1)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    return Visits(offsets, group.astype(jnp.int32), tile.astype(jnp.int32),
                  count.astype(jnp.int32), tm)


def rhs_tiles(k: int, n: int, itemsize: int) -> Tuple[int, int]:
    """``(tk, tn)`` of the rhs block: the whole ``[K, N]`` matrix if it is
    within :data:`RHS_BLOCK_BYTES`, else the widest cut of ``N`` into
    multiples of 128 lanes (at least 512) that is, else ``K`` halved too."""
    tk, tn = k, n
    while tk * tn * itemsize > RHS_BLOCK_BYTES and tn % 256 == 0 and tn > 512:
        tn //= 2
    while tk * tn * itemsize > RHS_BLOCK_BYTES and tk % 256 == 0:
        tk //= 2
    return tk, tn


def _kernel(offsets, group, tile, count, layer, lhs_ref, rhs_ref, out_ref,
            *scratch, tm: int, tiles_k: int):
    del count, layer
    v, k_i = pl.program_id(1), pl.program_id(2)

    def store(acc):
        g, t = group[v], tile[v]

        @pl.when((v == 0) | (tile[jnp.maximum(v - 1, 0)] != t))
        def _first_visit_of_the_tile():
            out_ref[...] = jnp.zeros_like(out_ref)

        row = t * tm + jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 0)
        mine = (row >= offsets[g]) & (row < offsets[g + 1])
        out_ref[...] = jnp.where(mine, acc.astype(out_ref.dtype), out_ref[...])

    part = jnp.dot(lhs_ref[...], rhs_ref[...],
                   preferred_element_type=jnp.float32)
    if tiles_k == 1:
        store(part)
        return
    acc_ref, = scratch

    @pl.when(k_i == 0)
    def _():
        acc_ref[...] = part

    @pl.when(k_i > 0)
    def _():
        acc_ref[...] += part

    @pl.when(k_i == tiles_k - 1)
    def _():
        store(acc_ref[...])


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, visits: Visits,
                   layer=0, tiles: Tuple[int, int] | None = None) -> jax.Array:
    """``out[r] = lhs[r] @ rhs[layer * E + g]`` for the rows ``r`` of each
    group ``g`` of ``visits`` (:func:`plan_visits` of the layer's ``E`` group
    sizes, ``lhs.shape[0]`` and a row tile).

    lhs:    [M, K], rows sorted by group, ``M`` a multiple of ``visits.tm``
    rhs:    [L*E, K, N] — every layer's groups, read in place
    layer:  int32 scalar (traced or not): whose groups ``visits`` counts
    tiles:  ``(tk, tn)`` of the rhs block; :func:`rhs_tiles` by default

    Returns ``[M, N]`` in ``lhs.dtype``; rows of no group as the module says.
    """
    m, k = lhs.shape
    n = rhs.shape[-1]
    tm = visits.tm
    E = visits.offsets.shape[0] - 1
    assert m % tm == 0 and rhs.shape[1] == k, (lhs.shape, rhs.shape, tm)
    assert rhs.dtype == lhs.dtype, (lhs.dtype, rhs.dtype)   # no cast: a copy
    tk, tn = tiles or rhs_tiles(k, n, rhs.dtype.itemsize)
    assert k % tk == 0 and n % tn == 0, (k, n, tk, tn)
    tiles_k = k // tk

    def lhs_map(n_i, v, k_i, offsets, group, tile, count, layer):
        return tile[v], k_i

    def rhs_map(n_i, v, k_i, offsets, group, tile, count, layer):
        return layer[0] * E + group[v], k_i, n_i

    def out_map(n_i, v, k_i, offsets, group, tile, count, layer):
        return tile[v], n_i

    item = lhs.dtype.itemsize
    blocks = (tk * tn * rhs.dtype.itemsize + tm * tk * item + tm * tn * item)
    call = pl.pallas_call(
        functools.partial(_kernel, tm=tm, tiles_k=tiles_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(n // tn, visits.count[0], tiles_k),
            in_specs=[pl.BlockSpec((tm, tk), lhs_map),
                      pl.BlockSpec((None, tk, tn), rhs_map)],
            out_specs=pl.BlockSpec((tm, tn), out_map),
            scratch_shapes=([pltpu.VMEM((tm, tn), jnp.float32)]
                            if tiles_k > 1 else [])),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=2 * blocks + tm * tn * 4 + (8 << 20)),
        interpret=_backend.interpret(),
    )
    with jax.named_scope("moe_grouped_matmul"):
        return call(visits.offsets, visits.group, visits.tile, visits.count,
                    jnp.asarray(layer, jnp.int32).reshape(1), lhs, rhs)
