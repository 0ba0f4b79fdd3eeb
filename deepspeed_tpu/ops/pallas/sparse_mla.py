"""A learned per-token selection inside latent paged attention (DeepSeek
Sparse Attention, ``glm_moe_dsa``) for TPU (Pallas): index scores, an exact
top-k threshold, attention over the chosen latent rows.

Beside the latent pool ``[NB, bs, W]`` (``mla_attention.py``) a second pool
``[NB, bs, Di]`` holds one INDEX KEY a token a layer, under the same page
ids. A query token scores every cached token it may see,

    I[t, s] = sum_j w[t, j] * relu(q_idx[t, j] . k_idx[s])        s <= t

over ``Hi`` small index heads with signed weights, keeps the ``topk`` largest
(all, where it sees no more than ``topk``) and attends over those alone.

Three pieces of work, each a Mosaic kernel with a plain ``jnp`` twin
(``*_reference``) the CPU tests hold it to:

1. :func:`index_scores` (``dsa_index_decode`` / ``dsa_index_chunk``): the
   scores of a slot's query tokens against the slot's index pages, reduced
   over the heads before they leave the chip. They come out TILED, ``[N, C,
   R, T]`` float32 — tile ``c`` holds positions ``c * T .. c * T + T - 1`` —
   with ``-inf`` where a query may not look (past itself, past the context):
   the form the two readers below take a tile of at a time.
2. :func:`select` (``dsa_select``): per query row the EXACT k-th largest
   score — a bisection on the float32 bit pattern by compare-and-count
   sweeps of the row in VMEM, no sort — and ``pcut``, the position up to
   which scores EQUAL to it are kept, so that exactly ``k`` are
   (``jax.lax.top_k``'s rule: of equal scores the lower position first).
   ``keep(s) = I > thr or (I == thr and s <= pcut)``. A block of query rows
   (:func:`_select_block`: as many as a walk's fixed cost wants and VMEM
   holds) walks its tiles once for bounds — the row's largest score above,
   the smallest of 2,048 disjoint groups' maxima below, which the k-th
   largest cannot lie under — then once a HALVING of what lies between, and
   stops when every row has a candidate that counts exactly ``k`` scores at
   or over it: no score lies between that candidate and the k-th largest,
   so one more walk for the smallest score at or over it gives ``thr``, and
   no tie is cut. Some 25 walks where a sweep a bit took 1 + 32 + 2; a row
   whose ties outnumber its quota runs to the last bit and through 32 more
   for ``pcut``, as it always did. :func:`select_counted` hands back the
   walks a block took.
3. :func:`attend_decode` (``dsa_attend_decode``): a decode row's absorbed
   attention over its chosen rows, gathered by XLA into ``[N, K, W]``
   (:func:`chosen_positions` compacts the kept positions without a sort,
   a scatter or a gather);
   :func:`attend_chunk` (``dsa_attend_chunk``): a prompt chunk's absorbed
   attention over the slot's latent pages under the per-(query, key) mask
   ``keep``, one mask for all heads — ``mla_attention._mla_kernel``'s walk
   with a tile of scores copied beside each chunk of pages. (A per-query
   gather in prefill would be ``256 x 2048`` rows a slot a layer.)
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas import _backend
from deepspeed_tpu.ops.pallas.mla_attention import _pick_rows_block
from deepspeed_tpu.ops.pallas.paged_attention import NEG_INF, _flash_update


LANES = 128
#: keys a tile of a chunk's scores: the attention kernel's chunk of pages
#: (its scores of 512 query rows against a tile are a MiB in float32)
CHUNK_TILE = 512
#: keys a tile of a decode row's scores (a copy of 512 KiB of index keys)
DECODE_TILE = 2048


def tile_pages(block_size: int, max_blocks: int, tile: int) -> int:
    """Pages a tile of scores covers: about ``tile`` keys, whole lane tiles,
    no more than the block table holds."""
    unit = LANES // math.gcd(block_size, LANES)
    want = max(unit, tile // block_size // unit * unit)
    return min(want, -(-max_blocks // unit) * unit)


def _pad_tables(block_tables, pages: int):
    """The block table padded to whole tiles of ``pages`` (page 0: masked)."""
    MB = block_tables.shape[1]
    pad = -MB % pages
    bt = block_tables.astype(jnp.int32)
    return jnp.pad(bt, ((0, 0), (0, pad))) if pad else bt


# --------------------------------------------------------------------------- #
# 1. index scores
# --------------------------------------------------------------------------- #


def _weighted_relu_sum(q_ref, w_ref, kk, rows: int, heads: int):
    """``sum_j w_j relu(q_j . k)`` of a block: ``[rows, T]`` float32. Decode
    (``rows == 1``): q ``[Hi, D]``, w ``[Hi, 1]``, one product, the sum down
    the sublanes. Chunk: q ``[Hi, rows, D]``, w ``[rows, Hi]``, a product a
    head and the head's weights as a column."""
    dot = lambda a: jax.lax.dot_general(
        a.astype(kk.dtype), kk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    if rows == 1:
        s = jnp.maximum(dot(q_ref[0]), 0.0) * w_ref[0]
        acc = jnp.sum(s, axis=0, keepdims=True)
    else:
        acc = None
        for h in range(heads):
            s = jnp.maximum(dot(q_ref[0, h]), 0.0) * w_ref[0, :, h:h + 1]
            acc = s if acc is None else acc + s
    # -0.0 is +0.0 to a comparison and another bit pattern to the bisection
    return jnp.where(acc == 0.0, 0.0, acc)


def _masked(acc, c, tile, q_pos, ctx):
    pos = c * tile + jax.lax.broadcasted_iota(jnp.int32, acc.shape, 1)
    return jnp.where(jnp.logical_and(pos < ctx, pos <= q_pos), acc, -jnp.inf)


def _index_chunk_kernel(bt_ref, q0_ref, cl_ref, q_ref, w_ref, k_hbm, o_ref,
                        kbuf, sem, *, rows, heads, block_size, pages):
    """One grid step = (slot, block of query tokens, tile of keys)."""
    n, iq, c = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    T = pages * block_size
    lim = jnp.minimum(cl_ref[n], q0_ref[n] + (iq + 1) * rows)

    def copies():
        return [((c * pages + j) * block_size < lim, pltpu.make_async_copy(
            k_hbm.at[bt_ref[n, c * pages + j]], kbuf.at[j], sem.at[0]))
            for j in range(pages)]

    @pl.when(c * T < lim)
    def _():
        for need, cp in copies():
            @pl.when(need)
            def _():
                cp.start()
        for need, cp in copies():
            @pl.when(need)
            def _():
                cp.wait()
        kk = kbuf[...].reshape(T, -1)
        acc = _weighted_relu_sum(q_ref, w_ref, kk, rows, heads)
        q_pos = q0_ref[n] + iq * rows + jax.lax.broadcasted_iota(
            jnp.int32, acc.shape, 0)
        o_ref[0, 0] = _masked(acc, c, T, q_pos, cl_ref[n])

    @pl.when(c * T >= lim)
    def _():
        o_ref[0, 0] = jnp.full(o_ref.shape[2:], -jnp.inf, jnp.float32)


def _index_decode_kernel(bt_ref, q0_ref, cl_ref, q_ref, w_ref, k_hbm, o_ref,
                         kbuf, sems, *, heads, block_size, pages):
    """One grid step = one decode row; a loop with a dynamic trip count walks
    its tiles of index pages through a two-slot copy pipeline."""
    n = pl.program_id(0)
    T = pages * block_size
    lim = jnp.minimum(cl_ref[n], q0_ref[n] + 1)
    nt = jax.lax.div(lim + (T - 1), T)

    def copies(c, slot):
        return [((c * pages + j) * block_size < lim, pltpu.make_async_copy(
            k_hbm.at[bt_ref[n, c * pages + j]], kbuf.at[slot, j],
            sems.at[slot])) for j in range(pages)]

    def start(c, slot):
        for need, cp in copies(c, slot):
            @pl.when(need)
            def _():
                cp.start()

    o_ref[...] = jnp.full(o_ref.shape, -jnp.inf, jnp.float32)

    @pl.when(nt > 0)
    def _():
        start(0, 0)

    def tile(c, carry):
        slot = jax.lax.rem(c, 2)

        @pl.when(c + 1 < nt)
        def _():
            start(c + 1, 1 - slot)

        for need, cp in copies(c, slot):
            @pl.when(need)
            def _():
                cp.wait()
        kk = kbuf[slot].reshape(T, -1)
        acc = _weighted_relu_sum(q_ref, w_ref, kk, 1, heads)
        o_ref[0, c] = _masked(acc, c, T, q0_ref[n], cl_ref[n])
        return carry

    jax.lax.fori_loop(0, nt, tile, 0)


def index_scores(q: jax.Array, w: jax.Array, pool: jax.Array,
                 block_tables: jax.Array, q_pos0: jax.Array,
                 ctx_lens: jax.Array) -> jax.Array:
    """Index scores of ``N`` slots of ``R`` query tokens over index pages.

    q:            [N, R, Hi, D] index queries (rotated), the pool's dtype
    w:            [N, R, Hi] float32 head weights (signed, scaled)
    pool:         [NB, bs, D] index-key pages (all layers' pages as one list,
                  block tables offset by ``l * pages a layer``)
    block_tables: [N, MB] int32
    q_pos0:       [N] int32 position of each slot's first query token
    ctx_lens:     [N] int32 tokens of the pages the slot may read

    Returns ``[N, C, R, T]`` float32: ``[n, c, r, t]`` is the score of query
    ``r`` against position ``c * T + t``, ``-inf`` where that position is not
    below ``ctx`` or lies past the query's own (``q_pos0 + r``). ``T`` is
    :data:`DECODE_TILE` keys for ``R == 1`` and :data:`CHUNK_TILE` else (in
    whole pages, whole lane tiles)."""
    N, R, Hi, D = q.shape
    NB, bs, Dp = pool.shape
    assert Dp == D and w.shape == (N, R, Hi)
    decode = R == 1
    P = tile_pages(bs, block_tables.shape[1],
                   DECODE_TILE if decode else CHUNK_TILE)
    bt = _pad_tables(block_tables, P)
    C, T = bt.shape[1] // P, P * bs
    prefetch = [bt, q_pos0.astype(jnp.int32), ctx_lens.astype(jnp.int32)]
    w = w.astype(jnp.float32)
    if decode:
        kernel = functools.partial(_index_decode_kernel, heads=Hi,
                                   block_size=bs, pages=P)
        grid = (N,)
        in_specs = [pl.BlockSpec((1, Hi, D), lambda n, *_: (n, 0, 0)),
                    pl.BlockSpec((1, Hi, 1), lambda n, *_: (n, 0, 0)),
                    pl.BlockSpec(memory_space=pl.ANY)]
        operands = [q[:, 0], w[:, 0, :, None], pool]
        out_spec = pl.BlockSpec((1, C, 1, T), lambda n, *_: (n, 0, 0, 0))
        scratch = [pltpu.VMEM((2, P, bs, D), pool.dtype),
                   pltpu.SemaphoreType.DMA((2,))]
        semantics = ("arbitrary",)
    else:
        # query tokens a block: all of a small slot, else 256 (a head's
        # product is then [256, D] x [D, T])
        Rb = R if R <= 256 else 256
        assert R % Rb == 0 and (Rb % 8 == 0 or Rb == R), (R, Rb)
        kernel = functools.partial(_index_chunk_kernel, rows=Rb, heads=Hi,
                                   block_size=bs, pages=P)
        grid = (N, R // Rb, C)
        in_specs = [
            pl.BlockSpec((1, Hi, Rb, D), lambda n, iq, c, *_: (n, 0, iq, 0)),
            pl.BlockSpec((1, Rb, Hi), lambda n, iq, c, *_: (n, iq, 0)),
            pl.BlockSpec(memory_space=pl.ANY)]
        operands = [jnp.swapaxes(q, 1, 2), w, pool]
        out_spec = pl.BlockSpec((1, 1, Rb, T),
                                lambda n, iq, c, *_: (n, c, iq, 0))
        scratch = [pltpu.VMEM((P, bs, D), pool.dtype),
                   pltpu.SemaphoreType.DMA((1,))]
        semantics = ("arbitrary",) * 3
    call = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=grid, in_specs=in_specs,
            out_specs=out_spec, scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((N, C, R, T), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics),
        interpret=_backend.interpret(),
    )
    with jax.named_scope("dsa_index_decode" if decode else "dsa_index_chunk"):
        return call(*prefetch, *operands)


def index_scores_reference(q, w, pool, block_tables, q_pos0, ctx_lens):
    """Plain ``jnp`` statement of :func:`index_scores` (same tiling)."""
    N, R, Hi, D = q.shape
    bs = pool.shape[1]
    P = tile_pages(bs, block_tables.shape[1],
                   DECODE_TILE if R == 1 else CHUNK_TILE)
    bt = _pad_tables(block_tables, P)
    keys = pool[bt].reshape(N, -1, D).astype(jnp.float32)
    s = jnp.einsum("nrhd,nsd->nrhs", q.astype(pool.dtype).astype(jnp.float32),
                   keys, precision="highest")
    acc = jnp.sum(jnp.maximum(s, 0.0) * w.astype(jnp.float32)[..., None],
                  axis=2)
    acc = jnp.where(acc == 0.0, 0.0, acc)
    pos = jnp.arange(keys.shape[1])[None, None, :]
    q_pos = q_pos0[:, None, None] + jnp.arange(R)[None, :, None]
    acc = jnp.where((pos < ctx_lens[:, None, None]) & (pos <= q_pos), acc,
                    -jnp.inf)
    return acc.reshape(N, R, -1, P * bs).transpose(0, 2, 1, 3)


def untile(scores: jax.Array) -> jax.Array:
    """``[N, C, R, T]`` tiled scores as ``[N, R, C * T]``."""
    N, C, R, T = scores.shape
    return scores.transpose(0, 2, 1, 3).reshape(N, R, C * T)


# --------------------------------------------------------------------------- #
# 2. the exact k-th largest score of a row
# --------------------------------------------------------------------------- #


def _sortable(x):
    """float32 -> int32 whose signed order is the floats' order."""
    b = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(b < 0, b ^ jnp.int32(0x7FFFFFFF), b)


def _unsortable(k):
    return jax.lax.bitcast_convert_type(
        jnp.where(k < 0, k ^ jnp.int32(0x7FFFFFFF), k), jnp.float32)


#: float32 registers (8 sublanes x 128 lanes) a tile of a row block may fill
#: (a sweep keeps a candidate and a running count a row beside the tile it
#: reads, in a register file of 64), and those an iteration of a sweep's
#: loop reads where a tile is smaller
_SELECT_TILE_REGISTERS = 64
_SELECT_BODY_REGISTERS = 32
#: bytes of scores a grid step may hold (Pallas keeps two such blocks)
_SELECT_BLOCK_BYTES = 18 << 20
#: the most a row may keep for the first walk to bound its threshold from
#: below (GLM-5 keeps 2,048): that many disjoint groups of a row's scores
#: keep their maxima, ``[groups // T, rows, T]`` float32 of VMEM
_SELECT_GROUPS = 2048
_KEY_NEG_INF = -2139095041           # _sortable(-inf)


def _select_block(R: int, C: int, T: int) -> Tuple[int, int]:
    """``(query rows a grid step, tiles a loop iteration)`` of
    :func:`select` for ``R`` rows of ``C`` tiles of ``T`` scores: the most
    rows whose tile fills no more than :data:`_SELECT_TILE_REGISTERS`
    registers and whose block :data:`_SELECT_BLOCK_BYTES` — what a walk
    costs beside its loads (the counts' sum along the lanes, the next
    candidate, the scalar that ends the bisection: some 0.4 us on a v5e) is
    then spread over them — and as many tiles an iteration as
    :data:`_SELECT_BODY_REGISTERS` hold."""
    fits = [r for r in range(8, R + 1, 8) if R % r == 0
            and r // 8 * (T // LANES) <= _SELECT_TILE_REGISTERS
            and C * r * T * 4 <= _SELECT_BLOCK_BYTES]
    rows = max(fits) if fits else (8 if R % 8 == 0 else R)
    registers = -(-rows // 8) * (T // LANES)
    return rows, max(1, min(C, _SELECT_BODY_REGISTERS // registers))


def _select_kernel(ext_ref, s_ref, k_ref, thr_ref, pcut_ref, sweeps_ref,
                   gmax_sc, *, tile, unroll):
    """One grid step = (slot, block of query rows): the rows' tiles of
    scores are in VMEM and every sweep walks the ``ext`` tiles that can hold
    a valid score, ``unroll`` tiles a loop iteration, a PIECE of a tile at a
    time: 16 registers of it (128 lanes of 128 rows, 1,024 of 16), beside as
    many of the candidate and of the running result. The walk compares the
    float32 scores themselves (-0.0 is +0.0 to it, as to a sort); only the
    candidates are bisected as sortable integers, a column a row."""
    n = pl.program_id(0)
    ext = ext_ref[n]
    C, rows = s_ref.shape[1], s_ref.shape[2]
    G = gmax_sc.shape[0]
    W = LANES * max(1, min(tile // LANES, 16 // max(1, rows // 8)))
    while tile % W:
        W -= LANES
    want = k_ref[0]                                          # [rows, 1]
    wide = lambda col: jnp.broadcast_to(col, (rows, W))
    # [rows, W] -> [rows, 1] by ``op``, the lanes' registers first
    down = lambda op, along, x: along(functools.reduce(op, [
        x[:, j:j + LANES] for j in range(0, W, LANES)]), axis=1,
        keepdims=True)

    def walk(step, carry, U=unroll):
        """``carry = step(c, u, carry)`` over the tiles: ``U`` an iteration
        (``u`` the tile's place in it), then what ``C % U`` leaves. The last
        iteration may run past ``ext``: those tiles hold -inf alone, which
        is under every candidate and counts for none."""
        def group(i, carry):
            for u in range(U):
                carry = step(i * U + u, u, carry)
            return carry

        groups = jnp.minimum(jax.lax.div(ext + (U - 1), U), C // U)
        carry = jax.lax.fori_loop(0, groups, group, carry)
        if C % U == 0:
            return carry
        return jax.lax.fori_loop(groups * U, ext,
                                 lambda c, carry: step(c, 0, carry), carry)

    def pieces(op, init):
        """``acc = op(acc, piece [rows, W], its first position)`` over every
        piece of every tile: ``[rows, W]``."""
        def step(c, u, acc):
            for j in range(0, tile, W):
                acc = op(acc, s_ref[0, c, :, j:j + W], (c, j))
            return acc

        return walk(step, jnp.full((rows, W), init))

    def count(pred):
        """Per row the scores ``pred(piece, (tile, lane of its first))``
        holds for: ``[rows, 1]``."""
        return down(jnp.add, jnp.sum, pieces(
            lambda acc, s, at: acc + pred(s, at).astype(jnp.int32),
            jnp.int32(0)))

    # 1. bounds, from one walk: the maxima of G * tile disjoint groups of a
    # row's scores (a tile's index modulo G, a score's place in its tile).
    # The largest is the row's largest; each group that is not empty holds
    # a score at or over the smallest, so the k-th largest is at or over it
    # where k is no more than the groups (-inf where a group is empty: no
    # narrowing, still exact)
    gmax_sc[...] = jnp.full(gmax_sc.shape, -jnp.inf, jnp.float32)

    def maxima(c, u, carry):
        gmax_sc[u] = jnp.maximum(gmax_sc[u], s_ref[0, c])
        return carry

    walk(maxima, 0, G)
    fold = lambda op, along: down(op, along, functools.reduce(op, [
        gmax_sc[g, :, j:j + W] for g in range(G) for j in range(0, tile, W)]))
    top = fold(jnp.maximum, jnp.max)
    low = jnp.where(want <= G * tile, fold(jnp.minimum, jnp.min), -jnp.inf)
    lo0 = jnp.maximum(_sortable(low), jnp.int32(_KEY_NEG_INF))
    hi0 = jnp.maximum(_sortable(top), lo0)

    # 2. the largest v of [lo, hi] with count(score >= v) >= k, halving the
    # interval a sweep (the differences wrap: read as unsigned). A row whose
    # candidate counts exactly k is done — no score lies between the
    # candidate and the k-th largest — and the block is when every row is
    def halve(carry):
        lo, hi, seen, _, sweeps = carry
        span = hi - lo
        mid = lo + jax.lax.shift_right_logical(span, 1) + (span & 1)
        cand = wide(_unsortable(mid))
        cnt = count(lambda s, _: s >= cand)
        ok = cnt >= want
        lo = jnp.where(ok, mid, lo)
        hi = jnp.where(cnt == want, mid, jnp.where(ok, hi, mid - 1))
        return (lo, hi, jnp.where(ok, cnt, seen),
                jnp.max(jnp.where(hi != lo, 1, 0)), sweeps + 1)

    lo, _, seen, _, sweeps = jax.lax.while_loop(
        lambda carry: carry[3] > 0, halve,
        (lo0, hi0, jnp.full((rows, 1), -1, jnp.int32),
         jnp.max(jnp.where(hi0 != lo0, 1, 0)), jnp.int32(1)))

    # 3. the threshold is the smallest score at or over what was accepted
    # (+inf for a row that holds none: it keeps nothing)
    floor, none = wide(_unsortable(lo)), jnp.full((rows, W), jnp.inf)
    thr = down(jnp.minimum, jnp.min, pieces(
        lambda m, s, _: jnp.minimum(m, jax.lax.select(s >= floor, s, none)),
        jnp.float32(jnp.inf)))
    thr = jnp.where(thr == 0.0, 0.0, thr)
    edge = wide(thr)

    # count(score >= thr) is the accepted candidate's own count; a row that
    # accepted none (its lower bound was its threshold) is counted now
    blind = jnp.max(jnp.where(seen < 0, 1, 0))
    seen = jax.lax.fori_loop(
        0, blind, lambda _, seen: jnp.where(
            seen < 0, count(lambda s, _: s >= edge), seen), seen)

    # 4. only where a row has more scores EQUAL to its threshold than it may
    # keep (1 + 31 sweeps more; else none): the largest p with
    # count(score == thr and pos < p) < quota — position p holds the last
    # equal score kept
    crowded = seen > want
    any_crowded = jnp.max(jnp.where(crowded, 1, 0))
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, W), 1)

    def cut(_, p):
        quota = want - count(lambda s, _: s > edge)

        def pbit(i, p):
            cand = p + jnp.left_shift(jnp.int32(1), 30 - i)
            at = wide(cand)
            return jnp.where(count(lambda s, cj: jnp.logical_and(
                s == edge, cj[0] * tile + cj[1] + col < at)) < quota, cand, p)

        return jax.lax.fori_loop(0, 31, pbit, p)

    p = jax.lax.fori_loop(0, any_crowded, cut,
                          jnp.zeros((rows, 1), jnp.int32))
    thr_ref[0] = thr
    pcut_ref[0] = jnp.where(crowded, p, jnp.int32(2**31 - 1))
    # (a slot that holds nothing walks nothing: it counts for none)
    sweeps_ref[n, pl.program_id(1)] = jnp.where(
        ext > 0, sweeps + 1 + blind + 32 * any_crowded, 0)


def select_counted(scores: jax.Array, k: jax.Array, ctx_lens: jax.Array
                   ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """:func:`select` and, third, the walks over its tiles each block of
    query rows took: ``[N, R // rows a block]`` int32 (the bounds, a sweep a
    halving, the threshold's; 32 more where a row's ties were cut; 0 for a
    slot whose ``ctx_lens`` is 0)."""
    N, C, R, T = scores.shape
    assert T % LANES == 0, T
    Rs, U = _select_block(R, C, T)
    assert R % Rs == 0 and (Rs % 8 == 0 or Rs == R), (R, Rs)
    G = max(1, min(C, _SELECT_GROUPS // T))
    ext = jnp.clip((ctx_lens.astype(jnp.int32) + T - 1) // T, 0, C)
    idx = lambda n, r, *_: (n, r, 0)
    call = pl.pallas_call(
        functools.partial(_select_kernel, tile=T, unroll=U),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(N, R // Rs),
            in_specs=[pl.BlockSpec((1, C, Rs, T),
                                   lambda n, r, *_: (n, 0, r, 0)),
                      pl.BlockSpec((1, Rs, 1), idx)],
            out_specs=[pl.BlockSpec((1, Rs, 1), idx),
                       pl.BlockSpec((1, Rs, 1), idx),
                       pl.BlockSpec(memory_space=pltpu.SMEM)],
            scratch_shapes=[pltpu.VMEM((G, Rs, T), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((N, R, 1), jnp.float32),
                   jax.ShapeDtypeStruct((N, R, 1), jnp.int32),
                   jax.ShapeDtypeStruct((N, R // Rs), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=(2 * C + G) * Rs * T * 4 + (8 << 20)),
        interpret=_backend.interpret(),
    )
    with jax.named_scope("dsa_select"):
        thr, pcut, sweeps = call(ext, scores, k.astype(jnp.int32)[..., None])
    return thr[..., 0], pcut[..., 0], sweeps


def select(scores: jax.Array, k: jax.Array, ctx_lens: jax.Array
           ) -> Tuple[jax.Array, jax.Array]:
    """Per query row the exact ``k``-th largest score and the tie cut.

    scores:   [N, C, R, T] float32 (:func:`index_scores`)
    k:        [N, R] int32, 1 <= k <= the row's count of finite scores
    ctx_lens: [N] int32: no finite score lies at or past it

    Returns ``(thr [N, R] float32, pcut [N, R] int32)``: exactly ``k``
    positions of the row satisfy ``score > thr or (score == thr and position
    <= pcut)`` — of equal scores the lower positions, ``jax.lax.top_k``'s
    rule."""
    return select_counted(scores, k, ctx_lens)[:2]


def select_reference(scores, k, ctx_lens=None):
    """Plain ``jnp`` statement of :func:`select`: a sort."""
    del ctx_lens
    flat = untile(scores)                                   # [N, R, S]
    ordered = -jnp.sort(-flat, axis=-1)
    thr = jnp.take_along_axis(ordered, (k - 1)[..., None], axis=-1)
    quota = k[..., None] - jnp.sum(flat > thr, axis=-1, keepdims=True)
    equal = flat == thr
    seen = jnp.cumsum(equal, axis=-1)
    # the position of the quota-th equal score
    at = jnp.argmax(equal & (seen == quota), axis=-1)
    crowded = jnp.sum(equal, axis=-1) > quota[..., 0]
    return thr[..., 0], jnp.where(crowded, at, 2**31 - 1).astype(jnp.int32)


def keep_mask(scores, thr, pcut):
    """``[N, R, S]`` bool from tiled scores: what :func:`select` keeps."""
    flat = untile(scores)
    pos = jnp.arange(flat.shape[-1], dtype=jnp.int32)
    return (flat > thr[..., None]) | ((flat == thr[..., None])
                                      & (pos <= pcut[..., None]))


def chosen_positions(keep: jax.Array, topk: int) -> jax.Array:
    """``keep`` ``[N, S]`` bool with at most ``topk`` set a row -> the set
    positions in ascending order ``[N, topk]`` int32 (``S`` where a row has
    fewer). No sort, no scatter and no gather: slot ``j`` finds its block of
    128 positions by the blocks' running counts, reads the block's own
    running count through a one-hot product (counts up to 128 are exact in
    bfloat16), and its place in the block is how many of them do not pass
    its rank. (Two gathers of ``[N, topk, 128]`` rows took 2.7 ms of a
    16-row decode step of five layers on a v5e.)"""
    N, S = keep.shape
    pad = -S % LANES
    blocks = jnp.pad(keep, ((0, 0), (0, pad))).reshape(N, -1, LANES)
    B = blocks.shape[1]
    inside = jnp.cumsum(blocks.astype(jnp.int32), axis=-1)    # [N, B, 128]
    count = inside[..., -1]                                   # [N, B]
    ends = jnp.cumsum(count, axis=-1)
    slot = jnp.arange(topk, dtype=jnp.int32)
    passed = ends[:, None, :] <= slot[None, :, None]          # [N, topk, B]
    blk = jnp.sum(passed, axis=-1, dtype=jnp.int32)
    rank = slot[None, :] - jnp.sum(
        jnp.where(passed, count[:, None, :], 0), axis=-1)     # in its block
    mine = (jnp.arange(B, dtype=jnp.int32) == blk[..., None])
    rows = jnp.einsum("nkb,nbl->nkl", mine.astype(jnp.bfloat16),
                      inside.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)
    off = jnp.sum(rows <= rank[..., None].astype(jnp.float32), axis=-1,
                  dtype=jnp.int32)
    return jnp.where(blk < B, blk * LANES + off, S).astype(jnp.int32)


# --------------------------------------------------------------------------- #
# 3. attention over the selection
# --------------------------------------------------------------------------- #


def _attend_decode_kernel(ns_ref, on_ref, q_ref, g_ref, *refs, scale, v_dim,
                          side):
    if side:
        side_ref, o_ref, acc_sc, m_sc, l_sc = refs
    else:
        o_ref, acc_sc, m_sc, l_sc = refs
    n = pl.program_id(0)
    m_sc[:] = jnp.full_like(m_sc, NEG_INF)
    l_sc[:] = jnp.zeros_like(l_sc)
    acc_sc[:] = jnp.zeros_like(acc_sc)
    q = q_ref[0]                                               # [H, W]
    g = g_ref[0]                                               # [K, W]
    dot = lambda k: jax.lax.dot_general(
        q.astype(k.dtype), k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    sc = dot(g)
    col = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
    # (a slot past the row's count holds a row of the pool: finite, weighed 0)
    _flash_update(sc, col < ns_ref[n], g[:, :v_dim], m_sc, l_sc, acc_sc)
    if side:
        sk = side_ref[0]                                       # [8, W]
        sc_s = dot(sk)
        cc = jax.lax.broadcasted_iota(jnp.int32, sc_s.shape, 1)
        row1 = jax.lax.broadcasted_iota(jnp.int32, (sk.shape[0], 1), 0)
        seen = on_ref[n] > 0
        sv = jnp.where(jnp.logical_and(row1 == 0, seen), sk[:, :v_dim],
                       0.0).astype(sk.dtype)
        _flash_update(sc_s, jnp.logical_and(cc == 0, seen), sv, m_sc, l_sc,
                      acc_sc)
    l = l_sc[:, 0:1]
    o_ref[0] = (acc_sc[:] / jnp.where(l > 0.0, l, 1.0)).astype(o_ref.dtype)


def attend_decode(q: jax.Array, rows: jax.Array, n_rows: jax.Array, *,
                  v_dim: int, softmax_scale: float,
                  side: Optional[jax.Array] = None,
                  side_on: Optional[jax.Array] = None) -> jax.Array:
    """Absorbed attention of ``N`` decode rows over their gathered rows.

    q:      [N, H, W] absorbed queries
    rows:   [N, K, W] the chosen latent rows, the first ``n_rows[n]`` live
    side:   [N, 8, W] row 0 the step's own latent row (not yet in the pool),
            attended where ``side_on[n]`` is not 0

    Returns ``[N, H, v_dim]``."""
    N, H, W = q.shape
    K = rows.shape[1]
    assert rows.shape == (N, K, W) and v_dim <= W
    has_side = side is not None
    idx = lambda n, *_: (n, 0, 0)
    in_specs = [pl.BlockSpec((1, H, W), idx), pl.BlockSpec((1, K, W), idx)]
    operands = [q, rows]
    if has_side:
        assert side.shape == (N, side.shape[1], W) and side.shape[1] % 8 == 0
        in_specs.append(pl.BlockSpec((1, side.shape[1], W), idx))
        operands.append(side)
    on = jnp.zeros((N,), jnp.int32) if side_on is None else side_on
    call = pl.pallas_call(
        functools.partial(_attend_decode_kernel, scale=float(softmax_scale),
                          v_dim=v_dim, side=has_side),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(N,), in_specs=in_specs,
            out_specs=pl.BlockSpec((1, H, v_dim), idx),
            scratch_shapes=[pltpu.VMEM((H, v_dim), jnp.float32),
                            pltpu.VMEM((H, LANES), jnp.float32),
                            pltpu.VMEM((H, LANES), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((N, H, v_dim), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_backend.interpret(),
    )
    with jax.named_scope("dsa_attend_decode"):
        return call(n_rows.astype(jnp.int32), on.astype(jnp.int32), *operands)


def attend_decode_reference(q, rows, n_rows, *, v_dim, softmax_scale,
                            side=None, side_on=None):
    """Plain ``jnp`` statement of :func:`attend_decode` (float32)."""
    N, H, W = q.shape
    g = rows.astype(jnp.float32)
    mask = jnp.arange(g.shape[1])[None, :] < n_rows[:, None]
    if side is not None:
        g = jnp.concatenate([g, side[:, :1].astype(jnp.float32)], axis=1)
        mask = jnp.concatenate([mask, (side_on > 0)[:, None]], axis=1)
    s = jnp.einsum("nhw,nkw->nhk", q.astype(jnp.float32), g) * softmax_scale
    mask = jnp.broadcast_to(mask[:, None, :], s.shape)
    s = jnp.where(mask, s, NEG_INF)
    p = jnp.where(mask, jnp.exp(s - s.max(axis=-1, keepdims=True)), 0.0)
    l = p.sum(axis=-1, keepdims=True)
    g = jnp.where(mask[:, 0, :, None], g, 0.0)
    return jnp.einsum("nhk,nkv->nhv", p / jnp.where(l > 0, l, 1.0),
                      g[..., :v_dim]).astype(q.dtype)


def _attend_chunk_kernel(bt_ref, q0_ref, cl_ref, q_ref, thr_ref, pcut_ref,
                         kv_hbm, sc_hbm, o_ref, kv_buf, sc_buf, sems, cnt,
                         keep_sc, acc_sc, m_sc, l_sc, *, scale, heads, v_dim,
                         block_size, pages, n_slots, n_qblocks, rows_block):
    """``mla_attention._mla_kernel``'s walk — one grid step = (slot, block of
    query rows), a loop over the chunks of pages the rows can see, two slots
    of copies — with the tile of index scores of the block's query TOKENS
    copied beside each chunk, and ``keep`` of it as the mask of every head's
    rows."""
    P, bs, RB = pages, block_size, rows_block
    T, TB = P * bs, rows_block // heads
    n, iq = pl.program_id(0), pl.program_id(1)

    def limit_of(n_, iq_):
        last_pos = q0_ref[n_] + ((iq_ + 1) * RB - 1) // heads
        return jnp.maximum(jnp.minimum(cl_ref[n_], last_pos + 1), 1)

    def n_chunks_of(n_, iq_):
        return jax.lax.div(limit_of(n_, iq_) + (T - 1), T)

    def copies(n_, iq_, c_, slot):
        lim = limit_of(n_, iq_)
        out = [(True, pltpu.make_async_copy(
            sc_hbm.at[n_, c_, pl.ds(iq_ * TB, TB)], sc_buf.at[slot],
            sems.at[slot]))]
        for j in range(P):
            page = bt_ref[n_, c_ * P + j]
            out.append(((c_ * P + j) * bs < lim, pltpu.make_async_copy(
                kv_hbm.at[page], kv_buf.at[slot, j], sems.at[slot])))
        return out

    def start(n_, iq_, c_, slot):
        for need, cp in copies(n_, iq_, c_, slot):
            if need is True:
                cp.start()
                continue

            @pl.when(need)
            def _():
                cp.start()

    def wait(n_, iq_, c_, slot):
        for j, (need, cp) in enumerate(copies(n_, iq_, c_, slot)):
            if need is True:
                cp.wait()
                continue

            @pl.when(need)
            def _():
                cp.wait()

            @pl.when(jnp.logical_not(need))
            def _():
                kv_buf[slot, j - 1] = jnp.zeros_like(kv_buf[slot, j - 1])

    step = n * n_qblocks + iq

    @pl.when(step == 0)
    def _():
        cnt[0] = 0
        start(0, 0, 0, 0)

    m_sc[:] = jnp.full_like(m_sc, NEG_INF)
    l_sc[:] = jnp.zeros_like(l_sc)
    acc_sc[:] = jnp.zeros_like(acc_sc)

    base = cnt[0]
    nc = n_chunks_of(n, iq)
    nxt_n = jnp.where(iq + 1 < n_qblocks, n, jnp.minimum(n + 1, n_slots - 1))
    nxt_iq = jnp.where(iq + 1 < n_qblocks, iq + 1, 0)
    has_next = step + 1 < n_slots * n_qblocks
    thr, pcut = thr_ref[0], pcut_ref[0]                        # [TB, 1]
    col = jax.lax.broadcasted_iota(jnp.int32, (TB, T), 1)

    def chunk(c, carry):
        slot = jax.lax.rem(base + c, 2)

        @pl.when(c + 1 < nc)
        def _():
            start(n, iq, c + 1, 1 - slot)

        @pl.when(jnp.logical_and(c + 1 == nc, has_next))
        def _():
            start(nxt_n, nxt_iq, 0, 1 - slot)

        wait(n, iq, c, slot)
        # the scores are -inf wherever a query may not look, so ``keep`` is
        # the causal and the context mask too
        si = sc_buf[slot]                                      # [TB, T]
        keep = jnp.logical_or(si > thr, jnp.logical_and(
            si == thr, c * T + col <= pcut)).astype(jnp.float32)
        for i in range(TB):
            keep_sc[i * heads:(i + 1) * heads] = jnp.broadcast_to(
                keep[i:i + 1], (heads, T))
        q = q_ref[0]                                           # [RB, W]
        kk = kv_buf[slot].reshape(T, -1)                       # [T, W]
        vv = kv_buf[slot, :, :, :v_dim].reshape(T, v_dim)
        sc = jax.lax.dot_general(q.astype(kk.dtype), kk,
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
        _flash_update(sc, keep_sc[:] > 0.0, vv, m_sc, l_sc, acc_sc)
        return carry

    jax.lax.fori_loop(0, nc, chunk, 0)
    cnt[0] = base + nc
    l = l_sc[:, 0:1]
    o_ref[0] = (acc_sc[:] / jnp.where(l > 0.0, l, 1.0)).astype(o_ref.dtype)


def attend_chunk(q: jax.Array, pool: jax.Array, block_tables: jax.Array,
                 q_pos0: jax.Array, ctx_lens: jax.Array, scores: jax.Array,
                 thr: jax.Array, pcut: jax.Array, *, heads: int, v_dim: int,
                 softmax_scale: float) -> jax.Array:
    """Absorbed attention of ``N`` slots of prompt rows over latent pages,
    each query token over the positions its selection keeps.

    q:       [N, Cs * heads, W] absorbed queries, token major
    pool:    [NB, bs, W] latent pages
    scores:  [N, C, Cs, T] the slots' index scores (:func:`index_scores`)
    thr, pcut: [N, Cs] (:func:`select`)

    Returns ``[N, Cs * heads, v_dim]``; a token that keeps nothing (an empty
    slot's) gets zeros."""
    N, R, W = q.shape
    NB, bs, Wp = pool.shape
    _, C, Cs, T = scores.shape
    assert Wp == W and R == Cs * heads and T % bs == 0
    RB = _pick_rows_block(R, heads)
    TB, nq, P = RB // heads, R // RB, T // bs
    assert TB % 8 == 0 or TB == Cs, \
        f"a block of {RB} query rows is {TB} tokens: not whole sublane tiles"
    bt = _pad_tables(block_tables, P)
    assert bt.shape[1] == C * P, (bt.shape, C, P)
    kernel = functools.partial(
        _attend_chunk_kernel, scale=float(softmax_scale), heads=heads,
        v_dim=v_dim, block_size=bs, pages=P, n_slots=N, n_qblocks=nq,
        rows_block=RB)
    tok = lambda n, iq, *_: (n, iq, 0)
    call = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(N, nq),
            in_specs=[pl.BlockSpec((1, RB, W), tok),
                      pl.BlockSpec((1, TB, 1), tok),
                      pl.BlockSpec((1, TB, 1), tok),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, RB, v_dim), tok),
            scratch_shapes=[
                pltpu.VMEM((2, P, bs, W), pool.dtype),
                pltpu.VMEM((2, TB, T), jnp.float32),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((RB, T), jnp.float32),
                pltpu.VMEM((RB, v_dim), jnp.float32),
                pltpu.VMEM((RB, LANES), jnp.float32),
                pltpu.VMEM((RB, LANES), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((N, R, v_dim), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_backend.interpret(),
    )
    with jax.named_scope("dsa_attend_chunk"):
        return call(bt, q_pos0.astype(jnp.int32), ctx_lens.astype(jnp.int32),
                    q, thr.astype(jnp.float32)[..., None],
                    pcut.astype(jnp.int32)[..., None], pool, scores)


def attend_chunk_reference(q, pool, block_tables, q_pos0, ctx_lens, scores,
                           thr, pcut, *, heads, v_dim, softmax_scale):
    """Plain ``jnp`` statement of :func:`attend_chunk` (float32)."""
    del q_pos0, ctx_lens                    # the scores' -inf carries both
    N, R, W = q.shape
    T, bs = scores.shape[3], pool.shape[1]
    bt = _pad_tables(block_tables, T // bs)
    rows = pool[bt].reshape(N, -1, W).astype(jnp.float32)
    mask = jnp.repeat(keep_mask(scores, thr, pcut), heads, axis=1)
    s = jnp.einsum("nrw,ntw->nrt", q.astype(jnp.float32), rows) \
        * softmax_scale
    s = jnp.where(mask, s, NEG_INF)
    p = jnp.where(mask, jnp.exp(s - s.max(axis=-1, keepdims=True)), 0.0)
    l = p.sum(axis=-1, keepdims=True)
    return jnp.einsum("nrt,ntv->nrv", p / jnp.where(l > 0, l, 1.0),
                      rows[..., :v_dim]).astype(q.dtype)


# --------------------------------------------------------------------------- #
# 4. a pass that holds ONE sequence: attention over the selection, expanded
# --------------------------------------------------------------------------- #

#: query heads a grid step of :func:`attend_expanded` takes are the most
#: whose share of the pass's rows — queries, outputs and the running softmax
#: of every slot — stays under this many bytes of VMEM (GLM-5: 8 MiB a head,
#: four heads; the score tiles, the weights and the expanded tile are some
#: 20 MiB more whatever the group)
EXPANDED_GROUP_BYTES = 40 << 20
_EXPANDED_VMEM_LIMIT = 100 << 20


def _expanded_group(heads: int, rows: int, k_dim: int, v_dim: int,
                    itemsize: int) -> int:
    """Heads a grid step (a power-of-two share of ``heads``)."""
    a_head = rows * (v_dim * 4 + 2 * LANES * 4
                     + 2 * (k_dim + v_dim) * itemsize)
    g = heads
    while g % 2 == 0 and g * a_head > EXPANDED_GROUP_BYTES:
        g //= 2
    return g


def _attend_expanded_kernel(bt_ref, lim_ref, q_ref, w_ref, thr_ref, pcut_ref,
                            kv_hbm, sc_hbm, o_ref, kv_buf, sc_buf, sems,
                            k_sc, v_sc, bias_sc, acc_sc, m_sc, l_sc, *,
                            scale, group, k_dim, block_size, pages, n_slots):
    """One grid step = a group of heads over the WHOLE pass: a loop over the
    tiles of keys any slot can see, two slots of copies (the tile's latent
    pages and each seeing slot's tile of index scores). A tile is expanded
    once a head — ``[T, W] x [W, k + v]``, the rotary key through the
    identity rows of the weights, the softmax scale into the keys — and
    every slot that sees it attends it, one mask a slot for all heads. The
    group's heads are one traced body laid out side by side (``unroll``): a
    head's softmax then runs under the next head's products, 12% of the
    kernel at 9k and at 32k of context (my chip run, PR 58; four heads
    compile in under a second more than the loop)."""
    P, bs, G, N = pages, block_size, group, n_slots
    T = P * bs
    Cs, v_dim = acc_sc.shape[1:]
    top = lim_ref[0]
    for i in range(1, N):
        top = jnp.maximum(top, lim_ref[i])
    nt = jax.lax.div(top + (T - 1), T)

    def copies(c, slot):
        out = [(c * T < lim_ref[i], pltpu.make_async_copy(
            sc_hbm.at[i, c], sc_buf.at[slot, i], sems.at[slot]))
            for i in range(N)]
        return out + [((c * P + j) * bs < top, pltpu.make_async_copy(
            kv_hbm.at[bt_ref[c * P + j]], kv_buf.at[slot, j], sems.at[slot]))
            for j in range(P)]

    def start(c, slot):
        for need, cp in copies(c, slot):
            @pl.when(need)
            def _():
                cp.start()

    def wait(c, slot):
        for j, (need, cp) in enumerate(copies(c, slot)):
            @pl.when(need)
            def _():
                cp.wait()

            if j >= N:          # a page past every slot's keys: 0 x 0, not
                @pl.when(jnp.logical_not(need))      # 0 x what VMEM held
                def _():
                    kv_buf[slot, j - N] = jnp.zeros_like(kv_buf[slot, j - N])

    m_sc[:] = jnp.full_like(m_sc, NEG_INF)
    l_sc[:] = jnp.zeros_like(l_sc)
    acc_sc[:] = jnp.zeros_like(acc_sc)

    @pl.when(nt > 0)
    def _():
        start(0, 0)

    col = jax.lax.broadcasted_iota(jnp.int32, (Cs, T), 1)

    def tile(c, carry):
        slot = jax.lax.rem(c, 2)

        @pl.when(c + 1 < nt)
        def _():
            start(c + 1, 1 - slot)

        wait(c, slot)
        rows = kv_buf[slot].reshape(T, -1)                     # [T, W]

        def expand(g, carry):
            kv = jax.lax.dot_general(rows, w_ref[g].astype(rows.dtype),
                                     (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            k_sc[g] = (kv[:, :k_dim] * scale).astype(k_sc.dtype)
            v_sc[g] = kv[:, k_dim:].astype(v_sc.dtype)
            return carry

        jax.lax.fori_loop(0, G, expand, 0, unroll=True)

        def a_slot(i, carry):
            @pl.when(c * T < lim_ref[i])
            def _():
                # the scores are -inf wherever a query may not look, so the
                # selection's mask is the causal and the context mask too
                si, thr = sc_buf[slot, i], thr_ref[i]          # [Cs, T]
                keep = jnp.logical_or(si > thr, jnp.logical_and(
                    si == thr, c * T + col <= pcut_ref[i]))
                bias_sc[:] = jnp.where(keep, 0.0, NEG_INF)

                def a_head(g, carry):
                    r = i * G + g
                    sc = jax.lax.dot_general(
                        q_ref[i, g].astype(k_sc.dtype), k_sc[g],
                        (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) + bias_sc[:]
                    m_prev = m_sc[r, :, 0:1]
                    m_new = jnp.maximum(m_prev,
                                        jnp.max(sc, axis=1, keepdims=True))
                    # a row that has kept nothing yet: exp(-1e30 - 0), not
                    # exp(-1e30 + 1e30)
                    p = jnp.exp(sc - jnp.where(m_new > 0.5 * NEG_INF, m_new,
                                               0.0))
                    alpha = jnp.exp(m_prev - m_new)
                    l_sc[r, :, 0:1] = l_sc[r, :, 0:1] * alpha + jnp.sum(
                        p, axis=1, keepdims=True)
                    m_sc[r, :, 0:1] = m_new
                    acc_sc[r] = acc_sc[r] * alpha + jax.lax.dot_general(
                        p.astype(v_sc.dtype), v_sc[g],
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    return carry

                jax.lax.fori_loop(0, G, a_head, 0, unroll=True)

            return carry

        jax.lax.fori_loop(0, N, a_slot, 0)
        return carry

    jax.lax.fori_loop(0, nt, tile, 0)

    def finish(i, carry):
        for g in range(G):
            l = l_sc[i * G + g, :, 0:1]
            o_ref[i, :, g * v_dim:(g + 1) * v_dim] = (
                acc_sc[i * G + g] / jnp.where(l > 0.0, l, 1.0)
            ).astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, N, finish, 0)


def attend_expanded(q: jax.Array, w_kv: jax.Array, pool: jax.Array,
                    block_table: jax.Array, key_lims: jax.Array,
                    scores: jax.Array, thr: jax.Array, pcut: jax.Array, *,
                    k_dim: int, softmax_scale: float) -> jax.Array:
    """Expanded attention of ``N`` slots of prompt rows that are ONE
    sequence's, each query token over the positions its selection keeps: the
    sequence's latent pages go through ``w_kv`` once a head a pass, whatever
    the number of slots that read them.

    q:           [N, H, Cs, k_dim] queries, nope part then rotated part
    w_kv:        [H, W, k_dim + v] a head's map from a latent ROW to its key
                 and its value: ``W_UK`` then the identity on the row's
                 rotary key, beside ``W_UV``, zeros under the row's padding
    pool:        [NB, bs, W] latent pages
    block_table: [MB] int32, the sequence's
    key_lims:    [N] int32 keys a slot's rows may see (0: an empty slot)
    scores:      [N, C, Cs, T] the slots' index scores (:func:`index_scores`)
    thr, pcut:   [N, Cs] (:func:`select`)

    Returns ``[N, Cs, H * v]``, a token's heads side by side; a token that
    keeps nothing (an empty slot's) gets zeros."""
    N, H, Cs, Dk = q.shape
    NB, bs, W = pool.shape
    _, C, _, T = scores.shape
    v_dim = w_kv.shape[2] - k_dim
    assert Dk == k_dim and w_kv.shape[:2] == (H, W) and v_dim > 0
    assert scores.shape[:3] == (N, C, Cs) and T % bs == 0
    P = T // bs
    bt = _pad_tables(block_table[None], P)[0]
    assert bt.shape[0] == C * P, (bt.shape, C, P)
    G = _expanded_group(H, N * Cs, k_dim, v_dim, pool.dtype.itemsize)
    kernel = functools.partial(
        _attend_expanded_kernel, scale=float(softmax_scale), group=G,
        k_dim=k_dim, block_size=bs, pages=P, n_slots=N)
    whole = lambda g, *_: (0, 0, 0)
    call = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(H // G,),
            in_specs=[pl.BlockSpec((N, G, Cs, k_dim),
                                   lambda g, *_: (0, g, 0, 0)),
                      pl.BlockSpec((G, W, k_dim + v_dim),
                                   lambda g, *_: (g, 0, 0)),
                      pl.BlockSpec((N, Cs, 1), whole),
                      pl.BlockSpec((N, Cs, 1), whole),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((N, Cs, G * v_dim),
                                   lambda g, *_: (0, 0, g)),
            scratch_shapes=[
                pltpu.VMEM((2, P, bs, W), pool.dtype),
                pltpu.VMEM((2, N, Cs, T), jnp.float32),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((G, T, k_dim), pool.dtype),
                pltpu.VMEM((G, T, v_dim), pool.dtype),
                pltpu.VMEM((Cs, T), jnp.float32),
                pltpu.VMEM((N * G, Cs, v_dim), jnp.float32),
                pltpu.VMEM((N * G, Cs, LANES), jnp.float32),
                pltpu.VMEM((N * G, Cs, LANES), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((N, Cs, H * v_dim), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_EXPANDED_VMEM_LIMIT),
        interpret=_backend.interpret(),
    )
    with jax.named_scope("dsa_attend_expanded"):
        return call(bt, key_lims.astype(jnp.int32), q, w_kv,
                    thr.astype(jnp.float32)[..., None],
                    pcut.astype(jnp.int32)[..., None], pool, scores)


def attend_expanded_reference(q, w_kv, pool, block_table, key_lims, scores,
                              thr, pcut, *, k_dim, softmax_scale):
    """Plain ``jnp`` statement of :func:`attend_expanded` (float32)."""
    del key_lims                            # the scores' -inf carries them
    N, H, Cs, _ = q.shape
    T, bs = scores.shape[3], pool.shape[1]
    bt = _pad_tables(block_table[None], T // bs)[0]
    rows = pool[bt].reshape(-1, pool.shape[2]).astype(jnp.float32)
    kv = jnp.einsum("tw,hwd->htd", rows, w_kv.astype(jnp.float32))
    mask = keep_mask(scores, thr, pcut)[:, None]           # [N, 1, Cs, S]
    s = jnp.einsum("nhcd,htd->nhct", q.astype(jnp.float32),
                   kv[..., :k_dim]) * softmax_scale
    s = jnp.where(mask, s, NEG_INF)
    p = jnp.where(mask, jnp.exp(s - s.max(axis=-1, keepdims=True)), 0.0)
    l = p.sum(axis=-1, keepdims=True)
    out = jnp.einsum("nhct,htv->nchv", p / jnp.where(l > 0, l, 1.0),
                     kv[..., k_dim:])
    return out.reshape(N, Cs, -1).astype(q.dtype)
