"""Paged attention over LATENT pages (multi-head latent attention, MLA) for
TPU (Pallas), and the row write that puts new tokens' latents into them.

A latent page is ``[bs, W]``: one row a token, holding the compressed
key/value latent ``c_kv`` (``kv_lora_rank`` values, after its norm), the one
rotary key all heads share (``qk_rope_head_dim`` values, after rotation) and
zeros up to ``W``, the next multiple of 128 lanes (576 -> 640: the width the
device's tiling gives a 576-wide row anyway). There is no head axis and no
K/V pair: in the ABSORBED form every query head scores against the whole row
and reads its output from the row's first ``kv_lora_rank`` values,

    q_abs[h] = [q_nope[h] W_UK[h]^T | q_rope[h] | 0]            [W]
    score    = q_abs[h] . row * scale
    o_lat[h] = sum_t p_t row_t[:kv_lora_rank]

so a page is copied into VMEM ONCE and serves as keys and as values. The
caller absorbs ``W_UK`` into the queries before and applies ``W_UV`` to
``o_lat`` after (``inference/v2/ragged_model.py``).

One kernel body serves every program that reads the pool:

- decode rows (scope ``mla_decode``): one query token a sequence, its ``H``
  heads the ``M`` dimension of both products (``[H, W] x [W, tokens]`` and
  ``[H, tokens] x [tokens, kv_lora_rank]``); optionally a per-sequence SIDE
  slab of freshly decoded rows (the decode step's side buffer: the pool is
  frozen through the step's layers) folded into the same online softmax;
- prompt-chunk and verify rows (scope ``mla_chunk``): ``Cs`` query tokens a
  slot, row ``r`` of the slot's ``Cs * H`` query rows being token ``r // H``,
  causal by absolute position, in blocks of query rows.

One grid step = (one slot, one block of query rows). Inside it a loop with a
DYNAMIC trip count walks the slot's chunks of ``P`` pages — only as many as
the rows can see — through a two-slot manual DMA pipeline
(``pltpu.make_async_copy``); the last chunk of a step starts the first chunk
of the next step's copies, so the whole batch is one stream of page reads.
(The K/V kernels' grid has a chunk axis of the LONGEST context's length and
pays a grid step for every chunk a shorter sequence does not have; with
contexts of 0.3k-9k in one batch that was most of the steps.)
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas import _backend
from deepspeed_tpu.ops.pallas.paged_attention import (
    NEG_INF, _flash_update, _row_group)


LANES = 128
#: pages a chunk of the kernel's loop. Both products of a chunk run over all
#: its tokens, so a context's last, partly filled chunk is computed whole —
#: and still longer chunks are faster: 32 decode rows of 1.5k-token contexts
#: over 40 layers took 6.86 / 5.35 / 4.68 / 4.60 ms at 2 / 4 / 8 / 12 pages
#: (my chip run, PR 33): a chunk's fixed costs (issuing and awaiting its
#: copies, the masks, the softmax state's update) outweigh the padding. The
#: body unrolls a chunk's page copies, so its trace grows with this; 12 is
#: what the VMEM budget below would allow, for 1.6% more
PAGES_PER_CHUNK = 8


def latent_row_width(kv_lora_rank: int, qk_rope_head_dim: int) -> int:
    """Values a latent row takes in the pool: latent + rotary key, padded to
    whole 128-lane tiles (what the device's layout would pad it to anyway)."""
    return -(-(kv_lora_rank + qk_rope_head_dim) // LANES) * LANES


def _mla_kernel(bt_ref, q0_ref, cl_ref, j_ref, l_ref, *refs, scale, heads,
                v_dim, block_size, pages_per_chunk, max_blocks, n_slots,
                n_qblocks, rows_block, n_side):
    """See the module docstring. ``refs``: q block, [side block,] the pool
    (HBM), the output block, then scratch (page slabs, DMA semaphores, the
    running chunk count, flash state)."""
    del l_ref                                   # used by the side BlockSpec
    side = n_side > 0
    if side:
        q_ref, side_ref, kv_hbm, o_ref, kv_buf, sems, cnt, acc_sc, m_sc, \
            l_sc = refs
    else:
        q_ref, kv_hbm, o_ref, kv_buf, sems, cnt, acc_sc, m_sc, l_sc = refs
    P, bs, RB = pages_per_chunk, block_size, rows_block
    T = P * bs
    n, iq = pl.program_id(0), pl.program_id(1)

    def limit_of(n_, iq_):
        """Tokens of the pages this block of query rows can see: up to its
        last row's own position, within the slot's context (>= 1 so that a
        step always runs one chunk: empty slots mask to zeros)."""
        last_pos = q0_ref[n_] + ((iq_ + 1) * RB - 1) // heads
        return jnp.maximum(jnp.minimum(cl_ref[n_], last_pos + 1), 1)

    def n_chunks_of(n_, iq_):
        return jax.lax.div(limit_of(n_, iq_) + (T - 1), T)

    def copies(n_, iq_, c_, slot):
        """(needed, copy) of each page of chunk ``c_`` — built alike at start
        and at wait, so the semaphore counts agree."""
        lim = limit_of(n_, iq_)
        out = []
        for j in range(P):
            page = bt_ref[n_, jnp.minimum(c_ * P + j, max_blocks - 1)]
            out.append(((c_ * P + j) * bs < lim, pltpu.make_async_copy(
                kv_hbm.at[page], kv_buf.at[slot, j], sems.at[slot])))
        return out

    def start(n_, iq_, c_, slot):
        for need, cp in copies(n_, iq_, c_, slot):
            @pl.when(need)
            def _():
                cp.start()

    def wait(n_, iq_, c_, slot):
        for j, (need, cp) in enumerate(copies(n_, iq_, c_, slot)):
            @pl.when(need)
            def _():
                cp.wait()

            # a page not copied holds whatever the slab held: its scores are
            # masked, but 0 * NaN = NaN through the value product, so zero it
            @pl.when(jnp.logical_not(need))
            def _():
                kv_buf[slot, j] = jnp.zeros_like(kv_buf[slot, j])

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
    ctx = cl_ref[n]
    nxt_n = jnp.where(iq + 1 < n_qblocks, n, jnp.minimum(n + 1, n_slots - 1))
    nxt_iq = jnp.where(iq + 1 < n_qblocks, iq + 1, 0)
    has_next = step + 1 < n_slots * n_qblocks
    # a query row's position: row r of the slot is token r // heads
    row = jax.lax.broadcasted_iota(jnp.int32, (RB, T), 0)
    q_pos = q0_ref[n] + (iq * RB + row) // heads
    col = jax.lax.broadcasted_iota(jnp.int32, (RB, T), 1)

    def chunk(c, carry):
        slot = jax.lax.rem(base + c, 2)

        @pl.when(c + 1 < nc)
        def _():
            start(n, iq, c + 1, 1 - slot)

        @pl.when(jnp.logical_and(c + 1 == nc, has_next))
        def _():
            start(nxt_n, nxt_iq, 0, 1 - slot)

        wait(n, iq, c, slot)
        q = q_ref[0]                                           # [RB, W]
        kk = kv_buf[slot].reshape(T, -1)                       # [T, W]
        vv = kv_buf[slot, :, :, :v_dim].reshape(T, v_dim)
        sc = jax.lax.dot_general(q.astype(kk.dtype), kk,
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
        tok = c * T + col
        mask = jnp.logical_and(tok < ctx, tok <= q_pos)
        _flash_update(sc, mask, vv, m_sc, l_sc, acc_sc)
        return carry

    jax.lax.fori_loop(0, nc, chunk, 0)
    cnt[0] = base + nc

    if side:
        # fold the side slab: rows 0..j hold the tokens at positions ctx + cc
        # decoded in this chunk of steps (row j the current one); the rows
        # after hold zeros or an earlier chunk's values and are masked. Row
        # j is always visible, so l > 0 even with an empty prefix.
        jcur = j_ref[0]
        sk = side_ref[0, 0]                                    # [n_side, W]
        sc_s = jax.lax.dot_general(q_ref[0].astype(sk.dtype), sk,
                                   (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32) * scale
        cc = jax.lax.broadcasted_iota(jnp.int32, (RB, n_side), 1)
        row1 = jax.lax.broadcasted_iota(jnp.int32, (n_side, 1), 0)
        sv = jnp.where(row1 <= jcur, sk[:, :v_dim], 0.0).astype(sk.dtype)
        _flash_update(sc_s, cc <= jcur, sv, m_sc, l_sc, acc_sc)

    l = l_sc[:, 0:1]
    o_ref[0] = (acc_sc[:] / jnp.where(l > 0.0, l, 1.0)).astype(o_ref.dtype)


def _pick_rows_block(rows: int, heads: int) -> int:
    """Query rows a grid step takes: all of a decode row's heads; of a
    chunk's ``Cs * heads`` rows the largest power-of-two multiple of
    ``heads`` up to 512 that divides them."""
    rb = heads
    while rb * 2 <= min(rows, 512) and rows % (rb * 2) == 0:
        rb *= 2
    return rb


def mla_paged_attention(q: jax.Array, pool: jax.Array,
                        block_tables: jax.Array, q_pos0: jax.Array,
                        ctx_lens: jax.Array, *, heads: int, v_dim: int,
                        softmax_scale: float,
                        side: Optional[jax.Array] = None, side_j=None,
                        layer_idx=None) -> jax.Array:
    """Absorbed-form attention of ``N`` slots of query rows over latent pages.

    q:            [N, R, W]   R = tokens * heads query rows a slot, token
                  major (row r: token r // heads, head r % heads), already
                  absorbed and padded to the row width (module docstring)
    pool:         [NB, bs, W] latent pages (callers pass all layers' pages as
                  one list and block tables offset by ``l * pages a layer``)
    block_tables: [N, MB] int32
    q_pos0:       [N] int32   position of each slot's first query token
    ctx_lens:     [N] int32   tokens of the pages a slot may read; row r sees
                  page tokens ``t < ctx`` with ``t <= q_pos0 + r // heads``
    side:         [L, N, Cs, W] side slabs with ``layer_idx`` and ``side_j``
                  (traced int32): decode rows only (R == heads); rows
                  ``cc <= side_j`` of slab ``[layer_idx, n]`` are the tokens
                  at positions ``ctx + cc`` and are attended after the pages
                  (pass ``q_pos0 >= ctx`` there: every page token is seen)

    Returns ``[N, R, v_dim]``: per query row the softmax-weighted sum of the
    rows' first ``v_dim`` values (zeros where nothing is visible)."""
    N, R, W = q.shape
    NB, bs, Wp = pool.shape
    MB = block_tables.shape[1]
    assert Wp == W and R % heads == 0 and v_dim <= W
    RB = _pick_rows_block(R, heads)
    nq = R // RB
    esize = jnp.dtype(pool.dtype).itemsize
    # pages a chunk: at most PAGES_PER_CHUNK, the scores of a block of rows
    # against a chunk near a MiB in float32, a chunk's two slabs within 4 MiB
    P = max(1, min(MB, PAGES_PER_CHUNK, (1 << 20) // (RB * bs * 4),
                   (4 << 20) // (2 * bs * W * esize)))
    n_side = 0
    if side is not None:
        assert R == heads and side.ndim == 4 and side.shape[1] == N \
            and side.shape[3] == W and side.shape[2] % 8 == 0, side.shape
        n_side = side.shape[2]
    kernel = functools.partial(
        _mla_kernel, scale=float(softmax_scale), heads=heads, v_dim=v_dim,
        block_size=bs, pages_per_chunk=P, max_blocks=MB, n_slots=N,
        n_qblocks=nq, rows_block=RB, n_side=n_side)
    idx = lambda n, iq, *_: (n, iq, 0)
    in_specs = [pl.BlockSpec((1, RB, W), idx)]
    operands = [block_tables.astype(jnp.int32), q_pos0.astype(jnp.int32),
                ctx_lens.astype(jnp.int32),
                jnp.asarray(0 if side_j is None else side_j,
                            jnp.int32).reshape(1),
                jnp.asarray(0 if layer_idx is None else layer_idx,
                            jnp.int32).reshape(1), q]
    if n_side:
        in_specs.append(pl.BlockSpec(
            (1, 1, n_side, W),
            lambda n, iq, bt, q0, cl, jj, ll: (ll[0], n, 0, 0)))
        operands.append(side)
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
    operands.append(pool)
    call = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(N, nq), in_specs=in_specs,
            out_specs=pl.BlockSpec((1, RB, v_dim), idx),
            scratch_shapes=[
                pltpu.VMEM((2, P, bs, W), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((RB, v_dim), jnp.float32),
                pltpu.VMEM((RB, LANES), jnp.float32),
                pltpu.VMEM((RB, LANES), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((N, R, v_dim), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_backend.interpret(),
    )
    # the scope is the kernel's name in a device trace (op_name metadata)
    with jax.named_scope("mla_decode" if R == heads else "mla_chunk"):
        return call(*operands)


def mla_paged_attention_reference(q, pool, block_tables, q_pos0, ctx_lens, *,
                                  heads, v_dim, softmax_scale, side=None,
                                  side_j=None, layer_idx=None):
    """Plain ``jnp`` statement of :func:`mla_paged_attention` (float32)."""
    N, R, W = q.shape
    rows = pool[block_tables].reshape(N, -1, W).astype(jnp.float32)
    tok = jnp.arange(rows.shape[1])[None, None, :]
    q_pos = q_pos0[:, None, None] + (jnp.arange(R) // heads)[None, :, None]
    mask = (tok < ctx_lens[:, None, None]) & (tok <= q_pos)
    if side is not None:
        sk = side[layer_idx].astype(jnp.float32)               # [N, Cs, W]
        rows = jnp.concatenate([rows, sk], axis=1)
        seen = jnp.arange(sk.shape[1])[None, None, :] <= side_j
        mask = jnp.concatenate(
            [mask, jnp.broadcast_to(seen, (N, R, sk.shape[1]))], axis=2)
    s = jnp.einsum("nrw,ntw->nrt", q.astype(jnp.float32), rows) \
        * softmax_scale
    s = jnp.where(mask, s, NEG_INF)
    p = jnp.where(mask, jnp.exp(s - s.max(axis=-1, keepdims=True)), 0.0)
    l = p.sum(axis=-1, keepdims=True)
    out = jnp.einsum("nrt,ntv->nrv", p / jnp.where(l > 0, l, 1.0),
                     rows[..., :v_dim])
    return out.astype(q.dtype)


# --------------------------------------------------------------------------- #
# row write: the decode step's write after its layers (scope ``kv_flush``)
# --------------------------------------------------------------------------- #


def _row_write_kernel(bt_ref, pre_ref, side_ref, pool_in, pool_out, *,
                      n_rows, group, n_slots, layers):
    """One grid step = (a tile of layers, one sequence, one group of slots):
    the group's rows of every layer of the tile come in, the side rows that
    fall in it replace theirs, the block goes back (``paged_kv_row_write``'s
    scheme on a pool with no head axis)."""
    del bt_ref
    s, g = pl.program_id(1), pl.program_id(2)
    pos0 = pre_ref[s]
    base = _row_group(pos0, g, n_rows, group, n_slots) * group
    W = pool_out.shape[-1]
    slot = base + jax.lax.broadcasted_iota(jnp.int32, (group, W), 0)
    # positions past the block table are written nowhere
    pos = [jnp.where(pos0 + j < n_slots, pos0 + j, -1) for j in range(n_rows)]

    def layer(l, carry):
        cur = pool_in[l, 0].astype(jnp.float32)
        for j in range(n_rows):
            cur = jnp.where(slot == pos[j], side_ref[l, 0, pl.ds(j, 1), :],
                            cur)
        pool_out[l, 0] = cur.astype(pool_out.dtype)
        return carry

    jax.lax.fori_loop(0, layers, layer, 0)


def mla_row_write(pool: jax.Array, side: jax.Array, block_tables: jax.Array,
                  prefix: jax.Array, n_rows: int) -> jax.Array:
    """Write ``n_rows`` new tokens a sequence into EVERY layer's latent
    pages, in place: token ``j`` of sequence ``s`` (position ``prefix[s] +
    j``) goes to slot ``pos % bs`` of page ``block_tables[s, pos // bs]``,
    its row taken from ``side[l, s, j]``.

    pool:  [L, NB, bs, W] — ALIASED: the returned pool reuses the buffer
    side:  [L, S, >= n_rows, W]

    As in ``paged_kv_row_write`` the unit is the aligned group of slots one
    tile holds (16 of a bfloat16 pool; Mosaic refuses a DMA of one row of a
    page): read, the new rows put in, written back; a block carries the group
    for as many layers as fit a MiB. Positions past the block table are
    written nowhere; sequences sharing a page (the engine's pad rows, all at
    its scratch page) leave either's rows there."""
    L, NB, bs, W = pool.shape
    S, MB = block_tables.shape
    assert side.shape[:2] == (L, S) and side.shape[3] == W
    item = jnp.dtype(pool.dtype).itemsize
    G = min(32 // item, bs)
    assert bs % G == 0
    n_groups = (n_rows + 2 * G - 2) // G
    rows_side = side.shape[2]
    layer_bytes = max(G * W * item, rows_side * W * 4)
    LT = max(t for t in range(1, L + 1)
             if L % t == 0 and (t == 1 or t * layer_bytes <= 1 << 20))

    def pool_map(lt, s, g, bt, pre):
        gg = _row_group(pre[s], g, n_rows, G, MB * bs)
        return (lt, bt[s, gg * G // bs], gg % (bs // G), 0)

    pool_spec = pl.BlockSpec((LT, 1, G, W), pool_map)
    call = pl.pallas_call(
        functools.partial(_row_write_kernel, n_rows=n_rows, group=G,
                          n_slots=MB * bs, layers=LT),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(L // LT, S, n_groups),
            # single rows are read out of the side buffer: 32-bit rows, which
            # a kernel may slice anywhere (the values are exact in float32)
            in_specs=[pl.BlockSpec((LT, 1, rows_side, W),
                                   lambda lt, s, g, bt, pre: (lt, s, 0, 0)),
                      pool_spec],
            out_specs=pool_spec),
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3),
        interpret=_backend.interpret(),
    )
    with jax.named_scope("mla_row_write"):
        return call(block_tables.astype(jnp.int32), prefix.astype(jnp.int32),
                    side.astype(jnp.float32), pool)
