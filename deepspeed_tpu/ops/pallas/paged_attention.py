"""Paged (blocked-KV) decode attention for TPU (Pallas).

Parity role: the reference's ragged inference kernels — blocked flash decode over a
paged KV cache (``inference/v2/kernels/ragged_ops/blocked_flash``, the CUDA
flash-attn wrapper reading ``linear_blocked_kv_rotary``-filled KV pages). SURVEY §7
ranks this the hardest kernel in the project; this is the TPU-native take:

  - The KV cache lives in HBM as COMBINED head-major pages
    ``[num_blocks, 2, H_kv, bs, D]`` — one page holds a sequence-chunk's K
    (index 0) AND V (index 1). Two design forces meet here:
    (1) HEAD-MAJOR rows: a page's trailing dims are (block_size, head_dim) =
    (128, 128)-class shapes, so no pool view ever carries a padded sublane
    tile — with the head count second-minor, XLA assigns a padded layout and
    every pool-sized reshape in the layer scan materialises a multi-hundred-MB
    copy (measured 26+ ms per decode step at 0.55B); TP slices the pool on the
    head dim with each shard's pages still contiguous.
    (2) K+V COMBINED: one page = one value copy — half the copy count of
    split K/V pools — and an int8 page's scale tile rides as one more small
    copy instead of two. (Rounds 4 and 5 read the decode kernel as bound by
    its copies' count at smaller pages; at the cells' pages of 64-512 KiB
    the stream of page copies by itself reads 92% of the HBM rate, PERF.md,
    PR 51.)
  - One grid step = one decode row, and inside it a loop over the GROUPS of
    pages that hold a key the row sees (``_decode_walk_kernel``): page ids
    come from the scalar-prefetched block table and a group streams HBM ->
    VMEM through manual copies (``pltpu.make_async_copy``) into one of
    three slots. The call's groups are one sequence across rows, started
    two ahead of the one computed, so the whole decode batch is one
    continuous stream of page reads with compute hidden under it; a table
    entry a row does not use costs nothing. No materialised per-sequence KV
    copy (the XLA fallback below pays that copy).
  - Online softmax (flash) across a row's groups with running (m, l, acc)
    in VMEM scratch, exactly like the training flash kernel
    (``ops/pallas/flash_attention.py``).
  - Heads: a KV head's G query heads meet that head's keys only — ``Hkv``
    products of ``[G, D] x [D, T]`` a group (and as many for p@V). With so
    few rows a product the MXU is bound by loading K and V as its weights,
    0.09 us a 128 KiB page against the 0.16 us its copy takes, so the
    products hide under the stream while scores, mask and ``exp`` are done
    once. (The form before PR 51 — ONE ``[H, D] x [D, Hkv*T]`` product
    with the other heads' columns masked block-diagonally, over chunks of
    up to 32 pages on a grid as wide as the block table — did ``Hkv`` times
    the mask and ``exp`` and spent three grid steps in four on chunks that
    held no key: 46% of the HBM rate at 8 query heads over 2 KV heads where
    this reads 83%. ``paged_decode_attention_step`` still has it.)
  - int8 pages (``kv_scales``): values int8 with per-token-head f32 scales
    (reference role: ZeRO-Inference's KV quantization, README.md:23, on the
    blocked-flash path). Scales live in one (8k, 128) f32 tile per page —
    K rows then V rows, flat index kv*Hkv*bs + h*bs + t at (idx//128,
    idx%128) — so the dequant stream is a single aligned DMA. In-kernel the
    scales fold in as score-column (K) and p-column (V) multipliers applied
    per 128-lane sub-block (tile lane rows map 1:1 onto score column blocks;
    no relayout, no dequantized slab).

Decode-only by design (one query token per sequence): SplitFuse prompt chunks take
the chunked-flash path (``paged_chunk_attention``) — chunk attention is
compute-bound, while decode attention is bound by the page stream and must
not copy the KV.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from deepspeed_tpu.ops.pallas import _backend


NEG_INF = -1e30


def kv_quantize_rows(x: jax.Array):
    """Symmetric per-row int8 quantization for KV pages: ``[..., D]`` ->
    (int8 values ``[..., D]``, f32 scale ``[...]``). One scale per
    token-head row — the granularity the paged kernels dequantize at
    (reference role: the int8 KV strategy of ZeRO-Inference, README.md:23;
    the v1 dense tier uses the same scheme)."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    s = amax / 127.0
    q = jnp.round(xf / jnp.maximum(s, 1e-20)[..., None])
    return q.astype(jnp.int8), s


def kv_dequantize_rows(q: jax.Array, s: jax.Array) -> jax.Array:
    """Inverse of :func:`kv_quantize_rows`: (int8 values ``[..., D]``, f32
    scales ``[...]``) -> f32 rows. This is the CPU ``lax.*`` reference for
    what the kernels' in-flight dequant computes — the kernels fold the
    per-row scale into score/p columns instead of materialising this
    product, an algebraic identity, so reference attention over
    ``kv_dequantize_rows(pages)`` is the ground truth the int8 kernel
    paths are tested against (tests/unit/test_paged_attention.py)."""
    return q.astype(jnp.float32) * s[..., None]


def kv_write_dequant(x: jax.Array) -> jax.Array:
    """Quantize-then-dequantize: the value an int8 page actually stores and
    every later reader dequantizes back. The fused decode paths
    (side-buffer slab, step-kernel registers) pass new K/V rows through
    this BEFORE attending, so the current token is attended at its POOL
    value — the same value the verify step's write-then-attend reads from
    the pages — instead of its raw pre-quantization value (a ~1/254
    relative semantic gap that would break the spec-on/off byte gates).

    Re-quantizing the result is BYTE-idempotent: the max-abs element maps
    to exactly +-127, so a second ``kv_quantize_rows`` reproduces the same
    int8 values AND the same f32 scale — ``s = fl(amax/127)`` satisfies
    ``fl(fl(127*s)/127) == s`` (the div->mul->div composition is
    idempotent after the first division; measured over 17.7M f32 bit
    patterns), so raw-row writers (ragged pass, verify step) and deq'd-row
    re-quantizers (decode step, sidebuf flush) store bit-identical page
    bytes for the same token (pinned by tests/unit/test_paged_attention.py).

    Returns f32 — NOT the input dtype: the kernels dequantize pages as
    int8 * f32 scale in f32, so a bf16 round-trip here would round the
    attended value away from what every pool read computes (a ~1e-2-class
    gap on bf16 engines, exactly the kind the pool-value discipline
    exists to close)."""
    q, s = kv_quantize_rows(x)
    return kv_dequantize_rows(q, s)


def _scale_tile_rows(h_kv: int, bs: int) -> int:
    """Sublane rows of one page's scale tile, padded to the (8, 128) f32
    tile: a page's 2*Hkv*bs scales (K + V) occupy 2*Hkv*bs/128 lane rows;
    Mosaic DMA slices must be whole tiles, so the row count rounds up to 8
    (<= 6% of the int8 page body — the price of one aligned copy)."""
    r = (2 * h_kv * bs) // 128
    return -(-r // 8) * 8


def _scales_to_tiles(s: jax.Array) -> jax.Array:
    """[NB, 2, Hkv, bs] f32 logical scales -> [NB, R8, 128] DMA-aligned
    tiles (flat index kv*Hkv*bs + h*bs + t at (idx // 128, idx % 128)).
    Already-tiled input (ndim 3) passes through. The SERVING pools store
    scales in tile layout AT REST (ragged/kv_cache.py) so no pass ever pays
    a pool-sized pad+reshape; this conversion exists for logical-layout
    callers (tests, one-shot uses)."""
    if s.ndim == 3:
        return s
    NB, _, h_kv, bs = s.shape
    r8 = _scale_tile_rows(h_kv, bs)
    flat = s.reshape(NB, 2 * h_kv * bs).astype(jnp.float32)
    pad = r8 * 128 - 2 * h_kv * bs
    if pad:
        flat = jnp.pad(flat, ((0, 0), (0, pad)))
    return flat.reshape(NB, r8, 128)


def kv_scales_to_tiles(s: jax.Array) -> jax.Array:
    """Public tiling hook (see :func:`_scales_to_tiles`)."""
    return _scales_to_tiles(s)


def kv_scale_tiles_shape(num_blocks: int, h_kv: int, bs: int):
    """At-rest tile-layout shape of a scale pool: [NB, R8, 128] f32."""
    return (num_blocks, _scale_tile_rows(h_kv, bs), 128)


def _colscale_pages(mat, tile_ref, n_pages, nsub, off):
    """Apply per-token-head dequant scales to ``mat``'s columns, one aligned
    128-lane piece at a time: column block (page jp, sub t) multiplies by
    scale-tile lane row ``tile_ref[jp, off + t, :]``. The ONE shared
    implementation of the int8 fold for every kernel (decode, batched
    sidebuf, chunk) — the lane-alignment assumption (bs*Hkv % 128 == 0 and,
    for per-head addressing, bs % 128 == 0) lives here."""
    cols = []
    for jp in range(n_pages):
        for t in range(nsub):
            c0 = (jp * nsub + t) * 128
            cols.append(mat[:, c0:c0 + 128] * tile_ref[jp, off + t, :][None, :])
    return jnp.concatenate(cols, axis=1) if len(cols) > 1 else cols[0]


#: the most pages a chunk of the in-layer-write step and of the split-K
#: kernels holds, whatever the bytes allow: their body issues, awaits and
#: zeroes a chunk's page copies one by one, unrolled at six places, so its
#: trace and its code grow with P. By bytes alone one KV head of 128 would
#: take 63 pages a chunk (8k tokens: a 2k context computes 8k), and a
#: 28-layer decode step then traced for 13 s a bucket on the chip's host
#: (PERF.md, PR 31). 8 KV heads take 7 and 4 take 15: under the cap. (The
#: decode step's side-buffer kernel and a paged pass's decode rows walk
#: groups of ``_pick_decode_pages`` pages instead.)
MAX_PAGES_PER_CHUNK = 32


def _pick_pages_per_chunk(bs: int, h_kv: int, d: int, esize: int,
                          max_blocks: int,
                          scale_tile_rows: int = 0, flash_heads: int = 0,
                          out_bytes: int = 0) -> int:
    """Pages a chunk of ``_decode_body`` (the in-layer-write step) and of
    the split-K kernels: the largest P with the 2-slot combined-KV slabs
    within ~8 MB of VMEM (~16 MB on v5e; q blocks and score tiles are
    small), up to ``MAX_PAGES_PER_CHUNK``. Fatter chunks amortise the
    per-grid-step fixed cost: about a microsecond a step whether or not the
    chunk holds a key (PERF.md, PR 51).

    ``flash_heads``: H of the f32 flash
    scratch ((m, l) [H, 128] pair + [H, D] accumulator) — the running
    partial state split-K multiplies across virtual rows, reserved off the
    top so fat chunks can't overrun the budget. ``out_bytes``: the
    double-buffered output blocks a caller pipelines (the split-K kernel's
    f32 (out, lse) partial blocks). ``scale_tile_rows``: R8 of an int8
    page's scale tile — charged PER PAGE (each resident page slot carries
    its scale-tile slot, so the cost scales with P, not off the top)."""
    import os
    budget = int(os.environ.get("DSTPU_PAGED_VMEM_BUDGET",
                                8 * 1024 * 1024)) - out_bytes
    if flash_heads:
        budget -= (flash_heads * d + 2 * flash_heads * 128) * 4
    per_page = 2 * 2 * bs * h_kv * d * esize     # 2 slots x (K + V)
    if scale_tile_rows:
        per_page += 2 * scale_tile_rows * 128 * 4  # 2 slots x scale tile
    return max(1, min(max_blocks, budget // per_page, MAX_PAGES_PER_CHUNK))


def _alibi_slope(head, H: int):
    """Elementwise ALiBi slope for q-head index array ``head`` (f32) —
    the standard geometric schedule (2^(-8/H) powers, with the
    interpolation for non-power-of-two H), computed ANALYTICALLY so kernels
    need no slope operand (a [H] vector operand would need sublane-layout
    gymnastics; an exp2 over an iota needs none). Matches
    models/decoder.alibi_slopes (parity-tested)."""
    import math as _m
    if _m.log2(H).is_integer():
        s1 = 2.0 ** (-(2.0 ** -(_m.log2(H) - 3)))
        return jnp.exp2(_m.log2(s1) * (head + 1.0))
    closest = 2 ** _m.floor(_m.log2(H))
    s1 = 2.0 ** (-(2.0 ** -(_m.log2(closest) - 3)))
    s2 = 2.0 ** (-(2.0 ** -(_m.log2(2 * closest) - 3)))
    return jnp.where(head < closest,
                     jnp.exp2(_m.log2(s1) * (head + 1.0)),
                     jnp.exp2(_m.log2(s2) * (2.0 * (head - closest) + 1.0)))


# ALiBi in the paged kernels (reference parity: the v1 fused softmax takes
# alibi on its kernel path, csrc/transformer/inference/csrc/softmax.cu, and
# module_inject/containers/bloom.py serves BLOOM injected): the bias
# slope_h * (k_pos - q_pos) is applied as slope_h * k_pos ONLY — the
# -slope_h * q_pos term is constant along each softmax row and cancels
# exactly, and dropping it keeps every kernel's bias independent of the
# query position bookkeeping (the references use the same form, so kernel
# and reference lse streams shift by the same row constant).


def _chunk_mask(c, ctx_limit, T, h_kv, bs, H, tok_lo=None):
    """[H, P*Hkv*bs] block-diagonal + context mask for a head-major chunk
    slab: column j <-> (page p = j // (Hkv*bs), kv head (j // bs) % Hkv,
    token p*bs + j % bs); row i's kv head is i // G. Built directly in 2D —
    merging a (sublane, lane) pair via reshape is a relayout Mosaic
    rejects. ``tok_lo`` (sliding window) additionally hides tokens below
    the window start."""
    W = (T // bs) * h_kv * bs  # == P * Hkv * bs
    col = jax.lax.broadcasted_iota(jnp.int32, (H, W), 1)
    groups = H // h_kv
    row_kv = jax.lax.broadcasted_iota(jnp.int32, (H, W), 0) // groups
    tok = c * T + (col // (h_kv * bs)) * bs + jax.lax.rem(col, bs)
    col_kv = jax.lax.rem(col // bs, h_kv)
    mask = jnp.logical_and(col_kv == row_kv, tok < ctx_limit)
    if tok_lo is not None:
        mask = jnp.logical_and(mask, tok >= tok_lo)
    return mask


def _flash_update(sc, mask, vv, m_sc, l_sc, acc_sc, v_scale_fn=None,
                  compute_dtype=jnp.bfloat16):
    """One online-softmax update of the running (m, l, acc) scratch.

    ``v_scale_fn`` (int8 KV pages): applies the per-column V dequant scales
    to p before the pv dot, so the int8 V slab never materialises a
    dequantized copy (p @ (s * v) == (p * s) @ v, column-wise).
    ``compute_dtype``: dot dtype for an int8 ``vv`` (bf16 on the serving
    path — MXU; f32 when the caller's q is f32, keeping tests exact)."""
    sc = jnp.where(mask, sc, NEG_INF)
    m_prev = m_sc[:, 0:1]
    m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
    # explicit mask, not exp(sc - m_new) alone: in an all-masked chunk
    # (ctx 0, or garbage pages past ctx) m_new == sc == NEG_INF and the
    # bare exp would emit 1.0 per masked column
    p = jnp.where(mask, jnp.exp(sc - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_sc[:, 0:1] = l_sc[:, 0:1] * alpha + jnp.sum(p, axis=1, keepdims=True)
    m_sc[:, 0:1] = m_new
    pv = p if v_scale_fn is None else v_scale_fn(p)
    if vv.dtype == jnp.int8:
        vv = vv.astype(compute_dtype)
    pv_dot = jax.lax.dot_general(pv.astype(vv.dtype), vv,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    acc_sc[:] = acc_sc[:] * alpha + pv_dot


def _decode_body(bt_ref, cl_ref, q_ref, knew_ref, vnew_ref,
                 kv_hbm, o_ref,
                 kv_buf, sems, acc_sc, m_sc, l_sc, *,
                 scale, block_size, pages_per_chunk, n_chunks, max_blocks,
                 n_seqs, h_kv, groups, window=None,
                 sc_hbm=None, sc_buf=None, alibi=False):
    """The body of the in-layer-write decode step
    (:func:`paged_decode_attention_step`; the decode step's side-buffer form
    and a paged pass's decode rows run ``_decode_walk_kernel``): a grid of
    (sequence, CHUNK of P pages) over the whole block table, a chunk's live
    pages copied and all P computed in one block-diagonal product. The
    pages hold tokens [0, ctx-1) and the current token's attention term
    folds in from registers (``knew_ref/vnew_ref``) at finalize.

    ``sc_hbm/sc_buf`` (int8 pages): per-page scale tiles, one DMA per page.

    ``window`` (static, sliding-window serving — Mistral/Qwen2 parity,
    reference ``inference/v2/model_implementations/mistral``): the query at
    position ctx-1 attends only tokens >= ctx - window. Chunks wholly below
    the window start are skipped (grid range) and pages outside
    [window_lo, ctx) are neither DMA'd nor computed — the window bounds the
    per-step KV read the way the reference's sliding cache does."""
    quant = sc_hbm is not None
    P, bs, T = pages_per_chunk, block_size, pages_per_chunk * block_size
    HB = h_kv * bs
    s, c = pl.program_id(0), pl.program_id(1)
    g = s * n_chunks + c                   # global step: the pipeline clock
    H = h_kv * groups

    def tok_lo_of(s_):
        # first visible token (window start); 0 without a window
        if window is None:
            return jnp.int32(0)
        return jnp.maximum(cl_ref[s_] - window, 0)

    def c0_of(s_):
        # first REAL chunk index (chunks wholly below the window skip).
        # Clamped to the last chunk: window=1 in step mode has tok_lo ==
        # ctx-1, which on a chunk boundary would otherwise give c0 == nc and
        # an empty chunk range — finalize must always run once.
        if window is None:
            return jnp.int32(0)
        return jnp.minimum(jax.lax.div(tok_lo_of(s_), T),
                           n_chunks_of(s_) - 1)

    def n_chunks_of(s_):
        # every sequence runs >= 1 chunk (ctx 0 rows mask to zeros)
        return jax.lax.div(jnp.maximum(cl_ref[s_] - 1, 1) + (T - 1), T)

    def page_needed(s_, c_, j):
        """Page j of chunk c_ overlaps [tok_lo, ctx - 1)? Skipped
        pages are neither started nor waited (identical predicate on both
        sides keeps the semaphore counts consistent)."""
        t0 = (c_ * P + j) * bs
        need = t0 < jnp.maximum(cl_ref[s_] - 1, 1)
        if window is not None:
            need = jnp.logical_and(need, t0 + bs > tok_lo_of(s_))
        return need

    def chunk_copies(s_, c_, slot):
        """The per-page copy descriptors for chunk c_ of sequence s_ (built
        identically at start and wait — same (src, dst, sem) triples and
        the same ``page_needed`` predicates). One combined K+V copy per
        page (+ one scale-tile copy for int8 pages) — the kernel is
        per-copy bound, so copy count is the scarce resource."""
        cps = []
        for j in range(P):
            page = bt_ref[s_, jnp.minimum(c_ * P + j, max_blocks - 1)]
            cps.append((page_needed(s_, c_, j), pltpu.make_async_copy(
                kv_hbm.at[page], kv_buf.at[slot, j], sems.at[slot])))
            if quant:
                cps.append((page_needed(s_, c_, j), pltpu.make_async_copy(
                    sc_hbm.at[page], sc_buf.at[slot, j], sems.at[slot])))
        return cps

    per_page = 2 if quant else 1

    def start_copies(s_, c_, slot):
        for need, cp in chunk_copies(s_, c_, slot):
            @pl.when(need)
            def _():
                cp.start()

    def wait_copies(s_, c_, slot):
        for j2, (need, cp) in enumerate(chunk_copies(s_, c_, slot)):
            @pl.when(need)
            def _():
                cp.wait()
            if j2 % per_page == 0:   # the combined value copy of page j2
                # a skipped page's V half holds garbage; the online-softmax
                # p rows are exactly 0 there, but 0 * NaN = NaN, so the V
                # slab must be finite — zero it (K needs nothing: masked
                # scores are replaced before use)
                @pl.when(jnp.logical_not(need))
                def _():
                    kv_buf[slot, j2 // per_page, HB:, :] = jnp.zeros_like(
                        kv_buf[slot, j2 // per_page, HB:, :])
            if quant and j2 % per_page == 1:
                # same reasoning for the V scale rows (they fold into p)
                @pl.when(jnp.logical_not(need))
                def _():
                    sc_buf[slot, j2 // per_page] = jnp.zeros_like(
                        sc_buf[slot, j2 // per_page])

    # prime the pipeline — only when chunk (0, 0) is real (with a window,
    # sequence 0 may start at a later chunk, whose copy is issued by the
    # preceding grid step's next-real block below; priming chunk 0 anyway
    # would put stale completions on the slot-0 semaphore)
    @pl.when(jnp.logical_and(g == 0, c0_of(0) == 0))
    def _():
        start_copies(0, 0, 0)

    # issue the next REAL chunk's DMA before this chunk's compute; unreal
    # steps (c outside this sequence's chunk range) still run this control so
    # the two-slot protocol stays consistent across skipped steps
    s_n = jax.lax.div(g + 1, n_chunks)
    c_n = jax.lax.rem(g + 1, n_chunks)
    next_real = jnp.logical_and(
        g + 1 < n_seqs * n_chunks,
        jnp.logical_and(c_n < n_chunks_of(s_n), c_n >= c0_of(s_n)))

    @pl.when(next_real)
    def _():
        start_copies(s_n, c_n, jax.lax.rem(g + 1, 2))

    ctx = cl_ref[s]
    nc_s = n_chunks_of(s)
    c0_s = c0_of(s)

    @pl.when(jnp.logical_and(c < nc_s, c >= c0_s))
    def _():
        slot = jax.lax.rem(g, 2)
        wait_copies(s, c, slot)

        @pl.when(c == c0_s)
        def _():
            m_sc[:] = jnp.full_like(m_sc, NEG_INF)
            l_sc[:] = jnp.zeros_like(l_sc)
            acc_sc[:] = jnp.zeros_like(acc_sc)

        q = q_ref[0]                                           # [H, D]
        # slice the REF, not a loaded value: loading the whole combined
        # slab and slicing the value forces a full-slab relayout per chunk
        kk = kv_buf[slot, :, :HB, :].reshape(P * HB, -1)
        vv = kv_buf[slot, :, HB:, :].reshape(P * HB, -1)
        mask = _chunk_mask(c, ctx - 1, T, h_kv, bs, H,
                           tok_lo=None if window is None else tok_lo_of(s))
        v_scale_fn = None
        if quant:
            # int8 pages: convert to q's dtype for the dots (bf16 MXU path
            # in serving; f32 when q is f32 so tests stay exact) — a VPU
            # cast over a VMEM-resident slab, cheap next to the HBM read
            # this halves. Per-row dequant scales fold in as score-column
            # (K) and p-column (V) multipliers applied per 128-lane
            # sub-block (the scale tile's lane rows map 1:1 onto score
            # column blocks — no cross-tile relayout), never materialising
            # a dequantized slab.
            kk = kk.astype(q.dtype)
            nsub = HB // 128
            st = sc_buf[slot]                      # [P, R8, 128]
            v_scale_fn = functools.partial(_colscale_pages, tile_ref=st,
                                           n_pages=P, nsub=nsub, off=nsub)
        # dots run in the page dtype (bf16 MXU path for serving caches) with
        # f32 accumulation; identical math to before for f32 pools
        sc = jax.lax.dot_general(q.astype(kk.dtype), kk,
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
        if quant:
            sc = _colscale_pages(sc, st, P, nsub, 0)
        if alibi:
            col = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
            tok = c * T + (col // HB) * bs + jax.lax.rem(col, bs)
            head = jax.lax.broadcasted_iota(jnp.float32, sc.shape, 0)
            sc = sc + _alibi_slope(head, H) * tok.astype(jnp.float32)
        _flash_update(sc, mask, vv, m_sc, l_sc, acc_sc,
                      v_scale_fn=v_scale_fn, compute_dtype=q.dtype)

        @pl.when(c == nc_s - 1)
        def _():
            # fold in the current token from registers (one extra softmax
            # column per head group), then normalise
            qf = q_ref[0].astype(jnp.float32)
            kn = knew_ref[0]
            vn = vnew_ref[0]
            sc_rows = []
            pv_rows = []
            for h in range(h_kv):
                qh = qf[h * groups:(h + 1) * groups, :]        # [G, D]
                knh = kn[h, :].astype(jnp.float32)             # [D]
                sc_rows.append(jnp.sum(qh * knh[None, :], axis=1,
                                       keepdims=True) * scale)
            sc_cur = jnp.concatenate(sc_rows, axis=0)          # [H, 1]
            if alibi:
                headf = jax.lax.broadcasted_iota(jnp.float32, (H, 1), 0)
                sc_cur = sc_cur + _alibi_slope(headf, H) \
                    * (ctx - 1).astype(jnp.float32)
            m_l = m_sc[:, 0:1]
            m_f = jnp.maximum(m_l, sc_cur)
            alpha_f = jnp.exp(m_l - m_f)
            p_cur = jnp.exp(sc_cur - m_f)                      # [H, 1]
            for h in range(h_kv):
                vnh = vn[h, :].astype(jnp.float32)             # [D]
                pv_rows.append(p_cur[h * groups:(h + 1) * groups, :]
                               * vnh[None, :])
            pv_term = jnp.concatenate(pv_rows, axis=0)         # [H, D]
            l_f = l_sc[:, 0:1] * alpha_f + p_cur
            acc_f = acc_sc[:] * alpha_f + pv_term
            safe_l = jnp.where(l_f > 0.0, l_f, 1.0)
            out = (acc_f / safe_l).astype(o_ref.dtype)
            o_ref[0] = jnp.where(ctx > 0, out, jnp.zeros_like(out))


#: the most pages a group of the decode kernel holds, whatever the bytes
#: allow: a group's copies are issued and awaited one by one, unrolled, at
#: one place each in two forms
MAX_PAGES_PER_GROUP = 16

#: the slots a decode call's groups stream through: one computed from, the
#: others in flight. With two, the stream stops whenever a row's prologue,
#: its fold of the slab or a masked last group takes longer than one
#: group's copies; the third slot hid that at every cell's shape (PERF.md,
#: PR 51: 244 -> 220 us at cell 12's, 213 -> 184 at Trinity's window), a
#: fourth added nothing
_DECODE_SLOTS = 3


def _pick_decode_pages(bs: int, h_kv: int, d: int, esize: int,
                       max_blocks: int, scale_tile_rows: int = 0) -> int:
    """Pages a group of the decode kernel's walk holds (PERF.md, PR 51): as
    many as keep the slots of K+V pages (and int8 scale tiles) within 8 MB
    of VMEM, up to ``MAX_PAGES_PER_GROUP`` and the table's pages. A group's
    turn costs about half a microsecond whatever it holds (its copies'
    descriptors and, a KV head, one chain of product, row statistics and
    product), against 0.16 us a 128 KiB page at the HBM rate, so a group is
    large: 2 pages a group read 39% of the rate at cell 12's shape, 4 56%,
    8 72%, 16 75% (two slots). A step's other blocks (q, the slab's K and V
    rows, the output, each twice) and its state are a few dozen KiB beside
    the slots."""
    per_page = _DECODE_SLOTS * 2 * h_kv * bs * d * esize
    if scale_tile_rows:
        per_page += _DECODE_SLOTS * scale_tile_rows * 128 * 4
    return max(1, min(max_blocks, (8 * 1024 * 1024) // per_page,
                      MAX_PAGES_PER_GROUP))


def _decode_update(sc, mask, vh, h, m_sc, l_sc, acc_sc, v_scale=None):
    """One KV head's online-softmax update over a group's keys (the decode
    kernel's and the chunk kernel's): ``sc`` ``[rows, T]`` float32 scores,
    ``mask`` their visibility (None where every key is seen), ``vh`` ``[T,
    D]`` the head's values as the MXU takes them, ``v_scale`` ``[1, T]`` an
    int8 page's dequant scales; the state is ``[Hkv, rows, .]``. ``p`` goes
    to the MXU in the values' dtype."""
    if mask is not None:
        sc = jnp.where(mask, sc, NEG_INF)
    m_prev = m_sc[h, :, 0:1]
    m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
    p = jnp.exp(sc - m_new)
    if mask is not None:
        # explicit, not exp alone: a row that has seen nothing yet has
        # m_new == sc == NEG_INF and the bare exp would give 1.0
        p = jnp.where(mask, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_sc[h, :, 0:1] = l_sc[h, :, 0:1] * alpha + jnp.sum(p, axis=1,
                                                        keepdims=True)
    m_sc[h, :, 0:1] = m_new
    if v_scale is not None:
        p = p * v_scale
    acc_sc[h] = acc_sc[h] * alpha + jax.lax.dot_general(
        p.astype(vh.dtype), vh, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _decode_walk_kernel(*refs, scale, block_size, pages, max_blocks, n_seqs,
                        h_kv, groups, window=None, n_side=0, quant=False,
                        with_lse=False, alibi=False):
    """One grid step = one decode row; inside it a loop walks the GROUPS of
    ``pages`` consecutive pages that hold a key the row sees — from the
    window's first page (0 without one) to the page that holds token
    ``n - 1`` — and nothing else is copied, awaited, multiplied or masked: a
    table entry the row does not use costs nothing, and a row computes at
    most one group more than it holds.

    The groups stream HBM -> VMEM through ``kv_buf``'s slots, and the stream
    does not stop at a row's end: the call's groups — rows in order, a row's
    groups in order, a row that walks none passed over — are ONE sequence,
    started in that order (``issue``; ``st`` holds where the sequence
    stands) and computed in that order, each turn of the loop starting one
    group before it waits for its own, so all slots but the one computed
    from are in flight across rows' prologues and folds. A group wholly
    inside ``[lo, n)`` issues its copies without predicates and builds no
    mask; the group that holds the context's end or the window's start
    copies the pages that hold a visible key, zeroes the V half of the
    others (``p`` is exactly 0 there, but 0 * NaN is NaN) and masks. A KV
    head's queries meet that head's keys only: ``Hkv`` products of ``[G, D]
    x [D, T]``, the state laid out ``[Hkv, G, .]``.

    ``n_side`` > 0 (the decode step's side buffer): the pages hold the
    FROZEN prefix ``[0, cl)`` and the row's slab ``[n_side*Hkv, D]`` its
    freshly decoded K/V rows (row cc*Hkv + h = step cc's KV head h, at
    position cl + cc); after the last group rows cc <= ``j`` fold into the
    same (m, l, acc) — one flash stream over pages and slab, no lse merge —
    and the query's position is cl + j, so the window's start moves with j.
    Without a slab the pages hold everything (``cl`` keys) and a row of no
    key writes zeros. ``quant``: int8 pages, their scale tiles one more copy
    a page, applied as score-column (K) and p-column (V) multipliers."""
    side = n_side > 0
    it = iter(refs)
    bt_ref, cl_ref = next(it), next(it)
    j_ref = next(it) if side else None
    if side:
        next(it)        # the layer's index: the slab's index maps read it
    q_ref = next(it)
    sidek_ref, sidev_ref = (next(it), next(it)) if side else (None, None)
    kv_hbm = next(it)
    sc_hbm = next(it) if quant else None
    o_ref = next(it)
    lse_ref = next(it) if with_lse else None
    q_sc, kv_buf = next(it), next(it)
    sc_buf = next(it) if quant else None
    sems, st, acc_sc, m_sc, l_sc = it
    NS = kv_buf.shape[0]

    P, bs, G = pages, block_size, groups
    T, H = P * bs, h_kv * groups
    s = pl.program_id(0)
    mxu = q_sc.dtype

    def span(s_):
        """Row ``s_``: the keys ``[lo, n)`` of its pages that it sees and
        the groups ``[g_lo, g_hi)`` that hold them (none: ``g_hi == g_lo``)."""
        cl = cl_ref[s_]
        n = jnp.minimum(cl, max_blocks * bs)                # the table's keys
        if window is None:
            lo = jnp.int32(0)
        elif side:
            lo = jnp.maximum(cl + j_ref[0] + 1 - window, 0)
        else:
            lo = jnp.maximum(cl - window, 0)
        g_lo = jax.lax.div(lo, T)
        return lo, n, g_lo, jnp.maximum(jax.lax.div(n + (T - 1), T), g_lo)

    def inner(g, lo, n):
        """Does every key of group ``g`` lie in ``[lo, n)``?"""
        return jnp.logical_and(g * T >= lo, (g + 1) * T <= n)

    def needed(page, lo, n):
        return jnp.logical_and(page * bs < n, (page + 1) * bs > lo)

    def copies(s_, g, slot):
        """(index in the table, its copies) of each page of a group, built
        identically at start and wait. An index past the table's end repeats
        its last entry (never needed)."""
        cps = []
        for jp in range(P):
            page = bt_ref[s_, jnp.minimum(g * P + jp, max_blocks - 1)]
            cp = [pltpu.make_async_copy(kv_hbm.at[page], kv_buf.at[slot, jp],
                                        sems.at[slot])]
            if quant:
                cp.append(pltpu.make_async_copy(
                    sc_hbm.at[page], sc_buf.at[slot, jp], sems.at[slot]))
            cps.append((g * P + jp, cp))
        return cps

    def start(s_, g, slot):
        lo_, n_, _, _ = span(s_)
        whole = inner(g, lo_, n_)

        @pl.when(whole)
        def _():
            for _, cp in copies(s_, g, slot):
                for c in cp:
                    c.start()

        @pl.when(jnp.logical_not(whole))
        def _():
            for page, cp in copies(s_, g, slot):
                @pl.when(needed(page, lo_, n_))
                def _():
                    for c in cp:
                        c.start()

    def issue():
        """Start the copies of the next group of the call's sequence, if one
        is left: ``st[0]`` and ``st[1]`` are the row and the group to look
        at next (a group of -1: the row's first), ``st[2]`` how many groups
        were started, ``st[3]`` how many were computed."""
        def spent(c):
            s_i, g_i = c
            _, _, lo_g, hi_g = span(jnp.minimum(s_i, n_seqs - 1))
            return jnp.logical_and(
                s_i < n_seqs, jnp.where(g_i < 0, lo_g, g_i) >= hi_g)

        s_i, g_i = jax.lax.while_loop(
            spent, lambda c: (c[0] + 1, jnp.int32(-1)), (st[0], st[1]))
        _, _, lo_g, _ = span(jnp.minimum(s_i, n_seqs - 1))
        g_i = jnp.where(g_i < 0, lo_g, g_i)
        st[0] = s_i

        @pl.when(s_i < n_seqs)
        def _():
            start(s_i, g_i, jax.lax.rem(st[2], NS))
            st[1] = g_i + 1
            st[2] = st[2] + 1

    @pl.when(s == 0)
    def _():
        st[0] = jnp.int32(0)
        st[1] = jnp.int32(-1)
        st[2] = jnp.int32(0)
        st[3] = jnp.int32(0)
        for _ in range(NS - 1):
            issue()

    lo, n, g_lo, g_hi = span(s)

    m_sc[:] = jnp.full_like(m_sc, NEG_INF)
    l_sc[:] = jnp.zeros_like(l_sc)
    acc_sc[:] = jnp.zeros_like(acc_sc)
    qf = q_ref[0].astype(jnp.float32)                          # [H, D]
    for h in range(h_kv):
        q_sc[h] = qf[h * G:(h + 1) * G, :].astype(mxu)

    def attend(slot, g, masked):
        k0 = g * T
        mask = None
        if masked or alibi:
            k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, (G, T), 1)
        if masked:
            mask = k_pos < n
            if window is not None:
                mask = jnp.logical_and(mask, k_pos >= lo)
        for h in range(h_kv):
            # slice the REF: the head's K and V rows of every page
            kh = kv_buf[slot, :, 0, h].reshape(T, -1)
            vh = kv_buf[slot, :, 1, h].reshape(T, -1)
            sc = jax.lax.dot_general(q_sc[h], kh.astype(mxu),
                                     (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32) * scale
            v_scale = None
            if quant:
                # a tile holds K's scales from flat index h*bs on, V's from
                # (Hkv + h)*bs on
                sc = sc * _chunk_scale_row(sc_buf, slot, P, h * bs, bs)
                v_scale = _chunk_scale_row(sc_buf, slot, P, (h_kv + h) * bs,
                                           bs)
            if alibi:
                head = h * G + jax.lax.broadcasted_iota(jnp.int32, (G, T), 0)
                sc = sc + _alibi_slope(head.astype(jnp.float32), H) \
                    * k_pos.astype(jnp.float32)
            _decode_update(sc, mask, vh.astype(mxu), h, m_sc, l_sc, acc_sc,
                           v_scale=v_scale)

    def group(g, carry):
        # the slot the group before this one was computed from is free:
        # NS - 1 groups are in flight while one is computed
        issue()
        slot = jax.lax.rem(st[3], NS)
        st[3] = st[3] + 1

        whole = inner(g, lo, n)

        @pl.when(whole)
        def _():
            for _, cp in copies(s, g, slot):
                for c in cp:
                    c.wait()
            attend(slot, g, masked=False)

        @pl.when(jnp.logical_not(whole))
        def _():
            for jp, (page, cp) in enumerate(copies(s, g, slot)):
                need = needed(page, lo, n)

                @pl.when(need)
                def _():
                    for c in cp:
                        c.wait()

                @pl.when(jnp.logical_not(need))
                def _():
                    kv_buf[slot, jp, 1] = jnp.zeros_like(kv_buf[slot, jp, 1])
                    if quant:
                        sc_buf[slot, jp] = jnp.zeros_like(sc_buf[slot, jp])
            attend(slot, g, masked=True)

        return carry

    jax.lax.fori_loop(g_lo, g_hi, group, 0)

    # the state as the output lies, [H, .]
    m = m_sc[:].reshape(H, -1)[:, 0:1]
    l = l_sc[:].reshape(H, -1)[:, 0:1]
    acc = acc_sc[:].reshape(H, -1)
    if side:
        # fold the slab: one [H, D] x [D, n_side*Hkv] product under the
        # block-diagonal + step mask. Rows past j hold zeros or garbage —
        # masked. Column j is always visible, so l > 0 even at prefix 0
        jcur = j_ref[0]
        sk = sidek_ref[0, 0]                                   # [Cs*Hkv, D]
        sv = sidev_ref[0, 0]
        Ws = n_side * h_kv
        col = jax.lax.broadcasted_iota(jnp.int32, (H, Ws), 1)
        row_kv = jax.lax.broadcasted_iota(jnp.int32, (H, Ws), 0) // G
        cc = col // h_kv
        smask = jnp.logical_and(jax.lax.rem(col, h_kv) == row_kv, cc <= jcur)
        if window is not None:
            smask = jnp.logical_and(smask, cc >= jcur + 1 - window)
        sc_s = jax.lax.dot_general(q_ref[0].astype(sk.dtype), sk,
                                   (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32) * scale
        if alibi:
            # a slab row's position is prefix + cc
            headf = jax.lax.broadcasted_iota(jnp.float32, (H, Ws), 0)
            sc_s = sc_s + _alibi_slope(headf, H) \
                * (cl_ref[s] + cc).astype(jnp.float32)
        # rows > j may hold reused garbage; p is 0 there but 0 * inf = NaN
        # through the product, so zero sv's dead rows
        row1 = jax.lax.broadcasted_iota(jnp.int32, (Ws, 1), 0)
        sv = jnp.where(row1 // h_kv <= jcur, sv, 0.0)
        sc_s = jnp.where(smask, sc_s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(sc_s, axis=1, keepdims=True))
        p = jnp.where(smask, jnp.exp(sc_s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(sv.dtype), sv, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m = m_new
    safe_l = jnp.where(l > 0.0, l, 1.0)
    o_ref[0] = (acc / safe_l).astype(o_ref.dtype)
    if lse_ref is not None:
        # lse = m + log(l) per head; NEG_INF when nothing was attended (the
        # merge hook for a second attention piece — same contract as
        # flash_attention_packed's lse output)
        lse_ref[0] = jnp.broadcast_to(
            jnp.where(l > 0.0, m + jnp.log(safe_l), NEG_INF),
            lse_ref[0].shape)


def _decode_walk_call(q, kv_pages, block_tables, lens, *, scale, window,
                      kv_scales, alibi, with_lse=False, side=None):
    """The decode kernel's ``pallas_call``: ``side`` is ``(side_k, side_v, j,
    layer_idx)`` with the slabs ``[L, S, Cs*Hkv, D]``, or None where the
    pages hold everything."""
    S, H, D = q.shape
    NB, two, Hkv, bs, Dk = kv_pages.shape
    assert two == 2 and Dk == D, (kv_pages.shape, D)
    assert H % Hkv == 0, f"GQA: {H} q heads not divisible by {Hkv} kv heads"
    assert (bs * Hkv) % 8 == 0, \
        f"page rows {Hkv}*{bs} must align to the 8-sublane tile"
    G = H // Hkv
    MB = block_tables.shape[1]
    quant = kv_scales is not None
    r8 = _scale_tile_rows(Hkv, bs) if quant else 0
    if quant:
        assert (Hkv * bs) % 128 == 0, "scale tiles need lane alignment"
    P = _pick_decode_pages(bs, Hkv, D, jnp.dtype(kv_pages.dtype).itemsize,
                           MB, r8)
    # what the MXU is handed: the pages as stored (int8 pages widen to q's
    # dtype exactly), q and p rounded to that
    mxu = q.dtype if quant else kv_pages.dtype
    n_pre = 4 if side is not None else 2
    row = lambda s, *pre: (s, 0, 0)
    prefetch = [block_tables.astype(jnp.int32), lens.astype(jnp.int32)]
    in_specs = [pl.BlockSpec((1, H, D), row)]
    operands = [q]
    n_side = 0
    if side is not None:
        side_k, side_v, j, layer_idx = side
        CsH = side_k.shape[2]
        n_side = CsH // Hkv
        prefetch += [jnp.asarray(j, jnp.int32).reshape(1),
                     jnp.asarray(layer_idx, jnp.int32).reshape(1)]
        slab = pl.BlockSpec((1, 1, CsH, D),
                            lambda s, bt, cl, jj, ll: (ll[0], s, 0, 0))
        in_specs += [slab, slab]
        operands += [side_k, side_v]
    in_specs += [pl.BlockSpec(memory_space=pl.ANY)]     # pages stay in HBM
    operands += [kv_pages]
    if quant:
        in_specs += [pl.BlockSpec(memory_space=pl.ANY)]
        operands += [_scales_to_tiles(kv_scales)]
    out_specs = pl.BlockSpec((1, H, D), row)
    out_shape = jax.ShapeDtypeStruct((S, H, D), q.dtype)
    if with_lse:
        # lse rides as a [1, H, 128] f32 block (broadcast along the lane dim:
        # a bare [1, H] output would hand Mosaic a sub-lane tile)
        out_specs = [out_specs, pl.BlockSpec((1, H, 128), row)]
        out_shape = [out_shape, jax.ShapeDtypeStruct((S, H, 128), jnp.float32)]
    NS = _DECODE_SLOTS
    scratch = [pltpu.VMEM((Hkv, G, D), mxu),
               pltpu.VMEM((NS, P, 2, Hkv, bs, D), kv_pages.dtype)]
    if quant:
        scratch += [pltpu.VMEM((NS, P, r8, 128), jnp.float32)]
    scratch += [
        pltpu.SemaphoreType.DMA((NS,)),
        pltpu.SMEM((4,), jnp.int32),
        pltpu.VMEM((Hkv, G, D), jnp.float32),
        pltpu.VMEM((Hkv, G, 128), jnp.float32),
        pltpu.VMEM((Hkv, G, 128), jnp.float32),
    ]
    kernel = functools.partial(
        _decode_walk_kernel, scale=scale, block_size=bs, pages=P,
        max_blocks=MB, n_seqs=S, h_kv=Hkv, groups=G, window=window,
        n_side=n_side, quant=quant, with_lse=with_lse, alibi=alibi)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_pre, grid=(S,), in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            # the slots are handed from row to row, so the rows run in order
            dimension_semantics=("arbitrary",)),
        interpret=_backend.interpret(),
    )(*prefetch, *operands)


def _kv_flat(kv_pages):
    """[NB, 2, Hkv, bs, D] -> [NB, 2*Hkv*bs, D] (bitcast view for the DMA)."""
    NB, two, Hkv, bs, D = kv_pages.shape
    assert two == 2
    return kv_pages.reshape(NB, 2 * Hkv * bs, D)


def paged_decode_attention_sidebuf(q: jax.Array,
                                   kv_pages: jax.Array,
                                   block_tables: jax.Array,
                                   prefix_lens: jax.Array,
                                   side_k: jax.Array,
                                   side_v: jax.Array,
                                   j,
                                   softmax_scale: Optional[float] = None,
                                   window: Optional[int] = None,
                                   kv_scales: Optional[jax.Array] = None,
                                   layer_idx=None,
                                   alibi: bool = False) -> jax.Array:
    """Decode attention over a FROZEN paged prefix plus a per-sequence side
    slab of freshly decoded K/V — the kernel of the decode step's side
    buffer (``inference/v2/ragged_model._build_decode_sidebuf``).

    q:            [S, H, D]         one query per sequence (step j's token)
    kv_pages:     [NB, 2, H_kv, bs, D] frozen prefix pages (K + V combined)
    block_tables: [S, MB] int32
    prefix_lens:  [S] int32         tokens in the pages (EXCLUDING the chunk)
    side_k/v:     [S, C, H_kv, D]   side slab; rows 0..j are real (row j is
                  the current token), rows > j are ignored. MAY instead be
                  the whole per-layer stack [L, S, C, H_kv, D] with
                  ``layer_idx`` (traced int32): the kernel's BlockSpec then
                  pulls layer ``layer_idx``'s block directly — the caller
                  avoids a dynamic_slice that would MATERIALISE the layer's
                  [S, C, Hkv, D] slab per call (measured ~150 us/layer of
                  pure copy traffic in the decode step).
    j:            int32 scalar      current step within the chunk
    window:       optional static sliding window over position prefix + j
    kv_scales:    [NB, 2, H_kv, bs] f32 — int8 pages: per-token-head dequant
                  scales (the side slab stays bf16; only the prefix pages,
                  the dominant stream, are quantized)

    Returns [S, H, D]. Reference role: the blocked-flash KV stream fused with
    the in-flight tokens (``inference/v2/kernels/ragged_ops/blocked_flash``).
    """
    S, H, D = q.shape
    NB, two, Hkv, bs, Dk = kv_pages.shape
    if side_k.ndim == 4 and layer_idx is None:
        # single-layer logical [S, C, Hkv, D]
        side_k = side_k[None]
        side_v = side_v[None]
        layer_idx = 0
    if side_k.ndim == 5:
        # [L, S, C, Hkv, D] logical -> flat rows (NOTE: at head counts
        # whose (Hkv, D) tile pads this reshape relayout-copies the whole
        # stack per call — hot callers keep the buffer PRE-FLATTENED as
        # [L, S, C*Hkv, D] and skip this branch)
        assert layer_idx is not None, "multi-layer side slabs need layer_idx"
        Ls, S2, Cs, Hkv2, D2 = side_k.shape
        assert Hkv2 == Hkv and D2 == D
        side_k = side_k.reshape(Ls, S2, Cs * Hkv, D)
        side_v = side_v.reshape(Ls, S2, Cs * Hkv, D)
    Ls, S2, CsH, D2 = side_k.shape
    assert CsH % Hkv == 0
    assert two == 2 and Dk == D and D2 == D and S2 == S
    assert D % 128 == 0 and CsH % 8 == 0, \
        "side-slab kernel needs lane-aligned D and 8-sublane-aligned C*Hkv"
    scale = softmax_scale if softmax_scale is not None else 1.0 / (D ** 0.5)
    with jax.named_scope("paged_decode_sidebuf"):
        return _decode_walk_call(
            q, kv_pages, block_tables, prefix_lens, scale=scale,
            window=window, kv_scales=kv_scales, alibi=alibi,
            side=(side_k, side_v, j, layer_idx))


def _row_group(pos0, g, n_rows, group, n_slots):
    """The g-th aligned group of ``group`` slots that ``n_rows`` tokens from
    position ``pos0`` on fall in, held at the last one they (and the block
    table's ``n_slots``) reach: a grid step past it revisits that block."""
    last = jnp.minimum(pos0 + n_rows - 1, n_slots - 1)
    return jnp.minimum(pos0 // group + g, last // group)


def _kv_row_write_kernel(bt_ref, pre_ref, *refs, n_rows, h_kv, group,
                         block_size, n_slots, layers, quant):
    """One grid step = (a tile of layers, one sequence, one group of slots):
    the group's K and V rows of every layer of the tile come in, the side
    rows that fall in it replace theirs, the block goes back. Every step
    merges ALL its sequence's rows that hit its block — and, for an int8
    pool, all that hit its page's scale tile — so a block revisited by the
    next step (same index: neither fetched again nor written between) ends
    up the same."""
    del bt_ref
    if quant:
        sk_ref, sv_ref, ssk_ref, ssv_ref, kv_in, sc_in, kv_out, sc_out = refs
    else:
        sk_ref, sv_ref, kv_in, kv_out = refs
    s, g = pl.program_id(1), pl.program_id(2)
    pos0 = pre_ref[s]
    base = _row_group(pos0, g, n_rows, group, n_slots) * group
    page0 = base // block_size * block_size
    D = kv_out.shape[-1]
    slot = base + jax.lax.broadcasted_iota(jnp.int32, (group, D), 0)
    if quant:
        r8 = sc_out.shape[2]
        tile = (jax.lax.broadcasted_iota(jnp.int32, (r8, 128), 0) * 128
                + jax.lax.broadcasted_iota(jnp.int32, (r8, 128), 1))
    # positions past the block table are written nowhere
    pos = [jnp.where(pos0 + j < n_slots, pos0 + j, -1) for j in range(n_rows)]

    def layer(l, carry):
        for kv, side in ((0, sk_ref), (1, sv_ref)):
            for h in range(h_kv):
                cur = kv_in[l, 0, kv, h].astype(jnp.float32)
                for j in range(n_rows):
                    cur = jnp.where(slot == pos[j],
                                    side[l, 0, pl.ds(j * h_kv + h, 1), :], cur)
                kv_out[l, 0, kv, h] = cur.astype(kv_out.dtype)
        if quant:
            cur = sc_in[l, 0]
            for kv, side in ((0, ssk_ref), (1, ssv_ref)):
                for h in range(h_kv):
                    for j in range(n_rows):
                        t = pos[j] - page0
                        idx = jnp.where((t >= 0) & (t < block_size),
                                        (kv * h_kv + h) * block_size + t, -1)
                        cur = jnp.where(
                            tile == idx,
                            side[l, 0, pl.ds(j * h_kv + h, 1), :], cur)
            sc_out[l, 0] = cur
        return carry

    jax.lax.fori_loop(0, layers, layer, 0)


def paged_kv_row_write(kv_pages: jax.Array, side_k: jax.Array,
                       side_v: jax.Array, block_tables: jax.Array,
                       prefix: jax.Array, n_rows: int,
                       kv_scales: Optional[jax.Array] = None):
    """Write ``n_rows`` new tokens per sequence into EVERY layer's pages, in
    place: token ``j`` of sequence ``s`` (position ``prefix[s] + j``) goes to
    slot ``pos % bs`` of page ``block_tables[s, pos // bs]``, its ``H_kv`` K
    rows and ``H_kv`` V rows taken from rows ``j*H_kv + h`` of the side
    buffers. The cost follows the rows written, not the pages they fall in.

    kv_pages:     [L, NB, 2, H_kv, bs, D] — ALIASED: the returned pool
                  reuses the input buffer
    side_k/v:     [L, S, >= n_rows*H_kv, D]
    block_tables: [S, MB] int32     prefix: [S] int32
    kv_scales:    [L, NB, R8, 128] f32 scale tiles of an int8 pool (also
                  aliased): the rows quantize per token-head
                  (:func:`kv_quantize_rows`) and their scales land at the
                  tile offset ``kv*H_kv*bs + h*bs + slot``.

    Mosaic refuses a DMA of one row into a page (a slice of the slot
    dimension must be aligned to the pool's tiling), so the unit is the
    aligned group of slots one tile holds (16 of a bf16 pool): the group is
    read, the new rows replace theirs, the group is written back. One block
    carries that group for K and V of as many layers as fit a MiB, so a
    step moves ``S`` blocks per tile of layers — at Mistral-7B widths 32 x
    1 MiB for 32 rows x 16 layers, where whole pages were 1,024 x 512 KiB
    three times over. Positions past the block table are written nowhere.
    Two sequences whose tables hold the same page (the engine's pad rows,
    all at its scratch page) leave either's rows there.

    Returns the pool, or ``(pool, kv_scales)``."""
    L, NB, two, Hkv, bs, D = kv_pages.shape
    S, MB = block_tables.shape
    assert two == 2 and side_k.shape[:2] == (L, S)
    quant = kv_scales is not None
    item = jnp.dtype(kv_pages.dtype).itemsize
    G = min(32 // item, bs)                     # the slots of one tile
    assert bs % G == 0
    n_groups = (n_rows + 2 * G - 2) // G        # groups n_rows can straddle
    rows_side = side_k.shape[2]
    # layers a block: as many as keep the pool's block and a step's side
    # rows (float32; values and, for an int8 pool, scales) under a MiB each
    layer_bytes = max(2 * Hkv * G * D * item,
                      (4 if quant else 2) * rows_side * max(D, 128) * 4)
    LT = max(t for t in range(1, L + 1)
             if L % t == 0 and (t == 1 or t * layer_bytes <= 1 << 20))

    def block(s, g, bt, pre):
        """(page, group of slots in it) of grid step (s, g)."""
        gg = _row_group(pre[s], g, n_rows, G, MB * bs)
        return bt[s, gg * G // bs], gg % (bs // G)

    def side_spec(width):
        return pl.BlockSpec((LT, 1, rows_side, width),
                            lambda lt, s, g, bt, pre: (lt, s, 0, 0))

    # single rows are read out of the side buffers: 32-bit rows, which a
    # kernel may slice anywhere (the values are exact in float32)
    sides = [side_k.astype(jnp.float32), side_v.astype(jnp.float32)]
    pools = [kv_pages]
    def pool_map(lt, s, g, bt, pre):
        page, slots = block(s, g, bt, pre)
        return (lt, page, 0, 0, slots, 0)

    pool_specs = [pl.BlockSpec((LT, 1, 2, Hkv, G, D), pool_map)]
    if quant:
        kq, ks = kv_quantize_rows(side_k)
        vq, vs = kv_quantize_rows(side_v)
        lanes = side_k.shape[:3] + (128,)
        sides = [kq.astype(jnp.float32), vq.astype(jnp.float32),
                 jnp.broadcast_to(ks[..., None], lanes),
                 jnp.broadcast_to(vs[..., None], lanes)]
        pools.append(kv_scales)
        pool_specs.append(pl.BlockSpec(
            (LT, 1) + kv_scales.shape[2:],
            lambda lt, s, g, bt, pre: (lt, block(s, g, bt, pre)[0], 0, 0)))
    first_pool = 2 + len(sides)                 # after the two prefetched
    call = pl.pallas_call(
        functools.partial(_kv_row_write_kernel, n_rows=n_rows, h_kv=Hkv,
                          group=G, block_size=bs, n_slots=MB * bs,
                          layers=LT, quant=quant),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(L // LT, S, n_groups),
            in_specs=[side_spec(x.shape[-1]) for x in sides] + pool_specs,
            out_specs=pool_specs),
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in pools],
        input_output_aliases={first_pool + i: i for i in range(len(pools))},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3),
        interpret=_backend.interpret(),
    )
    with jax.named_scope("paged_kv_row_write"):
        out = call(block_tables.astype(jnp.int32), prefix.astype(jnp.int32),
                   *sides, *pools)
    return tuple(out) if quant else out[0]


def kv_run_group(kv_pages: jax.Array, n: int) -> Optional[int]:
    """The slots one grid step of :func:`paged_kv_run_write` reads, merges and
    writes back for runs of ``n`` rows into ``kv_pages`` ``[.., 2, H_kv, bs,
    D]`` — or None where the kernel does not apply and the caller keeps its
    row scatter (a head width that is no whole number of lane tiles, a page
    smaller than the pool's tile, a run that is no whole number of tiles and
    not shorter than one either).

    The unit is the pool's tile of slots (16 of a bf16 pool: Mosaic moves no
    less of the slot dimension). A run shorter than a tile takes one tile a
    step; a longer one takes as many tiles as a page, the run and 4 MiB of
    float32 rows (the run with a group of its neighbours' rows either side,
    K and V) allow: the bytes are small either way, the steps are what cost."""
    *_, Hkv, bs, D = kv_pages.shape
    G = 32 // jnp.dtype(kv_pages.dtype).itemsize
    if D % 128 or bs % G or (n >= G and n % G):
        return None
    group = G
    while (n >= 2 * group and bs % (2 * group) == 0
           and 2 * Hkv * (n + 4 * group) * D * 4 <= 4 << 20):
        group *= 2
    return group


def _run_steps(n: int, group: int, aligned: bool) -> int:
    """Grid steps a run: the aligned groups of ``group`` slots that ``n``
    rows can straddle (``aligned``: from a multiple of ``min(n, group)``)."""
    return -(-n // group) if aligned else (n + 2 * group - 2) // group


class KvRunPlan(NamedTuple):
    """The grid of :func:`paged_kv_run_write`, a step an entry of each int32
    array: the run whose rows the step lays, the page (before the layer's
    offset) and the group of slots in it that it reads and writes back, the
    position of that group's first slot, the positions ``[lo, hi)`` it may
    write, and where the group's rows start in the run's rows as the kernel
    is handed them. The arrays are padded to whole tiles of 128: a scalar
    operand shorter than a tile that XLA keeps in VMEM reaches a kernel's
    SMEM wrong (the block step's buckets of 2 to 64 rows halted the core;
    PERF.md, PR 62). ``n``, ``group`` and ``steps`` are the plan's statics:
    a run's rows, the slots a step takes, the steps in all."""
    n: int
    group: int
    steps: int
    run: jax.Array
    page: jax.Array
    slot: jax.Array
    base: jax.Array
    lo: jax.Array
    hi: jax.Array
    off: jax.Array


def kv_run_plan(block_tables: jax.Array, pos0: jax.Array, count: jax.Array,
                n: int, group: int, bs: int,
                aligned: bool = False) -> KvRunPlan:
    """Plan the writer's steps for runs of ``n`` rows over pages of ``bs``
    slots: run ``r``'s ``count[r]`` rows go to positions from ``pos0[r]``
    through ``block_tables[r]``. Run ``r``'s steps walk the groups of
    ``group`` slots its positions fall in and stay at the last one; the
    steps of a run that holds no row (a padding slot) repeat the step before
    them (those in front of the first run that holds rows, its first step).
    A repeated step fetches and writes nothing — so an empty run touches no
    page at all, where its table's page might be another run's — and lays
    what the step it repeats laid.

    Runs ``r`` and ``r + 1`` that are one sequence's consecutive chunks
    (``n >= group``; the same page, ``pos0`` apart by ``n``, the first one
    whole) lie consecutively in ROW order too, so where one's last group is
    the other's first, either step lays BOTH runs' rows (``[lo, hi)``
    reaches over the neighbour's): the block is fetched once for the two
    steps, and each must leave it whole. Runs shorter than a group share no
    group with another run of the call but on a page nobody reads. The plan
    depends on no layer: callers make it once, outside their scan."""
    R, MB = block_tables.shape
    steps = _run_steps(n, group, aligned)
    tables = block_tables.astype(jnp.int32)
    pos0, count = pos0.astype(jnp.int32), count.astype(jnp.int32)
    g = jnp.arange(steps, dtype=jnp.int32)[None]
    last = (pos0 + jnp.maximum(count, 1) - 1) // group
    grp = jnp.minimum((pos0 // group)[:, None] + g, last[:, None])  # [R, st]
    at = jnp.arange(R * steps, dtype=jnp.int32)
    held = jnp.where(jnp.repeat(count > 0, steps), at, -1)
    seen = jax.lax.cummax(held)
    src = jnp.where(seen >= 0, seen, jnp.argmax(held >= 0).astype(jnp.int32))
    run, base = src // steps, grp.reshape(-1)[src] * group
    page_at = jnp.minimum(base // bs, MB - 1)
    page = tables[run, page_at]
    lo = pos0[run]
    hi = lo + count[run]
    if n >= group:
        before, after = jnp.maximum(run - 1, 0), jnp.minimum(run + 1, R - 1)
        lo = jnp.where((before < run) & (pos0[before] + n == pos0[run])
                       & (count[before] == n)
                       & (tables[before, page_at] == page), pos0[before], lo)
        hi = jnp.where((after > run) & (pos0[after] == pos0[run] + n)
                       & (count[run] == n)
                       & (tables[after, page_at] == page),
                       pos0[after] + count[after], hi)
    arrays = (run, page, base % bs // group, base, lo, hi,
              base - pos0[run] + group)
    return KvRunPlan(n, group, R * steps, *(
        jnp.pad(a, (0, -a.shape[0] % 128)) for a in arrays))


def _kv_run_write_kernel(run_ref, page_ref, slot_ref, base_ref, lo_ref, hi_ref,
                         off_ref, k_ref, v_ref, kv_in, kv_out, *, n, h_kv,
                         group):
    """One grid step = one group of slots of one run: the group's K and V
    rows come in, the rows of the step's positions ``[lo, hi)`` that fall in
    it are laid over theirs, the group goes back.

    ``n >= group`` (a prompt chunk): ``k_ref`` / ``v_ref`` hold the run's
    rows head-major with ``group`` rows of its neighbours in ROW order either
    side, and the rows of the group are one slice of them at a dynamic
    offset. ``n < group`` (a block of a few positions): the run's rows are
    picked one by one, as ``_kv_row_write_kernel`` does."""
    del run_ref, page_ref, slot_ref
    i = pl.program_id(0)
    base, lo, hi = base_ref[i], lo_ref[i], hi_ref[i]
    D = kv_out.shape[-1]
    if n < group:
        pos = base + jax.lax.broadcasted_iota(jnp.int32, (group, D), 0)
        rows = [jnp.where(lo + j < hi, lo + j, -1) for j in range(n)]
        for kv, src in ((0, k_ref), (1, v_ref)):
            for h in range(h_kv):
                cur = kv_in[0, kv, h].astype(jnp.float32)
                for j in range(n):
                    cur = jnp.where(pos == rows[j],
                                    src[0, pl.ds(j * h_kv + h, 1), :], cur)
                kv_out[0, kv, h] = cur.astype(kv_out.dtype)
        return
    # a slice at a dynamic row is taken of rows ONE lane tile wide
    pos = base + jax.lax.broadcasted_iota(jnp.int32, (group, 128), 0)
    take = (pos >= lo) & (pos < hi)
    off = off_ref[i]                 # in [1, n + group - 1]
    for kv, src in ((0, k_ref), (1, v_ref)):
        for h in range(h_kv):
            for c in range(D // 128):
                lanes = pl.ds(c * 128, 128)
                new = src[0, h * (D // 128) + c, pl.ds(off, group), :]
                cur = kv_in[0, kv, h, :, lanes].astype(jnp.float32)
                kv_out[0, kv, h, :, lanes] = jnp.where(
                    take, new, cur).astype(kv_out.dtype)


def _run_rows_with_neighbours(x, R: int, n: int, group: int):
    """``x`` ``[R * n, H_kv, D]`` -> float32 ``[R, H_kv * D / 128, n + 2 *
    group, 128]``: run ``r``'s rows head-major — a head's lane tiles as rows
    of their own, ``h * D / 128 + c`` — with the ``group`` rows before and
    after it in ROW order (zeros past either end). Static slices only."""
    T = R * n
    length = n + 2 * group
    pieces = -(-length // n)
    xt = jnp.moveaxis(x.astype(jnp.float32).reshape(T, -1, 128), 1, 0)
    xt = jnp.pad(xt, ((0, 0), (group, (pieces - 1) * n - group), (0, 0)))
    Hkv, _, D = xt.shape
    parts = [xt[:, j * n:j * n + T].reshape(Hkv, R, n, D)[
        :, :, :min(n, length - j * n)] for j in range(pieces)]
    return jnp.moveaxis(jnp.concatenate(parts, axis=2), 1, 0)


def paged_kv_run_write(kv_pages: jax.Array, k: jax.Array, v: jax.Array,
                       plan: KvRunPlan, page0) -> jax.Array:
    """Write RUNS of new K/V rows into the pages, in place: rows ``r * n +
    i`` of ``k`` / ``v``, ``i < count[r]``, are the tokens at positions
    ``pos0[r] + i`` of the sequence with ``block_tables[r]`` — a prompt
    chunk's rows in a paged pass, a block's in a block step — as
    :func:`kv_run_plan` laid them out. The cost follows the groups of slots
    the runs fill, not the rows: XLA's scatter prices every (row, head)
    index (about 70 ns each on a v5e, whatever the bytes; PERF.md, PR 62).

    kv_pages: [NB, 2, H_kv, bs, D] — ALIASED: the returned pool reuses the
              input buffer
    k, v:     [R * n, H_kv, D]
    plan:     :func:`kv_run_plan` of the runs, at ``group`` =
              :func:`kv_run_group` of the pool and ``n``
    page0:    int32 scalar added to the plan's pages: ``l * NB`` for layer
              ``l``'s pages of a pool of several layers

    Every slot outside a run keeps what it held (a page the ring reuses may
    hold live rows beside the run's). A run of no rows touches no page. The
    positions are inside the table (the scheduler's reservation). Runs write
    different slots, except on a page nobody reads (the engine's pad rows,
    all at its scratch page, leave any of theirs there)."""
    NB, two, Hkv, bs, D = kv_pages.shape
    n, group, steps, *arrays = plan
    R = k.shape[0] // n
    assert two == 2 and k.shape == v.shape == (R * n, Hkv, D)
    assert bs % group == 0 and (n < group or n % group == 0), (group, n, bs)
    if n < group:
        rows = (n * Hkv, D)
        sides = [x.astype(jnp.float32).reshape(R, *rows) for x in (k, v)]
        side_spec = pl.BlockSpec(
            (1, *rows), lambda i, run, *_: (run[i], 0, 0))
    else:
        rows = (Hkv * D // 128, n + 2 * group, 128)
        sides = [_run_rows_with_neighbours(x, R, n, group) for x in (k, v)]
        side_spec = pl.BlockSpec(
            (1, *rows), lambda i, run, *_: (run[i], 0, 0, 0))
    pool_spec = pl.BlockSpec(
        (1, 2, Hkv, group, D),
        lambda i, run, page, slot, *_: (page[i], 0, 0, slot[i], 0))
    call = pl.pallas_call(
        functools.partial(_kv_run_write_kernel, n=n, h_kv=Hkv, group=group),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7, grid=(steps,),
            in_specs=[side_spec, side_spec, pool_spec],
            out_specs=pool_spec),
        out_shape=jax.ShapeDtypeStruct(kv_pages.shape, kv_pages.dtype),
        input_output_aliases={9: 0},
        compiler_params=pltpu.CompilerParams(
            # a group two runs share is handed from step to step
            dimension_semantics=("arbitrary",)),
        interpret=_backend.interpret(),
    )
    arrays[1] = plan.page + page0
    with jax.named_scope("paged_kv_run_write"):
        return call(*arrays, *sides, kv_pages)


def _decode_kernel_smalld(bt_ref, cl_ref, q_ref, kv_ref, o_ref,
                          acc_sc, m_sc, l_sc, *, scale, block_size,
                          max_blocks, h_kv, groups, window=None,
                          alibi=False):
    """BlockSpec-pipelined fallback for head dims the manual-DMA path can't
    carry (Mosaic requires DMA lane extents aligned to 128; D=64-class
    models land here). One grid step = (sequence, page), pages pulled by the
    Pallas pipeline via the scalar-prefetched block table, per-kv-head dots
    — the original kernel design, adequate off the serving hot path."""
    s, i = pl.program_id(0), pl.program_id(1)
    bs = block_size
    H = h_kv * groups

    @pl.when(i == 0)
    def _():
        m_sc[:] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc_sc[:] = jnp.zeros_like(acc_sc)

    ctx = cl_ref[s]
    lo = jnp.int32(0) if window is None else jnp.maximum(ctx - window, 0)

    @pl.when(jnp.logical_and(i * bs < ctx, (i + 1) * bs > lo))
    def _():
        q = q_ref[0].astype(jnp.float32)                       # [H, D]
        # one head group's [G, bs] mask serves every group (it depends on
        # the token column only). Built at that shape, never sliced: the
        # TPU compiler ABORTS the process (no Python exception) on a row
        # slice of an i1 vector past the first 8-sublane tile, which an
        # [H, bs] mask sliced per group hits at H > 8.
        tok = i * bs + jax.lax.broadcasted_iota(jnp.int32, (groups, bs), 1)
        mh = jnp.logical_and(tok < ctx, tok >= lo)
        for h in range(h_kv):
            rows = slice(h * groups, (h + 1) * groups)
            qh = q[rows, :]                                    # [G, D]
            kh = kv_ref[0, 0, h].astype(jnp.float32)           # [bs, D]
            vh = kv_ref[0, 1, h].astype(jnp.float32)
            sc = jax.lax.dot_general(qh, kh, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32) * scale
            if alibi:
                gof = jax.lax.broadcasted_iota(jnp.float32, (groups, bs), 0)
                kpf = i * bs + jax.lax.broadcasted_iota(
                    jnp.float32, (groups, bs), 1)
                sc = sc + _alibi_slope(h * groups + gof, H) * kpf
            sc = jnp.where(mh, sc, NEG_INF)
            m_prev = m_sc[rows, 0:1]
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
            p = jnp.where(mh, jnp.exp(sc - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_sc[rows, 0:1] = l_sc[rows, 0:1] * alpha \
                + jnp.sum(p, axis=1, keepdims=True)
            m_sc[rows, 0:1] = m_new
            acc_sc[rows, :] = acc_sc[rows, :] * alpha + jax.lax.dot_general(
                p, vh, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(i == max_blocks - 1)
    def _():
        l = l_sc[:, 0:1]
        safe_l = jnp.where(l > 0.0, l, 1.0)
        o_ref[0] = (acc_sc[:] / safe_l).astype(o_ref.dtype)


def _paged_decode_smalld(q, kv_pages, block_tables, ctx_lens, scale,
                         window=None, alibi=False):
    S, H, D = q.shape
    NB, _, Hkv, bs, _ = kv_pages.shape
    G = H // Hkv
    MB = block_tables.shape[1]
    kernel = functools.partial(_decode_kernel_smalld, scale=scale,
                               block_size=bs, max_blocks=MB, h_kv=Hkv,
                               groups=G, window=window, alibi=alibi)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, MB),
        in_specs=[
            pl.BlockSpec((1, H, D), lambda s, i, bt, cl: (s, 0, 0)),
            pl.BlockSpec((1, 2, Hkv, bs, D),
                         lambda s, i, bt, cl: (bt[s, i], 0, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, D), lambda s, i, bt, cl: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, D), jnp.float32),
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, 128), jnp.float32),
        ],
    )
    call = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, H, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_backend.interpret(),
    )
    with jax.named_scope("paged_decode_smalld"):
        return call(block_tables.astype(jnp.int32), ctx_lens.astype(jnp.int32),
                    q, kv_pages)


def paged_decode_attention(q: jax.Array,
                           kv_pages: jax.Array,
                           block_tables: jax.Array,
                           ctx_lens: jax.Array,
                           softmax_scale: Optional[float] = None,
                           window: Optional[int] = None,
                           with_lse: bool = False,
                           kv_scales: Optional[jax.Array] = None,
                           alibi: bool = False):
    """Single-token-per-sequence attention over a paged KV cache.

    q:            [S, H, D]        one query token per sequence
    kv_pages:     [NB, 2, H_kv, bs, D] combined head-major pages (K=0, V=1)
    block_tables: [S, MB] int32    physical page ids per sequence (0-padded)
    ctx_lens:     [S] int32        tokens visible per sequence (incl. current)
    window:       optional static sliding-window span (Mistral-style): only
                  tokens >= ctx - window are attended or read.
    with_lse:     also return lse [S, H] f32 (m + log l; NEG_INF for empty
                  rows) — the hook for merging with a second attention piece.
    kv_scales:    [NB, 2, H_kv, bs] f32 — int8 pages (see module docstring).

    Returns [S, H, D] (plus lse when requested). Rows whose ctx_len is 0
    return zeros.
    """
    D = q.shape[-1]
    scale = softmax_scale if softmax_scale is not None else 1.0 / (D ** 0.5)
    if D % 128 != 0:   # manual-DMA lane-alignment limit — see _paged_decode_smalld
        assert not with_lse, "with_lse needs the manual-DMA path (D % 128 == 0)"
        assert kv_scales is None, \
            "int8 pages need the manual-DMA path (D % 128 == 0)"
        return _paged_decode_smalld(q, kv_pages, block_tables,
                                    ctx_lens, scale, window=window,
                                    alibi=alibi)
    with jax.named_scope("paged_decode"):
        res = _decode_walk_call(q, kv_pages, block_tables, ctx_lens,
                                scale=scale, window=window,
                                kv_scales=kv_scales, alibi=alibi,
                                with_lse=with_lse)
    if with_lse:
        return res[0], res[1][:, :, 0]
    return res


def _decode_step_kernel(bt_ref, cl_ref, q_ref, knew_ref, vnew_ref,
                        kv_hbm, o_ref, kvout_ref,
                        kv_buf, sems, acc_sc, m_sc, l_sc, **kw):
    """Decode STEP attention: the shared body in step mode — paged flash over
    the PRIOR context (pages hold tokens [0, ctx-1)) + the current token's
    term inline from the k_new/v_new operands; the pool passes through
    untouched, aliased input -> output.

    Why this shape: the current token's K/V must both enter attention AND
    land in the pages. Expressing the page write as an XLA scatter BEFORE an
    opaque kernel that reads the pool made XLA's copy-insertion clone the
    (hundreds of MB) pool around the custom call — measured 3x decode
    slowdown; an in-kernel DMA write is blocked by DMA tiling at arbitrary
    sublane offsets. So: the kernel needs only tokens < ctx-1 from the pages
    (the current token rides registers), ``input_output_aliases`` declares
    the pool linear through the call, and the caller scatters the new rows
    into the returned pool AFTER — every link in the carry chain is a
    declared alias or a canonical in-place scatter, so the pool is never
    copied.

    ``cl_ref[s]`` counts tokens INCLUDING the current one."""
    del kvout_ref  # aliased pass-through; written by the caller
    _decode_body(bt_ref, cl_ref, q_ref, knew_ref, vnew_ref, kv_hbm,
                 o_ref, kv_buf, sems, acc_sc, m_sc, l_sc, **kw)


def _decode_step_kernel_quant(bt_ref, cl_ref, q_ref, knew_ref, vnew_ref,
                              kv_hbm, sc_hbm,
                              o_ref, kvout_ref,
                              kv_buf, sc_buf, sems,
                              acc_sc, m_sc, l_sc, **kw):
    # value pool aliases through (caller-side scatter); scale TILES are
    # read-only inputs — they are a fresh pad/reshape copy of the at-rest
    # scale pool, so the caller's scale scatter needs no aliasing or
    # ordering against this kernel
    del kvout_ref
    _decode_body(bt_ref, cl_ref, q_ref, knew_ref, vnew_ref, kv_hbm,
                 o_ref, kv_buf, sems, acc_sc, m_sc, l_sc,
                 sc_hbm=sc_hbm, sc_buf=sc_buf, **kw)


def _step_write_rows(block_tables, ctx_lens, NB, Hkv, bs, S):
    """Flat head-major row destinations of the current token's K and V rows
    in the combined pool [NB*2*Hkv*bs, D]: K row ((page*2 + 0)*Hkv + h)*bs
    + slot, V row ((page*2 + 1)*Hkv + h)*bs + slot; ctx 0 -> OOB drop."""
    pv = jnp.maximum(ctx_lens - 1, 0)
    page_w = block_tables[jnp.arange(S), pv // bs]
    h = jnp.arange(Hkv)[None, :]
    slot = (pv % bs)[:, None]
    k_rows = ((page_w[:, None] * 2 + 0) * Hkv + h) * bs + slot   # [S, Hkv]
    v_rows = ((page_w[:, None] * 2 + 1) * Hkv + h) * bs + slot
    oob = NB * 2 * Hkv * bs
    valid = ctx_lens[:, None] > 0
    k_rows = jnp.where(valid, k_rows, oob)
    v_rows = jnp.where(valid, v_rows, oob)
    return jnp.concatenate([k_rows.reshape(-1), v_rows.reshape(-1)])


def paged_decode_attention_step(q: jax.Array,
                                k_new: jax.Array,
                                v_new: jax.Array,
                                kv_pages: jax.Array,
                                block_tables: jax.Array,
                                ctx_lens: jax.Array,
                                softmax_scale: Optional[float] = None,
                                window: Optional[int] = None,
                                kv_scales: Optional[jax.Array] = None,
                                alibi: bool = False):
    """One fused decode step per sequence: write ``k_new/v_new`` (the current
    token's K/V, position ``ctx_lens - 1``) into the paged cache AND return
    attention over the full context including the current token (with
    ``window``, over the trailing ``window`` tokens only).

    q:            [S, H, D]       k_new/v_new: [S, H_kv, D]
    kv_pages:     [NB, 2, H_kv, bs, D] — ALIASED: the returned pool reuses
                  the input buffer (donate it at the jit boundary)
    block_tables: [S, MB] int32   ctx_lens: [S] int32 (INCLUDING current)
    kv_scales:    [NB, 2, H_kv, bs] f32 — int8 pages; the new token's rows
                  quantize and scatter into the returned scale pool.

    Returns ``(out [S, H, D], kv_pages)`` — with scales,
    ``(out, kv_pages, kv_scales)``. ctx_lens == 0 rows write nothing and
    return zeros.
    """
    S, H, D = q.shape
    NB, two, Hkv, bs, Dk = kv_pages.shape
    assert two == 2 and Dk == D and H % Hkv == 0
    G = H // Hkv
    MB = block_tables.shape[1]
    scale = softmax_scale if softmax_scale is not None else 1.0 / (D ** 0.5)
    quant = kv_scales is not None
    if quant:
        assert D % 128 == 0 and (Hkv * bs) % 128 == 0
    if D % 128 != 0:
        # small-D fallback: scatter first (pools here are small), then the
        # BlockSpec-pipelined kernel over the full context
        rows = _step_write_rows(block_tables, ctx_lens, NB, Hkv, bs, S)
        new = jnp.concatenate([k_new.reshape(S * Hkv, D),
                               v_new.reshape(S * Hkv, D)])
        kvf = kv_pages.reshape(NB * 2 * Hkv * bs, D).at[rows].set(
            new.astype(kv_pages.dtype), mode="drop").reshape(kv_pages.shape)
        out = _paged_decode_smalld(q, kvf, block_tables, ctx_lens, scale,
                                   window=window, alibi=alibi)
        return out, kvf
    P = _pick_pages_per_chunk(bs, Hkv, D, jnp.dtype(kv_pages.dtype).itemsize,
                              MB, flash_heads=H,
                              scale_tile_rows=_scale_tile_rows(Hkv, bs)
                              if quant else 0)
    NC = -(-MB // P)
    assert (bs * Hkv) % 8 == 0

    kernel = functools.partial(
        _decode_step_kernel_quant if quant else _decode_step_kernel,
        scale=scale, block_size=bs, pages_per_chunk=P,
        n_chunks=NC, max_blocks=MB, n_seqs=S, h_kv=Hkv, groups=G,
        window=window, alibi=alibi)
    flat = (NB, 2 * Hkv * bs, D)
    in_specs = [
        pl.BlockSpec((1, H, D), lambda s, c, bt, cl: (s, 0, 0)),
        pl.BlockSpec((1, Hkv, D), lambda s, c, bt, cl: (s, 0, 0)),
        pl.BlockSpec((1, Hkv, D), lambda s, c, bt, cl: (s, 0, 0)),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    out_specs = [
        pl.BlockSpec((1, H, D), lambda s, c, bt, cl: (s, 0, 0)),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    out_shape = [jax.ShapeDtypeStruct((S, H, D), q.dtype),
                 jax.ShapeDtypeStruct(flat, kv_pages.dtype)]
    scratch = [pltpu.VMEM((2, P, 2 * Hkv * bs, D), kv_pages.dtype)]
    operands = [block_tables.astype(jnp.int32), ctx_lens.astype(jnp.int32),
                q, k_new, v_new, _kv_flat(kv_pages)]
    # call args: (bt, cl, q, k_new, v_new, kv_pool[, scale_tiles]) ->
    # the value pool aliases input -> output; scale tiles are a read-only
    # converted copy
    aliases = {5: 1}
    if quant:
        r8 = _scale_tile_rows(Hkv, bs)
        in_specs += [pl.BlockSpec(memory_space=pl.ANY)]
        scratch += [pltpu.VMEM((2, P, r8, 128), jnp.float32)]
        operands += [_scales_to_tiles(kv_scales)]
    scratch += [
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.VMEM((H, D), jnp.float32),
        pltpu.VMEM((H, 128), jnp.float32),
        pltpu.VMEM((H, 128), jnp.float32),
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, NC),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    call = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_backend.interpret(),
    )
    with jax.named_scope("paged_decode_step"):
        res = call(*operands)
    out, kvf = res[0], res[1]
    # the write happens HERE, after the kernel: a canonical in-place scatter
    # on the aliased-through pool (see _decode_step_kernel docstring)
    rows = _step_write_rows(block_tables, ctx_lens, NB, Hkv, bs, S)
    if quant:
        kq, ks_new = kv_quantize_rows(k_new)                   # [S,Hkv,D]/[S,Hkv]
        vq, vs_new = kv_quantize_rows(v_new)
        new = jnp.concatenate([kq.reshape(S * Hkv, D),
                               vq.reshape(S * Hkv, D)])
        kvf = kvf.reshape(NB * 2 * Hkv * bs, D).at[rows].set(
            new, mode="drop")
        # scale scatter targets the AT-REST pool in its own layout (the
        # kernel read tiles, so this is an ordinary in-place scatter)
        news = jnp.concatenate([ks_new.reshape(-1), vs_new.reshape(-1)])
        if kv_scales.ndim == 3:                # tiled at rest [NB, R8, 128]
            r8 = _scale_tile_rows(Hkv, bs)
            hb2 = 2 * Hkv * bs
            sdest = (rows // hb2) * (r8 * 128) + rows % hb2
            scf = kv_scales.reshape(NB * r8 * 128).at[sdest].set(
                news, mode="drop")
            return (out, kvf.reshape(NB, 2, Hkv, bs, D),
                    scf.reshape(NB, r8, 128))
        scf = kv_scales.reshape(NB * 2 * Hkv * bs).at[rows].set(
            news, mode="drop")
        return (out, kvf.reshape(NB, 2, Hkv, bs, D),
                scf.reshape(NB, 2, Hkv, bs))
    new = jnp.concatenate([k_new.reshape(S * Hkv, D),
                           v_new.reshape(S * Hkv, D)])
    kvf = kvf.reshape(NB * 2 * Hkv * bs, D).at[rows].set(
        new.astype(kvf.dtype), mode="drop")
    return (out, kvf.reshape(NB, 2, Hkv, bs, D))


def paged_chunk_attention(q: jax.Array,
                          kv_pages: jax.Array,
                          block_table: jax.Array,
                          q_start,
                          ctx_len,
                          softmax_scale: Optional[float] = None,
                          block_q: int = 128,
                          window: Optional[int] = None,
                          alibi: bool = False) -> jax.Array:
    """Prompt-chunk (prefill) flash attention over one sequence's paged KV.

    The single-chunk convenience wrapper: one slot of
    :func:`paged_chunk_attention_batched` (ONE kernel body — a masking or
    softmax fix lands in both paths by construction).

    q:           [C, H, D]
    kv_pages:    [NB, 2, H_kv, bs, D] (combined head-major pages)
    block_table: [MB] int32
    q_start:     int32 — absolute position of q row 0
    ctx_len:     int32 — KV tokens visible in total (= q_start + C for prefill)

    Rows past the real chunk length are computed but meaningless (the caller
    ignores them); with ctx_len == 0 the output is zeros.
    """
    return paged_chunk_attention_batched(
        q[None], kv_pages, jnp.asarray(block_table)[None],
        jnp.asarray(q_start, jnp.int32)[None],
        jnp.asarray(ctx_len, jnp.int32)[None],
        softmax_scale=softmax_scale, block_q=block_q, window=window,
        alibi=alibi)[0]





#: the most pages a grid step of the chunk kernel attends, whatever the bytes
#: allow: a step issues and awaits its page copies one by one, unrolled (the
#: body itself is traced once, over the concatenated pages)
MAX_PAGES_PER_CHUNK_STEP = 8

#: what a chunk step's blocks, scratch and score arrays may take of VMEM
#: (the compiler's default keeps a kernel to 16 MiB of a v5e's 128)
_CHUNK_VMEM_LIMIT = 64 * 1024 * 1024


def _pick_chunk_pages(bs: int, h_kv: int, d: int, esize: int, rows: int,
                      max_blocks: int, scale_tile_rows: int = 0) -> int:
    """Pages a step of the chunk kernel attends. A KV head's step pays its
    row statistics — two lane reductions and the ``m``/``l``/``alpha``
    columns, a cost by ``rows`` alone, 3.6 us at 1,024 rows on a v5e — beside
    products that grow with the keys and the head's width, so a step takes
    the keys that make the products the larger part: ``P * bs * d`` of 128 K
    (512 keys at a width of 256, 1,024 at 128; PERF.md, PR 49). No more than
    ``MAX_PAGES_PER_CHUNK_STEP`` or the table's pages, and no more than keep
    the two slots of K+V pages (and int8 scale tiles) and the score arrays —
    one KV head's ``[rows, P * bs]`` scores and ``p`` in float32 and ``p``
    again as the MXU takes it — within 16 MB of VMEM."""
    per_page = 2 * 2 * h_kv * bs * d * esize + rows * bs * 10
    if scale_tile_rows:
        per_page += 2 * scale_tile_rows * 128 * 4
    return max(1, min(max_blocks, -(-128 * 1024 // (bs * d)),
                      (16 * 1024 * 1024) // per_page,
                      MAX_PAGES_PER_CHUNK_STEP))


def _chunk_scale_row(sc_buf, slot, pages, flat0, bs):
    """One head's per-token dequant scales over a step's pages as a ``[1, P *
    bs]`` row, read from the step's scale tiles ``sc_buf[slot]`` ``[P, R8,
    128]``; ``flat0`` (= kv*Hkv*bs + h*bs, ``h`` traced) is the head's FLAT
    index in a tile. Handles bs that is not itself a multiple of 128: the
    engine gate requires (Hkv*bs) % 128 == 0, so a head's span either covers
    whole lane rows (bs >= 128) or shares one lane row with its neighbours at
    a 128-aligned base (bs < 128), in which case the span is sliced out of
    that row."""
    row, lane0 = flat0 // 128, flat0 % 128
    pieces = []
    for j in range(pages):
        if bs % 128 == 0:
            pieces += [sc_buf[slot, j, pl.ds(row + t0, 1), :]
                       for t0 in range(bs // 128)]
        else:
            pieces.append(sc_buf[slot, j, pl.ds(row, 1), pl.ds(lane0, bs)])
    return jnp.concatenate(pieces, axis=1) if len(pieces) > 1 else pieces[0]


def _chunk_group_inner(k0, T, row0, bq, ctx, window, causal_block=1):
    """Is every key of the group ``[k0, k0 + T)`` visible to every row of
    the q-block ``[row0, row0 + bq)`` — below its first row (with
    ``causal_block`` B > 1: the last row of the first row's block of B
    positions), inside ``ctx`` and inside its last row's window? Such a
    group builds no mask."""
    first = row0 if causal_block == 1 else row0 | (causal_block - 1)
    inner = (k0 + T - 1 <= first) & (k0 + T <= ctx)
    if window is not None:
        inner = inner & (k0 > row0 + bq - 1 - window)
    return inner


def _chunk_kernel_batched(bt_ref, meta_ref, q_ref, kv_hbm, o_ref,
                          q_sc, kv_buf, sems, acc_sc, m_sc, l_sc, *, scale,
                          block_size, block_q, pages, max_blocks, h_kv,
                          groups, window=None, sc_hbm=None, sc_buf=None,
                          alibi=False, causal_block=1):
    """Grid (slot, q-block); each slot is an independent prompt chunk with
    its own block table and (q_start, ctx) row in ``meta_ref``. A step walks
    the GROUPS of ``pages`` consecutive pages that hold a key some row of the
    q-block sees — from the window's first page (0 without one) to the
    diagonal or the context's end, so a table entry the sequence does not
    use costs nothing — through a two-slot pipeline of page copies, and
    attends a whole group at once. Slot padding (ctx 0) walks no group and
    writes zeros. With ``window``, row q_pos attends only k_pos > q_pos -
    window. ``sc_hbm``/``sc_buf`` (int8 pages): the pages' scale tiles,
    applied as score-column (K) and p-column (V) multipliers. With
    ``causal_block`` B > 1 (a power of two) a key is visible iff its BLOCK of
    B positions is not later than the row's — ``k_pos // B <= q_pos // B``,
    causal across blocks and two-way inside one: a row's causal limit is the
    last position of its block, ``q_pos | (B - 1)``, which the mask, the walk's
    last page and the no-mask test follow; B = 1 traces what it traced.

    The MXU takes the operands as they are stored: bfloat16 q against
    bfloat16 (or int8, widened exactly) pages is one pass to float32; any
    float32 operand keeps float32 products. q is laid out for the products
    (``[Hkv, bq * G, D]``) once a step. A group wholly below the q-block's
    first row, inside ``ctx`` and inside the last row's window builds no
    mask."""
    sl, iq = pl.program_id(0), pl.program_id(1)
    bq, G, bs, P = block_q, groups, block_size, pages
    T, R = P * bs, bq * G
    quant = sc_hbm is not None
    q0 = meta_ref[sl, 0]
    ctx = jnp.minimum(meta_ref[sl, 1], max_blocks * bs)   # the table's keys
    row0 = q0 + iq * bq                      # the q-block's first position
    # keys [lo, hi) are visible to some row of this q-block
    hi = jnp.minimum(ctx, row0 + bq if causal_block == 1
                     else ((row0 + bq - 1) | (causal_block - 1)) + 1)
    lo = jnp.int32(0) if window is None \
        else jnp.maximum(row0 - window + 1, 0)
    g_lo = jax.lax.div(lo, T)
    g_hi = jax.lax.div(hi + (T - 1), T)
    mxu = q_sc.dtype

    m_sc[:] = jnp.full_like(m_sc, NEG_INF)
    l_sc[:] = jnp.zeros_like(l_sc)
    acc_sc[:] = jnp.zeros_like(acc_sc)
    qf = q_ref[0].astype(jnp.float32)                          # [bq, H, D]
    for h in range(h_kv):
        q_sc[h] = qf[:, h * G:(h + 1) * G, :].reshape(R, -1).astype(mxu)

    def copies(g, slot):
        """A group's page copies, built identically at start and wait. A
        page past the table's end repeats its last entry (masked)."""
        cps = []
        for j in range(P):
            page = bt_ref[sl, jnp.minimum(g * P + j, max_blocks - 1)]
            cps.append(pltpu.make_async_copy(
                kv_hbm.at[page], kv_buf.at[slot, j], sems.at[slot]))
            if quant:
                cps.append(pltpu.make_async_copy(
                    sc_hbm.at[page], sc_buf.at[slot, j], sems.at[slot]))
        return cps

    def attend(slot, k0, masked):
        if masked:
            # row r of a head's R is q row r // G, at q_pos = row0 + r // G;
            # for an integer a, a <= r // G is a * G <= r: no division, and
            # the mask is built as the scores lie, [R, T]
            r = jax.lax.broadcasted_iota(jnp.int32, (R, T), 0)
            k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, (R, T), 1)
            # (a block's keys all count as its first: k_pos & -B)
            k_lim = k_pos if causal_block == 1 else k_pos & -causal_block
            mask = ((k_lim - row0) * G <= r) & (k_pos < ctx)
            if window is not None:
                mask = mask & (r < (k_pos - row0 + window) * G)

        def head(h, carry):
            # slice the REF: a head's K and V rows of every page of the group
            kh = kv_buf[slot, :, 0, h].reshape(T, -1)
            vh = kv_buf[slot, :, 1, h].reshape(T, -1)
            sc = jax.lax.dot_general(q_sc[h], kh.astype(mxu),
                                     (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32) * scale
            if quant:
                # K scales for head h start at flat index h*bs in a tile
                sc = sc * _chunk_scale_row(sc_buf, slot, P, h * bs, bs)
            if alibi:
                # rows of this slice are (q-row, g) for q heads h*G + g;
                # built in (bq, G, T), then merged to the scores' [R, T]
                gof = jax.lax.broadcasted_iota(jnp.int32, (bq, G, T), 1)
                slope = _alibi_slope((h * G + gof).astype(jnp.float32),
                                     h_kv * G)
                kpf = (k0 + jax.lax.broadcasted_iota(
                    jnp.int32, (bq, G, T), 2)).astype(jnp.float32)
                sc = sc + (slope * kpf).reshape(R, T)
            _decode_update(
                sc, mask if masked else None, vh.astype(mxu), h, m_sc, l_sc,
                acc_sc, v_scale=_chunk_scale_row(
                    sc_buf, slot, P, (h_kv + h) * bs, bs) if quant else None)
            return carry

        # ONE head's body, looped: unrolled, eight KV heads of two branches
        # compiled for half a minute
        jax.lax.fori_loop(0, h_kv, head, 0)

    @pl.when(g_lo < g_hi)
    def _():
        for cp in copies(g_lo, 0):
            cp.start()

    def group(g, carry):
        slot = jax.lax.rem(g - g_lo, 2)

        @pl.when(g + 1 < g_hi)
        def _():
            for cp in copies(g + 1, 1 - slot):
                cp.start()

        for cp in copies(g, slot):
            cp.wait()
        k0 = g * T
        inner = _chunk_group_inner(k0, T, row0, bq, ctx, window,
                                   causal_block)

        @pl.when(inner)
        def _():
            attend(slot, k0, masked=False)

        @pl.when(jnp.logical_not(inner))
        def _():
            attend(slot, k0, masked=True)

        return carry

    jax.lax.fori_loop(g_lo, g_hi, group, 0)

    l = l_sc[:, :, 0:1]
    safe_l = jnp.where(l > 0.0, l, 1.0)
    o = (acc_sc[:] / safe_l).reshape(h_kv, bq, G, -1)           # [Hkv, R, D]
    o_ref[0] = jnp.moveaxis(o, 0, 1).reshape(bq, h_kv * G,
                                             -1).astype(o_ref.dtype)


def _chunk_kernel_batched_quant(bt_ref, meta_ref, q_ref, kv_hbm, sc_hbm,
                                o_ref, q_sc, kv_buf, sc_buf, sems, *rest,
                                **kw):
    _chunk_kernel_batched(bt_ref, meta_ref, q_ref, kv_hbm, o_ref, q_sc,
                          kv_buf, sems, *rest, sc_hbm=sc_hbm, sc_buf=sc_buf,
                          **kw)


def paged_chunk_attention_batched(q: jax.Array,
                                  kv_pages: jax.Array,
                                  block_tables: jax.Array,
                                  q_starts: jax.Array,
                                  ctx_lens: jax.Array,
                                  softmax_scale: Optional[float] = None,
                                  block_q: int = 128,
                                  window: Optional[int] = None,
                                  kv_scales: Optional[jax.Array] = None,
                                  alibi: bool = False,
                                  causal_block: int = 1) -> jax.Array:
    """Prefill flash attention for SEVERAL prompt chunks in one kernel.

    Multi-chunk SplitFuse: a pass that carries one chunk per pallas call
    serialises prefill on per-call fixed costs; with the slot in the grid,
    N prompts' chunks prefill in one launch.

    q:            [NC, Cs, H, D]  — slot-major chunk rows
    kv_pages:     [NB, 2, H_kv, bs, D] (combined head-major pages)
    block_tables: [NC, MB] int32
    q_starts:     [NC] int32 — absolute position of each slot's row 0
    ctx_lens:     [NC] int32 — KV tokens visible per slot (0 = empty slot)
    kv_scales:    [NB, 2, H_kv, bs] f32 — int8 pages (dequant in-kernel)
    causal_block: B, a power of two — causal by BLOCKS of B positions (the
                  kernel's docstring); 1 is causal by position

    Returns [NC, Cs, H, D]; empty slots return zeros.
    """
    if causal_block < 1 or causal_block & (causal_block - 1):
        raise ValueError("causal_block must be a power of two (a row's causal "
                         f"limit is q_pos | (B - 1)), got {causal_block}")
    assert causal_block == 1 or window is None, \
        "a window beside the block rule is not wired"
    NC, Cs, H, D = q.shape
    NB, two, Hkv, bs, _ = kv_pages.shape
    assert two == 2 and H % Hkv == 0
    G = H // Hkv
    MB = block_tables.shape[1]
    scale = softmax_scale if softmax_scale is not None else 1.0 / (D ** 0.5)
    quant = kv_scales is not None
    bq = block_q
    while Cs % bq != 0:
        bq //= 2
    bq = max(bq, 1)
    nq = Cs // bq
    r8 = _scale_tile_rows(Hkv, bs) if quant else 0
    P = _pick_chunk_pages(bs, Hkv, D, kv_pages.dtype.itemsize, bq * G, MB,
                          r8)
    # what the MXU is handed: the operands as stored where both are
    # bfloat16 (int8 pages widen to it exactly), float32 otherwise
    mxu = jnp.bfloat16 if (q.dtype == jnp.bfloat16 and kv_pages.dtype in (
        jnp.bfloat16, jnp.int8)) else jnp.float32

    meta = jnp.stack([jnp.asarray(q_starts, jnp.int32),
                      jnp.asarray(ctx_lens, jnp.int32)], axis=1)   # [NC, 2]
    kernel = functools.partial(
        _chunk_kernel_batched_quant if quant else _chunk_kernel_batched,
        scale=scale, block_size=bs, block_q=bq, pages=P, max_blocks=MB,
        h_kv=Hkv, groups=G, window=window, alibi=alibi,
        causal_block=causal_block)
    in_specs = [
        pl.BlockSpec((1, bq, H, D), lambda sl, iq, bt, m: (sl, iq, 0, 0)),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    operands = [block_tables.astype(jnp.int32), meta, q, kv_pages]
    scratch = [pltpu.VMEM((Hkv, bq * G, D), mxu),
               pltpu.VMEM((2, P, 2, Hkv, bs, D), kv_pages.dtype)]
    if quant:
        assert (Hkv * bs) % 128 == 0
        in_specs += [pl.BlockSpec(memory_space=pl.ANY)]
        operands += [_scales_to_tiles(kv_scales)]
        scratch += [pltpu.VMEM((2, P, r8, 128), jnp.float32)]
    scratch += [
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.VMEM((Hkv, bq * G, D), jnp.float32),
        pltpu.VMEM((Hkv, bq * G, 128), jnp.float32),
        pltpu.VMEM((Hkv, bq * G, 128), jnp.float32),
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(NC, nq),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bq, H, D),
                               lambda sl, iq, bt, m: (sl, iq, 0, 0)),
        scratch_shapes=scratch,
    )
    call = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((NC, Cs, H, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_CHUNK_VMEM_LIMIT),
        interpret=_backend.interpret(),
    )
    with jax.named_scope("paged_chunk"):
        return call(*operands)


# --------------------------------------------------------------------------- #
# jnp references
# --------------------------------------------------------------------------- #

def _gather_seq(kv_pages, block_tables, G):
    """[S, MB] tables over combined pages -> per-sequence K/V
    [S, MB*bs, H, D] (repeated to q heads) — the copy the kernels avoid."""
    S, MB = block_tables.shape
    NB, _, Hkv, bs, D = kv_pages.shape
    pages = kv_pages[block_tables]                 # [S, MB, 2, Hkv, bs, D]
    k_seq = jnp.moveaxis(pages[:, :, 0], 2, 3).reshape(S, MB * bs, Hkv, D)
    v_seq = jnp.moveaxis(pages[:, :, 1], 2, 3).reshape(S, MB * bs, Hkv, D)
    return jnp.repeat(k_seq, G, axis=2), jnp.repeat(v_seq, G, axis=2)


def paged_decode_attention_reference(q, kv_pages, block_tables, ctx_lens,
                                     softmax_scale: Optional[float] = None,
                                     window: Optional[int] = None,
                                     with_lse: bool = False,
                                     alibi: bool = False):
    """jnp reference (gathers each sequence's pages)."""
    S, H, D = q.shape
    NB, _, Hkv, bs, _ = kv_pages.shape
    G = H // Hkv
    MB = block_tables.shape[1]
    scale = softmax_scale if softmax_scale is not None else 1.0 / (D ** 0.5)
    k_seq, v_seq = _gather_seq(kv_pages, block_tables, G)
    sc = jnp.einsum("shd,sthd->sht", q.astype(jnp.float32),
                    k_seq.astype(jnp.float32)) * scale
    if alibi:
        head = jnp.arange(H, dtype=jnp.float32)
        sc = sc + (_alibi_slope(head, H)[None, :, None]
                   * jnp.arange(MB * bs, dtype=jnp.float32)[None, None, :])
    mask = jnp.arange(MB * bs)[None, None, :] < ctx_lens[:, None, None]
    if window is not None:
        mask = mask & (jnp.arange(MB * bs)[None, None, :]
                       >= jnp.maximum(ctx_lens - window, 0)[:, None, None])
    sc = jnp.where(mask, sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    p = jnp.where(ctx_lens[:, None, None] > 0, p, 0.0)
    out = jnp.einsum("sht,sthd->shd", p, v_seq.astype(jnp.float32))
    if with_lse:
        lse = jax.scipy.special.logsumexp(sc, axis=-1)
        lse = jnp.where(ctx_lens[:, None] > 0, lse, NEG_INF)
        return out.astype(q.dtype), lse
    return out.astype(q.dtype)


def paged_decode_attention_step_reference(q, k_new, v_new, kv_pages,
                                          block_tables, ctx_lens,
                                          softmax_scale: Optional[float] = None,
                                          window: Optional[int] = None,
                                          alibi: bool = False):
    """jnp reference: scatter the new rows, then dense paged-decode reference."""
    S, H, D = q.shape
    NB, _, Hkv, bs, _ = kv_pages.shape
    rows = _step_write_rows(block_tables, ctx_lens, NB, Hkv, bs, S)
    new = jnp.concatenate([k_new.reshape(S * Hkv, D),
                           v_new.reshape(S * Hkv, D)])
    kvf = kv_pages.reshape(NB * 2 * Hkv * bs, D).at[rows].set(
        new.astype(kv_pages.dtype), mode="drop").reshape(kv_pages.shape)
    out = paged_decode_attention_reference(q, kvf, block_tables, ctx_lens,
                                           softmax_scale, window=window,
                                           alibi=alibi)
    return out, kvf


def paged_decode_attention_sidebuf_reference(q, kv_pages, block_tables,
                                             prefix_lens, side_k, side_v, j,
                                             softmax_scale=None, window=None,
                                             alibi=False):
    """jnp reference: paged prefix piece (with lse) merged with dense masked
    attention over the side slab — the two-piece computation the fused
    kernel replaces."""
    S, H, D = q.shape
    _, Cs, Hkv, _ = side_k.shape
    G = H // Hkv
    scale = softmax_scale if softmax_scale is not None else 1.0 / (D ** 0.5)
    if window is not None:
        # page piece window start moves with the in-chunk step j
        eff_ctx = prefix_lens + j + 1
        out_p, lse_p = _paged_reference_lse_lo(
            q, kv_pages, block_tables, prefix_lens,
            jnp.maximum(eff_ctx - window, 0), scale)
    else:
        out_p, lse_p = paged_decode_attention_reference(
            q, kv_pages, block_tables, prefix_lens, scale, with_lse=True,
            alibi=alibi)
    qg = q.reshape(S, Hkv, G, D).astype(jnp.float32)
    sc = jnp.einsum("shgd,schd->shgc", qg,
                    side_k.astype(jnp.float32)) * scale
    if alibi:
        head = jnp.arange(H, dtype=jnp.float32).reshape(Hkv, G)
        sc = sc + (_alibi_slope(head, H)[None, :, :, None]
                   * (prefix_lens[:, None, None, None]
                      + jnp.arange(Cs, dtype=jnp.float32)[None, None, None, :]))
    col_ok = (jnp.arange(Cs) <= j)[None, None, None, :]
    if window is not None:
        col_ok = jnp.logical_and(col_ok,
                                 (jnp.arange(Cs) >= j + 1 - window)
                                 [None, None, None, :])
    sc = jnp.where(col_ok, sc, NEG_INF)
    m_s = jnp.max(sc, axis=-1, keepdims=True)
    p = jnp.where(col_ok, jnp.exp(sc - m_s), 0.0)
    l_s = jnp.sum(p, axis=-1, keepdims=True)
    out_s = jnp.einsum("shgc,schd->shgd", p,
                       side_v.astype(jnp.float32)) / jnp.maximum(l_s, 1e-30)
    lse_s = (m_s + jnp.log(jnp.maximum(l_s, 1e-30)))[..., 0]
    lse_pg = lse_p.reshape(S, Hkv, G)
    m_tot = jnp.maximum(lse_pg, lse_s)
    w_p = jnp.exp(lse_pg - m_tot)[..., None]
    w_s = jnp.exp(lse_s - m_tot)[..., None]
    out = (w_p * out_p.reshape(S, Hkv, G, D).astype(jnp.float32)
           + w_s * out_s) / (w_p + w_s)
    return out.reshape(S, H, D).astype(q.dtype)


def _paged_reference_lse_lo(q, kv_pages, block_tables, ctx_lens,
                            tok_lo, scale):
    """Dense paged reference with a per-sequence lower bound on visible
    tokens (side-slab window reference support)."""
    S, H, D = q.shape
    NB, _, Hkv, bs, _ = kv_pages.shape
    G = H // Hkv
    MB = block_tables.shape[1]
    k_seq, v_seq = _gather_seq(kv_pages, block_tables, G)
    sc = jnp.einsum("shd,sthd->sht", q.astype(jnp.float32),
                    k_seq.astype(jnp.float32)) * scale
    pos = jnp.arange(MB * bs)[None, None, :]
    mask = (pos < ctx_lens[:, None, None]) & (pos >= tok_lo[:, None, None])
    sc = jnp.where(mask, sc, NEG_INF)
    any_row = jnp.any(mask, axis=-1)
    p = jax.nn.softmax(sc, axis=-1)
    p = jnp.where(any_row[:, :, None], p, 0.0)
    out = jnp.einsum("sht,sthd->shd", p, v_seq.astype(jnp.float32))
    lse = jax.scipy.special.logsumexp(sc, axis=-1)
    lse = jnp.where(any_row, lse, NEG_INF)
    return out.astype(q.dtype), lse


def paged_chunk_attention_batched_reference(q, kv_pages, block_tables,
                                            q_starts, ctx_lens,
                                            softmax_scale: Optional[float] = None,
                                            window: Optional[int] = None,
                                            alibi: bool = False,
                                            causal_block: int = 1):
    """jnp reference: per-slot single-chunk reference, stacked."""
    outs = []
    for sl in range(q.shape[0]):
        outs.append(paged_chunk_attention_reference(
            q[sl], kv_pages, block_tables[sl],
            q_starts[sl], ctx_lens[sl], softmax_scale, window=window,
            alibi=alibi, causal_block=causal_block))
    return jnp.stack(outs)


def paged_chunk_attention_reference(q, kv_pages, block_table, q_start,
                                    ctx_len, softmax_scale: Optional[float] = None,
                                    window: Optional[int] = None,
                                    alibi: bool = False,
                                    causal_block: int = 1):
    """jnp reference for the chunk kernel (materialises the [C, MB*bs] scores)."""
    C, H, D = q.shape
    NB, _, Hkv, bs, _ = kv_pages.shape
    G = H // Hkv
    MB = block_table.shape[0]
    scale = softmax_scale if softmax_scale is not None else 1.0 / (D ** 0.5)
    k_seq, v_seq = _gather_seq(kv_pages, block_table[None], G)
    k_seq, v_seq = k_seq[0], v_seq[0]              # [MB*bs, H, D]
    sc = jnp.einsum("qhd,khd->hqk", q.astype(jnp.float32),
                    k_seq.astype(jnp.float32)) * scale
    if alibi:
        head = jnp.arange(H, dtype=jnp.float32)
        sc = sc + (_alibi_slope(head, H)[:, None, None]
                   * jnp.arange(MB * bs, dtype=jnp.float32)[None, None, :])
    q_pos = q_start + jnp.arange(C)
    k_pos = jnp.arange(MB * bs)
    k_lim = k_pos if causal_block == 1 else k_pos & -causal_block
    mask = (k_lim[None, :] <= q_pos[:, None]) & (k_pos[None, :] < ctx_len)
    if window is not None:
        mask = mask & (k_pos[None, :] > q_pos[:, None] - window)
    sc = jnp.where(mask[None], sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    p = jnp.where(jnp.any(mask, axis=-1)[None, :, None], p, 0.0)
    out = jnp.einsum("hqk,khd->qhd", p, v_seq.astype(jnp.float32))
    return out.astype(q.dtype)
