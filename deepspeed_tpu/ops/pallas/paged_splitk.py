"""Flash-decoding: split-K paged decode attention with an LSE merge.

The chunk-serial decode kernel (``paged_attention._decode_body``) walks each
sequence's page chunks SEQUENTIALLY with a running (m, l, acc) online
softmax — grid parallelism is over sequences only, so a small batch of
long-context rows (the production tail) leaves the chip idle and per-token
latency grows linearly with ctx. Flash-decoding partitions each sequence's
block-table range into S grid-parallel SPLITS, each emitting an (acc, lse)
partial under the kernel's existing per-head ``lse = m + log(l)`` output
contract (paged_attention.py:494-497, NEG_INF for empty rows); a small
second pass combines the partials with logsumexp weights:

    m_tot = max_p(lse_p);  w_p = exp(lse_p - m_tot)
    out   = sum_p(w_p * out_p) / sum_p(w_p)

which is exactly the flash combination ``w_p * out_p = exp(m_p - m_tot) *
acc_p`` — the same two-piece merge the sidebuf reference already pins
(``paged_decode_attention_sidebuf_reference``), generalised to S pieces.

Two implementations, one ladder:

- **Pallas** (``paged_decode_attention_splitk_pallas``): the decode grid
  becomes (S * n_splits, ceil(NC / n_splits)) VIRTUAL rows — row r carries
  (sequence r // SP, split r % SP) and walks only its split's chunk range
  through the same 2-slot DMA pipeline, always emitting (out, lse) partials
  (f32); the merge runs outside in XLA. Every virtual row runs >= 1 chunk
  so empty splits finalize to (zeros, NEG_INF) through the skipped-page +
  masked-score path, and the merge drops them with weight 0. Lane-aligned
  head dims only (the manual-DMA limit).
- **XLA fallback** (``paged_decode_attention_xla``): one ``lax.scan`` over
  a sequence's page chunks with the split axis BATCHED — split=1 runs NC
  sequential scan steps (the chunk-serial anatomy), split=S runs ceil(NC/S)
  steps with S-fold fatter gathers/dots per step. The sequential depth
  falls on any backend (what that is worth on the chip is not measured:
  ``PERF.md`` §7), and this path carries the cases the
  manual-DMA kernel cannot (small head dims, per-sequence traced window
  starts).

Caller composition (dispatched through ``AttentionKernelSpec``):

- ragged decode pass: straight ``paged_decode_attention_splitk``.
- fused decode step, in-layer write: scatter-FIRST (the small-D fallback's
  pattern, and exactly ``paged_decode_attention_step_reference``'s
  semantics), then full-context split-K decode — int8 pools get
  quantize-on-write for free because the current token is attended at its
  pool value.
- sidebuf: split-K partials over the frozen prefix (traced per-sequence
  window start ``prefix + j + 1 - window``) + one dense side-slab partial,
  merged as S+1 pieces.
- spec verify: ``paged_chunk_attention_splitk`` — XLA-composed only (the
  batched chunk kernel's q-block grid is compute-bound where split-K buys
  little; the split path exists so the verify stream stays on the same
  ladder rung as decode without a recompile).

int8 pages compose by dequantizing the gathered rows directly (k * s — the
same algebra the kernels fold into score/p columns); sliding window and
ALiBi compose positionally (absolute k positions, the k-pos-only ALiBi form
every paged kernel and reference uses).
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
    NEG_INF, _alibi_slope, _chunk_mask, _colscale_pages, _flash_update,
    _kv_flat, _pick_pages_per_chunk, _scale_tile_rows,
    _scales_to_tiles, _step_write_rows, kv_quantize_rows,
    paged_chunk_attention_batched, paged_decode_attention)


# --------------------------------------------------------------------- #
# the LSE merge (the one second pass every split path shares)
# --------------------------------------------------------------------- #

def merge_splitk_partials(out_p: jax.Array, lse_p: jax.Array):
    """Combine split-K partials along axis 1: ``out_p [S, SP, H, D]`` f32
    accumulator partials (each already normalised by its own l), ``lse_p
    [S, SP, H]`` f32 per-partial logsumexp (NEG_INF = empty partial).
    Returns ``(out [S, H, D] f32, lse [S, H] f32)`` — the same
    logsumexp-weighted combination the sidebuf reference pins for its
    two-piece merge, for any number of pieces. Empty partials carry weight
    0; an all-empty row returns (zeros, NEG_INF), matching the kernels'
    ctx-0 contract."""
    m = jnp.max(lse_p, axis=1)                                  # [S, H]
    # mask BEFORE exp: for an all-empty row lse_p - m == 0 and a bare exp
    # would weight garbage partials 1.0 (same reasoning as _flash_update's
    # explicit mask)
    w = jnp.where(lse_p > NEG_INF * 0.5,
                  jnp.exp(lse_p - m[:, None]), 0.0)             # [S, SP, H]
    den = jnp.sum(w, axis=1)                                    # [S, H]
    safe = jnp.where(den > 0.0, den, 1.0)
    out = jnp.sum(w[..., None] * out_p.astype(jnp.float32), axis=1) \
        / safe[..., None]
    lse = jnp.where(den > 0.0, m + jnp.log(safe), NEG_INF)
    return out, lse


def _scales_logical(kv_scales: jax.Array, NB: int, h_kv: int, bs: int):
    """[NB, R8, 128] at-rest tiles OR [NB, 2, Hkv, bs] logical -> logical
    f32 (the XLA paths dequantize rows directly, so they address scales
    logically; tile flat index kv*Hkv*bs + h*bs + t inverts by a plain
    slice)."""
    if kv_scales.ndim == 4:
        return kv_scales.astype(jnp.float32)
    r8 = _scale_tile_rows(h_kv, bs)
    return kv_scales.reshape(NB, r8 * 128)[:, :2 * h_kv * bs] \
        .reshape(NB, 2, h_kv, bs).astype(jnp.float32)


# --------------------------------------------------------------------- #
# XLA-composed fallback: scan over chunks, splits batched
# --------------------------------------------------------------------- #

@jax.named_scope("paged_decode_xla")
def paged_decode_attention_xla(q: jax.Array,
                               kv_pages: jax.Array,
                               block_tables: jax.Array,
                               ctx_lens: jax.Array,
                               softmax_scale: Optional[float] = None,
                               window: Optional[int] = None,
                               with_lse: bool = False,
                               kv_scales: Optional[jax.Array] = None,
                               alibi: bool = False,
                               n_splits: int = 1,
                               tok_lo: Optional[jax.Array] = None,
                               pages_per_chunk: int = 1):
    """Split-K decode attention composed from ``lax.*`` (no Pallas): one
    scan step gathers and attends ``pages_per_chunk`` pages PER SPLIT, so
    split=1 is the chunk-serial anatomy (NC sequential steps) and split=S
    trades sequential depth for per-step width (ceil(NC/S) steps, S-fold
    fatter dots) — the flash-decoding win, measurable on any backend.

    Same contract as :func:`paged_attention.paged_decode_attention` (any
    head dim), plus ``tok_lo`` ([S] int32, traced): an explicit per-sequence
    first-visible-token that OVERRIDES the ``window`` derivation — the
    sidebuf prefix piece's moving window start (``prefix + j + 1 -
    window``), which the static-window kernel cannot carry."""
    S, H, D = q.shape
    NB, two, Hkv, bs, Dk = kv_pages.shape
    assert two == 2 and Dk == D and H % Hkv == 0
    G = H // Hkv
    MB = block_tables.shape[1]
    scale = softmax_scale if softmax_scale is not None else 1.0 / (D ** 0.5)
    SP = max(1, int(n_splits))
    P = max(1, int(pages_per_chunk))
    NCg = -(-MB // P)
    NCl = -(-NCg // SP)
    T = P * bs
    ctx = ctx_lens.astype(jnp.int32)
    bt = block_tables.astype(jnp.int32)
    pad = SP * NCl * P - MB
    if pad:
        # padded table entries gather page 0 — finite pool bytes whose
        # scores the position mask drops
        bt = jnp.pad(bt, ((0, 0), (0, pad)))
    bt_x = jnp.moveaxis(bt.reshape(S, SP, NCl, P), 2, 0)   # [NCl, S, SP, P]
    if tok_lo is not None:
        lo = jnp.asarray(tok_lo, jnp.int32)
    elif window is not None:
        lo = jnp.maximum(ctx - window, 0)
    else:
        lo = None
    scl = None if kv_scales is None \
        else _scales_logical(kv_scales, NB, Hkv, bs)
    qg = q.astype(jnp.float32).reshape(S, Hkv, G, D)
    if alibi:
        slope = _alibi_slope(jnp.arange(H, dtype=jnp.float32),
                             H).reshape(Hkv, G)

    def body(carry, xs):
        m, l, acc = carry
        c, pages = xs                        # pages [S, SP, P]
        kv = kv_pages[pages]                 # [S, SP, P, 2, Hkv, bs, D]
        k = kv[:, :, :, 0].astype(jnp.float32)
        v = kv[:, :, :, 1].astype(jnp.float32)
        if scl is not None:
            ps = scl[pages]                  # [S, SP, P, 2, Hkv, bs]
            k = k * ps[:, :, :, 0][..., None]
            v = v * ps[:, :, :, 1][..., None]
        # token-major per split: [S, SP, Hkv, T, D]
        k = jnp.moveaxis(k, 3, 2).reshape(S, SP, Hkv, T, D)
        v = jnp.moveaxis(v, 3, 2).reshape(S, SP, Hkv, T, D)
        sc = jnp.einsum("shgd,sphtd->sphgt", qg, k) * scale
        # absolute token position of column t in split p at scan step c:
        # global chunk p*NCl + c
        pos = ((jnp.arange(SP, dtype=jnp.int32) * NCl + c) * T)[None, :, None] \
            + jnp.arange(T, dtype=jnp.int32)[None, None, :]     # [1, SP, T]
        mask = pos < ctx[:, None, None]                         # [S, SP, T]
        if lo is not None:
            mask = jnp.logical_and(mask, pos >= lo[:, None, None])
        maskb = mask[:, :, None, None, :]
        if alibi:
            sc = sc + slope[None, None, :, :, None] \
                * pos[:, :, None, None, :].astype(jnp.float32)
        sc = jnp.where(maskb, sc, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
        p = jnp.where(maskb, jnp.exp(sc - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] \
            + jnp.einsum("sphgt,sphtd->sphgd", p, v)
        return (m_new, l_new, acc_new), None

    init = (jnp.full((S, SP, Hkv, G), NEG_INF, jnp.float32),
            jnp.zeros((S, SP, Hkv, G), jnp.float32),
            jnp.zeros((S, SP, Hkv, G, D), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(
        body, init, (jnp.arange(NCl, dtype=jnp.int32), bt_x))
    safe_l = jnp.where(l > 0.0, l, 1.0)
    lse_p = jnp.where(l > 0.0, m + jnp.log(safe_l),
                      NEG_INF).reshape(S, SP, H)
    out_p = (acc / safe_l[..., None]).reshape(S, SP, H, D)
    out, lse = merge_splitk_partials(out_p, lse_p)
    out = out.astype(q.dtype)
    if with_lse:
        return out, lse
    return out


@jax.named_scope("paged_chunk_xla")
def paged_chunk_attention_xla(q: jax.Array,
                              kv_pages: jax.Array,
                              block_tables: jax.Array,
                              q_starts: jax.Array,
                              ctx_lens: jax.Array,
                              softmax_scale: Optional[float] = None,
                              window: Optional[int] = None,
                              kv_scales: Optional[jax.Array] = None,
                              alibi: bool = False,
                              n_splits: int = 1,
                              pages_per_chunk: int = 1):
    """Split-K batched chunk (multi-query) attention composed from
    ``lax.*`` — the spec-verify split path. Same contract as
    :func:`paged_attention.paged_chunk_attention_batched`: q ``[N, Cs, H,
    D]`` (slot n's rows sit at absolute positions ``q_starts[n] + i``,
    causal by absolute position, ctx-bounded, optional sliding window)."""
    N, Cs, H, D = q.shape
    NB, two, Hkv, bs, Dk = kv_pages.shape
    assert two == 2 and Dk == D and H % Hkv == 0
    G = H // Hkv
    MB = block_tables.shape[1]
    scale = softmax_scale if softmax_scale is not None else 1.0 / (D ** 0.5)
    SP = max(1, int(n_splits))
    P = max(1, int(pages_per_chunk))
    NCg = -(-MB // P)
    NCl = -(-NCg // SP)
    T = P * bs
    ctx = ctx_lens.astype(jnp.int32)
    bt = block_tables.astype(jnp.int32)
    pad = SP * NCl * P - MB
    if pad:
        bt = jnp.pad(bt, ((0, 0), (0, pad)))
    bt_x = jnp.moveaxis(bt.reshape(N, SP, NCl, P), 2, 0)
    qpos = q_starts.astype(jnp.int32)[:, None] \
        + jnp.arange(Cs, dtype=jnp.int32)[None, :]              # [N, Cs]
    scl = None if kv_scales is None \
        else _scales_logical(kv_scales, NB, Hkv, bs)
    qg = q.astype(jnp.float32).reshape(N, Cs, Hkv, G, D)
    if alibi:
        slope = _alibi_slope(jnp.arange(H, dtype=jnp.float32),
                             H).reshape(Hkv, G)

    def body(carry, xs):
        m, l, acc = carry
        c, pages = xs
        kv = kv_pages[pages]
        k = kv[:, :, :, 0].astype(jnp.float32)
        v = kv[:, :, :, 1].astype(jnp.float32)
        if scl is not None:
            ps = scl[pages]
            k = k * ps[:, :, :, 0][..., None]
            v = v * ps[:, :, :, 1][..., None]
        k = jnp.moveaxis(k, 3, 2).reshape(N, SP, Hkv, T, D)
        v = jnp.moveaxis(v, 3, 2).reshape(N, SP, Hkv, T, D)
        sc = jnp.einsum("nihgd,nphtd->npihgt", qg, k) * scale
        pos = ((jnp.arange(SP, dtype=jnp.int32) * NCl + c) * T)[None, :, None] \
            + jnp.arange(T, dtype=jnp.int32)[None, None, :]     # [1, SP, T]
        # causal by absolute position, ctx-bounded, optional window —
        # the batched chunk kernel's visibility rule
        mask = jnp.logical_and(
            pos[:, :, None, :] < ctx[:, None, None, None],
            pos[:, :, None, :] <= qpos[:, None, :, None])       # [N, SP, Cs, T]
        if window is not None:
            mask = jnp.logical_and(
                mask, pos[:, :, None, :] >= qpos[:, None, :, None]
                + 1 - window)
        maskb = mask[:, :, :, None, None, :]
        if alibi:
            sc = sc + slope[None, None, None, :, :, None] \
                * pos[:, :, None, None, None, :].astype(jnp.float32)
        sc = jnp.where(maskb, sc, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
        p = jnp.where(maskb, jnp.exp(sc - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] \
            + jnp.einsum("npihgt,nphtd->npihgd", p, v)
        return (m_new, l_new, acc_new), None

    init = (jnp.full((N, SP, Cs, Hkv, G), NEG_INF, jnp.float32),
            jnp.zeros((N, SP, Cs, Hkv, G), jnp.float32),
            jnp.zeros((N, SP, Cs, Hkv, G, D), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(
        body, init, (jnp.arange(NCl, dtype=jnp.int32), bt_x))
    safe_l = jnp.where(l > 0.0, l, 1.0)
    lse_p = jnp.where(l > 0.0, m + jnp.log(safe_l),
                      NEG_INF).reshape(N, SP, Cs * H)
    out_p = (acc / safe_l[..., None]).reshape(N, SP, Cs * H, D)
    out, _ = merge_splitk_partials(out_p, lse_p)
    return out.reshape(N, Cs, H, D).astype(q.dtype)


# --------------------------------------------------------------------- #
# Pallas split-K kernel: virtual-row grid over (sequence, split)
# --------------------------------------------------------------------- #

def _splitk_body(bt_ref, cl_ref, q_ref, kv_hbm, o_ref, lse_ref,
                 kv_buf, sems, acc_sc, m_sc, l_sc, *,
                 scale, block_size, pages_per_chunk, n_chunks_local,
                 n_splits, max_blocks, n_seqs, h_kv, groups,
                 window=None, sc_hbm=None, sc_buf=None, alibi=False):
    """Split-K decode body: grid row r is the VIRTUAL row (sequence
    r // n_splits, split r % n_splits); its chunk walk covers only global
    chunks [p*NCl, (p+1)*NCl) intersected with the sequence's real range,
    through the same 2-slot DMA pipeline as ``_decode_body``. ALWAYS
    finalizes (out, lse) f32 partials — every virtual row runs >= 1 local
    chunk, so a split wholly past ctx (or wholly below the window start)
    emits (zeros, NEG_INF) via skipped pages + masked scores and the merge
    drops it."""
    quant = sc_hbm is not None
    P, bs, T = pages_per_chunk, block_size, pages_per_chunk * block_size
    HB = h_kv * bs
    SP, NCl = n_splits, n_chunks_local
    r, c = pl.program_id(0), pl.program_id(1)
    g = r * NCl + c                        # global step: the pipeline clock
    H = h_kv * groups

    def tok_lo_of(s_):
        if window is None:
            return jnp.int32(0)
        return jnp.maximum(cl_ref[s_] - window, 0)

    def ncg_of(s_):
        # GLOBAL chunk count (every sequence covers >= 1 chunk)
        return jax.lax.div(jnp.maximum(cl_ref[s_], 1) + (T - 1), T)

    def nc_loc_of(r_):
        # local chunks virtual row r_ runs; clamped to >= 1 so finalize
        # always writes this row's partial (empty splits emit NEG_INF)
        s_ = jax.lax.div(r_, SP)
        return jnp.clip(ncg_of(s_) - jax.lax.rem(r_, SP) * NCl, 1, NCl)

    def c0_loc_of(r_):
        # first real LOCAL chunk (window skip), clamped into the local
        # range — a split wholly below the window start runs its last
        # local chunk fully masked (finalize must run once per row)
        if window is None:
            return jnp.int32(0)
        s_ = jax.lax.div(r_, SP)
        c0g = jnp.minimum(jax.lax.div(tok_lo_of(s_), T), ncg_of(s_) - 1)
        return jnp.clip(c0g - jax.lax.rem(r_, SP) * NCl, 0,
                        nc_loc_of(r_) - 1)

    def page_needed(r_, c_, j):
        s_ = jax.lax.div(r_, SP)
        t0 = ((jax.lax.rem(r_, SP) * NCl + c_) * P + j) * bs
        need = t0 < jnp.maximum(cl_ref[s_], 1)
        if window is not None:
            need = jnp.logical_and(need, t0 + bs > tok_lo_of(s_))
        return need

    def chunk_copies(r_, c_, slot):
        s_ = jax.lax.div(r_, SP)
        gc_ = jax.lax.rem(r_, SP) * NCl + c_
        cps = []
        for j in range(P):
            page = bt_ref[s_, jnp.minimum(gc_ * P + j, max_blocks - 1)]
            cps.append((page_needed(r_, c_, j), pltpu.make_async_copy(
                kv_hbm.at[page], kv_buf.at[slot, j], sems.at[slot])))
            if quant:
                cps.append((page_needed(r_, c_, j), pltpu.make_async_copy(
                    sc_hbm.at[page], sc_buf.at[slot, j], sems.at[slot])))
        return cps

    per_page = 2 if quant else 1

    def start_copies(r_, c_, slot):
        for need, cp in chunk_copies(r_, c_, slot):
            @pl.when(need)
            def _():
                cp.start()

    def wait_copies(r_, c_, slot):
        for j2, (need, cp) in enumerate(chunk_copies(r_, c_, slot)):
            @pl.when(need)
            def _():
                cp.wait()
            if j2 % per_page == 0:
                # skipped pages: V half must be finite (0 * NaN = NaN
                # through the pv dot); K needs nothing — masked scores are
                # replaced before use
                @pl.when(jnp.logical_not(need))
                def _():
                    kv_buf[slot, j2 // per_page, HB:, :] = jnp.zeros_like(
                        kv_buf[slot, j2 // per_page, HB:, :])
            if quant and j2 % per_page == 1:
                @pl.when(jnp.logical_not(need))
                def _():
                    sc_buf[slot, j2 // per_page] = jnp.zeros_like(
                        sc_buf[slot, j2 // per_page])

    @pl.when(jnp.logical_and(g == 0, c0_loc_of(0) == 0))
    def _():
        start_copies(0, 0, 0)

    r_n = jax.lax.div(g + 1, NCl)
    c_n = jax.lax.rem(g + 1, NCl)
    next_real = jnp.logical_and(
        g + 1 < n_seqs * SP * NCl,
        jnp.logical_and(c_n < nc_loc_of(r_n), c_n >= c0_loc_of(r_n)))

    @pl.when(next_real)
    def _():
        start_copies(r_n, c_n, jax.lax.rem(g + 1, 2))

    s = jax.lax.div(r, SP)
    gc = jax.lax.rem(r, SP) * NCl + c      # GLOBAL chunk index
    ctx = cl_ref[s]
    nc_loc = nc_loc_of(r)
    c0_loc = c0_loc_of(r)

    @pl.when(jnp.logical_and(c < nc_loc, c >= c0_loc))
    def _():
        slot = jax.lax.rem(g, 2)
        wait_copies(r, c, slot)

        @pl.when(c == c0_loc)
        def _():
            m_sc[:] = jnp.full_like(m_sc, NEG_INF)
            l_sc[:] = jnp.zeros_like(l_sc)
            acc_sc[:] = jnp.zeros_like(acc_sc)

        q = q_ref[0]                                           # [H, D]
        kk = kv_buf[slot, :, :HB, :].reshape(P * HB, -1)
        vv = kv_buf[slot, :, HB:, :].reshape(P * HB, -1)
        mask = _chunk_mask(gc, ctx, T, h_kv, bs, H,
                           tok_lo=None if window is None else tok_lo_of(s))
        v_scale_fn = None
        if quant:
            kk = kk.astype(q.dtype)
            nsub = HB // 128
            st = sc_buf[slot]                                  # [P, R8, 128]
            v_scale_fn = functools.partial(_colscale_pages, tile_ref=st,
                                           n_pages=P, nsub=nsub, off=nsub)
        sc = jax.lax.dot_general(q.astype(kk.dtype), kk,
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
        if quant:
            sc = _colscale_pages(sc, st, P, nsub, 0)
        if alibi:
            col = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
            tok = gc * T + (col // HB) * bs + jax.lax.rem(col, bs)
            head = jax.lax.broadcasted_iota(jnp.float32, sc.shape, 0)
            sc = sc + _alibi_slope(head, H) * tok.astype(jnp.float32)
        _flash_update(sc, mask, vv, m_sc, l_sc, acc_sc,
                      v_scale_fn=v_scale_fn, compute_dtype=q.dtype)

        @pl.when(c == nc_loc - 1)
        def _():
            l = l_sc[:, 0:1]
            safe_l = jnp.where(l > 0.0, l, 1.0)
            o_ref[0] = (acc_sc[:] / safe_l).astype(o_ref.dtype)
            lse = m_sc[:, 0:1] + jnp.log(safe_l)
            lse_ref[0] = jnp.broadcast_to(
                jnp.where(l > 0.0, lse, NEG_INF), lse_ref[0].shape)


def _splitk_kernel(bt_ref, cl_ref, q_ref, kv_hbm, o_ref, lse_ref,
                   kv_buf, sems, acc_sc, m_sc, l_sc, **kw):
    _splitk_body(bt_ref, cl_ref, q_ref, kv_hbm, o_ref, lse_ref,
                 kv_buf, sems, acc_sc, m_sc, l_sc, **kw)


def _splitk_kernel_quant(bt_ref, cl_ref, q_ref, kv_hbm, sc_hbm,
                         o_ref, lse_ref, kv_buf, sc_buf, sems,
                         acc_sc, m_sc, l_sc, **kw):
    _splitk_body(bt_ref, cl_ref, q_ref, kv_hbm, o_ref, lse_ref,
                 kv_buf, sems, acc_sc, m_sc, l_sc,
                 sc_hbm=sc_hbm, sc_buf=sc_buf, **kw)


def paged_decode_attention_splitk_pallas(q: jax.Array,
                                         kv_pages: jax.Array,
                                         block_tables: jax.Array,
                                         ctx_lens: jax.Array,
                                         n_splits: int,
                                         softmax_scale: Optional[float] = None,
                                         window: Optional[int] = None,
                                         with_lse: bool = False,
                                         kv_scales: Optional[jax.Array] = None,
                                         alibi: bool = False):
    """The Pallas split-K decode: (S * n_splits, ceil(NC / n_splits))
    virtual-row grid emitting f32 (out, lse) partials, merged in XLA.
    Lane-aligned head dims only (the manual-DMA limit); int8 pages
    compose — the always-on lse output lifts the chunk-serial kernel's
    quant+lse gap. Same contract as ``paged_decode_attention``."""
    S, H, D = q.shape
    NB, two, Hkv, bs, Dk = kv_pages.shape
    assert two == 2 and Dk == D, (kv_pages.shape, D)
    assert H % Hkv == 0
    assert D % 128 == 0, \
        "split-K Pallas path needs the manual-DMA alignment (D % 128 == 0)"
    G = H // Hkv
    MB = block_tables.shape[1]
    scale = softmax_scale if softmax_scale is not None else 1.0 / (D ** 0.5)
    quant = kv_scales is not None
    SP = max(1, int(n_splits))
    r8 = _scale_tile_rows(Hkv, bs)
    if quant:
        assert (Hkv * bs) % 128 == 0, "scale tiles need lane alignment"
    # reserve the split partials' state honestly: flash scratch + the f32
    # (out, lse) double-buffered output blocks (satellite of this PR —
    # splits multiply resident partial state, the page slabs must shrink)
    P = _pick_pages_per_chunk(bs, Hkv, D, jnp.dtype(kv_pages.dtype).itemsize,
                              MB, flash_heads=H,
                              out_bytes=2 * (H * D + H * 128) * 4,
                              scale_tile_rows=r8 if quant else 0)
    NCg = -(-MB // P)
    NCl = -(-NCg // SP)
    assert (bs * Hkv) % 8 == 0, \
        f"page rows {Hkv}*{bs} must align to the 8-sublane tile"

    kernel = functools.partial(
        _splitk_kernel_quant if quant else _splitk_kernel,
        scale=scale, block_size=bs, pages_per_chunk=P,
        n_chunks_local=NCl, n_splits=SP, max_blocks=MB, n_seqs=S,
        h_kv=Hkv, groups=G, window=window, alibi=alibi)
    in_specs = [
        pl.BlockSpec((1, H, D), lambda r, c, bt, cl: (r // SP, 0, 0)),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    out_specs = [
        pl.BlockSpec((1, H, D), lambda r, c, bt, cl: (r, 0, 0)),
        pl.BlockSpec((1, H, 128), lambda r, c, bt, cl: (r, 0, 0)),
    ]
    out_shape = [jax.ShapeDtypeStruct((S * SP, H, D), jnp.float32),
                 jax.ShapeDtypeStruct((S * SP, H, 128), jnp.float32)]
    scratch = [pltpu.VMEM((2, P, 2 * Hkv * bs, D), kv_pages.dtype)]
    operands = [block_tables.astype(jnp.int32), ctx_lens.astype(jnp.int32),
                q, _kv_flat(kv_pages)]
    if quant:
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
        grid=(S * SP, NCl),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    call = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            # the 2-slot DMA pipeline hands buffers across grid steps (and
            # across virtual rows), so iteration order stays sequential
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_backend.interpret(),
    )
    with jax.named_scope(f"paged_decode_splitk_sp{SP}"):
        out_p, lse_p = call(*operands)
    out, lse = merge_splitk_partials(out_p.reshape(S, SP, H, D),
                                     lse_p[:, :, 0].reshape(S, SP, H))
    out = out.astype(q.dtype)
    if with_lse:
        return out, lse
    return out


# --------------------------------------------------------------------- #
# dispatchers: one entry per caller shape
# --------------------------------------------------------------------- #

def paged_decode_attention_splitk(q: jax.Array,
                                  kv_pages: jax.Array,
                                  block_tables: jax.Array,
                                  ctx_lens: jax.Array,
                                  softmax_scale: Optional[float] = None,
                                  window: Optional[int] = None,
                                  with_lse: bool = False,
                                  kv_scales: Optional[jax.Array] = None,
                                  alibi: bool = False,
                                  n_splits: int = 1,
                                  pages_per_chunk: Optional[int] = None):
    """Split-count-dispatched decode attention: ``n_splits <= 1`` is
    byte-identical to ``paged_decode_attention`` (the exact chunk-serial
    program — split=1 adds nothing to re-test); ``n_splits > 1`` takes the
    Pallas virtual-row kernel on TPU (lane-aligned head dims) and the
    XLA-composed scan elsewhere — including small head dims on any backend,
    the same shape routing the chunk-serial wrapper's smalld fallback
    established."""
    if n_splits <= 1:
        if with_lse and kv_scales is not None:
            # the chunk-serial kernel refuses with_lse + int8 (no caller
            # needed it pre-split-K); the split=1 XLA scan serves it
            return paged_decode_attention_xla(
                q, kv_pages, block_tables, ctx_lens,
                softmax_scale=softmax_scale, window=window, with_lse=True,
                kv_scales=kv_scales, alibi=alibi, n_splits=1,
                pages_per_chunk=pages_per_chunk or 1)
        return paged_decode_attention(q, kv_pages, block_tables, ctx_lens,
                                      softmax_scale=softmax_scale,
                                      window=window, with_lse=with_lse,
                                      kv_scales=kv_scales, alibi=alibi)
    if q.shape[-1] % 128 == 0 and not _backend.interpret():
        return paged_decode_attention_splitk_pallas(
            q, kv_pages, block_tables, ctx_lens, n_splits,
            softmax_scale=softmax_scale, window=window, with_lse=with_lse,
            kv_scales=kv_scales, alibi=alibi)
    return paged_decode_attention_xla(
        q, kv_pages, block_tables, ctx_lens, softmax_scale=softmax_scale,
        window=window, with_lse=with_lse, kv_scales=kv_scales, alibi=alibi,
        n_splits=n_splits, pages_per_chunk=pages_per_chunk or 1)


def paged_chunk_attention_splitk(q: jax.Array,
                                 kv_pages: jax.Array,
                                 block_tables: jax.Array,
                                 q_starts: jax.Array,
                                 ctx_lens: jax.Array,
                                 softmax_scale: Optional[float] = None,
                                 window: Optional[int] = None,
                                 kv_scales: Optional[jax.Array] = None,
                                 alibi: bool = False,
                                 n_splits: int = 1,
                                 pages_per_chunk: Optional[int] = None):
    """Split-count-dispatched chunk attention (the spec-verify caller).
    ``n_splits <= 1`` is the batched Pallas chunk kernel unchanged; higher
    rungs take the XLA-composed split scan on EVERY backend — chunk
    attention is compute-bound (q-block x KV dots), so a split-K Pallas
    grid buys none of the decode win; the split path exists so verify
    streams ride the same ladder rung as decode without recompiling."""
    if n_splits <= 1:
        return paged_chunk_attention_batched(
            q, kv_pages, block_tables, q_starts, ctx_lens,
            softmax_scale=softmax_scale, window=window,
            kv_scales=kv_scales, alibi=alibi)
    return paged_chunk_attention_xla(
        q, kv_pages, block_tables, q_starts, ctx_lens,
        softmax_scale=softmax_scale, window=window, kv_scales=kv_scales,
        alibi=alibi, n_splits=n_splits,
        pages_per_chunk=pages_per_chunk or 1)


def paged_decode_attention_splitk_step(q: jax.Array,
                                       k_new: jax.Array,
                                       v_new: jax.Array,
                                       kv_pages: jax.Array,
                                       block_tables: jax.Array,
                                       ctx_lens: jax.Array,
                                       softmax_scale: Optional[float] = None,
                                       window: Optional[int] = None,
                                       kv_scales: Optional[jax.Array] = None,
                                       alibi: bool = False,
                                       n_splits: int = 2,
                                       pages_per_chunk: Optional[int] = None):
    """Split-K fused decode step: scatter the current token's K/V (and, for
    int8 pools, its quantized rows + scales) into the pools FIRST, then
    split-K decode over the full context — the small-D step fallback's
    scatter-first pattern, and exactly what
    ``paged_decode_attention_step_reference`` computes. Quantize-on-write
    semantics come free: the current token is attended at its pool value.
    Same contract as ``paged_decode_attention_step``."""
    S, H, D = q.shape
    NB, two, Hkv, bs, Dk = kv_pages.shape
    assert two == 2 and Dk == D and H % Hkv == 0
    bt = block_tables.astype(jnp.int32)
    cl = ctx_lens.astype(jnp.int32)
    rows = _step_write_rows(bt, cl, NB, Hkv, bs, S)
    if kv_scales is not None:
        kq, ks_new = kv_quantize_rows(k_new)
        vq, vs_new = kv_quantize_rows(v_new)
        new = jnp.concatenate([kq.reshape(S * Hkv, D),
                               vq.reshape(S * Hkv, D)])
        kvf = kv_pages.reshape(NB * 2 * Hkv * bs, D).at[rows].set(
            new, mode="drop").reshape(kv_pages.shape)
        news = jnp.concatenate([ks_new.reshape(-1), vs_new.reshape(-1)])
        if kv_scales.ndim == 3:            # tiled at rest [NB, R8, 128]
            r8 = _scale_tile_rows(Hkv, bs)
            hb2 = 2 * Hkv * bs
            sdest = (rows // hb2) * (r8 * 128) + rows % hb2
            scf = kv_scales.reshape(NB * r8 * 128).at[sdest].set(
                news, mode="drop").reshape(NB, r8, 128)
        else:
            scf = kv_scales.reshape(NB * 2 * Hkv * bs).at[rows].set(
                news, mode="drop").reshape(NB, 2, Hkv, bs)
        out = paged_decode_attention_splitk(
            q, kvf, bt, cl, softmax_scale=softmax_scale, window=window,
            kv_scales=scf, alibi=alibi, n_splits=n_splits,
            pages_per_chunk=pages_per_chunk)
        return out, kvf, scf
    new = jnp.concatenate([k_new.reshape(S * Hkv, D),
                           v_new.reshape(S * Hkv, D)])
    kvf = kv_pages.reshape(NB * 2 * Hkv * bs, D).at[rows].set(
        new.astype(kv_pages.dtype), mode="drop").reshape(kv_pages.shape)
    out = paged_decode_attention_splitk(
        q, kvf, bt, cl, softmax_scale=softmax_scale, window=window,
        alibi=alibi, n_splits=n_splits, pages_per_chunk=pages_per_chunk)
    return out, kvf


def paged_sidebuf_attention_splitk(q: jax.Array,
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
                                   alibi: bool = False,
                                   n_splits: int = 2,
                                   pages_per_chunk: Optional[int] = None):
    """Split-K frozen-prefix + side-slab decode: split-K partials over the
    paged prefix (with a sliding window the query position is ``prefix +
    j``, so the window start is the TRACED per-sequence ``prefix + j + 1 -
    window`` — the XLA path's ``tok_lo``) plus ONE dense side-slab partial,
    merged as S+1 logsumexp-weighted pieces — the sidebuf reference's
    two-piece merge generalised. Same contract as
    ``paged_decode_attention_sidebuf`` (int8 pools: the slab already holds
    ``kv_write_dequant``'d rows, so only the pages dequantize)."""
    S, H, D = q.shape
    NB, two, Hkv, bs, Dk = kv_pages.shape
    assert two == 2 and Dk == D and H % Hkv == 0
    G = H // Hkv
    scale = softmax_scale if softmax_scale is not None else 1.0 / (D ** 0.5)
    if side_k.ndim == 4 and layer_idx is None:
        # single-layer logical [S, C, Hkv, D]
        S2, Cs, Hkv2, D2 = side_k.shape
        sk = side_k.reshape(S2, Cs * Hkv2, D2)
        sv = side_v.reshape(S2, Cs * Hkv2, D2)
    else:
        if side_k.ndim == 5:               # [L, S, C, Hkv, D] logical
            Ls, S2, Cs, Hkv2, D2 = side_k.shape
            side_k = side_k.reshape(Ls, S2, Cs * Hkv2, D2)
            side_v = side_v.reshape(Ls, S2, Cs * Hkv2, D2)
        # pre-flattened [L, S, C*Hkv, D] with a traced layer_idx
        li = jnp.asarray(layer_idx, jnp.int32)
        sk = jax.lax.dynamic_index_in_dim(side_k, li, 0, keepdims=False)
        sv = jax.lax.dynamic_index_in_dim(side_v, li, 0, keepdims=False)
    CsH = sk.shape[1]
    assert CsH % Hkv == 0
    Cs = CsH // Hkv
    jj = jnp.asarray(j, jnp.int32)
    pfx = prefix_lens.astype(jnp.int32)

    # prefix piece: split-K partials over the frozen pages
    if window is None:
        out_pg, lse_pg = paged_decode_attention_splitk(
            q, kv_pages, block_tables, pfx, softmax_scale=scale,
            with_lse=True, kv_scales=kv_scales, alibi=alibi,
            n_splits=n_splits, pages_per_chunk=pages_per_chunk)
    else:
        # traced per-sequence window start — the XLA path only
        lo = jnp.maximum(pfx + jj + 1 - window, 0)
        out_pg, lse_pg = paged_decode_attention_xla(
            q, kv_pages, block_tables, pfx, softmax_scale=scale,
            with_lse=True, kv_scales=kv_scales, alibi=alibi,
            n_splits=max(1, int(n_splits)), tok_lo=lo,
            pages_per_chunk=pages_per_chunk or 1)

    # side piece: one dense partial over the slab (row cc's token sits at
    # position prefix + cc; rows cc <= j are real)
    qg = q.astype(jnp.float32).reshape(S, Hkv, G, D)
    skr = sk.astype(jnp.float32).reshape(S, Cs, Hkv, D)
    svr = sv.astype(jnp.float32).reshape(S, Cs, Hkv, D)
    cc = jnp.arange(Cs, dtype=jnp.int32)
    smask = cc <= jj                                           # [Cs]
    if window is not None:
        smask = jnp.logical_and(smask, cc >= jj + 1 - window)
    # rows past j may hold reused garbage; p is 0 there but 0 * inf = NaN
    # through the pv dot, so zero the dead V rows (the kernel's discipline)
    svr = jnp.where((cc <= jj)[None, :, None, None], svr, 0.0)
    sc_s = jnp.einsum("shgd,schd->shgc", qg, skr) * scale      # [S,Hkv,G,Cs]
    if alibi:
        slope = _alibi_slope(jnp.arange(H, dtype=jnp.float32),
                             H).reshape(Hkv, G)
        sc_s = sc_s + slope[None, :, :, None] \
            * (pfx[:, None, None, None] + cc[None, None, None, :]
               ).astype(jnp.float32)
    maskb = smask[None, None, None, :]
    sc_s = jnp.where(maskb, sc_s, NEG_INF)
    m_s = jnp.max(sc_s, axis=-1)                               # [S,Hkv,G]
    p_s = jnp.where(maskb, jnp.exp(sc_s - m_s[..., None]), 0.0)
    l_s = jnp.sum(p_s, axis=-1)
    safe_ls = jnp.where(l_s > 0.0, l_s, 1.0)
    out_s = (jnp.einsum("shgc,schd->shgd", p_s, svr)
             / safe_ls[..., None]).reshape(S, H, D)
    lse_s = jnp.where(l_s > 0.0, m_s + jnp.log(safe_ls),
                      NEG_INF).reshape(S, H)

    out2 = jnp.stack([out_pg.astype(jnp.float32), out_s], axis=1)
    lse2 = jnp.stack([lse_pg, lse_s], axis=1)
    out, _ = merge_splitk_partials(out2, lse2)
    return out.astype(q.dtype)
