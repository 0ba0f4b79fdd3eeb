"""Block-sparse attention for TPU (Pallas) — the splash-attention analog.

Parity role: the reference's Triton block-sparse kernels
(``ops/sparse_attention/matmul.py:196,628`` SDD/DSD matmuls and
``softmax.py:123`` sparse softmax) behind ``SparseSelfAttention``.  Those
kernels iterate only the *active* blocks of a static [H, nb, nb] layout; here
the same layouts (``ops/sparse_attention.py`` Fixed/Variable/BigBird/
BSLongformer builders) drive a Pallas kernel whose KV grid dimension is the
per-(head, q-block) list of active k-blocks, delivered via scalar prefetch —
compute and HBM traffic scale with the number of active blocks, not T^2.

Structure follows ``flash_attention.py`` (online softmax, fp32 accumulation,
custom VJP recomputing probabilities from the saved logsumexp).  The grid's
last dimension is ``max_nnz`` (the densest row of the layout); rows with fewer
active blocks no-op the tail steps.  Layouts are static numpy, so the
active-block index tables and fine-grained tile masks are built host-side once
and cached; identical per-head layouts collapse to one table.

Layout blocks are typically 16 (reference default); the kernel fuses
``block_mult`` layout rows/cols into one tile so the MXU sees [128, D]
operands, with the fine 16-granular pattern restored by an elementwise mask.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from deepspeed_tpu.ops.pallas import _backend


NEG_INF = -1e30


# --------------------------------------------------------------------------- #
# layout preprocessing (host-side, static)
# --------------------------------------------------------------------------- #


def _coarsen(layout: np.ndarray, mult: int) -> np.ndarray:
    """OR-reduce mult x mult tiles: a coarse tile is active if ANY fine block
    in it is active (the in-kernel fine mask restores exactness)."""
    H, nb, _ = layout.shape
    if mult == 1:
        return layout.astype(bool)
    nc = nb // mult
    return layout.reshape(H, nc, mult, nc, mult).any(axis=(2, 4))


def _row_tables(layout_c: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """([H, nq, max_nnz] active col ids padded with 0, [H, nq] counts)."""
    H, nq, nk = layout_c.shape
    counts = layout_c.sum(axis=2)
    max_nnz = max(int(counts.max()), 1)
    cols = np.zeros((H, nq, max_nnz), np.int32)
    for h in range(H):
        for i in range(nq):
            cs = np.nonzero(layout_c[h, i])[0]
            cols[h, i, :len(cs)] = cs
    return cols, counts.astype(np.int32)


# --------------------------------------------------------------------------- #
# kernels
# --------------------------------------------------------------------------- #


def _masked_scores(q, k, mask_ref, q_base, k_base, scale, causal):
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    m = mask_ref[0, :, :].astype(jnp.int32) > 0
    if causal:
        q_idx = q_base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_idx = k_base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        m = jnp.logical_and(m, q_idx >= k_idx)
    return jnp.where(m, s, NEG_INF)


def _safe_exp(s, ref):
    """exp(s - ref) with fully-masked rows forced to 0.  When every element
    of a row is masked, s == ref == NEG_INF and a naive exp(s - ref) would be
    exp(0) = 1, silently attending to everything the tile visited (and, in the
    backward, exploding p for rows whose saved lse is the NEG_INF sentinel)."""
    return jnp.where(s <= NEG_INF * 0.5, 0.0, jnp.exp(s - ref))


def _fwd_kernel(cols_ref, cnt_ref, q_ref, k_ref, v_ref, mask_ref,
                o_ref, lse_ref, acc_sc, m_sc, l_sc,
                *, scale, causal, bq, bk, snum, Hl):
    h, iq, s_i = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    hl = h % Hl

    @pl.when(s_i == 0)
    def _():
        m_sc[:] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc_sc[:] = jnp.zeros_like(acc_sc)

    @pl.when(s_i < cnt_ref[hl, iq])
    def _():
        s = _masked_scores(q_ref[0, 0, :, :], k_ref[0, 0, :, :], mask_ref,
                           iq * bq, cols_ref[hl, iq, s_i] * bk, scale, causal)
        v = v_ref[0, 0, :, :]
        m_prev = m_sc[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = _safe_exp(s, m_new)
        alpha = jnp.exp(jnp.minimum(m_prev - m_new, 0.0))
        l_sc[:, 0:1] = l_sc[:, 0:1] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_sc[:, 0:1] = m_new
        acc_sc[:] = acc_sc[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(s_i == snum - 1)
    def _():
        l = l_sc[:, 0:1]
        safe_l = jnp.where(l > 0.0, l, 1.0)
        o_ref[0, 0, :, :] = (acc_sc[:] / safe_l).astype(o_ref.dtype)
        lse = m_sc[:, 0:1] + jnp.log(safe_l)
        lse_ref[0, 0, :, :] = jnp.where(l > 0.0, lse, NEG_INF)


def _dq_kernel(cols_ref, cnt_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
               delta_ref, mask_ref, dq_ref, dq_sc,
               *, scale, causal, bq, bk, snum, Hl):
    h, iq, s_i = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    hl = h % Hl

    @pl.when(s_i == 0)
    def _():
        dq_sc[:] = jnp.zeros_like(dq_sc)

    @pl.when(s_i < cnt_ref[hl, iq])
    def _():
        k = k_ref[0, 0, :, :]
        s = _masked_scores(q_ref[0, 0, :, :], k, mask_ref,
                           iq * bq, cols_ref[hl, iq, s_i] * bk, scale, causal)
        p = _safe_exp(s, lse_ref[0, 0, :, :])
        dp = jax.lax.dot_general(do_ref[0, 0, :, :], v_ref[0, 0, :, :],
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0, :, :]) * scale
        dq_sc[:] += jax.lax.dot_general(ds.astype(k.dtype), k,
                                        (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    @pl.when(s_i == snum - 1)
    def _():
        dq_ref[0, 0, :, :] = dq_sc[:].astype(dq_ref.dtype)


def _dkv_kernel(rows_ref, cnt_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                delta_ref, mask_ref, dk_ref, dv_ref, dk_sc, dv_sc,
                *, scale, causal, bq, bk, snum, Hl):
    h, ik, s_i = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    hl = h % Hl

    @pl.when(s_i == 0)
    def _():
        dk_sc[:] = jnp.zeros_like(dk_sc)
        dv_sc[:] = jnp.zeros_like(dv_sc)

    @pl.when(s_i < cnt_ref[hl, ik])
    def _():
        q = q_ref[0, 0, :, :]
        do = do_ref[0, 0, :, :]
        s = _masked_scores(q, k_ref[0, 0, :, :], mask_ref,
                           rows_ref[hl, ik, s_i] * bq, ik * bk, scale, causal)
        p = _safe_exp(s, lse_ref[0, 0, :, :])
        dv_sc[:] += jax.lax.dot_general(p.astype(do.dtype), do,
                                        (((0,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v_ref[0, 0, :, :],
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0, :, :]) * scale
        dk_sc[:] += jax.lax.dot_general(ds.astype(q.dtype), q,
                                        (((0,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    @pl.when(s_i == snum - 1)
    def _():
        dk_ref[0, 0, :, :] = dk_sc[:].astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_sc[:].astype(dv_ref.dtype)


# --------------------------------------------------------------------------- #
# host-side builder (cached per layout)
# --------------------------------------------------------------------------- #


class _BSA:
    """Per-(layout, block, causal, mult) kernel bundle with a custom VJP."""

    def __init__(self, layout: np.ndarray, block: int, causal: bool,
                 block_mult: int):
        # collapse identical per-head layouts to one table
        if layout.shape[0] > 1 and (layout == layout[0:1]).all():
            layout = layout[0:1]
        Hl, nb, _ = layout.shape
        while nb % block_mult != 0 and block_mult > 1:
            block_mult //= 2
        self.block, self.causal, self.mult, self.Hl = block, causal, block_mult, Hl
        self.bq = self.bk = block * block_mult
        coarse = _coarsen(layout, block_mult)
        if causal:
            coarse = coarse & np.tril(np.ones(coarse.shape[1:], bool))
        self.cols, self.row_cnt = _row_tables(coarse)
        self.rows, self.col_cnt = _row_tables(np.swapaxes(coarse, 1, 2))
        self.fine_row = self._fine_tiles(layout, self.cols, self.row_cnt,
                                         transpose=False)
        self.fine_col = self._fine_tiles(layout, self.rows, self.col_cnt,
                                         transpose=True)
        self.snum = self.cols.shape[2]
        self.snum_c = self.rows.shape[2]

    def _fine_tiles(self, layout, table, counts, transpose):
        """int8 [Hl * n_outer * snum, bq, bk] elementwise tile masks.  For the
        row orientation outer = q-block and table holds k-cols; for the column
        orientation outer = k-block and table holds q-rows."""
        Hl, nb, _ = layout.shape
        m, b = self.mult, self.block
        n_outer, snum = table.shape[1], table.shape[2]
        out = np.zeros((Hl, n_outer, snum, self.bq, self.bk), np.int8)
        for h in range(Hl):
            for i in range(n_outer):
                for s in range(int(counts[h, i])):
                    j = int(table[h, i, s])
                    qi, ki = (j, i) if transpose else (i, j)
                    fine = layout[h, qi * m:(qi + 1) * m, ki * m:(ki + 1) * m]
                    out[h, i, s] = np.kron(fine.astype(np.int8),
                                           np.ones((b, b), np.int8))
        return out.reshape(Hl * n_outer * snum, self.bq, self.bk)

    def _common(self, kernel, grid, scalars, tensors, in_specs, out_specs,
                out_shape, scratch):
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars), grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch)
        return pl.pallas_call(
            kernel, grid_spec=grid_spec, out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel",
                                     "arbitrary")),
            interpret=_backend.interpret(),
        )(*scalars, *tensors)

    def fwd(self, q, k, v, scale):
        B, H, T, D = q.shape
        bq, bk, Hl, snum = self.bq, self.bk, self.Hl, self.snum
        nq = T // bq
        qs = lambda b, h, iq, s, cols, cnt: (b, h, iq, 0)
        ks = lambda b, h, iq, s, cols, cnt: (b, h, cols[h % Hl, iq, s], 0)
        ms = lambda b, h, iq, s, cols, cnt: ((h % Hl) * (nq * snum)
                                             + iq * snum + s, 0, 0)
        kernel = functools.partial(_fwd_kernel, scale=scale, causal=self.causal,
                                   bq=bq, bk=bk, snum=snum, Hl=Hl)
        return self._common(
            kernel, (B, H, nq, snum),
            [jnp.asarray(self.cols), jnp.asarray(self.row_cnt)],
            [q, k, v, jnp.asarray(self.fine_row)],
            in_specs=[pl.BlockSpec((1, 1, bq, D), qs),
                      pl.BlockSpec((1, 1, bk, D), ks),
                      pl.BlockSpec((1, 1, bk, D), ks),
                      pl.BlockSpec((1, bq, bk), ms)],
            out_specs=[pl.BlockSpec((1, 1, bq, D), qs),
                       pl.BlockSpec((1, 1, bq, 1), qs)],
            out_shape=[jax.ShapeDtypeStruct((B, H, T, D), q.dtype),
                       jax.ShapeDtypeStruct((B, H, T, 1), jnp.float32)],
            scratch=[pltpu.VMEM((bq, D), jnp.float32),
                     pltpu.VMEM((bq, 128), jnp.float32),
                     pltpu.VMEM((bq, 128), jnp.float32)])

    def bwd(self, q, k, v, o, lse, do, scale):
        B, H, T, D = q.shape
        bq, bk, Hl = self.bq, self.bk, self.Hl
        nq = nk = T // bq
        snum, snum_c = self.snum, self.snum_c
        delta = jnp.einsum("bhtd,bhtd->bht", do.astype(jnp.float32),
                           o.astype(jnp.float32))[..., None]

        qs = lambda b, h, iq, s, cols, cnt: (b, h, iq, 0)
        ks = lambda b, h, iq, s, cols, cnt: (b, h, cols[h % Hl, iq, s], 0)
        ms = lambda b, h, iq, s, cols, cnt: ((h % Hl) * (nq * snum)
                                             + iq * snum + s, 0, 0)
        dq = self._common(
            functools.partial(_dq_kernel, scale=scale, causal=self.causal,
                              bq=bq, bk=bk, snum=snum, Hl=Hl),
            (B, H, nq, snum),
            [jnp.asarray(self.cols), jnp.asarray(self.row_cnt)],
            [q, k, v, do, lse, delta, jnp.asarray(self.fine_row)],
            in_specs=[pl.BlockSpec((1, 1, bq, D), qs),
                      pl.BlockSpec((1, 1, bk, D), ks),
                      pl.BlockSpec((1, 1, bk, D), ks),
                      pl.BlockSpec((1, 1, bq, D), qs),
                      pl.BlockSpec((1, 1, bq, 1), qs),
                      pl.BlockSpec((1, 1, bq, 1), qs),
                      pl.BlockSpec((1, bq, bk), ms)],
            out_specs=pl.BlockSpec((1, 1, bq, D), qs),
            out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
            scratch=[pltpu.VMEM((bq, D), jnp.float32)])

        kks = lambda b, h, ik, s, rows, cnt: (b, h, ik, 0)
        qrs = lambda b, h, ik, s, rows, cnt: (b, h, rows[h % Hl, ik, s], 0)
        mcs = lambda b, h, ik, s, rows, cnt: ((h % Hl) * (nk * snum_c)
                                              + ik * snum_c + s, 0, 0)
        dk, dv = self._common(
            functools.partial(_dkv_kernel, scale=scale, causal=self.causal,
                              bq=bq, bk=bk, snum=snum_c, Hl=Hl),
            (B, H, nk, snum_c),
            [jnp.asarray(self.rows), jnp.asarray(self.col_cnt)],
            [q, k, v, do, lse, delta, jnp.asarray(self.fine_col)],
            in_specs=[pl.BlockSpec((1, 1, bq, D), qrs),
                      pl.BlockSpec((1, 1, bk, D), kks),
                      pl.BlockSpec((1, 1, bk, D), kks),
                      pl.BlockSpec((1, 1, bq, D), qrs),
                      pl.BlockSpec((1, 1, bq, 1), qrs),
                      pl.BlockSpec((1, 1, bq, 1), qrs),
                      pl.BlockSpec((1, bq, bk), mcs)],
            out_specs=[pl.BlockSpec((1, 1, bk, D), kks),
                       pl.BlockSpec((1, 1, bk, D), kks)],
            out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                       jax.ShapeDtypeStruct(v.shape, v.dtype)],
            scratch=[pltpu.VMEM((bk, D), jnp.float32),
                     pltpu.VMEM((bk, D), jnp.float32)])
        return dq, dk, dv


from deepspeed_tpu.utils.caching import LRUCache

# LRU-bounded: layouts are host tables + jitted kernels; long-lived serving
# with many distinct layouts must not accumulate them without eviction.
_CACHE: LRUCache = LRUCache(maxsize=32)


def _get_bsa(layout_bytes, shape, block, causal, block_mult) -> _BSA:
    key = (layout_bytes, shape, block, causal, block_mult)
    return _CACHE.get_or_create(
        key, lambda: _BSA(np.frombuffer(layout_bytes, np.uint8).reshape(shape),
                          block, causal, block_mult))


def block_sparse_attention_bhsd(q: jax.Array, k: jax.Array, v: jax.Array,
                                layout: np.ndarray, block: int,
                                causal: bool = False,
                                softmax_scale: Optional[float] = None,
                                block_mult: int = 8) -> jax.Array:
    """Block-sparse attention over [B, H, S, D] (the kernel's native layout;
    ``sparse_self_attention`` calls this directly to avoid transposes).
    Static [H, nb, nb] layout, 1 = attend; compute/HBM scale with active
    blocks, not T^2.  ``block`` is the layout granularity; kernel tiles fuse
    ``block_mult`` layout blocks per side.  Fully-masked rows produce zeros
    (matching the dense-mask reference path's safe-softmax guard)."""
    B, H, T, D = q.shape
    scale = softmax_scale if softmax_scale is not None else 1.0 / (D ** 0.5)
    layout = np.ascontiguousarray(layout.astype(np.uint8))
    if layout.ndim == 2:
        layout = layout[None]
    bsa = _get_bsa(layout.tobytes(), layout.shape, block, causal, block_mult)
    if T % bsa.bq != 0:
        raise ValueError(f"T={T} not divisible by kernel tile {bsa.bq}")

    @jax.custom_vjp
    def run(qt, kt, vt):
        o, _ = bsa.fwd(qt, kt, vt, scale)
        return o

    def run_fwd(qt, kt, vt):
        o, lse = bsa.fwd(qt, kt, vt, scale)
        return o, (qt, kt, vt, o, lse)

    def run_bwd(res, g):
        qt, kt, vt, o, lse = res
        return bsa.bwd(qt, kt, vt, o, lse, g, scale)

    run.defvjp(run_fwd, run_bwd)
    return run(q, k, v)


def block_sparse_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                           layout: np.ndarray, block: int,
                           causal: bool = False,
                           softmax_scale: Optional[float] = None,
                           block_mult: int = 8) -> jax.Array:
    """[B, T, H, D] convenience wrapper over
    :func:`block_sparse_attention_bhsd`."""
    out = block_sparse_attention_bhsd(
        jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
        layout, block, causal=causal, softmax_scale=softmax_scale,
        block_mult=block_mult)
    return jnp.swapaxes(out, 1, 2)
