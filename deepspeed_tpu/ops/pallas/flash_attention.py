"""Flash attention for TPU (Pallas).

Role in the framework: the training-side fused attention kernel — the TPU
replacement for the reference's CUDA attention stack (softmax/attention kernels in
``csrc/transformer/inference`` and the CUTLASS blocked-flash wrapper in
``inference/v2/kernels/ragged_ops/blocked_flash``). Online-softmax tiling (flash-2
style): O(T) memory, statistics kept in VMEM scratch across the KV grid dimension.

Supports: causal masking, packed-sequence ``segment_ids``, GQA (kv heads repeated in
the wrapper), bf16/f32 inputs with f32 accumulation, and a custom VJP whose backward
is ONE call: each tile's probabilities are formed once from the saved logsumexp and
give dq, dk and dv (two calls past ``FUSED_BWD_VMEM_BYTES``) — no [T, T] anywhere.

Layouts: q, k, v are [B, T, H, D] publicly, [B, H, T, D] in-kernel; lse [B, H, T, 1].
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from deepspeed_tpu.ops.pallas import _backend


# Re-tuned on v5e-1 (B=64/T=1024 and B=16/T=2048, H=16, D=64, causal,
# fwd+bwd): 1024/1024 beats 512/512 by ~23% and ~6% respectively — the larger
# score tile (4 MB fp32) amortises grid overhead and stays well inside VMEM.
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024
NEG_INF = -1e30


def _pick_block(t: int, preferred: int) -> int:
    b = min(preferred, t)
    while t % b != 0:
        b //= 2
    return max(b, 1)


# --------------------------------------------------------------------------- #
# forward
# --------------------------------------------------------------------------- #


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_sc, m_sc, l_sc, *, scale, causal, block_q, block_k, nk, H):
    h, iq, ik = pl.program_id(1), pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _():
        m_sc[:] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc_sc[:] = jnp.zeros_like(acc_sc)

    should_run = True
    if causal:
        # skip blocks strictly above the diagonal
        should_run = ik * block_k <= iq * block_q + block_q - 1

    @pl.when(should_run)
    def _():
        q = q_ref[0, 0, :, :]  # [bq, d]
        k = k_ref[0, 0, :, :]  # [bk, d]
        v = v_ref[0, 0, :, :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            q_idx = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_idx = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(q_idx >= k_idx, s, NEG_INF)
        m_prev = m_sc[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_sc[:, 0:1] = l_sc[:, 0:1] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_sc[:, 0:1] = m_new
        acc_sc[:] = acc_sc[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _():
        l = l_sc[:, 0:1]
        # guard fully-masked rows (l == 0)
        safe_l = jnp.where(l > 0.0, l, 1.0)
        o_ref[0, 0, :, :] = (acc_sc[:] / safe_l).astype(o_ref.dtype)
        lse = m_sc[:, 0:1] + jnp.log(safe_l)
        lse_ref[0, 0, :, :] = jnp.where(l > 0.0, lse, NEG_INF)


def _fwd(q, k, v, scale: float, causal: bool,
         block_q: int, block_k: int) -> Tuple[jax.Array, jax.Array]:
    # internal layout: [B, H, T, D] (blocks must keep the last two dims tileable)
    B, H, T, D = q.shape
    Tk = k.shape[2]
    bq = _pick_block(T, block_q)
    bk = _pick_block(Tk, block_k)
    nq, nk = T // bq, Tk // bk
    grid = (B, H, nq, nk)

    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               block_q=bq, block_k=bk, nk=nk, H=H)
    call = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, iq, ik: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, iq, ik: (b, h, ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, iq, ik: (b, h, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, T, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=_backend.interpret(),
    )
    # the scope is the kernel's name in a device trace (op_name metadata)
    with jax.named_scope("flash_fwd"):
        o, lse = call(q, k, v)
    return o, lse


# --------------------------------------------------------------------------- #
# packed (ragged prefill) forward: rows from MANY sequences concatenated
# --------------------------------------------------------------------------- #


def _fwd_kernel_packed(segq_ref, segk_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                       acc_sc, m_sc, l_sc, *, scale, block_q, block_k, nk,
                       window=None):
    """Flash forward over PACKED rows: causal by global row index AND masked to
    same-segment pairs. Row order within a segment must be position order
    (true for ragged prefill batches: the scheduler fills slots in position
    order, multi-slot prompts take consecutive slots — asserted where the
    batch is built, scheduler.schedule_pass), so row-index causality equals
    position causality and cross-segment pairs are masked out. ``window``
    additionally hides same-segment pairs more than window-1 rows apart
    (row distance == position distance under the same invariant)."""
    iq, ik = pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _():
        m_sc[:] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc_sc[:] = jnp.zeros_like(acc_sc)

    # packed rows are globally causal by row index (see docstring)
    should_run = ik * block_k <= iq * block_q + block_q - 1
    if window is not None:
        should_run = should_run & \
            ((ik + 1) * block_k > iq * block_q - window + 1)

    @pl.when(should_run)
    def _():
        q = q_ref[0, 0, :, :]
        k = k_ref[0, 0, :, :]
        v = v_ref[0, 0, :, :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        q_idx = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_idx = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        seg_q = segq_ref[0, :].reshape(-1, 1)          # [bq, 1]
        seg_k = segk_ref[0, :].reshape(1, -1)          # [1, bk]
        mask = (q_idx >= k_idx) & (seg_q == seg_k)
        if window is not None:
            mask = mask & (q_idx - k_idx < window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_sc[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_sc[:, 0:1] = l_sc[:, 0:1] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_sc[:, 0:1] = m_new
        acc_sc[:] = acc_sc[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _():
        l = l_sc[:, 0:1]
        safe_l = jnp.where(l > 0.0, l, 1.0)
        o_ref[0, 0, :, :] = (acc_sc[:] / safe_l).astype(o_ref.dtype)
        lse = m_sc[:, 0:1] + jnp.log(safe_l)
        lse_ref[0, 0, :, :] = jnp.where(l > 0.0, lse, NEG_INF)


def flash_attention_packed(q: jax.Array, k: jax.Array, v: jax.Array,
                           segment_ids: jax.Array,
                           softmax_scale: Optional[float] = None,
                           block_q: int = 512, block_k: int = 512,
                           with_lse: bool = False,
                           window: Optional[int] = None):
    """Packed ragged-prefill flash attention (inference fast path; fwd only).

    q [R, H, D]; k [R, Hkv, D], v [R, Hkv, Dv] (GQA kv repeated in here; Dv
    may differ from D — latent attention's expanded form has q/k of 192 and
    v of 128); segment_ids [R]
    int32 — rows attend only same-segment rows at <= their own row index.
    Padding rows should carry segment -1 (they then attend only other padding,
    and their output is never read). Returns [R, H, Dv] (plus lse [R, H] fp32
    when ``with_lse`` — the hook for merging with paged prior-context
    attention).

    Parity role: the reference's ragged blocked_flash prefill kernels
    (``inference/v2/kernels/ragged_ops/blocked_flash``) — here the in-pass
    tokens attend each other DENSELY on the MXU instead of through per-slot
    paged reads (measured 13 ms/layer paged-chunk vs ~1 ms packed at
    32x128 rows, v5e-1).
    """
    R, H, D = q.shape
    Hkv, Dv = k.shape[1], v.shape[2]
    assert H % Hkv == 0
    rep = H // Hkv
    scale = softmax_scale if softmax_scale is not None else 1.0 / (D ** 0.5)
    if R % 128 != 0:
        # Mosaic wants tile-aligned row blocks regardless of R's magnitude
        R2 = -(-R // 128) * 128
        q = jnp.pad(q, ((0, R2 - R), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, R2 - R), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, R2 - R), (0, 0), (0, 0)))
        segment_ids = jnp.pad(segment_ids, ((0, R2 - R),), constant_values=-1)
    Rp = q.shape[0]
    bq = _pick_block(Rp, block_q)
    bk = _pick_block(Rp, block_k)
    nq, nk = Rp // bq, Rp // bk

    qT = jnp.swapaxes(q, 0, 1)[None]   # [1, H, Rp, D]
    kT = jnp.swapaxes(k, 0, 1)[None]   # [1, Hkv, Rp, D] — GQA via index map
    vT = jnp.swapaxes(v, 0, 1)[None]
    seg = segment_ids.astype(jnp.int32)[None]   # [1, Rp]

    kernel = functools.partial(_fwd_kernel_packed, scale=scale,
                               block_q=bq, block_k=bk, nk=nk, window=window)
    call = pl.pallas_call(
        kernel,
        grid=(H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq), lambda h, iq, ik: (0, iq)),   # seg (q side)
            pl.BlockSpec((1, bk), lambda h, iq, ik: (0, ik)),   # seg (k side)
            pl.BlockSpec((1, 1, bq, D), lambda h, iq, ik: (0, h, iq, 0)),
            # GQA: kv head = q head // rep, no materialised repeat
            pl.BlockSpec((1, 1, bk, D), lambda h, iq, ik: (0, h // rep, ik, 0)),
            pl.BlockSpec((1, 1, bk, Dv), lambda h, iq, ik: (0, h // rep, ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, Dv), lambda h, iq, ik: (0, h, iq, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda h, iq, ik: (0, h, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, H, Rp, Dv), q.dtype),
            jax.ShapeDtypeStruct((1, H, Rp, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, Dv), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_backend.interpret(),
    )
    with jax.named_scope("flash_fwd_packed"):
        o, lse = call(seg, seg, qT, kT, vT)
    out = jnp.swapaxes(o[0], 0, 1)[:R]
    if with_lse:
        return out, jnp.swapaxes(lse[0, :, :, 0], 0, 1)[:R]
    return out


# --------------------------------------------------------------------------- #
# backward
# --------------------------------------------------------------------------- #

# One call a layer gives all three gradients (``_bwd_fused_kernel``). It keeps
# dq's float32 sum for a whole (batch, head) — ``T x D x 4`` bytes — in VMEM
# beside the tile's temporaries; ``_fused_bwd_vmem_bytes`` counts both from the
# static shapes, and the call is compiled under that count as its VMEM limit.
# A call whose count is over this budget takes the two older calls instead
# (``_bwd_dq_kernel`` and ``_bwd_dkv_kernel``: each forms every tile's scores
# again, and holds one row block of dq at a time). Three quarters of a v5e's
# 128 MiB of VMEM: the fused call won at every length that fits it, 65,536
# rows of 128 among them (``scripts/flash_bwd_table.py``; PERF.md, PR 43).
FUSED_BWD_VMEM_BYTES = 96 * 2**20


def _fused_bwd_vmem_bytes(T, D, bq, bk, itemsize):
    """An upper bound on the VMEM the fused backward call needs, from its
    static shapes: dq's accumulator and its output block (double-buffered),
    the score tile's four float32 temporaries and two casts, and the
    pipelined row blocks. (The compiler takes the main shapes in half of
    it: it re-uses the tile's temporaries.)"""
    D = -(-D // 128) * 128                           # a row is whole lane tiles
    acc = T * D * (4 + 2 * itemsize)
    tile = bq * bk * (4 * 4 + 2 * itemsize)
    blocks = (2 * (2 * bq + 4 * bk) * D * itemsize   # q, do; k, v, dk, dv
              + 2 * 2 * bq * 128 * 4                 # lse, delta: a lane tile
              + 2 * bk * D * 4)                      # dk's and dv's sums
    return acc + tile + blocks


def _tile_p_ds(q, k, v, do, lse, delta, iq, ik, *, scale, causal,
               block_q, block_k):
    """One tile's probabilities and score gradients, float32 ``[bq, bk]``:
    ``S = q k^T``, the causal mask, ``P = exp(S - lse)`` (a row whose lse is
    +inf reads 0), ``dP = do v^T``, ``dS = P (dP - delta)``."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        q_idx = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_idx = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(q_idx >= k_idx, s, NEG_INF)
    p = jnp.exp(s - lse)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    return p, p * (dp - delta) * scale


def _bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, dq_sc, dk_sc, dv_sc,
                      *, scale, causal, block_q, block_k, nq, nk):
    """Grid ``(B, H, nk, nq)``, the key tile outer: dk and dv sum over the
    inner axis as in ``_bwd_dkv_kernel``; dq's row block ``iq`` sums over the
    outer one in ``dq_sc[T, D]``, in the order ``_bwd_dq_kernel`` sums it.
    ``dq_ref`` is the whole ``[T, D]`` of a (batch, head): it stays in VMEM
    over both inner axes, each row block is cast into it once, at its last
    visit (``ik == nk - 1``), and the pipeline writes it out once."""
    ik, iq = pl.program_id(2), pl.program_id(3)
    rows = pl.ds(pl.multiple_of(iq * block_q, block_q), block_q)

    @pl.when(iq == 0)
    def _():
        dk_sc[:] = jnp.zeros_like(dk_sc)
        dv_sc[:] = jnp.zeros_like(dv_sc)

    @pl.when(ik == 0)
    def _():
        dq_sc[rows, :] = jnp.zeros((block_q, dq_sc.shape[1]), dq_sc.dtype)

    should_run = True
    if causal:
        should_run = ik * block_k <= iq * block_q + block_q - 1

    @pl.when(should_run)
    def _():
        q = q_ref[0, 0, :, :]
        k = k_ref[0, 0, :, :]
        do = do_ref[0, 0, :, :]
        p, ds = _tile_p_ds(q, k, v_ref[0, 0, :, :], do, lse_ref[0, 0, :, :],
                           delta_ref[0, 0, :, :], iq, ik, scale=scale,
                           causal=causal, block_q=block_q, block_k=block_k)
        ds = ds.astype(q.dtype)
        dv_sc[:] += jax.lax.dot_general(p.astype(do.dtype), do,
                                        (((0,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)
        dk_sc[:] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)
        dq_sc[rows, :] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(iq == nq - 1)
    def _():
        dk_ref[0, 0, :, :] = dk_sc[:].astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_sc[:].astype(dv_ref.dtype)

    @pl.when(ik == nk - 1)
    def _():
        dq_ref[0, 0, rows, :] = dq_sc[rows, :].astype(dq_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_sc, *, scale, causal, block_q, block_k, nk):
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _():
        dq_sc[:] = jnp.zeros_like(dq_sc)

    should_run = True
    if causal:
        should_run = ik * block_k <= iq * block_q + block_q - 1

    @pl.when(should_run)
    def _():
        k = k_ref[0, 0, :, :]
        _, ds = _tile_p_ds(q_ref[0, 0, :, :], k, v_ref[0, 0, :, :],
                           do_ref[0, 0, :, :], lse_ref[0, 0, :, :],
                           delta_ref[0, 0, :, :], iq, ik, scale=scale,
                           causal=causal, block_q=block_q, block_k=block_k)
        dq_sc[:] += jax.lax.dot_general(ds.astype(k.dtype), k,
                                        (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _():
        dq_ref[0, 0, :, :] = dq_sc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_sc, dv_sc,
                    *, scale, causal, block_q, block_k, nq):
    ik, iq = pl.program_id(2), pl.program_id(3)

    @pl.when(iq == 0)
    def _():
        dk_sc[:] = jnp.zeros_like(dk_sc)
        dv_sc[:] = jnp.zeros_like(dv_sc)

    should_run = True
    if causal:
        # block contributes only if some q >= some k
        should_run = iq * block_q + block_q - 1 >= ik * block_k

    @pl.when(should_run)
    def _():
        q = q_ref[0, 0, :, :]
        do = do_ref[0, 0, :, :]
        p, ds = _tile_p_ds(q, k_ref[0, 0, :, :], v_ref[0, 0, :, :], do,
                           lse_ref[0, 0, :, :], delta_ref[0, 0, :, :], iq, ik,
                           scale=scale, causal=causal, block_q=block_q,
                           block_k=block_k)
        dv_sc[:] += jax.lax.dot_general(p.astype(do.dtype), do,
                                        (((0,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)
        dk_sc[:] += jax.lax.dot_general(ds.astype(q.dtype), q,
                                        (((0,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    @pl.when(iq == nq - 1)
    def _():
        dk_ref[0, 0, :, :] = dk_sc[:].astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_sc[:].astype(dv_ref.dtype)


def _bwd(scale, causal, block_q, block_k, residuals, g):
    # imported here: a line added above the forward kernels would re-key their
    # Mosaic programs (a kernel's source locations are part of its key)
    from deepspeed_tpu.monitor.trace import tracer as _tracer
    q, k, v, o, lse = residuals
    do = g
    B, H, T, D = q.shape
    Tk = k.shape[2]
    bq = _pick_block(T, block_q)
    bk = _pick_block(Tk, block_k)
    nq, nk = T // bq, Tk // bk

    # delta = rowsum(do * o): [B, H, T] (small, XLA fuses this fine)
    delta = jnp.einsum("bhtd,bhtd->bht", do.astype(jnp.float32),
                       o.astype(jnp.float32))[..., None]

    params = dict(scale=scale, causal=causal, block_q=bq, block_k=bk)
    semantics = ("parallel", "parallel", "parallel", "arbitrary")

    # grid (B, H, nk, nq), the key tile outer: the fused call's and dkv's
    def of_q(b, h, ik, iq):
        if causal:
            # a tile the mask skips asks for the rows of the first tile that
            # runs, so nothing is copied in for it (its copies had no
            # products to hide behind: 0.36 ms of the call's 2.88 at seq4k)
            iq = jnp.maximum(iq, jnp.minimum(ik * bk // bq, nq - 1))
        return (b, h, iq, 0)

    def of_k(b, h, ik, iq):
        return (b, h, ik, 0)

    in_specs = [
        pl.BlockSpec((1, 1, bq, D), of_q),
        pl.BlockSpec((1, 1, bk, D), of_k),
        pl.BlockSpec((1, 1, bk, D), of_k),
        pl.BlockSpec((1, 1, bq, D), of_q),
        pl.BlockSpec((1, 1, bq, 1), of_q),
        pl.BlockSpec((1, 1, bq, 1), of_q),
    ]
    dkv_specs = [pl.BlockSpec((1, 1, bk, D), of_k),
                 pl.BlockSpec((1, 1, bk, D), of_k)]
    dkv_shape = [jax.ShapeDtypeStruct(k.shape, k.dtype),
                 jax.ShapeDtypeStruct(v.shape, v.dtype)]
    dkv_sums = [pltpu.VMEM((bk, D), jnp.float32),
                pltpu.VMEM((bk, D), jnp.float32)]

    # counted where the rule is traced: the engine's log line says which
    vmem = _fused_bwd_vmem_bytes(T, D, bq, bk, q.dtype.itemsize)
    if vmem <= FUSED_BWD_VMEM_BYTES:
        _tracer.bump("train/flash/bwd_fused")
        fused_call = pl.pallas_call(
            functools.partial(_bwd_fused_kernel, nq=nq, nk=nk, **params),
            grid=(B, H, nk, nq),
            in_specs=in_specs,
            out_specs=[pl.BlockSpec((1, 1, T, D),
                                    lambda b, h, ik, iq: (b, h, 0, 0))]
            + dkv_specs,
            out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype)] + dkv_shape,
            scratch_shapes=[pltpu.VMEM((T, D), jnp.float32)] + dkv_sums,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary",
                                     "arbitrary"),
                vmem_limit_bytes=vmem),
            interpret=_backend.interpret(),
        )
        # under the dkv call's name: the benchmark's readers know the
        # backward by ``flash_bwd_dq|flash_bwd_dkv`` (PERF.md section 7)
        with jax.named_scope("flash_bwd_dkv"):
            dq, dk, dv = fused_call(q, k, v, do, lse, delta)
        return dq, dk, dv

    _tracer.bump("train/flash/bwd_split")
    dq_call = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, nk=nk, **params),
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, iq, ik: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, iq, ik: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, iq, ik: (b, h, iq, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics),
        interpret=_backend.interpret(),
    )
    with jax.named_scope("flash_bwd_dq"):
        dq = dq_call(q, k, v, do, lse, delta)

    dkv_call = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, nq=nq, **params),
        grid=(B, H, nk, nq),
        in_specs=in_specs,
        out_specs=dkv_specs,
        out_shape=dkv_shape,
        scratch_shapes=dkv_sums,
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics),
        interpret=_backend.interpret(),
    )
    with jax.named_scope("flash_bwd_dkv"):
        dk, dv = dkv_call(q, k, v, do, lse, delta)
    return dq, dk, dv


# --------------------------------------------------------------------------- #
# public entry
# --------------------------------------------------------------------------- #


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, scale, causal, block_q, block_k):
    o, _ = _fwd(q, k, v, scale, causal, block_q, block_k)
    return o


def _flash_fwd(q, k, v, scale, causal, block_q, block_k):
    o, lse = _fwd(q, k, v, scale, causal, block_q, block_k)
    # named for jax.checkpoint: a policy that saves these two names
    # (activation_checkpointing's "flash_residuals_saveable") keeps what the
    # backward kernels read of the forward, and the forward kernel is not
    # run a second time. q, k and v are the caller's to keep or recompute.
    o = checkpoint_name(o, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return o, (q, k, v, o, lse)


_flash.defvjp(_flash_fwd, _bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = False,
                    segment_ids: Optional[jax.Array] = None,
                    softmax_scale: Optional[float] = None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K) -> jax.Array:
    """Flash attention over [B, T, H, D] tensors.

    GQA: if k/v have fewer heads than q, they are repeated to match (the kernel
    itself is per-head, so this costs HBM reads, not extra FLOPs on the MXU).
    ``segment_ids`` packing falls back to the jnp reference path for now (the
    ragged/paged Pallas kernel in ``ops/pallas/paged_attention.py`` is the
    long-sequence packed path).
    """
    if segment_ids is not None:
        from deepspeed_tpu.ops.attention import reference_attention
        return reference_attention(q, k, v, causal=causal, segment_ids=segment_ids,
                                   softmax_scale=softmax_scale)
    B, T, H, D = q.shape
    Hkv = k.shape[2]
    if Hkv != H:
        assert H % Hkv == 0, f"GQA heads {H} not divisible by kv heads {Hkv}"
        rep = H // Hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scale = softmax_scale if softmax_scale is not None else 1.0 / (D ** 0.5)
    # Ragged T LARGER than the block: the divisor-halving block picker
    # degrades hard there (e.g. T=1032 at block 1024 halves all the way to
    # 8-row q-tiles — MXU-starved; T <= block_q always gets one full-length
    # tile and needs nothing). For causal SELF-attention, pad T to the next
    # 128-multiple instead (<= 12% extra rows, >= 128-row tiles): padded KEYS
    # sit at k_idx >= T > q_idx of every real row, so the existing causal
    # mask drops them with no kernel change, and padded QUERY rows are
    # sliced off. (pad/slice are differentiable, so the custom-vjp backward
    # sees the padded shapes too.)
    T_out = T
    if causal and T == k.shape[1] and T > block_q and T % 128 != 0:
        T2 = -(-T // 128) * 128
        pad = [(0, 0), (0, T2 - T), (0, 0), (0, 0)]
        q, k, v = (jnp.pad(t, pad) for t in (q, k, v))
    q, k, v = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))  # -> [B, H, T, D]
    out = _flash(q, k, v, scale, causal, block_q, block_k)
    return jnp.swapaxes(out, 1, 2)[:, :T_out]
