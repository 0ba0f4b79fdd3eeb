"""Operations and bytes the latent-attention paged kernel needs for a call,
from the call's live contexts (``ops/pallas/mla_attention.py``: scopes
``mla_decode`` and ``mla_chunk``), and its share of the roofline.

What the algorithm needs, not what the kernel happens to move: a cached
token's row is read ONCE for all heads and holds ``kv_lora_rank +
qk_rope_head_dim`` values (the lane padding the pool stores beside them is
not needed work); every query head scores against the whole row and reads
its output from the row's first ``kv_lora_rank`` values. At the published
widths (512 + 64, 32 heads, bfloat16): 1,152 B and 32 x (576 + 512) x 2 =
69,632 operations a cached token a layer, 60 operations a byte against a
v5e's 240 — memory-bound by the count, four times denser than a kernel that
reads keys and values per head.
"""

from __future__ import annotations

from typing import Dict, Iterable, Tuple


def row_bytes(kv_lora_rank: int, qk_rope_head_dim: int,
              itemsize: int = 2) -> int:
    """Bytes of a cached token a layer that attention needs."""
    return (kv_lora_rank + qk_rope_head_dim) * itemsize


def token_flops(heads: int, kv_lora_rank: int, qk_rope_head_dim: int) -> int:
    """Operations a query token spends on one cached token, all heads: the
    score over the whole row, the output from its latent part."""
    return heads * 2 * ((kv_lora_rank + qk_rope_head_dim) + kv_lora_rank)


def decode_call(ctx_lens: Iterable[int], heads: int, kv_lora_rank: int,
                qk_rope_head_dim: int, itemsize: int = 2,
                side_rows: int = 0) -> Tuple[int, int]:
    """``(operations, bytes)`` of one call for decode rows: each row reads
    its ``ctx`` cached tokens (and ``side_rows`` rows of its side slab), the
    queries come in and the latent outputs go out once a row."""
    ctx = [int(c) for c in ctx_lens]
    tokens = sum(ctx) + side_rows * len(ctx)
    width = kv_lora_rank + qk_rope_head_dim
    io = len(ctx) * heads * (width + kv_lora_rank) * itemsize
    return (tokens * token_flops(heads, kv_lora_rank, qk_rope_head_dim),
            tokens * row_bytes(kv_lora_rank, qk_rope_head_dim, itemsize) + io)


def chunk_call(slots: Iterable[Tuple[int, int, int]], heads: int,
               kv_lora_rank: int, qk_rope_head_dim: int,
               itemsize: int = 2) -> Tuple[int, int]:
    """``(operations, bytes)`` of one call for prompt-chunk slots ``(q0, n,
    ctx)``: ``n`` query tokens from position ``q0``, token ``i`` of them
    seeing ``min(ctx, q0 + i + 1)`` cached tokens; the slot's ``ctx`` rows
    are read once (the kernel reads them once a block of queries: that is
    its cost, not the algorithm's)."""
    width = kv_lora_rank + qk_rope_head_dim
    flops = bytes_ = 0
    for q0, n, ctx in slots:
        seen = sum(min(int(ctx), int(q0) + i + 1) for i in range(int(n)))
        flops += seen * token_flops(heads, kv_lora_rank, qk_rope_head_dim)
        bytes_ += int(ctx) * row_bytes(kv_lora_rank, qk_rope_head_dim,
                                       itemsize)
        bytes_ += int(n) * heads * (width + kv_lora_rank) * itemsize
    return flops, bytes_


def roofline(flops: float, bytes_: float, seconds: float,
             peaks: Dict[str, float]) -> Dict[str, float]:
    """The least time the chip could take for that work over the time it
    took: ``share`` in percent, and which bound is the larger (``bound``:
    ``"memory"`` or ``"compute"``)."""
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = bytes_ / peaks["hbm_bytes_per_s"]
    return {"share": 100.0 * max(t_flops, t_bytes) / seconds,
            "bound": "memory" if t_bytes >= t_flops else "compute",
            "compute_s": t_flops, "memory_s": t_bytes}
