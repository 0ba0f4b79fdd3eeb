"""FLOPs the flash-attention kernels execute (``ops/pallas/flash_attention.py``),
from a call's shapes: blocks the causal mask skips are not counted, blocks on
the diagonal are counted whole, as the kernel computes them.

Per computed (block_q x block_k) tile and head, with head size D:
forward 2 matmuls (``q k^T``, ``p v``) = 4 bq bk D; backward-dq 3 (``q k^T``,
``do v^T``, ``ds k``) = 6 bq bk D; backward-dkv 4 (``q k^T``, ``do v^T``,
``p^T do``, ``ds^T q``) = 8 bq bk D. The block rule is the kernel's own
(``_pick_block`` with its defaults of 1024), copied here because the
benchmark keeps its own arithmetic; ``tests/chipbench/test_named.py`` holds
the copy to the kernel's.
"""

from __future__ import annotations

DEFAULT_BLOCK = 1024
MATMULS = {"flash_fwd": 2, "flash_bwd_dq": 3, "flash_bwd_dkv": 4}


def pick_block(t: int, preferred: int = DEFAULT_BLOCK) -> int:
    b = min(preferred, t)
    while t % b != 0:
        b //= 2
    return max(b, 1)


def computed_tiles(t_q: int, t_k: int, bq: int, bk: int, causal: bool) -> int:
    """Tiles whose ``should_run`` holds: ``ik * bk <= iq * bq + bq - 1``."""
    nq, nk = t_q // bq, t_k // bk
    if not causal:
        return nq * nk
    return sum(1 for iq in range(nq) for ik in range(nk)
               if ik * bk <= iq * bq + bq - 1)


def call_flops(kernel: str, batch: int, heads: int, seq: int, head_dim: int,
               causal: bool = True) -> float:
    """FLOPs one call of ``kernel`` executes on ``[batch, heads, seq,
    head_dim]`` self-attention."""
    bq = bk = pick_block(seq)
    tiles = computed_tiles(seq, seq, bq, bk, causal)
    return 2.0 * MATMULS[kernel] * bq * bk * head_dim * tiles * batch * heads
