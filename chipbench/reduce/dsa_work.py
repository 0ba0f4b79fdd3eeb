"""Operations and bytes the selection's kernels need for a call
(``ops/pallas/sparse_mla.py``: ``dsa_index_decode`` / ``dsa_index_chunk``
under the scope ``index/score``, ``dsa_attend_decode`` under ``decode``,
``dsa_attend_chunk`` under ``prefill``), from the call's widths and what its
rows held.

What the algorithm needs, not what a kernel happens to move or compute, so a
reading made with these is a lower bound of the kernel's share of its
roofline:

- the INDEX of a query token against one cached token: ``Hi`` heads' dot
  products of ``Di`` values, ``Hi x Di x 2`` operations (8,192 at 32 heads
  of 128; the relu, the weights and the sum over heads are not counted), and
  the token's index key read once a call whoever many queries score it:
  ``Di x 2`` bytes (256 B). A decode row reads its whole context's keys and
  computes little: bytes bind. A chunk slot's 256 queries share the reads:
  operations bind.
- ATTENTION of a query token over one CHOSEN token, all heads, absorbed:
  the score over the whole latent row and the output from its latent part,
  ``H x 2 x ((R + dr) + R)`` operations (139,264 at 64 heads, 512 + 64), and
  the row's ``(R + dr) x 2`` bytes (1,152 B) read once a call. A decode row
  attends ``min(ctx, topk)`` rows — what its gather read. A chunk's query at
  position ``t`` attends ``min(t + 1, topk)`` positions: a kernel that walks
  every page below the query under a mask (``dsa_attend_chunk`` today)
  computes ``t + 1`` and reads LOW by design; the slot's rows below its last
  query are read once.
"""

from __future__ import annotations

from typing import Dict, Iterable, Tuple


def widths(config) -> Dict[str, int]:
    """The kernels' widths from a configuration file's published keys."""
    return {"layers": int(config["num_hidden_layers"]),
            "heads": int(config["num_attention_heads"]),
            "rank": int(config["kv_lora_rank"]),
            "rope": int(config["qk_rope_head_dim"]),
            "index_heads": int(config["index_n_heads"]),
            "index_dim": int(config["index_head_dim"]),
            "topk": int(config["index_topk"])}


def index_pair_flops(w: Dict[str, int]) -> int:
    return w["index_heads"] * w["index_dim"] * 2


def attend_pair_flops(w: Dict[str, int]) -> int:
    return w["heads"] * 2 * ((w["rank"] + w["rope"]) + w["rank"])


def index_decode_call(w, rows: int, ctx: int, itemsize: int = 2
                      ) -> Tuple[float, float]:
    """``(operations, bytes)`` of one layer's ``dsa_index_decode`` call:
    ``rows`` live rows whose contexts sum to ``ctx`` (a call is linear in a
    row's context); the queries and weights in, a float32 score out."""
    io = rows * w["index_heads"] * (w["index_dim"] * itemsize + 4)
    return (float(ctx) * index_pair_flops(w),
            float(ctx) * (w["index_dim"] * itemsize + 4) + io)


def attend_decode_call(w, ctxs_capped: float, rows: int, itemsize: int = 2
                       ) -> Tuple[float, float]:
    """``(operations, bytes)`` of one layer's ``dsa_attend_decode`` call:
    ``ctxs_capped`` the sum over live rows of ``min(ctx, topk)``."""
    width = w["rank"] + w["rope"]
    io = rows * w["heads"] * (width + w["rank"]) * itemsize
    return (float(ctxs_capped) * attend_pair_flops(w),
            float(ctxs_capped) * width * itemsize + io)


def _slots(ntok: Iterable[int], cached: Iterable[int]):
    return [(int(c), int(n)) for n, c in zip(ntok, cached) if int(n)]


def index_chunk_call(w, ntok, cached, itemsize: int = 2
                     ) -> Tuple[float, float]:
    """``(operations, bytes)`` of one layer's ``dsa_index_chunk`` call over
    a paged pass's slots: slot ``(cached, n)`` holds ``n`` queries from
    position ``cached``, query ``i`` scoring ``cached + i + 1`` tokens; the
    slot's ``cached + n`` keys are read once, a float32 score a pair
    written."""
    flops = bytes_ = 0.0
    for c, n in _slots(ntok, cached):
        pairs = n * c + n * (n + 1) // 2
        flops += pairs * index_pair_flops(w)
        bytes_ += (c + n) * w["index_dim"] * itemsize + pairs * 4 \
            + n * w["index_heads"] * (w["index_dim"] * itemsize + 4)
    return flops, bytes_


def attend_chunk_call(w, ntok, cached, itemsize: int = 2
                      ) -> Tuple[float, float]:
    """``(operations, bytes)`` of one layer's ``dsa_attend_chunk`` call:
    query ``i`` of a slot attends ``min(cached + i + 1, topk)`` positions;
    the slot's rows are read once, its scores once."""
    width = w["rank"] + w["rope"]
    flops = bytes_ = 0.0
    for c, n in _slots(ntok, cached):
        seen = sum(min(c + i + 1, w["topk"]) for i in range(n))
        flops += seen * attend_pair_flops(w)
        bytes_ += (c + n) * width * itemsize \
            + (n * c + n * (n + 1) // 2) * 4 \
            + n * w["heads"] * (width + w["rank"]) * itemsize
    return flops, bytes_
