"""Operations and bytes the attention of a BLOCK STEP needs for a call — the
pass of a model that generates by diffusion over blocks
(``deepspeed_tpu/inference/v2/ragged_model.py::build_block_step``: the batched
chunk kernel ``paged_chunk`` under the block rule, one slot a live row, the
row's ``B`` block rows as its queries) — from a configuration's widths
(``kv_work.widths``) and what the program says its rows held: the ``rows``
and ``ctx_tokens`` arguments of its ``serve/block/step`` spans.

What the algorithm needs, not what the kernel happens to move: a live row's
``B`` queries see its whole cached context and its own block, ``ctx + B``
keys, which are read ONCE a pass a layer at a token's K and V (2 x KV heads x
head width values: 2 KiB at 4 heads of 128 in bfloat16) — whole pages are what
the kernel copies, so a reading is a lower bound — and its ``B`` queries come
in and its ``B`` outputs go out once. A query-key pair costs a query head ``4
x head width`` operations: ``B x 4 x heads x head width`` a key, 32 to a byte
at ``B`` = 4, 32 heads over 4 — against a v5e's 240, so bytes bind.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple


def block_call(w: Dict[str, Any], block: int, rows: float,
               ctx_tokens: float) -> Tuple[float, float]:
    """``(operations, bytes)`` of one layer's attention in one block step
    over ``rows`` live rows that hold ``ctx_tokens`` cached tokens between
    them (their block's ``block`` rows not counted in it)."""
    keys = float(ctx_tokens) + float(rows) * block
    queries = float(rows) * block
    pair_flops = 4 * w["heads"] * w["head_dim"]
    row_io = 2 * w["heads"] * w["head_dim"] * w["itemsize"]
    return block * keys * pair_flops, keys * w["token_bytes"] + queries * row_io
