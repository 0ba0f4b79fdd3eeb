"""Operations and bytes the two power-retention kernels need for a call
(``ops/pallas/power_retention.py``: ``pr_decode_step`` under the scope
``pr/step``, ``pr_chunk_scan`` under ``pr/scan``), from the call's widths and
the rows that were LIVE in it.

What the algorithm needs, not what the kernel happens to move or compute: a
reading made with these is a lower bound of the kernel's share of its
roofline. A KV head's state is ``d (d + 1) / 2`` pairs (8,256 at ``d`` 128:
the layout's 65 tiles of 128 lanes hold 64 empty entries, and the eight
normalisers ride in one tile of 8 sublanes — neither counts) by ``d`` value
channels and one normaliser, float32; a row of a padded bucket moves the dump
slot's state and counts nothing; a float32 product that the kernel issues as
several bfloat16 passes counts once.

- A decode row reads its state once and writes it once — ``2 x Hk x pairs x
  (d + 1) x 4`` bytes a layer (65 MiB at 8 heads of 128) — beside its
  operands (q, k and v in the model's dtype, the gate a head) and its result
  (float32). Per state value a multiply for the decay, a multiply-add for the
  key's write and one a query head for the read: ``3 + 2 G`` operations, 13
  at ``G`` 5, under two an operation a byte against a v5e's 240. Bytes bind.
- A prompt token in the chunked form at chunk ``C``, a query head (each
  product once, a causal product at the half the mask keeps): its row of
  ``q k^T`` and of the weights' product with ``v``, ``2 x C d``; its read of
  the state before the chunk, ``2 x pairs x (d + 1)``; and a KV head's part
  of the chunk's write, ``2 x pairs x (d + 1)``. Its bytes: q, k, v (and v
  transposed) in the model's dtype, the output in float32, the running sums a
  KV head twice; and each live slot's state in and out once.
"""

from __future__ import annotations

from typing import Tuple


def pairs(d: int) -> int:
    """The entries of a head's expansion that hold a pair."""
    return d * (d + 1) // 2


def state_bytes(kv_heads: int, d: int) -> int:
    """One (sequence, layer) state: ``S`` and ``z`` of every KV head."""
    return kv_heads * pairs(d) * (d + 1) * 4


def decode_call(rows: float, heads: int, kv_heads: int, d: int,
                itemsize: int = 2) -> Tuple[float, float]:
    """``(operations, bytes)`` of one ``pr_decode_step`` call over ``rows``
    live rows of one layer."""
    G = heads // kv_heads
    io = (heads + 2 * kv_heads) * d * itemsize + kv_heads * 4 + heads * d * 4
    return (rows * (3.0 + 2 * G) * kv_heads * pairs(d) * (d + 1),
            rows * (2.0 * state_bytes(kv_heads, d) + io))


def scan_token_flops(heads: int, kv_heads: int, d: int, chunk: int) -> int:
    """Operations a prompt token needs a layer in the chunked form."""
    return (heads * (2 * chunk * d + 2 * pairs(d) * (d + 1))
            + kv_heads * 2 * pairs(d) * (d + 1))


def scan_call(tokens: float, slots: float, heads: int, kv_heads: int, d: int,
              chunk: int, itemsize: int = 2) -> Tuple[float, float]:
    """``(operations, bytes)`` of one ``pr_chunk_scan`` call over ``tokens``
    live rows in ``slots`` live chunk slots of one layer."""
    per_token = (heads + 3 * kv_heads) * d * itemsize + heads * d * 4 \
        + 2 * kv_heads * 4
    return (tokens * float(scan_token_flops(heads, kv_heads, d, chunk)),
            tokens * float(per_token)
            + slots * 2.0 * state_bytes(kv_heads, d))


def widths(config) -> dict:
    """The kernels' widths from a configuration file's published keys (and
    the chunk it assumes)."""
    return {"heads": int(config["num_attention_heads"]),
            "kv_heads": int(config["num_key_value_heads"]),
            "d": int(config["head_dim"]),
            "layers": int(config["num_hidden_layers"]),
            "chunk": int(config["assumed_numbers"]["chunk_size"])}
