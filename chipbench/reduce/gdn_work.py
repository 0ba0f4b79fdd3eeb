"""Operations and bytes the two gated delta-rule kernels need for a call
(``ops/pallas/gdn.py``: ``gdn_decode_step`` under the scope ``gdn/step``,
``gdn_chunk_scan`` under ``gdn/scan``), from the call's widths and the rows
that were LIVE in it.

What the algorithm needs, not what the kernel happens to move or compute: a
reading made with these is a lower bound of the kernel's share of its
roofline (a row of a padded bucket moves the dump slot's state too and counts
nothing here; the chunked scan builds a triangular inverse the algorithm does
not need and multiplies float32 matrices in six bfloat16 passes, counted
once).

- A decode row reads its state once and writes it once — ``2 x N x E x 4``
  bytes (4 MiB at ``N`` 128, ``E`` 4,096) — and its convolution tail the same
  way (``2 x (K - 1) x W x 4``, the tail's values held in float32); its
  operands: the decay, beta, v and the output a channel in float32, q and k a
  key head's ``N`` values each, the convolution's new input a convolved
  channel. Per state value a multiply for the decay, a multiply-add for
  ``S'^T k``, one for the correction and one for ``S^T q``: 7 operations,
  under one an operation a byte against a v5e's 240. Bytes bound it.
- A prompt token in the chunked form at chunk size ``Q``, a value head of
  ``P`` channels over a key head of ``N`` values (each product counted once,
  a causal product at the half of it the mask keeps): a key head's rows of
  ``K K^T`` and ``Q K^T``, ``2 x Q N``; a value head's ``K S`` and ``Q S``
  and its part of the chunk's new state, ``3 x 2 N P``; the triangular solve
  for its correction and the intra-chunk product, ``2 x Q P``. Its bytes: q
  and k (and k transposed) a key head in the model's dtype, v in it and the
  output in float32 a channel, the running sums, beta and their transposes a
  value head; and each live slot's state in and out once.
"""

from __future__ import annotations

from typing import Tuple


def decode_call(rows: float, key_heads: int, value_heads: int, d_key: int,
                d_value: int, d_conv: int) -> Tuple[float, float]:
    """``(operations, bytes)`` of one ``gdn_decode_step`` call over ``rows``
    live rows of one layer."""
    d_inner = value_heads * d_value
    state = d_key * d_inner
    conv_width = 2 * key_heads * d_key + d_inner
    tail = (d_conv - 1) * conv_width
    io = (4 * d_inner + 2 * key_heads * d_key + conv_width) * 4
    return rows * 7.0 * state, rows * (2.0 * 4 * (state + tail) + io)


def scan_token_flops(key_heads: int, value_heads: int, d_key: int,
                     d_value: int, chunk: int) -> int:
    """Operations a prompt token needs a layer in the chunked form."""
    return (key_heads * 2 * chunk * d_key
            + value_heads * (6 * d_key * d_value + 2 * chunk * d_value))


def scan_call(tokens: float, slots: float, key_heads: int, value_heads: int,
              d_key: int, d_value: int, chunk: int, itemsize: int = 2
              ) -> Tuple[float, float]:
    """``(operations, bytes)`` of one ``gdn_chunk_scan`` call over ``tokens``
    live rows in ``slots`` live chunk slots of one layer."""
    d_inner = value_heads * d_value
    per_token = (3 * key_heads * d_key + d_inner) * itemsize \
        + d_inner * 4 + 5 * value_heads * 4
    return (tokens * float(scan_token_flops(key_heads, value_heads, d_key,
                                            d_value, chunk)),
            tokens * float(per_token) + slots * 2.0 * 4 * d_key * d_inner)


def widths(config) -> dict:
    """The kernels' widths from a configuration file's published keys."""
    return {"key_heads": int(config["linear_num_key_heads"]),
            "value_heads": int(config["linear_num_value_heads"]),
            "d_key": int(config["linear_key_head_dim"]),
            "d_value": int(config["linear_value_head_dim"]),
            "d_conv": int(config["linear_conv_kernel_dim"]),
            "chunk": int(config.get("assumed_numbers", {}).get(
                "chunk_size", 64))}
