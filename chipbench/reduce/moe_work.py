"""Operations and bytes an MoE layer's grouped products need for a call, and
the two Mamba-2 (SSD) state kernels' work where B and C come in groups.

What the algorithm needs, counted from the PUBLISHED shapes, whatever kernel
or padding implements them (a stack stored zero-padded to whole lane tiles is
still counted at the published width).

- One grouped product of a layer (``up``: ``[rows, hidden] x [hidden, width]``
  an expert; ``down``: the other way): every expert that some row chose is
  read once, ``hidden x width x itemsize`` bytes; the rows come in and go out
  once; ``2 x rows x hidden x width`` operations. A decode step's few rows an
  expert make it bytes, a prefill pass's hundreds make it operations.
- The SSD kernels with ``G`` groups of B and C (``reduce/ssd_work.py`` counts
  one): a decode row reads ``2 G N`` values of B and C where one group reads
  ``2 N``; a prompt token in the product form needs a row of ``C B^T`` a
  group, ``G x 2 Q N`` operations, and carries ``3 G N`` values of B and C
  (and B transposed).
"""

from __future__ import annotations

from typing import Tuple

from chipbench.reduce import ssd_work


def grouped_product(rows: float, touched: float, hidden: int, width: int,
                    itemsize: int = 2) -> Tuple[float, float]:
    """``(operations, bytes)`` of one grouped product over ``rows``
    assignments that reach ``touched`` experts of one layer."""
    matrix = hidden * width
    return (2.0 * rows * matrix,
            (touched * matrix + rows * (hidden + width)) * float(itemsize))


def expert_layer(rows: float, touched: float, hidden: int, width: int,
                 matrices: int = 2, itemsize: int = 2) -> Tuple[float, float]:
    """``(operations, bytes)`` of a layer's ``matrices`` grouped products
    (2: up and down; 3 with a gate)."""
    flops, bytes_ = grouped_product(rows, touched, hidden, width, itemsize)
    return matrices * flops, matrices * bytes_


def ssd_decode_call(rows: float, d_inner: int, d_state: int, conv_width: int,
                    d_conv: int, groups: int) -> Tuple[float, float]:
    """``ssd_work.decode_call`` with ``groups`` pairs of B and C a row."""
    flops, bytes_ = ssd_work.decode_call(rows, d_inner, d_state, conv_width,
                                         d_conv)
    return flops, bytes_ + rows * 2.0 * (groups - 1) * d_state * 4


def ssd_scan_call(tokens: float, slots: int, heads: int, d_head: int,
                  d_state: int, chunk: int, groups: int
                  ) -> Tuple[float, float]:
    """``ssd_work.scan_call`` with ``groups`` pairs of B and C a token."""
    _, bytes_ = ssd_work.scan_call(tokens, slots, heads, d_head, d_state,
                                   chunk)
    flops = tokens * float(ssd_work.scan_token_flops(
        heads, d_head, d_state, chunk, groups))
    return flops, bytes_ + tokens * 3.0 * (groups - 1) * d_state * 4
