"""Operations and bytes the two Mamba-2 (SSD) state kernels need for a call
(``ops/pallas/ssm.py``: ``ssd_decode_step`` under the scope ``ssm/step``,
``ssd_chunk_scan`` under ``ssm/scan``), from the call's shapes and live rows.

What the algorithm needs, not what the kernel happens to move.

- A decode row reads its state once and writes it once — ``2 x N x E x 4``
  bytes (8 MiB at ``N`` 128, ``E`` 8,192) — and its convolution tail the same
  way (``2 x (K - 1) x W x 4``, the tail's values held in float32); per state
  value a multiply-add for the update and one for the output, 4 operations:
  0.5 an operation a byte against a v5e's 240. Bytes bound it.
- A prompt token in the product form at chunk size ``Q``, a head of ``P``
  channels over ``N`` state values: ``2 Q N`` for its row of ``C B^T`` (one
  group: once for all heads of the group), ``2 Q P`` for its row of the
  intra-chunk product, ``2 N P`` for the carried state's ``C S`` and ``2 N P``
  for its part of the chunk's new state. The matrices are float32 and the
  program multiplies them at the highest precision, which the MXU does in
  six bfloat16 passes: the floor counts the operations ONCE, against the
  published bfloat16 peak, so a reading of a sixth is the kernel at the
  MXU's rate. Its bytes: ``dt x`` in and ``y`` out a channel in float32, B and
  C (and B transposed), the running sums twice a head, and each slot's
  state in and out once.
"""

from __future__ import annotations

from typing import Tuple


def decode_call(rows: float, d_inner: int, d_state: int, conv_width: int,
                d_conv: int) -> Tuple[float, float]:
    """``(operations, bytes)`` of one ``ssd_decode_step`` call over ``rows``
    live rows of one layer."""
    state = d_state * d_inner
    tail = (d_conv - 1) * conv_width
    io = (3 * d_inner + 2 * d_state) * 4        # decay, dt x, y; B, C
    return rows * 4.0 * state, rows * (2.0 * 4 * (state + tail) + io)


def scan_token_flops(heads: int, d_head: int, d_state: int, chunk: int,
                     groups: int = 1) -> int:
    """Operations a prompt token needs a layer in the product form."""
    return (groups * 2 * chunk * d_state
            + heads * (2 * chunk * d_head + 4 * d_state * d_head))


def scan_call(tokens: float, slots: int, heads: int, d_head: int,
              d_state: int, chunk: int) -> Tuple[float, float]:
    """``(operations, bytes)`` of one ``ssd_chunk_scan`` call over ``tokens``
    live rows in ``slots`` chunk slots of one layer."""
    d_inner = heads * d_head
    per_token = (2 * d_inner + 3 * d_state + 2 * heads) * 4
    return (tokens * float(scan_token_flops(heads, d_head, d_state, chunk)),
            tokens * float(per_token) + slots * 2.0 * 4 * d_state * d_inner)
