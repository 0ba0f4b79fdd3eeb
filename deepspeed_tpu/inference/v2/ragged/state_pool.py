"""Per-sequence recurrent state beside the paged KV cache.

A state-space (Mamba) layer keeps, for every tracked sequence, a state of
fixed size whatever the sequence's length: the recurrence's ``h`` and the
causal convolution's tail (the last ``K - 1`` inputs of every convolved
channel: values of the model's dtype, held in float32). Two recurrences
share the pool (``ops/pallas/ssm.py`` states both):

- Mamba-1: ``h`` is ``[N, E]`` float32, ``N`` state values for each of ``E``
  channels (320 KiB a sequence a layer at ``N`` 16, ``E`` 5120), and the
  convolution runs over the ``E`` channels;
- Mamba-2 (SSD): a matrix ``[P, N]`` for each of ``H`` heads, held in the SAME
  layout ``[N, E]`` with ``E = H * P`` and channel ``h * P + p`` of head ``h``
  on the lanes (4 MiB a sequence a layer at ``N`` 128 and 128 heads of 64:
  ``N`` fills the sublanes, two heads a 128-lane tile, no padding), and the
  convolution runs over ``E + 2 G N`` channels — x, B and C together
  (``conv_dim``; 8,448 there), padded to whole tiles a tap (9,216).

A third tenant keeps NO recurrence: a layer of compressed convolutional
attention (zaya) writes K and V into pages as any attention layer does, and
beside them keeps the tail of the two small convolutions that mix its q and k
along the sequence, plus the one earlier value that its second value head is
(2 taps x 1,408 channels, padded to 2,048: 16 KiB a sequence a layer). Its
pool is :meth:`StatePoolConfig.tails_only`: ``ssm`` of zero size, ``conv`` as
below, and the layer updates a decode row's tail itself (no recurrence kernel
follows to do it).

A fourth tenant keeps NO tail: a power-retention layer (brumby;
``ops/pallas/power_retention.py``) holds, a KV head, the sum of values times
the key's symmetric square and a normaliser beside it — ``ssm`` with ``N =
Hk d + 8`` (the heads' value channels, then a normaliser a head) and ``E =
D`` (the square's ``d (d + 1) / 2`` pairs in ``d / 2 + 1`` tiles of ``d``
lanes: 1,032 x 8,320 = 32.75 MiB a sequence a layer at 8 heads of 128) — and
convolves nothing: ``d_conv`` is 1 and ``conv`` is of zero size, the mirror
of the third tenant. A model of such layers alone holds no pages at all: the
slots are everything a sequence costs the device.

Pages do not fit that: their lifetime follows tokens, the allocator frees and
shares them block by block, and a state can be neither shared nor rolled
back. So there is a second kind of per-sequence device state with a lifetime
of its own:

- a *slot* is taken when the scheduler first tracks a sequence and given
  back at ``flush``; there are as many slots as tracked sequences
  (``max_tracked_sequences``), so taking one cannot fail once admission has
  passed. Sizing rule: a slot costs :meth:`StatePoolConfig.bytes_per_slot`
  whatever the context, so where that is large (36.9 MiB over 9 Mamba-2
  layers; 163.8 MiB over 5 power-retention layers) the slots, not the pages,
  set how many sequences an engine tracks:
  whoever builds the engine takes ``(tracked + 1) x bytes_per_slot`` off the
  memory budget first and gives the pages the rest (docs/SERVING.md);
- a slot is zeroed when taken, not when freed: the first pass that runs a
  sequence's position 0 starts its state from zero instead of reading the
  slot (``RaggedBatch.chunk_state_mode``), so what a freed slot still holds
  is never read;
- the pools carry one slot more than the allocator hands out, the *dump*
  slot: padding rows of a bucket and empty chunk slots read and write it, as
  the KV pool's scratch page takes theirs.

Device arrays (``Lm`` state-space layers, ``NS`` slots, ``W`` the convolved
channels: ``E``, or ``conv_dim`` padded to a multiple of 1,024)::

    ssm  [Lm, NS + 1, N, E]               float32
    conv [Lm, NS + 1, (K - 1) * 8, W / 8] float32: tap j of a slot is its rows
                                          8j..8j+7, channel w at
                                          [w // (W/8), w % (W/8)]

``E`` lies on the lanes in both (``[.., E, N]`` with ``N = 16`` would pad
every 16 values to a 128-lane tile), and both are laid out so that one slot
of one layer is a whole number of device tiles (8 x 128 of 32 bits): a
kernel then moves a slot as one block (or, where a block would be 4 MiB, a
few blocks of channels), where it lies
(``ops/pallas/ssm.py::ssm_decode_step``, ``ssd_decode_step``). The tails hold
the model's dtype's values (what ``in_proj`` gave, exact in float32); as
``[.., K - 1, E]`` of that dtype their 3 rows would pad to 16, and as flat
rows ``[Lm * (NS + 1), (K - 1) * E]`` only XLA's row scatter could update
them, which costs by the row (3.3 ms of a 19 ms decode step at 128 rows x 26
layers; and laid out ``[Lm, NS + 1, ..]`` that flat view was a copy of the
pool in every layer of every step; both from the chip and the compiled step,
PR 31). They travel with the pages as one donated pytree
(:class:`StatefulKV`) through every serving program, which updates them by
slot, in place.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, List, NamedTuple, Optional

import jax.numpy as jnp


class StatefulKV(NamedTuple):
    """What a serving program of a model with state-space layers is handed
    (and hands back) in place of the bare page pool."""
    pages: Any          # the KV pool, or its (int8 values, scales) tuple
    ssm: Any            # [Lm, NS + 1, N, E] float32
    conv: Any           # [Lm, NS + 1, (K - 1) * 8, W / 8] float32


@dataclass
class StatePoolConfig:
    num_layers: int             # state-space layers (Lm)
    num_slots: int              # NS, the dump slot not counted
    d_inner: int                # E (Mamba-2: heads x head size)
    d_state: int                # N
    d_conv: int                 # K (1: no convolution, a tail of zero size)
    # channels the convolution runs over where they are not the E of the
    # state (Mamba-2: x, B and C together); None: E
    conv_dim: Optional[int] = None

    def __post_init__(self):
        if self.d_inner % 8:
            raise ValueError(f"d_inner {self.d_inner} is not a multiple of 8")

    @classmethod
    def tails_only(cls, num_layers: int, num_slots: int, taps: int,
                   channels: int) -> "StatePoolConfig":
        """The pool of layers that keep a convolution tail and NO recurrent
        state (compressed convolutional attention, beside its pages): ``ssm``
        is of zero size and a slot costs its ``taps`` x ``channels`` tail."""
        return cls(num_layers=num_layers, num_slots=num_slots, d_inner=0,
                   d_state=0, d_conv=taps + 1, conv_dim=channels)

    @property
    def conv_width(self) -> int:
        """``W``: the convolved channels as the tail pool holds a tap."""
        if self.conv_dim is None:
            return self.d_inner
        return -(-self.conv_dim // 1024) * 1024

    def bytes_per_slot(self) -> int:
        """One sequence's state over all layers."""
        return self.num_layers * 4 * (self.d_inner * self.d_state
                                      + self.conv_width * (self.d_conv - 1))

    def total_bytes(self) -> int:
        return (self.num_slots + 1) * self.bytes_per_slot()

    def zeros(self):
        """``(ssm, conv)``, freshly allocated."""
        L, S = self.num_layers, self.num_slots + 1
        return (jnp.zeros((L, S, self.d_state, self.d_inner), jnp.float32),
                jnp.zeros((L, S, (self.d_conv - 1) * 8, self.conv_width // 8),
                          jnp.float32))


class StateSlotAllocator:
    """Free list over the ``num_slots`` state slots, with the gauges the
    engine reports (``engine.state_slots()``)."""

    def __init__(self, num_slots: int):
        self.total = int(num_slots)
        self._free: List[int] = list(range(self.total - 1, -1, -1))
        self.peak = 0

    @property
    def live(self) -> int:
        return self.total - len(self._free)

    def take(self) -> int:
        if not self._free:
            raise RuntimeError(
                f"all {self.total} state slots are taken: one per tracked "
                "sequence (max_tracked_sequences)")
        slot = self._free.pop()
        self.peak = max(self.peak, self.live)
        return slot

    def free(self, slot: int) -> None:
        assert 0 <= slot < self.total and slot not in self._free, slot
        self._free.append(int(slot))
