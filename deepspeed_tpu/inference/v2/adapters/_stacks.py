"""What the adapters share: stacking layers' canonical weights as the layer
loop scans them, and padding expert stacks to whole lane tiles. Eager
transforms of weights; no traced program calls them."""

from __future__ import annotations

from typing import Any, Callable, List, Tuple

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2.model_spec import RaggedModelSpec, layer_units


def _stack(trees: List[Any]) -> Any:
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


def _stack_leaf_by_leaf(trees: List[Any]) -> Any:
    """:func:`_stack`, waiting for each leaf's stack before the next leaf is
    begun. Layers handed over on the host are put on the device as they are
    stacked, and nothing waits for a dispatch: :func:`_stack` has every
    leaf's layers AND every leaf's stack on the device before the first copy
    is done and freed — the layers' bytes twice, which six layers of 128
    experts (7.25 GiB) do not leave room for beside what a 16 GB chip holds
    later. Here the device holds the stacks made so far, and one leaf twice
    (15.38 GiB -> under the weights and pool's own 13.7 as the engine comes
    up: chip, PR 61)."""
    return jax.tree_util.tree_map(
        lambda *xs: jax.block_until_ready(jnp.stack(xs)), *trees)


def _stack_units(spec: RaggedModelSpec, layer: Callable[[int], Any]) -> Tuple:
    """``weights["layers"]`` of a model of several kinds, from ``layer(i)``
    (layer ``i``'s canonical weights): one entry per unit of
    :func:`layer_units` — a run's layers stacked, or for a unit of p kinds a
    tuple of p trees, tree k stacking layer k of each of its repeats."""
    return tuple(
        _stack([layer(l0 + i) for i in range(n)]) if len(specs) == 1 else
        tuple(_stack([layer(l0 + i * len(specs) + k) for i in range(n)])
              for k in range(len(specs)))
        for specs, l0, n in layer_units(spec))


def _pad_expert_width(w_up: jax.Array, w_down: jax.Array):
    """Two-matrix experts ``[E, hid, F]``, ``[E, F, hid]`` with ``F`` padded
    with zeros to whole 128-lane tiles (nemotron_h: 1856 -> 1920, 3.4% more
    bytes). The result is the same — ``act(0) = 0`` for every plain
    activation here but gelu's, whose 0 it is too, and a zero row of
    ``w_down`` adds nothing — and both grouped kernels need it: the chip
    lays a ``[.., 2688, 1856]`` array out with 2688 on the lanes (no padding
    that way), so a kernel that wants rows of 1856 is first handed a
    transposed COPY of the whole stack (1.2 GiB a two-layer unit; compile,
    PR 42), and XLA's ``ragged_dot`` reads the unpadded matrices at 87 GB/s
    (chip table, PR 42)."""
    pad = -w_up.shape[-1] % 128
    if not pad:
        return w_up, w_down
    return (jnp.pad(w_up, ((0, 0), (0, 0), (0, pad))),
            jnp.pad(w_down, ((0, 0), (0, pad), (0, 0))))
