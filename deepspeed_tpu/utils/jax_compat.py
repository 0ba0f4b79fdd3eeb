"""The package's import points for ``shard_map`` and the Pallas TPU module.

Both are plain pass-throughs on the one installation there is (jax 0.9,
where ``jax.shard_map`` and ``pltpu.CompilerParams`` exist under those
names). They remain so the call sites and jaxlint JL006, which sends every
such import here, need not change before ROADMAP D12 removes this module:

- ``from deepspeed_tpu.utils.jax_compat import shard_map``
- ``pltpu = jax_compat.import_pltpu()``
"""

from __future__ import annotations


def shard_map(*args, **kwargs):
    """``jax.shard_map``."""
    import jax

    return jax.shard_map(*args, **kwargs)


def import_pltpu():
    """``jax.experimental.pallas.tpu``."""
    from jax.experimental.pallas import tpu as pltpu  # jaxlint: disable=JL006
    return pltpu
