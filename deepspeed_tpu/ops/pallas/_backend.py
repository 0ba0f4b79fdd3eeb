"""Where this package's Pallas kernels run.

One answer for every kernel module: compiled by Mosaic on a TPU, run through
the Pallas interpreter on the CPU backend (the test suite), refused anywhere
else. Kernel modules read it at call time as ``_backend.interpret()`` so a
test that compiles a kernel for a described (unattached) TPU steers all of
them from this one function.
"""

from __future__ import annotations

import jax


def interpret() -> bool:
    """The ``interpret=`` argument of every ``pl.pallas_call`` here."""
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels in deepspeed_tpu compile for 'tpu' and interpret on "
        f"'cpu'; the default JAX backend is {platform!r}")
