"""Unit tests for the two import points left in utils/jax_compat.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from deepspeed_tpu.utils.jax_compat import import_pltpu, shard_map


@pytest.mark.parametrize("kwargs", [
    {}, {"check_vma": False}, {"axis_names": {"x"}},
], ids=["plain", "check_vma", "axis_names"])
def test_shard_map_passes_the_modern_signature_through(kwargs):
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("x",))
    f = shard_map(lambda x: x * 2, mesh=mesh, in_specs=P(), out_specs=P(),
                  **kwargs)
    np.testing.assert_allclose(np.asarray(f(jnp.arange(4.0))),
                               2 * np.arange(4.0))


def test_import_pltpu_is_the_pallas_tpu_module():
    from jax.experimental.pallas import tpu as pltpu
    got = import_pltpu()
    assert got is pltpu
    assert hasattr(got, "CompilerParams")
