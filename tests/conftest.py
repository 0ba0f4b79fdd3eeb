"""Test harness: N virtual CPU devices standing in for a TPU slice.

Parity with the reference's distributed-in-one-box harness
(``tests/unit/common.py DistributedTest`` — N local worker processes over NCCL/gloo):
on JAX we instead force the host platform to expose 8 virtual CPU devices
(``xla_force_host_platform_device_count``) and run real SPMD shardings over them in
one process. Multi-rank semantics (allgather/reduce-scatter/all-to-all layouts,
dp-resize checkpointing) are exercised exactly as the reference exercises them with
N local processes.
"""

import os

# Must be set before jax is imported anywhere.
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                               + os.environ.get("XLA_FLAGS", ""))
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("DSTPU_LOG_LEVEL", "warning")

import jax  # noqa: E402
import pytest  # noqa: E402

# tests always run on the 8-device virtual CPU mesh, whatever the machine
# holds: force the platform at the config level too
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_threefry_partitionable", True)

# Persistent XLA compile cache: the suite is compile-dominated (engine fused
# steps, ragged decode programs, ...), and a warm rerun is several times
# faster. One helper decides the directory (utils/compile_cache.py): where
# JAX_COMPILATION_CACHE_DIR is set the suite uses it as it stands.
from deepspeed_tpu.utils.compile_cache import setup_compile_cache  # noqa: E402

setup_compile_cache(min_compile_time_secs=0.5)


@pytest.fixture(autouse=True)
def _reset_global_state():
    """Fresh topology/comms-logger per test."""
    yield
    from deepspeed_tpu.comm import reset_topology, get_comms_logger
    reset_topology()
    get_comms_logger().reset()
    get_comms_logger().configure(enabled=False)


@pytest.fixture
def eight_devices():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    return devs[:8]


@pytest.fixture
def tmp_ckpt_dir(tmp_path):
    return str(tmp_path / "ckpt")
