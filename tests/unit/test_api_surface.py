"""Reference-spelled API surface: a DeepSpeed user's import lines must resolve.

Parity check against the reference's public import surface
(``deepspeed/__init__.py`` + subpackage re-exports) — every line here mirrors
an import found in DeepSpeed tutorials/user code.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp


@pytest.mark.parametrize("module, names, exact", [
    ("deepspeed_tpu",
     ("initialize", "init_inference", "add_config_arguments",
      "zero", "pipe", "moe", "module_inject", "checkpoint",
      "monitor", "profiling", "runtime", "accelerator", "sequence",
      "DeepSpeedEngine", "PipelineModule", "OnDevice",
      "init_distributed", "checkpointing", "comm", "ops", "utils"), False),
    # the serving package exports these and nothing else: a load generator
    # is the benchmark's (chipbench/traffic/), not the library's
    ("deepspeed_tpu.inference.v2.serving",
     ("AdmissionController", "CostModel", "PrefillWorker", "Replica",
      "ServingCluster", "RequestHandle", "ServingFrontend", "DOWN",
      "DRAINING", "HEALTHY", "REJOINING", "SUSPECT", "HealthMonitor",
      "KVOffloadManager", "ClusterPrefixIndex", "ServingRouter"), True),
], ids=["root", "serving"])
def test_exported_names(module, names, exact):
    import importlib
    import types
    mod = importlib.import_module(module)
    for name in names:
        assert hasattr(mod, name), name
    if exact:
        public = {n for n, v in vars(mod).items() if not n.startswith("_")
                  and not isinstance(v, types.ModuleType)}
        assert public == set(names)


def test_reference_import_lines():
    from deepspeed_tpu.moe.layer import MoE                    # noqa: F401
    from deepspeed_tpu.moe.utils import is_moe_param           # noqa: F401
    from deepspeed_tpu.moe.sharded_moe import top1gating, top2gating  # noqa: F401
    from deepspeed_tpu.sequence.layer import DistributedAttention     # noqa: F401
    from deepspeed_tpu.pipe import (LayerSpec, PipelineModule,  # noqa: F401
                                    TiedLayerSpec)
    from deepspeed_tpu.zero import Init, GatheredParameters    # noqa: F401
    from deepspeed_tpu.accelerator import get_accelerator      # noqa: F401
    from deepspeed_tpu.ops.adam import FusedAdam               # noqa: F401
    from deepspeed_tpu.utils.numa import (check_for_numactl,   # noqa: F401
                                          get_numa_cores, get_numactl_cmd)
    assert get_accelerator() is not None


def test_zero_init_and_gathered_parameters():
    import deepspeed_tpu as ds
    import flax.linen as nn

    class M(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(8)(x)

    m = M()
    with ds.zero.Init():
        shapes = jax.eval_shape(lambda r: m.init(r, jnp.zeros((1, 4))),
                                jax.random.PRNGKey(0))
    assert all(hasattr(l, "shape") for l in jax.tree_util.tree_leaves(shapes))

    params = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 4)))
    with ds.zero.GatheredParameters(params) as host_params:
        leaves = jax.tree_util.tree_leaves(host_params)
        assert all(isinstance(np.asarray(l), np.ndarray) for l in leaves)


def test_layer_spec_builds():
    from deepspeed_tpu.pipe import LayerSpec
    spec = LayerSpec(dict, a=1)
    assert spec.build() == {"a": 1}


def test_numactl_cmd_shape():
    from deepspeed_tpu.utils.numa import get_numactl_cmd
    argv, cores = get_numactl_cmd("0-7", num_local_procs=2, local_rank=1)
    assert argv[0] == "numactl" and "-C" in argv
    assert cores == [4, 5, 6, 7]
