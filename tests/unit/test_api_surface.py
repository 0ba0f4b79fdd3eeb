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


def _engine_has_no_burst():
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
    assert not hasattr(InferenceEngineV2, "decode_steps")
    assert hasattr(InferenceEngineV2, "decode_pipeline")


def _warmup_takes_no_burst_lengths():
    import inspect
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
    assert list(inspect.signature(InferenceEngineV2.warmup).parameters) == [
        "self", "buckets", "spec_ks"]


def _config_has_no_burst_lengths():
    from deepspeed_tpu.inference.v2.config_v2 import (
        CompileConfig, RaggedInferenceEngineConfig)
    gone = "warmup_" + "decode_steps"   # in two: no file spells the option
    with pytest.raises(TypeError, match=gone):
        CompileConfig(**{gone: [4]})
    with pytest.raises(TypeError, match=gone):
        RaggedInferenceEngineConfig.load(
            {"compile": {"warmup": True, gone: [4]}})


def _no_builder_takes_a_step_count():
    import inspect
    from deepspeed_tpu.inference.v2 import ragged_mla, ragged_model
    builders = {f"{m.__name__.rsplit('.', 1)[1]}.{n}": f
                for m in (ragged_model, ragged_mla)
                for n, f in vars(m).items()
                if n.lstrip("_").startswith("build_") and callable(f)}
    assert "ragged_model.build_decode_step" in builders
    assert "ragged_mla.build_decode_step" in builders
    assert not [n for n in builders if "multistep" in n]
    for name, f in builders.items():
        assert "n_steps" not in inspect.signature(f).parameters, name


@pytest.mark.parametrize("check", [
    _engine_has_no_burst, _warmup_takes_no_burst_lengths,
    _config_has_no_burst_lengths, _no_builder_takes_a_step_count],
    ids=lambda f: f.__name__.lstrip("_"))
def test_one_program_decodes_a_token(check):
    """The n-step burst (PR 45): no method, warm-up family, option or builder
    argument chooses how many tokens one dispatch decodes — one."""
    check()
