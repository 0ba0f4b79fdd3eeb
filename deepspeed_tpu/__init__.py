"""deepspeed_tpu — a TPU-native large-model training & inference framework.

Capability parity with DeepSpeed (reference ``deepspeed/__init__.py``): a single
``initialize(...)`` entry point building a training engine from model + config
(``deepspeed/__init__.py:64``), ``init_inference`` (``:269``), plus the comm, ops,
checkpoint, monitor and launcher subsystems — all re-designed for JAX/XLA on TPU:
device meshes + named shardings instead of process groups and hooks, XLA collectives
over ICI/DCN instead of NCCL, Pallas kernels instead of CUDA.
"""

__version__ = "0.1.0"
version = __version__

from deepspeed_tpu.config import DeepSpeedTPUConfig, ConfigError
from deepspeed_tpu import comm
from deepspeed_tpu import ops  # noqa: F401
from deepspeed_tpu.utils.logging import logger

# reference-spelled subpackage surface (parity: deepspeed/__init__.py imports
# ops/module_inject/zero/pipe/moe/... eagerly so `deepspeed.X` works)
from deepspeed_tpu import accelerator  # noqa: F401
from deepspeed_tpu import checkpoint  # noqa: F401
from deepspeed_tpu import module_inject  # noqa: F401
from deepspeed_tpu import moe  # noqa: F401
from deepspeed_tpu import monitor  # noqa: F401
from deepspeed_tpu import pipe  # noqa: F401
from deepspeed_tpu import profiling  # noqa: F401
from deepspeed_tpu import runtime  # noqa: F401
from deepspeed_tpu import sequence  # noqa: F401
from deepspeed_tpu import utils  # noqa: F401
from deepspeed_tpu import zero  # noqa: F401
from deepspeed_tpu.comm.comm import init_distributed  # noqa: F401
from deepspeed_tpu.pipe import PipelineModule  # noqa: F401
from deepspeed_tpu.runtime import activation_checkpointing as checkpointing  # noqa: F401
from deepspeed_tpu.runtime.engine import DeepSpeedTPUEngine as DeepSpeedEngine  # noqa: F401
from deepspeed_tpu.utils.init_on_device import OnDevice  # noqa: F401


def initialize(args=None,
               model=None,
               optimizer=None,
               model_parameters=None,
               training_data=None,
               lr_scheduler=None,
               distributed_port: int = 29500,
               mesh_topology=None,
               dist_init_required=None,
               collate_fn=None,
               config=None,
               config_params=None,
               rngs=None,
               tp_rules=None,
               model_family=None,
               param_specs=None):
    """Initialize the training engine.

    Parity: ``deepspeed.initialize`` (``deepspeed/__init__.py:64``). Returns a tuple
    of ``(engine, optimizer, dataloader, lr_scheduler)``.

    TPU-first differences: ``model`` is a flax module (or any (init_fn, apply_fn)
    pair); the engine owns a jitted, sharded train step rather than wrapping an
    nn.Module with hooks.
    """
    # import + config validation first: no side effects (init_distributed) before
    # anything that can raise
    from deepspeed_tpu.runtime.engine import DeepSpeedTPUEngine
    from deepspeed_tpu.utils import fault_injection

    # arm the deterministic fault plan, if any (no-op unless $DSTPU_FAULTS is
    # set) — how a subprocess worker is handed its faults
    fault_injection.install_from_env()
    # arm span tracing from $DSTPU_TRACE (no-op unless set; config.monitor.
    # trace reaches the same tracer through the engine) — docs/OBSERVABILITY.md
    from deepspeed_tpu.monitor import trace as _trace
    _trace.install_from_env()
    # the process's account of what jax traces, lowers and compiles, by the
    # stage of set-up (idempotent; the serving engine installs it through
    # setup_compile_cache) — docs/OBSERVABILITY.md, "Set-up and compiles"
    from deepspeed_tpu.utils.compile_cache import install_compile_listener
    install_compile_listener()

    config = DeepSpeedTPUConfig.load(config if config is not None else config_params)
    comm.init_distributed()
    engine_cls = DeepSpeedTPUEngine
    engine_kwargs = {}
    if config.hybrid_engine.enabled:
        # parity: deepspeed.initialize returning DeepSpeedHybridEngine
        # (__init__.py:156-196) when hybrid_engine.enabled
        from deepspeed_tpu.runtime.hybrid_engine import DeepSpeedTPUHybridEngine
        engine_cls = DeepSpeedTPUHybridEngine
        engine_kwargs["inference_config"] = {
            "tensor_parallel": {"tp_size": config.hybrid_engine.inference_tp_size},
            "max_out_tokens": config.hybrid_engine.max_out_tokens,
        }
    # set-up's first stage (tracer.stage): net of a state build inside it
    with _trace.tracer.stage("engine_init"):
        engine = engine_cls(
            args=args,
            model=model,
            optimizer=optimizer,
            model_parameters=model_parameters,
            training_data=training_data,
            lr_scheduler=lr_scheduler,
            mesh_topology=mesh_topology,
            collate_fn=collate_fn,
            config=config,
            rngs=rngs,
            tp_rules=tp_rules,
            model_family=model_family,
            param_specs=param_specs,
            **engine_kwargs,
        )
    return engine, engine.optimizer, engine.training_dataloader, engine.lr_scheduler


def init_inference(model=None, config=None, model_parameters=None,
                   mesh_topology=None, init_cache_fn=None, **kwargs):
    """Parity: ``deepspeed.init_inference`` (``deepspeed/__init__.py:269``).
    Extra kwargs are config overrides (reference accepts flat kwargs too)."""
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.config import InferenceConfig
    cfg = InferenceConfig.load(config, **kwargs)
    if isinstance(model, str):
        # local path / cached HF identifier (parity: reference accepts model
        # names and loads via transformers)
        from transformers import AutoConfig, AutoModelForCausalLM
        from transformers import AutoModelForMaskedLM
        auto_cls = (AutoModelForMaskedLM
                    if AutoConfig.from_pretrained(model).model_type == "bert"
                    else AutoModelForCausalLM)
        model = auto_cls.from_pretrained(model)
    from deepspeed_tpu.module_inject import convert_hf_model, is_hf_model
    if is_hf_model(model):
        # injection-policy path (parity: _apply_injection_policy engine.py:408).
        # Caller-supplied model_parameters (a pre-converted flax tree) win over
        # the torch state_dict.
        model, _zoo_cfg, variables = convert_hf_model(model,
                                                      dtype=cfg.compute_dtype)
        if model_parameters is None:
            model_parameters = variables["params"]
    return InferenceEngine(model=model, config=cfg,
                           model_parameters=model_parameters,
                           mesh_topology=mesh_topology,
                           init_cache_fn=init_cache_fn)


def add_config_arguments(parser):
    """Parity: ``deepspeed.add_config_arguments`` (``deepspeed/__init__.py:246``)."""
    group = parser.add_argument_group("DeepSpeedTPU", "DeepSpeedTPU configurations")
    group.add_argument("--deepspeed", default=False, action="store_true",
                       help="Enable DeepSpeedTPU (helper flag for config scripts)")
    group.add_argument("--deepspeed_config", default=None, type=str,
                       help="Path to DeepSpeedTPU json configuration")
    group.add_argument("--deepscale", default=False, action="store_true",
                       help=argparse_suppress())
    return parser


def argparse_suppress():
    import argparse
    return argparse.SUPPRESS
