"""Rehearse ``chip_smoke.py`` without the chip (costs no chip time).

    python scripts/chip_rehearse.py cpu        # the phases, tiny, on the CPU
    python scripts/chip_rehearse.py cpu4       # --multichip on 4 virtual CPUs
    python scripts/chip_rehearse.py compile    # real sizes, compiled for v5e
    python scripts/chip_rehearse.py compile4   # ... the four-chip train steps

``cpu`` and ``cpu4`` run the smoke's own phase functions at a small
:class:`chip_smoke.Sizes` with the Pallas kernels interpreted: they find wrong
arguments, shapes and control flow. ``compile`` and ``compile4`` hand the real
programs to the TPU compiler for a described, unattached ``v5e:2x2``
(``jax.experimental.topologies``): they find what only that compiler refuses,
and print each program's device memory, Mosaic kernel calls and collectives.
Nothing here runs on a chip and no number printed here is a device
measurement. The steering a rehearsal needs (interpret off, the flash path
on, a mesh over described devices) happens here, not through an option of
the program.
"""

from __future__ import annotations

import argparse
import contextlib
import os
import sys
import time

MODE = sys.argv[1] if len(sys.argv) > 1 else ""
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("TPU_LOG_DIR", "disabled")     # else libtpu logs to /tmp
if MODE == "cpu4":
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                               + os.environ.get("XLA_FLAGS", ""))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import chip_smoke  # noqa: E402
from chip_smoke import REAL, Sizes, log  # noqa: E402

#: head_dim stays 128 and kv_heads * block_size stays a multiple of 128, so
#: every kernel variant of the real run (manual-DMA decode, int8 scale tiles,
#: side buffer, split-K) is the one interpreted here
TINY = Sizes(
    widths=dict(vocab_size=1024, hidden_size=512, intermediate_size=1024,
                num_attention_heads=4, num_key_value_heads=2,
                sliding_window=256),
    seq=256, train_layers=1, serve_layers=2, block_size=64, max_context=512,
    prompts=(20, 64, 90, 128, 150, 200, 230, 260),
    new_tokens=(4, 4, 4, 4, 4, 4, 4, 24),
    ref_prompt=200, multi_layers=4, multi_ref_layers=2)


# --------------------------------------------------------------------------- #
# cpu / cpu4
# --------------------------------------------------------------------------- #

def rehearse_cpu() -> None:
    chip_smoke.phase_kernels(TINY, seed=0, on_chip=False)
    chip_smoke.phase_train(TINY, seed=0, on_chip=False)
    chip_smoke.phase_serve(TINY, seed=0, num_blocks=96)


def rehearse_cpu4() -> None:
    assert len(jax.devices()) == 4, jax.devices()
    chip_smoke.phase_multichip(TINY, seed=0, on_chip=False)


# --------------------------------------------------------------------------- #
# compile for a described chip
# --------------------------------------------------------------------------- #

#: ``memory_stats()["bytes_limit"]`` of a v5e chip (chip runs of PR 22); a
#: described device reports none
V5E_HBM_LIMIT = int(15.75 * 2**30)

def described_devices():
    from jax.experimental import topologies
    # an executable for a described device is written to the persistent
    # cache but cannot be read back without a chip: keep the cache off
    jax.config.update("jax_enable_compilation_cache", False)
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2").devices


@contextlib.contextmanager
def steered_to_tpu():
    """The CPU backend means "interpret", "dense attention" and "no memory
    limit" to the code under rehearsal; the programs compiled here are for
    a TPU v5e."""
    from deepspeed_tpu.accelerator import get_accelerator
    from deepspeed_tpu.ops import attention
    from deepspeed_tpu.ops.pallas import _backend
    accelerator = type(get_accelerator())
    saved = (_backend.interpret, attention._use_pallas,
             accelerator.total_memory)
    _backend.interpret = lambda: False
    attention._use_pallas = lambda: True
    accelerator.total_memory = lambda self, device_index=None: V5E_HBM_LIMIT
    try:
        yield
    finally:
        (_backend.interpret, attention._use_pallas,
         accelerator.total_memory) = saved


def report(name: str, compiled, t0: float) -> None:
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    gib = 2.0 ** 30
    log(f"{name}: compiled in {time.time() - t0:.1f} s; per device: "
        f"arguments {mem.argument_size_in_bytes / gib:.2f} GiB, outputs "
        f"{mem.output_size_in_bytes / gib:.2f}, aliased "
        f"{mem.alias_size_in_bytes / gib:.2f}, temporaries "
        f"{mem.temp_size_in_bytes / gib:.2f}; "
        f"{text.count('tpu_custom_call')} Mosaic calls; collectives "
        f"{chip_smoke.collective_counts(text)}")


def abstract(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), jnp.result_type(x),
                                       sharding=sharding), tree)


class LowerOnly:
    """Stands in for one of the serving engine's jitted programs: a call
    compiles it for the described chip at the REAL depth and pool size (the
    engine it came from is one layer deep on the CPU) and runs nothing."""

    def __init__(self, jitted, name, real_weights, real_kv, sharding):
        self.jitted, self.name = jitted, name
        self.real = (real_weights, real_kv)
        self.sharding = sharding

    def __call__(self, weights, kv, *args):
        t0 = time.time()
        compiled = self.jitted.lower(
            *self.real, *abstract(args, self.sharding)).compile()
        report(self.name, compiled, t0)
        # every program returns (..., new_kv); its callers rebind the pool
        # and block on the rest
        return (None,) * (len(compiled.out_tree.children()) - 1) + (kv,)


def compile_serving(devices) -> None:
    """Every program ``InferenceEngineV2.warmup`` builds, walked by the
    engine's own warm-up over its own argument shapes."""
    from jax.sharding import SingleDeviceSharding
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.inference.v2.ragged.kv_cache import KVCacheConfig
    from deepspeed_tpu.models.llama import LlamaForCausalLM
    from deepspeed_tpu.utils.tree import tree_cast, tree_size_bytes

    chip = SingleDeviceSharding(devices[0])
    L = REAL.serve_layers
    cfg = chip_smoke.model_config(REAL, 1, dtype=jnp.bfloat16)
    model = LlamaForCausalLM(cfg)
    params = jax.jit(lambda k: tree_cast(
        model.init(k, jnp.zeros((1, 8), jnp.int32))["params"],
        jnp.bfloat16))(jax.random.PRNGKey(0))
    layer_bytes = tree_size_bytes(params["layers_0"])
    weight_bytes = tree_size_bytes(params) + (L - 1) * layer_bytes
    budget = int(V5E_HBM_LIMIT * chip_smoke.HBM_FILL) - weight_bytes \
        - chip_smoke.HBM_HEADROOM
    nb = KVCacheConfig.from_memory_budget(
        L, cfg.num_key_value_heads, cfg.head_dim, budget,
        block_size=REAL.block_size).num_blocks
    log(f"serving: depth {L}, {nb} blocks (as the smoke sizes them for "
        f"{V5E_HBM_LIMIT / 2**30:.2f} GiB of HBM)")

    def engine_for(sizes, extra):
        conf = chip_smoke.serve_config(sizes, num_blocks=8)
        conf["compile"] = {"warmup": False}
        conf.update(extra)
        e = InferenceEngineV2(model=model, model_parameters=params,
                              config=conf)
        real_w = abstract(e.weights, chip)
        real_w["layers"] = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct((L,) + x.shape[1:], x.dtype,
                                           sharding=chip), real_w["layers"])
        kv = e.kv.kv
        real_kv = jax.ShapeDtypeStruct((L, nb + 1) + kv.shape[2:], kv.dtype,
                                       sharding=chip)

        def stand_in(jitted, name):
            return LowerOnly(jitted, name, real_w, real_kv, chip)

        for cache, label in ((e._step_progs, "decode_step"),
                             (e._verify_progs, "verify_step")):
            def get_or_create(key, build, _orig=cache.get_or_create,
                              _label=label):
                return stand_in(_orig(key, build), f"{_label}{key}")
            cache.get_or_create = get_or_create
        e._pass_rungs = {r: stand_in(p, f"ragged_pass[split {r}]")
                         for r, p in e._pass_rungs.items()}
        e._pass_prefill = stand_in(e._ensure_prefill_pass(), "prefill_pass")
        # the page round trip serves preemption, which a windowed model
        # refuses; its two gather/scatter programs hold no kernel
        e.fetch_pages = lambda blocks: None
        e.put_pages = lambda pages, blocks: None
        return e

    with steered_to_tpu():
        log("serving: the smoke's engine (windowed), warm-up grid")
        engine_for(REAL, {}).warmup()
        log("serving: the verify step needs a model without a window "
            "(spec x window is refused): max_context = window drops it")
        no_window = chip_smoke.dataclasses.replace(
            REAL, max_context=cfg.sliding_window)
        e = engine_for(no_window, {"spec_decode": {"enabled": True, "k": 3}})
        e.warmup(buckets=[8], spec_ks=[3])

        log("serving: the dense f32 reference of the logits check")
        T = REAL.ref_prompt + chip_smoke.FORCED_TOKENS
        pshape = jax.eval_shape(lambda k: tree_cast(
            LlamaForCausalLM(chip_smoke.model_config(
                REAL, L, dtype=jnp.bfloat16)).init(
                    k, jnp.zeros((1, 8), jnp.int32))["params"],
            jnp.bfloat16), jax.random.PRNGKey(0))
        t0 = time.time()
        report("dense_reference", chip_smoke.dense_reference(
            REAL, L, list(range(6))).lower(
                abstract(pshape, chip),
                jax.ShapeDtypeStruct((1, T), jnp.int32, sharding=chip)
            ).compile(), t0)


_jit = jax.jit


class AbstractJit:
    """``jax.jit`` for the engine's state build: compiles the build AS THE
    ENGINE JITS IT (its shardings and every other option; the init key its
    argument, by shape) for the described devices, then returns the shapes
    it would produce, carrying its out_shardings. Nothing is allocated (a
    described device cannot hold an array)."""

    def __init__(self, fn, **options):
        self.fn, self.options = fn, options

    def __call__(self, *args):
        t0 = time.time()
        shapes = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args)
        report("state_build",
               _jit(self.fn, **self.options).lower(*shapes).compile(), t0)
        out = jax.eval_shape(self.fn, *args)
        return jax.tree_util.tree_map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            out, self.options["out_shardings"])


def compile_train_step(devices, layers: int, fsdp: int, prefetch_depth,
                       label: str, global_batch: int = 0):
    """The engine's full fused train step — built by a real
    ``DeepSpeedTPUEngine`` over a mesh of described devices. Returns the
    compiled step."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    import deepspeed_tpu
    from deepspeed_tpu.comm.mesh import BATCH_AXES, build_topology
    from deepspeed_tpu.config import MeshConfig
    from deepspeed_tpu.models.llama import LlamaForCausalLM
    from deepspeed_tpu.runtime.zero import prefetch as zero3_prefetch

    topo = build_topology(MeshConfig(data=1, fsdp=fsdp),
                          devices=list(devices[:fsdp]))
    global_batch = global_batch or fsdp
    config = chip_smoke.train_config(global_batch=global_batch, fsdp=fsdp,
                                     prefetch_depth=prefetch_depth)
    model = LlamaForCausalLM(chip_smoke.model_config(
        REAL, layers, dtype=jnp.bfloat16, remat=True))
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, config=config, mesh_topology=topo)
    batch = chip_smoke.train_batch_data(REAL, 0, global_batch)
    with steered_to_tpu():       # the state build traces the model too
        jax.jit = AbstractJit
        try:
            engine._ensure_state(batch)
        finally:
            jax.jit = _jit
        armed = engine._zero3_plan is not None
        assert armed == (prefetch_depth is not None), \
            f"explicit ZeRO-3 schedule armed = {armed}"
        zero3_prefetch.configure(engine._zero3_plan)
        # the engine asks the default backend which options it may pass
        options = engine._compiler_options("tpu")
        engine._compiler_options = lambda backend=None: options
        sharded = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(
                (engine.gas_, x.shape[0] // engine.gas_) + x.shape[1:],
                x.dtype,
                sharding=NamedSharding(topo.mesh, P(None, BATCH_AXES))), batch)
        t0 = time.time()
        # as train_batch does at its first step: choose what the checkpointed
        # layers keep, then compile (and, with a choice, hold the compiled
        # step's memory against the limit)
        step = engine._make_fused_step(sharded)
        compiled = step.lower(engine.state, sharded).compile() \
            if engine.remat_plan is None \
            else engine._compile_fitted(engine.state, sharded)
    if engine.remat_plan is not None:
        log(f"{label}: {engine.remat_plan.describe()}")
    report(label, compiled, t0)
    return compiled


def rehearse_compile() -> None:
    devices = described_devices()
    compile_serving(devices)
    compile_train_step(devices, REAL.train_layers, 1, None,
                       f"train_step[1 chip, depth {REAL.train_layers}]")
    compile_train_step(devices, REAL.multi_ref_layers, 1, None,
                       f"train_step[1 chip, depth {REAL.multi_ref_layers}, "
                       "4 accumulated microbatches: the --multichip "
                       "comparison]", global_batch=4)


def rehearse_compile4() -> None:
    devices = described_devices()
    for layers in (REAL.multi_ref_layers, REAL.multi_layers):
        for depth in (None, 1):
            compile_train_step(
                devices, layers, 4, depth,
                f"train_step[fsdp=4, depth {layers}, "
                f"{'implicit' if depth is None else f'prefetch-{depth}'}]")


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=["cpu", "cpu4", "compile", "compile4"])
    args = ap.parse_args()
    {"cpu": rehearse_cpu, "cpu4": rehearse_cpu4, "compile": rehearse_compile,
     "compile4": rehearse_compile4}[args.mode]()
    log(f"rehearsal '{args.mode}' passed")
