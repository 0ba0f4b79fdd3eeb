"""Data analyzer, OnDevice meta-init, elastic agent tests.

Parity model: reference ``tests/unit`` data-efficiency + elasticity coverage;
the DistributedFixture save/resize pattern maps to the agent restarting at a
new world size.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.data.data_analyzer import DataAnalyzer
from deepspeed_tpu.elasticity.elastic_agent import DSElasticAgent
from deepspeed_tpu.elasticity.elasticity import ElasticityError
from deepspeed_tpu.utils.init_on_device import (OnDevice, abstract_init,
                                                current_on_device,
                                                materialize_sharded)


# --------------------------------------------------------------------------- #
# data analyzer
# --------------------------------------------------------------------------- #

def _dataset(n=50):
    rng = np.random.default_rng(0)
    return [rng.integers(0, 100, size=rng.integers(3, 20)) for _ in range(n)]


def test_analyzer_map_reduce_roundtrip(tmp_path):
    ds = _dataset()
    an = DataAnalyzer(ds, {"seqlen": lambda s: len(s),
                           "vocab_rarity": lambda s: float(np.mean(s))},
                      save_path=str(tmp_path), num_workers=3)
    an.run()
    v = DataAnalyzer.metric_values(str(tmp_path), "seqlen")
    assert v.shape == (50,)
    np.testing.assert_array_equal(v, [len(s) for s in ds])
    diffs = DataAnalyzer.load_difficulties(str(tmp_path), "seqlen")
    assert diffs.min() == 0.0 and diffs.max() == 1.0
    # inverse index exists and covers all samples
    import json
    inv = json.load(open(tmp_path / "seqlen" / "metric_to_sample.json"))
    covered = sorted(i for b in inv["buckets"].values() for i in b)
    assert covered == list(range(50))


def test_analyzer_detects_missing_parts(tmp_path):
    ds = _dataset(10)
    an = DataAnalyzer(ds, {"m": len}, save_path=str(tmp_path), num_workers=2)
    an.run_map(0)  # worker 1 never ran
    with pytest.raises(ValueError, match="missing map parts"):
        an.run_reduce()


def test_analyzer_feeds_sampler(tmp_path):
    from deepspeed_tpu.data.data_sampler import DeepSpeedDataSampler
    ds = _dataset(32)
    an = DataAnalyzer(ds, {"seqlen": len}, save_path=str(tmp_path))
    an.run()
    diffs = DataAnalyzer.load_difficulties(str(tmp_path), "seqlen")
    sampler = DeepSpeedDataSampler(total_samples=32, micro_batch_size=4,
                                   difficulties=diffs)
    batch = next(iter(sampler))
    assert len(batch) == 4


# --------------------------------------------------------------------------- #
# OnDevice
# --------------------------------------------------------------------------- #

def test_abstract_init_allocates_nothing_and_matches_shapes(eight_devices):
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHead
    model = GPT2LMHead(GPT2Config(vocab_size=64, n_positions=16, n_embd=32,
                                  n_layer=2, n_head=2))
    batch = {"input_ids": jnp.zeros((1, 16), jnp.int32)}
    with OnDevice(dtype=jnp.bfloat16, device="meta") as ctx:
        assert current_on_device() is ctx
        abstract = abstract_init(model, batch)
    assert current_on_device() is None
    leaves = jax.tree_util.tree_leaves(abstract)
    assert all(isinstance(l, jax.ShapeDtypeStruct) for l in leaves)

    # materialize directly sharded over fsdp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(eight_devices), ("fsdp",))
    sh = jax.tree_util.tree_map(
        lambda l: NamedSharding(mesh, P("fsdp") if l.shape and
                                l.shape[0] % 8 == 0 else P()), abstract)
    params = materialize_sharded(model, batch, sh)
    real = jax.tree_util.tree_leaves(params)
    assert all(tuple(a.shape) == tuple(b.shape) for a, b in zip(real, leaves))


# --------------------------------------------------------------------------- #
# elastic agent
# --------------------------------------------------------------------------- #

_ELASTIC_CFG = {"elasticity": {
    "enabled": True, "max_train_batch_size": 64,
    "micro_batch_sizes": [1, 2, 4], "min_gpus": 1, "max_gpus": 16,
    "version": 0.1,
}}


def test_agent_success_first_try():
    calls = []

    def run_fn(world_size, micro_batch, gas, resume):
        calls.append((world_size, micro_batch, gas, resume))

    agent = DSElasticAgent(_ELASTIC_CFG, run_fn, device_counts=[4])
    rec = agent.run()
    assert rec.world_size == 4 and not rec.error and not calls[0][3]
    # batch invariant: micro * gas * ws == the resolved elastic batch
    ws, mb, gas, _ = calls[0]
    final, _v, _m = __import__("deepspeed_tpu.elasticity.elasticity",
                               fromlist=["compute_elastic_config"]
                               ).compute_elastic_config(
        _ELASTIC_CFG, world_size=4, return_microbatch=True)
    assert mb * gas * ws == final <= 64


def test_agent_restarts_at_new_world_size_with_resume():
    calls = []

    def run_fn(world_size, micro_batch, gas, resume):
        calls.append((world_size, micro_batch, gas, resume))
        if len(calls) == 1:
            raise RuntimeError("node lost")  # first membership dies

    agent = DSElasticAgent(_ELASTIC_CFG, run_fn, device_counts=[12, 4])
    rec = agent.run()
    assert [c[0] for c in calls] == [12, 4]
    assert calls[1][3] is True  # resumed from checkpoint
    assert rec.restarts == 1
    # global batch invariant across the resize
    batches = {mb * gas * ws for ws, mb, gas, _ in calls}
    assert len(batches) == 1


def test_agent_gives_up_after_budget():
    def run_fn(**kw):
        raise RuntimeError("always fails")

    agent = DSElasticAgent(_ELASTIC_CFG, run_fn, device_counts=[4],
                           max_restarts=2)
    with pytest.raises(RuntimeError, match="always fails"):
        agent.run()
    assert len(agent.records) == 3  # initial + 2 restarts


def test_agent_rejects_incompatible_world_size():
    def run_fn(**kw):
        pass

    agent = DSElasticAgent(_ELASTIC_CFG, run_fn, device_counts=[7])
    with pytest.raises(ElasticityError):
        agent.run()
