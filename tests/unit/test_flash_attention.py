"""Flash-attention kernel vs jnp reference (parity: reference tests/unit/ops
kernel-vs-baseline pattern). Runs through the Pallas interpreter on CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.attention import reference_attention
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention


def make_qkv(B=2, T=256, H=4, D=64, Hkv=None, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    Hkv = Hkv or H
    q = jax.random.normal(ks[0], (B, T, H, D), dtype)
    k = jax.random.normal(ks[1], (B, T, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, T, Hkv, D), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_reference(causal):
    q, k, v = make_qkv()
    out = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_forward_uneven_blocks():
    # T not divisible by the preferred block -> _pick_block halves it
    q, k, v = make_qkv(T=192)
    out = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_gradients_match_reference(causal):
    q, k, v = make_qkv(B=1, T=128, H=2, D=32)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal,
                                       block_q=64, block_k=64) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=causal) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g1, g2, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4,
                                   atol=5e-4, err_msg=f"d{name} mismatch")


def test_gqa_head_repeat():
    q, k, v = make_qkv(H=8, Hkv=2)
    out = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    kr = jnp.repeat(k, 4, axis=2)
    vr = jnp.repeat(v, 4, axis=2)
    ref = reference_attention(q, kr, vr, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_bf16_inputs():
    q, k, v = make_qkv(dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, dtype=np.float32),
                               np.asarray(ref, dtype=np.float32),
                               rtol=2e-2, atol=2e-2)


def test_softmax_scale_override():
    q, k, v = make_qkv(T=128)
    out = flash_attention(q, k, v, softmax_scale=0.5, block_q=64, block_k=64)
    ref = reference_attention(q, k, v, softmax_scale=0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_segment_ids_fallback_path():
    q, k, v = make_qkv(T=64)
    seg = jnp.concatenate([jnp.zeros((2, 32), jnp.int32),
                           jnp.ones((2, 32), jnp.int32)], axis=1)
    out = flash_attention(q, k, v, segment_ids=seg)
    ref = reference_attention(q, k, v, segment_ids=seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_dispatch_runs_the_kernel_per_shard_under_an_engine_mesh(
        eight_devices, monkeypatch):
    """The SPMD partitioner cannot split a Mosaic kernel, so a program over
    more than one device must call it inside a shard_map (on a TPU anything
    else fails to lower; the interpreter hides that). Under the ambient mesh
    the dispatcher shards the batch over the data axes and the heads over
    'tensor', and results and gradients stay those of plain attention."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from deepspeed_tpu.comm.mesh import (BATCH_AXES, TENSOR_AXIS,
                                         build_topology, set_topology)
    from deepspeed_tpu.config import MeshConfig
    from deepspeed_tpu.ops import attention
    monkeypatch.setattr(attention, "_use_pallas", lambda: True)
    monkeypatch.setattr(attention, "FLASH_MIN_SEQ", 128)
    topo = set_topology(build_topology(MeshConfig(data=2, fsdp=2, tensor=2)))
    rows = NamedSharding(topo.mesh, P(BATCH_AXES, None, TENSOR_AXIS, None))
    q, k, v = (jax.device_put(t, rows)
               for t in make_qkv(B=4, T=128, H=4, D=32))

    def loss(attn):
        return lambda q, k, v: jnp.sum(attn(q, k, v, causal=True) ** 2)

    assert "shard_map" in str(jax.make_jaxpr(
        lambda q, k, v: attention.dot_product_attention(q, k, v, causal=True)
    )(q, k, v))
    got = jax.jit(jax.value_and_grad(loss(attention.dot_product_attention),
                                     argnums=(0, 1, 2)))(q, k, v)
    want = jax.value_and_grad(loss(reference_attention),
                              argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-4, atol=2e-4)
