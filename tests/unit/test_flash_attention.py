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


def _dense_bwd(scale, causal, residuals, do):
    """The backward's own equations over the whole ``[T, Tk]`` score matrix,
    in float32, from the residuals the kernels are handed (so a row whose
    ``lse`` is +inf has probability 0, as ring attention's sentinel means)."""
    q, k, v, o, lse = (t.astype(jnp.float32) for t in residuals)
    do = do.astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        s = jnp.where(jnp.tril(jnp.ones(s.shape[-2:], bool)), s, -1e30)
    p = jnp.exp(s - lse)
    dp = jnp.einsum("bhqd,bhkd->bhqk", do, v)
    ds = p * (dp - jnp.sum(do * o, -1, keepdims=True)) * scale
    return (jnp.einsum("bhqk,bhkd->bhqd", ds, k),
            jnp.einsum("bhqk,bhqd->bhkd", ds, q),
            jnp.einsum("bhqk,bhqd->bhkd", p, do))


def _flash_counts():
    from deepspeed_tpu.monitor.trace import tracer
    return (tracer.totals.get("train/flash/bwd_fused", 0),
            tracer.totals.get("train/flash/bwd_split", 0))


# T, Tk, block_q, block_k, head size, dtype, causal; then what is special
BWD_CASES = {
    # nq x nk = 4 x 4: a dq row block is met by four key tiles, and under
    # the causal skip the first by one (the rest of its row never runs)
    "causal_4x4_d64": (256, 256, 64, 64, 64, jnp.float32, True, None),
    "full_4x4_d64": (256, 256, 64, 64, 64, jnp.float32, False, None),
    "causal_2x4_d128": (256, 256, 128, 64, 128, jnp.float32, True, None),
    "causal_4x2_d64": (256, 256, 64, 128, 64, jnp.float32, True, None),
    "causal_1x1_d128": (128, 128, 128, 128, 128, jnp.float32, True, None),
    "full_1x2_d64": (64, 128, 64, 64, 64, jnp.float32, False, None),
    # cross attention: T != Tk
    "cross_2x4_d64": (128, 256, 64, 64, 64, jnp.float32, False, None),
    "cross_4x1_d128": (256, 64, 64, 64, 128, jnp.float32, False, None),
    "causal_4x4_d128_bf16": (256, 256, 64, 64, 128, jnp.bfloat16, True, None),
    "full_2x2_d64_bf16": (128, 128, 64, 64, 64, jnp.bfloat16, False, None),
    # T = 200 over a block of 128: padded to 256 in flash_attention
    "causal_ragged_200": (200, 200, 128, 128, 64, jnp.float32, True, None),
    # ring attention's sentinel, on some rows and on all of them
    "causal_4x4_lse_inf_rows": (256, 256, 64, 64, 64, jnp.float32, True,
                                "inf_rows"),
    "full_2x4_lse_inf_all": (128, 256, 64, 64, 64, jnp.float32, False,
                             "inf_all"),
    # the kernel's own causal mask over T != Tk (rows against the first keys:
    # key tiles 2 and 3 meet no row, and ask for the last row block there is)
    "causal_cross_2x4_direct": (128, 256, 64, 64, 64, jnp.float32, True,
                                "direct"),
    # over the VMEM budget: the two older calls
    "causal_4x4_split": (256, 256, 64, 64, 64, jnp.float32, True, "split"),
    "cross_2x4_split_bf16": (128, 256, 64, 64, 128, jnp.bfloat16, False,
                             "split"),
}


@pytest.mark.parametrize("case", BWD_CASES)
def test_backward_matches_reference(case, monkeypatch):
    """The one fused backward call (and, over the budget, the two it
    replaced) against ``reference_attention``'s gradients; called with
    residuals of the test's own (an ``lse`` of +inf; the kernel's causal
    mask over T != Tk), against the backward's equations written out
    densely."""
    from deepspeed_tpu.ops.pallas import flash_attention as fa
    T, Tk, bq, bk, D, dtype, causal, special = BWD_CASES[case]
    ks = jax.random.split(jax.random.PRNGKey(T + Tk + D), 4)
    q = jax.random.normal(ks[0], (2, T, 2, D), dtype)
    k = jax.random.normal(ks[1], (2, Tk, 2, D), dtype)
    v = jax.random.normal(ks[2], (2, Tk, 2, D), dtype)
    if special == "split":
        monkeypatch.setattr(fa, "FUSED_BWD_VMEM_BYTES", 0)
    # float32 holds the reference to 5e-4, as the older test does; bfloat16
    # to one step of its rounding (2**-7) at the largest gradient
    rtol, atol = (5e-4, 5e-4) if dtype == jnp.float32 else (2 ** -7, None)
    before = _flash_counts()

    if special in ("inf_rows", "inf_all", "direct"):
        scale = D ** -0.5
        qh, kh, vh = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))
        o, lse = fa._fwd(qh, kh, vh, scale, causal, bq, bk)
        dead = {"inf_rows": jnp.arange(T) % 3 == 0,
                "inf_all": jnp.ones(T, bool),
                "direct": jnp.zeros(T, bool)}[special]
        lse = jnp.where(dead[None, None, :, None], jnp.inf, lse)
        do = jax.random.normal(ks[3], qh.shape, dtype)
        got = fa._bwd(scale, causal, bq, bk, (qh, kh, vh, o, lse), do)
        want = _dense_bwd(scale, causal, (qh, kh, vh, o, lse), do)
        assert not np.any(np.asarray(got[0])[:, :, np.asarray(dead)])
        if special == "inf_all":
            assert not any(np.any(np.asarray(g)) for g in got)
    else:
        def loss(attn, **kw):
            return lambda q, k, v: jnp.sum(
                attn(q, k, v, causal=causal, **kw).astype(jnp.float32) ** 2)
        got = jax.grad(loss(flash_attention, block_q=bq, block_k=bk),
                       argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(loss(reference_attention), argnums=(0, 1, 2))(
            *(t.astype(jnp.float32) for t in (q, k, v)))
    fused, split = (a - b for a, b in zip(_flash_counts(), before))
    assert (fused, split) == ((0, 1) if special == "split" else (1, 0))
    for g, w, name in zip(got, want, "qkv"):
        assert g.dtype == dtype
        g, w = np.asarray(g, np.float32), np.asarray(w)
        np.testing.assert_allclose(
            g, w, rtol=rtol, atol=atol or rtol * np.abs(w).max(),
            err_msg=f"d{name} mismatch")


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


@pytest.mark.parametrize("path", ["fused", "split"])
def test_the_engines_log_line_counts_the_backward_calls(monkeypatch, path):
    """``train/flash/bwd_fused`` and ``train/flash/bwd_split`` count the
    backward calls a step traces by the path each took; the engine zeroes
    both before it traces its fitted step and says them in its ``activation
    checkpointing:`` log line, so what an earlier trace in the process
    counted (here 7 calls) is not said of this step."""
    import deepspeed_tpu
    from deepspeed_tpu.accelerator import get_accelerator
    from deepspeed_tpu.models import llama
    from deepspeed_tpu.monitor.trace import tracer
    from deepspeed_tpu.ops import attention
    from deepspeed_tpu.ops.pallas import flash_attention as fa
    from deepspeed_tpu.utils.logging import logger
    lines = []
    monkeypatch.setattr(logger, "info", lambda msg, *a: lines.append(
        msg % a if a else msg))
    monkeypatch.setattr(logger, "log",
                        lambda level, msg, *a: lines.append(str(msg)))
    monkeypatch.setattr(type(get_accelerator()), "total_memory",
                        lambda self, device_index=None: 1 << 30)
    monkeypatch.setattr(attention, "_use_pallas", lambda: True)
    monkeypatch.setattr(attention, "FLASH_MIN_SEQ", 64)
    if path == "split":
        monkeypatch.setattr(fa, "FUSED_BWD_VMEM_BYTES", 0)
    tracer.note("train/flash/bwd_fused", 7)
    tracer.note("train/flash/bwd_split", 7)
    model = llama.LlamaForCausalLM(llama.LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, remat=True))
    engine, *_ = deepspeed_tpu.initialize(
        model=model, rngs=jax.random.PRNGKey(0),
        config={"train_batch_size": 8, "steps_per_print": 0,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 3}, "mesh": {"fsdp": 8}})
    batch = {"input_ids": np.arange(8 * 64, dtype=np.int32).reshape(8, 64)
             % 128}
    assert np.isfinite(float(engine.train_batch(batch)))
    said, = [l for l in lines if "activation checkpointing: rung" in l]
    fused = tracer.totals["train/flash/bwd_fused"]
    split = tracer.totals["train/flash/bwd_split"]
    calls = fused + split
    assert 1 <= calls < 7 and (fused if path == "split" else split) == 0
    assert (f"one call in {fused:.0f} of the {calls:.0f} traced "
            f"(train/flash/bwd_fused") in said
    engine.destroy()
