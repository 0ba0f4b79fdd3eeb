"""Kernel edge-case sweeps: odd heads, non-divisible T, dtype matrix.

Parity: reference ``tests/unit/inference/v2`` (34 files of per-kernel
shape/dtype sweeps) and ``tests/unit/ops`` — the classes of input the fast
paths are most likely to get wrong. Runs on the Pallas interpreter (CPU);
the TPU lowering of the main-path kernels is compiled by
tests/unit/test_chip_compile.py and run by chip_smoke.py's kernels phase.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.attention import reference_attention
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
from deepspeed_tpu.ops.pallas.paged_attention import (
    paged_chunk_attention, paged_chunk_attention_reference,
    paged_decode_attention, paged_decode_attention_reference)


def _rand(key, *shape, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(key), shape, dtype)


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


class TestFlashEdgeCases:
    """Shape/dtype matrix for the flash kernel (block padding, GQA, tails)."""

    @pytest.mark.parametrize("T", [1, 7, 63, 65, 127, 200])
    def test_non_divisible_seq_lengths(self, T):
        """T values that never align with the kernel's block sizes."""
        q = _rand(0, 1, T, 4, 64)
        k = _rand(1, 1, T, 4, 64)
        v = _rand(2, 1, T, 4, 64)
        got = flash_attention(q, k, v, causal=True)
        ref = reference_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5, rtol=2e-4)

    @pytest.mark.parametrize("H,Hkv", [(3, 3), (5, 1), (6, 3), (7, 7)])
    def test_odd_head_counts(self, H, Hkv):
        """Odd / non-power-of-two head counts, incl. odd GQA groupings."""
        T = 48
        q = _rand(3, 2, T, H, 32)
        k = _rand(4, 2, T, Hkv, 32)
        v = _rand(5, 2, T, Hkv, 32)
        got = flash_attention(q, k, v, causal=True)
        rep = H // Hkv
        ref = reference_attention(q, jnp.repeat(k, rep, 2),
                                  jnp.repeat(v, rep, 2), causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5, rtol=2e-4)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("D", [32, 64, 128])
    def test_dtype_by_head_dim(self, dtype, D):
        T = 64
        q = _rand(6, 1, T, 2, D, dtype=dtype)
        k = _rand(7, 1, T, 2, D, dtype=dtype)
        v = _rand(8, 1, T, 2, D, dtype=dtype)
        got = flash_attention(q, k, v, causal=False)
        ref = reference_attention(q, k, v, causal=False)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(ref, np.float32),
            atol=_tol(dtype), rtol=2e-2 if dtype == jnp.bfloat16 else 2e-4)

    @pytest.mark.parametrize("T", [33, 96])
    def test_gradients_at_odd_lengths(self, T):
        q = _rand(9, 1, T, 2, 32)
        k = _rand(10, 1, T, 2, 32)
        v = _rand(11, 1, T, 2, 32)

        def f_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=True) ** 2)

        def f_ref(q, k, v):
            return jnp.sum(reference_attention(q, k, v, causal=True) ** 2)

        g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-4, rtol=5e-3)


class TestPagedEdgeCases:
    """Paged decode/chunk over ragged context lengths and block geometry."""

    @pytest.mark.parametrize("bs", [4, 16])          # KV page size
    @pytest.mark.parametrize("ctxs", [[1], [0, 5, 9, 64], [17, 3, 31]])
    def test_decode_ragged_contexts(self, bs, ctxs):
        NB, Hkv, H, D = 24, 2, 4, 32
        S = len(ctxs)
        kv = _rand(20, NB, 2, Hkv, bs, D)
        q = _rand(22, S, H, D)
        mb = max(-(-max(max(ctxs), 1) // bs), 1)
        bts = jnp.asarray(
            np.arange(S * mb).reshape(S, mb) % NB, jnp.int32)
        cls_ = jnp.asarray(ctxs, jnp.int32)
        got = paged_decode_attention(q, kv, bts, cls_)
        ref = paged_decode_attention_reference(q, kv, bts, cls_)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5, rtol=2e-4)
        # zero-context rows must be exactly zero, not NaN
        for i, c in enumerate(ctxs):
            if c == 0:
                assert np.all(np.asarray(got)[i] == 0)

    @pytest.mark.parametrize("C,q_start", [(1, 0), (5, 3), (31, 1), (17, 40)])
    def test_chunk_odd_sizes_and_offsets(self, C, q_start):
        NB, bs, Hkv, H, D = 16, 8, 2, 4, 32
        kv = _rand(23, NB, 2, Hkv, bs, D)
        q = _rand(25, C, H, D)
        ctx = q_start + C
        nb = -(-ctx // bs)
        bt = jnp.asarray(np.arange(nb) % NB, jnp.int32)
        got = paged_chunk_attention(q, kv, bt, jnp.int32(q_start),
                                    jnp.int32(ctx))
        ref = paged_chunk_attention_reference(q, kv, bt, jnp.int32(q_start),
                                              jnp.int32(ctx))
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5, rtol=2e-4)

    def test_decode_single_token_context_bf16(self):
        NB, bs, Hkv, H, D = 8, 8, 1, 2, 64
        kv = _rand(26, NB, 2, Hkv, bs, D, dtype=jnp.bfloat16)
        q = _rand(28, 1, H, D, dtype=jnp.bfloat16)
        bts = jnp.zeros((1, 1), jnp.int32)
        cls_ = jnp.asarray([1], jnp.int32)
        got = paged_decode_attention(q, kv, bts, cls_)
        ref = paged_decode_attention_reference(q, kv, bts, cls_)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(ref, np.float32),
                                   atol=2e-2, rtol=2e-2)
