"""Paged attention kernel tests (parity role: reference
``tests/unit/inference/v2/kernels/ragged_ops`` — kernel vs reference
comparisons). Pools use the combined page layout [NB, 2, Hkv, bs, D]
(K = index 0, V = index 1; see ops/pallas/paged_attention.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas.paged_attention import (
    paged_chunk_attention, paged_chunk_attention_reference,
    paged_decode_attention, paged_decode_attention_reference,
    paged_decode_attention_step, paged_decode_attention_step_reference)


def _setup(rng, S, H, D, Hkv, NB, bs, MB):
    q = jnp.asarray(rng.randn(S, H, D), jnp.float32)
    kv = jnp.asarray(rng.randn(NB, 2, Hkv, bs, D), jnp.float32)
    bt = jnp.asarray(rng.permutation(NB)[:S * MB].reshape(S, MB), jnp.int32)
    return q, kv, bt


class TestPagedDecode:

    @pytest.mark.parametrize("Hkv", [2, 8])
    def test_matches_reference(self, Hkv):
        rng = np.random.RandomState(0)
        S, H, D, NB, bs, MB = 5, 8, 64, 32, 8, 4
        q, kv, bt = _setup(rng, S, H, D, Hkv, NB, bs, MB)
        cl = jnp.asarray([1, 8, 13, 30, 32], jnp.int32)
        out = paged_decode_attention(q, kv, bt, cl)
        ref = paged_decode_attention_reference(q, kv, bt, cl)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_empty_rows_zero(self):
        rng = np.random.RandomState(1)
        q, kv, bt = _setup(rng, 3, 4, 64, 2, 16, 8, 2)
        cl = jnp.asarray([5, 0, 0], jnp.int32)
        out = np.asarray(paged_decode_attention(q, kv, bt, cl))
        assert np.all(out[1:] == 0)
        assert np.any(out[0] != 0)

    def test_jit(self):
        rng = np.random.RandomState(2)
        q, kv, bt = _setup(rng, 4, 8, 64, 4, 16, 8, 2)
        cl = jnp.asarray([3, 9, 16, 1], jnp.int32)
        out = jax.jit(paged_decode_attention)(q, kv, bt, cl)
        ref = paged_decode_attention_reference(q, kv, bt, cl)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_large_d_manual_dma_path(self):
        """D = 128 exercises the manual-DMA two-slot pipeline (the serving
        path) rather than the BlockSpec fallback."""
        rng = np.random.RandomState(6)
        S, H, Hkv, D, NB, bs, MB = 3, 4, 2, 128, 16, 8, 4
        q, kv, bt = _setup(rng, S, H, D, Hkv, NB, bs, MB)
        cl = jnp.asarray([2, 17, 32], jnp.int32)
        out = paged_decode_attention(q, kv, bt, cl)
        ref = paged_decode_attention_reference(q, kv, bt, cl)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-4)


class TestPagedChunkBatched:

    def test_matches_per_slot_reference(self):
        from deepspeed_tpu.ops.pallas.paged_attention import (
            paged_chunk_attention_batched, paged_chunk_attention_batched_reference)
        rng = np.random.RandomState(11)
        NC, Cs, H, Hkv, D, bs, MB = 4, 16, 8, 2, 64, 8, 6
        NB = NC * MB + 2
        kv = jnp.asarray(rng.randn(NB, 2, Hkv, bs, D), jnp.float32)
        q = jnp.asarray(rng.randn(NC, Cs, H, D), jnp.float32)
        bt = jnp.asarray(rng.permutation(NB - 1)[:NC * MB].reshape(NC, MB) + 1,
                         jnp.int32)
        q0s = jnp.asarray([0, 13, 40, 0], jnp.int32)
        ctxs = jnp.asarray([16, 29, 56, 0], jnp.int32)   # last slot empty
        out = jax.jit(paged_chunk_attention_batched)(q, kv, bt, q0s, ctxs)
        ref = paged_chunk_attention_batched_reference(q, kv, bt, q0s, ctxs)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-4)
        assert np.all(np.asarray(out)[3] == 0)


# what a step of several pages, walked only where the sequence has pages, can
# get wrong: (dims NC, Cs, H, Hkv, D, bs, MB), pages a step, q_starts, ctxs and
# what else the call is given
_STEP_CASES = {
    "bfloat16_inputs": dict(dims=(3, 16, 8, 2, 64, 8, 8), P=3,
                            q0s=[0, 13, 40], ctxs=[16, 29, 56],
                            dtype=jnp.bfloat16),
    "ctx_not_a_multiple_of_a_step": dict(dims=(2, 16, 8, 2, 64, 8, 8), P=3,
                                         q0s=[29, 5], ctxs=[45, 21]),
    "q0_inside_a_page": dict(dims=(2, 16, 4, 2, 32, 8, 8), P=2,
                             q0s=[13, 35], ctxs=[29, 51]),
    "window_edge_inside_a_group": dict(dims=(3, 16, 8, 2, 64, 8, 10), P=3,
                                       q0s=[0, 30, 61], ctxs=[16, 46, 77],
                                       window=20),
    "window_wider_than_a_context": dict(dims=(2, 16, 4, 2, 32, 8, 6), P=2,
                                        q0s=[0, 20], ctxs=[16, 36],
                                        window=64),
    "empty_slot_between_live_ones": dict(dims=(3, 16, 8, 2, 64, 8, 8), P=2,
                                         q0s=[24, 0, 40], ctxs=[40, 0, 56]),
    "g20_over_one_kv_head": dict(dims=(2, 8, 20, 1, 32, 8, 6), P=4,
                                 q0s=[3, 38], ctxs=[11, 46]),
    "d256": dict(dims=(2, 8, 4, 2, 256, 8, 6), P=4,
                 q0s=[0, 33], ctxs=[8, 41]),
    "int8_pages_of_128": dict(dims=(2, 16, 4, 2, 32, 128, 3), P=2,
                              q0s=[0, 300], ctxs=[16, 316], int8=True),
    "int8_pages_sharing_a_lane_row": dict(dims=(2, 16, 4, 2, 32, 64, 5), P=2,
                                          q0s=[70, 200], ctxs=[86, 216],
                                          int8=True),
    "int8_pages_bfloat16_q": dict(dims=(2, 16, 4, 2, 32, 64, 5), P=3,
                                  q0s=[70, 200], ctxs=[86, 216], int8=True,
                                  dtype=jnp.bfloat16),
    "alibi": dict(dims=(2, 16, 8, 2, 64, 8, 6), P=2,
                  q0s=[0, 29], ctxs=[16, 45], alibi=True),
    "alibi_window": dict(dims=(2, 16, 8, 2, 64, 8, 6), P=4,
                         q0s=[0, 29], ctxs=[16, 45], alibi=True, window=12),
    "verify_slots_of_4_rows": dict(dims=(5, 4, 8, 2, 64, 8, 6), P=2,
                                   q0s=[0, 17, 44, 0, 30],
                                   ctxs=[4, 21, 48, 0, 33]),
    "one_page_a_step": dict(dims=(2, 16, 8, 2, 64, 8, 6), P=1,
                            q0s=[0, 29], ctxs=[16, 45]),
    "step_longer_than_the_table": dict(dims=(2, 16, 8, 2, 64, 8, 5), P=8,
                                       q0s=[0, 24], ctxs=[16, 40]),
    "picked_step": dict(dims=(2, 16, 8, 2, 64, 8, 12), P=None,
                        q0s=[70, 5], ctxs=[86, 21], window=48),
}


class TestPagedChunkStep:
    """The chunk kernel's grid step: ``P`` pages at once, from the window's
    first page to the diagonal or the context's end, the MXU handed the
    operands as stored, no mask where a group is wholly visible."""

    def _arguments(self, case, seed=49):
        from deepspeed_tpu.ops.pallas.paged_attention import kv_quantize_rows
        NC, Cs, H, Hkv, D, bs, MB = case["dims"]
        rng = np.random.RandomState(seed)
        dtype = case.get("dtype", jnp.float32)
        NB = NC * MB + 1
        kv = jnp.asarray(rng.randn(NB, 2, Hkv, bs, D), dtype)
        q = jnp.asarray(rng.randn(NC, Cs, H, D), dtype)
        bt = jnp.asarray(rng.permutation(NB - 1)[:NC * MB].reshape(NC, MB) + 1,
                         jnp.int32)
        kw = {k: case[k] for k in ("window", "alibi") if k in case}
        ref_kv, call_kv = kv.astype(jnp.float32), kv
        if case.get("int8"):
            call_kv, sc = kv_quantize_rows(kv)
            ref_kv = call_kv.astype(jnp.float32) * sc[..., None]
            kw["kv_scales"] = sc
        tail = (bt, jnp.asarray(case["q0s"], jnp.int32),
                jnp.asarray(case["ctxs"], jnp.int32))
        return (q, call_kv) + tail, (q.astype(jnp.float32), ref_kv) + tail, kw

    @staticmethod
    def _pages(monkeypatch, pages):
        """Hold a step to ``pages`` pages (None: what the shapes pick)."""
        from deepspeed_tpu.ops.pallas import paged_attention as pa
        if pages is not None:
            monkeypatch.setattr(pa, "_pick_chunk_pages",
                                lambda *a, **kw: pages)
        return pa

    @pytest.mark.parametrize("name", sorted(_STEP_CASES))
    def test_matches_float32_reference(self, name, monkeypatch):
        case = _STEP_CASES[name]
        pa = self._pages(monkeypatch, case["P"])
        args, ref_args, kw = self._arguments(case)
        out = pa.paged_chunk_attention_batched(*args, **kw)
        assert out.dtype == args[0].dtype
        kw.pop("kv_scales", None)
        ref = pa.paged_chunk_attention_batched_reference(*ref_args, **kw)
        # a bfloat16 call rounds p for the MXU and its output: 2**-8
        tol = dict(atol=3e-5, rtol=3e-4) if out.dtype == jnp.float32 \
            else dict(atol=2e-2, rtol=2e-2)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref), **tol)
        for sl, ctx in enumerate(case["ctxs"]):
            if ctx == 0:
                assert not np.asarray(out[sl], np.float32).any()

    @pytest.mark.parametrize("name", ["q0_inside_a_page",
                                      "window_edge_inside_a_group",
                                      "bfloat16_inputs",
                                      "int8_pages_sharing_a_lane_row"])
    def test_unmasked_and_masked_branch_agree(self, name, monkeypatch):
        """A group every row sees whole may take either branch: with the
        interior test switched off every group builds its mask, and the
        result is the same."""
        pa = self._pages(monkeypatch, 1)
        case = _STEP_CASES[name]
        args, _, kw = self._arguments(case)
        taken = []
        inner = pa._chunk_group_inner
        monkeypatch.setattr(
            pa, "_chunk_group_inner",
            lambda *a: taken.append(1) or inner(*a))
        out = pa.paged_chunk_attention_batched(*args, **kw)
        assert taken                         # the kernel asks this function
        monkeypatch.setattr(pa, "_chunk_group_inner",
                            lambda k0, *a: k0 < 0)
        masked = pa.paged_chunk_attention_batched(*args, **kw)
        # the same arithmetic: what is left is how the interpreter's two
        # programs fuse it (one unit in the last place of a float32)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(masked, np.float32),
                                   atol=1e-6, rtol=1e-6)

    def test_which_groups_build_no_mask(self):
        """The predicate itself, on plain integers: below the first row,
        inside ctx, inside the last row's window."""
        from deepspeed_tpu.ops.pallas.paged_attention import \
            _chunk_group_inner as inner
        i = lambda *a: bool(inner(*map(np.int32, a[:5]), a[5]))
        assert i(0, 24, 23, 16, 40, None)        # last key == first row
        assert not i(0, 24, 22, 16, 40, None)    # straddles the diagonal
        assert not i(24, 24, 60, 16, 47, None)   # runs past ctx
        assert i(24, 24, 60, 16, 48, None)
        assert i(24, 24, 60, 16, 76, 52)         # 24 > 60 + 15 - 52
        assert not i(24, 24, 60, 16, 76, 51)     # the last row's edge at 24

    def test_step_pages_follow_the_shapes(self):
        from deepspeed_tpu.ops.pallas.paged_attention import (
            MAX_PAGES_PER_CHUNK_STEP, _pick_chunk_pages)
        cell11 = _pick_chunk_pages(128, 2, 256, 2, 128 * 8, 272)
        assert 2 <= cell11 <= MAX_PAGES_PER_CHUNK_STEP
        # more rows a KV head or wider pages: no more pages a step
        assert _pick_chunk_pages(128, 1, 128, 2, 128 * 20, 64) <= cell11
        assert _pick_chunk_pages(128, 8, 128, 2, 128 * 4, 40) <= \
            _pick_chunk_pages(128, 4, 128, 2, 128 * 4, 40)
        # never more than the table has, never fewer than one
        assert _pick_chunk_pages(128, 2, 256, 2, 1024, 3) == 3
        assert _pick_chunk_pages(128, 8, 256, 4, 128 * 64, 40) == 1
        assert _pick_chunk_pages(8, 2, 64, 4, 64, 100) == \
            MAX_PAGES_PER_CHUNK_STEP


class TestPagedDecodeStep:
    """Fused decode step: prior-context flash + inline current token + page
    write, pool aliased through. Edge cases: ctx 1 (no pages yet), page
    boundary, ctx 0 (padding row: no write, zero output)."""

    @pytest.mark.parametrize("Hkv,ctxs", [
        (8, [9, 17, 30]),
        (2, [1, 8, 32]),          # GQA; ctx=1; exact page boundary
        (4, [0, 5]),              # padding row
    ])
    def test_matches_reference(self, Hkv, ctxs):
        rng = np.random.RandomState(7)
        S, H, D, bs = len(ctxs), 8, 64, 8
        MB = 4
        NB = S * MB + 2
        kv = jnp.asarray(rng.randn(NB, 2, Hkv, bs, D), jnp.float32)
        q = jnp.asarray(rng.randn(S, H, D), jnp.float32)
        kn = jnp.asarray(rng.randn(S, Hkv, D), jnp.float32)
        vn = jnp.asarray(rng.randn(S, Hkv, D), jnp.float32)
        # disjoint per-sequence page tables (pages are exclusive in serving)
        bt = jnp.asarray(rng.permutation(NB - 1)[:S * MB].reshape(S, MB) + 1,
                         jnp.int32)
        cl = jnp.asarray(ctxs, jnp.int32)
        out, kvf = jax.jit(paged_decode_attention_step)(q, kn, vn, kv, bt, cl)
        orf, kvrf = paged_decode_attention_step_reference(q, kn, vn, kv,
                                                          bt, cl)
        np.testing.assert_allclose(np.asarray(out), np.asarray(orf),
                                   atol=2e-5, rtol=2e-4)
        np.testing.assert_array_equal(np.asarray(kvf), np.asarray(kvrf))
        for i, c in enumerate(ctxs):
            if c == 0:
                assert np.all(np.asarray(out)[i] == 0)

    def test_manual_dma_path_d128(self):
        rng = np.random.RandomState(8)
        S, H, Hkv, D, bs, MB = 2, 4, 2, 128, 8, 3
        NB = S * MB + 1
        kv = jnp.asarray(rng.randn(NB, 2, Hkv, bs, D), jnp.float32)
        q = jnp.asarray(rng.randn(S, H, D), jnp.float32)
        kn = jnp.asarray(rng.randn(S, Hkv, D), jnp.float32)
        vn = jnp.asarray(rng.randn(S, Hkv, D), jnp.float32)
        bt = jnp.asarray(rng.permutation(NB - 1)[:S * MB].reshape(S, MB) + 1,
                         jnp.int32)
        cl = jnp.asarray([6, 20], jnp.int32)
        out, kvf = paged_decode_attention_step(q, kn, vn, kv, bt, cl)
        orf, kvrf = paged_decode_attention_step_reference(q, kn, vn, kv,
                                                          bt, cl)
        np.testing.assert_allclose(np.asarray(out), np.asarray(orf),
                                   atol=2e-5, rtol=2e-4)
        np.testing.assert_array_equal(np.asarray(kvf), np.asarray(kvrf))


class TestPagedChunk:

    @pytest.mark.parametrize("q_start,ctx", [(0, 16), (13, 29), (40, 56)])
    def test_matches_reference(self, q_start, ctx):
        rng = np.random.RandomState(3)
        C, H, D, Hkv, NB, bs, MB = 16, 8, 64, 2, 32, 8, 8
        q = jnp.asarray(rng.randn(C, H, D), jnp.float32)
        kv = jnp.asarray(rng.randn(NB, 2, Hkv, bs, D), jnp.float32)
        bt = jnp.asarray(rng.permutation(NB)[:MB], jnp.int32)
        out = paged_chunk_attention(q, kv, bt, q_start, ctx)
        ref = paged_chunk_attention_reference(q, kv, bt, q_start, ctx)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_empty_ctx_zero(self):
        rng = np.random.RandomState(4)
        q = jnp.asarray(rng.randn(8, 4, 64), jnp.float32)
        kv = jnp.asarray(rng.randn(16, 2, 2, 8, 64), jnp.float32)
        bt = jnp.zeros((4,), jnp.int32)
        out = np.asarray(paged_chunk_attention(q, kv, bt, 0, 0))
        assert np.all(out == 0)

    def test_matches_dense_flash_prefill(self):
        """Chunk attention over pages == dense causal attention on the same KV."""
        from deepspeed_tpu.ops.attention import reference_attention
        rng = np.random.RandomState(5)
        C, H, D, NB, bs = 16, 4, 64, 8, 8
        MB = C // bs
        q = jnp.asarray(rng.randn(C, H, D), jnp.float32)
        kd = jnp.asarray(rng.randn(C, H, D), jnp.float32)
        vd = jnp.asarray(rng.randn(C, H, D), jnp.float32)
        bt = jnp.asarray([3, 5], jnp.int32)
        kv_pages = jnp.zeros((NB, 2, H, bs, D), jnp.float32)
        kv_pages = kv_pages.at[bt, 0].set(
            jnp.moveaxis(kd.reshape(MB, bs, H, D), 1, 2))
        kv_pages = kv_pages.at[bt, 1].set(
            jnp.moveaxis(vd.reshape(MB, bs, H, D), 1, 2))
        out = paged_chunk_attention(q, kv_pages, bt, 0, C)
        ref = reference_attention(q[None], kd[None], vd[None], causal=True)[0]
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)


class TestPackedFlash:
    """flash_attention_packed: the prefill-from-zero fast path's kernel
    (segment-masked packed flash; ragged_model.build_prefill_forward)."""

    @pytest.mark.parametrize("Hkv", [4, 2])
    def test_matches_per_segment_reference(self, Hkv):
        from deepspeed_tpu.ops.attention import reference_attention
        from deepspeed_tpu.ops.pallas.flash_attention import (
            flash_attention_packed)
        rng = np.random.RandomState(3)
        H, D = 4, 32
        lens = [7, 19, 3, 33]
        R = sum(lens)
        seg = np.concatenate([np.full(n, i, np.int32)
                              for i, n in enumerate(lens)])
        q = jnp.asarray(rng.randn(R, H, D), jnp.float32)
        k = jnp.asarray(rng.randn(R, Hkv, D), jnp.float32)
        v = jnp.asarray(rng.randn(R, Hkv, D), jnp.float32)
        out, lse = flash_attention_packed(q, k, v, jnp.asarray(seg),
                                          with_lse=True)
        rep = H // Hkv
        r0 = 0
        for n in lens:
            sl = slice(r0, r0 + n)
            ref = reference_attention(
                q[None, sl], jnp.repeat(k[None, sl], rep, 2),
                jnp.repeat(v[None, sl], rep, 2), causal=True)[0]
            np.testing.assert_allclose(np.asarray(out[sl]), np.asarray(ref),
                                       atol=2e-5)
            r0 += n
        assert bool(jnp.isfinite(lse).all())

    def test_padding_rows_are_isolated(self):
        """Rows with segment -1 (slot padding) must not leak into real rows."""
        from deepspeed_tpu.ops.attention import reference_attention
        from deepspeed_tpu.ops.pallas.flash_attention import (
            flash_attention_packed)
        rng = np.random.RandomState(4)
        H, D = 2, 16
        # real rows 0..9 (segment 0), pad rows 10..15 (segment -1) with huge
        # values that would visibly corrupt the output if attended
        seg = np.asarray([0] * 10 + [-1] * 6, np.int32)
        q = jnp.asarray(rng.randn(16, H, D), jnp.float32)
        k = jnp.asarray(rng.randn(16, H, D), jnp.float32).at[10:].set(100.0)
        v = jnp.asarray(rng.randn(16, H, D), jnp.float32).at[10:].set(1e6)
        out = flash_attention_packed(q, k, v, jnp.asarray(seg))
        ref = reference_attention(q[None, :10], k[None, :10], v[None, :10],
                                  causal=True)[0]
        np.testing.assert_allclose(np.asarray(out[:10]), np.asarray(ref),
                                   atol=2e-5)
        assert bool(jnp.isfinite(out).all())

    def test_jit_and_nondivisible_rows(self):
        from deepspeed_tpu.ops.pallas.flash_attention import (
            flash_attention_packed)
        rng = np.random.RandomState(5)
        R, H, D = 200, 2, 32   # R > 128 and not a multiple of 128 -> pads
        seg = np.repeat([0, 1], 100).astype(np.int32)
        q = jnp.asarray(rng.randn(R, H, D), jnp.float32)
        k = jnp.asarray(rng.randn(R, H, D), jnp.float32)
        o1 = flash_attention_packed(q, k, k, jnp.asarray(seg))
        o2 = jax.jit(flash_attention_packed)(q, k, k, jnp.asarray(seg))
        assert o1.shape == (R, H, D)
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-6)


class TestPagedDecodeSidebuf:
    """Fused frozen-prefix + side-slab decode kernel (the attention body of
    the decode step's side-buffer form). Reference = the round-4 two-piece
    computation: paged prefix with lse, dense side piece, lse merge."""

    @pytest.mark.parametrize("Hkv,j", [(2, 0), (2, 3), (4, 5), (8, 7)])
    def test_matches_reference(self, Hkv, j):
        from deepspeed_tpu.ops.pallas.paged_attention import (
            paged_decode_attention_sidebuf,
            paged_decode_attention_sidebuf_reference)
        rng = np.random.RandomState(3)
        S, H, D, bs, MB, C = 4, 8, 128, 8, 3, 8
        NB = S * MB + 1
        q = jnp.asarray(rng.randn(S, H, D), jnp.float32)
        kv = jnp.asarray(rng.randn(NB, 2, Hkv, bs, D), jnp.float32)
        bt = jnp.asarray(rng.permutation(NB - 1)[:S * MB].reshape(S, MB) + 1,
                         jnp.int32)
        # prefix 0 (fresh sequence: all context in the side slab), mid-page,
        # page boundary, full
        prefix = jnp.asarray([0, 5, bs, MB * bs], jnp.int32)
        sk = jnp.asarray(rng.randn(S, C, Hkv, D), jnp.float32)
        sv = jnp.asarray(rng.randn(S, C, Hkv, D), jnp.float32)
        out = jax.jit(paged_decode_attention_sidebuf,
                      static_argnames=())(q, kv, bt, prefix, sk, sv, j)
        ref = paged_decode_attention_sidebuf_reference(q, kv, bt, prefix,
                                                       sk, sv, j)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-4)

    @pytest.mark.parametrize("window,j", [(12, 0), (12, 6), (4, 7)])
    def test_windowed_matches_reference(self, window, j):
        """Sliding window over position prefix + j: the page-side window
        start moves with j; side columns below j+1-window hide."""
        from deepspeed_tpu.ops.pallas.paged_attention import (
            paged_decode_attention_sidebuf,
            paged_decode_attention_sidebuf_reference)
        rng = np.random.RandomState(9)
        S, H, Hkv, D, bs, MB, C = 3, 4, 2, 128, 8, 3, 8
        NB = S * MB + 1
        q = jnp.asarray(rng.randn(S, H, D), jnp.float32)
        kv = jnp.asarray(rng.randn(NB, 2, Hkv, bs, D), jnp.float32)
        bt = jnp.asarray(rng.permutation(NB - 1)[:S * MB].reshape(S, MB) + 1,
                         jnp.int32)
        prefix = jnp.asarray([0, 7, 2 * bs + 3], jnp.int32)
        sk = jnp.asarray(rng.randn(S, C, Hkv, D), jnp.float32)
        sv = jnp.asarray(rng.randn(S, C, Hkv, D), jnp.float32)
        out = paged_decode_attention_sidebuf(q, kv, bt, prefix, sk, sv, j,
                                             window=window)
        ref = paged_decode_attention_sidebuf_reference(
            q, kv, bt, prefix, sk, sv, j, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-4)


class TestInt8Pages:
    """int8 KV pages: kernels with (int8 values, per-token-head scales) must
    match the bf16/f32 reference run on the dequantized pages exactly (the
    dequant is algebraically folded, not approximated — scale commutes
    through the dots)."""

    def _qpages(self, rng, NB, Hkv, bs, D):
        from deepspeed_tpu.ops.pallas.paged_attention import kv_quantize_rows
        kv = jnp.asarray(rng.randn(NB, 2, Hkv, bs, D), jnp.float32)
        kvq, sc = kv_quantize_rows(kv)
        kvd = kvq.astype(jnp.float32) * sc[..., None]
        return kvq, sc, kvd

    def test_decode_matches_dequant_reference(self):
        from deepspeed_tpu.ops.pallas.paged_attention import (
            paged_decode_attention, paged_decode_attention_reference)
        rng = np.random.RandomState(21)
        S, H, Hkv, D, bs, MB = 3, 8, 2, 128, 128, 2
        NB = S * MB + 1
        kvq, sc, kvd = self._qpages(rng, NB, Hkv, bs, D)
        q = jnp.asarray(rng.randn(S, H, D), jnp.float32)
        bt = jnp.asarray(rng.permutation(NB - 1)[:S * MB].reshape(S, MB) + 1,
                         jnp.int32)
        cl = jnp.asarray([5, 130, 256], jnp.int32)
        out = paged_decode_attention(q, kvq, bt, cl, kv_scales=sc)
        ref = paged_decode_attention_reference(q, kvd, bt, cl)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=3e-5, rtol=3e-4)

    def test_sidebuf_matches_dequant_reference(self):
        from deepspeed_tpu.ops.pallas.paged_attention import (
            paged_decode_attention_sidebuf,
            paged_decode_attention_sidebuf_reference)
        rng = np.random.RandomState(22)
        S, H, Hkv, D, bs, MB, C = 3, 4, 2, 128, 128, 2, 8
        NB = S * MB + 1
        kvq, sc, kvd = self._qpages(rng, NB, Hkv, bs, D)
        q = jnp.asarray(rng.randn(S, H, D), jnp.float32)
        bt = jnp.asarray(rng.permutation(NB - 1)[:S * MB].reshape(S, MB) + 1,
                         jnp.int32)
        prefix = jnp.asarray([0, 70, 200], jnp.int32)
        sk = jnp.asarray(rng.randn(S, C, Hkv, D), jnp.float32)
        sv = jnp.asarray(rng.randn(S, C, Hkv, D), jnp.float32)
        out = paged_decode_attention_sidebuf(q, kvq, bt, prefix, sk, sv, 5,
                                             kv_scales=sc)
        ref = paged_decode_attention_sidebuf_reference(q, kvd, bt, prefix,
                                                       sk, sv, 5)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=3e-5, rtol=3e-4)

    def test_step_quantizes_new_rows(self):
        from deepspeed_tpu.ops.pallas.paged_attention import (
            kv_quantize_rows, paged_decode_attention_step,
            paged_decode_attention_step_reference)
        rng = np.random.RandomState(23)
        S, H, Hkv, D, bs, MB = 2, 4, 2, 128, 128, 2
        NB = S * MB + 1
        kvq, sc, kvd = self._qpages(rng, NB, Hkv, bs, D)
        q = jnp.asarray(rng.randn(S, H, D), jnp.float32)
        kn = jnp.asarray(rng.randn(S, Hkv, D), jnp.float32)
        vn = jnp.asarray(rng.randn(S, Hkv, D), jnp.float32)
        bt = jnp.asarray(rng.permutation(NB - 1)[:S * MB].reshape(S, MB) + 1,
                         jnp.int32)
        cl = jnp.asarray([6, 140], jnp.int32)
        out, kvf, scf = paged_decode_attention_step(
            q, kn, vn, kvq, bt, cl, kv_scales=sc)
        # the kernel attends the CURRENT token at full precision from
        # registers (quantization happens at the page write, for future
        # reads) — so the attention reference uses unquantized kn/vn
        orf, _ = paged_decode_attention_step_reference(q, kn, vn, kvd, bt, cl)
        np.testing.assert_allclose(np.asarray(out), np.asarray(orf),
                                   atol=3e-5, rtol=3e-4)
        # the returned pool holds the QUANTIZED new rows: it must dequantize
        # to the reference pool built from dequantized new rows
        knq, kns = kv_quantize_rows(kn)
        vnq, vns = kv_quantize_rows(vn)
        knd = knq.astype(jnp.float32) * kns[..., None]
        vnd = vnq.astype(jnp.float32) * vns[..., None]
        _, kvrf = paged_decode_attention_step_reference(q, knd, vnd, kvd,
                                                        bt, cl)
        kvfd = kvf.astype(jnp.float32) * scf[..., None]
        np.testing.assert_allclose(np.asarray(kvfd), np.asarray(kvrf),
                                   atol=1e-6)

    def test_chunk_matches_dequant_reference(self):
        from deepspeed_tpu.ops.pallas.paged_attention import (
            paged_chunk_attention_batched,
            paged_chunk_attention_batched_reference)
        rng = np.random.RandomState(24)
        NC, Cs, H, Hkv, D, bs, MB = 2, 16, 4, 2, 128, 128, 2
        NB = NC * MB + 1
        kvq, sc, kvd = self._qpages(rng, NB, Hkv, bs, D)
        q = jnp.asarray(rng.randn(NC, Cs, H, D), jnp.float32)
        bt = jnp.asarray(rng.permutation(NB - 1)[:NC * MB].reshape(NC, MB) + 1,
                         jnp.int32)
        q0s = jnp.asarray([0, 100], jnp.int32)
        ctxs = jnp.asarray([16, 116], jnp.int32)
        out = paged_chunk_attention_batched(q, kvq, bt, q0s, ctxs,
                                            kv_scales=sc)
        ref = paged_chunk_attention_batched_reference(q, kvd, bt, q0s, ctxs)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=3e-5, rtol=3e-4)


def _walk_group(bs, Hkv, D, MB, esize=4, quant=False):
    from deepspeed_tpu.ops.pallas.paged_attention import (
        _pick_decode_pages, _scale_tile_rows)
    return bs * _pick_decode_pages(
        bs, Hkv, D, esize, MB, _scale_tile_rows(Hkv, bs) if quant else 0)


#: name -> (H, Hkv, D, bs, MB, prefixes, options). A prefix is tokens, or a
#: function of the tokens a group of the kernel's walk holds at that shape;
#: options: window, j (default 4), int8, alibi, C (the slab's steps, default 8)
_WALK_CASES = {
    # no key in the pages, and one
    "prefix_0_and_1": (8, 2, 128, 8, 24, [0, 1, 0, 1], {}),
    # the context's end on a page's and on a group's last slot, and one past
    "ends_on_a_page_and_on_a_group": (
        8, 2, 128, 8, 40, [8, lambda T: T, lambda T: 2 * T, 16], {}),
    "one_past_a_group": (
        8, 2, 128, 8, 40, [lambda T: T + 1, lambda T: 2 * T + 1, 9], {}),
    # more pages than a chunk of the older kernel could hold (32)
    "longer_than_32_pages": (8, 2, 128, 8, 48, [40 * 8 + 3, 48 * 8], {}),
    "very_different_lengths": (
        8, 2, 128, 8, 48, [3, 48 * 8, 0, 130, 17, 300, 1, 255], {}),
    # a row that walks nothing between two that do: the stream is handed
    # over the gap by a cold start, and the first row of all starts cold
    "empty_rows_between": (8, 2, 128, 8, 24, [0, 0, 100, 0, 0, 150, 0], {}),
    "window_start_inside_a_group": (
        8, 2, 128, 8, 48, [lambda T: 2 * T + 5, 300, 7, 48 * 8],
        {"window": 37}),
    "window_skips_whole_groups": (
        8, 2, 128, 8, 48, [lambda T: 3 * T - 1, 48 * 8, 200, 0],
        {"window": 100, "j": 2}),
    "window_of_one_step": (8, 2, 128, 8, 24, [64, 65, 0], {"window": 1,
                                                           "j": 0}),
    "int8_pages": (4, 2, 128, 128, 20, [0, 70, 1024, 1025, 2500],
                   {"int8": True, "j": 5}),
    "int8_pages_windowed": (4, 2, 128, 128, 20, [1300, 2560, 129],
                            {"int8": True, "window": 1100, "j": 1}),
    "alibi": (8, 2, 128, 8, 40, [0, 5, 130, 320], {"alibi": True, "j": 3}),
    "heads_8_over_2": (8, 2, 128, 8, 24, [0, 77, 150, 192], {"j": 0, "C": 4}),
    "heads_16_over_2_width_256": (16, 2, 256, 8, 24, [0, 77, 150, 192],
                                  {"j": 0, "C": 4}),
    "heads_20_over_1": (20, 1, 128, 8, 24, [0, 77, 150, 192], {"j": 0}),
    "heads_32_over_2": (32, 2, 128, 8, 24, [0, 77, 150, 192], {"j": 0,
                                                               "C": 4}),
    "heads_32_over_4": (32, 4, 128, 8, 24, [0, 77, 150, 192], {"j": 0,
                                                               "C": 2}),
    "heads_32_over_8": (32, 8, 128, 8, 24, [0, 77, 150, 192], {"j": 0,
                                                               "C": 1}),
    "table_272_pages_over_a_3_page_row": (8, 2, 128, 8, 272, [20, 24, 3], {}),
    # what the several-rows-a-step form of the older kernel was tested on
    "batched": (4, 2, 128, 8, 3, [0, 5, 8, 24, 1, 16, 13, 20], {}),
    "batched_windowed": (4, 2, 128, 8, 3, [0, 5, 8, 24, 1, 16, 13, 20],
                         {"window": 12}),
    "batched_int8": (4, 2, 128, 128, 2, [0, 70, 128, 250],
                     {"int8": True, "j": 5}),
}


class TestDecodeWalk:
    """The decode kernel's walk over a row's groups of pages
    (``_decode_walk_kernel``) against the two-piece reference: where a
    context ends and a window starts relative to pages and groups, rows that
    walk nothing, every cell's head shape, int8 pages, ALiBi."""

    @pytest.mark.parametrize("name", sorted(_WALK_CASES))
    def test_sidebuf_matches_reference(self, name):
        from deepspeed_tpu.ops.pallas.paged_attention import (
            kv_quantize_rows, paged_decode_attention_sidebuf,
            paged_decode_attention_sidebuf_reference)
        H, Hkv, D, bs, MB, prefixes, opt = _WALK_CASES[name]
        int8 = opt.get("int8", False)
        T = _walk_group(bs, Hkv, D, MB, esize=1 if int8 else 4, quant=int8)
        prefix = [p(T) if callable(p) else p for p in prefixes]
        assert max(prefix) <= MB * bs
        rng = np.random.RandomState(51)
        S, C, j = len(prefix), opt.get("C", 8), opt.get("j", 4)
        held = [-(-p // bs) for p in prefix]
        NB = sum(held) + 1
        kv = jnp.asarray(rng.randn(NB, 2, Hkv, bs, D), jnp.float32)
        # a row's pages are its own; page 0 is nobody's and holds NaN, as do
        # the table's unused entries: what a row does not have is not read
        kv = kv.at[0].set(jnp.nan)
        order = rng.permutation(NB - 1) + 1
        bt = np.zeros((S, MB), np.int32)
        at = 0
        for s_, n in enumerate(held):
            bt[s_, :n] = order[at:at + n]
            at += n
        q = jnp.asarray(rng.randn(S, H, D), jnp.float32)
        sk = jnp.asarray(rng.randn(S, C, Hkv, D), jnp.float32)
        sv = jnp.asarray(rng.randn(S, C, Hkv, D), jnp.float32)
        kw = {k: opt[k] for k in ("window", "alibi") if k in opt}
        pages, scales = kv, {}
        if int8:
            pages, sc = kv_quantize_rows(kv.at[0].set(0.0))
            kv = (pages.astype(jnp.float32) * sc[..., None]).at[0].set(
                jnp.nan)
            scales = {"kv_scales": sc.at[0].set(jnp.nan)}
        out = paged_decode_attention_sidebuf(
            q, pages, jnp.asarray(bt), jnp.asarray(prefix, jnp.int32), sk,
            sv, j, **kw, **scales)
        # the reference gathers whole tables: give it zeros where the kernel
        # must not look
        ref = paged_decode_attention_sidebuf_reference(
            q, kv.at[0].set(0.0), jnp.asarray(bt),
            jnp.asarray(prefix, jnp.int32), sk, sv, j, **kw)
        assert np.all(np.isfinite(np.asarray(out))), name
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=3e-5, rtol=3e-4)

    @pytest.mark.parametrize("Hkv,window", [(2, None), (8, None), (2, 21)])
    def test_without_a_slab_matches_reference_and_its_lse(self, Hkv, window):
        """A paged pass's decode rows: the pages hold everything, a row of
        no key writes zeros and an lse of NEG_INF."""
        rng = np.random.RandomState(52)
        S, H, D, bs, MB = 6, 8, 128, 8, 40
        NB = S * MB
        q, kv, bt = _setup(rng, S, H, D, Hkv, NB, bs, MB)
        T = _walk_group(bs, Hkv, D, MB)
        cl = jnp.asarray([0, 1, T, T + 1, 2 * T + 5, MB * bs], jnp.int32)
        out, lse = paged_decode_attention(q, kv, bt, cl, window=window,
                                          with_lse=True)
        ref, ref_lse = paged_decode_attention_reference(
            q, kv, bt, cl, window=window, with_lse=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-4)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                                   atol=2e-5, rtol=2e-5)
        assert np.all(np.asarray(out)[0] == 0)

    def test_group_pages_follow_the_shapes(self):
        """A group is as large as keeps the slots inside 8 MB, up to
        ``MAX_PAGES_PER_GROUP`` and the table's width: at the cells' shapes
        16 pages of 64 and of 128 KiB, 10 of 256 KiB, 5 of 512 KiB."""
        from deepspeed_tpu.ops.pallas.paged_attention import (
            _DECODE_SLOTS, MAX_PAGES_PER_GROUP, _pick_decode_pages)
        picked = {}
        for hkv, d, mb in ((2, 128, 96), (2, 256, 272), (1, 128, 96),
                           (4, 128, 208), (8, 128, 40)):
            p = picked[hkv, d] = _pick_decode_pages(128, hkv, d, 2, mb)
            assert 1 <= p <= MAX_PAGES_PER_GROUP
            assert _DECODE_SLOTS * p * 2 * hkv * 128 * d * 2 <= 8 << 20
        assert picked == {(2, 128): 16, (2, 256): 10, (1, 128): 16,
                          (4, 128): 10, (8, 128): 5}
        assert _pick_decode_pages(128, 8, 128, 2, 3) == 3
        # int8 pages: half the bytes and a scale tile a page
        assert _pick_decode_pages(128, 8, 128, 1, 40, 16) == 10


class TestAlibi:
    """ALiBi in the paged kernels (BLOOM serving parity — reference
    csrc/transformer/inference/csrc/softmax.cu applies alibi on the fused
    softmax path). The kernels add slope_h * k_pos; the -slope_h * q_pos
    term is a softmax row constant and cancels."""

    def test_slope_helper_matches_model_slopes(self):
        from deepspeed_tpu.models.decoder import alibi_slopes
        from deepspeed_tpu.ops.pallas.paged_attention import _alibi_slope
        for H in (4, 8, 16, 12, 14):
            got = _alibi_slope(jnp.arange(H, dtype=jnp.float32), H)
            np.testing.assert_allclose(np.asarray(got),
                                       np.asarray(alibi_slopes(H)),
                                       rtol=1e-6)

    @pytest.mark.parametrize("D", [64, 128])
    def test_decode_matches_reference(self, D):
        from deepspeed_tpu.ops.pallas.paged_attention import (
            paged_decode_attention, paged_decode_attention_reference)
        rng = np.random.RandomState(41)
        S, H, Hkv, NB, bs, MB = 3, 8, 2, 20, 8, 4
        q, kv, bt = _setup(rng, S, H, D, Hkv, NB, bs, MB)
        cl = jnp.asarray([1, 9, 30], jnp.int32)
        out = paged_decode_attention(q, kv, bt, cl, alibi=True)
        ref = paged_decode_attention_reference(q, kv, bt, cl, alibi=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-4)

    def test_step_matches_reference(self):
        from deepspeed_tpu.ops.pallas.paged_attention import (
            paged_decode_attention_step, paged_decode_attention_step_reference)
        rng = np.random.RandomState(42)
        S, H, Hkv, D, bs, MB = 2, 4, 2, 128, 8, 3
        NB = S * MB + 1
        kv = jnp.asarray(rng.randn(NB, 2, Hkv, bs, D), jnp.float32)
        q = jnp.asarray(rng.randn(S, H, D), jnp.float32)
        kn = jnp.asarray(rng.randn(S, Hkv, D), jnp.float32)
        vn = jnp.asarray(rng.randn(S, Hkv, D), jnp.float32)
        bt = jnp.asarray(rng.permutation(NB - 1)[:S * MB].reshape(S, MB) + 1,
                         jnp.int32)
        cl = jnp.asarray([6, 17], jnp.int32)
        out, kvf = paged_decode_attention_step(q, kn, vn, kv, bt, cl,
                                               alibi=True)
        orf, kvrf = paged_decode_attention_step_reference(q, kn, vn, kv,
                                                          bt, cl, alibi=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(orf),
                                   atol=2e-5, rtol=2e-4)

    def test_chunk_matches_reference(self):
        from deepspeed_tpu.ops.pallas.paged_attention import (
            paged_chunk_attention_batched,
            paged_chunk_attention_batched_reference)
        rng = np.random.RandomState(43)
        NC, Cs, H, Hkv, D, bs, MB = 2, 16, 8, 2, 64, 8, 6
        NB = NC * MB + 1
        kv = jnp.asarray(rng.randn(NB, 2, Hkv, bs, D), jnp.float32)
        q = jnp.asarray(rng.randn(NC, Cs, H, D), jnp.float32)
        bt = jnp.asarray(rng.permutation(NB - 1)[:NC * MB].reshape(NC, MB) + 1,
                         jnp.int32)
        q0s = jnp.asarray([0, 13], jnp.int32)
        ctxs = jnp.asarray([16, 29], jnp.int32)
        out = paged_chunk_attention_batched(q, kv, bt, q0s, ctxs, alibi=True)
        ref = paged_chunk_attention_batched_reference(q, kv, bt, q0s, ctxs,
                                                      alibi=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-4)

    def test_sidebuf_matches_reference(self):
        from deepspeed_tpu.ops.pallas.paged_attention import (
            paged_decode_attention_sidebuf,
            paged_decode_attention_sidebuf_reference)
        rng = np.random.RandomState(44)
        S, H, Hkv, D, bs, MB, C = 4, 8, 2, 128, 8, 3, 8
        NB = S * MB + 1
        kv = jnp.asarray(rng.randn(NB, 2, Hkv, bs, D), jnp.float32)
        q = jnp.asarray(rng.randn(S, H, D), jnp.float32)
        bt = jnp.asarray(rng.permutation(NB - 1)[:S * MB].reshape(S, MB) + 1,
                         jnp.int32)
        prefix = jnp.asarray([0, 5, bs, 2 * bs + 3], jnp.int32)
        sk = jnp.asarray(rng.randn(S, C, Hkv, D), jnp.float32)
        sv = jnp.asarray(rng.randn(S, C, Hkv, D), jnp.float32)
        out = paged_decode_attention_sidebuf(q, kv, bt, prefix, sk, sv, 5,
                                             alibi=True)
        ref = paged_decode_attention_sidebuf_reference(q, kv, bt, prefix,
                                                       sk, sv, 5, alibi=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-4)


class TestKVQuantizeEdgeCases:
    """kv_quantize_rows / kv_scales_to_tiles edge cases + the pinned
    round-trip error bound — the numeric contract docs/SERVING.md
    "Quantized KV" documents (the rtol tier derives from it)."""

    def test_zero_rows(self):
        from deepspeed_tpu.ops.pallas.paged_attention import kv_quantize_rows
        q, s = kv_quantize_rows(jnp.zeros((0, 3, 16), jnp.float32))
        assert q.shape == (0, 3, 16) and q.dtype == jnp.int8
        assert s.shape == (0, 3)

    def test_all_zero_row_quantizes_to_zero(self):
        from deepspeed_tpu.ops.pallas.paged_attention import (
            kv_dequantize_rows, kv_quantize_rows)
        q, s = kv_quantize_rows(jnp.zeros((2, 16), jnp.float32))
        assert not np.asarray(q).any()
        assert np.isfinite(np.asarray(s)).all()      # the 1e-20 floor holds
        assert not np.asarray(kv_dequantize_rows(q, s)).any()

    def test_single_element_extremes_and_saturation(self):
        from deepspeed_tpu.ops.pallas.paged_attention import kv_quantize_rows
        # one huge element per row: it maps to EXACTLY +-127 (amax/s == 127
        # by construction — no clipping needed), tiny siblings round to 0
        x = np.zeros((2, 128), np.float32)
        x[0, 3] = 3e4
        x[0, 7] = 1e-3
        x[1, 5] = -2e-6
        q, s = kv_quantize_rows(jnp.asarray(x))
        q = np.asarray(q)
        assert q[0, 3] == 127 and q[0, 7] == 0
        assert q[1, 5] == -127                        # row max-abs element
        assert np.abs(q).max() <= 127                 # never overflows int8
        # extreme magnitudes at both ends stay finite
        x2 = np.full((1, 128), 3.0e38, np.float32)
        q2, s2 = kv_quantize_rows(jnp.asarray(x2))
        assert np.isfinite(np.asarray(s2)).all()
        assert (np.asarray(q2) == 127).all()

    def test_roundtrip_error_bound_pinned(self):
        from deepspeed_tpu.ops.pallas.paged_attention import (
            kv_dequantize_rows, kv_quantize_rows)
        rng = np.random.RandomState(0)
        x = (rng.randn(64, 4, 128) * np.exp(rng.randn(64, 4, 1))
             ).astype(np.float32)
        q, s = kv_quantize_rows(jnp.asarray(x))
        deq = np.asarray(kv_dequantize_rows(q, s))
        amax = np.abs(x).max(-1, keepdims=True)
        # |x - deq(q(x))| <= s/2 = amax/254 per element (round-to-nearest
        # of x/s), the bound the rtol gate tier derives from
        assert (np.abs(x - deq) <= amax / 254 * (1 + 1e-5)).all()

    def test_write_dequant_value_idempotent(self):
        # the fused decode paths' invariant: re-quantizing the POOL value
        # reproduces the identical int8 bytes AND the identical scale
        # bytes, so every pool writer — raw-row quantizers (ragged pass,
        # verify step) and deq'd-row re-quantizers (decode step, sidebuf
        # flush) — stores bit-identical pages for the same token. The
        # scale exactness is a property of the amax/127 derivation:
        # s = fl(amax/127) satisfies fl(fl(127*s)/127) == s (verified
        # over 17.7M f32 bit patterns across the exponent range; the
        # div->mul->div composition is idempotent after the first
        # division), and the deq'd row's amax element is exactly
        # fl(127*s) because its max-abs value quantizes to +-127.
        from deepspeed_tpu.ops.pallas.paged_attention import (
            kv_quantize_rows, kv_write_dequant)
        rng = np.random.RandomState(1)
        x = jnp.asarray(rng.randn(32, 2, 128).astype(np.float32))
        q1, s1 = kv_quantize_rows(x)
        deq = kv_write_dequant(x)
        q2, s2 = kv_quantize_rows(deq)
        assert np.array_equal(np.asarray(q1), np.asarray(q2))
        assert np.array_equal(np.asarray(s1), np.asarray(s2))

    def test_scales_to_tiles_layout_and_padding(self):
        from deepspeed_tpu.ops.pallas.paged_attention import (
            kv_scale_tiles_shape, kv_scales_to_tiles)
        rng = np.random.RandomState(2)
        # 2*Hkv*bs = 256 scales -> 2 lane rows, padded to the 8-row tile:
        # a NON-multiple-of-8 logical row count (the padding case)
        NB, Hkv, bs = 3, 2, 64
        s = rng.rand(NB, 2, Hkv, bs).astype(np.float32)
        tiles = np.asarray(kv_scales_to_tiles(jnp.asarray(s)))
        assert tiles.shape == kv_scale_tiles_shape(NB, Hkv, bs) == (NB, 8, 128)
        flat = tiles.reshape(NB, -1)
        # flat index kv*Hkv*bs + h*bs + t holds scale [kv, h, t]
        for kv_i in range(2):
            for h in range(Hkv):
                idx = kv_i * Hkv * bs + h * bs + np.arange(bs)
                assert np.array_equal(flat[:, idx], s[:, kv_i, h, :])
        # the padded lanes are zero (DMA-read, multiplied only under masks)
        assert not flat[:, 2 * Hkv * bs:].any()
        # already-tiled input passes through untouched
        assert np.array_equal(
            np.asarray(kv_scales_to_tiles(jnp.asarray(tiles))), tiles)
