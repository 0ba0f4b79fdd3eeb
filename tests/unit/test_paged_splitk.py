"""Flash-decoding split-K: kernel/XLA-scan vs reference equality on CPU
interpret (docs/SERVING.md "Attention kernels").

The split-K module (``ops/pallas/paged_splitk.py``) cuts each sequence's
page range into S grid-parallel splits emitting ``(acc, lse)`` partials
under the chunk-serial kernel's ``lse = m + log(l)`` contract, merged by
one logsumexp-weighted pass. These tests pin, for every caller shape the
``AttentionKernelSpec`` dispatchers route (decode, chunk/verify, fused
step, sidebuf):

- split=S output == split=1 output == jnp reference across ctx edges
  (0, 1, block boundary, mid-page, full table), window starts, ALiBi and
  int8 pools — including splits that cover NO pages for short rows (the
  empty-split NEG_INF partial the merge must zero-weight);
- the fused-step contract: pool bytes (and int8 scale bytes) after a
  split-K step are byte-identical to the chunk-serial step kernel's;
- the ``_pick_pages_per_chunk`` VMEM budget math at the boundary — the
  split-K flash scratch and f32 partial blocks reserve off the top, int8
  scale tiles charge per page.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas import paged_attention as pa
from deepspeed_tpu.ops.pallas import paged_splitk as sk
from deepspeed_tpu.ops.pallas.paged_attention import (
    NEG_INF, _pick_pages_per_chunk)

S, H, HKV, D, BS, NB, MB = 4, 4, 2, 128, 64, 48, 6
# ctx edges: empty row, single token, one-token-past-block-boundary,
# mid-table, full block table
CTX_EDGES = [0, 1, 65, 200, MB * BS]


def _setup(seed=0, d=D):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(S, H, d).astype(np.float32))
    kv = jnp.asarray(rng.randn(NB, 2, HKV, BS, d).astype(np.float32))
    bt = jnp.asarray(rng.choice(NB, size=(S, MB), replace=False)
                     .astype(np.int32))
    return rng, q, kv, bt


def _ctx():
    return jnp.asarray(np.array(CTX_EDGES[:S], np.int32))


class TestMergeContract:

    def test_single_split_identity(self):
        rng = np.random.RandomState(3)
        out_p = rng.randn(S, 1, H, D).astype(np.float32)
        lse_p = rng.randn(S, 1, H).astype(np.float32)
        out, lse = sk.merge_splitk_partials(jnp.asarray(out_p),
                                            jnp.asarray(lse_p))
        np.testing.assert_allclose(np.asarray(out), out_p[:, 0], atol=1e-6)
        np.testing.assert_allclose(np.asarray(lse), lse_p[:, 0], atol=1e-6)

    def test_empty_partials_zero_weight(self):
        # a split that saw no pages contributes (garbage acc, NEG_INF lse)
        # — the merge must weight it exactly zero, and all-empty rows must
        # come out (0, NEG_INF), the chunk-serial kernel's empty-row form
        rng = np.random.RandomState(4)
        out_p = rng.randn(2, 3, H, D).astype(np.float32)
        lse_p = rng.randn(2, 3, H).astype(np.float32)
        out_p[0, 1] = 7.0                     # garbage in a dead split
        lse_p[0, 1] = NEG_INF
        lse_p[1] = NEG_INF                    # all splits empty
        out, lse = sk.merge_splitk_partials(jnp.asarray(out_p),
                                            jnp.asarray(lse_p))
        live = np.stack([out_p[0, 0], out_p[0, 2]], 0)
        wl = np.stack([lse_p[0, 0], lse_p[0, 2]], 0)
        m = wl.max(0)
        w = np.exp(wl - m)
        expect = (w[..., None] * live).sum(0) / w.sum(0)[..., None]
        np.testing.assert_allclose(np.asarray(out)[0], expect, atol=1e-5)
        assert np.all(np.asarray(out)[1] == 0)
        assert np.all(np.asarray(lse)[1] <= NEG_INF * 0.5)


class TestDecodeSplitK:

    @pytest.mark.parametrize("ns", [1, 4, 16])
    def test_xla_matches_reference_ctx_edges(self, ns):
        _, q, kv, bt = _setup(0)
        cl = _ctx()
        ref = pa.paged_decode_attention_reference(q, kv, bt, cl)
        out, _ = sk.paged_decode_attention_xla(q, kv, bt, cl, with_lse=True,
                                               n_splits=ns)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=3e-5, rtol=3e-5)

    @pytest.mark.parametrize("ns", [2, 4, 8])
    def test_pallas_interpret_matches_reference(self, ns):
        _, q, kv, bt = _setup(1)
        cl = _ctx()
        ref, lse_ref = pa.paged_decode_attention_reference(
            q, kv, bt, cl, with_lse=True)
        out, lse = sk.paged_decode_attention_splitk_pallas(
            q, kv, bt, cl, ns, with_lse=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=3e-5, rtol=3e-5)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_ref),
                                   atol=3e-5, rtol=3e-5)
        # empty row keeps the chunk-serial kernel's (0, NEG_INF) form
        assert np.all(np.asarray(out)[0] == 0)
        assert np.all(np.asarray(lse)[0] <= NEG_INF * 0.5)

    @pytest.mark.parametrize("path", ["xla", "pallas"])
    def test_window_starts(self, path):
        _, q, kv, bt = _setup(2)
        # window starts at 0 (ctx <= w), mid-block, and block boundary
        for window in (11, BS, 3 * BS):
            cl = _ctx()
            ref = pa.paged_decode_attention_reference(q, kv, bt, cl,
                                                      window=window)
            if path == "xla":
                out, _ = sk.paged_decode_attention_xla(
                    q, kv, bt, cl, window=window, with_lse=True, n_splits=4)
            else:
                out, _ = sk.paged_decode_attention_splitk_pallas(
                    q, kv, bt, cl, 4, window=window, with_lse=True)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       atol=3e-5, rtol=3e-5)

    @pytest.mark.parametrize("path", ["xla", "pallas"])
    def test_alibi(self, path):
        _, q, kv, bt = _setup(3)
        cl = _ctx()
        ref = pa.paged_decode_attention_reference(q, kv, bt, cl, alibi=True)
        if path == "xla":
            out, _ = sk.paged_decode_attention_xla(q, kv, bt, cl, alibi=True,
                                                   with_lse=True, n_splits=4)
        else:
            out, _ = sk.paged_decode_attention_splitk_pallas(
                q, kv, bt, cl, 4, alibi=True, with_lse=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=3e-5, rtol=3e-5)

    @pytest.mark.parametrize("path", ["xla", "pallas"])
    def test_int8_pool(self, path):
        _, q, kv, bt = _setup(4)
        cl = _ctx()
        kvq, scl = pa.kv_quantize_rows(kv)
        tiles = pa.kv_scales_to_tiles(scl)
        kvd = pa.kv_dequantize_rows(kvq, scl)
        ref = pa.paged_decode_attention_reference(q, kvd, bt, cl)
        if path == "xla":
            out, _ = sk.paged_decode_attention_xla(
                q, kvq, bt, cl, kv_scales=tiles, with_lse=True, n_splits=4)
        else:
            out, _ = sk.paged_decode_attention_splitk_pallas(
                q, kvq, bt, cl, 4, kv_scales=tiles, with_lse=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-4, rtol=2e-4)

    def test_dispatcher_split1_is_base_kernel(self):
        _, q, kv, bt = _setup(5)
        cl = _ctx()
        base = pa.paged_decode_attention(q, kv, bt, cl)
        out = sk.paged_decode_attention_splitk(q, kv, bt, cl, n_splits=1)
        # byte-identical: the dispatcher routes to the SAME program
        assert np.array_equal(np.asarray(base), np.asarray(out))

    def test_small_head_dim_routes_xla(self):
        # D=16 (the CPU bench model): split-K must compose via the XLA scan
        _, q, kv, bt = _setup(6, d=16)
        cl = _ctx()
        ref = pa.paged_decode_attention_reference(q, kv, bt, cl)
        for ns in (2, 8):
            out = sk.paged_decode_attention_splitk(q, kv, bt, cl,
                                                   n_splits=ns)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       atol=3e-5, rtol=3e-5)


class TestChunkSplitK:

    @pytest.mark.parametrize("ns", [1, 4])
    def test_matches_batched_kernel(self, ns):
        rng, _, kv, bt = _setup(7)
        Cs = 8
        q = jnp.asarray(rng.randn(S, Cs, H, D).astype(np.float32))
        qs = jnp.asarray(np.array([0, 1, 60, 190], np.int32))
        cl = jnp.asarray(np.array([5, 9, 68, 198], np.int32))
        ref = pa.paged_chunk_attention_batched(q, kv, bt, qs, cl)
        out = sk.paged_chunk_attention_splitk(q, kv, bt, qs, cl, n_splits=ns)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=3e-5, rtol=3e-5)

    def test_window_alibi_compose(self):
        rng, _, kv, bt = _setup(8)
        Cs = 8
        q = jnp.asarray(rng.randn(S, Cs, H, D).astype(np.float32))
        qs = jnp.asarray(np.array([0, 1, 60, 190], np.int32))
        cl = jnp.asarray(np.array([5, 9, 68, 198], np.int32))
        ref = pa.paged_chunk_attention_batched(q, kv, bt, qs, cl,
                                               window=9, alibi=True)
        out = sk.paged_chunk_attention_splitk(q, kv, bt, qs, cl, window=9,
                                              alibi=True, n_splits=4)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=3e-5, rtol=3e-5)


class TestStepSplitK:

    def test_pool_bytes_match_fused_kernel(self):
        rng, q, kv, bt = _setup(9)
        cl = jnp.asarray(np.array([1, 65, 200, 0], np.int32))
        kn = jnp.asarray(rng.randn(S, HKV, D).astype(np.float32))
        vn = jnp.asarray(rng.randn(S, HKV, D).astype(np.float32))
        o1, kv1 = pa.paged_decode_attention_step(q, kn, vn, kv, bt, cl)
        o2, kv2 = sk.paged_decode_attention_splitk_step(q, kn, vn, kv, bt,
                                                        cl, n_splits=2)
        assert np.array_equal(np.asarray(kv1), np.asarray(kv2))
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                                   atol=3e-5, rtol=3e-5)

    def test_int8_write_dequant_semantics(self):
        # engine contract: int8 callers pass kv_write_dequant'd rows, so
        # register-attend (fused kernel) and pool-attend (scatter-first
        # split-K) see the SAME values; re-quantization is byte-idempotent
        rng, q, kv, bt = _setup(10)
        cl = jnp.asarray(np.array([1, 65, 200, 0], np.int32))
        kvq, scl = pa.kv_quantize_rows(kv)
        tiles = pa.kv_scales_to_tiles(scl)
        kn = pa.kv_write_dequant(
            jnp.asarray(rng.randn(S, HKV, D).astype(np.float32)))
        vn = pa.kv_write_dequant(
            jnp.asarray(rng.randn(S, HKV, D).astype(np.float32)))
        o1, kv1, sc1 = pa.paged_decode_attention_step(q, kn, vn, kvq, bt, cl,
                                                      kv_scales=tiles)
        o2, kv2, sc2 = sk.paged_decode_attention_splitk_step(
            q, kn, vn, kvq, bt, cl, kv_scales=tiles, n_splits=2)
        assert np.array_equal(np.asarray(kv1), np.asarray(kv2))
        assert np.array_equal(np.asarray(sc1), np.asarray(sc2))
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                                   atol=3e-4, rtol=3e-4)


class TestSidebufSplitK:

    def _slabs(self, rng, Cs=8):
        skb = jnp.asarray(rng.randn(S, Cs, HKV, D).astype(np.float32))
        svb = jnp.asarray(rng.randn(S, Cs, HKV, D).astype(np.float32))
        return skb, svb

    @pytest.mark.parametrize("j", [0, 7])
    @pytest.mark.parametrize("ns", [1, 4])
    def test_matches_reference(self, j, ns):
        rng, q, kv, bt = _setup(11)
        pfx = jnp.asarray(np.array([0, 1, 130, 300], np.int32))
        skb, svb = self._slabs(rng)
        ref = pa.paged_decode_attention_sidebuf_reference(
            q, kv, bt, pfx, skb, svb, j)
        out = sk.paged_sidebuf_attention_splitk(q, kv, bt, pfx, skb, svb, j,
                                                n_splits=ns)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=3e-5, rtol=3e-5)

    def test_window_alibi_match_fused_kernel(self):
        # window+alibi ground truth is the FUSED KERNEL: the jnp sidebuf
        # reference's window branch drops alibi on the prefix piece
        # (_paged_reference_lse_lo has no alibi term)
        rng, q, kv, bt = _setup(12)
        pfx = jnp.asarray(np.array([0, 1, 130, 300], np.int32))
        skb, svb = self._slabs(rng)
        for j in (5,):
            kout = pa.paged_decode_attention_sidebuf(
                q, kv, bt, pfx, skb, svb, j, window=17, alibi=True)
            out = sk.paged_sidebuf_attention_splitk(
                q, kv, bt, pfx, skb, svb, j, window=17, alibi=True,
                n_splits=4)
            np.testing.assert_allclose(np.asarray(out), np.asarray(kout),
                                       atol=3e-5, rtol=3e-5)

    def test_int8_pool(self):
        rng, q, kv, bt = _setup(13)
        pfx = jnp.asarray(np.array([0, 1, 130, 300], np.int32))
        skb, svb = self._slabs(rng)
        kvq, scl = pa.kv_quantize_rows(kv)
        tiles = pa.kv_scales_to_tiles(scl)
        kvd = pa.kv_dequantize_rows(kvq, scl)
        ref = pa.paged_decode_attention_sidebuf_reference(
            q, kvd, bt, pfx, skb, svb, 3)
        out = sk.paged_sidebuf_attention_splitk(
            q, kvq, bt, pfx, skb, svb, 3, kv_scales=tiles, n_splits=2)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-4, rtol=2e-4)

    def test_layered_and_flat_slabs(self):
        rng, q, kv, bt = _setup(14)
        pfx = jnp.asarray(np.array([0, 1, 130, 300], np.int32))
        Cs, L = 8, 2
        skL = jnp.asarray(rng.randn(L, S, Cs, HKV, D).astype(np.float32))
        svL = jnp.asarray(rng.randn(L, S, Cs, HKV, D).astype(np.float32))
        for li in range(L):
            ref = pa.paged_decode_attention_sidebuf_reference(
                q, kv, bt, pfx, skL[li], svL[li], 2)
            out = sk.paged_sidebuf_attention_splitk(
                q, kv, bt, pfx, skL, svL, 2, layer_idx=jnp.int32(li),
                n_splits=2)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       atol=3e-5, rtol=3e-5)
            flat = sk.paged_sidebuf_attention_splitk(
                q, kv, bt, pfx, skL.reshape(L, S, Cs * HKV, D),
                svL.reshape(L, S, Cs * HKV, D), 2,
                layer_idx=jnp.int32(li), n_splits=2)
            np.testing.assert_allclose(np.asarray(flat), np.asarray(ref),
                                       atol=3e-5, rtol=3e-5)


class TestVmemBudget:
    """Pin the _pick_pages_per_chunk budget math at the boundary."""

    def test_flash_scratch_reserves_off_the_top(self, monkeypatch):
        bs, hkv, d, esize = 64, 2, 128, 4
        per_page = 2 * 2 * bs * hkv * d * esize
        flash = (8 * d + 2 * 8 * 128) * 4       # H=8 f32 (m, l, acc)
        # budget sized for EXACTLY 3 pages once the flash scratch is off
        # the top: one byte less must drop to 2
        monkeypatch.setenv("DSTPU_PAGED_VMEM_BUDGET",
                           str(3 * per_page + flash))
        assert _pick_pages_per_chunk(bs, hkv, d, esize, 64,
                                     flash_heads=8) == 3
        monkeypatch.setenv("DSTPU_PAGED_VMEM_BUDGET",
                           str(3 * per_page + flash - 1))
        assert _pick_pages_per_chunk(bs, hkv, d, esize, 64,
                                     flash_heads=8) == 2

    def test_splitk_partial_blocks_reserve_off_the_top(self, monkeypatch):
        bs, hkv, d, esize, Hq = 64, 2, 128, 4, 8
        per_page = 2 * 2 * bs * hkv * d * esize
        flash = (Hq * d + 2 * Hq * 128) * 4
        outb = 2 * (Hq * d + Hq * 128) * 4      # double-buffered (out, lse)
        monkeypatch.setenv("DSTPU_PAGED_VMEM_BUDGET",
                           str(2 * per_page + flash + outb))
        assert _pick_pages_per_chunk(bs, hkv, d, esize, 64, flash_heads=Hq,
                                     out_bytes=outb) == 2
        monkeypatch.setenv("DSTPU_PAGED_VMEM_BUDGET",
                           str(2 * per_page + flash + outb - 1))
        assert _pick_pages_per_chunk(bs, hkv, d, esize, 64, flash_heads=Hq,
                                     out_bytes=outb) == 1

    def test_scale_tiles_charge_per_page(self, monkeypatch):
        bs, hkv, d = 64, 2, 128
        r8 = pa._scale_tile_rows(hkv, bs)
        per_page = 2 * 2 * bs * hkv * d * 1      # int8 pool: esize 1
        per_page_q = per_page + 2 * r8 * 128 * 4
        # budget one byte shy of 5 quant-charged pages: with the per-page
        # scale-tile charge only 4 fit; dropping the charge would let the
        # 5th page in — the accounting is what keeps fat int8 chunks honest
        monkeypatch.setenv("DSTPU_PAGED_VMEM_BUDGET",
                           str(5 * per_page_q - 1))
        assert _pick_pages_per_chunk(bs, hkv, d, 1, 64,
                                     scale_tile_rows=r8) == 4
        assert _pick_pages_per_chunk(bs, hkv, d, 1, 64) == 5

    def test_one_kv_head_is_capped_by_pages_not_bytes(self):
        # 128 KiB a page: the bytes alone would allow 63 pages a chunk
        assert _pick_pages_per_chunk(128, 1, 128, 2, 96, flash_heads=20) \
            == pa.MAX_PAGES_PER_CHUNK == 32
        # ... and the widths the other cells serve stay where bytes put them
        assert _pick_pages_per_chunk(128, 8, 128, 2, 40, flash_heads=32) == 7
        assert _pick_pages_per_chunk(128, 4, 128, 2, 208, flash_heads=32) == 15

    def test_floor_is_one_page(self, monkeypatch):
        monkeypatch.setenv("DSTPU_PAGED_VMEM_BUDGET", "1")
        assert _pick_pages_per_chunk(64, 2, 128, 4, 64, flash_heads=8,
                                     out_bytes=1 << 20) == 1
