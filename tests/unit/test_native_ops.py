"""Native host tier: AIO handle + host optimizer kernels.

Parity model: reference ``tests/unit/ops/aio`` (read/write round-trips across
block sizes, single vs parallel submit) and ``tests/unit/ops/adam``
(``DeepSpeedCPUAdam`` vs ``torch.optim.Adam`` reference maths). Both the
native C++ path and the Python fallback are exercised.
"""

import numpy as np
import pytest

from deepspeed_tpu.ops.native import (AsyncIOHandle, HostAdam, HostAdagrad,
                                      HostLion, bf16_to_f32, f32_to_bf16,
                                      native_available, swap_in_tensors,
                                      swap_out_tensors)
from deepspeed_tpu.ops.native import aio as aio_mod


def _round_trip(handle, tmp_path, nbytes, offset=0):
    rng = np.random.default_rng(0)
    src = rng.integers(0, 256, nbytes, dtype=np.uint8)
    path = str(tmp_path / f"blob_{nbytes}_{offset}.bin")
    if offset:
        with open(path, "wb") as f:
            f.write(b"\0" * offset)
    assert handle.async_pwrite(src, path, offset) == 0
    assert handle.wait() == 1
    dst = np.zeros_like(src)
    assert handle.sync_pread(dst, path, offset) == 0
    np.testing.assert_array_equal(src, dst)


@pytest.mark.parametrize("changed", ["host", "source"])
def test_native_library_keyed_by_source_and_host(monkeypatch, tmp_path,
                                                 changed):
    """The build directory travels with a copied working tree and the build
    uses -march=native: a library from another host or other source must
    have another name, so it is never the one loaded here."""
    from deepspeed_tpu.ops.native import builder
    here = builder._lib_path()
    if changed == "host":
        monkeypatch.setattr(builder, "host_fingerprint",
                            lambda: "0123456789ab")
    else:
        other = tmp_path / "ds_native.cpp"
        with open(builder._SRC, "rb") as f:
            other.write_bytes(f.read() + b"\n// edited\n")
        monkeypatch.setattr(builder, "_SRC", str(other))
    assert builder._lib_path() != here


class TestAsyncIOHandle:

    @pytest.mark.parametrize("nbytes", [17, 4096, 1 << 20, (1 << 20) + 13])
    def test_round_trip_sizes(self, tmp_path, nbytes):
        h = AsyncIOHandle(block_size=64 * 1024, thread_count=4)
        try:
            _round_trip(h, tmp_path, nbytes)
        finally:
            h.close()

    def test_offset_io(self, tmp_path):
        h = AsyncIOHandle(block_size=1024, thread_count=2)
        try:
            _round_trip(h, tmp_path, 5000, offset=4096)
        finally:
            h.close()

    def test_many_inflight(self, tmp_path):
        h = AsyncIOHandle(block_size=4096, thread_count=4)
        try:
            arrs = [np.full(10000, i, np.uint8) for i in range(10)]
            paths = [str(tmp_path / f"t{i}.bin") for i in range(10)]
            swap_out_tensors(h, arrs, paths)
            assert h.wait() == 10
            outs = [np.zeros(10000, np.uint8) for _ in range(10)]
            swap_in_tensors(h, outs, paths)
            assert h.wait() == 10
            for i, o in enumerate(outs):
                assert (o == i).all()
        finally:
            h.close()

    def test_read_missing_file_errors(self, tmp_path):
        h = AsyncIOHandle(thread_count=1)
        try:
            buf = np.zeros(16, np.uint8)
            rc_submit = h.async_pread(buf, str(tmp_path / "nope.bin"))
            assert rc_submit != 0 or h.wait() < 0
            assert h.inflight() == 0  # failed submit must not pin the buffer
        finally:
            h.close()

    def test_queue_depth_throttle_round_trip(self, tmp_path):
        # depth 2 with many more chunks than depth: submit throttles but all IO lands
        h = AsyncIOHandle(block_size=1024, queue_depth=2, thread_count=2)
        try:
            _round_trip(h, tmp_path, 64 * 1024)
        finally:
            h.close()

    def test_o_direct_request(self, tmp_path):
        # page-aligned buffer + aligned block size: the O_DIRECT branch engages
        from deepspeed_tpu.ops.native import aligned_empty
        h = AsyncIOHandle(block_size=4096, thread_count=2, use_o_direct=True)
        try:
            src = aligned_empty(64 * 4096, np.uint8)
            assert src.ctypes.data % 4096 == 0 or not native_available()
            src[:] = np.random.default_rng(0).integers(0, 256, src.size, dtype=np.uint8)
            path = str(tmp_path / "odirect.bin")
            assert h.sync_pwrite(src, path) == 0
            dst = aligned_empty(64 * 4096, np.uint8)
            assert h.sync_pread(dst, path) == 0
            np.testing.assert_array_equal(src, dst)
        finally:
            h.close()

    def test_o_direct_unaligned_block_size_falls_back(self, tmp_path):
        # block_size 1000 breaks the O_DIRECT grid mid-request; the handle must
        # detect that and use buffered IO rather than erroring with EINVAL
        h = AsyncIOHandle(block_size=1000, thread_count=2, use_o_direct=True)
        try:
            _round_trip(h, tmp_path, 8192)
        finally:
            h.close()

    def test_typed_array_round_trip(self, tmp_path):
        h = AsyncIOHandle(thread_count=2)
        try:
            src = np.random.default_rng(1).standard_normal(1000).astype(np.float32)
            path = str(tmp_path / "f32.bin")
            assert h.sync_pwrite(src, path) == 0
            dst = np.zeros_like(src)
            assert h.sync_pread(dst, path) == 0
            np.testing.assert_array_equal(src, dst)
        finally:
            h.close()

    def test_accessors(self):
        h = AsyncIOHandle(block_size=2048, queue_depth=7, thread_count=3,
                          single_submit=True, overlap_events=False)
        try:
            assert h.get_block_size() == 2048
            assert h.get_queue_depth() == 7
            assert h.get_thread_count() == 3
            assert h.get_single_submit() is True
            assert h.get_overlap_events() is False
        finally:
            h.close()

    def test_python_fallback_round_trip(self, tmp_path, monkeypatch):
        monkeypatch.setattr(aio_mod, "load_native", lambda: None)
        h = aio_mod.AsyncIOHandle(thread_count=2)
        try:
            assert h._handle is None  # really on the fallback
            _round_trip(h, tmp_path, 3000)
        finally:
            h.close()


def _ref_adam(p, g, m, v, step, lr, b1, b2, eps, wd, adamw):
    p, g, m, v = (x.astype(np.float64) for x in (p, g, m, v))
    if not adamw and wd > 0:
        g = g + wd * p
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    bc1 = 1 - b1 ** step
    bc2 = 1 - b2 ** step
    upd = (m / bc1) / (np.sqrt(v / bc2) + eps)
    if adamw and wd > 0:
        upd = upd + wd * p
    return p - lr * upd, m, v


class TestHostOptimizers:

    @pytest.mark.parametrize("adamw", [True, False])
    def test_adam_matches_reference_math(self, adamw):
        rng = np.random.default_rng(2)
        n = 4097
        p = rng.standard_normal(n).astype(np.float32)
        m = np.zeros(n, np.float32)
        v = np.zeros(n, np.float32)
        pr, mr, vr = p.copy(), m.copy(), v.copy()
        opt = HostAdam(lr=1e-2, weight_decay=0.01, adamw_mode=adamw)
        for step in range(1, 4):
            g = rng.standard_normal(n).astype(np.float32)
            exp_p, exp_m, exp_v = _ref_adam(pr, g, mr, vr, step, 1e-2, 0.9,
                                            0.999, 1e-8, 0.01, adamw)
            opt.step(step, p, g, m, v)
            pr, mr, vr = exp_p, exp_m, exp_v
            np.testing.assert_allclose(p, exp_p.astype(np.float32), rtol=2e-5,
                                       atol=2e-6)
        np.testing.assert_allclose(m, mr.astype(np.float32), rtol=2e-5, atol=2e-6)

    def test_adam_matches_jitted_fused_adam(self):
        import jax.numpy as jnp
        from deepspeed_tpu.ops.adam import FusedAdam
        rng = np.random.default_rng(3)
        n = 1000
        p = rng.standard_normal(n).astype(np.float32)
        g = rng.standard_normal(n).astype(np.float32)
        fused = FusedAdam(lr=1e-3, weight_decay=0.1)
        st = fused.init({"w": jnp.asarray(p)})
        jp, jst = fused.update({"w": jnp.asarray(g)}, st, {"w": jnp.asarray(p)})

        hp, hm, hv = p.copy(), np.zeros(n, np.float32), np.zeros(n, np.float32)
        HostAdam(lr=1e-3, weight_decay=0.1).step(1, hp, g, hm, hv)
        np.testing.assert_allclose(hp, np.asarray(jp["w"]), rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(hm, np.asarray(jst["exp_avg"]["w"]), rtol=2e-5,
                                   atol=2e-6)

    def test_adagrad(self):
        rng = np.random.default_rng(4)
        n = 513
        p = rng.standard_normal(n).astype(np.float32)
        g = rng.standard_normal(n).astype(np.float32)
        h = np.zeros(n, np.float32)
        p0 = p.copy()
        HostAdagrad(lr=0.1).step(1, p, g, h)
        np.testing.assert_allclose(
            p, p0 - 0.1 * g / (np.abs(g) + 1e-10), rtol=1e-5, atol=1e-6)

    def test_lion(self):
        rng = np.random.default_rng(5)
        n = 257
        p = rng.standard_normal(n).astype(np.float32)
        g = rng.standard_normal(n).astype(np.float32)
        m = rng.standard_normal(n).astype(np.float32)
        p0, m0 = p.copy(), m.copy()
        HostLion(lr=1e-3, weight_decay=0.1).step(1, p, g, m)
        c = 0.9 * m0 + 0.1 * g
        np.testing.assert_allclose(p, p0 - 1e-3 * (np.sign(c) + 0.1 * p0),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(m, 0.99 * m0 + 0.01 * g, rtol=1e-5, atol=1e-6)

    def test_fallback_matches_native(self):
        if not native_available():
            pytest.skip("no native lib to compare against")
        rng = np.random.default_rng(6)
        n = 2048
        p = rng.standard_normal(n).astype(np.float32)
        g = rng.standard_normal(n).astype(np.float32)
        pn, mn, vn = p.copy(), np.zeros(n, np.float32), np.zeros(n, np.float32)
        pf, mf, vf = p.copy(), np.zeros(n, np.float32), np.zeros(n, np.float32)
        nat = HostAdam(lr=1e-2, weight_decay=0.05)
        assert nat._lib is not None
        fb = HostAdam(lr=1e-2, weight_decay=0.05)
        fb._lib = None
        nat.step(1, pn, g, mn, vn)
        fb.step(1, pf, g, mf, vf)
        # native kernels use FMA contraction (-O3); allow last-ulp drift
        np.testing.assert_allclose(pn, pf, rtol=5e-5, atol=1e-6)
        np.testing.assert_allclose(vn, vf, rtol=5e-5, atol=1e-6)


class TestBf16Convert:

    def test_round_trip(self):
        src = np.array([1.0, -2.5, 3.14159, 1e-8, 65504.0, 0.0], np.float32)
        bf = f32_to_bf16(src)
        back = bf16_to_f32(bf)
        np.testing.assert_allclose(back, src, rtol=1e-2, atol=1e-9)

    def test_matches_jax_bf16(self):
        import jax.numpy as jnp
        src = np.random.default_rng(7).standard_normal(4096).astype(np.float32)
        ours = bf16_to_f32(f32_to_bf16(src))
        jaxs = np.asarray(jnp.asarray(src).astype(jnp.bfloat16).astype(jnp.float32))
        np.testing.assert_array_equal(ours, jaxs)

    def test_nan_inf_preserved(self):
        src = np.array([np.nan, -np.nan, np.inf, -np.inf], np.float32)
        # include a worst-case NaN payload whose rounding would carry
        src = np.concatenate([src, np.frombuffer(
            np.array([0x7FFFFFFF, 0xFFFFFFFF], np.uint32).tobytes(), np.float32)])
        back = bf16_to_f32(f32_to_bf16(src))
        assert np.isnan(back[[0, 1, 4, 5]]).all()
        assert np.isposinf(back[2]) and np.isneginf(back[3])

    def test_nan_preserved_fallback(self, monkeypatch):
        from deepspeed_tpu.ops.native import cpu_optimizer as co
        monkeypatch.setattr(co, "load_native", lambda: None)
        src = np.frombuffer(
            np.array([0x7FFFFFFF, 0x3F800000], np.uint32).tobytes(), np.float32).copy()
        back = co.bf16_to_f32(co.f32_to_bf16(src))
        assert np.isnan(back[0]) and back[1] == 1.0

    def test_bad_dst_rejected(self):
        with pytest.raises(ValueError):
            f32_to_bf16(np.ones(100, np.float32), dst=np.empty(10, np.uint16))
        with pytest.raises(ValueError):
            bf16_to_f32(np.ones(4, np.uint16), dst=np.empty(4, np.float64))
