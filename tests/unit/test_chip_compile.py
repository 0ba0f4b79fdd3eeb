"""Main-path Pallas kernels, compiled for a described TPU v5e at real widths.

The suite runs every kernel through the Pallas interpreter on the CPU, which
accepts programs the chip's compiler refuses (a slice not aligned to the
tiling, too much VMEM) or aborts on. The TPU compiler is installed with jax
and compiles for a chip that is described, not attached
(``jax.experimental.topologies``), so these cases ask it directly: each one
lowers a kernel at Mistral-7B width (32 q / 8 kv heads, head_dim 128,
128-token pages) for ``v5e:2x2`` and asserts the Mosaic kernel is in the
compiled program. Nothing runs; results are checked on the chip by
``chip_smoke.py``'s kernels phase.

The small-head-dim cases pin a repair: ``_paged_decode_smalld`` used to make
the compiler ABORT THE PROCESS (``Check failed: limits[i] <= dim(i)``, no
Python exception) at these four shapes — every 64/80/96-wide-head family
would have killed a server at its first decode compile.
"""

import math
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from deepspeed_tpu.ops.pallas import _backend
from deepspeed_tpu.ops.pallas.flash_attention import (flash_attention,
                                                      flash_attention_packed)
from deepspeed_tpu.ops.pallas.mla_attention import (mla_paged_attention,
                                                    mla_row_write)
from deepspeed_tpu.ops.pallas.paged_attention import (
    kv_scale_tiles_shape, paged_chunk_attention_batched,
    paged_decode_attention, paged_decode_attention_sidebuf,
    paged_decode_attention_step)
from deepspeed_tpu.ops.pallas import sparse_mla
from deepspeed_tpu.ops.pallas.gdn import gdn_chunk_scan, gdn_decode_step
from deepspeed_tpu.ops.pallas.ssm import ssd_chunk_scan, ssd_decode_step
from deepspeed_tpu.ops.pallas.paged_splitk import (
    paged_decode_attention_splitk_pallas, paged_sidebuf_attention_splitk)

H, HKV, D, BS = 32, 8, 128, 128      # LlamaConfig.mistral_7b heads, page size
WINDOW = 4096
S, MB, NB = 8, 40, 64                # sequences, pages per sequence, pool
BF16, I8, I32, F32 = jnp.bfloat16, jnp.int8, jnp.int32, jnp.float32


@pytest.fixture(scope="module")
def v5e():
    """The four described chips of a v5e 2x2. The persistent compilation
    cache is off around these compiles: an executable for a described device
    is written to the cache but cannot be read back without a chip, so the
    next run would warn and compile again anyway."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    prior = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", prior)
    cc.reset_cache()


def _pool(h_kv=HKV, d=D, dtype=BF16):
    return ((NB, 2, h_kv, BS, d), dtype)


_Q = ((S, H, D), BF16)
_BT = ((S, MB), I32)
_CL = ((S,), I32)
_NEW = ((S, HKV, D), BF16)
_SCALES = (kv_scale_tiles_shape(NB, HKV, BS), F32)   # at-rest tile layout
_SIDE = ((S, 16, HKV, D), BF16)                      # 16-step side slab
_CHUNK_Q = ((4, 128, H, D), BF16)                    # 4 slots x 128 tokens
_TRAIN = ((1, 4096, H, D), BF16)                     # kv heads repeated to H
_PACKED = 1024


def _flash_sq(q, k, v):
    return jnp.sum(flash_attention(q, k, v, causal=True).astype(F32) ** 2)


def _decode_smalld(h, h_kv, d):
    return (paged_decode_attention,
            [((S, h, d), BF16), _pool(h_kv, d), _BT, _CL])


# latent attention at JoyAI-LLM-Flash's widths: rows of 512 + 64 values in 640
# (whole lane tiles), 32 query heads, 80 pages a sequence, 40 layers of pages
_LAT = dict(heads=32, v_dim=512, softmax_scale=192 ** -0.5)
_LAT_POOL = ((40 * 64, BS, 640), BF16)
_LAT_Q = ((32, 32, 640), BF16)
_LAT_BT, _LAT_CL = ((32, 80), I32), ((32,), I32)


_DSA = dict(v_dim=512, softmax_scale=256 ** -0.5)
_DSA_IPOOL = ((5 * 6437, BS, 128), BF16)


CASES = {
    # one query token a sequence, its 32 heads the rows of both products
    "mla_decode": (lambda *a: mla_paged_attention(*a, **_LAT),
                   [_LAT_Q, _LAT_POOL, _LAT_BT, _LAT_CL, _LAT_CL]),
    # the fused decode schedule: frozen pages + a side slab of 8 rows, the
    # layer's slab picked by a traced index
    "mla_decode_side": (
        lambda q, p, bt, q0, cl, side, j, l: mla_paged_attention(
            q, p, bt, q0, cl, side=side, side_j=j, layer_idx=l, **_LAT),
        [_LAT_Q, _LAT_POOL, _LAT_BT, _LAT_CL, _LAT_CL,
         ((40, 32, 8, 640), BF16), ((), I32), ((), I32)]),
    # 4 chunk slots of 256 tokens: 8,192 query rows a slot in blocks of 512
    "mla_chunk": (lambda *a: mla_paged_attention(*a, **_LAT),
                  [((4, 256 * 32, 640), BF16), _LAT_POOL, ((4, 80), I32),
                   ((4,), I32), ((4,), I32)]),
    "mla_row_write": (
        lambda p, side, bt, pre: mla_row_write(p, side, bt, pre, 1),
        [((40, 64, BS, 640), BF16), ((40, 32, 8, 640), BF16), _LAT_BT,
         _LAT_CL]),
    # the expanded form's packed flash: q/k of 192 (128 + the 64 rotary
    # values), v of 128
    "packed_prefill_qk192_v128": (
        lambda q, k, v, seg: flash_attention_packed(q, k, v, seg),
        [((_PACKED, H, 192), BF16), ((_PACKED, H, 192), BF16),
         ((_PACKED, H, D), BF16), ((_PACKED,), I32)]),
    "flash_fwd": (lambda q, k, v: flash_attention(q, k, v, causal=True),
                  [_TRAIN] * 3),
    "flash_bwd": (jax.grad(_flash_sq, argnums=(0, 1, 2)), [_TRAIN] * 3),
    # the one fused backward call is compiled under its own count of VMEM
    # (``_fused_bwd_vmem_bytes``): head size 64 (half a lane tile a row),
    # float32 operands, and cross attention with no mask
    "flash_bwd_d64": (jax.grad(_flash_sq, argnums=(0, 1, 2)),
                      [((1, 4096, H, 64), BF16)] * 3),
    "flash_bwd_f32": (jax.grad(_flash_sq, argnums=(0, 1, 2)),
                      [((1, 2048, 4, D), F32)] * 3),
    "flash_bwd_cross": (
        jax.grad(lambda q, k, v: jnp.sum(
            flash_attention(q, k, v).astype(F32) ** 2), argnums=(0, 1, 2)),
        [((1, 2048, 8, D), BF16)] + [((1, 4096, 8, D), BF16)] * 2),
    "packed_prefill": (
        lambda q, k, v, seg: flash_attention_packed(q, k, v, seg,
                                                    window=WINDOW),
        [((_PACKED, H, D), BF16), ((_PACKED, HKV, D), BF16),
         ((_PACKED, HKV, D), BF16), ((_PACKED,), I32)]),
    "decode": (paged_decode_attention, [_Q, _pool(), _BT, _CL]),
    "decode_window": (
        lambda *a: paged_decode_attention(*a, window=WINDOW),
        [_Q, _pool(), _BT, _CL]),
    "fused_step": (
        lambda *a: paged_decode_attention_step(*a, window=WINDOW),
        [_Q, _NEW, _NEW, _pool(), _BT, _CL]),
    "chunk_batched": (
        lambda *a: paged_chunk_attention_batched(*a, window=WINDOW),
        [_CHUNK_Q, _pool(), ((4, MB), I32), ((4,), I32), ((4,), I32)]),
    "sidebuf": (
        lambda q, kv, bt, pl_, sk, sv, j: paged_decode_attention_sidebuf(
            q, kv, bt, pl_, sk, sv, j, window=WINDOW),
        [_Q, _pool(), _BT, _CL, _SIDE, _SIDE, ((), I32)]),
    # the same kernel as cell 12's decode step hands it (ZAYA1-8B: 8 query
    # heads over 2 KV heads of 128, 64 rows, 96-page tables) and as cell 11's
    # does (Qwen3-Next: 16 over 2 heads of 256, 272-page tables): the slab the
    # whole stack of layers, the layer's index traced
    "sidebuf_h8_kv2_d128_rows64": (
        lambda q, kv, bt, pl_, sk, sv, j, l: paged_decode_attention_sidebuf(
            q, kv, bt, pl_, sk, sv, j, layer_idx=l),
        [((64, 8, D), BF16), _pool(2, D), ((64, 96), I32), ((64,), I32),
         ((20, 64, 8, D), BF16), ((20, 64, 8, D), BF16), ((), I32),
         ((), I32)]),
    "sidebuf_h16_kv2_d256_pages272": (
        lambda q, kv, bt, pl_, sk, sv, j, l: paged_decode_attention_sidebuf(
            q, kv, bt, pl_, sk, sv, j, layer_idx=l),
        [((64, 16, 256), BF16), _pool(2, 256), ((64, 272), I32),
         ((64,), I32), ((3, 64, 8, 256), BF16), ((3, 64, 8, 256), BF16),
         ((), I32), ((), I32)]),
    "splitk4": (
        lambda *a: paged_decode_attention_splitk_pallas(*a, 4,
                                                        window=WINDOW),
        [_Q, _pool(), _BT, _CL]),
    # no window here: with one, the side-buffer split takes the XLA scan
    # (its window start is traced per sequence) and there is no kernel
    "sidebuf_splitk2": (
        lambda q, kv, bt, pl_, sk, sv, j: paged_sidebuf_attention_splitk(
            q, kv, bt, pl_, sk, sv, j, n_splits=2),
        [_Q, _pool(), _BT, _CL, _SIDE, _SIDE, ((), I32)]),
    "decode_int8": (
        lambda q, kv, bt, cl, sc: paged_decode_attention(
            q, kv, bt, cl, kv_scales=sc),
        [_Q, _pool(dtype=I8), _BT, _CL, _SCALES]),
    "step_int8": (
        lambda q, kn, vn, kv, bt, cl, sc: paged_decode_attention_step(
            q, kn, vn, kv, bt, cl, kv_scales=sc),
        [_Q, _NEW, _NEW, _pool(dtype=I8), _BT, _CL, _SCALES]),
    # head_dim % 128 != 0: the BlockSpec-pipelined decode kernel
    "smalld_h16_kv16_d64": _decode_smalld(16, 16, 64),
    "smalld_h32_kv32_d80": _decode_smalld(32, 32, 80),
    "smalld_h64_kv64_d96": _decode_smalld(64, 64, 96),
    "smalld_h32_kv8_d64": _decode_smalld(32, 8, 64),
    "smalld_step_h32_kv8_d64": (
        paged_decode_attention_step,
        [((S, 32, 64), BF16), ((S, 8, 64), BF16), ((S, 8, 64), BF16),
         _pool(8, 64), _BT, _CL]),
    # the Mamba-2 (SSD) state kernels at granite-4.0-h-small's widths: 128
    # heads of 64 over a state of 128, a pass of 4 chunk slots of 256 and a
    # 64-row step over the pools of 9 layers x 73 slots
    "ssd_chunk_scan_h128_p64_n128": (
        ssd_chunk_scan,
        [((1024, 128), F32), ((1024, 8192), F32), ((1024, 128), F32),
         ((1024, 128), F32), ((128,), F32), ((4, 128, 8192), F32),
         ((4,), I32)]),
    "ssd_decode_step_h128_p64_n128": (
        ssd_decode_step,
        [((9, 73, 128, 8192), F32), ((9, 73, 24, 1152), F32), ((), I32),
         ((64,), I32), ((64, 128), F32), ((64, 8192), F32), ((64, 128), F32),
         ((64, 128), F32), ((128,), F32), ((64, 8448), BF16)]),
    # the gated delta-rule kernels at Qwen3-Next's widths: 16 key heads of
    # 128 serving 32 value heads of 128, a pass of 8 chunk slots of 256 in
    # chunks of 64 and a 64-row step over the pools of 9 layers x 73 slots
    "gdn_chunk_scan_hk16_hv32_n128": (
        gdn_chunk_scan,
        [((2048, 2048), BF16), ((2048, 2048), BF16), ((2048, 4096), BF16),
         ((2048, 32), F32), ((2048, 32), F32), ((8, 128, 4096), F32),
         ((8,), I32)]),
    "gdn_decode_step_hk16_hv32_n128": (
        gdn_decode_step,
        [((9, 73, 128, 4096), F32), ((9, 73, 24, 1024), F32), ((), I32),
         ((64,), I32), ((64, 32), F32), ((64, 32), F32), ((64, 2048), BF16),
         ((64, 2048), BF16), ((64, 4096), BF16), ((64, 8192), BF16)]),
    # 256-wide heads, 16 query heads over 2 KV heads (Qwen3-Next's attention):
    # the paged decode kernel over 264 pages a sequence
    "decode_h16_kv2_d256": (
        paged_decode_attention,
        [((S, 16, 256), BF16), _pool(2, 256), ((S, 264), I32), _CL]),
    # the same heads' prompt chunks over cached pages: 8 slots of 256 rows,
    # 272-page tables, several pages a grid step through manual copies
    "chunk_h16_kv2_d256": (
        paged_chunk_attention_batched,
        [((8, 256, 16, 256), BF16), _pool(2, 256), ((8, 272), I32),
         ((8,), I32), ((8,), I32)]),
    # the selection inside latent attention at GLM-5's widths (32 index heads
    # of 128, 64 query heads over rows of 640, the 2,048 best kept), over the
    # benchmark's pools (5 layers x 6,437 pages) and 272-page tables: a
    # decode row's index scores over its pages (a loop of 2,048-key tiles) ..
    "dsa_index_decode": (sparse_mla.index_scores, [
        ((16, 1, 32, 128), BF16), ((16, 1, 32), F32), _DSA_IPOOL,
        ((16, 272), I32), ((16,), I32), ((16,), I32)]),
    # .. a chunk slot's 256 queries (a grid of 512-key tiles, a product a head)
    "dsa_index_chunk": (sparse_mla.index_scores, [
        ((8, 256, 32, 128), BF16), ((8, 256, 32), F32), _DSA_IPOOL,
        ((8, 272), I32), ((8,), I32), ((8,), I32)]),
    # .. the exact k-th largest of 34,816 scores a row at the cell's two
    # shapes, a pass's slots and a step's rows as ONE slot's (the row block,
    # the unroll and the VMEM limit are ``_select_block``'s from each)
    "dsa_select": (sparse_mla.select_counted, [
        ((8, 68, 256, 512), F32), ((8, 256), I32), ((8,), I32)]),
    "dsa_select_decode": (sparse_mla.select_counted, [
        ((1, 17, 16, 2048), F32), ((1, 16), I32), ((1,), I32)]),
    # .. a decode row over its 2,048 gathered rows and its own from the side
    "dsa_attend_decode": (
        lambda q, r, n, s, o: sparse_mla.attend_decode(
            q, r, n, side=s, side_on=o, **_DSA),
        [((16, 64, 640), BF16), ((16, 2048, 640), BF16), ((16,), I32),
         ((16, 8, 640), BF16), ((16,), I32)]),
    # .. a chunk slot's 16,384 query rows over its pages under the mask
    "dsa_attend_chunk": (
        lambda *a: sparse_mla.attend_chunk(*a, heads=64, **_DSA),
        [((8, 256 * 64, 640), BF16), ((5 * 6437, BS, 640), BF16),
         ((8, 272), I32), ((8,), I32), ((8,), I32),
         ((8, 68, 256, 512), F32), ((8, 256), F32), ((8, 256), I32)]),
    # .. and a pass that is ONE sequence's: 4 heads a grid step over all 8
    # slots, a 512-key tile of latent rows expanded once a head in VMEM
    "dsa_attend_expanded": (
        lambda *a: sparse_mla.attend_expanded(*a, k_dim=256,
                                              softmax_scale=1 / 16),
        [((8, 64, 256, 256), BF16), ((64, 640, 512), BF16),
         ((5 * 6437, BS, 640), BF16), ((272,), I32), ((8,), I32),
         ((8, 68, 256, 512), F32), ((8, 256), F32), ((8, 256), I32)]),
    # int8 pages under a chunk: a scale tile a page beside its copy
    "chunk_int8": (
        lambda q, kv, bt, q0, cl, sc: paged_chunk_attention_batched(
            q, kv, bt, q0, cl, kv_scales=sc),
        [_CHUNK_Q, _pool(dtype=I8), ((4, MB), I32), ((4,), I32), ((4,), I32),
         _SCALES]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, v5e, monkeypatch):
    # the CPU backend means "interpret" to every kernel module; this test
    # compiles for a chip that is described, so it steers them here
    monkeypatch.setattr(_backend, "interpret", lambda: False)
    fn, shapes = CASES[case]
    chip = SingleDeviceSharding(v5e[0])
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
            for shape, dtype in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), \
        f"{case}: no Mosaic kernel in the compiled program"


@pytest.mark.parametrize("seq,calls", [(65536, 2), (131072, 3)])
def test_flash_backward_is_one_call_up_to_its_vmem_budget(seq, calls, v5e,
                                                          monkeypatch):
    """dq's float32 sum of a whole (batch, head) lives in VMEM: at 65,536
    rows of 128 the count is 90 MiB, inside ``FUSED_BWD_VMEM_BYTES``, and the
    gradient is the forward call and ONE backward call; twice that is over
    the budget and takes the two older calls."""
    monkeypatch.setattr(_backend, "interpret", lambda: False)
    x = jax.ShapeDtypeStruct((1, seq, 1, D), BF16,
                             sharding=SingleDeviceSharding(v5e[0]))
    text = jax.jit(jax.grad(_flash_sq, argnums=(0, 1, 2))).lower(
        x, x, x).compile().as_text()
    assert text.count("tpu_custom_call") == calls


def test_flash_dispatch_compiles_over_a_four_chip_mesh(v5e, monkeypatch):
    """The train step's attention under ``mesh: {fsdp: 4}``. The SPMD
    partitioner refuses a bare Mosaic kernel in a program over four devices
    ("cannot be automatically partitioned"); the dispatcher must hand it
    over inside a shard_map."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from deepspeed_tpu.comm.mesh import (BATCH_AXES, build_topology,
                                         set_topology)
    from deepspeed_tpu.config import MeshConfig
    from deepspeed_tpu.ops import attention
    monkeypatch.setattr(_backend, "interpret", lambda: False)
    monkeypatch.setattr(attention, "_use_pallas", lambda: True)
    topo = set_topology(build_topology(MeshConfig(data=1, fsdp=4),
                                       devices=list(v5e)))
    rows = NamedSharding(topo.mesh, P(BATCH_AXES))
    args = [jax.ShapeDtypeStruct((4, 4096, H, D), BF16, sharding=rows)] * 3
    compiled = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(attention.dot_product_attention(
            q, k, v, causal=True).astype(F32) ** 2), argnums=(0, 1, 2))
    ).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_v2_engine_refuses_to_span_chips_at_tp1(v5e):
    """At tensor_parallel=1 the engine's mesh takes every visible device,
    but its kernels run outside shard_map there and a Mosaic kernel cannot
    be partitioned: on a four-chip host the first compile would fail with
    the partitioner's message. Refused by name at build instead."""
    from deepspeed_tpu.comm.mesh import build_topology
    from deepspeed_tpu.config import MeshConfig
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    model = LlamaForCausalLM(LlamaConfig.tiny())
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    four = build_topology(MeshConfig(data=4), devices=list(v5e))
    with pytest.raises(NotImplementedError, match="one replica per chip"):
        InferenceEngineV2(model=model, model_parameters=params,
                          mesh_topology=four)


@pytest.mark.parametrize("T", [1, 32])
def test_moe_layer_scan_reads_expert_stacks_in_place(T, v5e):
    """The MoE layer scan at Mixtral-8x7B width (8 experts of 4096 x 14336,
    3 layers, T tokens top-2). XLA's grouped GEMM is a custom call and
    takes no fused operand: handed the scan's per-layer slice it made the
    compiler copy each layer's three 896 MiB expert stacks to a temporary
    first (57% of the decode step's device time on the chip). The stacks
    now reach it whole; nothing may write a layer's stack again. One token
    (2 rows): XLA keeps the kernel for row counts that are multiples of 8
    and otherwise multiplies by every group of the stack, so ``_moe_ffn``
    pads the rows."""
    from deepspeed_tpu.inference.v2.ragged_model import (_moe_ffn,
                                                         _split_expert_stacks)
    L, E, K, N = 3, 8, 4096, 14336
    chip = SingleDeviceSharding(v5e[0])

    def bf16(*shape):
        return jax.ShapeDtypeStruct(shape, BF16, sharding=chip)

    layers = {"moe": {"router": bf16(L, K, E), "w_gate": bf16(L, E, K, N),
                      "w_up": bf16(L, E, K, N), "w_down": bf16(L, E, N, K)}}
    x = bf16(T, K)

    def fwd(layers, x):
        scanned, experts = _split_expert_stacks(layers)

        def layer_fn(x, wl):
            w, l = wl
            return x + _moe_ffn(x, {**w["moe"], **experts}, 2, BF16, l), None

        return jax.lax.scan(layer_fn, x,
                            (scanned, jnp.arange(L, dtype=I32)))[0]

    compiled = jax.jit(fwd).lower(layers, x).compile()
    text = compiled.as_text()
    kernels = [ln for ln in text.splitlines()
               if "custom-call(" in ln and "ragged-dot" in ln.split("=")[0]]
    assert kernels, "no grouped-GEMM kernel in the compiled program"
    copies = [ln.strip()[:120] for ln in text.splitlines()
              if f"= bf16[{E},{K},{N}]" in ln or f"= bf16[{E},{N},{K}]" in ln]
    assert not copies, f"a layer's expert stack is materialised: {copies}"
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("T", [1, 32])
@pytest.mark.parametrize("model", ["trinity", "joyai_held"])
def test_moe_layer_scan_of_small_experts_takes_the_pallas_grouped_matmul(
        model, T, v5e, monkeypatch):
    """The same layer scan at Trinity-Mini's expert shapes (128 experts of
    2048 x 1024, top-8 of a sigmoid router) and at JoyAI's held share (16 of
    256 experts of 2048 x 768 behind the full router): bfloat16 matrices of 4
    and 3 MiB take ``ops/pallas/grouped_matmul.py`` — three Mosaic calls in
    the scan body (gate, up, down) under the scope ``moe_ffn/experts``, no
    ``ragged-dot`` custom call and so no ``ragged-dot-metadata`` kernel, and
    the stacks reach the calls whole: nothing copies or slices a layer's
    experts. Mixtral's 112 MiB matrices stay on XLA's kernel: the case above
    keeps holding."""
    from deepspeed_tpu.inference.v2.ragged_model import (_moe_ffn,
                                                         _split_expert_stacks)
    monkeypatch.setattr(_backend, "interpret", lambda: False)
    L, K = 4, 2048
    E, held, N, routed = ((128, None, 1024, 128) if model == "trinity"
                          else (16, (0, 16), 768, 256))
    routing = {"score_func": "sigmoid", "route_norm": True,
               "route_scale": 2.826}
    if held:
        routing["held"] = held
    chip = SingleDeviceSharding(v5e[0])

    def bf16(*shape):
        return jax.ShapeDtypeStruct(shape, BF16, sharding=chip)

    layers = {"moe": {"router": bf16(L, K, routed),
                      "expert_bias": bf16(L, routed),
                      "w_gate": bf16(L, E, K, N), "w_up": bf16(L, E, K, N),
                      "w_down": bf16(L, E, N, K)}}

    def fwd(layers, x):
        scanned, experts = _split_expert_stacks(layers)

        def layer_fn(x, wl):
            w, l = wl
            return x + _moe_ffn(x, {**w["moe"], **experts}, 8, BF16, l,
                                routing=routing), None

        return jax.lax.scan(layer_fn, x,
                            (scanned, jnp.arange(L, dtype=I32)))[0]

    compiled = jax.jit(fwd).lower(layers, bf16(T, K)).compile()
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 3 and all(
        "moe_ffn/experts/moe_grouped_matmul" in ln for ln in calls), calls
    assert "ragged-dot" not in text
    stack = re.compile(rf"= bf16\[(\d+,)*({K},{N}|{N},{K})\]\S* "
                       r"(copy|dynamic-slice|dynamic_slice|fusion)\(")
    staged = [ln.strip()[:120] for ln in text.splitlines() if stack.search(ln)]
    assert not staged, f"a layer's expert stack is materialised: {staged}"
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def _on(chip):
    def arr(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
    return arr


HID, FFN, VOCAB = 4096, 14336, 32000


def _mistral_7b(arr, L, weight=None):
    """Spec and stacked weight tree (shapes only) of Mistral-7B at ``L``
    layers; ``weight(L, K, N)`` makes a layer-stacked matrix (bf16 unless
    given)."""
    from deepspeed_tpu.inference.v2.model_spec import RaggedModelSpec
    w = weight or (lambda *shape: arr(BF16, *shape))
    spec = RaggedModelSpec(family="llama", num_layers=L, hidden_size=HID,
                           num_heads=H, num_kv_heads=HKV, head_dim=D,
                           vocab_size=VOCAB, window=WINDOW, dtype=BF16)
    weights = {
        "embed": arr(BF16, VOCAB, HID), "lm_head": arr(BF16, HID, VOCAB),
        "final_norm": {"scale": arr(BF16, HID)},
        "layers": {"ln1": {"scale": arr(BF16, L, HID)},
                   "ln2": {"scale": arr(BF16, L, HID)},
                   "wq": w(L, HID, H * D), "wk": w(L, HID, HKV * D),
                   "wv": w(L, HID, HKV * D), "wo": w(L, H * D, HID),
                   "mlp": {"w_gate": w(L, HID, FFN), "w_up": w(L, HID, FFN),
                           "w_down": w(L, FFN, HID)}}}
    return spec, weights


def _compile_decode_step(spec, weights, kv, rows, max_blocks, **build):
    """The fused decode step of ``rows`` rows compiled for the chip the
    shapes are on; a model with state-space layers is handed its rows'
    state slots."""
    from deepspeed_tpu.inference.v2.model_spec import num_state_layers
    from deepspeed_tpu.inference.v2.ragged_model import build_decode_step
    arr = _on(weights["embed"].sharding)
    state = (arr(I32, rows),) if num_state_layers(spec) else ()
    return jax.jit(build_decode_step(spec, **build), donate_argnums=(1,)
                   ).lower(weights, kv, arr(I32, rows), arr(I32, rows),
                           arr(I32, rows, max_blocks), arr(I32, rows),
                           arr(jnp.uint32, 2), arr(F32), *state).compile()


#: family -> (its stage, decode rows, pages a sequence, what builds the step)
_DECODE_STEPS = {
    "mistral": (lambda arr: _mistral_7b(arr, 16) + (
        arr(BF16, 16, 792, 2, HKV, BS, D),), 32, MB,
        {"window_ring_ok": True}),
    "jamba": (lambda arr: _jamba2_3b(arr), 128, 96, {}),
    "joyai": (lambda arr: _joyai_flash(arr), 32, 80, {}),
    "granite": (lambda arr: _granite_stage(arr), 64, 80, {}),
    "nemotron": (lambda arr: _nemotron_stage(arr), 128, 96, {}),
    "qwen3_next": (lambda arr: _qwen3_next_stage(arr), 64, 264, {}),
    "zaya": (lambda arr: _zaya_stage(arr), 64, 96, {}),
    "brumby": (lambda arr: _brumby_stage(arr), 32, 272, {}),
}


@pytest.fixture(scope="module")
def compiled_step(v5e):
    """``family -> (compiled decode step, spec, pools)`` at the sizes the
    benchmark's cells run, each compiled once for every test of this file
    that reads it (the caller turns the kernels' interpreter off first)."""
    built = {}

    def get(family):
        if family not in built:
            stage, rows, max_blocks, build = _DECODE_STEPS[family]
            spec, weights, kv = stage(_on(SingleDeviceSharding(v5e[0])))
            built[family] = (_compile_decode_step(
                spec, weights, kv, rows, max_blocks, **build), spec, kv)
        return built[family]

    return get


#: the Mosaic calls of each family's decode step (PERF.md section 3's table
#: of names: the paged kernels, the row write under ``kv_flush``, the state
#: kernels, the latent ones, the experts' grouped product)
_DECODE_STEP_CALLS = {
    "mistral": {"paged_decode_sidebuf", "paged_kv_row_write"},
    "jamba": {"paged_decode_sidebuf", "paged_kv_row_write",
              "ssm_decode_step"},
    "joyai": {"mla_decode", "mla_row_write", "moe_grouped_matmul"},
    "granite": {"paged_decode_sidebuf", "paged_kv_row_write",
                "ssd_decode_step", "moe_grouped_matmul"},
    "nemotron": {"paged_decode_sidebuf", "paged_kv_row_write",
                 "ssd_decode_step", "moe_grouped_matmul"},
    "qwen3_next": {"paged_decode_sidebuf", "paged_kv_row_write",
                   "gdn_decode_step", "moe_grouped_matmul"},
    # (compressed convolutional attention adds no kernel: the mixing is
    # XLA's, the tail's shift the layer's own)
    "zaya": {"paged_decode_sidebuf", "paged_kv_row_write",
             "moe_grouped_matmul"},
    # (no layer holds pages: no paged kernel and no row write after the
    # layers)
    "brumby": {"pr_decode_step"},
}


#: the MoE layer bodies of a decode step whose held share gives its rows a
#: bound (``ragged_model.held_rows_bound``: JoyAI's 16 of 256 at 32 rows x
#: top-8, its 39 MoE layers one scanned body; Qwen3-Next's 64 of 512 at 64 x
#: top-10, the four bodies of its repeating unit). Each holds the compact
#: path's two loops: over slabs of sorted rows, and over a slab's live chunks
#: in the combine (docs/SERVING.md "Held experts"); granite and nemotron hold
#: half, zaya every expert beside a skip id: no bound, no such loop
_COMPACT_MOE_BODIES = {"joyai": 1, "qwen3_next": 4}


@pytest.mark.parametrize("family", sorted(_DECODE_STEPS))
def test_decode_step_loops_over_its_layers_and_nothing_else(
        family, compiled_step, monkeypatch):
    """One program decodes one token (PR 45 took the loop over steps out of
    the builders; the lowered text changed with it, so the recorded hashes
    cannot speak across that PR): the compiled step's ``while`` loops are its
    scans over units of layers that repeat — the compiler unrolls a unit of
    one — and, inside them, the two loops of each MoE layer body that takes
    the compact path of a held share (``_COMPACT_MOE_BODIES``); its Mosaic
    calls are the family's kernels by name."""
    from deepspeed_tpu.inference.v2 import model_spec as ms
    monkeypatch.setattr(_backend, "interpret", lambda: False)
    compiled, spec, _ = compiled_step(family)
    text = compiled.as_text()
    loops = re.findall(r"^\s*(?:ROOT )?%(\S+) = .*? while\(", text, re.M)
    assert len(loops) == sum(n > 1 for _, _, n in ms.layer_units(spec)) \
        + 2 * _COMPACT_MOE_BODIES.get(family, 0), loops
    mosaic = {m.group(1) for m in re.finditer(
        r"^\s*(?:ROOT )?%([A-Za-z_]\w*?)(?:\.\d+)? = .*"
        r'custom_call_target="tpu_custom_call"', text, re.M)}
    assert mosaic == _DECODE_STEP_CALLS[family]


@pytest.mark.parametrize("pool", ["bf16", "int8"])
def test_decode_step_writes_kv_rows_in_place(pool, v5e, compiled_step,
                                             monkeypatch):
    """The serving decode step at Mistral-7B width (16 layers, 32 rows,
    pages of 128 tokens, a pool of 792 pages). Its K/V write used to gather
    two whole pages per sequence per layer (``[L, S, 2, 2, Hkv, bs, D]``,
    512 MiB), merge them with the new rows and scatter them back — three
    passes, a quarter of the step on the chip. Now: a kernel writes the
    rows; no instruction produces a value of that size, nothing copies the
    pool, the pool (and an int8 pool's scale tiles) is the output's
    buffer, and what the program keeps besides its arguments is small."""
    monkeypatch.setattr(_backend, "interpret", lambda: False)
    L, rows, pages = 16, 32, 792
    kv_dtype = BF16 if pool == "bf16" else I8
    if pool == "bf16":
        compiled = compiled_step("mistral")[0]
    else:
        arr = _on(SingleDeviceSharding(v5e[0]))
        spec, weights = _mistral_7b(arr, L)
        kv = (arr(I8, L, pages, 2, HKV, BS, D),
              arr(F32, L, *kv_scale_tiles_shape(pages, HKV, BS)))
        compiled = _compile_decode_step(spec, weights, kv, rows, MB,
                                        window_ring_ok=True)
    text = compiled.as_text()
    assert "paged_kv_row_write" in text, "the row writer is not in the program"
    pool_elems = L * pages * 2 * HKV * BS * D
    span_elems = L * rows * 2 * 2 * HKV * BS * D
    produced = re.compile(r"^\s*(?:ROOT )?%\S+ = \w+\[([\d,]+)\]\S* (\S+?)\(")
    big = []
    for line in text.splitlines():
        m = produced.match(line)
        if not m:
            continue
        dims = [int(d) for d in m.group(1).split(",")]
        n = math.prod(dims)
        if dims[-2:] == [BS, D] and (
                n == span_elems
                or (n == pool_elems and m.group(2).startswith("copy"))):
            big.append(line.strip()[:100])
    assert not big, f"whole pages or a copy of the pool: {big}"
    n_pools = 2 if pool == "int8" else 1
    header = text.splitlines()[0]
    assert len(re.findall(r"\{\d+\}: \(1[23], \{\}", header)) == n_pools, \
        header[:200]
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_elems * jnp.dtype(kv_dtype).itemsize
    assert mem.temp_size_in_bytes < 64 << 20


_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) \((.*)\) -> .* \{$")
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%(\S+) = (?:\w+\[([\d,]*)\]\S*|\(.*?\)) ([\w-]+)\(")
_CALLS = re.compile(r"calls=%([^,\s}]+)")


def _executed(text):
    """``(instructions, parameters of each computation)`` of a compiled
    program's text, the instructions being those that run by themselves:
    ``(name, dims, opcode, line)`` of every computation that no fusion
    calls (the entry, loop bodies and conditions). What sits inside a
    fusion is a step of that fusion's own pipeline, not a value in memory."""
    params, lines, name = {}, {}, None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            name = m.group(1)
            params[name], lines[name] = m.group(2), []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            lines[name].append(line)
    fused = {m.group(1) for body in lines.values() for line in body
             if " fusion(" in line for m in [_CALLS.search(line)] if m}
    out = []
    for name, body in lines.items():
        if name in fused:
            continue
        for line in body:
            m = _INSTRUCTION.match(line)
            if m:
                dims = tuple(int(d) for d in (m.group(2) or "").split(",")
                             if d)
                out.append((m.group(1), dims, m.group(3), line))
    return out, params


def _rows_of_every_choice(text, choices, widths):
    """The values in memory of a compiled pass that hold a row for every
    choice of its router (``choices`` = rows x top-k) at one of the model's
    ``widths``: what the compact path of a held share leaves none of
    (docs/SERVING.md "Held experts") — its gather, products, activation and
    combine see slabs of ``held_rows_bound`` rows."""
    return [line.strip()[:120] for _, dims, _, line in _executed(text)[0]
            if len(dims) >= 2 and choices in dims[:-1] and dims[-1] in widths]


_QKV = ((HID, H * D), (HID, HKV * D))                 # wq; wk and wv
_FREE = ("parameter", "get-tuple-element", "bitcast")


def _layer_matrices(instructions, whole_layers_only):
    """Instructions that produce one layer's ``wq``, ``wk`` or ``wv`` as a
    value of its own: ``[1, K, N]`` as the scan slices it or, unless
    ``whole_layers_only``, ``[K, N]`` and either transposed. (A pass over
    1,024 rows has activations of ``[1024, 4096]``; there only the sliced
    form tells a weight.)"""
    found = []
    for name, dims, op, _ in instructions:
        sliced = len(dims) == 3 and dims[0] == 1
        if op in _FREE or (whole_layers_only and not sliced):
            continue
        core = dims[1:] if sliced else dims
        if core in _QKV or core[::-1] in _QKV:
            found.append(f"{name} = {list(dims)} {op}")
    return found


def _stacks_read_in_place(instructions, params, L):
    """``(K, N)`` of every layer-stacked matrix ``[L, K, N]`` that is an
    operand of a fusion around a dot (``kind=kOutput``), sorted."""
    stacks = []
    for _, _, op, line in instructions:
        if op == "fusion" and "kind=kOutput" in line:
            stacks += [(int(k), int(n)) for k, n in re.findall(
                rf"(?:bf16|s8)\[{L},(\d+),(\d+)\]",
                params[_CALLS.search(line).group(1)])]
    return sorted(stacks)


@pytest.mark.parametrize("rows,tree", [(8, "bf16"), (16, "bf16"),
                                       (32, "bf16"), (8, "int8")])
def test_decode_step_reads_qkv_weights_in_place(rows, tree, v5e, monkeypatch):
    """The decode step at Mistral-7B width, 16 layers, in each bucket the
    benchmark's cells run. The q, k and v projections used to read layer
    ``l`` of their stacked weights into on-chip memory as a value of its own
    (``%constant_dynamic-slice_fusion.6/.7/.8``, ``[1, 4096, 4096]`` and two
    ``[1, 4096, 1024]``: the matrix's one HBM read, overlapped by nothing)
    and then transpose it there (three ``copy``), because the reshape to
    heads was folded into the dot's output layout: 6 such instructions a
    layer in every bucket, 1.4 ms of an 11.9 ms step on the chip. Now every
    stacked matrix of the layer is an operand of its dot's own fusion, as
    ``wo`` and the FFN's always were, and an int8 tree's dequantizing
    convert sits in that fusion too."""
    from deepspeed_tpu.inference.v2.ragged_model import build_decode_step
    monkeypatch.setattr(_backend, "interpret", lambda: False)
    L, pages = 16, 792
    arr = _on(SingleDeviceSharding(v5e[0]))

    def int8(L, K, N):
        return {"w8": arr(I8, L, K, N), "scale": arr(F32, L, 1, N)}

    spec, weights = _mistral_7b(arr, L, int8 if tree == "int8" else None)
    compiled = jax.jit(
        build_decode_step(spec, window_ring_ok=True), donate_argnums=(1,)
    ).lower(weights, arr(BF16, L, pages, 2, HKV, BS, D), arr(I32, rows),
            arr(I32, rows), arr(I32, rows, MB), arr(I32, rows),
            arr(jnp.uint32, 2)).compile()
    instructions, params = _executed(compiled.as_text())
    staged = _layer_matrices(instructions, whole_layers_only=False)
    assert not staged, f"a layer's projection matrix is materialised: {staged}"
    assert _stacks_read_in_place(instructions, params, L) == sorted(
        [(HID, H * D), (HID, HKV * D), (HID, HKV * D), (H * D, HID),
         (HID, FFN), (HID, FFN), (FFN, HID)])
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.fixture(scope="module")
def compiled_pass(v5e):
    """``program -> compiled``: the two prefill programs of one engine at
    Mistral-7B width (16 layers, 4 slots of 256 tokens, 32 decode rows, a
    pool of 792 pages), each compiled once for the tests that read it (the
    caller turns the kernels' interpreter off first)."""
    from deepspeed_tpu.inference.v2.ragged.ragged_batch import RaggedBatch
    from deepspeed_tpu.inference.v2.ragged_model import (
        build_prefill_forward, build_ragged_forward)
    build = {"serve_prefill_packed": build_prefill_forward,
             "serve_paged_pass": build_ragged_forward}
    built = {}

    def get(program):
        if program not in built:
            L, pages, slots, slot, rows = 16, 792, 4, 256, 32
            arr = _on(SingleDeviceSharding(v5e[0]))
            spec, weights = _mistral_7b(arr, L)
            # the arrays a pass is handed, as the scheduler sizes them
            host = RaggedBatch(num_slots=slots, slot_size=slot,
                               max_sequences=rows,
                               max_blocks=MB).device_arrays()
            pages_written = slots * slot // BS + slots
            batch = {k: arr(I32, pages_written) if v is None
                     else arr(I32, *v.shape) for k, v in host.items()}
            built[program] = jax.jit(
                build[program](spec), donate_argnums=(1,)).lower(
                weights, arr(BF16, L, pages, 2, HKV, BS, D), batch).compile()
        return built[program]

    return get


@pytest.mark.parametrize("program", ["serve_prefill_packed",
                                     "serve_paged_pass"])
def test_prefill_programs_stage_no_projection_weights(program, compiled_pass,
                                                      monkeypatch):
    """The two prefill programs of the same engine (4 slots of 256 tokens,
    32 decode rows) run the same layer body. Before the projections kept
    their 2-D results the packed pass staged and transposed all three
    matrices (6 ``[1, 4096, *]`` temporaries a layer) and the paged pass two
    of them (4); neither may gain one back."""
    monkeypatch.setattr(_backend, "interpret", lambda: False)
    L = 16
    instructions, params = _executed(compiled_pass(program).as_text())
    staged = _layer_matrices(instructions, whole_layers_only=True)
    assert not staged, f"a layer's projection matrix is materialised: {staged}"
    assert _stacks_read_in_place(instructions, params, L).count(
        (HID, HKV * D)) == 2


def _copies_of_the_pool(text, pool_elems):
    """The instructions of a compiled program that produce a value as large
    as the page pool by copying (what a second consumer of the scan's carry
    costs: ``_kv_page_write``'s docstring)."""
    produced = re.compile(r"^\s*(?:ROOT )?%\S+ = \w+\[([\d,]+)\]\S* (\S+?)\(")
    return [line.strip()[:100] for line in text.splitlines()
            for m in [produced.match(line)]
            if m and m.group(2).startswith("copy") and math.prod(
                int(d) for d in m.group(1).split(",")) == pool_elems]


def test_paged_pass_writes_its_chunks_as_runs_in_place(compiled_pass,
                                                       monkeypatch):
    """The paged pass at Mistral-7B width as cell 1 runs it (16 layers, 4
    slots of 256 tokens, 32 decode rows, pages of 128, a pool of 792 pages:
    6.2 GiB). Its K/V write was one scatter index a row a KV head, K and V —
    16,896 a layer at about 70 ns each, whatever the bytes (PERF.md, PR 62).
    Now a kernel writes each slot's rows as one run, the pool aliased
    through it inside the scan, and the 32 decode rows alone are scattered
    after it: the kernel is in the program, nothing copies the pool, the
    pool is the output's buffer and the temporaries stay small."""
    monkeypatch.setattr(_backend, "interpret", lambda: False)
    L, pages = 16, 792
    compiled = compiled_pass("serve_paged_pass")
    text = compiled.as_text()
    mosaic = {m.group(1) for m in re.finditer(
        r"^\s*(?:ROOT )?%([A-Za-z_]\w*?)(?:\.\d+)? = .*"
        r'custom_call_target="tpu_custom_call"', text, re.M)}
    assert "paged_kv_run_write" in mosaic, mosaic
    pool_elems = L * pages * 2 * HKV * BS * D
    assert not _copies_of_the_pool(text, pool_elems)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_elems * 2
    assert mem.temp_size_in_bytes < 64 << 20, mem.temp_size_in_bytes >> 20


def _jamba2_3b(arr):
    """Spec and stacked weight trees (shapes only) of AI21-Jamba2-3B, all 28
    layers, as ``adapt_jamba`` stacks them, and its pools for 160 tracked
    sequences and ``pages`` pages."""
    from deepspeed_tpu.inference.v2 import adapters, model_spec as ms
    from deepspeed_tpu.inference.v2.ragged.state_pool import (StatefulKV,
                                                              StatePoolConfig)
    from deepspeed_tpu.models.jamba import JambaConfig, JambaForCausalLM
    cfg = JambaConfig.jamba2_3b(dtype=BF16)
    model = JambaForCausalLM(cfg)
    shapes = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, 8), I32))["params"],
        jax.random.PRNGKey(0))
    held = {}

    def adapt(p):
        held["spec"], w = adapters.adapt_jamba(p, cfg)
        return w

    weights = jax.tree_util.tree_map(
        lambda a: arr(BF16, *a.shape), jax.eval_shape(adapt, shapes))
    spec = held["spec"]
    spec.dtype = BF16
    pool = StatePoolConfig(num_layers=ms.num_state_layers(spec),
                           num_slots=160, d_inner=5120, d_state=16, d_conv=4)
    ssm_shape, conv_shape = jax.eval_shape(pool.zeros)
    kv = StatefulKV(arr(BF16, ms.num_page_layers(spec), 2049, 2, 1, BS, D),
                    arr(ssm_shape.dtype, *ssm_shape.shape),
                    arr(conv_shape.dtype, *conv_shape.shape))
    return spec, weights, kv


def _state_pool_values(text, kv):
    """``(opcode, line)`` of every instruction outside a fusion whose value
    has a state pool's shape (as it is, or as the flat rows a scatter takes)
    and that is not free (a parameter, a tuple element, a bitcast) nor the
    in-place update itself."""
    ssm, conv = kv.ssm.shape, kv.conv.shape
    pools = {ssm, (ssm[0] * ssm[1],) + ssm[2:], conv,
             (conv[0] * conv[1],) + conv[2:],
             (conv[0] * conv[1], 3, ssm[3]), (conv[0], conv[1], 3, ssm[3])}
    instructions, _ = _executed(text)
    return [(op, line.strip()[:120]) for _, dims, op, line in instructions
            if dims in pools and op not in _FREE + ("fusion", "custom-call",
                                                    "while", "tuple")]


@pytest.mark.parametrize("program", ["serve_decode_step",
                                     "serve_prefill_packed"])
def test_jamba_programs_update_the_state_pools_in_place(program, v5e,
                                                        compiled_step,
                                                        monkeypatch):
    """AI21-Jamba2-3B at its published widths, all 28 layers: the 128-row
    decode step and the packed prefill pass (4 slots of 256). Both state
    kernels are in the program; the pools (1.33 GiB of ``h``, 0.25 GiB of
    convolution tails) are the outputs' buffers; and no instruction copies a
    pool or lays it out anew — a first layout of the tails, ``[Lm, NS + 1,
    (K-1) E]``, had the flat view a row scatter needs copied in every layer
    (127 MiB of temporaries, twice a layer, against 4 now), and a reshape of
    the tile-exact pool to ``[.., K - 1, E]`` would do the same."""
    from deepspeed_tpu.inference.v2.ragged.ragged_batch import RaggedBatch
    from deepspeed_tpu.inference.v2 import model_spec as ms, ragged_model as rm
    monkeypatch.setattr(_backend, "interpret", lambda: False)
    arr = _on(SingleDeviceSharding(v5e[0]))
    if program == "serve_decode_step":
        compiled, spec, kv = compiled_step("jamba")        # 128 rows
        kernel = "ssm_decode_step"
    else:
        spec, weights, kv = _jamba2_3b(arr)
        host = RaggedBatch(num_slots=4, slot_size=256, max_sequences=128,
                           max_blocks=96).device_arrays()
        batch = {k: arr(I32, 4 * 256 // BS + 4) if host[k] is None
                 else arr(I32, *host[k].shape)
                 for k in rm.PREFILL_PASS_KEYS + rm.STATE_PASS_KEYS}
        compiled = jax.jit(rm.build_prefill_forward(spec), donate_argnums=(1,)
                           ).lower(weights, kv, batch).compile()
        kernel = "ssm_chunk_scan"
    assert [n for _, _, n in ms.layer_runs(spec)] == [7, 1, 13, 1, 6]
    text = compiled.as_text()
    assert kernel in text, "the state kernel is not in the program"
    moved = _state_pool_values(text, kv)
    assert not moved, f"a state pool is copied or laid out anew: {moved}"
    mem = compiled.memory_analysis()
    pools = sum(math.prod(a.shape) * jnp.dtype(a.dtype).itemsize for a in kv)
    assert mem.alias_size_in_bytes >= pools
    assert mem.temp_size_in_bytes < 256 << 20


def _joyai_flash(arr, pages=700):
    """Spec and stacked weight trees (shapes only) of JoyAI-LLM-Flash, all 40
    layers at published widths with experts 0-15 of 256 held, as
    ``adapt_joyai`` stacks them, and its pool of ``pages`` latent pages."""
    from deepspeed_tpu.inference.v2 import adapters, model_spec as ms
    from deepspeed_tpu.models.joyai import JoyaiConfig, JoyaiForCausalLM
    cfg = JoyaiConfig.joyai_llm_flash(dtype=BF16, experts_held=(0, 16))
    model = JoyaiForCausalLM(cfg)
    shapes = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, 8), I32))["params"],
        jax.random.PRNGKey(0))
    held = {}

    def adapt(p):
        held["spec"], w = adapters.adapt_joyai(p, cfg)
        return w

    weights = jax.tree_util.tree_map(
        lambda a: arr(BF16, *a.shape), jax.eval_shape(adapt, shapes))
    spec = held["spec"]
    spec.dtype = BF16
    return spec, weights, arr(BF16, 40, pages + 1, BS, ms.latent_width(spec))


@pytest.mark.parametrize("program", ["serve_decode_step",
                                     "serve_prefill_packed",
                                     "serve_paged_pass"])
def test_joyai_programs_keep_the_latent_pool_and_the_weights_in_place(
        program, v5e, compiled_step, monkeypatch):
    """JoyAI-LLM-Flash as the benchmark's configuration runs it: all 40
    layers at published widths, 16 of 256 experts held (8.90 GiB of weights)
    and 700 latent pages (4.28 GiB): the 32-row decode step, the packed
    prefill pass (4 slots of 256) and the paged pass. The latent kernels are
    in the program; the pool is the output's buffer and no instruction
    copies it; and the up-projections ``w_uk``/``w_uv`` (4 MiB each a layer)
    are read where they lie — stored ``[R, H, d]`` they were copied
    transposed in every layer of the decode step — and no layer's matrix is
    staged before its dot."""
    from deepspeed_tpu.inference.v2.ragged.ragged_batch import RaggedBatch
    from deepspeed_tpu.inference.v2 import model_spec as ms, ragged_model as rm
    monkeypatch.setattr(_backend, "interpret", lambda: False)
    arr = _on(SingleDeviceSharding(v5e[0]))
    spec, weights, kv = _joyai_flash(arr)
    assert [n for _, _, n in ms.layer_runs(spec)] == [1, 39]
    assert kv.shape == (40, 701, 128, 640)
    host = RaggedBatch(num_slots=4, slot_size=256, max_sequences=32,
                       max_blocks=80).device_arrays()
    if program == "serve_decode_step":
        compiled = compiled_step("joyai")[0]                # 32 rows
        kernels = ("mla_decode", "mla_row_write")
    elif program == "serve_prefill_packed":
        batch = {k: arr(I32, 4 * 256 // BS + 4) if host[k] is None
                 else arr(I32, *host[k].shape) for k in rm.PREFILL_PASS_KEYS}
        compiled = jax.jit(rm.build_prefill_forward(spec), donate_argnums=(1,)
                           ).lower(weights, kv, batch).compile()
        kernels = ("flash_fwd_packed",)
    else:
        batch = {k: arr(I32, *host[k].shape) for k in rm.PAGED_PASS_KEYS}
        compiled = jax.jit(rm.build_ragged_forward(spec), donate_argnums=(1,)
                           ).lower(weights, kv, batch).compile()
        kernels = ("mla_decode", "mla_chunk")
    text = compiled.as_text()
    for kernel in kernels:
        assert kernel in text, f"{kernel} is not in the program"
    if program != "serve_decode_step":
        # 16 of 256 held: a pass's MoE layers work on slabs of 1,152 (the
        # paged pass: 1,056 rows x top-8) or 1,024 sorted rows, and nothing
        # holds a row of 2,048 or of 768 for every choice
        tokens = 4 * 256 + (32 if program == "serve_paged_pass" else 0)
        assert rm.pass_held_rows_bound(spec, weights, tokens) == (
            1152 if program == "serve_paged_pass" else 1024)
        assert not _rows_of_every_choice(text, tokens * 8, (2048, 768))
    pool = math.prod(kv.shape) * 2
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool
    assert mem.temp_size_in_bytes < 256 << 20
    instructions, _ = _executed(text)
    pools = {kv.shape, (40 * 701,) + kv.shape[2:],
             (40 * 701 * 128, 640)}
    moved = [line.strip()[:120] for _, dims, op, line in instructions
             if dims in pools and op not in _FREE + (
                 "fusion", "custom-call", "while", "tuple", "scatter")]
    assert not moved, f"the latent pool is copied or laid out anew: {moved}"
    ups = [line.strip()[:120] for _, dims, op, line in instructions
           if op in ("copy", "transpose")
           and sorted(dims) in (sorted((32, 512, 128)),)]
    assert not ups, f"w_uk / w_uv are copied in every layer: {ups}"
    # nor is a layer's q_b_proj (18 MiB) staged and copied transposed before
    # its dot: 1.6 ms of the decode step on the chip until its result went
    # behind a barrier, as the dense q/k/v results do (PR 30)
    assert "constant_dynamic-slice_fusion" not in text


@pytest.mark.parametrize("pages", [1, 8, 64])
@pytest.mark.parametrize("page", [(BS, 640), (2, 8, BS, 128)],
                         ids=["latent", "kv"])
def test_page_gather_stages_no_pool(page, pages, v5e):
    """The engine's page gather (preempt-offload, ``export_kv``, and every
    engine's warm-up) over the benchmark's pools — latent rows, 700 pages of
    6.25 MiB over 40 layers, and keys and values per head, 791 pages of 8
    MiB over 16: as one gather of whole latent pages the compiled program
    held 4.3 GiB of temporaries — the whole pool staged — and the warm-up
    ran out of memory on the chip; a page at a time it holds next to none,
    whichever the layout."""
    from deepspeed_tpu.inference.v2.engine_v2 import _gather_pages
    arr = _on(SingleDeviceSharding(v5e[0]))
    layers, blocks = (40, 701) if len(page) == 2 else (16, 792)
    pool = arr(BF16, layers, blocks, *page)
    compiled = jax.jit(_gather_pages).lower(pool, arr(I32, pages)).compile()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes == pages * layers * math.prod(page) * 2
    assert mem.temp_size_in_bytes < 16 << 20


# --------------------------------------------------------------------------- #
# the train step: what its checkpointed layers keep (runtime/
# activation_checkpointing.py), chosen by the engine for a described v5e
# --------------------------------------------------------------------------- #

#: ``memory_stats()["bytes_limit"]`` of a v5e chip (chip runs of PR 22); a
#: described device reports none
V5E_HBM_LIMIT = int(15.75 * 2 ** 30)


class _ShapesOnly:
    """``jax.jit`` for the engine's state build: the shapes it would make,
    under its out_shardings (a described device cannot hold an array)."""

    def __init__(self, fn, **options):
        self.fn, self.options = fn, options

    def __call__(self, *args):
        return jax.tree_util.tree_map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            jax.eval_shape(self.fn, *args), self.options["out_shardings"])


def _train_engine(v5e, monkeypatch, layers, fsdp=1, rows=1, remat_policy=None,
                  jit=_ShapesOnly, seed=0):
    """``chipbench/configs/mistral7b-train-d2.json``'s engine (``layers``
    deep, ``rows`` sequences of 4096 a chip a step, ZeRO-3 over ``fsdp``
    chips) over described chips, its state built from a first batch with
    ``jit`` in ``jax.jit``'s place. Returns (engine, topology)."""
    import json
    import numpy as np
    import deepspeed_tpu
    from deepspeed_tpu.accelerator import get_accelerator
    from deepspeed_tpu.comm.mesh import build_topology
    from deepspeed_tpu.config import MeshConfig
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from deepspeed_tpu.ops import attention
    monkeypatch.setattr(_backend, "interpret", lambda: False)
    monkeypatch.setattr(attention, "_use_pallas", lambda: True)
    monkeypatch.setattr(type(get_accelerator()), "total_memory",
                        lambda self, device_index=None: V5E_HBM_LIMIT)
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "chipbench", "configs",
                           "mistral7b-train-d2.json")) as f:
        config = dict(json.load(f)["train"])
    config.update(train_batch_size=rows * fsdp,
                  train_micro_batch_size_per_gpu=rows,
                  mesh={"data": 1, "fsdp": fsdp})
    topo = build_topology(MeshConfig(data=1, fsdp=fsdp),
                          devices=list(v5e[:fsdp]))
    model = LlamaForCausalLM(LlamaConfig.mistral_7b(
        num_hidden_layers=layers, dtype=BF16, remat=True,
        remat_policy=remat_policy))
    engine, *_ = deepspeed_tpu.initialize(model=model, config=config,
                                          mesh_topology=topo,
                                          rngs=jax.random.PRNGKey(seed))
    batch = {"input_ids": np.zeros((rows * fsdp, 4096), np.int32)}
    with monkeypatch.context() as m:
        m.setattr(jax, "jit", jit)
        engine._ensure_state(batch)
    return engine, topo


def _train_step(v5e, monkeypatch, layers, fsdp=1, rows=1, remat_policy=None):
    """The fused step of that engine, built and compiled as ``train_batch``
    builds it at its first step. Returns (engine, compiled)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from deepspeed_tpu.comm.mesh import BATCH_AXES
    engine, topo = _train_engine(v5e, monkeypatch, layers, fsdp, rows,
                                 remat_policy)
    # the engine asks the default backend which options it may pass
    options = engine._compiler_options("tpu")
    monkeypatch.setattr(engine, "_compiler_options",
                        lambda backend=None: options)
    staged = {"input_ids": jax.ShapeDtypeStruct(
        (1, rows * fsdp, 4096), I32,
        sharding=NamedSharding(topo.mesh, P(None, BATCH_AXES)))}
    step = engine._make_fused_step(staged)
    if engine.remat_plan is None:
        return engine, step.lower(engine.state, staged).compile()
    return engine, engine._compile_fitted(engine.state, staged)


class _CompiledBuild(_ShapesOnly):
    """... after compiling the build for the described chips as the engine
    jits it (its shardings, its arguments by shape); ``texts`` keeps what the
    compiler made of each."""

    texts: list = []
    real = staticmethod(jax.jit)

    def __call__(self, *args):
        shapes = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args)
        self.texts.append(self.real(self.fn, **self.options).lower(
            *shapes).compile().as_text())
        return super().__call__(*args)


@pytest.mark.parametrize("fsdp", [1, 4])
def test_state_build_takes_the_init_key_as_a_parameter(fsdp, v5e,
                                                       monkeypatch):
    """The lazy state build of cells ``mistral7b-train.seq4k`` and
    ``mistral7b-zero3x4.seq4k`` as the chip's compiler sees it: the init key
    is the program's one parameter, and the compiled text is the same at two
    seeds — no ``u32[2]`` constant that a seed could change, so the second
    run on a machine finds the build in the persistent cache."""
    monkeypatch.setattr(_CompiledBuild, "texts", [])
    for seed in (0, 1):
        _train_engine(v5e, monkeypatch, layers=2, fsdp=fsdp,
                      jit=_CompiledBuild, seed=seed)
    first, second = _CompiledBuild.texts
    assert first == second
    entry = re.search(r"^ENTRY [^\n]*\{\n(.*?)^\}", first,
                      re.M | re.S).group(1)
    params = re.findall(r"= (\w+\[[\d,]*\])\S* parameter\(\d+\)", entry)
    assert params == ["u32[2]"], params
    assert not re.search(r"u32\[2\][^ ]* constant\(", first)


def _device_bytes(compiled) -> int:
    mem = compiled.memory_analysis()
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)


def _all_gathers(text) -> int:
    return text.count(" all-gather(") + text.count(" all-gather-start(")


def test_train_step_keeps_the_flash_residuals_and_fits(v5e, monkeypatch):
    """Cell ``mistral7b-train.seq4k`` at its real shapes: with room for
    every product and the flash forward's output and log-sum-exp, a layer's
    backward runs the forward kernel no second time — 2 Mosaic calls a
    layer (forward, and the one backward call that gives dq, dk and dv),
    where full recompute makes 3. Every backward call the step traces took
    the fused kernel, and the step needs no more of the chip than with the
    two calls it replaced (dq's sum lives in VMEM, not in a new buffer)."""
    from deepspeed_tpu.monitor.trace import tracer
    from deepspeed_tpu.ops.pallas import flash_attention as fa
    with monkeypatch.context() as m:
        m.setattr(fa, "FUSED_BWD_VMEM_BYTES", 0)
        _, two_calls = _train_step(v5e, m, layers=2)
    assert two_calls.as_text().count("tpu_custom_call") == 6
    assert tracer.totals["train/flash/bwd_fused"] == 0
    engine, compiled = _train_step(v5e, monkeypatch, layers=2)
    assert tracer.totals["train/flash/bwd_fused"] >= 1
    assert tracer.totals["train/flash/bwd_split"] == 0
    assert _device_bytes(compiled) <= _device_bytes(two_calls)
    plan = engine.remat_plan
    assert plan.rung == 0 and plan.limit_bytes == V5E_HBM_LIMIT
    # a layer: its input, q, k, v, o_proj's output, gate and up, the
    # attention output twice (as the kernel and as the model lay it out)
    # and the log-sum-exp
    assert plan.kept_per_layer[0] == 2 * 4096 * (
        4 * 4096 + 2 * 1024 + 2 * 14336 + 4096) + 4 * 32 * 4096
    assert compiled.as_text().count("tpu_custom_call") == 4
    assert _device_bytes(compiled) <= V5E_HBM_LIMIT


@pytest.mark.parametrize("fsdp", [1, 4])
def test_train_step_feeds_no_product_through_an_exponential(fsdp, v5e,
                                                            monkeypatch):
    """Both training cells' steps, 2 layers deep. On one chip no
    ``convolution`` takes as an operand a producer fusion that holds an
    ``exponential``: with the MLP's gate as the plain ``act(gate) * up`` six
    products a layer did (``silu(gate)`` re-formed tile by tile inside the
    forward ``down_proj``, ``dx`` through ``gate_proj`` and ``up_proj`` and
    the three ``dW`` with their AdamW updates: 12 at PR 43, 1.4-2.3 times
    the MXU's time each by the compiler's own estimate);
    ``models/llama.py::gated_activation`` hands ``act(gate) * up`` and
    ``(dgate, dup)`` on through an ``optimization_barrier`` each, and the
    compiler forms them as epilogues of the products that make their
    inputs. A compiler that stops honouring the barriers, or a new producer
    of the kind, trips this. Over four chips the engine tells the rule that
    the gradients are reduced across devices and it stands down: the
    producers are there as they were (which shows that the reader finds
    them), and no product that carries an all-gather's pieces gains the
    exponential as an epilogue — two a layer would, at 0.6 ms each on the
    chip (PR 44)."""
    from deepspeed_tpu.profiling.compiled_products import (fed_through,
                                                           product_fusions)
    _, compiled = _train_step(v5e, monkeypatch, layers=2, fsdp=fsdp)
    fusions = product_fusions(compiled.as_text())
    # a layer's 7 projections forward, 14 backward, and the head's 3
    assert len(fusions) >= 2 * 21 + 3
    fed = [f.name for f in fed_through(fusions, "exponential")]
    if fsdp == 1:
        assert fed == []
    else:
        assert len(fed) == 12
        assert [f.name for f in fusions if "all-gather" in f.epilogue
                and "exponential" in f.epilogue] == []


def test_train_step_over_four_chips_gathers_no_weights_for_a_second_forward(
        v5e, monkeypatch):
    """The same walk under ``mesh: {fsdp: 4}`` (cell ``mistral7b-zero3x4
    .seq4k``'s, 2 layers deep): the kernel sits in a shard_map there and its
    names still reach the policy; and the forward that is not re-run gathers
    no layer's weights a third time."""
    engine, kept = _train_step(v5e, monkeypatch, layers=2, fsdp=4)
    assert engine.remat_plan.rung == 0
    named, full = _train_step(v5e, monkeypatch, layers=2, fsdp=4,
                              remat_policy="none")
    assert named.remat_plan is None
    kept, full = kept.as_text(), full.as_text()
    assert (kept.count("tpu_custom_call"),
            full.count("tpu_custom_call")) == (4, 6)
    assert _all_gathers(kept) < _all_gathers(full)


@pytest.mark.parametrize("estimate", ["as_made", "too_hopeful"])
def test_train_step_without_room_for_every_product_keeps_fewer(
        estimate, v5e, monkeypatch):
    """The guard's case: six sequences a step on the same chip. What rung 0
    keeps of them does not fit beside the state and the gradients. The
    engine's estimate says so and the rung it gives compiles and fits; and
    were the estimate too hopeful (here: made to say rung 0), the compiler
    refuses that step and the engine compiles one rung lower, which fits."""
    from deepspeed_tpu.runtime import activation_checkpointing as ac
    if estimate == "too_hopeful":
        monkeypatch.setattr(ac, "choose_rung", lambda *a, **kw: 0)
    engine, compiled = _train_step(v5e, monkeypatch, layers=2, rows=6)
    assert 0 < engine.remat_plan.rung < len(ac.LADDER) - 1
    assert compiled.as_text().count("tpu_custom_call") == 4
    assert _device_bytes(compiled) <= V5E_HBM_LIMIT


def _granite_stage(arr):
    """Spec, stacked weight trees (shapes only) and pools of granite-4.0-h-
    small as the benchmark's configuration runs it: published layers 0-9 at
    published widths, 36 of 72 experts held (9.24 GiB of weights), 2,656
    pages and the state pool of 72 + 1 slots (2.63 GiB)."""
    from deepspeed_tpu.inference.v2 import adapters
    from deepspeed_tpu.inference.v2.ragged.state_pool import (StatefulKV,
                                                              StatePoolConfig)
    from deepspeed_tpu.models.granite import (GraniteConfig,
                                              GraniteForCausalLM)
    cfg = GraniteConfig.granite_4_0_h_small(
        num_hidden_layers=10, layer_types=tuple(
            "attention" if i == 5 else "mamba" for i in range(10)),
        experts_held=(0, 36), dtype=BF16)
    model = GraniteForCausalLM(cfg)
    shapes = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, 8), I32))["params"],
        jax.random.PRNGKey(0))
    held = {}

    def adapt(p):
        held["spec"], w = adapters.adapt_granite(p, cfg)
        return w

    weights = jax.tree_util.tree_map(
        lambda a: arr(BF16, *a.shape), jax.eval_shape(adapt, shapes))
    spec = held["spec"]
    spec.dtype = BF16
    pool = StatePoolConfig(num_layers=9, num_slots=72, d_inner=8192,
                           d_state=128, d_conv=4, conv_dim=8448)
    ssm_shape, conv_shape = jax.eval_shape(pool.zeros)
    kv = StatefulKV(arr(BF16, 1, 2657, 2, 8, BS, D),
                    arr(F32, *ssm_shape.shape), arr(F32, *conv_shape.shape))
    return spec, weights, kv


@pytest.mark.parametrize("program", ["serve_decode_step",
                                     "serve_prefill_packed",
                                     "serve_paged_pass"])
def test_granite_programs_update_the_state_pools_in_place(program, v5e,
                                                          compiled_step,
                                                          monkeypatch):
    """granite-4.0-h-small's stage 0 at published widths: the 64-row decode
    step, the packed prefill pass (4 slots of 256) and the paged pass. Both
    SSD kernels are where they belong; the pools (2.60 GiB of states, 72 MiB
    of tails, 1.30 GiB of pages) are the outputs' buffers; and the program's
    temporaries stay small — XLA's gather of four 4 MiB states sliced the
    WHOLE state pool into column blocks first (2.6 GiB of copies a layer,
    and the pass did not fit the chip; compile, PR 39), so those rows move
    one dynamic slice each."""
    from deepspeed_tpu.inference.v2.ragged.ragged_batch import RaggedBatch
    from deepspeed_tpu.inference.v2 import model_spec as ms, ragged_model as rm
    monkeypatch.setattr(_backend, "interpret", lambda: False)
    arr = _on(SingleDeviceSharding(v5e[0]))
    spec, weights, kv = _granite_stage(arr)
    assert [n for _, _, n in ms.layer_runs(spec)] == [5, 1, 4]
    rows, pages = 64, 80
    host = RaggedBatch(num_slots=4, slot_size=256, max_sequences=rows,
                       max_blocks=pages).device_arrays()
    if program == "serve_decode_step":
        compiled = compiled_step("granite")[0]              # 64 rows
        kernels, limit = ("ssd_decode_step",), 64 << 20
    elif program == "serve_prefill_packed":
        batch = {k: arr(I32, 4 * 256 // BS + 4) if host[k] is None
                 else arr(I32, *host[k].shape)
                 for k in rm.PREFILL_PASS_KEYS + rm.STATE_PASS_KEYS}
        compiled = jax.jit(rm.build_prefill_forward(spec), donate_argnums=(1,)
                           ).lower(weights, kv, batch).compile()
        kernels, limit = ("ssd_chunk_scan",), 384 << 20
    else:
        batch = {k: arr(I32, *host[k].shape)
                 for k in rm.PAGED_PASS_KEYS + rm.STATE_PASS_KEYS}
        compiled = jax.jit(rm.build_ragged_forward(spec), donate_argnums=(1,)
                           ).lower(weights, kv, batch).compile()
        kernels, limit = ("ssd_chunk_scan", "ssd_decode_step"), 384 << 20
    text = compiled.as_text()
    for kernel in kernels + ("moe_grouped_matmul",):
        assert kernel in text, f"{kernel} is not in the program"
    assert "mini-gather" not in text
    mem = compiled.memory_analysis()
    pools = sum(math.prod(a.shape) * jnp.dtype(a.dtype).itemsize for a in kv)
    assert mem.alias_size_in_bytes >= pools
    assert mem.temp_size_in_bytes < limit


def _nemotron_stage(arr):
    """Spec, stacked weight trees (shapes only) and pools of Nemotron 3 Nano
    30B-A3B as the benchmark's configuration runs it: published layers 0-15
    (``MEMEM*EMEMEM*EME``) at published widths, 64 of 128 experts held, half
    the vocabulary (9.84 GiB of weights), 5,256 pages over the two attention
    layers and the state pool of 144 + 1 slots over the seven Mamba layers
    (2.05 GiB)."""
    from deepspeed_tpu.inference.v2 import adapters
    from deepspeed_tpu.inference.v2.ragged.state_pool import (StatefulKV,
                                                              StatePoolConfig)
    from deepspeed_tpu.models.nemotron_h import (NemotronHConfig,
                                                 NemotronHForCausalLM)
    cfg = NemotronHConfig.nemotron_3_nano_30b_a3b(
        num_hidden_layers=16, hybrid_override_pattern="MEMEM*EMEMEM*EME",
        experts_held=(0, 64), vocab_size=65536, dtype=BF16)
    model = NemotronHForCausalLM(cfg)
    shapes = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, 8), I32))["params"],
        jax.random.PRNGKey(0))
    held = {}

    def adapt(p):
        held["spec"], w = adapters.adapt_nemotron_h(p, cfg)
        return w

    weights = jax.tree_util.tree_map(
        lambda a: arr(BF16, *a.shape), jax.eval_shape(adapt, shapes))
    spec = held["spec"]
    spec.dtype = BF16
    pool = StatePoolConfig(num_layers=7, num_slots=144, d_inner=4096,
                           d_state=128, d_conv=4, conv_dim=6144)
    ssm_shape, conv_shape = jax.eval_shape(pool.zeros)
    kv = StatefulKV(arr(BF16, 2, 5257, 2, 2, BS, D),
                    arr(F32, *ssm_shape.shape), arr(F32, *conv_shape.shape))
    return spec, weights, kv


@pytest.mark.parametrize("program", ["serve_decode_step",
                                     "serve_prefill_packed",
                                     "serve_paged_pass"])
def test_nemotron_programs_scan_units_and_update_the_pools_in_place(
        program, v5e, compiled_step, monkeypatch):
    """Nemotron 3 Nano's first 16 layers at published widths: the 128-row
    decode step, the packed prefill pass (4 slots of 256) and the paged pass.
    The 16 one-block layers are three scans (``M, E, (MEM*EME) x 2``), not
    sixteen; both SSD kernels take 8 groups of B and C; the experts'
    products — a width of 14.5 lane tiles, zero-padded to 15 when the weights
    are adapted — are the Pallas grouped matmul's (XLA's ragged-dot kernel is
    nowhere) and NO expert stack is copied (unpadded, the chip lays
    ``[.., 2688, 1856]`` out with 2688 on the lanes and the kernel was handed
    a transposed copy of every ``w_up`` stack: 4.9 GiB of temporaries, and
    the step did not fit; compile, PR 42); the pools are the outputs' buffers
    and the temporaries stay small."""
    from deepspeed_tpu.inference.v2.ragged.ragged_batch import RaggedBatch
    from deepspeed_tpu.inference.v2 import model_spec as ms, ragged_model as rm
    monkeypatch.setattr(_backend, "interpret", lambda: False)
    arr = _on(SingleDeviceSharding(v5e[0]))
    spec, weights, kv = _nemotron_stage(arr)
    assert [(len(s), n) for s, _, n in ms.layer_units(spec)] == [
        (1, 1), (1, 1), (7, 2)]
    assert ms.num_page_layers(spec) == 2 and ms.num_state_layers(spec) == 7
    rows, pages = 128, 96
    host = RaggedBatch(num_slots=4, slot_size=256, max_sequences=rows,
                       max_blocks=pages).device_arrays()
    if program == "serve_decode_step":
        compiled = compiled_step("nemotron")[0]             # 128 rows
        kernels, limit = ("ssd_decode_step",), 512 << 20
    elif program == "serve_prefill_packed":
        batch = {k: arr(I32, 4 * 256 // BS + 4) if host[k] is None
                 else arr(I32, *host[k].shape)
                 for k in rm.PREFILL_PASS_KEYS + rm.STATE_PASS_KEYS}
        compiled = jax.jit(rm.build_prefill_forward(spec), donate_argnums=(1,)
                           ).lower(weights, kv, batch).compile()
        kernels, limit = ("ssd_chunk_scan",), 512 << 20
    else:
        batch = {k: arr(I32, *host[k].shape)
                 for k in rm.PAGED_PASS_KEYS + rm.STATE_PASS_KEYS}
        compiled = jax.jit(rm.build_ragged_forward(spec), donate_argnums=(1,)
                           ).lower(weights, kv, batch).compile()
        kernels, limit = ("ssd_chunk_scan", "ssd_decode_step"), 512 << 20
    text = compiled.as_text()
    for kernel in kernels + ("moe_grouped_matmul",):
        assert kernel in text, f"{kernel} is not in the program"
    assert "ragged-dot" not in text and "mini-gather" not in text
    assert not re.search(r"bf16\[\d+,64,(2688,1920|1920,2688)\]\S* copy\(",
                         text), "an expert stack is copied"
    mem = compiled.memory_analysis()
    pools = sum(math.prod(a.shape) * jnp.dtype(a.dtype).itemsize for a in kv)
    assert mem.alias_size_in_bytes >= pools
    assert mem.temp_size_in_bytes < limit


def _qwen3_next_stage(arr):
    """Spec, stacked weight trees (shapes only) and pools of
    Qwen3-Next-80B-A3B as the benchmark's configuration runs it: published
    layers 0-11 (three periods of 3 Gated DeltaNet layers and 1 attention
    layer) at published widths, 64 of 512 experts held, ids 0-18,991 of the
    vocabulary (5.46 GiB of weights), 8,000 pages over the three attention
    layers (256-wide heads, 64 values rotated) and the state pool of 72 + 1
    slots over the nine delta layers (1.34 GiB)."""
    from deepspeed_tpu.inference.v2 import adapters
    from deepspeed_tpu.inference.v2.ragged.state_pool import (StatefulKV,
                                                              StatePoolConfig)
    from deepspeed_tpu.models.qwen3_next import (Qwen3NextConfig,
                                                 Qwen3NextForCausalLM)
    cfg = Qwen3NextConfig.qwen3_next_80b_a3b(
        num_hidden_layers=12, experts_held=(0, 64), vocab_size=18992,
        dtype=BF16)
    model = Qwen3NextForCausalLM(cfg)
    shapes = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, 8), I32))["params"],
        jax.random.PRNGKey(0))
    held = {}

    def adapt(p):
        held["spec"], w = adapters.adapt_qwen3_next(p, cfg)
        return w

    weights = jax.tree_util.tree_map(
        lambda a: arr(BF16, *a.shape), jax.eval_shape(adapt, shapes))
    spec = held["spec"]
    spec.dtype = BF16
    pool = StatePoolConfig(num_layers=9, num_slots=72, d_inner=4096,
                           d_state=128, d_conv=4, conv_dim=8192)
    ssm_shape, conv_shape = jax.eval_shape(pool.zeros)
    kv = StatefulKV(arr(BF16, 3, 8001, 2, 2, BS, 256),
                    arr(F32, *ssm_shape.shape), arr(F32, *conv_shape.shape))
    return spec, weights, kv


@pytest.mark.parametrize("program", ["serve_decode_step",
                                     "serve_prefill_packed",
                                     "serve_paged_pass"])
def test_qwen3_next_programs_hold_the_delta_kernels_and_the_pools_in_place(
        program, v5e, compiled_step, monkeypatch):
    """Qwen3-Next's first 12 layers at published widths: the 64-row decode
    step, the packed prefill pass (8 slots of 256) and the paged pass. Both
    delta-rule kernels are where they belong, the attention is the paged
    kernels' at 256-wide heads (64 values rotated before them), the 64 held
    experts' products (2 MiB matrices) are the Pallas grouped matmul's and no
    stack is copied; the pools (1.27 GiB of states, 62 MiB of tails, 5.86 GiB
    of pages) are the outputs' buffers and the temporaries stay small."""
    from deepspeed_tpu.inference.v2.ragged.ragged_batch import RaggedBatch
    from deepspeed_tpu.inference.v2 import model_spec as ms, ragged_model as rm
    monkeypatch.setattr(_backend, "interpret", lambda: False)
    arr = _on(SingleDeviceSharding(v5e[0]))
    spec, weights, kv = _qwen3_next_stage(arr)
    assert ms.num_page_layers(spec) == 3 and ms.num_state_layers(spec) == 9
    assert spec.head_dim == 256 and spec.rotary_dim == 64
    rows, pages, slots = 64, 264, 8
    host = RaggedBatch(num_slots=slots, slot_size=256, max_sequences=rows,
                       max_blocks=pages).device_arrays()
    if program == "serve_decode_step":
        compiled = compiled_step("qwen3_next")[0]           # 64 rows
        kernels, limit = ("gdn_decode_step",), 128 << 20
    elif program == "serve_prefill_packed":
        batch = {k: arr(I32, slots * 256 // BS + slots) if host[k] is None
                 else arr(I32, *host[k].shape)
                 for k in rm.PREFILL_PASS_KEYS + rm.STATE_PASS_KEYS}
        compiled = jax.jit(rm.build_prefill_forward(spec), donate_argnums=(1,)
                           ).lower(weights, kv, batch).compile()
        kernels, limit = ("gdn_chunk_scan",), 1024 << 20
    else:
        batch = {k: arr(I32, *host[k].shape)
                 for k in rm.PAGED_PASS_KEYS + rm.STATE_PASS_KEYS}
        compiled = jax.jit(rm.build_ragged_forward(spec), donate_argnums=(1,)
                           ).lower(weights, kv, batch).compile()
        kernels, limit = ("gdn_chunk_scan", "gdn_decode_step"), 1024 << 20
    text = compiled.as_text()
    for kernel in kernels + ("moe_grouped_matmul",):
        assert kernel in text, f"{kernel} is not in the program"
    assert "ragged-dot" not in text and "mini-gather" not in text
    assert not re.search(r"bf16\[\d+,64,(2048,512|512,2048)\]\S* copy\(",
                         text), "an expert stack is copied"
    if program != "serve_decode_step":
        # 64 of 512 held: a pass's MoE layers work on slabs of 5,376 (the
        # paged pass: 2,112 rows x top-10) or 5,120 sorted rows, and nothing
        # holds a row of 2,048 or of 512 for every choice
        tokens = slots * 256 + (rows if program == "serve_paged_pass" else 0)
        assert rm.pass_held_rows_bound(spec, weights, tokens) == (
            5376 if program == "serve_paged_pass" else 5120)
        assert not _rows_of_every_choice(text, tokens * 10, (2048, 512))
    mem = compiled.memory_analysis()
    pools = sum(math.prod(a.shape) * jnp.dtype(a.dtype).itemsize for a in kv)
    assert mem.alias_size_in_bytes >= pools
    assert mem.temp_size_in_bytes < limit, mem.temp_size_in_bytes >> 20


def _zaya_stage(arr):
    """Spec, stacked weight tree (shapes only) and pools of ZAYA1-8B as the
    benchmark's configuration runs it: published layers 0-19 at published
    widths, all 16 experts and the whole 262,272-row vocabulary tied to the
    head (8.73 GiB of weights), 1,810 pages over all twenty layers (2 KV
    heads of 128) and the tail pool of 72 + 1 slots over the same twenty
    (22.8 MiB, no recurrent state)."""
    from deepspeed_tpu.inference.v2 import adapters
    from deepspeed_tpu.inference.v2.ragged.state_pool import (StatefulKV,
                                                              StatePoolConfig)
    from deepspeed_tpu.models.zaya import ZayaConfig, ZayaForCausalLM
    cfg = ZayaConfig.zaya1_8b(num_hidden_layers=20, dtype=BF16)
    model = ZayaForCausalLM(cfg)
    shapes = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, 8), I32))["params"],
        jax.random.PRNGKey(0))
    held = {}

    def adapt(p):
        held["spec"], w = adapters.adapt_zaya(p, cfg)
        return w

    weights = jax.tree_util.tree_map(
        lambda a: arr(BF16, *a.shape), jax.eval_shape(adapt, shapes))
    spec = held["spec"]
    spec.dtype = BF16
    pool = StatePoolConfig.tails_only(20, 72, taps=2, channels=1408)
    ssm_shape, conv_shape = jax.eval_shape(pool.zeros)
    kv = StatefulKV(arr(BF16, 20, 1811, 2, 2, BS, 128),
                    arr(F32, *ssm_shape.shape), arr(F32, *conv_shape.shape))
    return spec, weights, kv


@pytest.mark.parametrize("program", ["serve_decode_step",
                                     "serve_prefill_packed",
                                     "serve_paged_pass"])
def test_zaya_programs_keep_the_pools_and_the_weights_in_place(
        program, v5e, compiled_step, monkeypatch):
    """ZAYA1-8B's first 20 layers at published widths: the 64-row decode
    step, the packed prefill pass (4 slots of 256) and the paged pass. Every
    layer addresses both pools: the pages (4.4 GiB) and the tails (22.8 MiB)
    are the outputs' buffers; the 16 experts' products (8 MiB matrices) are
    the Pallas grouped matmul's and no stack is copied; the tied head reads
    the 262,272-row embedding where it lies (no float32 copy of it: 2 GiB)
    and the temporaries stay small."""
    from deepspeed_tpu.inference.v2.ragged.ragged_batch import RaggedBatch
    from deepspeed_tpu.inference.v2 import model_spec as ms, ragged_model as rm
    monkeypatch.setattr(_backend, "interpret", lambda: False)
    arr = _on(SingleDeviceSharding(v5e[0]))
    spec, weights, kv = _zaya_stage(arr)
    assert ms.num_page_layers(spec) == ms.num_state_layers(spec) == 20
    assert spec.head_dim == 128 and spec.rotary_dim == 64
    assert kv.ssm.size == 0
    rows, pages, slots = 64, 96, 4
    host = RaggedBatch(num_slots=slots, slot_size=256, max_sequences=rows,
                       max_blocks=pages).device_arrays()
    if program == "serve_decode_step":
        compiled = compiled_step("zaya")[0]                 # 64 rows
        limit = 256 << 20
    elif program == "serve_prefill_packed":
        batch = {k: arr(I32, slots * 256 // BS + slots) if host[k] is None
                 else arr(I32, *host[k].shape)
                 for k in rm.PREFILL_PASS_KEYS + rm.STATE_PASS_KEYS}
        compiled = jax.jit(rm.build_prefill_forward(spec), donate_argnums=(1,)
                           ).lower(weights, kv, batch).compile()
        limit = 1024 << 20
    else:
        batch = {k: arr(I32, *host[k].shape)
                 for k in rm.PAGED_PASS_KEYS + rm.STATE_PASS_KEYS}
        compiled = jax.jit(rm.build_ragged_forward(spec), donate_argnums=(1,)
                           ).lower(weights, kv, batch).compile()
        limit = 1024 << 20
    text = compiled.as_text()
    assert "moe_grouped_matmul" in text
    assert "ragged-dot" not in text and "mini-gather" not in text
    assert not re.search(r"bf16\[\d+,16,2048,2048\]\S* copy\(", text), \
        "an expert stack is copied"
    assert not re.search(r"f32\[262272,2048\]", text), \
        "the tied head's embedding is widened to float32"
    mem = compiled.memory_analysis()
    pools = sum(math.prod(a.shape) * jnp.dtype(a.dtype).itemsize for a in kv)
    assert mem.alias_size_in_bytes >= pools
    assert mem.temp_size_in_bytes < limit, mem.temp_size_in_bytes >> 20


def _brumby_stage(arr):
    """Spec, stacked weight tree (shapes only) and pools of Brumby-14B-Base as
    the benchmark's configuration runs it: published layers 0-4 at published
    widths with the whole 151,936-row vocabulary (5.975 GiB of weights), the
    state pool of 36 + 1 slots over the five power-retention layers (1,032 x
    8,320 float32 a layer: 5.92 GiB) with a tail pool of zero size, and a
    page pool that is its scratch page."""
    from deepspeed_tpu.inference.v2 import adapters
    from deepspeed_tpu.inference.v2.ragged.state_pool import (StatefulKV,
                                                              StatePoolConfig)
    from deepspeed_tpu.models.brumby import BrumbyConfig, BrumbyForCausalLM
    cfg = BrumbyConfig.brumby_14b_base(num_hidden_layers=5, dtype=BF16)
    model = BrumbyForCausalLM(cfg)
    shapes = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, 8), I32))["params"],
        jax.random.PRNGKey(0))
    held = {}

    def adapt(p):
        held["spec"], w = adapters.adapt_brumby(p, cfg)
        return w

    weights = jax.tree_util.tree_map(
        lambda a: arr(BF16, *a.shape), jax.eval_shape(adapt, shapes))
    spec = held["spec"]
    spec.dtype = BF16
    m = spec.mamba
    pool = StatePoolConfig(num_layers=5, num_slots=36, d_inner=m["d_inner"],
                           d_state=m["d_state"], d_conv=m["d_conv"])
    ssm_shape, conv_shape = jax.eval_shape(pool.zeros)
    kv = StatefulKV(arr(BF16, 1, 1, 2, 8, BS, 128),
                    arr(F32, *ssm_shape.shape), arr(F32, *conv_shape.shape))
    return spec, weights, kv


def test_brumby_decode_step_keeps_the_states_in_place_and_no_expansion(
        compiled_step, monkeypatch):
    """Brumby-14B-Base's first 5 layers at published widths, the 32-row
    decode step of the benchmark's cell: the 5.92 GiB state pool is the
    output's buffer (aliased through ``pr_decode_step``, no copy of it), the
    temporaries stay far under the file's 1 GiB of headroom, and the
    expansion of a key or a query is no array in HBM — the only arrays that
    wide are the pool itself and its flat view."""
    from deepspeed_tpu.inference.v2 import model_spec as ms
    monkeypatch.setattr(_backend, "interpret", lambda: False)
    compiled, spec, kv = compiled_step("brumby")
    assert ms.num_page_layers(spec) == 0 and ms.num_state_layers(spec) == 5
    assert kv.ssm.shape == (5, 37, 1032, 8320) and kv.conv.size == 0
    text = compiled.as_text()
    assert "pr_decode_step" in text and "paged_kv_row_write" not in text
    mem = compiled.memory_analysis()
    pools = sum(math.prod(a.shape) * jnp.dtype(a.dtype).itemsize for a in kv)
    assert pools > 5.9 * 2 ** 30 and mem.alias_size_in_bytes >= pools
    assert mem.temp_size_in_bytes < 256 << 20, mem.temp_size_in_bytes >> 20
    wide = set(re.findall(r"\b(?:f32|bf16)\[([\d,]*8320)\]", text))
    assert wide == {"5,37,1032,8320", "185,1032,8320"}, wide


def _glm5_stage(arr, pages=6436):
    """Spec, stacked weight trees (shapes only) and the two pools of GLM-5 as
    the benchmark's configuration runs it: 5 layers (one dense, four MoE with
    experts 0-15 of 256 held) at published widths, an eighth of the
    vocabulary (7.28 GiB of weights), ``pages`` pages of latent rows and of
    index keys (5.89 GiB)."""
    from deepspeed_tpu.inference.v2 import adapters
    from deepspeed_tpu.models.glm_dsa import GlmDsaConfig, GlmDsaForCausalLM
    cfg = GlmDsaConfig.glm_5(dtype=BF16, experts_held=(0, 16),
                             num_hidden_layers=5, first_k_dense_replace=1,
                             vocab_size=19360)
    model = GlmDsaForCausalLM(cfg)
    shapes = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, 8), I32))["params"],
        jax.random.PRNGKey(0))
    held = {}

    def adapt(p):
        held["spec"], w = adapters.adapt_glm_dsa(p, cfg)
        return w

    weights = jax.tree_util.tree_map(
        lambda a: arr(BF16, *a.shape), jax.eval_shape(adapt, shapes))
    spec = held["spec"]
    spec.dtype = BF16
    return spec, weights, (arr(BF16, 5, pages + 1, BS, 640),
                           arr(BF16, 5, pages + 1, BS, 128))


def test_glm5_decode_step_reads_the_index_pool_and_gathers_its_selection(
        v5e, monkeypatch):
    """GLM-5's 16-row decode step as the benchmark's cell runs it (contexts
    to 34,816: 272-page tables): the selection's three kernels and two row
    writes are in it and the dense latent kernel is not — no program attends
    over all cached tokens of a row; both pools are the output's buffers and
    no instruction copies either; what attention reads of the latent pool is
    a gather of 2,048 rows a sequence; and the temporaries stay far under
    the file's 1 GiB of headroom."""
    from deepspeed_tpu.inference.v2 import model_spec as ms
    monkeypatch.setattr(_backend, "interpret", lambda: False)
    arr = _on(SingleDeviceSharding(v5e[0]))
    spec, weights, kv = _glm5_stage(arr)
    assert [n for _, _, n in ms.layer_runs(spec)] == [1, 4]
    weight_bytes = sum(math.prod(a.shape) * 2
                       for a in jax.tree_util.tree_leaves(weights))
    assert abs(weight_bytes / 2 ** 30 - 7.28) < 0.01
    compiled = _compile_decode_step(spec, weights, kv, 16, 272)
    text = compiled.as_text()
    mosaic = {m.group(1) for m in re.finditer(
        r"^\s*(?:ROOT )?%([A-Za-z_]\w*?)(?:\.\d+)? = .*"
        r'custom_call_target="tpu_custom_call"', text, re.M)}
    # (experts of 6,144 x 2,048 take XLA's ragged product, not the Pallas
    # grouped one: ``ragged_model.moe_grouped_kernel``'s rule)
    assert mosaic == {"dsa_index_decode", "dsa_select", "dsa_attend_decode",
                      "mla_row_write"}, mosaic
    pools = sum(math.prod(a.shape) * 2 for a in kv)
    mem = compiled.memory_analysis()
    assert pools > 5.88 * 2 ** 30 and mem.alias_size_in_bytes >= pools
    assert mem.temp_size_in_bytes < 256 << 20, mem.temp_size_in_bytes >> 20
    instructions, _ = _executed(text)
    shapes = {a.shape for a in kv} | {
        (5 * 6437,) + a.shape[2:] for a in kv} | {
        (5 * 6437 * 128, a.shape[3]) for a in kv}
    moved = [line.strip()[:120] for _, dims, op, line in instructions
             if dims in shapes and op not in _FREE + (
                 "fusion", "custom-call", "while", "tuple", "gather")]
    assert not moved, f"a pool is copied or laid out anew: {moved}"
    # (the gather sits inside a fusion: read the whole text for it)
    gathers = set(re.findall(r"= bf16\[([\d,]+,640)\]\S* gather\(", text))
    assert gathers == {"16,2048,640"}, gathers


def test_glm5_paged_pass_holds_both_chunk_kernels_and_fits(v5e, monkeypatch):
    """GLM-5's paged pass as the benchmark's cell runs it (8 slots of 256, 16
    decode rows, 272-page tables): a chunk's rows attend under the
    selection's mask by ``dsa_attend_expanded`` where the pass is one
    sequence's and by ``dsa_attend_chunk`` where it is not — both in the
    program, under a conditional — both pools are the output's buffers, and
    the temporaries (784.5 MiB before the expanded branch came, 764.5 with
    it: compile, PR 58; 764.9 since ``dsa_select`` hands its walks back and
    the layer loop carries their sum: compile, PR 60) stay under the
    configuration's 1 GiB of headroom and the 1.57 GiB its fill leaves
    beside it: ``dsa_select``'s block of 128 rows and its group maxima are
    VMEM, and nothing of the selection is a second copy of the scores."""
    from deepspeed_tpu.inference.v2 import ragged_model as rm
    from deepspeed_tpu.inference.v2.ragged.ragged_batch import RaggedBatch
    monkeypatch.setattr(_backend, "interpret", lambda: False)
    arr = _on(SingleDeviceSharding(v5e[0]))
    spec, weights, kv = _glm5_stage(arr)
    host = RaggedBatch(num_slots=8, slot_size=256, max_sequences=16,
                       max_blocks=272).device_arrays()
    batch = {k: arr(I32, *host[k].shape) for k in rm.PAGED_PASS_KEYS}
    compiled = jax.jit(rm.build_ragged_forward(spec), donate_argnums=(1,)
                       ).lower(weights, kv, batch).compile()
    text = compiled.as_text()
    mosaic = {m.group(1) for m in re.finditer(
        r"^\s*(?:ROOT )?%([A-Za-z_]\w*?)(?:\.\d+)? = .*"
        r'custom_call_target="tpu_custom_call"', text, re.M)}
    assert mosaic == {"dsa_index_chunk", "dsa_index_decode", "dsa_select",
                      "dsa_attend_chunk", "dsa_attend_expanded",
                      "dsa_attend_decode"}, mosaic
    assert " conditional(" in text
    pools = sum(math.prod(a.shape) * 2 for a in kv)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pools
    print(f"glm5 paged pass: temporaries {mem.temp_size_in_bytes / 2**20:.1f}"
          " MiB")
    assert mem.temp_size_in_bytes < 766 << 20, mem.temp_size_in_bytes >> 20


def _sdar_stage(arr, pages=3448):
    """Spec, stacked weight tree (shapes only) and the page pool of
    SDAR-30B-A3B as the benchmark's configuration runs it: 6 of 48 layers at
    published widths, all 128 experts of 768, the whole 151,936-row
    vocabulary untied (8.12 GiB of weights), ``pages`` pages of 128 tokens
    (5.05 GiB)."""
    from deepspeed_tpu.inference.v2 import adapters
    from deepspeed_tpu.models.sdar import SdarMoeConfig, SdarMoeForCausalLM
    cfg = SdarMoeConfig.sdar_30b_a3b(dtype=BF16, num_hidden_layers=6)
    model = SdarMoeForCausalLM(cfg)
    shapes = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, 8), I32))["params"],
        jax.random.PRNGKey(0))
    held = {}

    def adapt(p):
        held["spec"], w = adapters.adapt_sdar(p, cfg)
        return w

    weights = jax.tree_util.tree_map(
        lambda a: arr(BF16, *a.shape), jax.eval_shape(adapt, shapes))
    spec = held["spec"]
    spec.dtype = BF16
    return spec, weights, arr(BF16, 6, pages + 1, 2, 4, BS, 128)


def test_sdar_block_step_holds_the_chunk_kernel_and_the_pool_in_place(
        v5e, monkeypatch):
    """SDAR's block step as the benchmark's cell runs it (128 rows of 4
    block rows, 80-page tables): the attention is the batched chunk kernel
    under the block rule and the experts' products the Pallas grouped kernel
    (128 experts of 2048 x 768: ``moe_grouped_kernel``'s small-expert case,
    fed by 512 rows x 8 choices); the pool is the output's buffer, and the
    temporaries — the float32 logits of 512 rows x 151,936 apart, which are
    an output — stay far under the configuration's 1 GiB of headroom."""
    from deepspeed_tpu.inference.v2 import ragged_model as rm
    monkeypatch.setattr(_backend, "interpret", lambda: False)
    arr = _on(SingleDeviceSharding(v5e[0]))
    spec, weights, kv = _sdar_stage(arr)
    assert (spec.causal_block, spec.mask_token_id) == (4, 151669)
    weight_bytes = sum(math.prod(a.shape) * 2
                       for a in jax.tree_util.tree_leaves(weights))
    assert weight_bytes == 8722111488
    S, MB, B = 128, 80, 4
    compiled = jax.jit(rm.build_block_step(spec), donate_argnums=(1,)).lower(
        weights, kv, arr(I32, S, B), arr(I32, S, B), arr(I32, S),
        arr(I32, S), arr(I32, S, MB), arr(I32, S), arr(F32)).compile()
    text = compiled.as_text()
    mosaic = {m.group(1) for m in re.finditer(
        r"^\s*(?:ROOT )?%([A-Za-z_]\w*?)(?:\.\d+)? = .*"
        r'custom_call_target="tpu_custom_call"', text, re.M)}
    # (and the blocks' rows go to the pages as runs of 4: PR 62)
    assert mosaic == {"paged_chunk", "moe_grouped_matmul",
                      "paged_kv_run_write"}, mosaic
    assert not [line for line in text.splitlines()
                if " scatter(" in line and "kv_write" in line], \
        "a row scatter is back in the block step's write"
    assert not _copies_of_the_pool(text, math.prod(kv.shape))
    pool = math.prod(kv.shape) * 2
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool > 5.0 * 2 ** 30
    logits = S * B * 151936 * 4
    assert mem.output_size_in_bytes - pool < logits + (1 << 20)
    print(f"sdar block step: temporaries {mem.temp_size_in_bytes / 2**20:.1f}"
          " MiB")
    assert mem.temp_size_in_bytes < 320 << 20, mem.temp_size_in_bytes >> 20
