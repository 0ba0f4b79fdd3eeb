"""One attention-kernel interface for the v2 serving stack.

``AttentionKernelSpec`` is the single dispatch surface every v2 device
program routes its attention through — the ragged paged pass, the packed
prefill fast path, the fused decode step, and the
speculative verify step (``ragged_model.py`` builders). Before it existed,
each builder picked kernels per call site (window/alibi partials, TP
shard_map wrapping, int8-scale keyword plumbing) and the engine carried one
build-time refusal per (feature x feature) pair that had never been wired;
composing a new pool layout meant touching every site. Now:

- **trace-time dispatch** keys on the pool's dtype at the call: every method
  takes ``kv_scales=None`` — ``None`` is a bf16/f32 pool, a scale-tile array
  is an int8 pool and the method routes to the kernel's dequantizing
  variant. Sliding window and ALiBi are bound once at construction.
- **build-time capability** lives in ONE table
  (:meth:`validate_engine_build`): the engine asks it instead of scattering
  refusals, so what composes (int8 x prefix cache, int8 x spec decode,
  int8 x page fabric) and what does not (int8 x tensor parallel,
  spec x a page ring, i.e. a window in every layer) is decided — and tested — in one place
  (tests/unit/test_kv_quant_stack.py pins the surviving refusal messages).

int8 write semantics (the invariant the byte gates rest on): quantize-on-
write is the semantic boundary — every program attends a token through the
value its int8 page stores. Paths that write-then-attend (ragged pass
decode rows, spec verify) get this for free; fused paths that attend the
current token from registers or the side slab pass new K/V through
``kv_write_dequant`` first (``ops/pallas/paged_attention.py``), so all
paths agree on the attended VALUES and differ only at cross-kernel
float-association noise (~1e-7 — the same level the fp16 byte-stream gates
already tolerate between the chunk/decode/sidebuf kernels).
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax

from deepspeed_tpu.ops.pallas.flash_attention import flash_attention_packed
from deepspeed_tpu.ops.pallas.mla_attention import mla_paged_attention
from deepspeed_tpu.ops.pallas.paged_attention import (
    paged_chunk_attention_batched, paged_decode_attention,
    paged_decode_attention_sidebuf, paged_decode_attention_step)
from deepspeed_tpu.ops.pallas.paged_splitk import (
    paged_chunk_attention_splitk, paged_decode_attention_splitk,
    paged_decode_attention_splitk_step, paged_sidebuf_attention_splitk)

_QUANT_TP_MSG = "int8 KV pages + TP not wired"
_SPLIT_TP_MSG = ("attention.decode_splits > 1 with tensor_parallel > 1 is "
                 "not wired (the split-K LSE merge would land outside the "
                 "shard_map body)")


#: one reason for every refusal beside latent pages (``spec.mla``)
LATENT_PAGES_MSG = ("{what} is not wired for a model with latent attention "
                    "(MLA): its pages hold one latent row a token a layer, "
                    "not keys and values per head (docs/SERVING.md \"Latent "
                    "pages\")")


#: .. and beside an index-key pool (``spec.mla["index"]``: a learned
#: selection inside latent attention)
INDEX_POOL_MSG = ("{what} is not wired for a model that selects inside "
                  "latent attention: a token's index key lies in a second "
                  "pool under the same page ids, and would have to move "
                  "with its latent row (docs/SERVING.md \"A selection over "
                  "them\")")


#: what every feature that needs a copy of a sequence's recurrent state at
#: some earlier position is refused with (docs/SERVING.md "State-space layers")
STATE_SNAPSHOT_MSG = (
    "{what} is not wired for a model with state-space (Mamba) layers: a "
    "layer's recurrent state is one fixed-size value per sequence that every "
    "token overwrites, so pages of an earlier position have no state to go "
    "with them — it takes a snapshot of the state at a block boundary, which "
    "no program writes")


#: .. and beside generation by diffusion over blocks (``spec.causal_block``
#: > 1: attention causal by blocks, a block denoised in place over the pages)
BLOCK_DIFFUSION_MSG = (
    "{what} is not wired for a model that generates by diffusion over "
    "blocks (causal_block > 1): a block's positions are written to the "
    "pages several times before their tokens are final, and only the chunk "
    "kernel knows the block rule (docs/SERVING.md \"Block-diffusion "
    "generation\")")


class AttentionKernelSpec:
    """Kernel dispatch for one model spec on one mesh.

    Construction binds the per-model statics (window, alibi, tp, mesh);
    each method is called inside a traced program with the per-layer pool
    view and routes to the right kernel variant. TP wrapping (shard_map on
    the 'tensor' axis) is applied here — one helper, identical in_specs per
    kernel shape — so no builder carries its own wrapping."""

    def __init__(self, spec: Any, mesh=None, tp: int = 1, n_splits: int = 1):
        self.spec = spec
        self.mesh = mesh
        self.tp = int(tp)
        self.n_splits = int(n_splits)
        # the model's own softmax scale (granite) is the kernels' static
        # argument; None or the neutral value leaves them their default,
        # head_dim ** -0.5
        attn_scale = getattr(spec, "attn_scale", None)
        scale = {} if attn_scale in (None, spec.head_dim ** -0.5) else {
            "softmax_scale": float(attn_scale)}
        if self.n_splits > 1:
            # flash-decoding rung: every paged caller routes through the
            # split-K dispatchers so decode, fused step, sidebuf and spec
            # verify all ride the same ladder rung (ONE compiled program
            # per rung). tp > 1 keeps the chunk-serial path — refused at
            # build time by validate_engine_build.
            assert self.tp == 1, _SPLIT_TP_MSG
            ns = self.n_splits
            self._decode = functools.partial(
                paged_decode_attention_splitk, window=spec.window,
                alibi=spec.alibi, n_splits=ns, **scale)
            self._chunk = functools.partial(
                paged_chunk_attention_splitk, window=spec.window,
                alibi=spec.alibi, n_splits=ns, **scale)
            self._step = functools.partial(
                paged_decode_attention_splitk_step, window=spec.window,
                alibi=spec.alibi, n_splits=ns, **scale)
            self._sidebuf = functools.partial(
                paged_sidebuf_attention_splitk, window=spec.window,
                alibi=spec.alibi, n_splits=ns, **scale)
        else:
            self._decode = functools.partial(
                paged_decode_attention, window=spec.window, alibi=spec.alibi,
                **scale)
            # (the block rule of a model that generates by diffusion over
            # blocks; 1 for every other)
            self._chunk = functools.partial(
                paged_chunk_attention_batched, window=spec.window,
                alibi=spec.alibi, **scale,
                causal_block=getattr(spec, "causal_block", 1))
            self._step = functools.partial(
                paged_decode_attention_step, window=spec.window,
                alibi=spec.alibi, **scale)
            self._sidebuf = functools.partial(
                paged_decode_attention_sidebuf, window=spec.window,
                alibi=spec.alibi, **scale)
        self._packed = functools.partial(flash_attention_packed,
                                         window=spec.window, **scale)
        mla = getattr(spec, "mla", None)
        if mla is not None:
            # latent pages (ragged_mla.py): one kernel for every program
            # that reads the pool; the scale is the expanded form's, of the
            # whole q/k width
            self._latent = functools.partial(
                mla_paged_attention, heads=spec.num_heads,
                v_dim=mla["kv_lora_rank"],
                softmax_scale=(mla["qk_nope_head_dim"]
                               + mla["qk_rope_head_dim"]) ** -0.5)

    # ------------------------------------------------------------------ #
    # build-time capability surface
    # ------------------------------------------------------------------ #

    @staticmethod
    def validate_engine_build(spec: Any, cfg: Any) -> None:
        """THE build-time capability table for the v2 engine: raises the
        canonical refusal for every (feature x feature) pair the kernel
        surface cannot carry, in one place. ``spec`` is the adapted
        :class:`~deepspeed_tpu.inference.v2.model_spec.RaggedModelSpec``,
        ``cfg`` the :class:`RaggedInferenceEngineConfig`. What is absent
        here COMPOSES: int8 KV pages run under the prefix cache, spec
        decode, preempt-offload and the cross-engine page fabric (the PR
        that collapsed those three former refusals into this table)."""
        tp = cfg.tensor_parallel
        if getattr(spec, "causal_block", 1) > 1:
            B = spec.causal_block
            if B & (B - 1):
                raise ValueError(
                    f"causal_block={B} is not a power of two: a row's causal "
                    "limit is q_pos | (B - 1) in the chunk kernel")
            sm = cfg.state_manager
            if sm.chunk_slot_size % B or cfg.kv_cache.block_size % B:
                raise ValueError(
                    f"chunk slot size {sm.chunk_slot_size} and page size "
                    f"{cfg.kv_cache.block_size} must be multiples of "
                    f"causal_block={B}: a block split over two chunk slots "
                    "or two pages' walks could not see its later half")
            attn = getattr(cfg, "attention", None)
            refused = {
                "spec_decode.enabled (a verify step's k + 1 rows are causal "
                "by position)": cfg.spec_decode.enabled,
                "prefix_cache.enabled (a cached page would have to end on a "
                "committed block)": cfg.prefix_cache.enabled,
                "kv_quant.enabled (a denoise pass's rows would be quantized "
                "and re-quantized in place)": cfg.kv_quant.enabled,
                "a sliding window (the page ring aliases the block's write "
                "span)": spec.window is not None
                or bool(spec.layer_kinds),
                "tensor_parallel > 1 (the block step runs outside any "
                "shard_map)": tp > 1,
                "lora.enabled (the block step takes no adapter operands)":
                    cfg.lora.enabled,
                "attention.decode_splits > 1 (the split-K chunk kernel is "
                "causal by position)":
                    attn is not None and attn.decode_splits > 1,
            }   # (serving.preemption is the frontend's to refuse, export_kv
            #      / import_kv the engine's)
            for what, on in refused.items():
                if on:
                    raise NotImplementedError(BLOCK_DIFFUSION_MSG.format(
                        what=what))
            if spec.mla is not None or spec.mamba is not None \
                    or spec.cca is not None or spec.alibi:
                raise NotImplementedError(BLOCK_DIFFUSION_MSG.format(
                    what="latent pages, a recurrent state, a convolution "
                    "tail or ALiBi"))
        # latent pages (multi-head latent attention): one row a token a
        # layer with no head axis. What reads or moves pages by the K/V
        # pair's shape, or shards them by heads, cannot carry them yet
        if getattr(spec, "mla", None) is not None:
            attn = getattr(cfg, "attention", None)
            refused = {
                "kv_quant.enabled (int8 pages keep one scale a token-head "
                "and need head_dim % 128 == 0; a latent row has no heads "
                "and its parts, the latent and the rotary key, would need "
                "a scale each)": cfg.kv_quant.enabled,
                "tensor_parallel > 1 (the pages have no head axis to shard "
                "and the latent kernel runs outside any shard_map)": tp > 1,
                "attention.decode_splits > 1 (the split-K rungs are the K/V "
                "kernels')": attn is not None and attn.decode_splits > 1,
                "lora.enabled (adapters target q/k/v/o projections this "
                "attention does not have)": cfg.lora.enabled,
                "quantization.weight_bits (the latent projections are read "
                "as plain arrays)":
                    cfg.quantization.weight_bits in (4, 8),
            }
            for what, on in refused.items():
                if on:
                    raise NotImplementedError(LATENT_PAGES_MSG.format(
                        what=what))
            if "index" in spec.mla:
                refused = {
                    "prefix_cache.enabled (a copied page would need its "
                    "index keys copied too)": cfg.prefix_cache.enabled,
                    "spec_decode.enabled (the verify step's k + 1 rows a "
                    "sequence would each need a selection of their own; no "
                    "dense attention stands in)": cfg.spec_decode.enabled,
                }   # (serving.preemption: offload is the frontend's to
                #      refuse, export_kv / import_kv the engine's)
                for what, on in refused.items():
                    if on:
                        raise NotImplementedError(INDEX_POOL_MSG.format(
                            what=what))
        if tp > 1 and (spec.num_heads % tp or spec.num_kv_heads % tp):
            raise ValueError(
                f"tensor_parallel={tp} does not divide num_heads="
                f"{spec.num_heads} and num_kv_heads={spec.num_kv_heads}: "
                "the paged kernels shard whole heads over the 'tensor' "
                "axis, and the engine does not fall back to tp=1 — pick a "
                "tensor_parallel that divides both")
        if cfg.kv_quant.enabled:
            if cfg.tensor_parallel > 1:
                raise NotImplementedError(
                    "kv_quant with tensor_parallel > 1 is not wired")
            if (spec.head_dim % 128 != 0
                    or (spec.num_kv_heads * cfg.kv_cache.block_size)
                    % 128 != 0):
                raise ValueError(
                    "kv_quant needs head_dim % 128 == 0 and "
                    "num_kv_heads * block_size % 128 == 0 (the kernels' "
                    "scale-tile lane alignment; got head_dim="
                    f"{spec.head_dim}, num_kv_heads={spec.num_kv_heads}, "
                    f"block_size={cfg.kv_cache.block_size})")
        attn = getattr(cfg, "attention", None)
        if attn is not None and attn.decode_splits > 1:
            if cfg.tensor_parallel > 1:
                raise NotImplementedError(_SPLIT_TP_MSG)
            # everything else composes: sliding window / ALiBi mask inside
            # each split, int8 dequant per gathered page, spec verify rides
            # the chunk dispatcher, small head dims take the XLA scan
        # the two window refusals are the page ring's, and the ring engages
        # only where EVERY layer is windowed (``spec.window``, one kind). A
        # model that mixes windowed and full layers (``spec.layer_kinds``)
        # keeps whole-context pages in every layer, has no ring, and
        # composes with both
        # a model with state-space (Mamba) layers keeps ONE recurrent state a
        # sequence, which every token overwrites: whatever hands a sequence
        # pages of an earlier position, or moves its pages without its
        # state, would need a snapshot of the state at a block boundary,
        # which no program writes (docs/SERVING.md "State-space layers")
        # .. and so does a layer that keeps a convolution tail beside its
        # pages (``spec.cca``): pages of an earlier position have no tail
        if getattr(spec, "mamba", None) is not None \
                or getattr(spec, "cca", None) is not None:
            refused = {
                "prefix_cache.enabled (a cached prefix's pages carry no "
                "state for the layers that do not attend)":
                    cfg.prefix_cache.enabled,
                "spec_decode.enabled (rejected drafts have already advanced "
                "the state; rolling back needs the state before them)":
                    cfg.spec_decode.enabled,
            }   # (serving.preemption: offload is the frontend's to refuse)
            for what, on in refused.items():
                if on:
                    raise NotImplementedError(
                        STATE_SNAPSHOT_MSG.format(what=what))
            if cfg.lora.enabled or tp > 1:
                raise NotImplementedError(
                    "multi-tenant LoRA and tensor_parallel > 1 are not wired "
                    "for a model with state-space (Mamba) layers: the fused "
                    "decode programs hand the rows' state slots where the "
                    "adapter operands go, and the state kernels run outside "
                    "any shard_map")
        if cfg.prefix_cache.enabled and spec.window is not None:
            raise NotImplementedError(
                "prefix_cache with a sliding-window model is not wired: "
                f"every layer is windowed ({spec.window} tokens), so the "
                "page ring overwrites pages in place, which would rot "
                "cached content under a live sharer")
        if cfg.spec_decode.enabled and spec.window is not None:
            raise NotImplementedError(
                "spec_decode with a sliding-window model is not wired "
                "(the page ring aliases the verify step's k+1-ahead "
                f"write span): every layer is windowed ({spec.window} "
                "tokens), so the ring is on")

    # ------------------------------------------------------------------ #
    # trace-time dispatch (called inside jitted programs)
    # ------------------------------------------------------------------ #

    def _tp_wrap(self, fn, in_specs, out_specs):
        return jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)

    def decode(self, q, kv_l, block_tables, ctx_lens,
               kv_scales: Optional[Any] = None):
        """Single-token-per-sequence decode attention (one ctx-bounded
        query row per sequence) over the per-layer pool view ``kv_l``
        ([L*NB, 2, Hkv, bs, D]; block tables pre-offset by l*NB)."""
        if self.tp > 1:
            assert kv_scales is None, _QUANT_TP_MSG
            from jax.sharding import PartitionSpec as P
            from deepspeed_tpu.comm.mesh import TENSOR_AXIS
            fn = self._tp_wrap(
                self._decode,
                in_specs=(P(None, TENSOR_AXIS, None),
                          P(None, None, TENSOR_AXIS, None, None),
                          P(None, None), P(None)),
                out_specs=P(None, TENSOR_AXIS, None))
            return fn(q, kv_l, block_tables, ctx_lens)
        if kv_scales is not None:
            return self._decode(q, kv_l, block_tables, ctx_lens,
                                kv_scales=kv_scales)
        return self._decode(q, kv_l, block_tables, ctx_lens)

    def chunk(self, q, kv_l, block_tables, q_starts, ctx_lens,
              kv_scales: Optional[Any] = None):
        """Batched prompt-chunk (and spec-verify) flash attention: one slot
        per chunk, causal by absolute position."""
        if self.tp > 1:
            assert kv_scales is None, _QUANT_TP_MSG
            from jax.sharding import PartitionSpec as P
            from deepspeed_tpu.comm.mesh import TENSOR_AXIS
            fn = self._tp_wrap(
                self._chunk,
                in_specs=(P(None, None, TENSOR_AXIS, None),
                          P(None, None, TENSOR_AXIS, None, None),
                          P(None, None), P(None), P(None)),
                out_specs=P(None, None, TENSOR_AXIS, None))
            return fn(q, kv_l, block_tables, q_starts, ctx_lens)
        if kv_scales is not None:
            return self._chunk(q, kv_l, block_tables, q_starts, ctx_lens,
                               kv_scales=kv_scales)
        return self._chunk(q, kv_l, block_tables, q_starts, ctx_lens)

    def decode_step(self, q, k_new, v_new, kv_l, block_tables, ctx_lens,
                    kv_scales: Optional[Any] = None):
        """Fused write+attend decode step (pool aliased through the kernel;
        new rows scattered after). Returns ``(out, kv_l)`` — with scales,
        ``(out, kv_l, kv_scales)``. For int8 pools pass ``k_new/v_new``
        through ``kv_write_dequant`` first (module docstring)."""
        if self.tp > 1:
            assert kv_scales is None, _QUANT_TP_MSG
            from jax.sharding import PartitionSpec as P
            from deepspeed_tpu.comm.mesh import TENSOR_AXIS
            fn = self._tp_wrap(
                self._step,
                in_specs=(P(None, TENSOR_AXIS, None),
                          P(None, TENSOR_AXIS, None),
                          P(None, TENSOR_AXIS, None),
                          P(None, None, TENSOR_AXIS, None, None),
                          P(None, None), P(None)),
                out_specs=(P(None, TENSOR_AXIS, None),
                           P(None, None, TENSOR_AXIS, None, None)))
            return fn(q, k_new, v_new, kv_l, block_tables, ctx_lens)
        if kv_scales is not None:
            return self._step(q, k_new, v_new, kv_l, block_tables, ctx_lens,
                              kv_scales=kv_scales)
        return self._step(q, k_new, v_new, kv_l, block_tables, ctx_lens)

    def sidebuf(self, q, kv_l, block_tables, prefix_lens, side_k, side_v, j,
                layer_idx, kv_scales: Optional[Any] = None):
        """Frozen-prefix + side-slab decode attention (the decode step's
        side-buffer form). Only reachable at tp == 1
        (``ragged_model.side_buffer_fits``), so no TP wrap. For int8 pools the
        slab must hold ``kv_write_dequant``'d rows (module docstring)."""
        assert self.tp == 1, "side-buffer schedule is tp == 1 only"
        kw = {} if kv_scales is None else dict(kv_scales=kv_scales)
        return self._sidebuf(q, kv_l, block_tables, prefix_lens,
                             side_k, side_v, j, layer_idx=layer_idx, **kw)

    def latent(self, q, pages, block_tables, q_pos0, ctx_lens, side=None,
               side_j=None, layer_idx=None):
        """Absorbed-form attention over latent pages
        (``ops/pallas/mla_attention.py``): ``q`` ``[N, rows, W]`` already in
        the rows' space, ``pages`` ``[L*NB, bs, W]`` with block tables
        pre-offset by ``l*NB``; decode rows may bring the fused schedule's
        side slab. tp == 1 only (refused at build)."""
        return self._latent(q, pages, block_tables, q_pos0, ctx_lens,
                            side=side, side_j=side_j, layer_idx=layer_idx)

    def packed(self, q, k, v, seg):
        """Packed segment-masked prefill flash (no paged reads — the
        prefill-from-zero fast path)."""
        if self.tp > 1:
            from jax.sharding import PartitionSpec as P
            from deepspeed_tpu.comm.mesh import TENSOR_AXIS
            fn = self._tp_wrap(
                self._packed,
                in_specs=(P(None, TENSOR_AXIS, None),
                          P(None, TENSOR_AXIS, None),
                          P(None, TENSOR_AXIS, None), P(None)),
                out_specs=P(None, TENSOR_AXIS, None))
            return fn(q, k, v, seg)
        return self._packed(q, k, v, seg)
