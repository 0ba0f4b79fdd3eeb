"""The serving programs of a model with multi-head latent attention (MLA).

``ragged_model.py``'s builders hand a spec with ``spec.mla`` to the ones
here. The layer body is theirs (``_transformer_layer``: its MLA half
projects ``q_nope``, ``q_rope`` and the rows' latent rows, then calls the
program's ``attend``); what differs is the pool and the form of attention.

The pool is ``[L, NB, bs, W]`` (``ragged/kv_cache.py``): one latent row a
token a layer — ``c_kv`` after its norm, the shared rotary key after
rotation, zeros up to the lane tile — with no head axis and no K/V pair.
Attention is one function in two forms that agree through that pool:

- EXPANDED (:func:`mla_expanded`; scope ``attn/mla/prefill``): keys and
  values of every head are made from the rows' latents (``W_UK``, ``W_UV``)
  and attended by the packed flash kernel at q/k width ``nope + rope`` and v
  width ``v_head_dim``. The packed prefill pass uses it: all a row can see
  was computed in the pass, and per (query, key, head) it costs
  ``2 (nope + rope + v)`` operations.
- ABSORBED (:func:`mla_absorb_q` / :func:`mla_absorb_o`; scope
  ``attn/mla/absorb`` around the two products, ``attn/mla/decode`` and
  ``attn/mla/prefill`` around the kernel): ``W_UK`` goes into the queries and
  ``W_UV`` onto the output, and the kernel
  (``ops/pallas/mla_attention.py``) reads latent pages as they lie, each
  once, as keys and as values: ``2 (W + kv_lora_rank)`` operations per
  (query, key, head) but ``W`` values read per key for all heads. Every
  program that reads the pool uses it: decode rows (ragged pass, fused
  decode step), the verify step, and a paged chunk's rows — for
  the chunk's EARLIER context and, after its rows are written, for its own
  rows too (docs/SERVING.md "Latent pages" has the count against expanding
  the cached latents).

New rows reach the pool as K/V rows do: a flat scatter in the ragged pass and
the verify step, whole pages in the packed pass, and in the fused decode
step through a side buffer ``[L, S, 8, W]`` that the kernel attends beside
the frozen pages and one row write (scope ``kv_flush``) puts into the pool
after the layers.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2.attention import AttentionKernelSpec
from deepspeed_tpu.inference.v2.ragged_model import (
    RaggedModelSpec, _embed_in, _greedy_accept, _layer_dest, _norm,
    _pass_rows_live, _router_stream, _sample_logits, _scan_layers,
    _stream_out, _stream_turns, _transformer_layer, _unembed)
from deepspeed_tpu.ops.pallas.mla_attention import mla_row_write


def mla_expanded(spec: RaggedModelSpec, ak: AttentionKernelSpec, w, q_nope,
                 q_rope, lat, seg):
    """Expanded form over packed rows: ``[N, H * v]``."""
    R, dr = spec.mla["kv_lora_rank"], spec.mla["qk_rope_head_dim"]
    N, H = q_nope.shape[:2]
    ckv = lat[:, :R]
    k_nope = jnp.einsum("tr,hrd->thd", ckv, w["w_uk"])
    v = jnp.einsum("tr,hrd->thd", ckv, w["w_uv"])
    k_rope = jnp.broadcast_to(lat[:, None, R:R + dr], (N, H, dr))
    out = ak.packed(jnp.concatenate([q_nope, q_rope], axis=-1),
                    jnp.concatenate([k_nope, k_rope], axis=-1), v, seg)
    return out.reshape(N, -1)


def mla_absorb_q(spec: RaggedModelSpec, w, q_nope, q_rope, width: int):
    """Queries in the latent rows' space ``[N, H, W]``: ``q_nope W_UK^T``
    beside the rotated part, zeros where the rows hold zeros."""
    q_lat = jnp.einsum("thd,hrd->thr", q_nope, w["w_uk"])
    pad = width - q_lat.shape[-1] - q_rope.shape[-1]
    return jnp.concatenate(
        [q_lat, q_rope, jnp.zeros(q_rope.shape[:2] + (pad,), q_rope.dtype)],
        axis=-1)


def mla_absorb_o(w, o_lat):
    """Latent outputs ``[N, H, R]`` -> ``[N, H * v]`` through ``W_UV``."""
    return jnp.einsum("thr,hrd->thd", o_lat, w["w_uv"]).reshape(
        o_lat.shape[0], -1)


def _finish(spec, weights, x):
    return _norm(x, weights["final_norm"], spec.norm, spec.eps, spec.dtype,
                 spec.norm_plus_one)


def build_paged_pass(spec: RaggedModelSpec) -> Callable:
    """``build_ragged_forward`` over latent pages: every row's latent is
    scattered into the pool, then chunk slots and decode rows attend
    absorbed, causal by absolute position."""
    H, R = spec.num_heads, spec.mla["kv_lora_rank"]

    def fwd(weights, pool, b):
        NC = b["chunk_ntok"].shape[0]
        CT = b["chunk_tokens"].shape[0]
        Cs = CT // NC
        L, NB, bs, W = pool.shape
        tokens = jnp.concatenate([b["chunk_tokens"], b["decode_tokens"]])
        positions = jnp.concatenate([b["chunk_positions"],
                                     b["decode_positions"]])
        x = _router_stream(
            spec, _embed_in(spec, weights, tokens, positions), weights,
            lambda: _pass_rows_live(b, Cs, True))

        def make_body(rs, experts, l0):
            ak = AttentionKernelSpec(rs)

            def layer_fn(carry, scanned):
                x, flat = carry
                w, l = scanned

                def attend(q_nope, q_rope, lat):
                    dest = _layer_dest(b["kv_dest"], l, NB, bs, L)
                    flat_ = flat.at[dest].set(lat.astype(flat.dtype),
                                              mode="drop")
                    pages = flat_.reshape(L * NB, bs, W)
                    with jax.named_scope("absorb"):
                        q = mla_absorb_q(rs, w, q_nope, q_rope, W)
                    with jax.named_scope("prefill"):
                        o_c = ak.latent(
                            q[:CT].reshape(NC, Cs * H, W), pages,
                            b["chunk_block_tables"] + l * NB, b["chunk_q0"],
                            b["chunk_ctx_lens"])
                    with jax.named_scope("decode"):
                        o_d = ak.latent(
                            q[CT:], pages, b["decode_block_tables"] + l * NB,
                            b["decode_ctx_lens"] - 1, b["decode_ctx_lens"])
                    with jax.named_scope("absorb"):
                        out = mla_absorb_o(w, jnp.concatenate(
                            [o_c.reshape(CT, H, R), o_d], axis=0))
                    return out, flat_

                x, (flat,) = _transformer_layer(rs, w, x, positions, attend,
                                                experts=experts, l=l - l0)
                return (x, flat), None

            return layer_fn

        x, flat = _scan_layers(spec, weights["layers"], make_body,
                               (x, pool.reshape(L * NB * bs, W)))
        turns = _stream_turns(x)
        x = _finish(spec, weights, _stream_out(x))
        last_rows = (jnp.arange(NC) * Cs
                     + jnp.maximum(b["chunk_ntok"] - 1, 0))
        logits = _unembed(spec, weights,
                          jnp.concatenate([x[last_rows], x[CT:]], axis=0))
        return (logits[:NC], logits[NC:], flat.reshape(pool.shape)) + turns

    return fwd


def build_packed_prefill(spec: RaggedModelSpec) -> Callable:
    """``build_prefill_forward`` over latent pages: the expanded form on the
    packed rows, then whole pages of latent rows written by the page plan."""

    def fwd(weights, pool, b):
        NC = b["chunk_ntok"].shape[0]
        CT = b["chunk_tokens"].shape[0]
        Cs = CT // NC
        S = b["decode_tokens"].shape[0]
        L, NB, bs, W = pool.shape
        positions = b["chunk_positions"]
        x = _router_stream(
            spec, _embed_in(spec, weights, b["chunk_tokens"], positions),
            weights, lambda: _pass_rows_live(b, Cs, False))
        # the page plan's windows of rows (RaggedBatch.page_ids/rows/fill)
        j = jnp.arange(bs, dtype=jnp.int32)
        rows = jnp.minimum(b["page_rows"][:, None] + j[None, :], CT - 1)
        valid = (j[None, :] < b["page_fill"][:, None])[..., None]

        def make_body(rs, experts, l0):
            ak = AttentionKernelSpec(rs)

            def layer_fn(carry, scanned):
                x, pages = carry
                w, l = scanned

                def attend(q_nope, q_rope, lat):
                    with jax.named_scope("prefill"):
                        out = mla_expanded(rs, ak, w, q_nope, q_rope, lat,
                                           b["row_seg"])
                    # sentinel pages (id >= NB) go out of range GLOBALLY
                    tgt = jnp.where(b["page_ids"] < NB,
                                    l * NB + b["page_ids"], L * NB)
                    new = jnp.where(valid, lat[rows], 0).astype(pages.dtype)
                    return out, pages.at[tgt].set(new, mode="drop")

                x, (pages,) = _transformer_layer(rs, w, x, positions, attend,
                                                 experts=experts, l=l - l0)
                return (x, pages), None

            return layer_fn

        x, pages = _scan_layers(spec, weights["layers"], make_body,
                                (x, pool.reshape(L * NB, bs, W)))
        turns = _stream_turns(x)
        x = _finish(spec, weights, _stream_out(x))
        last_rows = (jnp.arange(NC) * Cs
                     + jnp.maximum(b["chunk_ntok"] - 1, 0))
        logits = _unembed(spec, weights, x[last_rows])
        return (logits, jnp.zeros((S, logits.shape[1]), logits.dtype),
                pages.reshape(pool.shape)) + turns

    return fwd


def build_decode_step(spec: RaggedModelSpec, do_sample: bool,
                      top_k: int) -> Callable:
    """``ragged_model.build_decode_step``'s side-buffer form over latent
    pages: the pool stays frozen through the layers, each layer's latent row
    goes to a side buffer ``[L, S, 8, W]`` (one sublane tile, row 0 the
    step's) the kernel attends beside the pages, and one row write puts the
    rows into the pool after the layers."""

    def fwd(weights, pool, ids, positions, block_tables, ctx, key,
            temperature=1.0):
        S = ids.shape[0]
        L, NB, bs, W = pool.shape
        pages = pool.reshape(L * NB, bs, W)
        # ctx counts this step's token; the pages hold the prefix
        prefix = jnp.maximum(ctx - 1, 0)
        x = _router_stream(spec, _embed_in(spec, weights, ids, positions),
                           weights)

        def make_body(rs, experts, l0):
            ak = AttentionKernelSpec(rs)

            def layer_fn(carry, scanned):
                x, side = carry
                w, l = scanned

                def attend(q_nope, q_rope, lat):
                    side_ = jax.lax.dynamic_update_slice(
                        side, lat[None, :, None].astype(side.dtype),
                        (l, 0, 0, 0))
                    with jax.named_scope("absorb"):
                        q = mla_absorb_q(rs, w, q_nope, q_rope, W)
                    with jax.named_scope("decode"):
                        o_lat = ak.latent(
                            q, pages, block_tables + l * NB, prefix,
                            prefix, side=side_, side_j=0, layer_idx=l)
                    with jax.named_scope("absorb"):
                        return mla_absorb_o(w, o_lat), side_

                x, (side,) = _transformer_layer(
                    rs, w, x, positions, attend, experts=experts, l=l - l0)
                return (x, side), None

            return layer_fn

        x, side = _scan_layers(spec, weights["layers"], make_body,
                               (x, jnp.zeros((L, S, 8, W), pool.dtype)))
        turns = _stream_turns(x)
        logits = _unembed(spec, weights,
                          _finish(spec, weights, _stream_out(x)))
        # the kernels READ the pool inside the layers; the barrier orders
        # the in-place write after them instead of cloning the pool
        pool, _ = jax.lax.optimization_barrier((pool, logits))
        with jax.named_scope("kv_flush"):
            new_pool = mla_row_write(pool, side, block_tables, prefix, 1)
        nxt = _sample_logits(logits, key, do_sample, top_k, temperature)
        return (nxt, logits, new_pool) + turns

    return fwd


def build_verify(spec: RaggedModelSpec, k: int) -> Callable:
    """``build_verify_step`` over latent pages: all ``k + 1`` rows of a
    sequence are scattered into the pool, then attended absorbed, one slot a
    sequence, causal by absolute position — the visible set of every row is
    what the decode step sees one token at a time."""
    H, R = spec.num_heads, spec.mla["kv_lora_rank"]
    K1 = k + 1

    def fwd(weights, pool, ids, draft, n_draft, positions0, block_tables,
            ctx0):
        S = ids.shape[0]
        L, NB, bs, W = pool.shape
        MB = block_tables.shape[1]
        tokens = jnp.concatenate([ids[:, None], draft], axis=1)
        positions = positions0[:, None] + jnp.arange(K1, dtype=jnp.int32)[None]
        pos_flat = positions.reshape(-1)
        page = jnp.take_along_axis(
            block_tables, jnp.minimum(positions // bs, MB - 1), axis=1)
        dest = (page * bs + positions % bs).reshape(-1)
        x = _embed_in(spec, weights, tokens.reshape(-1), pos_flat)

        def make_body(rs, experts, l0):
            ak = AttentionKernelSpec(rs)

            def layer_fn(carry, scanned):
                x, flat = carry
                w, l = scanned

                def attend(q_nope, q_rope, lat):
                    flat_ = flat.at[_layer_dest(dest, l, NB, bs, L)].set(
                        lat.astype(flat.dtype), mode="drop")
                    with jax.named_scope("absorb"):
                        q = mla_absorb_q(rs, w, q_nope, q_rope, W)
                    with jax.named_scope("decode"):
                        o_lat = ak.latent(
                            q.reshape(S, K1 * H, W),
                            flat_.reshape(L * NB, bs, W),
                            block_tables + l * NB, positions0,
                            ctx0 + (K1 - 1))
                    with jax.named_scope("absorb"):
                        return (mla_absorb_o(w, o_lat.reshape(S * K1, H, R)),
                                flat_)

                x, (flat,) = _transformer_layer(
                    rs, w, x, pos_flat, attend, experts=experts, l=l - l0)
                return (x, flat), None

            return layer_fn

        x, flat = _scan_layers(spec, weights["layers"], make_body,
                               (x, pool.reshape(L * NB * bs, W)))
        logits = _unembed(spec, weights, _finish(spec, weights, x)
                          ).reshape(S, K1, -1)
        return _greedy_accept(logits, draft, n_draft) + (
            flat.reshape(pool.shape),)

    return fwd
