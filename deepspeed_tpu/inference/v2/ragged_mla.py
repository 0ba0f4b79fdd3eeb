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
  (query, key, head) but ``W`` values read per key for all heads. What
  reads the pool uses it: decode rows (ragged pass, fused decode step), the
  verify step, and a paged chunk's rows — for the chunk's EARLIER context
  and, after its rows are written, for its own rows too.
- EXPANDED AGAIN, where it pays: expanding a cached token costs ``2 H R
  (nope + v)`` a layer whoever reads it, and attending it costs a query
  token ``2 H (nope + rope + v)`` expanded against ``2 H (W + R)`` absorbed,
  so ``n`` query tokens of ONE sequence in a pass break even at ``n = R
  (nope + v) / (W + R - nope - rope - v)`` — 359 at GLM-5's widths: one
  slot of 256 loses, two win, a pass of 8 that is one prompt's does 1.85
  times fewer operations (docs/SERVING.md "Latent pages"). A selecting
  model's paged pass takes it for its chunk rows when they are one
  sequence's (:func:`one_sequence`, read from the slots; a ``lax.cond`` a
  layer around :func:`_chunk_expanded`), the expansion inside the kernel.

New rows reach the pool as K/V rows do: a flat scatter in the ragged pass and
the verify step, whole pages in the packed pass, and in the fused decode
step through a side buffer ``[L, S, 8, W]`` that the kernel attends beside
the frozen pages and one row write (scope ``kv_flush``) puts into the pool
after the layers.

A SELECTION over them (``spec.mla["index"]``; GLM-5's DeepSeek Sparse
Attention): the cache is then a pair of pools — the latent pages and
``[L, NB, bs, Di]`` index keys under the same page ids — every program
writes a token's index key wherever it writes its latent row, and every
program that reads the pool attends over what the indexer chose
(``ops/pallas/sparse_mla.py``): a chunk slot under the per-(query, key) mask
of its scores against its thresholds (scopes ``index/score``,
``index/select``, ``prefill``: absorbed, ``dsa_attend_chunk``, or, the pass
one sequence's, expanded, ``dsa_attend_expanded``), a decode row over its
chosen rows gathered
out of the latent pages (``index/score``, ``index/select``,
``index/gather``, ``decode``). The packed pass selects nothing: it cannot
hold more tokens of a sequence than the selection keeps (asserted; an engine
whose pass could takes the paged pass instead). No program, absorbed or
expanded, falls back to attention over all cached tokens.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2.attention import AttentionKernelSpec
from deepspeed_tpu.inference.v2.model_spec import RaggedModelSpec
from deepspeed_tpu.inference.v2.ragged_model import (
    _embed_in, _greedy_accept, _layer_dest, _norm, _pass_rows_live,
    _router_stream, _sample_logits, _scan_layers, _stream_out, _stream_turns,
    _transformer_layer, _unembed)
from deepspeed_tpu.ops.pallas import sparse_mla
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


def _scale(spec: RaggedModelSpec) -> float:
    return (spec.mla["qk_nope_head_dim"]
            + spec.mla["qk_rope_head_dim"]) ** -0.5


def _kept(topk: int, seen):
    """How many positions a query that sees ``seen`` keeps (1 for a pad
    row that sees none: the bisection wants a count)."""
    return jnp.clip(jnp.minimum(seen, topk), 1)


def _walks(walks, ctx):
    """``sparse_mla.select_counted``'s third result as the two counts a
    program hands back (:func:`_select_counts`): ``[the walks of the blocks
    of the slots that hold a token, how many such blocks]`` int32."""
    return jnp.stack([jnp.sum(walks),
                      jnp.sum(ctx > 0) * walks.shape[1]]).astype(jnp.int32)


def _no_walks(pools) -> tuple:
    """What a layer loop carries last beside an index pool: the selections'
    :func:`_walks` so far."""
    return (jnp.zeros((2,), jnp.int32),) if len(pools) > 1 else ()


def _select_counts(pools, carried) -> tuple:
    """What a program with a selection returns after its other results: its
    layers' :func:`_walks`, summed, under the always-on counters they feed
    (``engine_v2._ThreeResults``: a dict names its own)."""
    if len(pools) == 1:
        return ()
    return ({"serve/dsa/select_sweeps": carried[-1][0],
             "serve/dsa/select_blocks": carried[-1][1]},)


def select_chunk(spec: RaggedModelSpec, q_idx, w_idx, ipages, block_tables,
                 q0, ctx):
    """The selection of ``NC`` chunk slots' query tokens: ``(tiled scores,
    thr [NC, Cs], pcut [NC, Cs])`` (``sparse_mla.index_scores``,
    ``select``)."""
    return chunk_selection(spec, q_idx, w_idx, ipages, block_tables, q0,
                           ctx)[:3]


def chunk_selection(spec: RaggedModelSpec, q_idx, w_idx, ipages,
                    block_tables, q0, ctx):
    """:func:`select_chunk` and, fourth, the selection's :func:`_walks`."""
    Cs = q_idx.shape[1]
    with jax.named_scope("index"):
        with jax.named_scope("score"):
            scores = sparse_mla.index_scores(q_idx, w_idx, ipages,
                                             block_tables, q0, ctx)
        with jax.named_scope("select"):
            seen = jnp.minimum(ctx[:, None], q0[:, None] + 1 + jnp.arange(
                Cs, dtype=jnp.int32)[None])
            thr, pcut, walks = sparse_mla.select_counted(
                scores, _kept(spec.mla["index"]["topk"], seen), ctx)
    return scores, thr, pcut, _walks(walks, ctx)


def select_decode(spec: RaggedModelSpec, q_idx, w_idx, k_own, ipages, rows,
                  block_tables, q_pos, ctx):
    """:func:`decode_selection` without its fourth result."""
    return decode_selection(spec, q_idx, w_idx, k_own, ipages, rows,
                            block_tables, q_pos, ctx)[:3]


def decode_selection(spec: RaggedModelSpec, q_idx, w_idx, k_own, ipages,
                     rows, block_tables, q_pos, ctx):
    """The selection of ``S`` decode rows and its latent rows: ``(rows
    [S, topk, W] gathered from ``rows`` [pages * bs, W], how many of them are
    live [S], whether the row's OWN token is chosen [S], the selection's
    :func:`_walks`)``.

    A row at position ``q_pos`` scores the ``ctx`` tokens its pages hold.
    Where the pages hold the row's own token too (``ctx == q_pos + 1``: a
    ragged pass scatters before it attends) ``k_own`` is None; in the fused
    decode step the pages hold the prefix (``ctx == q_pos``), the own token's
    key ``k_own`` [S, Di] is scored here and takes part in the selection like
    any other, and the caller attends its latent row from the side buffer if
    it was chosen."""
    ix = spec.mla["index"]
    topk, bs = ix["topk"], ipages.shape[1]
    MB = block_tables.shape[1]
    own = k_own is not None
    with jax.named_scope("index"):
        with jax.named_scope("score"):
            scores = sparse_mla.index_scores(
                q_idx[:, None], w_idx[:, None], ipages, block_tables, q_pos,
                ctx)                                         # [S, C, 1, T]
            C, T = scores.shape[1], scores.shape[3]
            pos = (jnp.arange(C, dtype=jnp.int32)[:, None, None] * T
                   + jnp.arange(T, dtype=jnp.int32)[None, None, :])[None]
            if own:
                s_own = jnp.einsum("shd,sd->sh", q_idx.astype(jnp.float32),
                                   k_own.astype(jnp.float32),
                                   precision="highest")
                s_own = jnp.sum(jnp.maximum(s_own, 0.0) * w_idx, axis=-1)
                s_own = jnp.where(s_own == 0.0, 0.0, s_own)
                scores = jnp.where(pos == q_pos[:, None, None, None],
                                   s_own[:, None, None, None], scores)
        with jax.named_scope("select"):
            # the rows of a step as the query rows of ONE slot: the sweeps
            # then fill whole registers (a row a sublane, not a row a tile)
            seen = jnp.minimum(ctx, q_pos + 1) + int(own)
            top = jnp.max(ctx, keepdims=True) + int(own)
            thr, pcut, walks = sparse_mla.select_counted(
                scores.transpose(2, 1, 0, 3), _kept(topk, seen)[None], top)
            thr, pcut = thr[0][:, None], pcut[0][:, None]
        with jax.named_scope("gather"):
            keep = sparse_mla.keep_mask(scores, thr, pcut)[:, 0]  # [S, C*T]
            flat_pos = pos.reshape(1, -1)
            own_on = jnp.any(keep & (flat_pos == q_pos[:, None]), axis=-1) \
                if own else jnp.zeros(q_pos.shape, bool)
            keep = keep & (flat_pos < ctx[:, None])
            chosen = sparse_mla.chosen_positions(keep, topk)   # [S, topk]
            live = chosen < C * T
            # a position's page through a one-hot product, exact in float32
            # (XLA's gather of 32k single values took 0.27 ms a layer)
            at = jnp.minimum(chosen // bs, MB - 1)[..., None] == jnp.arange(
                MB, dtype=jnp.int32)
            page = jnp.einsum("skb,sb->sk", at.astype(jnp.float32),
                              block_tables.astype(jnp.float32),
                              precision="highest").astype(jnp.int32)
            got = rows[jnp.where(live, page * bs + chosen % bs, 0)]
    return got, jnp.sum(live, axis=-1, dtype=jnp.int32), \
        own_on.astype(jnp.int32), _walks(walks, top)


def _finish(spec, weights, x):
    return _norm(x, weights["final_norm"], spec.norm, spec.eps, spec.dtype,
                 spec.norm_plus_one)


def _pools(spec: RaggedModelSpec, cache) -> tuple:
    """A program's cache argument as a tuple of pools: the latent pages and,
    where the model selects, the index keys (the cache is then the pair)."""
    return tuple(cache) if "index" in spec.mla else (cache,)


def _cache(spec: RaggedModelSpec, pools):
    """The pools as the cache a program returns (:func:`_pools` undone)."""
    return tuple(pools) if "index" in spec.mla else pools[0]


def build_paged_pass(spec: RaggedModelSpec) -> Callable:
    """``build_ragged_forward`` over latent pages: every row's latent is
    scattered into the pool, then chunk slots and decode rows attend
    absorbed, causal by absolute position. With a selection the rows' index
    keys are scattered too, and every chunk slot and decode row attends over
    what its indexer chose."""
    H, R = spec.num_heads, spec.mla["kv_lora_rank"]

    def fwd(weights, cache, b):
        pools = _pools(spec, cache)
        NC = b["chunk_ntok"].shape[0]
        CT = b["chunk_tokens"].shape[0]
        Cs = CT // NC
        L, NB, bs, W = pools[0].shape
        tokens = jnp.concatenate([b["chunk_tokens"], b["decode_tokens"]])
        positions = jnp.concatenate([b["chunk_positions"],
                                     b["decode_positions"]])
        x = _router_stream(
            spec, _embed_in(spec, weights, tokens, positions), weights,
            lambda: _pass_rows_live(b, Cs, True))
        one_seq = len(pools) > 1 and one_sequence(
            b["chunk_ntok"], b["chunk_q0"], b["chunk_block_tables"], Cs)

        def make_body(rs, experts, l0):
            ak = AttentionKernelSpec(rs)

            def layer_fn(carry, scanned):
                x, *flats = carry
                w, l = scanned

                # ``index``: the indexer's (queries, weights, keys), or none
                def attend(q_nope, q_rope, lat, *index):
                    dest = _layer_dest(b["kv_dest"], l, NB, bs, L)
                    flats_ = tuple(
                        f.at[dest].set(r.astype(f.dtype), mode="drop")
                        for f, r in zip(flats, (lat,) + index[2:]))
                    pages = flats_[0].reshape(L * NB, bs, W)
                    if index:
                        out, walks = selected(q_nope, q_rope, pages, flats_,
                                              *index[:2])
                        return (out,) + flats_ + (flats[-1] + walks,)
                    with jax.named_scope("absorb"):
                        q = mla_absorb_q(rs, w, q_nope, q_rope, W)
                    with jax.named_scope("prefill"):
                        o_c = ak.latent(
                            q[:CT].reshape(NC, Cs * H, W), pages,
                            b["chunk_block_tables"] + l * NB,
                            b["chunk_q0"], b["chunk_ctx_lens"])
                    with jax.named_scope("decode"):
                        o_d = ak.latent(
                            q[CT:], pages,
                            b["decode_block_tables"] + l * NB,
                            b["decode_ctx_lens"] - 1,
                            b["decode_ctx_lens"])
                    with jax.named_scope("absorb"):
                        out = mla_absorb_o(w, jnp.concatenate(
                            [o_c.reshape(CT, H, R), o_d], axis=0))
                    return (out,) + flats_

                def selected(q_nope, q_rope, pages, flats_, q_idx, w_idx):
                    """Every row over what its indexer chose: ``([N, H * v],
                    the two selections' walks)``. The chunk rows of a pass
                    that holds ONE sequence attend expanded
                    (:func:`_chunk_expanded`), else absorbed."""
                    kw = dict(v_dim=R, softmax_scale=_scale(rs))
                    ipages = flats_[1].reshape(L * NB, bs, -1)
                    bt_c = b["chunk_block_tables"] + l * NB
                    *sel, walks_c = chunk_selection(
                        rs, q_idx[:CT].reshape((NC, Cs) + q_idx.shape[1:]),
                        w_idx[:CT].reshape(NC, Cs, -1), ipages, bt_c,
                        b["chunk_q0"], b["chunk_ctx_lens"])

                    def absorbed():
                        q = mla_absorb_q(rs, w, q_nope[:CT], q_rope[:CT], W)
                        o = sparse_mla.attend_chunk(
                            q.reshape(NC, Cs * H, W), pages, bt_c,
                            b["chunk_q0"], b["chunk_ctx_lens"], *sel,
                            heads=H, **kw)
                        return mla_absorb_o(w, o.reshape(CT, H, R))

                    # (the branches INSIDE the scope: a name in a branch
                    # comes after ``cond/branch_n_fun`` in an operation's
                    # path, and a reader of ``mla/prefill`` would miss it —
                    # so the absorbed branch's two products are prefill's)
                    with jax.named_scope("prefill"):
                        o_c = jax.lax.cond(
                            one_seq, lambda: _chunk_expanded(
                                rs, w, q_nope[:CT], q_rope[:CT], pages,
                                bt_c[0], jnp.where(b["chunk_ntok"] > 0,
                                                   b["chunk_ctx_lens"], 0),
                                *sel),
                            absorbed)
                    got, live, _, walks_d = decode_selection(
                        rs, q_idx[CT:], w_idx[CT:], None, ipages, flats_[0],
                        b["decode_block_tables"] + l * NB,
                        b["decode_ctx_lens"] - 1, b["decode_ctx_lens"])
                    with jax.named_scope("absorb"):
                        q = mla_absorb_q(rs, w, q_nope[CT:], q_rope[CT:], W)
                    with jax.named_scope("decode"):
                        o_d = sparse_mla.attend_decode(q, got, live, **kw)
                    with jax.named_scope("absorb"):
                        return jnp.concatenate([o_c, mla_absorb_o(w, o_d)],
                                               axis=0), walks_c + walks_d

                x, flats = _transformer_layer(rs, w, x, positions, attend,
                                              experts=experts, l=l - l0)
                return (x,) + tuple(flats), None

            return layer_fn

        # (beside an index pool the carry ends in the selections' walks)
        x, *flats = _scan_layers(
            spec, weights["layers"], make_body,
            (x,) + tuple(p.reshape(L * NB * bs, p.shape[-1]) for p in pools)
            + _no_walks(pools))
        turns = _stream_turns(x) + _select_counts(pools, flats)
        x = _finish(spec, weights, _stream_out(x))
        last_rows = (jnp.arange(NC) * Cs
                     + jnp.maximum(b["chunk_ntok"] - 1, 0))
        logits = _unembed(spec, weights,
                          jnp.concatenate([x[last_rows], x[CT:]], axis=0))
        return (logits[:NC], logits[NC:], _cache(spec, [
            f.reshape(p.shape) for f, p in zip(flats, pools)])) + turns

    return fwd


def build_packed_prefill(spec: RaggedModelSpec) -> Callable:
    """``build_prefill_forward`` over latent pages: the expanded form on the
    packed rows, then whole pages of latent rows written by the page plan —
    and of index keys beside an index pool. Nothing is selected there: the
    pass's rows start at position 0 and there are no more of them than the
    selection keeps, so every row's selection is all it sees."""

    def fwd(weights, cache, b):
        pools = _pools(spec, cache)
        NC = b["chunk_ntok"].shape[0]
        CT = b["chunk_tokens"].shape[0]
        assert len(pools) == 1 or CT <= spec.mla["index"]["topk"], (
            f"a packed pass of {CT} prompt tokens can hold more of one "
            "sequence than the selection keeps: it selects nothing "
            "(InferenceEngineV2.packed_prefill sends such an engine's "
            "prompts through the paged pass)")
        Cs = CT // NC
        S = b["decode_tokens"].shape[0]
        L, NB, bs, W = pools[0].shape
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
                x, *pages = carry
                w, l = scanned

                # ``index``: the indexer's (queries, weights, keys), or none
                def attend(q_nope, q_rope, lat, *index):
                    with jax.named_scope("prefill"):
                        out = mla_expanded(rs, ak, w, q_nope, q_rope, lat,
                                           b["row_seg"])
                    # sentinel pages (id >= NB) go out of range GLOBALLY
                    tgt = jnp.where(b["page_ids"] < NB,
                                    l * NB + b["page_ids"], L * NB)
                    return (out,) + tuple(
                        p.at[tgt].set(
                            jnp.where(valid, r[rows], 0).astype(p.dtype),
                            mode="drop")
                        for p, r in zip(pages, (lat,) + index[2:]))

                x, pages = _transformer_layer(rs, w, x, positions, attend,
                                              experts=experts, l=l - l0)
                return (x,) + tuple(pages), None

            return layer_fn

        x, *pages = _scan_layers(
            spec, weights["layers"], make_body,
            (x,) + tuple(p.reshape(L * NB, bs, p.shape[-1]) for p in pools))
        turns = _stream_turns(x)
        x = _finish(spec, weights, _stream_out(x))
        last_rows = (jnp.arange(NC) * Cs
                     + jnp.maximum(b["chunk_ntok"] - 1, 0))
        logits = _unembed(spec, weights, x[last_rows])
        return (logits, jnp.zeros((S, logits.shape[1]), logits.dtype),
                _cache(spec, [g.reshape(p.shape)
                              for g, p in zip(pages, pools)])) + turns

    return fwd


def build_decode_step(spec: RaggedModelSpec, do_sample: bool,
                      top_k: int) -> Callable:
    """``ragged_model.build_decode_step``'s side-buffer form over latent
    pages: the pool stays frozen through the layers, each layer's latent row
    goes to a side buffer ``[L, S, 8, W]`` (one sublane tile, row 0 the
    step's) the kernel attends beside the pages, and one row write puts the
    rows into the pool after the layers. With a selection both pools stay
    frozen and each has its side buffer and its row write; a row scores its
    pages' index keys and its own, and attends over the chosen rows gathered
    from the latent pages (and its own row from the side buffer where it
    chose it)."""
    R = spec.mla["kv_lora_rank"]

    def fwd(weights, cache, ids, positions, block_tables, ctx, key,
            temperature=1.0):
        pools = _pools(spec, cache)
        S = ids.shape[0]
        L, NB, bs, W = pools[0].shape
        pages = pools[0].reshape(L * NB, bs, W)
        # ctx counts this step's token; the pages hold the prefix
        prefix = jnp.maximum(ctx - 1, 0)
        x = _router_stream(spec, _embed_in(spec, weights, ids, positions),
                           weights)

        def make_body(rs, experts, l0):
            ak = AttentionKernelSpec(rs)

            def layer_fn(carry, scanned):
                x, *sides = carry
                w, l = scanned

                # ``index``: the indexer's (queries, weights, keys), or none
                def attend(q_nope, q_rope, lat, *index):
                    sides_ = tuple(
                        jax.lax.dynamic_update_slice(
                            s, r[None, :, None].astype(s.dtype), (l, 0, 0, 0))
                        for s, r in zip(sides, (lat,) + index[2:]))
                    with jax.named_scope("absorb"):
                        q = mla_absorb_q(rs, w, q_nope, q_rope, W)
                    if index:
                        o_lat, walks = selected(q, lat, *index)
                        sides_ += (sides[-1] + walks,)
                    else:
                        with jax.named_scope("decode"):
                            o_lat = ak.latent(
                                q, pages, block_tables + l * NB, prefix,
                                prefix, side=sides_[0], side_j=0, layer_idx=l)
                    with jax.named_scope("absorb"):
                        return (mla_absorb_o(w, o_lat),) + sides_

                def selected(q, lat, q_idx, w_idx, k_idx):
                    got, live, own_on, walks = decode_selection(
                        rs, q_idx, w_idx, k_idx.astype(pools[1].dtype),
                        pools[1].reshape(L * NB, bs, -1),
                        pages.reshape(L * NB * bs, W), block_tables + l * NB,
                        prefix, prefix)
                    with jax.named_scope("decode"):
                        own = jnp.pad(lat[:, None].astype(pages.dtype),
                                      ((0, 0), (0, 7), (0, 0)))
                        return sparse_mla.attend_decode(
                            q, got, live, v_dim=R, softmax_scale=_scale(rs),
                            side=own, side_on=own_on), walks

                x, sides = _transformer_layer(
                    rs, w, x, positions, attend, experts=experts, l=l - l0)
                return (x,) + tuple(sides), None

            return layer_fn

        x, *sides = _scan_layers(
            spec, weights["layers"], make_body,
            (x,) + tuple(jnp.zeros((L, S, 8, p.shape[-1]), p.dtype)
                         for p in pools) + _no_walks(pools))
        turns = _stream_turns(x) + _select_counts(pools, sides)
        logits = _unembed(spec, weights,
                          _finish(spec, weights, _stream_out(x)))
        # the kernels READ the pools inside the layers; the barrier orders
        # the in-place writes after them instead of cloning a pool
        *pools, _ = jax.lax.optimization_barrier((*pools, logits))
        with jax.named_scope("kv_flush"):
            new = _cache(spec, [
                mla_row_write(p, s, block_tables, prefix, 1)
                for p, s in zip(pools, sides)])
        nxt = _sample_logits(logits, key, do_sample, top_k, temperature)
        return (nxt, logits, new) + turns

    return fwd


def build_verify(spec: RaggedModelSpec, k: int) -> Callable:
    """``build_verify_step`` over latent pages: all ``k + 1`` rows of a
    sequence are scattered into the pool, then attended absorbed, one slot a
    sequence, causal by absolute position — the visible set of every row is
    what the decode step sees one token at a time."""
    if "index" in spec.mla:
        from deepspeed_tpu.inference.v2.attention import INDEX_POOL_MSG
        raise NotImplementedError(INDEX_POOL_MSG.format(
            what="the speculative verify step (its k + 1 rows a sequence "
            "would each need a selection of their own)"))
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


# --------------------------------------------------------------------------- #
# a paged pass that holds ONE sequence (module docstring, "expanded again")
# --------------------------------------------------------------------------- #


def one_sequence(chunk_ntok, chunk_q0, chunk_block_tables, slot_size: int):
    """Whether a pass's chunk slots hold ONE sequence in two or more slots
    and nothing else — the live slots are the first ``n >= 2``, each over
    slot 0's block table, at consecutive positions — as a traced scalar: what
    the scheduler gives a long prompt (``schedule_pass``: "a sequence may
    claim SEVERAL consecutive slots"), read from what the program is handed.
    ``InferenceEngineV2`` counts the same rule on the host
    (``serve/mla/expanded_passes``)."""
    i = jnp.arange(chunk_ntok.shape[0], dtype=jnp.int32)
    live = chunk_ntok > 0
    n = jnp.sum(live, dtype=jnp.int32)
    same = jnp.all(chunk_block_tables == chunk_block_tables[:1], axis=1) & (
        chunk_q0 == chunk_q0[0] + i * slot_size)
    return (n >= 2) & jnp.all(live == (i < n)) & jnp.all(same | ~live)


def expansion_weights(w, rank: int, rope: int, width: int):
    """``[H, W, nope + rope + v]``: a head's map from a latent ROW to its key
    and its value — ``W_UK`` over the latent part and the identity from the
    row's rotary key to the key's last ``rope`` values, beside ``W_UV``;
    zeros under the row's padding."""
    w_uk, w_uv = w["w_uk"], w["w_uv"]
    H, _, nope = w_uk.shape
    zeros = lambda *shape: jnp.zeros(shape, w_uk.dtype)
    below = jnp.concatenate(
        [zeros(width - rank, nope), jnp.eye(width - rank, rope,
                                            dtype=w_uk.dtype),
         zeros(width - rank, w_uv.shape[-1])], axis=-1)
    return jnp.concatenate(
        [jnp.concatenate([w_uk, zeros(H, rank, rope), w_uv], axis=-1),
         jnp.broadcast_to(below, (H,) + below.shape)], axis=1)


def expanded_queries(q_nope, q_rope, slots: int):
    """``[N, H, Cs, nope + rope]``: a slot's query tokens a head."""
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    return q.reshape((slots, -1) + q.shape[1:]).transpose(0, 2, 1, 3)


def _chunk_expanded(spec: RaggedModelSpec, w, q_nope, q_rope, pages,
                    block_table, key_lims, scores, thr, pcut):
    """The chunk rows of a pass that is one sequence, over what each row's
    indexer chose, EXPANDED: ``[CT, H * v]`` with no absorbed query and no
    latent output in between (the caller's scope: ``attn/mla/prefill``)."""
    m = spec.mla
    out = sparse_mla.attend_expanded(
        expanded_queries(q_nope, q_rope, scores.shape[0]),
        expansion_weights(w, m["kv_lora_rank"], m["qk_rope_head_dim"],
                          pages.shape[-1]),
        pages, block_table, key_lims, scores, thr, pcut,
        k_dim=m["qk_nope_head_dim"] + m["qk_rope_head_dim"],
        softmax_scale=_scale(spec))
    return out.reshape(q_nope.shape[0], -1)
