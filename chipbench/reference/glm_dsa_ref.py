"""A plain reference for GLM-5 (``model_type: glm_moe_dsa``): multi-head
latent attention over a learned per-token SELECTION (DeepSeek Sparse
Attention), over a sparse mixture of experts with a sigmoid router.

Written from the layer equations of the lineage's published modelling code
(DeepSeek-V2's attention, DeepSeek-V3's ``noaux_tc`` router with one group,
DeepSeek-V3.2-Exp's indexer), in ``jax.numpy`` and float32 with matmuls at
the highest precision, in the EXPANDED form only, with no kernel, cache,
batching or code of ``deepspeed_tpu``. Per layer, ``h = rms_norm(x; ln_in)``
at position ``t``:

1. ``c_q = rms_norm(h W_qa; q_a_norm)``; ``q = c_q W_qb`` -> ``[T, H, nope +
   rope]``; ``[c_kv | k_rope] = h W_kva``; ``c_kv = rms_norm(c_kv;
   kv_a_norm)``; rotary embedding on ``q_rope`` and the ONE shared ``k_rope``;
   ``[k_nope | v] = c_kv W_kvb`` per head (``v`` of ``v_head_dim``, which is
   not the nope width here); scale ``(nope + rope) ** -0.5``;
2. the indexer: ``q_idx = c_q W_iq`` -> ``[T, Hi, Di]``; ``k_idx =
   layer_norm(h W_ik; gain, bias)`` ``[T, Di]``; in both the FIRST
   ``index_rope_dim`` values are rotated by position (pairs ``(2i, 2i+1)``),
   the rest are not; ``w = (h W_iw) * Hi ** -0.5 * Di ** -0.5`` (signed);
   ``I[t, s] = sum_j w[t, j] relu(q_idx[t, j] . k_idx[s])`` for ``s <= t``;
3. the selection ``S_t``: the ``min(index_topk, t + 1)`` positions ``s <= t``
   of largest ``I[t, s]`` (``jax.lax.top_k`` on the float32 scores: of equal
   scores the lower position first);
4. ``o = softmax over s in S_t of (q k_s^T scale) v_s``; ``x += o W_o``;
5. ``h2 = rms_norm(x; ln_mlp)``; dense layers ``x += SwiGLU(h2)``; MoE layers
   exactly ``joyai_ref.sparse_mixture`` (sigmoid scores, top-k of scores +
   ``expert_bias``, weights the unbiased scores over the chosen ones' sum,
   times ``route_scale``, the HELD experts' part only, the shared expert
   unweighted);
6. final RMSNorm, untied head; the multi-token-prediction module is not here.

Weights: ``joyai_ref``'s dict, each layer with ``"index": {"wq": [Rq, Hi *
Di], "wk": [H, Di], "k_norm": [Di], "k_bias": [Di], "ww": [H, Hi]}``; ``hp``
adds ``index_heads``, ``index_head_dim``, ``index_rope_dim``, ``index_topk``
and ``index_eps``.

For memory only: the selection is computed in blocks of queries (a ``[T, T]``
mask of booleans is kept, never ``[T, T]`` scores), attention one head at a
time with that head's keys and values made inside the loop, and
:func:`forward_logits` calls a layer's two halves as two programs (each
compiled once a kind) so that the expert stacks are on the device only
while they are used.

For tests and for sizing a tolerance, not for use (``hp["fault"]``):
``dense`` (no selection), ``abs_topk`` (top-k by ``|I|``), ``no_relu``,
``unsigned_weights``, ``rope_tail`` (the LAST ``index_rope_dim`` values
rotated), ``keys_late`` (position ``s`` scored by the key of ``s - 1``),
``topk_minus_one``, ``chunk_shared`` (a chunk of ``hp["chunk"]`` queries
shares its last query's selection) — and joyai_ref's own
(``weigh_with_bias`` ...), which pass through. ``hp["index_dtype"]`` rounds
``q_idx``, ``k_idx`` and the scores to a lower precision (the selection's
control); ``act_dtype`` / ``rounding`` are joyai_ref's.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from chipbench.reference.decoder_ref import F32, rms_norm, rope, swiglu
# the router, the held experts' sum and the rounding are the lineage's own
from chipbench.reference.joyai_ref import (rounded_to, route,  # noqa: F401
                                           sparse_mixture)

QUERY_BLOCK = 1024
INDEX_BLOCK = 64


def _handed_on(rounding, act_dtype):
    """What a branch hands on: rounded to ``act_dtype`` where the traced
    flag ``rounding`` is set (joyai_ref's one program for the float32
    reference and its control)."""
    return lambda a: jnp.where(rounding, rounded_to(a, act_dtype), a)


def layer_norm(x, gain, bias, eps: float):
    x = x.astype(F32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * gain.astype(F32) \
        + bias.astype(F32)


def index_rope(x, pos, hp):
    """x [T, heads, Di]: the indexer's rotation."""
    dr = hp["index_rope_dim"]
    if hp.get("fault") == "rope_tail":
        return jnp.concatenate(
            [x[..., :-dr], rope(x[..., -dr:], pos, hp["rope_theta"])], -1)
    return jnp.concatenate(
        [rope(x[..., :dr], pos, hp["rope_theta"]), x[..., dr:]], axis=-1)


def index_keys(h, ix: Dict[str, Any], pos, hp: Dict[str, Any]):
    """``k_idx [T, Di]`` of rows ``h`` (normed input) at positions ``pos``."""
    k = layer_norm(h @ ix["wk"].astype(F32), ix["k_norm"], ix["k_bias"],
                   hp["index_eps"])
    return rounded_to(index_rope(k[:, None], pos, hp)[:, 0],
                      hp.get("index_dtype"))


def index_queries(h, cq, ix: Dict[str, Any], pos, hp: Dict[str, Any]):
    """``(q_idx [T, Hi, Di], w [T, Hi])`` of rows ``h`` and ``cq`` (normed
    query latent) at positions ``pos``."""
    hi, di = hp["index_heads"], hp["index_head_dim"]
    q = index_rope((cq @ ix["wq"].astype(F32)).reshape(-1, hi, di), pos, hp)
    w = (h @ ix["ww"].astype(F32)) * (hi ** -0.5 * di ** -0.5)
    if hp.get("fault") == "unsigned_weights":
        w = jnp.abs(w)
    return rounded_to(q, hp.get("index_dtype")), w


def index_scores(q, w, k, q_pos, hp: Dict[str, Any]):
    """``I`` of query rows ``q [B, Hi, Di]``, ``w [B, Hi]`` at positions
    ``q_pos [B]`` against keys ``k [S, Di]`` at positions ``0..S-1``:
    ``[B, S]`` float32, ``-inf`` where ``s > q_pos``. (In blocks of
    ``INDEX_BLOCK`` queries: the heads' products are ``[block, Hi, S]``.)"""
    fault = hp.get("fault")
    if fault == "keys_late":
        k = jnp.concatenate([k[:1], k[:-1]], axis=0)
    b = q.shape[0]
    block = min(INDEX_BLOCK, b)
    pad = -b % block

    def one_block(args):
        qb, wb = args
        s = jnp.einsum("bhd,sd->bhs", qb, k)
        if fault != "no_relu":
            s = jnp.maximum(s, 0.0)
        return jnp.sum(s * wb[..., None], axis=1)

    scores = jax.lax.map(one_block, (
        jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
            (-1, block) + q.shape[1:]),
        jnp.pad(w, ((0, pad), (0, 0))).reshape(-1, block, w.shape[1])))
    scores = rounded_to(scores.reshape(-1, k.shape[0])[:b],
                        hp.get("index_dtype"))
    scores = jnp.where(scores == 0.0, 0.0, scores)          # -0.0 is 0.0
    seen = jnp.arange(k.shape[0])[None, :] <= q_pos[:, None]
    return jnp.where(seen, scores, -jnp.inf)


def selection(scores, hp: Dict[str, Any]):
    """``[B, S]`` scores -> ``[B, S]`` bool: each row's ``min(index_topk,
    seen)`` positions of largest score."""
    fault, topk = hp.get("fault"), hp["index_topk"]
    seen = jnp.isfinite(scores)
    if fault == "dense":
        return seen
    if fault == "topk_minus_one":
        topk -= 1
    if scores.shape[-1] <= topk:
        return seen
    ranked = jnp.where(seen, jnp.abs(scores), -jnp.inf) \
        if fault == "abs_topk" else scores
    _, idx = jax.lax.top_k(ranked, topk)
    rows = jnp.arange(scores.shape[0])[:, None]
    picked = jnp.zeros(scores.shape, bool).at[rows, idx].set(True)
    return picked & seen


def keep_mask(q, w, k, hp: Dict[str, Any]):
    """``[T, T]`` bool: what each position attends to, in blocks of queries."""
    t = q.shape[0]
    block = min(4 * INDEX_BLOCK, t)
    pad = -t % block
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        (-1, block) + q.shape[1:])
    wp = jnp.pad(w, ((0, pad), (0, 0))).reshape(-1, block, w.shape[1])
    starts = jnp.arange(qp.shape[0]) * block

    def one_block(args):
        qb, wb, i0 = args
        pos = jnp.minimum(i0 + jnp.arange(block), t - 1)
        return selection(index_scores(qb, wb, k, pos, hp), hp)

    keep = jax.lax.map(one_block, (qp, wp, starts)).reshape(-1, t)[:t]
    if hp.get("fault") == "chunk_shared":
        c = hp["chunk"]
        last = jnp.minimum((jnp.arange(t) // c) * c + c - 1, t - 1)
        keep = keep[last] & (jnp.arange(t)[None, :]
                             <= jnp.arange(t)[:, None])
    return keep


def attention(cq, ckv, k_rope, keep, layer, hp, handed_on):
    """Latent attention, expanded, one head at a time, each head's output
    through its rows of ``W_o`` and summed: ``[T, hidden]`` (the heads'
    outputs side by side would be ``[T, H * v]`` in float32 twice over)."""
    hq, r = hp["num_heads"], hp["kv_lora_rank"]
    dn, dr, dv = (hp["qk_nope_head_dim"], hp["qk_rope_head_dim"],
                  hp["v_head_dim"])
    t = cq.shape[0]
    pos = jnp.arange(t)
    scale = hp.get("softmax_scale", (dn + dr) ** -0.5)
    wqb = layer["wqb"].reshape(-1, hq, dn + dr).transpose(1, 0, 2)
    wkvb = layer["wkvb"].reshape(r, hq, dn + dv).transpose(1, 0, 2)
    wo = layer["wo"].reshape(hq, dv, -1)
    block = min(QUERY_BLOCK, t)
    pad = -t % block
    keep_b = jnp.pad(keep, ((0, pad), (0, 0))).reshape(-1, block, t)

    def one_head(acc, args):
        wq, wkv, wo_h = args
        q = cq @ wq.astype(F32)                               # [T, dn + dr]
        q = jnp.concatenate(
            [q[:, :dn], rope(q[:, None, dn:], pos, hp["rope_theta"])[:, 0]],
            axis=-1)
        kv = ckv @ wkv.astype(F32)                            # [T, dn + dv]
        k = handed_on(jnp.concatenate([kv[:, :dn], k_rope], axis=-1))
        v = handed_on(kv[:, dn:])
        qb = jnp.pad(handed_on(q), ((0, pad), (0, 0))).reshape(
            -1, block, dn + dr)

        def one_block(args):
            qi, ki = args
            s = jnp.where(ki, (qi @ k.T) * scale, -jnp.inf)
            m = jnp.max(s, axis=-1, keepdims=True)
            p = jnp.where(ki, jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0)),
                          0.0)
            return (p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)) @ v

        a = jax.lax.map(one_block, (qb, keep_b)).reshape(-1, dv)[:t]
        return acc + handed_on(a) @ wo_h.astype(F32), None

    out, _ = jax.lax.scan(one_head, jnp.zeros((t, wo.shape[-1]), F32),
                          (wqb, wkvb, wo))
    return out


@functools.partial(jax.jit, static_argnames=("hp_items", "act_dtype"))
def attention_half(layer: Dict[str, Any], x, hp_items, act_dtype=None,
                   rounding=True):
    """``x`` [T, H] through a layer's attention (steps 1-4): ``x`` after the
    residual. ``layer`` needs the attention's and the indexer's weights
    only."""
    hp = dict(hp_items)
    r, eps, t = hp["kv_lora_rank"], hp["eps"], x.shape[0]
    pos = jnp.arange(t)

    handed_on = _handed_on(rounding, act_dtype)
    with jax.default_matmul_precision("highest"):
        h = handed_on(rms_norm(x, layer["ln_in"], eps))
        cq = handed_on(rms_norm(h @ layer["wqa"].astype(F32),
                                layer["q_a_norm"], eps))
        kva = h @ layer["wkva"].astype(F32)
        ckv = handed_on(rms_norm(kva[:, :r], layer["kv_a_norm"], eps))
        k_rope = kva[:, None, r:]
        if not hp.get("k_rope_unrotated"):
            k_rope = rope(k_rope, pos, hp["rope_theta"])
        ix = layer["index"]
        keep = keep_mask(*index_queries(h, cq, ix, pos, hp),
                         index_keys(h, ix, pos, hp), hp)
        return handed_on(x + attention(cq, ckv, k_rope[:, 0], keep, layer,
                                       hp, handed_on))


@functools.partial(jax.jit, static_argnames=("hp_items", "act_dtype"))
def ffn_half(layer: Dict[str, Any], x, hp_items, act_dtype=None,
             rounding=True):
    """``x`` through a layer's feed-forward (step 5): ``(x, the rows'
    routing margins in it)``."""
    hp = dict(hp_items)

    handed_on = _handed_on(rounding, act_dtype)
    with jax.default_matmul_precision("highest"):
        h = handed_on(rms_norm(x, layer["ln_mlp"], hp["eps"]))
        if "router" in layer:
            out, margin = sparse_mixture(h, layer, hp)
        else:
            # (in blocks of rows: [T, 12,288] twice in float32 is a GiB)
            t = h.shape[0]
            block = min(QUERY_BLOCK, t)
            hb = jnp.pad(h, ((0, -t % block), (0, 0))).reshape(
                -1, block, h.shape[1])
            out = jax.lax.map(lambda rows: swiglu(
                rows, layer["w_gate"], layer["w_up"], layer["w_down"]),
                hb).reshape(-1, h.shape[1])[:t]
            margin = jnp.full((t,), jnp.inf, F32)
        return handed_on(x + out), margin


_ATTENTION_KEYS = ("ln_in", "wqa", "q_a_norm", "wqb", "wkva", "kv_a_norm",
                   "wkvb", "wo", "index")


def one_layer(layer: Dict[str, Any], x, hp_items, act_dtype=None,
              rounding=True):
    """``x`` [T, H] through one layer: ``(x, the rows' margins in it)``. Two
    programs a kind of layer, the attention's weights handed to the first
    and the feed-forward's to the second."""
    x = attention_half({k: layer[k] for k in _ATTENTION_KEYS}, x, hp_items,
                       act_dtype, rounding)
    return ffn_half({k: v for k, v in layer.items()
                     if k not in _ATTENTION_KEYS}, x, hp_items, act_dtype,
                    rounding)


def hidden_states(weights: Dict[str, Any], ids, hp: Dict[str, Any],
                  act_dtype=None, rounding=True):
    """Final-norm hidden states [T, H] of one sequence ``ids`` [T], and each
    position's routing margin [T] (``joyai_ref``'s)."""
    hp_items = tuple(sorted(hp.items()))
    x = weights["embed"][jnp.asarray(ids)].astype(F32)
    x = jnp.where(rounding, rounded_to(x, act_dtype), x)
    margin = jnp.full((x.shape[0],), jnp.inf, F32)
    for layer in weights["layers"]:
        x, m = one_layer(layer, x, hp_items, act_dtype, rounding)
        margin = jnp.minimum(margin, m)
    return rms_norm(x, weights["final_norm"], hp["eps"]), margin


def forward_logits(weights: Dict[str, Any], ids, hp: Dict[str, Any],
                   rows=None, with_margin: bool = False, act_dtype=None,
                   rounding=True):
    """Logits [T, V] (or of ``rows`` only) of one sequence ``ids`` [T];
    ``with_margin`` adds those positions' routing margins."""
    x, margin = hidden_states(weights, ids, hp, act_dtype, rounding)
    if rows is not None:
        x, margin = x[rows], margin[rows]
    with jax.default_matmul_precision("highest"):
        logits = x @ jnp.asarray(weights["lm_head"]).astype(F32)
    return (logits, margin) if with_margin else logits


@functools.partial(jax.jit, static_argnames=("hp_items",))
def index_readings(ix: Dict[str, Any], h, cq, q_rows, hp_items):
    """The indexer and the selection by themselves, on given inputs: ``h``
    [S, H] normed inputs at positions ``0..S-1`` (float32 holding the values
    the program saw), ``cq`` [B, Rq] the normed query latents of the query
    rows at positions ``q_rows`` [B]. Returns ``(scores [B, S], keep [B, S],
    thr [B])``: ``thr`` the smallest kept score of a row."""
    hp = dict(hp_items)
    with jax.default_matmul_precision("highest"):
        k = index_keys(h, ix, jnp.arange(h.shape[0]), hp)
        q, w = index_queries(h[q_rows], cq, ix, q_rows, hp)
        scores = index_scores(q, w, k, q_rows, hp)
        keep = selection(scores, hp)
        thr = jnp.min(jnp.where(keep, scores, jnp.inf), axis=-1)
    return scores, keep, thr
