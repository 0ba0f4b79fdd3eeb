"""A plain reference for SDAR (``model_type: sdar_moe``; JetLM
SDAR-30B-A3B-Chat): a Qwen3-MoE decoder that GENERATES by diffusion over
blocks.

Written from the published ``config.json`` and the family's published
``block_diffusion_generate`` (``generate.py`` of github.com/JetLM/SDAR; the
model card), in ``jax.numpy`` and float32 with matmuls at the highest
precision, with no kernel, cache, batching or code of ``deepspeed_tpu``. With
``B`` the block length and ``blk(t) = t // B``:

- attention: ``h = rms_norm(x; ln_in)``; ``q = h W_q`` ``[T, Hq, D]``, ``k,
  v`` ``[T, Hkv, D]``, no bias; RMSNorm over each head's ``D`` values of q and
  of k with a learned gain a value (``q_norm``, ``k_norm``); rotary positions
  on the whole head, theta ``rope_theta``, no scaling; scores ``q . k /
  sqrt(D)``, grouped queries; **key s is visible to query t iff blk(s) <=
  blk(t)** — causal across blocks, two-way inside one — for prompt tokens
  and generated ones alike; softmax in float32; ``x += o W_o``;
- experts, EVERY layer: ``h = rms_norm(x; ln_ff)``; ``p = softmax(h W_r)``
  in float32 over all experts; the ``top_k`` largest; their weights ``p_e``
  over the sum of the chosen (``norm_topk_prob``); ``x += sum_e w_e
  (silu(h W_gate,e) * (h W_up,e)) W_down,e``. No shared expert, no bias;
- final RMSNorm, untied head. **The logits at position t score the token AT
  t** (no shift: a masked position predicts itself);
- generation (greedy), mask token ``m``: the first ``B * (P // B)`` prompt
  tokens are context; the current block starts as the ``P mod B`` left-over
  prompt tokens followed by ``m``s, every later block as ``B`` ``m``s. A
  DENOISE pass runs the whole sequence — context and block — and at each
  still-masked position takes ``x0 = argmax`` and ``conf = max softmax`` of
  its logits in float32 (``m`` itself is never chosen: its logit is left
  out of both); of the masked positions the ``n_s`` of highest ``conf``
  (ties to the lower position) take their ``x0``, with ``n_s`` the pass's
  entry of :func:`num_transfer_tokens` or what is left if fewer (static
  rule), or every masked position with ``conf > threshold`` if those are at
  least ``n_s`` (dynamic rule). Once no ``m`` is left the block is final:
  the next block's passes see it as context. Blocks until ``P + n``
  positions are final; what the last block holds past them is dropped.

Weights are a plain dict (all matrices ``[in, out]``)::

    {"embed": [V, H], "final_norm": [H], "lm_head": [H, V],
     "layers": [{"ln_in": [H], "ln_ff": [H],
                 "wq": [H, Hq*D], "wk": [H, Hkv*D], "wv": [H, Hkv*D],
                 "wo": [Hq*D, H], "q_norm": [D], "k_norm": [D],
                 "router": [H, E], "w_gate": [E, H, F], "w_up": [E, H, F],
                 "w_down": [E, F, H]}, ...]}

and ``hp`` gives ``num_heads``, ``num_kv_heads``, ``head_dim``, ``eps``,
``rope_theta``, ``top_k``, ``block_length`` and ``mask_token_id``. A layer's
weights may lie on the host (numpy): each layer is one jitted call that is
handed that layer's weights alone, the embedding is read on the host and the
head is computed a block of the vocabulary at a time over the rows asked
for, so that a model that fills the device beside the engine is never there
twice and 151,936 logits a row fit.

Departures from the published code:

- rotation pairs ``(x[2i], x[2i+1])``, as ``decoder_ref.py`` and the
  program's zoo do; the published code pairs ``(x[i], x[i + D/2])``, the same
  function after a fixed permutation of each head's q/k columns and norm
  gains;
- the mask token is never chosen (a trained model never predicts it; random
  weights would, once in ``V`` positions, and a block would never end);
- for memory only: attention runs one block of queries at a time, the
  experts one at a time over all tokens (every expert is evaluated for every
  token and weighed by its routing weight, 0 where not chosen: the same
  sum), the layers one jitted call each, the head in blocks.

A row's routing MARGIN is, at the least over the layers, the router's logit
of the last expert chosen less that of the first left out. Where it is small
the choice turns on rounding, and a system computing in bfloat16 may rightly
choose otherwise.

For tests and for sizing a tolerance, not for use: ``act_dtype`` rounds what
each part of a layer hands on (the embedding, the normed inputs, each
product's result, each branch's output, the residual stream) to a lower
precision, which is where a program that keeps its activations in that
precision rounds; ``causal=True`` masks by position (``s <= t``), the fault
of a program that ignores the block rule.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.decoder_ref import F32, rms_norm, rope, swiglu

QUERY_BLOCK = 512
VOCAB_BLOCK = 16384


def rounded(x, dtype):
    """``x`` at the precision of ``dtype``, still float32 (None: as it is)."""
    if dtype is None or jnp.dtype(dtype) == jnp.dtype(F32):
        return x
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, exponent_bits=info.nexp,
                                    mantissa_bits=info.nmant)


def num_transfer_tokens(block: int, steps: int) -> List[int]:
    """How many masked positions each of a block's ``steps`` denoise passes
    fills: ``block // steps``, the first ``block % steps`` passes one more."""
    return [block // steps + (i < block % steps) for i in range(steps)]


def attention(q, k, v, block: int, causal: bool = False):
    """q [T, Hq, D], k/v [T, Hkv, D] -> [T, Hq, D] under the block rule
    (``causal``: by position, the fault)."""
    t, hq, d = q.shape
    hkv = k.shape[1]
    rep = hq // hkv
    rows = min(QUERY_BLOCK, t)
    pad = -t % rows
    s_pos = jnp.arange(t)[None, :]
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        -1, rows, hkv, rep, d)

    def one_block(args):
        qi, i0 = args                                     # [rows, Hkv, rep, D]
        t_pos = (i0 + jnp.arange(rows))[:, None]
        seen = (s_pos <= t_pos) if causal \
            else (s_pos // block <= t_pos // block)       # [rows, T]
        s = jnp.einsum("tgrd,sgd->grts", qi, k) / jnp.sqrt(F32(d))
        s = jnp.where(seen[None, None], s, -jnp.inf)
        return jnp.einsum("grts,sgd->tgrd", jax.nn.softmax(s, axis=-1), v)

    out = jax.lax.map(one_block, (qb, jnp.arange(qb.shape[0]) * rows))
    return out.reshape(-1, hq, d)[:t]


def route(h, router, top_k: int):
    """Routing weight of every expert for every token ``[T, E]`` (0 where
    not chosen) and each token's margin ``[T]``."""
    logits = h.astype(F32) @ router.astype(F32)
    p = jax.nn.softmax(logits, axis=-1)
    top, idx = jax.lax.top_k(logits, top_k + 1)
    margin = top[:, top_k - 1] - top[:, top_k]
    idx = idx[:, :top_k]
    chosen = jnp.take_along_axis(p, idx, axis=-1)
    w = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    dense = jnp.sum(jax.nn.one_hot(idx, logits.shape[-1], dtype=F32)
                    * w[..., None], axis=1)
    return dense, margin


def sparse_mixture(h, layer: Dict[str, Any], top_k: int):
    dense, margin = route(h, layer["router"], top_k)

    def add_expert(acc, args):
        wg, wu, wd, weight = args
        return acc + weight[:, None] * swiglu(h, wg, wu, wd), None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(h),
                          (layer["w_gate"], layer["w_up"], layer["w_down"],
                           dense.T))
    return out, margin


@functools.partial(jax.jit, static_argnames=("hp", "act_dtype", "causal"))
def _layer(x, layer, hp, act_dtype=None, causal: bool = False):
    hp = dict(hp)
    act = lambda v: rounded(v, act_dtype)
    with jax.default_matmul_precision("highest"):
        f = lambda name: layer[name].astype(F32)
        T, D = x.shape[0], hp["head_dim"]
        positions = jnp.arange(T)
        u = act(rms_norm(x, f("ln_in"), hp["eps"]))
        q = act(u @ f("wq")).reshape(T, hp["num_heads"], D)
        k = act(u @ f("wk")).reshape(T, hp["num_kv_heads"], D)
        v = act(u @ f("wv")).reshape(T, hp["num_kv_heads"], D)
        q = rope(act(rms_norm(q, f("q_norm"), hp["eps"])), positions,
                 hp["rope_theta"])
        k = rope(act(rms_norm(k, f("k_norm"), hp["eps"])), positions,
                 hp["rope_theta"])
        o = attention(act(q), act(k), v, hp["block_length"], causal)
        x = act(x + act(act(o.reshape(T, -1)) @ f("wo")))
        g = act(rms_norm(x, f("ln_ff"), hp["eps"]))
        out, margin = sparse_mixture(g, layer, hp["top_k"])
        return act(x + act(out)), margin


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, block, eps: float):
    with jax.default_matmul_precision("highest"):
        return rms_norm(x, final_norm.astype(F32), eps) @ block.astype(F32)


def _static(hp: Dict[str, Any]):
    return tuple(sorted(hp.items()))


def forward_many(weights: Dict[str, Any], runs: Sequence[Dict[str, Any]],
                 hp: Dict[str, Any]):
    """Several whole-sequence forwards in ONE walk over the layers, each
    layer's weights handed to the device once for all of them. A run is
    ``{"ids": [T], "rows": positions whose logits are wanted, "act_dtype":
    None, "causal": False}``; returns for each ``(logits [len(rows), V],
    margins [len(rows)])``. Runs of one length share a compiled layer."""
    embed = np.asarray(weights["embed"])
    state = []
    for r in runs:
        ids = np.asarray(r["ids"], np.int32)
        state.append({
            "x": rounded(jnp.asarray(embed[ids]).astype(F32),
                         r.get("act_dtype")),
            "margin": jnp.full((ids.shape[0],), jnp.inf, F32),
            "act_dtype": None if r.get("act_dtype") is None
            else jnp.dtype(r["act_dtype"]).name,
            "causal": bool(r.get("causal", False)),
            "rows": jnp.asarray(np.asarray(
                r["rows"] if r.get("rows") is not None
                else np.arange(ids.shape[0])), jnp.int32)})
    static = _static(hp)
    for layer in weights["layers"]:
        layer = jax.device_put(layer)
        for s in state:
            s["x"], m = _layer(s["x"], layer, static, s["act_dtype"],
                               s["causal"])
            s["margin"] = jnp.minimum(s["margin"], m)
        # the layer's copy on the device is let go before the next comes up
        jax.block_until_ready([s["x"] for s in state])
        del layer
    lm_head = weights["lm_head"]
    V = lm_head.shape[1]
    parts = [[] for _ in state]
    for v0 in range(0, V, VOCAB_BLOCK):
        block = jnp.asarray(lm_head[:, v0:v0 + VOCAB_BLOCK])
        for s, out in zip(state, parts):
            out.append(_head(s["x"][s["rows"]], weights["final_norm"], block,
                             float(hp["eps"])))
    return [(jnp.concatenate(out, axis=1), s["margin"][s["rows"]])
            for s, out in zip(state, parts)]


def forward_logits(weights: Dict[str, Any], ids, hp: Dict[str, Any],
                   rows=None, with_margin: bool = False, act_dtype=None,
                   causal: bool = False):
    """Logits [T, V] (or of ``rows`` only) of one sequence ``ids`` [T] under
    the block rule; row t scores the token AT t. ``with_margin`` adds those
    positions' routing margins."""
    logits, margin = forward_many(
        weights, [dict(ids=ids, rows=rows, act_dtype=act_dtype,
                       causal=causal)], hp)[0]
    return (logits, margin) if with_margin else logits


def denoise_choice(logits, block_ids, n_take: int, rule: Dict[str, Any]):
    """One denoise pass's choice: ``logits`` ``[B, V]`` of the block
    ``block_ids`` ``[B]``; ``rule`` gives ``mask_token_id`` and, for the
    dynamic rule, ``threshold``. Returns ``(the block after the pass, x0 [B],
    conf [B] (0 where not masked), the positions that took their token)``.

    ``rule["order"]`` is for a check's CONTROLS, which have to come out as
    not correct: ``"least"`` ranks the masked positions by confidence the
    wrong way round, ``"position"`` takes them left to right whatever their
    confidence."""
    m = int(rule["mask_token_id"])
    lg = np.array(logits, np.float32)
    lg[:, m] = -np.inf
    x0 = lg.argmax(axis=-1)
    z = lg - lg.max(axis=-1, keepdims=True)
    conf = (1.0 / np.exp(z).sum(axis=-1)).astype(np.float32)
    ids = np.array(block_ids, np.int64)
    masked = ids == m
    conf = np.where(masked, conf, 0.0)
    cand = [i for i in range(len(ids)) if masked[i]]
    rank = {"least": lambda i: (conf[i], i), "position": lambda i: i}.get(
        rule.get("order"), lambda i: (-conf[i], i))
    best = sorted(cand, key=rank)[:int(n_take)]
    if rule.get("threshold") is not None:
        over = [i for i in cand if conf[i] > rule["threshold"]]
        if len(over) >= int(n_take):
            best = over
    for i in best:
        ids[i] = x0[i]
    return ids, x0, conf, sorted(best)


def generate(weights: Dict[str, Any], prompt, n: int, hp: Dict[str, Any],
             steps: int, rule: Optional[Dict[str, Any]] = None,
             trace: Optional[List] = None) -> List[int]:
    """``n`` tokens after ``prompt`` by diffusion over blocks, greedy,
    re-running the WHOLE sequence at every pass (no cache). ``rule``:
    ``{"threshold": t}`` for the dynamic rule, None for the static one.
    ``trace`` collects ``(context length, the block before the pass, x0,
    conf, the positions taken)`` of every pass."""
    B, m = int(hp["block_length"]), int(hp["mask_token_id"])
    rule = dict(rule or {}, mask_token_id=m)
    schedule = num_transfer_tokens(B, steps)
    prompt = [int(t) for t in prompt]
    whole = B * (len(prompt) // B)
    seq, lead = prompt[:whole], len(prompt) - whole
    block = prompt[whole:] + [m] * (B - lead)
    out: List[int] = []
    while len(out) < n:
        s = 0
        while m in block:
            rows = np.arange(len(seq), len(seq) + B)
            logits = forward_logits(weights, np.asarray(seq + block,
                                                        np.int32), hp, rows)
            new, x0, conf, took = denoise_choice(
                np.asarray(logits), block, schedule[min(s, steps - 1)], rule)
            if trace is not None:
                trace.append((len(seq), list(block), x0, conf, took))
            block = [int(t) for t in new]
            s += 1
        seq += block
        out += block[lead:]
        lead = 0
        block = [m] * B
    return out[:n]
