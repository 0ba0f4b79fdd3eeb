"""A plain reference for the afmoe family (Arcee Trinity).

Written from the layer equations of the modelling code published with the
checkpoints (``config.json``: ``model_type: afmoe``), in ``jax.numpy`` and
float32 with matmuls at the highest precision, with no kernel, cache,
batching or code of ``deepspeed_tpu``:

- ``x = embed[ids] * sqrt(hidden)`` (``mup_enabled``);
- each layer, four RMSNorms around two branches:
  ``x = x + post_attn_norm(attn(input_norm(x)))``;
  ``x = x + post_mlp_norm(ffn(pre_mlp_norm(x)))``;
- attention: ``q, k, v`` without bias; RMSNorm over each head's ``head_dim``
  values of ``q`` and of ``k`` (one learned gain per value); rotary position
  embedding (Su et al. 2021) on ``sliding`` layers ONLY, full layers use no
  position embedding at all; causal softmax attention with grouped queries
  (Ainslie et al. 2023), a sliding layer's position ``i`` sees
  ``i - W < j <= i``; then the gate: ``o_proj(out * sigmoid(h W_gate))`` with
  ``h`` the normed layer input;
- dense layers: SwiGLU (Shazeer 2020), ``(silu(h Wg) * (h Wu)) Wd``;
- MoE layers: ``scores = sigmoid(h W_r)``; the ``k`` experts chosen are the
  ``k`` largest of ``scores + expert_bias`` (the bias chooses, it does not
  weigh); their weights are their ``scores`` over the chosen scores' sum
  (``route_norm``), times ``route_scale``; the output is the weighted sum of
  the chosen experts' SwiGLU plus the shared expert's SwiGLU of the same
  input, unweighted;
- final RMSNorm, untied head.

Weights are a plain dict (all matrices ``[in, out]``)::

    {"embed": [V, H], "final_norm": [H], "lm_head": [H, V],
     "layers": [{"ln_in": [H], "ln_attn_out": [H], "ln_mlp_in": [H],
                 "ln_mlp_out": [H],
                 "wq": [H, Hq*D], "wk": [H, Hkv*D], "wv": [H, Hkv*D],
                 "wo": [Hq*D, H], "w_attn_gate": [H, Hq*D],
                 "q_norm": [D], "k_norm": [D],
                 # dense:  "w_gate": [H, F], "w_up": [H, F], "w_down": [F, H]
                 # sparse: "router": [H, E], "expert_bias": [E],
                 #         "w_gate": [E, H, F'], "w_up": ..., "w_down": ...,
                 #         "shared": {"w_gate": [H, F'], "w_up", "w_down"}
                 }, ...]}

and ``hp`` gives ``num_heads``, ``num_kv_heads``, ``head_dim``, ``eps``,
``rope_theta``, ``top_k``, ``route_norm``, ``route_scale``, ``embed_scale``
and, one entry per layer, ``windows`` (tokens, or None for a full layer) and
``rotary`` (bool).

Departures from the published code:

- rotation pairs ``(x[2i], x[2i+1])``, as ``decoder_ref.py`` and the
  program's zoo do; the published code pairs ``(x[i], x[i + D/2])``, the same
  function after a fixed permutation of each head's q/k columns;
- for memory only: attention runs one key/value group and one block of
  queries at a time, the experts one at a time, each over all tokens (every
  expert is evaluated for every token and masked by its routing weight,
  which is the same sum).

For tests and for sizing a tolerance, not for use: ``hp["weigh_with_bias"]``
weighs with the biased scores (a fault), ``hp["router_dtype"]`` computes the
router's scores in a lower precision, ``act_dtype`` rounds the activations
each branch hands on to a lower precision; a layer without ``w_attn_gate``
or ``shared`` is computed without them.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

# RMSNorm, the rotary embedding and SwiGLU are the decoder reference's own
from chipbench.reference.decoder_ref import F32, rms_norm, rope, swiglu

QUERY_BLOCK = 1024


def attention(q, k, v, window: Optional[int]):
    """q [T, Hq, D], k/v [T, Hkv, D] -> [T, Hq, D]; causal (+ window)."""
    t, hq, d = q.shape
    hkv = k.shape[1]
    rep = hq // hkv
    block = min(QUERY_BLOCK, t)
    pad = -t % block
    j = jnp.arange(t)[None, :]

    def one_group(args):
        qg, kg, vg = args                  # [rep, T + pad, D], [T, D], [T, D]

        def one_block(args):
            qb, i0 = args                                   # [rep, block, D]
            i = i0 + jnp.arange(block)[:, None]
            seen = j <= i
            if window is not None:
                seen = seen & (j > i - window)
            s = jnp.einsum("rtd,sd->rts", qb, kg) / jnp.sqrt(F32(d))
            s = jnp.where(seen[None], s, -jnp.inf)
            return jnp.einsum("rts,sd->rtd", jax.nn.softmax(s, axis=-1), vg)

        blocks = qg.reshape(rep, -1, block, d).transpose(1, 0, 2, 3)
        out = jax.lax.map(one_block,
                          (blocks, jnp.arange(blocks.shape[0]) * block))
        return out.transpose(1, 0, 2, 3).reshape(rep, -1, d)

    qg = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    qg = qg.transpose(1, 0, 2).reshape(hkv, rep, t + pad, d)
    out = jax.lax.map(one_group, (qg, k.transpose(1, 0, 2),
                                  v.transpose(1, 0, 2)))
    return out.reshape(hq, t + pad, d)[:, :t].transpose(1, 0, 2)


def route(h, layer: Dict[str, Any], hp: Dict[str, Any]):
    """Routing weight of every expert for every token [T, E] (0 where not
    chosen), and each token's margin [T]: the gap between the last expert
    chosen and the first left out, in ``scores + expert_bias``."""
    k = hp["top_k"]
    dt = hp.get("router_dtype", F32)
    scores = jax.nn.sigmoid(h.astype(dt) @ layer["router"].astype(dt)
                            ).astype(F32)                          # [T, E]
    biased = scores + layer["expert_bias"].astype(F32)
    top, idx = jax.lax.top_k(biased, k + 1)
    margin = top[:, k - 1] - top[:, k]
    idx = idx[:, :k]
    chosen = top[:, :k] if hp.get("weigh_with_bias") \
        else jnp.take_along_axis(scores, idx, axis=-1)
    if hp["route_norm"]:
        chosen = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    chosen = chosen * hp["route_scale"]
    dense = jnp.sum(jax.nn.one_hot(idx, scores.shape[-1], dtype=F32)
                    * chosen[..., None], axis=1)
    return dense, margin


def sparse_mixture(h, layer: Dict[str, Any], hp: Dict[str, Any]):
    dense, margin = route(h, layer, hp)

    def add_expert(acc, args):
        wg, wu, wd, weight = args
        return acc + weight[:, None] * swiglu(h, wg, wu, wd), None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(h),
                          (layer["w_gate"], layer["w_up"], layer["w_down"],
                           dense.T))
    if "shared" in layer:
        s = layer["shared"]
        out = out + swiglu(h, s["w_gate"], s["w_up"], s["w_down"])
    return out, margin


def hidden_states(weights: Dict[str, Any], ids, hp: Dict[str, Any],
                  act_dtype=None):
    """Final-norm hidden states [T, H] of one sequence ``ids`` [T], and each
    position's routing margin [T]: the least gap, over the MoE layers,
    between the last expert chosen and the first left out (in the router's
    own units, ``scores + expert_bias``). Where it is small the choice turns
    on rounding, and a system computing in bfloat16 may rightly choose
    otherwise."""
    hq, hkv, d = hp["num_heads"], hp["num_kv_heads"], hp["head_dim"]
    eps = hp["eps"]
    t = ids.shape[0]
    pos = jnp.arange(t)

    def handed_on(x):
        return x if act_dtype is None else x.astype(act_dtype).astype(F32)

    x = handed_on(weights["embed"][ids].astype(F32) * hp["embed_scale"])
    margin = jnp.full((t,), jnp.inf, F32)
    for layer, window, rotary in zip(weights["layers"], hp["windows"],
                                     hp["rotary"]):
        h = handed_on(rms_norm(x, layer["ln_in"], eps))
        q = rms_norm((h @ layer["wq"].astype(F32)).reshape(t, hq, d),
                     layer["q_norm"], eps)
        k = rms_norm((h @ layer["wk"].astype(F32)).reshape(t, hkv, d),
                     layer["k_norm"], eps)
        v = (h @ layer["wv"].astype(F32)).reshape(t, hkv, d)
        if rotary:
            q, k = rope(q, pos, hp["rope_theta"]), rope(k, pos, hp["rope_theta"])
        a = attention(handed_on(q), handed_on(k), handed_on(v),
                      window).reshape(t, hq * d)
        if "w_attn_gate" in layer:
            a = a * jax.nn.sigmoid(h @ layer["w_attn_gate"].astype(F32))
        a = handed_on(a) @ layer["wo"].astype(F32)
        x = handed_on(x + rms_norm(a, layer["ln_attn_out"], eps))
        h = handed_on(rms_norm(x, layer["ln_mlp_in"], eps))
        if "router" in layer:
            out, m = sparse_mixture(h, layer, hp)
            margin = jnp.minimum(margin, m)
        else:
            out = swiglu(h, layer["w_gate"], layer["w_up"], layer["w_down"])
        x = handed_on(x + rms_norm(out, layer["ln_mlp_out"], eps))
    return rms_norm(x, weights["final_norm"], eps), margin


def forward_logits(weights: Dict[str, Any], ids, hp: Dict[str, Any],
                   rows=None, with_margin: bool = False, act_dtype=None):
    """Logits [T, V] (or of ``rows`` only) of one sequence ``ids`` [T];
    ``with_margin`` adds those positions' routing margins."""
    with jax.default_matmul_precision("highest"):
        x, margin = hidden_states(weights, ids, hp, act_dtype)
        if rows is not None:
            x, margin = x[rows], margin[rows]
        logits = x @ weights["lm_head"].astype(F32)
    return (logits, margin) if with_margin else logits
