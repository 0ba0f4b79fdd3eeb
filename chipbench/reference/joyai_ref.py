"""A plain reference for the JoyAI-LLM-Flash family (``model_type:
joyai_llm_flash``): multi-head latent attention over a sparse mixture of
experts with a sigmoid router.

Written from the layer equations of the modelling code this lineage
publishes with its checkpoints (DeepSeek-V2's attention, DeepSeek-V3's
``noaux_tc`` router with one group), in ``jax.numpy`` and float32 with
matmuls at the highest precision, in the EXPANDED form only — keys and
values of every head made from the latent — with no kernel, cache, batching
or code of ``deepspeed_tpu``. Per layer, ``h = rms_norm(x; ln_in)``:

- ``c_q = rms_norm(h W_qa; q_a_norm)``; ``q = c_q W_qb`` -> ``[T, H, nope +
  rope]``, split ``q_nope | q_rope``;
- ``[c_kv | k_rope] = h W_kva``; ``c_kv = rms_norm(c_kv; kv_a_norm)``;
  ``k_rope`` is ONE rotary key shared by all heads; rotary position embedding
  (Su et al. 2021) on ``q_rope`` and ``k_rope`` only;
- ``[k_nope | v] = c_kv W_kvb`` per head; ``k = [k_nope | k_rope]``;
  ``o = softmax_causal(q k^T (nope + rope)^-1/2) v``; ``x += o W_o``;
- ``h2 = rms_norm(x; ln_mlp)``; dense layers: ``x += SwiGLU(h2)``; MoE
  layers: ``scores = sigmoid(h2 W_r)``; the ``k`` experts chosen are the
  ``k`` largest of ``scores + expert_bias`` (``e_score_correction_bias``:
  it chooses, it does not weigh); their weights are their ``scores`` over
  the chosen scores' sum + 1e-20 (``route_norm``), times ``route_scale``;
  ``x += sum_k w_k SwiGLU^(e_k)(h2) + SwiGLU^shared(h2)``, shared unweighted;
- final RMSNorm, untied head. The multi-token-prediction module feeds no
  logit of the main model and is not here.

``hp["held"] = (first, count)`` gives the reference the same share of the
experts the program holds: the router scores all experts and normalises over
all ``k`` chosen; the layer's ``w_gate``/``w_up``/``w_down`` stacks hold
experts ``first .. first + count - 1`` and only assignments to those add to
the output. What the absent experts would have added is left out.

Weights are a plain dict (all matrices ``[in, out]``)::

    {"embed": [V, H], "final_norm": [H], "lm_head": [H, V],
     "layers": [{"ln_in": [H], "ln_mlp": [H],
                 "wqa": [H, Rq], "q_a_norm": [Rq], "wqb": [Rq, Hq*(nope+rope)],
                 "wkva": [H, R + rope], "kv_a_norm": [R],
                 "wkvb": [R, Hq*(nope+v)], "wo": [Hq*v, H],
                 # dense:  "w_gate": [H, F], "w_up": [H, F], "w_down": [F, H]
                 # sparse: "router": [H, E], "expert_bias": [E],
                 #         "w_gate": [count, H, F'], "w_up": ..., "w_down": ...,
                 #         "shared": {"w_gate": [H, F'], "w_up", "w_down"}
                 }, ...]}

and ``hp`` gives ``num_heads``, ``kv_lora_rank``, ``qk_nope_head_dim``,
``qk_rope_head_dim``, ``v_head_dim``, ``eps``, ``rope_theta``, ``top_k``,
``route_norm``, ``route_scale`` and ``held`` (or None: all experts).

Departures from the published code:

- rotation pairs ``(x[2i], x[2i+1])``, as ``decoder_ref.py`` and the
  program's zoo do (and as ``rope_interleave: true`` lays a checkpoint out);
- for memory only: attention runs one head and one block of queries at a
  time, the held experts one at a time, each over all tokens (every held
  expert is evaluated for every token and masked by its routing weight, the
  same sum), and the layers are called one by one (each compiled once a
  kind), so that the whole model's weights are never upcast at once.

A row's routing MARGIN is, at the least over the MoE layers, how far the
nearest HELD expert is from changing sides of the selection: a chosen one's
``score + bias`` above the first expert left out, one left out below the
last chosen. With every expert held that is the gap between the last chosen
and the first left out. A swap between two absent experts changes no held
expert's part, only the normaliser, and that by the gap itself; a held
expert ranked tenth within rounding of the eighth is a choice that rounding
may make otherwise, whoever is ninth.

For tests and for sizing a tolerance, not for use: ``hp["weigh_with_bias"]``
weighs with the biased scores, ``hp["norm_over_held"]`` normalises over the
held choices only, ``hp["k_rope_unrotated"]`` leaves the shared key
unrotated, ``hp["softmax_scale"]`` replaces ``(nope + rope)^-1/2``,
``hp["router_dtype"]`` computes the router's scores in a lower precision
(all faults), ``act_dtype`` rounds the activations each branch hands on to a
lower precision — unless ``rounding``, a traced flag, is false: the float32
reference and its low-precision control are then ONE compiled program a kind
of layer (a float32 product at the highest precision compiles for seconds;
compile, PR 33); a layer without ``kv_a_norm`` or ``shared`` is computed
without them.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

# RMSNorm, the rotary embedding and SwiGLU are the decoder reference's own
from chipbench.reference.decoder_ref import F32, rms_norm, rope, swiglu

QUERY_BLOCK = 1024


def rounded_to(x, dtype):
    """``x`` (float32) with the values type ``dtype`` can hold, still in
    float32; None or float32 leaves it. Spelled as ``reduce_precision``, not
    as a cast there and back: under ``jit`` the TPU compiler drops such a
    pair of converts (a router reference "in bfloat16" then read 4e-6 from
    the float32 one where it reads 0.33; my chip runs, PR 33)."""
    if dtype is None or jnp.dtype(dtype) == jnp.dtype(F32):
        return x
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, exponent_bits=info.nexp,
                                    mantissa_bits=info.nmant)


def attention(q, k, v, scale: float):
    """q/k [T, H, Dk], v [T, H, Dv] -> [T, H, Dv]; causal."""
    t, h, dk = q.shape
    block = min(QUERY_BLOCK, t)
    pad = -t % block
    j = jnp.arange(t)[None, :]

    def one_head(args):
        qh, kh, vh = args                    # [T + pad, Dk], [T, Dk], [T, Dv]

        def one_block(args):
            qb, i0 = args
            i = i0 + jnp.arange(block)[:, None]
            s = jnp.where(j <= i, (qb @ kh.T) * scale, -jnp.inf)
            return jax.nn.softmax(s, axis=-1) @ vh

        blocks = qh.reshape(-1, block, dk)
        out = jax.lax.map(one_block,
                          (blocks, jnp.arange(blocks.shape[0]) * block))
        return out.reshape(-1, vh.shape[-1])

    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    out = jax.lax.map(one_head, (qp.transpose(1, 0, 2), k.transpose(1, 0, 2),
                                 v.transpose(1, 0, 2)))
    return out[:, :t].transpose(1, 0, 2)


def route(h, layer: Dict[str, Any], hp: Dict[str, Any]):
    """Routing weight of every expert for every token [T, E] (0 where not
    chosen), each token's margin [T] (the module's docstring) and whether
    each expert is held [E]."""
    k = hp["top_k"]
    dt = hp.get("router_dtype")         # inputs, product and scores rounded
    logits = rounded_to(rounded_to(h.astype(F32), dt)
                        @ rounded_to(layer["router"].astype(F32), dt), dt)
    scores = rounded_to(jax.nn.sigmoid(logits), dt)                # [T, E]
    e = scores.shape[-1]
    first, count = hp.get("held") or (0, e)
    is_held = (jnp.arange(e) >= first) & (jnp.arange(e) < first + count)
    biased = scores + layer["expert_bias"].astype(F32)
    top, idx = jax.lax.top_k(biased, k + 1)
    last_in, first_out = top[:, k - 1:k], top[:, k:k + 1]
    # how far the nearest HELD expert is from changing sides: a chosen one
    # from the first left out, one left out from the last chosen
    margin = jnp.min(jnp.where(
        is_held, jnp.where(biased >= last_in, biased - first_out,
                           last_in - biased), jnp.inf), axis=-1)
    idx = idx[:, :k]
    chosen = top[:, :k] if hp.get("weigh_with_bias") \
        else jnp.take_along_axis(scores, idx, axis=-1)
    if hp["route_norm"]:
        over = chosen * is_held[idx] if hp.get("norm_over_held") else chosen
        chosen = chosen / (jnp.sum(over, axis=-1, keepdims=True) + 1e-20)
    chosen = chosen * hp["route_scale"]
    dense = jnp.sum(jax.nn.one_hot(idx, e, dtype=F32) * chosen[..., None],
                    axis=1)
    return dense, margin, is_held


def sparse_mixture(h, layer: Dict[str, Any], hp: Dict[str, Any]):
    dense, margin, _ = route(h, layer, hp)
    first, count = hp.get("held") or (0, dense.shape[-1])

    def add_expert(acc, args):
        wg, wu, wd, weight = args
        return acc + weight[:, None] * swiglu(h, wg, wu, wd), None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(h),
                          (layer["w_gate"], layer["w_up"], layer["w_down"],
                           dense[:, first:first + count].T))
    if "shared" in layer:
        s = layer["shared"]
        out = out + swiglu(h, s["w_gate"], s["w_up"], s["w_down"])
    return out, margin


@functools.partial(jax.jit, static_argnames=("hp_items", "act_dtype"))
def one_layer(layer: Dict[str, Any], x, hp_items, act_dtype=None,
              rounding=True):
    """``x`` [T, H] through one layer: ``(x, the rows' margins in it)``;
    ``hp_items`` is ``hp`` as sorted items (a static argument of the jit);
    ``rounding`` false leaves what ``act_dtype`` would round as it is."""
    hp = dict(hp_items)
    hq, r = hp["num_heads"], hp["kv_lora_rank"]
    dn, dr, dv = (hp["qk_nope_head_dim"], hp["qk_rope_head_dim"],
                  hp["v_head_dim"])
    eps, t = hp["eps"], x.shape[0]
    pos = jnp.arange(t)

    def handed_on(a):
        return jnp.where(rounding, rounded_to(a, act_dtype), a)

    with jax.default_matmul_precision("highest"):
        h = handed_on(rms_norm(x, layer["ln_in"], eps))
        cq = handed_on(rms_norm(h @ layer["wqa"].astype(F32),
                                layer["q_a_norm"], eps))
        q = (cq @ layer["wqb"].astype(F32)).reshape(t, hq, dn + dr)
        kva = h @ layer["wkva"].astype(F32)
        ckv = kva[:, :r]
        if "kv_a_norm" in layer:
            ckv = rms_norm(ckv, layer["kv_a_norm"], eps)
        ckv = handed_on(ckv)
        k_rope = kva[:, None, r:]
        if not hp.get("k_rope_unrotated"):
            k_rope = rope(k_rope, pos, hp["rope_theta"])
        q = jnp.concatenate(
            [q[..., :dn], rope(q[..., dn:], pos, hp["rope_theta"])], axis=-1)
        kv = (ckv @ layer["wkvb"].astype(F32)).reshape(t, hq, dn + dv)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_rope, (t, hq, dr))], axis=-1)
        a = attention(handed_on(q), handed_on(k), handed_on(kv[..., dn:]),
                      hp.get("softmax_scale", (dn + dr) ** -0.5))
        x = handed_on(x + handed_on(a.reshape(t, hq * dv))
                      @ layer["wo"].astype(F32))
        h = handed_on(rms_norm(x, layer["ln_mlp"], eps))
        if "router" in layer:
            out, margin = sparse_mixture(h, layer, hp)
        else:
            out = swiglu(h, layer["w_gate"], layer["w_up"], layer["w_down"])
            margin = jnp.full((t,), jnp.inf, F32)
        return handed_on(x + out), margin


def hidden_states(weights: Dict[str, Any], ids, hp: Dict[str, Any],
                  act_dtype=None, rounding=True):
    """Final-norm hidden states [T, H] of one sequence ``ids`` [T], and each
    position's routing margin [T] (the module's docstring). Where it is
    small the choice turns on rounding, and a system computing in bfloat16
    may rightly choose otherwise."""
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
        # (a head handed up from the host goes to the device as it is stored
        # and is upcast there)
        logits = x @ jnp.asarray(weights["lm_head"]).astype(F32)
    return (logits, margin) if with_margin else logits
