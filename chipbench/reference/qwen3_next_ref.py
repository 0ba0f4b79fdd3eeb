"""A plain reference for Qwen3-Next (``model_type: qwen3_next``;
Qwen3-Next-80B-A3B-Instruct): Gated DeltaNet layers — linear attention whose
state a head is corrected by a delta rule — beside gated softmax attention
every fourth layer, every feed-forward a mixture of small experts plus a
shared expert behind a sigmoid gate.

Written from the layer equations of the model's published description
(``config.json``, the catalog's ``described_as`` and the family's published
modelling code), in ``jax.numpy`` and float32 with matmuls at the highest
precision, with no kernel, cache, state pool, batching or code of
``deepspeed_tpu``. ``N(x; w) = x * rsqrt(mean(x^2) + eps) * (1 + w)`` for every
norm but the Gated DeltaNet's own ``Ng``, whose gain is plain ``w``. ``h =
embed[ids]``; per layer ``l``::

    h += mixer_l(N(h; ln_in));   h += moe(N(h; ln_ff))

then ``logits = N(h; final_norm) W_head`` (untied head). ``hp["kinds"][l]``
says which mixer layer ``l`` has (``"attention"`` where ``(l + 1) % 4 == 0``
in the published model):

- ``"attention"``, ``H`` query heads over ``Hkv`` key/value heads of ``D``:
  ``x W_q`` viewed ``[H, 2 D]`` gives each head's ``q`` (first ``D``) and
  gate ``g`` (last ``D``); ``k = x W_k``, ``v = x W_v`` ``[Hkv, D]``; ``q =
  N(q; q_norm)``, ``k = N(k; k_norm)`` over each head's ``D``; the first
  ``rotary_dim`` values of each head rotated by the position, value ``i``
  paired with value ``i + rotary_dim / 2`` (half-split), the rest pass;
  ``o = softmax_causal(q k^T * D ** -0.5) v``, a key/value head serving ``H
  / Hkv`` query heads; ``out = (o * sigmoid(g)) W_o``;
- ``"delta"`` (Gated DeltaNet; Yang, Kautz & Hatamizadeh 2024), ``Hk`` key
  heads of ``N``, ``Hv`` value heads of ``P``, ``R = Hv / Hk``, ``K`` taps,
  per token ``t``::

      x W_qkvz viewed [Hk, 2 N + 2 R P]: a key head's q (N), k (N), its R
          value heads' v (R P) and z (R P);   x W_ba viewed [Hk, 2 R]: b, a
      c_t = silu(sum_j w_conv[:, j] * [q | k | v]_{t-K+1+j})   (zeros before 0,
          no bias; q, k and v flattened: Hk N + Hk N + Hv P channels)
      q = c_q / sqrt(sum c_q^2 + 1e-6) * N ** -0.5;  k = c_k / sqrt(sum c_k^2
          + 1e-6)    (a head; key head j serves value heads j R .. j R + R - 1)
      beta = sigmoid(b);   g = -exp(A_log) * softplus(a + dt_bias)  (a value head)
      S'   = exp(g_t) S_{t-1};   S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
      o_t  = S_t^T q_t                                  (S a value head [N, P])
      y_t  = Ng(o_t) * silu(z_t)      (the norm over each head's P FIRST, then
                                       the gate)
      out_t = y_t W_out

  The recurrence is a ``lax.scan`` over tokens, one token at a time, from
  ``S = 0`` — not the chunked (WY) form the program's prompt rows take;
- the experts: ``p = softmax(u W_r)`` in float32 over all ``Er`` experts; the
  ``top_k`` largest, renormalised to sum 1; expert ``i`` is ``SwiGLU_i(u) =
  (silu(u W_gate,i) * (u W_up,i)) W_down,i``; ``moe = sum_chosen p_i
  SwiGLU_i(u) + sigmoid(u . w_sg) * SwiGLU_shared(u)``.

``hp["held"] = (first, count)`` gives the reference the same share of the
experts the program holds: the router scores all ``Er`` experts, chooses and
renormalises over all ``top_k`` chosen; the layer's stacks hold experts
``first .. first + count - 1`` and only assignments to those add to the
output. What the absent experts would have added is left out, and that
partial result goes on to the next layer.

Weights are a plain dict (all matrices ``[in, out]``, the fused ones in the
published order)::

    {"embed": [V, H], "final_norm": [H], "head": [H, V],
     "layers": [{"ln_in": [H], "ln_ff": [H], "router": [H, Er],
                 "w_gate", "w_up": [count, H, F], "w_down": [count, F, H],
                 "shared": {"w_gate", "w_up": [H, Fs], "w_down": [Fs, H]},
                 "shared_gate": [H, 1],
                 # attention: "wq": [H, Hq*2*D], "wk", "wv": [H, Hkv*D],
                 #            "wo": [Hq*D, H], "q_norm", "k_norm": [D]
                 # delta: "w_qkvz": [H, 2 Hk N + 2 Hv P], "w_ba": [H, 2 Hv],
                 #        "conv_w": [2 Hk N + Hv P, K], "b_dt", "A_log": [Hv],
                 #        "g_norm": [P], "w_out": [Hv P, H]
                 }, ...]}

and ``hp`` gives ``num_heads``, ``num_kv_heads``, ``head_dim``,
``rotary_dim``, ``rope_theta``, ``eps``, ``key_heads``, ``value_heads``,
``key_dim``, ``value_dim``, ``top_k``, ``held`` (or None) and ``kinds``. A
layer's weights may lie on the host (numpy): each layer is one jitted call
that is handed that layer's weights alone, the embedding is read on the host
and the head is computed a block of the vocabulary at a time, so that a
model that fills the device beside the engine is never there twice.

Departures from the published description: the multi-token-prediction module
(``mtp.*``) is not built (the published modelling code ignores it when it
serves without drafts); the held share above. For memory only: attention runs
one block of queries at a time, the held experts one at a time over all
tokens (every held expert is evaluated for every token and weighed by its
routing weight, 0 where not chosen: the same sum), the layers one jitted
call each, the head in blocks. The blocked attention, the rounding helpers
and the walk over the layers are ``granite_ref``'s (``attention``,
``rounded``, ``chosen``, :func:`forward_variants`'s shape); the layer
mathematics is this file's own.

A row's routing MARGIN is, at the least over the layers, how far the nearest
HELD expert is from changing sides of the selection, in the router's logits
(the selection over the softmax is the selection over the logits): a chosen
one above the first expert left out, one left out below the last chosen.

For tests and for sizing a tolerance, not for use: ``state_dtype`` rounds the
state to a lower precision after every token; ``act_dtype`` rounds what each
part of a layer hands on (the normed input, each product's result, the
convolution's output, the normalised q and k, the block's output, the
residual stream); ``unrounded`` names what of the recurrence's inputs that
mode still leaves in float32 (``"c"``: the convolution's output);
``hp["drop"]`` names parts to leave out (``"conv_history"``, ``"decay"``,
``"delta"``: add ``beta k v^T`` without the correction, ``"gate"``,
``"attn_gate"``, ``"shared"``, ``"shared_gate"``, ``"rope"``, ``"qk_norm"``),
``hp["gate_before_norm"]`` swaps the delta mixer's gate and norm,
``hp["plain_norm"]`` takes ``w`` for ``1 + w``, ``hp["interleaved_rope"]``
pairs value ``2i`` with ``2i + 1``, ``hp["norm_over_held"]`` renormalises the
routing weights over the held choices only (all faults).
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.decoder_ref import F32, swiglu
from chipbench.reference.granite_ref import (VOCAB_BLOCK, _static, attention,
                                             chosen, rounded)

DELTA, ATTENTION = "delta", "attention"


def norm(x, w, hp: Dict[str, Any]):
    """``N(x; w)``: the gain is ``1 + w``."""
    x = x.astype(F32)
    gain = w.astype(F32) if hp.get("plain_norm") else 1.0 + w.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + hp["eps"]) * gain


def rope(x, positions, theta: float, rotary_dim: int, interleaved=False):
    """``x`` [T, heads, D]: the first ``rotary_dim`` values of each head
    rotated by ``positions`` [T], value ``i`` paired with ``i + rotary_dim /
    2``."""
    half = rotary_dim // 2
    inv = theta ** (-jnp.arange(0, rotary_dim, 2, dtype=F32) / rotary_dim)
    ang = positions.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    if interleaved:
        a, b = x[..., 0:rotary_dim:2], x[..., 1:rotary_dim:2]
        rot = jnp.stack([a * cos - b * sin, b * cos + a * sin],
                        axis=-1).reshape(x.shape[:-1] + (rotary_dim,))
    else:
        a, b = x[..., :half], x[..., half:rotary_dim]
        rot = jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return jnp.concatenate([rot, x[..., rotary_dim:]], axis=-1)


def split_q_gate(qg, H: int, D: int):
    """``x W_q`` ``[T, H * 2 D]`` -> (``q`` ``[T, H, D]``, the gate ``[T, H *
    D]``): a head's query, then its gate."""
    T = qg.shape[0]
    qg = qg.reshape(T, H, 2 * D)
    return qg[..., :D], qg[..., D:].reshape(T, H * D)


def split_qkvz(qkvz, ba, hp: Dict[str, Any]):
    """``x W_qkvz`` and ``x W_ba`` -> ``(q [T, Hk, N], k [T, Hk, N], v [T,
    Hv, P], z [T, Hv, P], b [T, Hv], a [T, Hv])``."""
    Hk, Hv, N, P = (hp["key_heads"], hp["value_heads"], hp["key_dim"],
                    hp["value_dim"])
    R, T = Hv // Hk, qkvz.shape[0]
    x = qkvz.reshape(T, Hk, 2 * N + 2 * R * P)
    y = ba.reshape(T, Hk, 2 * R)
    return (x[..., :N], x[..., N:2 * N],
            x[..., 2 * N:2 * N + R * P].reshape(T, Hv, P),
            x[..., 2 * N + R * P:].reshape(T, Hv, P),
            y[..., :R].reshape(T, Hv), y[..., R:].reshape(T, Hv))


def recurrence(q, k, v, g, beta, state_round=None, drop=()):
    """The gated delta rule by itself, token by token from ``S = 0``: ``q``,
    ``k`` ``[T, Hv, N]``, ``v`` ``[T, Hv, P]``, ``g``, ``beta`` ``[T, Hv]``
    -> (``o`` ``[T, Hv, P]``, the last ``S`` ``[Hv, N, P]``)."""
    def step(S, row):
        q_t, k_t, v_t, g_t, b_t = row
        if "decay" not in drop:
            S = jnp.exp(g_t)[:, None, None] * S
        seen = 0.0 if "delta" in drop else jnp.einsum("hnp,hn->hp", S, k_t)
        S = S + k_t[:, :, None] * (b_t[:, None] * (v_t - seen))[:, None, :]
        if state_round is not None:
            S = state_round(S)
        return S, jnp.einsum("hnp,hn->hp", S, q_t)

    S0 = jnp.zeros(k.shape[1:] + v.shape[2:], F32)
    S, o = jax.lax.scan(step, S0, (q, k, v, g, beta))
    return o, S


def delta_mixer(u, layer: Dict[str, Any], hp: Dict[str, Any], act, wide,
                state_round):
    """``u`` [T, H] -> (out [T, H], the state after the last token ``[Hv, P,
    N]``)."""
    drop = hp.get("drop", ())
    Hk, Hv, N, P = (hp["key_heads"], hp["value_heads"], hp["key_dim"],
                    hp["value_dim"])
    f = lambda name: layer[name].astype(F32)
    T = u.shape[0]
    q, k, v, z, b, a = split_qkvz(act(u @ f("w_qkvz")), act(u @ f("w_ba")),
                                  hp)
    mixed = jnp.concatenate([q.reshape(T, -1), k.reshape(T, -1),
                             v.reshape(T, -1)], axis=-1)
    w, K = f("conv_w"), layer["conv_w"].shape[1]
    pad = jnp.pad(mixed, ((K - 1, 0), (0, 0)))
    taps = range(K - 1, K) if "conv_history" in drop else range(K)
    c = jax.nn.silu(sum(pad[j:j + T] * w[:, j] for j in taps))
    c = jnp.where(wide["c"], c, act(c))
    unit = lambda x: x * jax.lax.rsqrt(
        jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)
    per_v = lambda x: jnp.repeat(x, Hv // Hk, axis=1)
    q = act(per_v(unit(c[:, :Hk * N].reshape(T, Hk, N))) * N ** -0.5)
    k = act(per_v(unit(c[:, Hk * N:2 * Hk * N].reshape(T, Hk, N))))
    v = c[:, 2 * Hk * N:].reshape(T, Hv, P)
    g = -jnp.exp(f("A_log")) * jax.nn.softplus(a + f("b_dt"))
    o, S = recurrence(q, k, v, g, jax.nn.sigmoid(b), state_round, drop)
    gate = 1.0 if "gate" in drop else jax.nn.silu(z)
    normed = lambda y: y * jax.lax.rsqrt(
        jnp.mean(y * y, axis=-1, keepdims=True) + hp["eps"]) * f("g_norm")
    y = normed(o * gate) if hp.get("gate_before_norm") else normed(o) * gate
    return act(act(y.reshape(T, Hv * P)) @ f("w_out")), \
        jnp.swapaxes(S, 1, 2)


def attention_mixer(u, layer: Dict[str, Any], hp: Dict[str, Any], act):
    drop = hp.get("drop", ())
    f = lambda name: layer[name].astype(F32)
    T, H, Hkv, D = (u.shape[0], hp["num_heads"], hp["num_kv_heads"],
                    hp["head_dim"])
    q, gate = split_q_gate(act(u @ f("wq")), H, D)
    k = act(u @ f("wk")).reshape(T, Hkv, D)
    v = act(u @ f("wv")).reshape(T, Hkv, D)
    if "qk_norm" not in drop:
        q, k = act(norm(q, f("q_norm"), hp)), act(norm(k, f("k_norm"), hp))
    if "rope" not in drop:
        at = jnp.arange(T)
        turn = lambda x: act(rope(x, at, hp["rope_theta"], hp["rotary_dim"],
                                  bool(hp.get("interleaved_rope"))))
        q, k = turn(q), turn(k)
    o = attention(q, k, v, D ** -0.5).reshape(T, H * D)
    if "attn_gate" not in drop:
        o = o * jax.nn.sigmoid(gate)
    return act(act(o) @ f("wo"))


def route(u, layer: Dict[str, Any], hp: Dict[str, Any]):
    """Routing weight of every expert for every token ``[T, Er]`` (0 where
    not chosen), each token's margin ``[T]`` (the module's docstring) and
    whether each expert is held ``[Er]``."""
    k = hp["top_k"]
    logits = u.astype(F32) @ layer["router"].astype(F32)
    e = logits.shape[-1]
    first, count = hp.get("held") or (0, e)
    is_held = (jnp.arange(e) >= first) & (jnp.arange(e) < first + count)
    top, idx = jax.lax.top_k(logits, k + 1)
    last_in, first_out = top[:, k - 1:k], top[:, k:k + 1]
    margin = jnp.min(jnp.where(
        is_held, jnp.where(logits >= last_in, logits - first_out,
                           last_in - logits), jnp.inf), axis=-1)
    p = jax.nn.softmax(logits, axis=-1)
    picked = p * jnp.sum(jax.nn.one_hot(idx[:, :k], e, dtype=F32), axis=1)
    counted = picked * is_held if hp.get("norm_over_held") else picked
    return picked / (jnp.sum(counted, axis=-1, keepdims=True) + 1e-30), \
        margin, is_held


def sparse_mixture(u, layer: Dict[str, Any], hp: Dict[str, Any]):
    """``sum_chosen-and-held p_i SwiGLU_i(u) + sigmoid(u . w_sg)
    SwiGLU_shared(u)`` and the rows' margins in this layer."""
    drop = hp.get("drop", ())
    dense, margin, _ = route(u, layer, hp)
    first, count = hp.get("held") or (0, dense.shape[-1])

    def add_expert(acc, args):
        wg, wu, wd, weight = args
        return acc + weight[:, None] * swiglu(u, wg, wu, wd), None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(u),
                          (layer["w_gate"], layer["w_up"], layer["w_down"],
                           dense[:, first:first + count].T))
    if "shared" not in drop:
        s = layer["shared"]
        shared = swiglu(u, s["w_gate"], s["w_up"], s["w_down"])
        if "shared_gate" not in drop:
            shared = shared * jax.nn.sigmoid(
                u @ layer["shared_gate"].astype(F32))
        out = out + shared
    return out, margin


@functools.partial(jax.jit, static_argnames=("kind", "hp", "act_dtypes",
                                             "state_dtypes"))
def _layer(x, layer, kind: str, hp, act_dtypes, state_dtypes, mode):
    """One layer; ``mode`` holds the traced choices: ``act`` and ``state``
    (0, or which of ``act_dtypes`` / ``state_dtypes`` to round to) and ``c``
    (leave the convolution's output unrounded)."""
    hp = dict(hp)
    act = lambda v: chosen(v, act_dtypes, mode["act"])
    S = jnp.zeros((0,), F32)
    with jax.default_matmul_precision("highest"):
        u = act(norm(x, layer["ln_in"], hp))
        if kind == DELTA:
            mixed, S = delta_mixer(
                u, layer, hp, act, mode,
                lambda S: chosen(S, state_dtypes, mode["state"]))
        else:
            mixed = attention_mixer(u, layer, hp, act)
        x = act(x + mixed)
        out, margin = sparse_mixture(act(norm(x, layer["ln_ff"], hp)), layer,
                                     hp)
        return act(x + act(out)), S, margin


@functools.partial(jax.jit, static_argnames=("hp",))
def _head(x, final_norm, block, hp):
    with jax.default_matmul_precision("highest"):
        return norm(x, final_norm, dict(hp)) @ block.astype(F32)


def forward_variants(weights: Dict[str, Any], ids, hp: Dict[str, Any],
                     variants, rows=None):
    """Several forwards of one sequence ``ids`` [T] in one walk over the
    layers, each layer's weights handed to the device ONCE for all of them.
    ``variants`` is a list of dicts of :func:`forward_logits`'s options
    (``held``, ``act_dtype``, ``state_dtype``, ``unrounded``, ``head``);
    returns for each ``(logits of rows or None, margins of rows, the delta
    layers' states [Ld, Hv, P, N])``."""
    ids = np.asarray(ids, np.int32)
    rows = np.arange(ids.shape[0]) if rows is None else np.asarray(rows)
    embed = weights["embed"]
    low = lambda key: tuple(dict.fromkeys(
        jnp.dtype(v[key]).name for v in variants
        if v.get(key) is not None and jnp.dtype(v[key]) != jnp.dtype(F32)))
    act_dtypes, state_dtypes = low("act_dtype"), low("state_dtype")
    which = lambda names, d: 0 if d is None or jnp.dtype(d).name not in names \
        else names.index(jnp.dtype(d).name) + 1
    runs = []
    for v in variants:
        h = dict(hp)
        if v.get("held") is not None:
            h["held"] = tuple(v["held"])
        wide = v.get("unrounded", hp.get("unrounded", ()))
        h.pop("unrounded", None)
        mode = {"act": jnp.int32(which(act_dtypes, v.get("act_dtype"))),
                "state": jnp.int32(which(state_dtypes, v.get("state_dtype"))),
                "c": jnp.asarray("c" in wide)}
        # (the embedding may lie on the host: its rows are read there)
        x = rounded(jnp.asarray(np.asarray(embed)[ids]).astype(F32),
                    v.get("act_dtype"))
        runs.append({"hp": _static(h), "x": x, "states": [], "mode": mode,
                     "margin": jnp.full((ids.shape[0],), jnp.inf, F32),
                     "head": v.get("head", True)})
    for kind, layer in zip(hp["kinds"], weights["layers"]):
        layer = jax.device_put(layer)
        for r in runs:
            r["x"], S, m = _layer(r["x"], layer, kind, r["hp"], act_dtypes,
                                  state_dtypes, r["mode"])
            r["margin"] = jnp.minimum(r["margin"], m)
            if kind == DELTA:
                r["states"].append(S)
        # one layer's copy on the device at a time (granite_ref's reason)
        jax.block_until_ready([r["x"] for r in runs])
        del layer
    at = jnp.asarray(rows)
    heads = [r for r in runs if r["head"]]
    head = weights["head"]
    V = head.shape[1]
    parts = [[] for _ in heads]
    for v0 in range(0, V if heads else 0, VOCAB_BLOCK):
        block = jnp.asarray(head[:, v0:v0 + VOCAB_BLOCK])
        for r, out in zip(heads, parts):
            out.append(_head(r["x"][at], weights["final_norm"], block,
                             r["hp"]))
    for r, out in zip(heads, parts):
        r["logits"] = jnp.concatenate(out, axis=1)
    return [(r.get("logits"), r["margin"][at],
             jnp.stack(r["states"]) if r["states"] else None) for r in runs]


def forward_logits(weights: Dict[str, Any], ids, hp: Dict[str, Any],
                   held=None, rows=None, with_margin: bool = False,
                   with_state: bool = False, act_dtype=None,
                   state_dtype=None, head: bool = True):
    """Logits [T, V] (or of ``rows`` only) of one sequence ``ids`` [T], given
    the share ``held`` of the experts (default ``hp["held"]``; None: all).
    ``with_margin`` adds those positions' routing margins, ``with_state`` the
    delta layers' states after the last token ``[Ld, Hv, P, N]``;
    ``head=False`` skips the logits (None in their place)."""
    logits, margin, states = forward_variants(
        weights, ids, hp, [dict(held=held, act_dtype=act_dtype,
                                state_dtype=state_dtype, head=head)],
        rows=rows)[0]
    out = (logits,)
    if with_margin:
        out += (margin,)
    if with_state:
        out += (states,)
    return out if len(out) > 1 else logits
