"""A plain reference for NVIDIA Nemotron-H (``model_type: nemotron_h``;
Nemotron 3 Nano 30B-A3B): layers that are ONE block each — a Mamba-2 mixer
with several groups of B and C, OR a mixture of two-matrix relu^2 experts
behind a sigmoid router with a shared expert, OR grouped-query attention.

Written from the layer equations of the model's published description
(``config.json`` and the catalog's ``described_as``), in ``jax.numpy`` and
float32 with matmuls at the highest precision, with no kernel, cache, state
pool, batching or code of ``deepspeed_tpu``. ``x = embed[ids]``; per layer,
whatever its block:

    x += block(rms_norm(x; ln))

then ``logits = rms_norm(x; final_norm) W_head`` (untied head).
``rms_norm(x; w) = x * rsqrt(mean(x^2) + eps) * w``. ``hp["kinds"][i]`` says
which block layer ``i`` is:

- ``"mamba"`` (Dao & Gu 2024), ``H`` heads of ``P``, ``E = H P``, ``N`` state
  values, ``G`` groups, ``K`` taps, per token ``t``::

      [z_t (E) | xBC_t (E + 2 G N) | dt_t (H)] = W_in u_t
      xBC_t = silu(b_conv + sum_j w_conv[:, j] * xBC_{t-K+1+j})  (zeros before 0)
      [X_t (H, P) | B_t (G, N) | C_t (G, N)] = xBC_t
      D_t = softplus(dt_t + dt_bias);   a = -exp(A_log)            (a head)
      g(h) = h // (H / G)                                   (a head's group)
      S_t[h] = exp(D_t[h] a[h]) S_{t-1}[h] + D_t[h] X_t[h] (outer) B_t[g(h)]
      y_t[h] = S_t[h] C_t[g(h)] + D[h] X_t[h]
      q_t = y_t * silu(z_t)
      n_t[group] = q_t[group] * rsqrt(mean(q_t[group]^2) + eps) * w_norm[group]
      block_t = W_out n_t

  the gate first, then the norm, each group of ``E / G`` channels normalised
  by itself. The recurrence is a ``lax.scan`` over tokens, one token at a
  time, from ``S = 0`` — not the chunked product form the program's prompt
  rows take. ``time_step_limit`` is ``(0, inf)``, the config class's default:
  no clamp on ``D_t`` (``time_step_min/max/floor`` are initialisation only);
- ``"moe"``: ``s = sigmoid(u W_r)`` in float32 over all ``Er`` experts; the
  ``top_k`` largest of ``s + bias`` are chosen (``e_score_correction_bias``
  chooses, it does not weigh; ``n_group`` 1: no group restriction); weights
  ``s_i / sum_chosen s`` times ``route_scale``; expert ``i`` is ``relu(u
  W_up,i)^2 W_down,i`` (two matrices, no gate); ``block = sum_k w_k expert_k(u)
  + shared(u)``, the shared expert the same form at its own width, every
  token, unweighted;
- ``"attention"``: ``q = u W_q`` ``[T, Hq, D]``, ``k, v`` ``[T, Hkv, D]``, no
  bias, NO rotation or position term of any kind (assumed: the published
  config carries ``rope_theta`` and the model type's attention reads none);
  ``o = softmax_causal(q k^T * D ** -0.5) v`` with grouped queries; ``block =
  o W_o``.

``hp["held"] = (first, count)`` gives the reference the same share of the
experts the program holds: the router scores all ``Er`` experts, chooses and
normalises over all ``top_k`` chosen; the layer's ``w_up``/``w_down`` stacks
hold experts ``first .. first + count - 1`` and only assignments to those add
to the output. What the absent experts would have added is left out.

Weights are a plain dict (all matrices ``[in, out]``)::

    {"embed": [V, H], "final_norm": [H], "head": [H, V],
     "layers": [{"ln": [H],
                 # moe: "router": [H, Er], "bias": [Er],
                 #      "w_up": [count, H, F], "w_down": [count, F, H],
                 #      "shared": {"w_up": [H, Fs], "w_down": [Fs, H]}
                 # attention: "wq": [H, Hq*D], "wk", "wv": [H, Hkv*D],
                 #            "wo": [Hq*D, H]
                 # mamba: "w_in": [H, 2E + 2GN + Hm], "conv_w": [E + 2GN, K],
                 #        "conv_b": [E + 2GN], "b_dt": [Hm], "A_log": [Hm],
                 #        "D": [Hm], "g_norm": [E], "w_out": [E, H]
                 }, ...]}

and ``hp`` gives ``num_heads``, ``num_kv_heads``, ``head_dim``, ``eps``,
``mamba_heads``, ``mamba_head_dim``, ``d_state``, ``n_groups``, ``top_k``,
``route_scale``, ``held`` (or None) and ``kinds``. A layer's weights may lie
on the host (numpy): each layer is one jitted call that is handed that
layer's weights alone, the embedding is read on the host and the head is
computed a block of the vocabulary at a time, so that a model that fills the
device beside the engine is never there twice.

Departures from the published description: none intended. For memory only:
attention runs one block of queries at a time, the held experts one at a
time over all tokens (every held expert is evaluated for every token and
weighed by its routing weight, 0 where not chosen: the same sum), the layers
one jitted call each, the head in blocks. The blocked evaluation, the
rounding helpers and the walk over the layers are ``granite_ref``'s
(``attention``, ``rounded``, ``chosen``, :func:`forward_variants`'s shape);
the layer mathematics is this file's own.

A row's routing MARGIN is, at the least over the ``"moe"`` layers, how far
the nearest HELD expert is from changing sides of the selection, in the
biased scores ``s + bias`` the selection is made on: a chosen one above the
first expert left out, one left out below the last chosen. Where it is small
the choice turns on rounding, and a system computing in bfloat16 may rightly
choose otherwise.

For tests and for sizing a tolerance, not for use: ``state_dtype`` rounds the
state to a lower precision after every token; ``act_dtype`` rounds what each
part of a layer hands on (the normed input, each product's result, the
convolution's output, the block's output, the residual stream);
``unrounded`` names what of the recurrence's inputs that mode still leaves
in float32 (``"c"``: x, ``"B_C"``: B and C); ``hp["drop"]`` names parts to
leave out (``"conv_history"``, ``"D"``, ``"gate"``, ``"gate_norm"``,
``"shared"``, ``"bias"``, ``"route_scale"``), ``hp["norm_before_gate"]``
swaps the gate and the norm, ``hp["one_group"]`` reads every head from group
0 and normalises over all ``E``, ``hp["gelu"]`` puts tanh-gelu where relu^2
is, ``hp["norm_over_held"]`` normalises the routing weights over the held
choices only (all faults).
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.decoder_ref import F32, rms_norm
from chipbench.reference.granite_ref import (VOCAB_BLOCK, _static, attention,
                                             chosen, rounded)

MAMBA, MOE, ATTENTION = "mamba", "moe", "attention"


def recurrence(dt, x, Bm, Cm, a, state_round=None):
    """The Mamba-2 recurrence by itself, token by token from ``S = 0``:
    ``dt`` ``[T, H]``, ``x`` ``[T, H, P]``, ``Bm``, ``Cm`` ``[T, G, N]``
    (head ``h`` reads group ``h // (H / G)``), ``a`` ``[H]`` (negative) ->
    (``S_t C_t`` for every token ``[T, H, P]``, the last ``S`` ``[H, P,
    N]``)."""
    H, G = x.shape[1], Bm.shape[1]
    group = jnp.arange(H) // (H // G)

    def step(S, row):
        dt_t, x_t, b_t, c_t = row
        S = jnp.exp(dt_t * a)[:, None, None] * S \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[group][:, None, :]
        if state_round is not None:
            S = state_round(S)
        return S, jnp.sum(S * c_t[group][:, None, :], axis=-1)

    S0 = jnp.zeros(x.shape[1:] + Bm.shape[2:], F32)
    S, y = jax.lax.scan(step, S0, (dt, x, Bm, Cm))
    return y, S


def mamba_block(u, layer: Dict[str, Any], hp: Dict[str, Any], act, wide,
                state_round):
    """``u`` [T, H] -> (out [T, H], the state after the last token
    ``[Hm, P, N]``)."""
    drop = hp.get("drop", ())
    Hm, P, N, G = (hp["mamba_heads"], hp["mamba_head_dim"], hp["d_state"],
                   hp["n_groups"])
    E, GN = Hm * P, G * N
    f = lambda name: layer[name].astype(F32)
    T = u.shape[0]
    zxd = act(u @ f("w_in"))
    z, a_in, dt = zxd[:, :E], zxd[:, E:2 * E + 2 * GN], zxd[:, 2 * E + 2 * GN:]
    w, K = f("conv_w"), layer["conv_w"].shape[1]
    pad = jnp.pad(a_in, ((K - 1, 0), (0, 0)))
    taps = range(K - 1, K) if "conv_history" in drop else range(K)
    c = jax.nn.silu(f("conv_b") + sum(pad[j:j + T] * w[:, j] for j in taps))
    x = jnp.where(wide["c"], c[:, :E], act(c[:, :E])).reshape(T, Hm, P)
    Bm, Cm = (jnp.where(wide["B_C"], v, act(v)).reshape(T, G, N)
              for v in (c[:, E:E + GN], c[:, E + GN:]))
    if hp.get("one_group"):
        Bm, Cm = (jnp.broadcast_to(v[:, :1], v.shape) for v in (Bm, Cm))
    dt = jax.nn.softplus(dt + f("b_dt"))
    y, S = recurrence(dt, x, Bm, Cm, -jnp.exp(f("A_log")), state_round)
    if "D" not in drop:
        y = y + f("D")[:, None] * x
    y = y.reshape(T, E)
    groups = 1 if hp.get("one_group") else G

    def norm(q):
        if "gate_norm" in drop:
            return q
        q = q.reshape(T, groups, E // groups)
        q = q * jax.lax.rsqrt(jnp.mean(q * q, axis=-1, keepdims=True)
                              + hp["eps"])
        return q.reshape(T, E) * f("g_norm")

    gate = jax.nn.silu(z)
    if "gate" in drop:
        n = norm(y)
    elif hp.get("norm_before_gate"):
        n = norm(y) * gate
    else:
        n = norm(y * gate)
    return act(act(n) @ f("w_out")), S


def route(u, layer: Dict[str, Any], hp: Dict[str, Any]):
    """Routing weight of every expert for every token ``[T, Er]`` (0 where
    not chosen), each token's margin ``[T]`` (the module's docstring) and
    whether each expert is held ``[Er]``."""
    k, drop = hp["top_k"], hp.get("drop", ())
    scores = jax.nn.sigmoid(u.astype(F32) @ layer["router"].astype(F32))
    biased = scores if "bias" in drop else scores + layer["bias"].astype(F32)
    e = scores.shape[-1]
    first, count = hp.get("held") or (0, e)
    is_held = (jnp.arange(e) >= first) & (jnp.arange(e) < first + count)
    top, idx = jax.lax.top_k(biased, k + 1)
    last_in, first_out = top[:, k - 1:k], top[:, k:k + 1]
    margin = jnp.min(jnp.where(
        is_held, jnp.where(biased >= last_in, biased - first_out,
                           last_in - biased), jnp.inf), axis=-1)
    on = jnp.sum(jax.nn.one_hot(idx[:, :k], e, dtype=F32), axis=1)
    picked = scores * on
    counted = picked * is_held if hp.get("norm_over_held") else picked
    dense = picked / (jnp.sum(counted, axis=-1, keepdims=True) + 1e-20)
    if "route_scale" not in drop:
        dense = dense * hp["route_scale"]
    return dense, margin, is_held


def relu2_mlp(u, w_up, w_down, gelu: bool = False):
    h = u @ w_up.astype(F32)
    h = jax.nn.gelu(h) if gelu else jnp.square(jnp.maximum(h, 0.0))
    return h @ w_down.astype(F32)


def expert_block(u, layer: Dict[str, Any], hp: Dict[str, Any]):
    """``sum_k w_k expert_k(u) + shared(u)`` and the rows' margins."""
    dense, margin, _ = route(u, layer, hp)
    first, count = hp.get("held") or (0, dense.shape[-1])
    gelu = bool(hp.get("gelu"))

    def add_expert(acc, args):
        wu, wd, weight = args
        return acc + weight[:, None] * relu2_mlp(u, wu, wd, gelu), None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(u),
                          (layer["w_up"], layer["w_down"],
                           dense[:, first:first + count].T))
    if "shared" not in hp.get("drop", ()):
        s = layer["shared"]
        out = out + relu2_mlp(u, s["w_up"], s["w_down"], gelu)
    return out, margin


@functools.partial(jax.jit, static_argnames=("kind", "hp", "act_dtypes",
                                             "state_dtypes"))
def _layer(x, layer, kind: str, hp, act_dtypes, state_dtypes, mode):
    """One layer; ``mode`` holds the traced choices: ``act`` and ``state``
    (0, or which of ``act_dtypes`` / ``state_dtypes`` to round to), ``c`` and
    ``B_C`` (leave that input of the recurrence unrounded)."""
    hp = dict(hp)
    act = lambda v: chosen(v, act_dtypes, mode["act"])
    T = x.shape[0]
    S, margin = jnp.zeros((0,), F32), jnp.full((T,), jnp.inf, F32)
    with jax.default_matmul_precision("highest"):
        f = lambda name: layer[name].astype(F32)
        u = act(rms_norm(x, f("ln"), hp["eps"]))
        if kind == MAMBA:
            out, S = mamba_block(
                u, layer, hp, act, mode,
                lambda S: chosen(S, state_dtypes, mode["state"]))
        elif kind == MOE:
            out, margin = expert_block(u, layer, hp)
            out = act(out)
        else:
            D = hp["head_dim"]
            q = act(u @ f("wq")).reshape(T, hp["num_heads"], D)
            k = act(u @ f("wk")).reshape(T, hp["num_kv_heads"], D)
            v = act(u @ f("wv")).reshape(T, hp["num_kv_heads"], D)
            o = attention(q, k, v, D ** -0.5).reshape(T, -1)
            out = act(act(o) @ f("wo"))
        return act(x + out), S, margin


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, block, eps: float):
    with jax.default_matmul_precision("highest"):
        return rms_norm(x, final_norm.astype(F32), eps) @ block.astype(F32)


def forward_variants(weights: Dict[str, Any], ids, hp: Dict[str, Any],
                     variants, rows=None):
    """Several forwards of one sequence ``ids`` [T] in one walk over the
    layers, each layer's weights handed to the device ONCE for all of them.
    ``variants`` is a list of dicts of :func:`forward_logits`'s options
    (``held``, ``act_dtype``, ``state_dtype``, ``unrounded``, ``head``);
    returns for each ``(logits of rows or None, margins of rows, states
    [Lm, Hm, P, N])``."""
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
                "c": jnp.asarray("c" in wide), "B_C": jnp.asarray("B_C" in wide)}
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
            if kind == MAMBA:
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
                             float(hp["eps"])))
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
    Mamba layers' states after the last token ``[Lm, Hm, P, N]``;
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
