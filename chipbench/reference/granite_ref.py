"""A plain reference for IBM Granite 4.0-H (``model_type:
granitemoehybrid``): Mamba-2 layers beside a few attention layers without
positions, every feed-forward a mixture of routed experts plus a shared MLP.

Written from the layer equations of the modelling code published for the
model type, in ``jax.numpy`` and float32 with matmuls at the highest
precision, with no kernel, cache, state pool, batching or code of
``deepspeed_tpu``. ``x = embedding_multiplier * embed[ids]``; per layer, with
``r = residual_multiplier``:

    x += r * mixer(rms_norm(x; ln_in))
    h2 = rms_norm(x; ln_ff);   x += r * (moe(h2) + shared(h2))

then ``logits = rms_norm(x; final_norm) embed^T / logits_scaling`` (tied
head). ``rms_norm(x; w) = x * rsqrt(mean(x^2) + eps) * w``.

- attention (``hp["kinds"][i] == "attention"``): ``q = h W_q`` ``[T, Hq, D]``,
  ``k, v`` ``[T, Hkv, D]``, no bias, NO rotation or position term of any kind;
  ``o = softmax_causal(q k^T * attention_multiplier) v`` with grouped queries
  (the published scale is 1/128, not ``D ** -0.5``); ``mixer = o W_o``;
- Mamba-2 (Dao & Gu 2024), ``H`` heads of ``P``, ``E = H P``, ``N`` state
  values, one group, ``K`` taps, per token ``t``::

      [z_t (E) | xBC_t (E + 2N) | dt_t (H)] = W_in u_t
      xBC_t = silu(b_conv + sum_j w_conv[:, j] * xBC_{t-K+1+j})   (zeros before 0)
      [X_t (H, P) | B_t (N) | C_t (N)] = xBC_t
      D_t = softplus(dt_t + dt_bias);   a = -exp(A_log)            (a head)
      S_t[h] = exp(D_t[h] a[h]) S_{t-1}[h] + D_t[h] X_t[h] (outer) B_t
      y_t[h] = S_t[h] C_t + D[h] X_t[h]
      g_t = y_t * silu(z_t);  n_t = g_t * rsqrt(mean(g_t^2) + eps) * w_norm
      mixer_t = W_out n_t

  (the gate first, then the norm, over all ``E``: one group). The recurrence
  is a ``lax.scan`` over tokens, one token at a time, from ``S = 0`` — not
  the chunked product form the program's prompt rows take. ``time_step_limit``
  is ``(0, inf)`` in the published config class: no clamp on ``D_t``;
- MoE: ``l = h2 W_r`` in float32; the ``top_k`` largest ``l``; weights =
  softmax over those ``top_k`` (no bias, no scale); expert ``e`` is
  ``(silu(h2 W_gate_e) * h2 W_up_e) W_down_e`` (the published checkpoint
  fuses gate and up in one ``[hidden, 2 F]`` matrix: its halves, in that
  order); ``moe = sum_k w_k expert_{e_k}(h2)``; ``shared``: the same SwiGLU at
  its own width, unweighted, every token.

``hp["held"] = (first, count)`` gives the reference the same share of the
experts the program holds: the router scores all experts and its softmax is
over all ``top_k`` chosen; the layer's ``w_gate``/``w_up``/``w_down`` stacks
hold experts ``first .. first + count - 1`` and only assignments to those add
to the output. What the absent experts would have added is left out.

Weights are a plain dict (all matrices ``[in, out]``)::

    {"embed": [V, H], "final_norm": [H],
     "layers": [{"ln_in": [H], "ln_ff": [H], "router": [H, Er],
                 "w_gate": [count, H, F], "w_up": ..., "w_down": [count, F, H],
                 "shared": {"w_gate": [H, Fs], "w_up": ..., "w_down": [Fs, H]},
                 # attention: "wq": [H, Hq*D], "wk", "wv": [H, Hkv*D],
                 #            "wo": [Hq*D, H]
                 # mamba: "w_in": [H, 2E + 2N + Hm], "conv_w": [E + 2N, K],
                 #        "conv_b": [E + 2N], "b_dt": [Hm], "A_log": [Hm],
                 #        "D": [Hm], "g_norm": [E], "w_out": [E, H]
                 }, ...]}

and ``hp`` gives ``num_heads``, ``num_kv_heads``, ``head_dim``,
``attn_scale``, ``eps``, ``mamba_heads``, ``mamba_head_dim``, ``d_state``,
``top_k``, ``held`` (or None), ``embed_scale``, ``residual_scale``,
``logits_scaling`` and ``kinds``. A layer's weights may lie on the host
(numpy): each layer is one jitted call that is handed that layer's weights
alone, the embedding is read on the host and the head is computed a block of
the vocabulary at a time, so that a model that fills the device beside the
engine is never there twice.

Departures from the published code: none intended. For memory only:
attention runs one block of queries at a time, the held experts one at a
time over all tokens (every held expert is evaluated for every token and
weighed by its routing weight, 0 where not chosen: the same sum), the layers
one jitted call each, the head in blocks.

A row's routing MARGIN is, at the least over the layers, how far the nearest
HELD expert is from changing sides of the selection, in the router's logits:
a chosen one above the first expert left out, one left out below the last
chosen. Where it is small the choice turns on rounding, and a system
computing in bfloat16 may rightly choose otherwise.

For tests and for sizing a tolerance, not for use: ``state_dtype`` rounds the
state to a lower precision after every token (a state pool held in that
precision); ``act_dtype`` rounds what each part of a layer hands on (the
embedding's product, the normed input, each product's result, the
convolution's output, each branch's output, the residual stream) to a lower
precision, which is where a program that keeps its activations in that
precision rounds; ``hp["unrounded"]`` names what of the recurrence's inputs
that mode still leaves in float32 (``"c"``: x as the recurrence reads it,
``"B_C"``: B and C) — a program rounds each and widens it again for its
float32 recurrence, a pair of converts a compiler may drop; ``hp["drop"]``
names parts to leave out (``"conv_history"``, ``"D"``, ``"gate"``,
``"gate_norm"``, ``"shared"``), ``hp["norm_before_gate"]`` swaps the gate
and the norm, ``hp["softmax_over_held"]`` normalises the routing weights
over the held choices only (all faults). Rounding is
``lax.reduce_precision``: a pair of converts the compiler is free to drop.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.decoder_ref import F32, rms_norm, swiglu

QUERY_BLOCK = 512
VOCAB_BLOCK = 16384
MAMBA, ATTENTION = "mamba", "attention"


def rounded(x, dtype):
    """``x`` at the precision of ``dtype``, still float32 (None: as it is)."""
    if dtype is None or jnp.dtype(dtype) == jnp.dtype(F32):
        return x
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, exponent_bits=info.nexp,
                                    mantissa_bits=info.nmant)


def chosen(x, dtypes, which):
    """``x`` rounded to ``dtypes[which - 1]``, or as it is where ``which`` is
    0. ``dtypes`` is static, ``which`` may be traced: forwards that differ
    only in where they round are then ONE compiled program a kind of layer
    (a float32 product at the highest precision compiles for seconds, and
    the chip's check runs four such forwards)."""
    for i, dtype in enumerate(dtypes):
        x = jnp.where(which == i + 1, rounded(x, dtype), x)
    return x


def attention(q, k, v, scale: float):
    """q [T, Hq, D], k/v [T, Hkv, D] -> [T, Hq, D]; causal, no positions."""
    t, hq, d = q.shape
    hkv = k.shape[1]
    rep = hq // hkv
    block = min(QUERY_BLOCK, t)
    pad = -t % block
    j = jnp.arange(t)[None, :]
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        -1, block, hkv, rep, d)

    def one_block(args):
        qi, i0 = args                                     # [block, Hkv, rep, D]
        seen = j <= (i0 + jnp.arange(block))[:, None]     # [block, T]
        s = jnp.einsum("tgrd,sgd->grts", qi, k) * scale
        s = jnp.where(seen[None, None], s, -jnp.inf)
        return jnp.einsum("grts,sgd->tgrd", jax.nn.softmax(s, axis=-1), v)

    out = jax.lax.map(one_block, (qb, jnp.arange(qb.shape[0]) * block))
    return out.reshape(-1, hq, d)[:t]


def recurrence(dt, x, Bm, Cm, a, state_dtype=None, state_round=None):
    """The Mamba-2 recurrence by itself, token by token from ``S = 0``:
    ``dt`` ``[T, H]``, ``x`` ``[T, H, P]``, ``Bm``, ``Cm`` ``[T, N]``, ``a``
    ``[H]`` (negative) -> (``S_t C_t`` for every token ``[T, H, P]``, the
    last ``S`` ``[H, P, N]``)."""
    def step(S, row):
        dt_t, x_t, b_t, c_t = row
        S = jnp.exp(dt_t * a)[:, None, None] * S \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        S = rounded(S, state_dtype) if state_round is None \
            else state_round(S)
        return S, S @ c_t

    S0 = jnp.zeros(x.shape[1:] + Bm.shape[1:], F32)
    S, y = jax.lax.scan(step, S0, (dt, x, Bm, Cm))
    return y, S


def mamba_mixer(u, layer: Dict[str, Any], hp: Dict[str, Any], act, wide,
                state_round):
    """``u`` [T, H] -> (out [T, H], the state after the last token
    ``[Hm, P, N]``). ``act`` rounds what a part hands on, ``state_round`` the
    state after a token; ``wide`` says which of the recurrence's inputs stay
    unrounded (``"c"``, ``"B_C"``: booleans, traced or not)."""
    drop = hp.get("drop", ())
    Hm, P, N = hp["mamba_heads"], hp["mamba_head_dim"], hp["d_state"]
    E = Hm * P
    f = lambda name: layer[name].astype(F32)
    T = u.shape[0]
    zxd = act(u @ f("w_in"))
    z, a_in, dt = zxd[:, :E], zxd[:, E:2 * E + 2 * N], zxd[:, 2 * E + 2 * N:]
    w, K = f("conv_w"), layer["conv_w"].shape[1]
    pad = jnp.pad(a_in, ((K - 1, 0), (0, 0)))
    taps = range(K - 1, K) if "conv_history" in drop else range(K)
    c = jax.nn.silu(f("conv_b") + sum(pad[j:j + T] * w[:, j] for j in taps))
    x = jnp.where(wide["c"], c[:, :E], act(c[:, :E]))
    Bm, Cm = c[:, E:E + N], c[:, E + N:]
    Bm, Cm = (jnp.where(wide["B_C"], v, act(v)) for v in (Bm, Cm))
    dt = jax.nn.softplus(dt + f("b_dt"))
    y, S = recurrence(dt, x.reshape(T, Hm, P), Bm, Cm, -jnp.exp(f("A_log")),
                      state_round=state_round)
    if "D" not in drop:
        y = y + f("D")[:, None] * x.reshape(T, Hm, P)
    y = y.reshape(T, E)
    norm = lambda g: g if "gate_norm" in drop else rms_norm(
        g, f("g_norm"), hp["eps"])
    gate = jax.nn.silu(z)
    if "gate" in drop:
        n = norm(y)
    elif hp.get("norm_before_gate"):
        n = norm(y) * gate
    else:
        n = norm(y * gate)
    return act(act(n) @ f("w_out")), S


def route(h, layer: Dict[str, Any], hp: Dict[str, Any]):
    """Routing weight of every expert for every token ``[T, Er]`` (0 where
    not chosen), each token's margin ``[T]`` (the module's docstring) and
    whether each expert is held ``[Er]``."""
    k = hp["top_k"]
    dt = hp.get("router_dtype")         # inputs, product and logits rounded
    logits = rounded(rounded(h.astype(F32), dt)
                     @ rounded(layer["router"].astype(F32), dt), dt)
    e = logits.shape[-1]
    first, count = hp.get("held") or (0, e)
    is_held = (jnp.arange(e) >= first) & (jnp.arange(e) < first + count)
    top, idx = jax.lax.top_k(logits, k + 1)
    last_in, first_out = top[:, k - 1:k], top[:, k:k + 1]
    margin = jnp.min(jnp.where(
        is_held, jnp.where(logits >= last_in, logits - first_out,
                           last_in - logits), jnp.inf), axis=-1)
    idx, top = idx[:, :k], top[:, :k]
    if hp.get("softmax_over_held"):
        top = jnp.where(is_held[idx], top, -jnp.inf)
    weights = jax.nn.softmax(top, axis=-1)
    dense = jnp.sum(jax.nn.one_hot(idx, e, dtype=F32) * weights[..., None],
                    axis=1)
    return dense, margin, is_held


def sparse_mixture(h, layer: Dict[str, Any], hp: Dict[str, Any]):
    """``moe(h) + shared(h)`` and the rows' margins in this layer."""
    dense, margin, _ = route(h, layer, hp)
    first, count = hp.get("held") or (0, dense.shape[-1])

    def add_expert(acc, args):
        wg, wu, wd, weight = args
        return acc + weight[:, None] * swiglu(h, wg, wu, wd), None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(h),
                          (layer["w_gate"], layer["w_up"], layer["w_down"],
                           dense[:, first:first + count].T))
    if "shared" not in hp.get("drop", ()):
        s = layer["shared"]
        out = out + swiglu(h, s["w_gate"], s["w_up"], s["w_down"])
    return out, margin


@functools.partial(jax.jit, static_argnames=("kind", "hp", "act_dtypes",
                                             "state_dtypes"))
def _layer(x, layer, kind: str, hp, act_dtypes, state_dtypes, mode):
    """One layer; ``mode`` holds the traced choices: ``act`` and ``state``
    (0, or which of ``act_dtypes`` / ``state_dtypes`` to round to), ``c`` and
    ``B_C`` (leave that input of the recurrence unrounded)."""
    hp = dict(hp)
    act = lambda v: chosen(v, act_dtypes, mode["act"])
    r = hp["residual_scale"]
    with jax.default_matmul_precision("highest"):
        f = lambda name: layer[name].astype(F32)
        u = act(rms_norm(x, f("ln_in"), hp["eps"]))
        if kind == MAMBA:
            mixed, S = mamba_mixer(
                u, layer, hp, act, mode,
                lambda S: chosen(S, state_dtypes, mode["state"]))
        else:
            T, D = x.shape[0], hp["head_dim"]
            q = act(u @ f("wq")).reshape(T, hp["num_heads"], D)
            k = act(u @ f("wk")).reshape(T, hp["num_kv_heads"], D)
            v = act(u @ f("wv")).reshape(T, hp["num_kv_heads"], D)
            o = attention(q, k, v, hp["attn_scale"]).reshape(T, -1)
            mixed = act(act(o) @ f("wo"))
            S = jnp.zeros((0,), F32)
        x = act(x + r * mixed)
        g = act(rms_norm(x, f("ln_ff"), hp["eps"]))
        out, margin = sparse_mixture(g, layer, hp)
        return act(x + r * act(out)), S, margin


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, block, eps: float):
    with jax.default_matmul_precision("highest"):
        return rms_norm(x, final_norm.astype(F32), eps) \
            @ block.astype(F32).T


def _static(hp: Dict[str, Any]):
    return tuple(sorted((k, tuple(v) if isinstance(v, (list, tuple)) else v)
                        for k, v in hp.items() if k != "kinds"))


def forward_variants(weights: Dict[str, Any], ids, hp: Dict[str, Any],
                     variants, rows=None):
    """Several forwards of one sequence ``ids`` [T] in one walk over the
    layers, each layer's weights handed to the device ONCE for all of them
    (a model whose layers lie on the host costs its bytes a walk).
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
        x = rounded(jnp.asarray(np.asarray(embed)[ids]).astype(F32)
                    * h["embed_scale"], v.get("act_dtype"))
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
        # the layer's copy on the device is let go before the next one (and
        # the head's blocks) comes up: dispatch runs ahead of the device, and
        # two layers' weights beside an engine that fills the chip are 0.9
        # GiB more than the check was given
        jax.block_until_ready([r["x"] for r in runs])
        del layer
    at = jnp.asarray(rows)
    heads = [r for r in runs if r["head"]]
    V = embed.shape[0]
    parts = [[] for _ in heads]
    for v0 in range(0, V if heads else 0, VOCAB_BLOCK):
        block = jnp.asarray(embed[v0:v0 + VOCAB_BLOCK])
        for r, out in zip(heads, parts):
            out.append(_head(r["x"][at], weights["final_norm"], block,
                             float(hp["eps"])))
    for r, out in zip(heads, parts):
        r["logits"] = jnp.concatenate(out, axis=1) / hp["logits_scaling"]
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
