"""A plain reference for ZAYA1 (``model_type: zaya``; Zyphra ZAYA1-8B):
compressed convolutional attention (CCA) in every layer, a top-1 router that
is an MLP on a state handed from layer to layer, 16 experts and a choice
that skips them, learned scales where a branch joins the stream.

Written from the layer equations of the model's published description
(``config.json``: ``cca_time0``/``cca_time1`` 2, 8 query heads over 2 KV
heads of 128, ``partial_rotary_factor`` 0.5, ``router_hidden_size`` 256, 16
experts top-1; the catalog's ``described_as``; the sibling configurations'
``cca``, ``zaya_use_eda``, ``zaya_use_mod``, ``scale_residual_merge``; the
papers arXiv:2510.04476 (CCA) and arXiv:2511.17127 (ZAYA1)), in
``jax.numpy`` and float32 with matmuls at the highest precision, with no
kernel, cache, tail pool, batching or code of ``deepspeed_tpu``. ``n(x; w) =
x * rsqrt(mean(x^2) + eps) * w``; ``h = embed[ids]``; per layer, with ``a``,
``c`` the layer's ``[4, hidden]`` scales and biases::

    h  = (a0 * h + c0) + (a1 * CCA(n(h; ln_in))      + c1)
    h  = (a2 * h + c2) + (a3 * MoE(n(h; ln_ff), r)   + c3)

then ``logits = n(h; final_norm) embed^T`` (tied head). One line of this
file a numbered step; the configuration file's ``assumed`` says which of
them the published configuration does not pin:

CCA on ``u`` ``[T, hidden]``, ``Hq`` query heads over ``Hk`` key/value heads
of ``d``, ``G = Hq / Hk``:

1. ``qp = u Wq`` ``[Hq d]``, ``kp = u Wk`` ``[Hk d]``, ``v1 = u Wv1``
   ``[d]``, ``z = u Wv2`` ``[d]``, no bias;
2. ``s = [qp ; kp]``; ``m_t = sum_j w0[:, j] s_{t - (K0 - 1) + j} + b0``
   (depthwise, ``K0 = cca_time0`` taps, PyTorch's ``Conv1d`` layout ``[C,
   K0]``); ``y_t = sum_j M[j] m_{t - (K1 - 1) + j} + b1`` (``K1 =
   cca_time1`` taps, grouped by head: ``w1`` ``[C, d, K1]`` = output
   channel, input channel of its head, tap); no activation; the INPUT is
   zero before position 0 (so ``m_{-1} = b0``, what two stacked ``Conv1d``
   over a left-padded input give);
3. ``q_j = y^q_j + (qp_j + kp_{j // G}) / 2``; ``k_i = y^k_i + (kp_i +
   mean_{j in i} qp_j) / 2`` — the mean from the PRE-convolution values;
4. ``q_j <- q_j * rsqrt(mean(q_j^2) + eps)`` (its norm becomes ``sqrt(d)``),
   ``k_i`` likewise and times the head's temperature ``temp_i``;
5. the first ``rotary_dim`` values of each head rotated by the position,
   value ``i`` paired with value ``i + rotary_dim / 2``; the rest pass;
6. key/value head 0's value is ``v1_t``, head 1's ``z_{t-1}`` (zero at 0);
7. ``o = softmax_causal(q k^T * d ** -0.5) v``; ``out = o Wo``.

The router and the experts on ``g`` ``[T, hidden]``, ``E`` experts:

1. ``r = g Wd + bd``; in every layer but the first ``r += gamma * r_in``;
   ``r`` goes on to the next layer;
2. ``logits = gelu(gelu(nr(r) W1 + b1) W2 + b2) W3`` (``E + 1`` of them;
   gelu exact); ``p = softmax(logits)``;
3. ``e = argmax(p + beta)``; the weight is ``p_e``, not renormalised;
4. ``e < E``: the branch is ``p_e * SwiGLU_e(g)``; ``e == E``: zero.

What a sequence keeps between tokens, were it run a token at a time — its
TAIL a layer — is ``[s ; z]`` of its last ``K0 + K1 - 2`` tokens; the
reference returns it (``[L, 1, C + d, taps]``, oldest token first on the last
axis; in the order ``hp["tail_order"]`` of its channels where given, for a
program that lays the channels out otherwise).

A row's routing MARGIN is, at the least over the layers, ``p + beta`` of the
chosen over the next: how far the choice (the skip choice as any other) is
from changing.

Weights are a plain dict (matrices ``[in, out]``, the convolutions in
PyTorch's layout)::

    {"embed": [V, H], "final_norm": [H], "head": [H, V] (embed^T),
     "layers": [{"ln_in", "ln_ff": [H], "res_scale", "res_bias": [4, H],
                 "wq": [H, Hq d], "wk": [H, Hk d], "wv1", "wv2": [H, d],
                 "conv0_w": [C, K0], "conv0_b": [C], "conv1_w": [C, d, K1],
                 "conv1_b": [C], "temp": [Hk], "wo": [Hq d, H],
                 "router_down": [H, R], "router_down_b": [R], "gamma": [R],
                 "router_norm": [R], "router_fc1", "router_fc2": [R, R],
                 "router_fc1_b", "router_fc2_b": [R], "router_out": [R, E+1],
                 "beta": [E + 1],
                 "w_gate", "w_up": [E, H, F], "w_down": [E, F, H]}, ...]}

A layer's weights may lie on the host (numpy): each layer is one jitted call
handed that layer's weights alone, the embedding is read on the host and the
head computed a block of the vocabulary at a time. For memory only: the
experts one at a time over all tokens (each weighed by its routing weight, 0
where not chosen: the same sum), attention a block of queries at a time
(``granite_ref.attention``, with the rounding helpers and the shape of the
walk over the layers).

For tests and for sizing a tolerance, not for use: ``act_dtype`` rounds what
each part of a layer hands on (the normed input, each product's result, the
depthwise convolution's output, q and k after rotation, the block's output,
the residual stream; never the router, which is float32); ``state_dtype``
rounds what a token reads of EARLIER tokens' ``s`` and ``z`` — a tail kept
at that precision — and the tail returned; ``hp["drop"]`` names parts to
leave out: ``"router_state"`` (no ``gamma * r_in``), ``"value_shift"`` (head
1's value the token's own ``z``), ``"qk_mean"``, ``"temp"``, ``"conv_bias"``,
``"conv_history"`` (every token convolved as if first), ``"skip"`` (the skip
choice never taken), ``"res_bias"``; ``hp["router_dtype"]`` rounds the
router's hidden values and logits (all faults).
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


def norm(x, w, eps: float):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(F32)


def rope(x, positions, theta: float, rotary_dim: int):
    """``x`` ``[T, H, D]``: the first ``rotary_dim`` values of each head
    rotated, value ``i`` paired with value ``i + rotary_dim / 2``."""
    half = rotary_dim // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) * 2.0 / rotary_dim)
    ang = positions.astype(F32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:rotary_dim]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rotary_dim:]], axis=-1)


def shifted(x, by: int):
    """``x`` ``[T, ..]`` as each token sees the token ``by`` before it; zero
    before position 0."""
    if by == 0:
        return x
    return jnp.pad(x, ((by, 0),) + ((0, 0),) * (x.ndim - 1))[:x.shape[0]]


def cca_mix(s, layer: Dict[str, Any], hp: Dict[str, Any], act, earlier):
    """Step 2: the two convolutions over ``s`` ``[T, C]``. ``earlier(x)`` is
    what a token reads of an earlier token's ``x`` (the tail's precision)."""
    drop = hp.get("drop", ())
    T, C = s.shape
    d = hp["head_dim"]
    w0, w1 = layer["conv0_w"].astype(F32), layer["conv1_w"].astype(F32)
    K0, K1 = w0.shape[1], w1.shape[2]
    bias = 0.0 if "conv_bias" in drop else 1.0
    b0, b1 = bias * layer["conv0_b"].astype(F32), \
        bias * layer["conv1_b"].astype(F32)
    if "conv_history" in drop:
        earlier = jnp.zeros_like
    old = earlier(s)

    def depthwise(back: int):
        """``m_{t - back}`` as token ``t`` computes it: of its own ``s`` where
        that is the token's, of the tail's values otherwise."""
        taps = []
        for j in range(K0):
            ago = back + K0 - 1 - j
            taps.append(w0[:, j] * shifted(s if ago == 0 else old, ago))
        return act(b0 + sum(taps))

    blocks = w1.reshape(C // d, d, d, K1)               # [head, out, in, tap]
    y = b1
    for j in range(K1):
        m = depthwise(K1 - 1 - j).reshape(T, C // d, d)
        y = y + jnp.einsum("thi,hoi->tho", m, blocks[..., j]).reshape(T, C)
    return y


def cca_qkv(u, layer: Dict[str, Any], hp: Dict[str, Any], act, earlier):
    """Steps 1-6 on the normed rows ``u``: ``(q [T, Hq, d], k [T, Hk, d], v
    [T, Hk, d], the tail [C + d, taps])`` — what attends, what a cache would
    hold of each token, and what a sequence would keep between tokens."""
    drop = hp.get("drop", ())
    f = lambda name: layer[name].astype(F32)
    T, Hq, Hk, d = (u.shape[0], hp["num_heads"], hp["num_kv_heads"],
                    hp["head_dim"])
    G = Hq // Hk
    qp, kp = act(u @ f("wq")), act(u @ f("wk"))                    # step 1
    v1, z = act(u @ f("wv1")), act(u @ f("wv2"))
    s = jnp.concatenate([qp, kp], axis=-1)
    y = cca_mix(s, layer, hp, act, earlier)                        # step 2
    qh, kh = qp.reshape(T, Hk, G, d), kp.reshape(T, Hk, d)
    q = y[:, :Hq * d].reshape(T, Hk, G, d)
    k = y[:, Hq * d:].reshape(T, Hk, d)
    if "qk_mean" not in drop:                                      # step 3
        q = q + (qh + kh[:, :, None]) / 2
        k = k + (kh + jnp.mean(qh, axis=2)) / 2
    unit = lambda x: x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + hp["eps"])
    q, k = unit(q).reshape(T, Hq, d), unit(k)                      # step 4
    if "temp" not in drop:
        k = k * f("temp")[:, None]
    at = jnp.arange(T)
    turn = lambda x: act(rope(x, at, hp["rope_theta"], hp["rotary_dim"]))
    q, k = turn(q), turn(k)                                        # step 5
    z_prev = z if "value_shift" in drop else shifted(earlier(z), 1)
    v = jnp.stack([v1, z_prev], axis=1)                            # step 6
    taps = max(1, layer["conv0_w"].shape[1] + layer["conv1_w"].shape[2] - 2)
    kept = jnp.concatenate([s, z], axis=-1)
    tail = jnp.pad(kept, ((taps, 0), (0, 0)))[-taps:]      # [taps, C + d]
    return q, k, v, earlier(tail).T


def cca(u, layer: Dict[str, Any], hp: Dict[str, Any], act, earlier):
    """The attention block on the normed rows ``u``: ``(out [T, hidden], the
    tail [C + d, taps])``."""
    q, k, v, tail = cca_qkv(u, layer, hp, act, earlier)
    T, Hq, d = q.shape
    o = attention(q, k, v, d ** -0.5).reshape(T, Hq * d)           # step 7
    return act(act(o) @ layer["wo"].astype(F32)), tail


def router_probabilities(g, layer: Dict[str, Any], hp: Dict[str, Any], r_in):
    """Steps 1 and 2: ``(p [T, E + 1], the router's state [T, R])``; ``r_in``
    None for the first layer."""
    drop = hp.get("drop", ())
    f = lambda name: layer[name].astype(F32)
    low = lambda x: rounded(x, hp.get("router_dtype"))
    gelu = lambda x: jax.nn.gelu(x, approximate=False)
    r = g.astype(F32) @ f("router_down") + f("router_down_b")       # step 1
    if r_in is not None and "router_state" not in drop:
        r = r + f("gamma") * r_in
    h = low(norm(r, f("router_norm"), hp["eps"]))                  # step 2
    h = low(gelu(h @ f("router_fc1") + f("router_fc1_b")))
    h = low(gelu(h @ f("router_fc2") + f("router_fc2_b")))
    return jax.nn.softmax(low(h @ f("router_out")), axis=-1), r


def route(g, layer: Dict[str, Any], hp: Dict[str, Any], r_in):
    """``(each token's routing weight over the E experts [T, E] (0 where not
    chosen; all zero for a token that skips), its margin [T], the router's
    state [T, R], whether it skipped [T])``."""
    E = hp["num_experts"]
    p, r = router_probabilities(g, layer, hp, r_in)
    biased = p + layer["beta"].astype(F32)                         # step 3
    if "skip" in hp.get("drop", ()):
        biased = biased.at[:, E].set(-jnp.inf)
    top, idx = jax.lax.top_k(biased, 2)
    e = idx[:, 0]
    weight = jnp.take_along_axis(p, e[:, None], axis=-1)
    dense = (jax.nn.one_hot(e, E + 1, dtype=F32) * weight)[:, :E]
    return dense, top[:, 0] - top[:, 1], r, e == E


def sparse_mixture(g, layer: Dict[str, Any], hp: Dict[str, Any], r_in):
    """Step 4: ``p_e * SwiGLU_e(g)``, zero for a token that skips."""
    dense, margin, r, skipped = route(g, layer, hp, r_in)

    def add_expert(acc, args):
        wg, wu, wd, weight = args
        return acc + weight[:, None] * swiglu(g, wg, wu, wd), None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(g),
                          (layer["w_gate"], layer["w_up"], layer["w_down"],
                           dense.T))
    return out, margin, r, skipped


@functools.partial(jax.jit, static_argnames=("hp", "act_dtypes",
                                             "state_dtypes"))
def _layer(x, r_in, layer, hp, act_dtypes, state_dtypes, mode):
    """One layer; ``mode`` holds the traced choices ``act`` and ``state`` (0,
    or which of ``act_dtypes`` / ``state_dtypes`` to round to) and ``first``
    (the first layer's router is handed no state: one compiled program
    serves it and the others)."""
    hp = dict(hp)
    act = lambda v: chosen(v, act_dtypes, mode["act"])
    earlier = lambda v: chosen(v, state_dtypes, mode["state"])
    a, c = layer["res_scale"].astype(F32), layer["res_bias"].astype(F32)
    if "res_bias" in hp.get("drop", ()):
        c = jnp.zeros_like(c)
    join = lambda i, x, y: act((a[i] * x + c[i]) + (a[i + 1] * y + c[i + 1]))
    with jax.default_matmul_precision("highest"):
        mixed, tail = cca(act(norm(x, layer["ln_in"], hp["eps"])), layer, hp,
                          act, earlier)
        x = join(0, x, mixed)
        out, margin, r, skipped = sparse_mixture(
            act(norm(x, layer["ln_ff"], hp["eps"])), layer, hp,
            jnp.where(mode["first"], 0.0, r_in))
        return join(2, x, act(out)), r, tail, margin, skipped


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, block, eps):
    with jax.default_matmul_precision("highest"):
        return norm(x, final_norm, eps) @ block.astype(F32)


def forward_variants(weights: Dict[str, Any], ids, hp: Dict[str, Any],
                     variants, rows=None, skipped=None):
    """Several forwards of one sequence ``ids`` [T] in one walk over the
    layers, each layer's weights handed to the device ONCE for all of them.
    ``variants`` is a list of dicts of :func:`forward_logits`'s options
    (``act_dtype``, ``state_dtype``, ``head``; others are ignored); returns
    for each ``(logits of rows or None, margins of rows, the layers' tails
    [L, 1, C + d, taps])``. ``skipped``, a list, is handed the first
    variant's share of tokens that took the skip choice, a layer."""
    ids = np.asarray(ids, np.int32)
    rows = np.arange(ids.shape[0]) if rows is None else np.asarray(rows)
    embed = weights["embed"]
    low = lambda key: tuple(dict.fromkeys(
        jnp.dtype(v[key]).name for v in variants
        if v.get(key) is not None and jnp.dtype(v[key]) != jnp.dtype(F32)))
    act_dtypes, state_dtypes = low("act_dtype"), low("state_dtype")
    which = lambda names, d: 0 if d is None or jnp.dtype(d).name not in names \
        else names.index(jnp.dtype(d).name) + 1
    order = hp.get("tail_order")
    order = None if order is None else np.asarray(order)
    static = _static({k: v for k, v in hp.items() if k != "tail_order"})
    R = weights["layers"][0]["router_down"].shape[1]
    runs = []
    for v in variants:
        mode = {"act": jnp.int32(which(act_dtypes, v.get("act_dtype"))),
                "state": jnp.int32(which(state_dtypes, v.get("state_dtype")))}
        # (the embedding may lie on the host: its rows are read there)
        x = rounded(jnp.asarray(np.asarray(embed)[ids]).astype(F32),
                    v.get("act_dtype"))
        runs.append({"x": x, "r": jnp.zeros((ids.shape[0], R), F32),
                     "tails": [], "mode": mode,
                     "margin": jnp.full((ids.shape[0],), jnp.inf, F32),
                     "head": v.get("head", True)})
    for i, layer in enumerate(weights["layers"]):
        layer = jax.device_put(layer)
        for n, r in enumerate(runs):
            r["x"], r["r"], tail, m, skip = _layer(
                r["x"], r["r"], layer, static, act_dtypes, state_dtypes,
                {**r["mode"], "first": jnp.asarray(i == 0)})
            r["margin"] = jnp.minimum(r["margin"], m)
            r["tails"].append(tail if order is None else tail[order])
            if n == 0 and skipped is not None:
                skipped.append(float(jnp.mean(skip)))
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
             jnp.stack(r["tails"])[:, None]) for r in runs]


def forward_logits(weights: Dict[str, Any], ids, hp: Dict[str, Any],
                   rows=None, with_margin: bool = False,
                   with_state: bool = False, act_dtype=None,
                   state_dtype=None, head: bool = True):
    """Logits [T, V] (or of ``rows`` only) of one sequence ``ids`` [T].
    ``with_margin`` adds those positions' routing margins, ``with_state`` the
    layers' tails ``[L, 1, C + d, taps]``; ``head=False`` skips the logits
    (None in their place)."""
    logits, margin, tails = forward_variants(
        weights, ids, hp, [dict(act_dtype=act_dtype, state_dtype=state_dtype,
                                head=head)], rows=rows)[0]
    out = (logits,)
    if with_margin:
        out += (margin,)
    if with_state:
        out += (tails,)
    return out if len(out) > 1 else logits
