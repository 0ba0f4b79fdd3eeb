"""A plain reference for the decoder families the benchmark runs.

Written from the published equations, in ``jax.numpy`` and float32 with
matmuls at the highest precision, with no kernel, cache, batching or code of
``deepspeed_tpu``:

- RMSNorm (Zhang & Sennrich 2019): ``x / sqrt(mean(x^2) + eps) * g``;
- rotary position embedding (Su et al. 2021, eq. 34): consecutive pairs
  ``(x[2i], x[2i+1])`` rotated by ``pos * theta^(-2i/d)``;
- grouped-query attention (Ainslie et al. 2023): query head ``h`` reads
  key/value head ``h // (Hq / Hkv)``; causal, and with a sliding window ``W``
  (Mistral 7B, Jiang et al. 2023) position ``i`` sees ``i - W < j <= i``;
- SwiGLU feed-forward (Shazeer 2020): ``(silu(x Wg) * (x Wu)) Wd``;
- Mixtral's sparse mixture (Jiang et al. 2024, eq. 1-2): router logits
  ``x Wr``, the ``k`` largest kept, softmax over those ``k`` only, and the
  chosen experts' SwiGLU outputs summed under those weights.

Weights are a plain dict (all matrices ``[in, out]``)::

    {"embed": [V, H], "final_norm": [H], "lm_head": [H, V],
     "layers": [{"ln1": [H], "ln2": [H],
                 "wq": [H, Hq*D], "wk": [H, Hkv*D], "wv": [H, Hkv*D],
                 "wo": [Hq*D, H],
                 # dense:  "w_gate": [H, F], "w_up": [H, F], "w_down": [F, H]
                 # sparse: "router": [H, E], "w_gate": [E, H, F],
                 #         "w_up": [E, H, F], "w_down": [E, F, H]
                 }, ...]}

Departures from the papers, for memory only: attention runs one key/value
head group at a time and the experts one at a time (``lax.map`` /
``lax.scan``), each over all tokens; every expert is evaluated for every
token and masked by its routing weight, which is the same sum.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

F32 = jnp.float32


def rms_norm(x, gain, eps: float):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * gain.astype(F32)


def rope(x, positions, theta: float):
    """x [T, heads, D], positions [T]."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)          # [D/2]
    ang = positions.astype(F32)[:, None] * inv[None, :]           # [T, D/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def attention(q, k, v, window: Optional[int]):
    """q [T, Hq, D], k/v [T, Hkv, D] -> [T, Hq, D]; causal (+ window)."""
    t, hq, d = q.shape
    hkv = k.shape[1]
    rep = hq // hkv
    i = jnp.arange(t)[:, None]
    j = jnp.arange(t)[None, :]
    seen = j <= i
    if window is not None:
        seen = seen & (j > i - window)

    def one_group(args):
        qg, kg, vg = args                       # [rep, T, D], [T, D], [T, D]
        s = jnp.einsum("rtd,sd->rts", qg, kg) / jnp.sqrt(F32(d))
        s = jnp.where(seen[None], s, -jnp.inf)
        return jnp.einsum("rts,sd->rtd", jax.nn.softmax(s, axis=-1), vg)

    qg = q.transpose(1, 0, 2).reshape(hkv, rep, t, d)
    out = jax.lax.map(one_group, (qg, k.transpose(1, 0, 2),
                                  v.transpose(1, 0, 2)))     # [Hkv, rep, T, D]
    return out.reshape(hq, t, d).transpose(1, 0, 2)


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate.astype(F32)) * (x @ w_up.astype(F32))) \
        @ w_down.astype(F32)


def sparse_mixture(x, layer: Dict[str, Any], top_k: int):
    logits = x @ layer["router"].astype(F32)                      # [T, E]
    top, idx = jax.lax.top_k(logits, top_k + 1)
    margin = top[:, top_k - 1] - top[:, top_k]      # chosen k-th over next
    top, idx = top[:, :top_k], idx[:, :top_k]
    gate = jax.nn.softmax(top, axis=-1)                           # [T, k]
    n_experts = logits.shape[-1]
    # routing weight of every expert for every token, 0 where not chosen
    dense = jnp.sum(jax.nn.one_hot(idx, n_experts, dtype=F32)
                    * gate[..., None], axis=1)                    # [T, E]

    def add_expert(acc, args):
        wg, wu, wd, weight = args
        return acc + weight[:, None] * swiglu(x, wg, wu, wd), None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(x),
                          (layer["w_gate"], layer["w_up"], layer["w_down"],
                           dense.T))
    return out, margin


def hidden_states(weights: Dict[str, Any], ids, hp: Dict[str, Any]):
    """Final-norm hidden states [T, H] of one sequence ``ids`` [T], and each
    position's routing margin [T]: the least gap, over the sparse layers,
    between the last expert chosen and the first left out (infinite for a
    dense model). Where it is small the choice turns on rounding, and a
    system computing in bfloat16 may rightly choose otherwise."""
    hq, hkv, d = hp["num_heads"], hp["num_kv_heads"], hp["head_dim"]
    t = ids.shape[0]
    pos = jnp.arange(t)
    x = weights["embed"][ids].astype(F32)
    margin = jnp.full((t,), jnp.inf, F32)
    for layer in weights["layers"]:
        h = rms_norm(x, layer["ln1"], hp["eps"])
        q = rope((h @ layer["wq"].astype(F32)).reshape(t, hq, d), pos,
                 hp["rope_theta"])
        k = rope((h @ layer["wk"].astype(F32)).reshape(t, hkv, d), pos,
                 hp["rope_theta"])
        v = (h @ layer["wv"].astype(F32)).reshape(t, hkv, d)
        a = attention(q, k, v, hp.get("window")).reshape(t, hq * d)
        x = x + a @ layer["wo"].astype(F32)
        h = rms_norm(x, layer["ln2"], hp["eps"])
        if "router" in layer:
            out, m = sparse_mixture(h, layer, hp["top_k"])
            x = x + out
            margin = jnp.minimum(margin, m)
        else:
            x = x + swiglu(h, layer["w_gate"], layer["w_up"], layer["w_down"])
    return rms_norm(x, weights["final_norm"], hp["eps"]), margin


def forward_logits(weights: Dict[str, Any], ids, hp: Dict[str, Any],
                   rows=None, with_margin: bool = False):
    """Logits [T, V] (or of ``rows`` only) of one sequence ``ids`` [T];
    ``with_margin`` adds those positions' routing margins."""
    with jax.default_matmul_precision("highest"):
        x, margin = hidden_states(weights, ids, hp)
        if rows is not None:
            x, margin = x[rows], margin[rows]
        logits = x @ weights["lm_head"].astype(F32)
    return (logits, margin) if with_margin else logits


def next_token_loss(weights: Dict[str, Any], ids, labels, hp: Dict[str, Any]):
    """Mean next-token negative log-likelihood of a batch ``ids`` [B, T]:
    position ``t`` predicts ``labels[t + 1]``; one sequence at a time."""
    def one(args):
        seq, lab = args
        logits = forward_logits(weights, seq, hp)[:-1]
        picked = jnp.take_along_axis(logits, lab[1:, None], axis=-1)[:, 0]
        return jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) - picked)
    return jnp.mean(jax.lax.map(one, (ids, labels)))
