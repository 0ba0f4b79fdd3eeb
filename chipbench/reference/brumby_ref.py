"""A plain reference for the Brumby family (Manifest AI Brumby-14B-Base).

Written from the family's public description (``config.json``: ``model_type:
brumby``, a Qwen3-14B-shaped block; Manifest AI's release note for
Brumby-14B-Base, 2025-10; Buckman, Gelada, Zhang, *Scaling Context Requires
Rethinking Attention*, 2025), in ``jax.numpy`` and float32 with matmuls at
the highest precision, with no kernel, cache, state pool, chunk, batching or
code of ``deepspeed_tpu``:

- ``x = embed[ids]``; each layer ``x = x + PR(rmsnorm_in(x))``; ``x = x +
  swiglu(rmsnorm_ff(x))``; a final RMSNorm; an untied head;
- **power retention** ``PR`` on the normed rows ``u_t`` of one sequence (``d``
  the head size, ``Hq`` query heads over ``Hk`` KV heads, query head ``j``
  reading KV head ``j // (Hq / Hk)``, power 2)::

      q_t = rope_t(nq(W_q u_t))   k_t = rope_t(nk(W_k u_t))   v_t = W_v u_t
          nq, nk: RMSNorm over a head's d values (a gain a head width);
          rope: the whole head, value i paired with i + d / 2
      lg_t = log_sigmoid(W_g u_t + b_g)     one a KV head;  c_t = sum_{s<=t} lg_s
      w[t, s] = ((q_t . k_s) / sqrt(d))^2 exp(c_t - c_s)            (s <= t)
      y_t = sum_s w[t, s] v_s / (sum_s w[t, s] + eps);   PR(u)_t = W_o y_t

  the ATTENTION form, over all pairs, one block of queries at a time: this is
  what ``forward_logits`` computes.

Weights are a plain dict (all matrices ``[in, out]``)::

    {"embed": [V, H], "final_norm": [H], "head": [H, V],
     "layers": [{"ln_in": [H], "ln_ff": [H], "wq": [H, Hq*d], "wk", "wv":
                 [H, Hk*d], "wg": [H, Hk], "b_g": [Hk], "q_norm", "k_norm":
                 [d], "wo": [Hq*d, H], "w_gate", "w_up": [H, F],
                 "w_down": [F, H]}, ...]}

and ``hp`` gives ``num_heads``, ``num_kv_heads``, ``head_dim``, ``eps`` (the
norms'), ``rope_theta``, ``retention_eps``. The weights may lie on the host
(numpy): each layer is one jitted call handed that layer's weights alone, the
embedding's rows are gathered on the host and the head is multiplied a block
of columns at a time, so that a model that fills the device beside the
engine is never there twice.

Departures from the published description: what it does not pin — the power,
the gate's form, the normaliser and its ``eps``, the ``1 / sqrt(d)`` — is one
reading of it, the configuration file's ``assumed`` list; for memory only,
queries go a block at a time and the head a block of columns at a time.

For tests and for sizing a tolerance, not for use: ``with_state`` computes
the layer in its STATE form instead (``S_t = g_t S_{t-1} + v_t pk(k_t)^T``,
``z_t = g_t z_{t-1} + pk(k_t)``, ``y_t = S_t pq(q_t) / (z_t . pq(q_t) + d
eps)``, token by token from an empty state; the same function, ``pq(a) .
pk(b) = (a . b)^2``) and returns every layer's state after the last token
beside the logits; the maps ``pk``, ``pq`` are the upper triangle's pairs, a
pair ``(i, j)`` an entry ``a_i a_j``, the key's times 2 off the diagonal —
in the order ``hp["expansion"] = (i [D], j [D], m [D])`` gives (whoever
compares the states hands the order of the other side; ``m`` 0 marks an
entry that holds no pair), by default row-major. ``state_dtype`` rounds
``S`` and ``z`` to a lower precision after every token (a state pool held in
it); ``act_dtype`` rounds what each part of a layer hands on (the normed
input, each product's result, the norms' and the rotation's output, the
mixer's and the feed-forward's output, the residual stream) where a program
that keeps its activations in that precision rounds. Rounding is
``lax.reduce_precision``.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
QUERY_BLOCK = 256
HEAD_BLOCK = 16384


def rounded(x, dtype):
    """``x`` at the precision of ``dtype``, still float32 (None: as it is)."""
    if dtype is None:
        return x
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, exponent_bits=info.nexp,
                                    mantissa_bits=info.nmant)


def rms_norm(x, gain, eps: float):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * gain.astype(F32)


def rope(x, positions, theta: float):
    """``x`` [T, heads, d] rotated by ``positions`` [T], value ``i`` paired
    with ``i + d / 2``."""
    d = x.shape[-1]
    # (the published expression, term for term: at position 4,000 a last bit
    # of a frequency is 2e-4 of a turn's angle)
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = positions.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def retention(q, k, v, lg, eps: float):
    """The attention form: ``q`` [T, Hk, G, d], ``k``, ``v`` [T, Hk, d],
    ``lg`` [T, Hk] -> ``y`` [T, Hk, G, d], a block of queries at a time."""
    T, Hk, G, d = q.shape
    c = jnp.cumsum(lg, axis=0)                                    # [T, Hk]
    block = min(QUERY_BLOCK, T)
    pad = -T % block
    j = jnp.arange(T)[None, :]
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0), (0, 0))).reshape(
        -1, block, Hk, G, d)
    cb = jnp.pad(c, ((0, pad), (0, 0))).reshape(-1, block, Hk)

    def one_block(args):
        qi, ci, i0 = args
        seen = j <= (i0 + jnp.arange(block))[:, None]             # [block, T]
        s = jnp.einsum("thgd,shd->hgts", qi, k) / jnp.sqrt(F32(d))
        decay = jnp.exp(jnp.where(seen[None], ci.T[:, :, None]
                                  - c.T[:, None, :], 0.0))        # [Hk, b, T]
        w = jnp.where(seen[None, None], s * s * decay[:, None], 0.0)
        return jnp.einsum("hgts,shd->thgd", w, v) \
            / (jnp.transpose(w.sum(-1), (2, 0, 1))[..., None] + eps)

    out = jax.lax.map(one_block, (qb, cb, jnp.arange(qb.shape[0]) * block))
    return out.reshape(-1, Hk, G, d)[:T]


def triangle(d: int):
    """The default order of a state's entries: the upper triangle's pairs
    row-major, as ``(i, j, m)``."""
    i, j = np.triu_indices(d)
    return i.astype(np.int32), j.astype(np.int32), \
        np.where(i == j, 1.0, 2.0).astype(np.float32)


def gate(lg):
    """``g = exp(lg)`` of one token's log-gates, by the series where ``|lg|``
    is small: a device's ``exp`` may be a part in a million off, always the
    same way (a TPU v5e's is), and a state multiplied by it token after token
    is then 0.5% off after 4,000 tokens of a gate near 1."""
    series = 1.0 + lg * (1.0 + lg * (0.5 + lg * (1.0 / 6 + lg * (1.0 / 24))))
    return jnp.where(lg > -0.05, series, jnp.exp(lg))


def recurrence(q, k, v, lg, expansion, eps: float, state_dtype=None):
    """The state form, token by token from an empty state: (``y`` [T, Hk, G,
    d], ``S`` [Hk, d, D], ``z`` [Hk, D] after the last token)."""
    i, j, m = (jnp.asarray(x) for x in expansion)
    Hk, d = k.shape[1:]
    holds = (m > 0).astype(F32)

    def step(carry, row):
        S, z = carry
        q_t, k_t, v_t, lg_t = row
        g = gate(lg_t)
        pk = k_t[:, i] * k_t[:, j] * m                            # [Hk, D]
        S = rounded(g[:, None, None] * S + v_t[:, :, None] * pk[:, None, :],
                    state_dtype)
        z = rounded(g[:, None] * z + pk, state_dtype)
        pq = q_t[..., i] * q_t[..., j] * holds                    # [Hk, G, D]
        y = jnp.einsum("hcD,hgD->hgc", S, pq) \
            / (jnp.einsum("hD,hgD->hg", z, pq)[..., None] + d * eps)
        return (S, z), y

    D = i.shape[0]
    start = (jnp.zeros((Hk, d, D), F32), jnp.zeros((Hk, D), F32))
    (S, z), y = jax.lax.scan(step, start, (q, k, v, lg))
    return y, S, z


def retention_mixer(u, layer: Dict[str, Any], hp: Dict[str, Any], expansion,
                    state_dtype, act_dtype):
    """``u`` [T, H] -> (out [T, H], the state after the last token as the
    pool lays it out, ``[D, Hk d + Hk]``: head ``i``'s ``S`` in columns ``i
    d .. (i + 1) d``, then ``z`` a head; of zero size in the attention
    form)."""
    f = lambda name: layer[name].astype(F32)
    act = lambda x: rounded(x, act_dtype)
    T = u.shape[0]
    H, Hk, d = hp["num_heads"], hp["num_kv_heads"], hp["head_dim"]
    pos = jnp.arange(T)
    q = act(u @ f("wq")).reshape(T, H, d)
    k = act(u @ f("wk")).reshape(T, Hk, d)
    v = act(u @ f("wv")).reshape(T, Hk, d)
    q = act(rope(act(rms_norm(q, f("q_norm"), hp["eps"])), pos,
                 hp["rope_theta"])).reshape(T, Hk, H // Hk, d)
    k = act(rope(act(rms_norm(k, f("k_norm"), hp["eps"])), pos,
                 hp["rope_theta"]))
    lg = jax.nn.log_sigmoid(act(u @ f("wg")) + f("b_g"))
    if expansion is None:
        y = retention(q, k, v, lg, hp["retention_eps"])
        state = jnp.zeros((0,), F32)
    else:
        y, S, z = recurrence(q, k, v, lg, expansion, hp["retention_eps"],
                             state_dtype)
        state = jnp.concatenate([S.reshape(Hk * d, -1), z], axis=0).T
    return act(act(y.reshape(T, H * d)) @ f("wo")), state


@functools.partial(jax.jit, static_argnames=("hp", "state_dtype", "act_dtype",
                                             "with_state"))
def _layer(x, layer, expansion, hp, state_dtype, act_dtype, with_state):
    hp = dict(hp)
    act = lambda v: rounded(v, act_dtype)
    with jax.default_matmul_precision("highest"):
        f = lambda name: layer[name].astype(F32)
        u = act(rms_norm(x, f("ln_in"), hp["eps"]))
        mixed, state = retention_mixer(
            u, layer, hp, expansion if with_state else None, state_dtype,
            act_dtype)
        x = act(x + mixed)
        g = act(rms_norm(x, f("ln_ff"), hp["eps"]))
        hid = act(jax.nn.silu(act(g @ f("w_gate"))) * act(g @ f("w_up")))
        return act(x + act(hid @ f("w_down"))), state


@functools.partial(jax.jit, static_argnames=("eps",))
def _normed(x, rows, final_norm, eps: float):
    return rms_norm(x[rows], final_norm.astype(F32), eps)


@jax.jit
def _head_block(x, block):
    with jax.default_matmul_precision("highest"):
        return x @ block.astype(F32)


def _static(hp: Dict[str, Any]):
    return tuple(sorted((k, v) for k, v in hp.items() if k != "expansion"))


def forward_logits(weights: Dict[str, Any], ids, hp: Dict[str, Any],
                   rows=None, state_dtype=None, with_state: bool = False,
                   act_dtype=None):
    """Logits [T, V] (or of ``rows`` only) of one sequence ``ids`` [T] in
    the attention form; ``with_state``: in the state form, and every layer's
    state after the last token beside them, ``[L, D, Hk d + Z]`` (``Z``: the
    KV heads in whole eights, as the pool holds a state's sublanes)."""
    ids = np.asarray(ids, np.int32)
    rows = jnp.arange(ids.shape[0]) if rows is None else jnp.asarray(rows)
    x = jnp.asarray(np.asarray(weights["embed"])[ids]).astype(F32)
    expansion = tuple(hp.get("expansion") or triangle(hp["head_dim"]))
    states = []
    for layer in weights["layers"]:
        x, state = _layer(x, layer, expansion, _static(hp), state_dtype,
                          act_dtype, with_state)
        states.append(state)
    x = _normed(x, rows, weights["final_norm"], float(hp["eps"]))
    head = weights["head"]
    logits = jnp.concatenate([
        _head_block(x, head[:, c:c + HEAD_BLOCK])
        for c in range(0, head.shape[1], HEAD_BLOCK)], axis=1)
    if not with_state:
        return logits
    pad = -hp["num_kv_heads"] % 8
    return logits, jnp.pad(jnp.stack(states), ((0, 0), (0, 0), (0, pad)))
