"""A plain reference for the Jamba family (AI21 Jamba2).

Written from the layer equations of the modelling code published with the
checkpoints (``config.json``: ``model_type: jamba``), in ``jax.numpy`` and
float32 with matmuls at the highest precision, with no kernel, cache, state
pool, batching or code of ``deepspeed_tpu``:

- ``x = embed[ids]``; each layer ``x = x + mixer(rmsnorm_in(x))``;
  ``x = x + swiglu(rmsnorm_ff(x))``; final RMSNorm; the head is the embedding
  (``tie_word_embeddings``);
- layer ``i`` is attention where ``i % attn_layer_period ==
  attn_layer_offset``, else Mamba (``hp["kinds"]``, one entry a layer);
- attention: ``q, k, v`` without bias, NO position embedding of any kind,
  causal softmax of ``q k^T / sqrt(head_dim)`` with grouped queries, ``W_o``;
- Mamba-1 (Gu & Dao 2023) with Jamba's three inner RMSNorms, per token ``t``
  (``E`` channels, ``N`` state values a channel, ``K`` taps)::

      [a_t, z_t] = W_in u_t
      c_t = silu(b_conv + sum_j w_conv[:, j] * a_{t-K+1+j})     (zeros before 0)
      [r_t, B_t, C_t] = W_x c_t;  r, B, C each through an RMSNorm of its own
      dt_t = softplus(W_dt r_t + b_dt);   A = -exp(A_log)
      h_t = exp(dt_t[:, None] * A) * h_{t-1} + (dt_t * c_t)[:, None] * B_t[None, :]
      y_t = h_t C_t + D * c_t;   out_t = W_out (y_t * silu(z_t))

  The recurrence is a ``lax.scan`` over tokens, one token at a time, from
  ``h = 0``.

Weights are a plain dict (all matrices ``[in, out]``)::

    {"embed": [V, H], "final_norm": [H],
     "layers": [{"ln_in": [H], "ln_ff": [H],
                 "w_gate": [H, F], "w_up": [H, F], "w_down": [F, H],
                 # attention: "wq": [H, Hq*D], "wk", "wv": [H, Hkv*D],
                 #            "wo": [Hq*D, H]
                 # mamba: "w_in": [H, 2E], "conv_w": [E, K], "conv_b": [E],
                 #        "w_x": [E, R+2N], "g_dt": [R], "g_b": [N], "g_c": [N],
                 #        "w_dt": [R, E], "b_dt": [E], "A_log": [E, N],
                 #        "D": [E], "w_out": [E, H]
                 }, ...]}

and ``hp`` gives ``num_heads``, ``num_kv_heads``, ``head_dim``, ``eps``,
``dt_rank``, ``d_state`` and ``kinds`` (``"mamba"`` or ``"attention"`` a
layer). A layer's weights may lie on the host (numpy): each layer is one
jitted call that is handed that layer's weights alone, so that a model that
fills the device beside the engine is never there twice.

Departures from the published code:

- the published Mamba path (``use_mamba_kernels``) fuses the convolution,
  the scan and the gate in kernels; here each is its equation. Its scan
  keeps ``h`` in float32, as here;
- for memory only: attention runs one block of queries at a time, and the
  layers one jitted call each.

For tests and for sizing a tolerance, not for use: ``state_dtype`` rounds
``h`` to a lower precision after every token (a state pool held in that
precision); ``act_dtype`` rounds what each part of a layer hands on (the
normed input, each product's result, the convolution's output, the mixer's
and the feed-forward's output, the residual stream) to a lower precision,
which is where a program that keeps its activations in that precision
rounds; ``hp["unrounded"]`` names what of the recurrence's inputs that mode
still leaves in float32 (``"dt_proj"``: the product under ``dt``'s softplus,
``"c"``: the convolution's output as the recurrence reads it, ``"B_C"``: ``B``
and ``C`` after their norms) — a program rounds each and widens it again for
its float32 recurrence, a pair of converts that a compiler may drop, and
which ones it drops is the compiler's choice; ``hp["drop"]`` names parts to
leave out (``"conv_history"``: the
convolution sees its own token only, ``"inner_norms"``, ``"D"``, ``"gate"``).
Rounding is ``lax.reduce_precision``: a pair of converts the compiler is free
to drop (the TPU's does: it keeps the excess precision).
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from chipbench.reference.decoder_ref import F32, rms_norm

QUERY_BLOCK = 512
MAMBA, ATTENTION = "mamba", "attention"


def rounded(x, dtype):
    """``x`` at the precision of ``dtype``, still float32 (None: as it is)."""
    if dtype is None:
        return x
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, exponent_bits=info.nexp,
                                    mantissa_bits=info.nmant)


def attention(q, k, v):
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
        s = jnp.einsum("tgrd,sgd->grts", qi, k) / jnp.sqrt(F32(d))
        s = jnp.where(seen[None, None], s, -jnp.inf)
        return jnp.einsum("grts,sgd->tgrd", jax.nn.softmax(s, axis=-1), v)

    out = jax.lax.map(one_block, (qb, jnp.arange(qb.shape[0]) * block))
    return out.reshape(-1, hq, d)[:t]


def recurrence(dt, c, Bm, Cm, A, state_dtype=None):
    """The selective recurrence by itself, token by token from ``h = 0``:
    ``dt``, ``c`` ``[T, E]``, ``Bm``, ``Cm`` ``[T, N]``, ``A`` ``[E, N]``
    (negative) -> (``h_t C_t`` for every token ``[T, E]``, the last ``h``
    ``[E, N]``)."""
    def step(h, row):
        dt_t, c_t, b_t, c_out = row
        h = jnp.exp(dt_t[:, None] * A) * h + (dt_t * c_t)[:, None] * b_t[None]
        h = rounded(h, state_dtype)
        return h, h @ c_out

    h, y = jax.lax.scan(step, jnp.zeros(A.shape, F32), (dt, c, Bm, Cm))
    return y, h


def mamba_mixer(u, layer: Dict[str, Any], hp: Dict[str, Any],
                state_dtype=None, act_dtype=None):
    """``u`` [T, H] -> (out [T, H], the state after the last token [E, N])."""
    drop = hp.get("drop", ())
    R, N = hp["dt_rank"], hp["d_state"]
    f = lambda name: layer[name].astype(F32)
    act = lambda v: rounded(v, act_dtype)
    T = u.shape[0]
    az = act(u @ f("w_in"))
    E = az.shape[1] // 2
    a, z = az[:, :E], az[:, E:]
    w, K = f("conv_w"), layer["conv_w"].shape[1]
    pad = jnp.pad(a, ((K - 1, 0), (0, 0)))
    taps = range(K - 1, K) if "conv_history" in drop else range(K)
    wide = hp.get("unrounded", ())
    c = jax.nn.silu(f("conv_b") + sum(pad[j:j + T] * w[:, j] for j in taps))
    rbc = act(act(c) @ f("w_x"))
    r, Bm, Cm = rbc[:, :R], rbc[:, R:R + N], rbc[:, R + N:]
    if "inner_norms" not in drop:
        r = rms_norm(r, f("g_dt"), hp["eps"])
        Bm = rms_norm(Bm, f("g_b"), hp["eps"])
        Cm = rms_norm(Cm, f("g_c"), hp["eps"])
    # what the float32 recurrence is handed (module docstring: "unrounded")
    c = c if "c" in wide else act(c)
    Bm, Cm = (Bm, Cm) if "B_C" in wide else (act(Bm), act(Cm))
    dt = act(r) @ f("w_dt")
    dt = jax.nn.softplus((dt if "dt_proj" in wide else act(dt)) + f("b_dt"))
    A = -jnp.exp(f("A_log"))                                       # [E, N]
    y, h = recurrence(dt, c, Bm, Cm, A, state_dtype)
    if "D" not in drop:
        y = y + f("D") * c
    if "gate" not in drop:
        y = y * jax.nn.silu(z)
    return act(act(y) @ f("w_out")), h


@functools.partial(jax.jit, static_argnames=("kind", "hp", "state_dtype",
                                             "act_dtype"))
def _layer(x, layer, kind: str, hp, state_dtype, act_dtype):
    hp = dict(hp)
    act = lambda v: rounded(v, act_dtype)
    with jax.default_matmul_precision("highest"):
        f = lambda name: layer[name].astype(F32)
        u = act(rms_norm(x, f("ln_in"), hp["eps"]))
        if kind == MAMBA:
            mixed, h = mamba_mixer(u, layer, hp, state_dtype, act_dtype)
        else:
            T, D = x.shape[0], hp["head_dim"]
            q = act(u @ f("wq")).reshape(T, hp["num_heads"], D)
            k = act(u @ f("wk")).reshape(T, hp["num_kv_heads"], D)
            v = act(u @ f("wv")).reshape(T, hp["num_kv_heads"], D)
            mixed = act(act(attention(q, k, v).reshape(T, -1)) @ f("wo"))
            h = jnp.zeros((0,), F32)
        x = act(x + mixed)
        g = act(rms_norm(x, f("ln_ff"), hp["eps"]))
        hid = act(jax.nn.silu(act(g @ f("w_gate"))) * act(g @ f("w_up")))
        return act(x + act(hid @ f("w_down"))), h


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, rows, final_norm, embed, eps: float):
    with jax.default_matmul_precision("highest"):
        return rms_norm(x[rows], final_norm.astype(F32), eps) \
            @ embed.astype(F32).T


def _static(hp: Dict[str, Any]):
    return tuple(sorted((k, tuple(v) if isinstance(v, (list, tuple)) else v)
                        for k, v in hp.items() if k != "kinds"))


def forward_logits(weights: Dict[str, Any], ids, hp: Dict[str, Any],
                   rows=None, state_dtype=None, with_state: bool = False,
                   act_dtype=None):
    """Logits [T, V] (or of ``rows`` only) of one sequence ``ids`` [T];
    ``with_state`` adds the Mamba layers' states after the last token
    ``[Lm, E, N]``."""
    ids = jnp.asarray(ids, jnp.int32)
    rows = jnp.arange(ids.shape[0]) if rows is None else jnp.asarray(rows)
    embed = jnp.asarray(weights["embed"])
    x = embed[ids].astype(F32)
    states = []
    for kind, layer in zip(hp["kinds"], weights["layers"]):
        x, h = _layer(x, layer, kind, _static(hp), state_dtype, act_dtype)
        if kind == MAMBA:
            states.append(h)
    logits = _head(x, rows, weights["final_norm"], embed, float(hp["eps"]))
    return (logits, jnp.stack(states)) if with_state else logits
