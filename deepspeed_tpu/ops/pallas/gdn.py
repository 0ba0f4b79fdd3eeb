"""Gated delta-rule kernels for the serving path (Gated DeltaNet; Qwen3-Next's
linear-attention layers).

One layer, per token ``t`` and value head ``h`` (``Hv`` value heads of ``P``
channels, ``E = Hv P``; ``Hk`` key heads of ``N`` values, each serving ``Hv /
Hk`` value heads), the state a matrix ``S[h]`` ``[N, P]``::

    S'      = exp(g_t[h]) S_{t-1}[h]                       (g <= 0, a head)
    S_t[h]  = S' + beta_t[h] k_t (v_t[h] - S'^T k_t)^T     (the delta rule)
    o_t[h]  = S_t[h]^T q_t

all in float32. Where Mamba-2 (``ssm.py``) decays its state and ADDS a
rank-one term, this CORRECTS the state: what the state already predicts for
the key, ``S'^T k_t``, is taken off the value before it is written. The state
takes the state pool's layout, ``[.., N, E]`` with channel ``h * P + p`` of
value head ``h`` on the lanes and the key's ``N`` values down the sublanes
(2 MiB a sequence a layer at ``N`` 128 and 32 heads of 128), so the pool, its
slots and its tails are the ones the Mamba layers use.

- :func:`gdn_decode_step`: one token per row, each row's state somewhere in
  the pool ``[Lm, slots, N, E]``, aliased through the call. A grid step takes
  one row's whole state where it lies (layer and slot from prefetched
  scalars), decays it, forms ``S'^T k`` (a reduction down the sublanes),
  writes the rank-one correction, forms ``S^T q`` (the second reduction) and
  puts the state back: one read and one write of the state, both reductions
  on the block in on-chip memory. The keys and queries arrive with their
  ``N`` values down the sublanes (``[S, N, 128]``: lane ``j`` key head ``j``,
  lane ``Hk + j`` query head ``j``), so the kernel turns nothing. The row's
  convolution tail rides along as in ``ssd_decode_step``.
- :func:`gdn_chunk_scan`: a pass's packed prompt rows, ``G`` chunk slots of
  ``Cs`` rows, in the chunked (WY / UT) form over chunks of ``Q`` tokens.
  With ``c_t`` the running sum of ``g`` inside a chunk, ``A[t, s] = beta_t
  exp(c_t - c_s) (k_t . k_s)`` for ``s < t`` and ``w_t`` the correction each
  token writes (``S_t = exp(g_t) S_{t-1} + k_t w_t^T``)::

      (I + A) W = beta (V - exp(c) K S_0)
      O         = exp(c) Q S_0 + (M o Q K^T) W,   M[t, s] = exp(c_t - c_s), s <= t
      S_Q       = exp(c_Q) S_0 + (exp(c_Q - c) K)^T W

  ``(I + A)^-1`` is unit lower triangular and is built by block forward
  substitution, doubling the block: with ``P_b`` the inverse's diagonal
  blocks of size ``b`` and ``A_b`` the part of ``A`` below the diagonal of
  each ``2b`` block, ``P_2b = P_b - P_b A_b P_b`` (two ``Q x Q`` products a
  level, ``log2 Q - 1`` levels; the Neumann doubling ``(I - A)(I + A^2)..``
  would cancel large powers of ``A`` where keys repeat). Everything that
  meets the state is a float32 product at the highest precision; ``Q K^T``
  and ``K K^T`` are products of the activations as they are (exact in one
  pass where they are bfloat16). ``exp`` only ever sees differences ``<= 0``.
  The state stays in on-chip memory across a slot's chunks; ``h0``/``cont``
  are ``ssd_chunk_scan``'s, so chunked and paged prefill resume a sequence.
  Rows with ``g = 0`` and ``beta = 0`` leave the state as it is, which is how
  a chunk shorter than its slot is padded.

Each has a plain-XLA twin (``*_xla``), the recurrence token by token, for
shapes the kernels refuse and as what the tests hold them to. The state is
float32 here as in ``ssm.py``, and for its reason: a state rounded to
bfloat16 after every token loses what a token wrote within a few hundred
tokens. On the CPU the kernels run through the Pallas interpreter.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from deepspeed_tpu.ops.pallas import _backend
from deepspeed_tpu.ops.pallas.ssm import (LANES, TAP_ROWS, _per_channel,
                                          _tail_rows)
from deepspeed_tpu.utils.jax_compat import import_pltpu

pltpu = import_pltpu()

_HIGHEST = jax.lax.Precision.HIGHEST
#: tokens of one chunk of :func:`gdn_chunk_scan`: the largest of these at or
#: under ``chunk`` that divides a chunk slot
GDN_CHUNKS = (128, 64, 32, 16, 8)
#: a row's whole state is one block of the decode kernel up to this many
#: bytes (2 MiB at N 128, E 4096: in and out, double-buffered, 8 MiB)
_DECODE_BLOCK_BYTES = 2 << 20


def _heads(x: jax.Array, H: int) -> jax.Array:
    return x.reshape(x.shape[:-1] + (H, x.shape[-1] // H))


# --------------------------------------------------------------------------- #
# one token per row, state in the pool
# --------------------------------------------------------------------------- #

def _decode_kernel(l_ref, slot_ref, dec_ref, beta_ref, v_ref, kq_ref, new_ref,
                   h_ref, t_ref, y_ref, ho_ref, to_ref, *, P: int, Hk: int):
    del l_ref, slot_ref               # read by the index maps
    E = h_ref.shape[3]
    rep = E // P // Hk                # value heads a key head serves
    kq = kq_ref[0]                                            # [N, 128]
    for hk in range(Hk):
        k = kq[:, hk:hk + 1]                                  # [N, 1]
        q = kq[:, Hk + hk:Hk + hk + 1]
        for r in range(rep):
            lanes = slice((hk * rep + r) * P, (hk * rep + r + 1) * P)
            S = dec_ref[0, :, lanes] * h_ref[0, 0, :, lanes]  # [N, P]
            pred = jnp.sum(S * k, axis=0, keepdims=True)      # S'^T k
            S = S + k * (beta_ref[0, :, lanes]
                         * (v_ref[0, :, lanes] - pred))
            ho_ref[0, 0, :, lanes] = S
            y_ref[0, :, lanes] = jnp.sum(S * q, axis=0, keepdims=True)
    # the tail: taps 1.. move down one, the row's new input is the newest
    kept = t_ref.shape[2] - TAP_ROWS
    if kept:
        to_ref[0, 0, :kept] = t_ref[0, 0, TAP_ROWS:]
    to_ref[0, 0, kept:] = new_ref[0]


def gdn_decode_step(pool: jax.Array, tails: jax.Array, l, slots: jax.Array,
                    g: jax.Array, beta: jax.Array, q: jax.Array,
                    k: jax.Array, v: jax.Array, new: jax.Array):
    """One step of the gated delta rule for ``S`` rows whose states lie in
    ``pool``, and the shift of their convolution tails in ``tails``.

    pool:  [Lm, NS, N, E] float32 — ALIASED; channel ``h * P + p`` of value
           head ``h`` on the lanes (the module's docstring)
    tails: [Lm, NS, (K-1)*8, W8] float32 — ALIASED, as ``ssd_decode_step``'s
    l:     the layer among the pool's ``Lm`` (traced scalar)
    slots: [S] int32, each row's slot (rows of a padded bucket name the dump
           slot; a slot named twice keeps one of the two results)
    g:     [S, Hv] float32 log-decay (<= 0)     beta: [S, Hv] float32
    q, k:  [S, Hk * N] (normalised; ``q`` scaled)     v: [S, E]
    new:   [S, W] the convolution's input at this token (q, k and v's)

    Returns ``(o [S, E] float32, pool, tails)``."""
    Lm, NS, N, E = pool.shape
    S, Hv = g.shape
    Hk = k.shape[1] // N
    P = E // Hv
    TR, W8 = tails.shape[2:]
    if P % LANES or N % 8 or W8 % LANES or 2 * Hk > LANES or Hv % Hk \
            or N * E * 4 > _DECODE_BLOCK_BYTES:
        return gdn_decode_step_xla(pool, tails, l, slots, g, beta, q, k, v,
                                   new)
    f32 = jnp.float32
    with jax.named_scope("gdn_decode_step"):
        # keys and queries with N down the sublanes: [S, N, 128]
        kq = jnp.concatenate([_heads(k, Hk), _heads(q, Hk)], axis=1)
        kq = jnp.pad(jnp.swapaxes(kq.astype(f32), 1, 2),
                     ((0, 0), (0, 0), (0, LANES - 2 * Hk)))
        row = lambda i, l_ref, s_ref: (i, 0, 0)
        state = pl.BlockSpec(
            (1, 1, N, E), lambda i, l_ref, s_ref: (l_ref[0], s_ref[i], 0, 0))
        tail = pl.BlockSpec(
            (1, 1, TR, W8), lambda i, l_ref, s_ref: (l_ref[0], s_ref[i], 0, 0))
        chan = pl.BlockSpec((1, 1, E), row)
        call = pl.pallas_call(
            functools.partial(_decode_kernel, P=P, Hk=Hk),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(S,),
                in_specs=[chan, chan, chan, pl.BlockSpec((1, N, LANES), row),
                          pl.BlockSpec((1, TAP_ROWS, W8), row), state, tail],
                out_specs=[chan, state, tail]),
            out_shape=[jax.ShapeDtypeStruct((S, 1, E), f32),
                       jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                       jax.ShapeDtypeStruct(tails.shape, tails.dtype)],
            input_output_aliases={7: 1, 8: 2},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=48 << 20),
            interpret=_backend.interpret(),
        )
        y, pool, tails = call(
            jnp.asarray(l, jnp.int32).reshape(1), slots.astype(jnp.int32),
            _per_channel(jnp.exp(g.astype(f32)), P)[:, None],
            _per_channel(beta, P)[:, None], v.astype(f32)[:, None], kq,
            _tail_rows(new, tails), pool, tails)
    return y[:, 0], pool, tails


def _delta_step(S, g, beta, q, k, v):
    """One token of the recurrence on ``S`` ``[.., N, Hv, P]``: ``g``,
    ``beta`` ``[.., Hv]``, ``q``, ``k`` ``[.., Hv, N]``, ``v`` ``[.., Hv, P]``
    -> (the new state, ``o`` ``[.., Hv, P]``)."""
    dot = functools.partial(jnp.einsum, precision=_HIGHEST)
    S = jnp.exp(g)[..., None, :, None] * S
    w = beta[..., None] * (v - dot("...nhp,...hn->...hp", S, k))
    S = S + dot("...hn,...hp->...nhp", k, w)
    return S, dot("...nhp,...hn->...hp", S, q)


def _per_value_head(x: jax.Array, Hk: int, Hv: int) -> jax.Array:
    """``[.., Hk * N]`` -> ``[.., Hv, N]`` float32: a key head's values for
    each of the value heads it serves."""
    return jnp.repeat(_heads(x.astype(jnp.float32), Hk), Hv // Hk, axis=-2)


def gdn_decode_step_xla(pool, tails, l, slots, g, beta, q, k, v, new):
    """:func:`gdn_decode_step` in plain XLA: gather the rows' states and
    tails, one step of the recurrence, scatter them back."""
    Lm, NS, N, E = pool.shape
    S, Hv = g.shape
    Hk = k.shape[1] // N
    TR, W8 = tails.shape[2:]
    f32 = jnp.float32
    with jax.named_scope("gdn_decode_step_xla"):
        flat = pool.reshape(Lm * NS, N, E)
        rows = l * NS + slots
        h, o = _delta_step(
            _heads(flat[rows], Hv), g.astype(f32), beta.astype(f32),
            _per_value_head(q, Hk, Hv), _per_value_head(k, Hk, Hv),
            _heads(v.astype(f32), Hv))
        tflat = tails.reshape(Lm * NS, TR, W8)
        shifted = jnp.concatenate(
            [tflat[rows][:, TAP_ROWS:], _tail_rows(new, tails)], axis=1)
        return (o.reshape(S, E),
                flat.at[rows].set(h.reshape(S, N, E)).reshape(pool.shape),
                tflat.at[rows].set(shifted).reshape(tails.shape))


# --------------------------------------------------------------------------- #
# a pass's packed prompt rows, state on chip across a slot
# --------------------------------------------------------------------------- #

def _scan_kernel(cont_ref, q_ref, k_ref, kt_ref, v_ref, col_ref, row_ref,
                 beta_ref, h0_ref, y_ref, ht_ref, h_sc, *,
                 blocks_per_slot: int, P: int, exact):
    tb, e = pl.program_id(0), pl.program_id(1)
    slot = tb // blocks_per_slot
    f32 = jnp.float32

    def dot(a, b, precision=_HIGHEST):
        return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                                   precision=precision,
                                   preferred_element_type=f32)

    @pl.when(jnp.logical_and(tb % blocks_per_slot == 0, cont_ref[slot] == 0))
    def _():
        h_sc[e] = h0_ref[0]

    Q = q_ref.shape[0]
    Qm, Km, Kt = q_ref[...], k_ref[...], kt_ref[0, 0]    # [Q, N] x2, [N, Q]
    # the activations' own products: exact in one pass where they are
    # bfloat16 (``exact`` is then None), float32 products otherwise
    KK, QK = dot(Km, Kt, exact), dot(Qm, Kt, exact)      # [Q, Q]
    Qf, Kf, Ktf = Qm.astype(f32), Km.astype(f32), Kt.astype(f32)
    t = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    s = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    seen, before = t >= s, t > s
    eye = (t == s).astype(f32)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, P), 1)
    col, row, bcol = col_ref[0, 0], row_ref[0, 0], beta_ref[0, 0]
    S_all = h_sc[e]                                      # [N, Eb]
    ys, states = [], []
    for r in range(S_all.shape[1] // P):
        cj, rj = col[:, r:r + 1], row[r:r + 1, :]        # [Q, 1], [1, Q]
        bj = bcol[:, r:r + 1]
        last = cj[Q - 1:Q, :]                            # [1, 1]
        D = jnp.exp(jnp.where(seen, cj - rj, -jnp.inf))  # exp(c_t - c_s)
        A = jnp.where(before, bj * D * KK, 0.0)
        # (I + A)^-1 by block forward substitution, the block doubled
        inv = eye - jnp.where((t // 2 == s // 2), A, 0.0)
        b = 2
        while b < Q:
            below = jnp.logical_and(
                t // (2 * b) == s // (2 * b),
                jnp.logical_and((t // b) % 2 == 1, (s // b) % 2 == 0))
            inv = inv - dot(inv, dot(jnp.where(below, A, 0.0), inv))
            b *= 2
        S = S_all[:, r * P:(r + 1) * P]                  # [N, P]
        carried = jnp.exp(cj)                            # exp(c_t)
        V = v_ref[:, r * P:(r + 1) * P].astype(f32)
        W = dot(inv, bj * (V - carried * dot(Kf, S)))    # [Q, P]
        ys.append(carried * dot(Qf, S)
                  + dot(jnp.where(seen, D * QK, 0.0), W))
        # ([1, 1] -> [N, P] one axis at a time, as ``_ssd_scan_kernel`` does
        # it: Mosaic broadcasts along the lanes or down the sublanes, not
        # both at once, and folds a multiplication by ones into one)
        whole = jnp.where(lane >= 0, jnp.exp(last), 0.0)  # exp(c_Q), [1, P]
        states.append(whole * S + dot(Ktf * jnp.exp(last - rj), W))
    y_ref[...] = ys[0] if len(ys) == 1 else jnp.concatenate(ys, axis=1)
    S_new = states[0] if len(states) == 1 else jnp.concatenate(states, axis=1)
    h_sc[e] = S_new
    ht_ref[0] = S_new


def gdn_chunk_scan(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                   beta: jax.Array, h0: jax.Array, cont: jax.Array,
                   chunk: int = 64):
    """The gated delta rule over ``G`` chunk slots of ``Cs`` packed rows
    each, in the chunked form over chunks of ``Q`` tokens (``chunk``, or the
    largest of :data:`GDN_CHUNKS` under it that divides ``Cs``).

    q, k: [G*Cs, Hk * N] (normalised; ``q`` scaled), any float dtype
    v:    [G*Cs, E]
    g:    [G*Cs, Hv] float32 log-decay (<= 0; zero on rows that hold no token)
    beta: [G*Cs, Hv] float32 (zero on rows that hold no token)
    h0:   [G, N, E] float32, the state a slot starts from
    cont: [G] int32, as ``ssm_chunk_scan``'s

    Returns ``(o [G*Cs, E] float32, hT [G, N, E] float32)``."""
    G, N, E = h0.shape
    T, Hv = g.shape
    Hk = k.shape[1] // N
    Cs, P = T // G, E // Hv
    Q = next((c for c in GDN_CHUNKS if c <= chunk and Cs % c == 0), 0)
    if not Q or P % LANES or N % LANES or Hv % Hk:
        return gdn_chunk_scan_xla(q, k, v, g, beta, h0, cont)
    R = Hv // Hk                    # value heads a key head serves: a block
    Eb, nC, bps = R * P, T // Q, Cs // Q
    f32 = jnp.float32
    with jax.named_scope("gdn_chunk_scan"):
        heads = lambda x: x.astype(f32).reshape(nC, Q, Hk, R)
        cum = jnp.cumsum(heads(g), axis=1)
        col = jnp.transpose(cum, (0, 2, 1, 3))                # [nC, Hk, Q, R]
        row = jnp.transpose(cum, (0, 2, 3, 1))                # [nC, Hk, R, Q]
        bcol = jnp.transpose(heads(beta), (0, 2, 1, 3))
        Kt = jnp.transpose(k.reshape(nC, Q, Hk, N), (0, 2, 3, 1))
        cont = cont.astype(jnp.int32).at[0].set(0)
        tok = lambda tb, e, c: (tb, e)
        slot = lambda tb, e, c: (tb // bps, 0, e)
        head = lambda tb, e, c: (tb, e, 0, 0)
        call = pl.pallas_call(
            functools.partial(
                _scan_kernel, blocks_per_slot=bps, P=P,
                exact=None if q.dtype == k.dtype == jnp.bfloat16
                else _HIGHEST),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(nC, Hk),
                in_specs=[pl.BlockSpec((Q, N), tok), pl.BlockSpec((Q, N), tok),
                          pl.BlockSpec((1, 1, N, Q), head),
                          pl.BlockSpec((Q, Eb), tok),
                          pl.BlockSpec((1, 1, Q, R), head),
                          pl.BlockSpec((1, 1, R, Q), head),
                          pl.BlockSpec((1, 1, Q, R), head),
                          pl.BlockSpec((1, N, Eb), slot)],
                out_specs=[pl.BlockSpec((Q, Eb), tok),
                           pl.BlockSpec((1, N, Eb), slot)],
                scratch_shapes=[pltpu.VMEM((Hk, N, Eb), f32)]),
            out_shape=[jax.ShapeDtypeStruct((T, E), f32),
                       jax.ShapeDtypeStruct((G, N, E), f32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=64 << 20),
            interpret=_backend.interpret(),
        )
        y, hT = call(cont, q, k, Kt, v, col, row, bcol, h0.astype(f32))
    return y, hT


def gdn_chunk_scan_xla(q, k, v, g, beta, h0, cont):
    """:func:`gdn_chunk_scan` in plain XLA, in the RECURRENT form: token by
    token, slot after slot."""
    G, N, E = h0.shape
    T, Hv = g.shape
    Hk = k.shape[1] // N
    Cs = T // G
    f32 = jnp.float32
    slots = lambda x: x.reshape((G, Cs) + x.shape[1:])
    xs = (slots(g.astype(f32)), slots(beta.astype(f32)),
          slots(_per_value_head(q, Hk, Hv)), slots(_per_value_head(k, Hk, Hv)),
          slots(_heads(v.astype(f32), Hv)))

    def step(S, row):
        return _delta_step(S, *row)

    with jax.named_scope("gdn_chunk_scan_xla"):
        ys, hs = [], []
        h = jnp.zeros((N, Hv, E // Hv), f32)
        for i in range(G):
            start = _heads(h0[i].astype(f32), Hv)
            h = jnp.where(cont[i] != 0, h, start) if i else start
            h, y = jax.lax.scan(step, h, tuple(x[i] for x in xs))
            ys.append(y.reshape(Cs, E))
            hs.append(h.reshape(N, E))
        return jnp.concatenate(ys), jnp.stack(hs)
