"""Gated delta-rule kernels for the serving path (Gated DeltaNet; Qwen3-Next's
linear-attention layers).

One layer, per token ``t`` and value head ``h`` (``Hv`` value heads of ``P``
channels, ``E = Hv P``; ``Hk`` key heads of ``N`` values, each serving ``Hv /
Hk`` value heads), the state a matrix ``S[h]`` ``[N, P]``::

    S'      = exp(g_t[h]) S_{t-1}[h]                       (g <= 0, a head)
    S_t[h]  = S' + beta_t[h] k_t (v_t[h] - S'^T k_t)^T     (the delta rule)
    o_t[h]  = S_t[h]^T q_t

all in float32. Where Mamba-2 (``ssm.py``) decays its state and ADDS a
rank-one term, this CORRECTS it: what the state already predicts for the key,
``S'^T k_t``, is taken off the value before it is written. The state takes the
pool's layout, ``[.., N, E]`` with channel ``h * P + p`` of value head ``h`` on
the lanes and the key's ``N`` values down the sublanes (2 MiB a sequence a layer
at ``N`` 128, 32 heads of 128): the pool, slots and tails of the Mamba layers.

- :func:`gdn_decode_step`: one token per row, each row's state somewhere in
  the pool ``[Lm, slots, N, E]``, aliased through the call. A grid step takes
  one row's whole state where it lies (layer and slot from prefetched
  scalars), decays it, forms ``S'^T k`` (a reduction down the sublanes),
  writes the rank-one correction, forms ``S^T q`` (the second reduction) and
  puts the state back: one read and one write of it, both reductions on chip.
  Keys and queries arrive with their ``N`` values down the sublanes (``[S, N,
  128]``: lane ``j`` key head ``j``, lane ``Hk + j`` query head ``j``), so the
  kernel turns nothing. The tail rides along as in ``ssd_decode_step``.
- :func:`gdn_chunk_scan`: a pass's packed prompt rows, ``G`` chunk slots of
  ``Cs`` rows, in the chunked (WY / UT) form over chunks of ``Q`` tokens.
  With ``c_t`` the running sum of ``g`` inside a chunk, ``A[t, s] = beta_t
  exp(c_t - c_s) (k_t . k_s)`` for ``s < t`` and ``w_t`` the correction each
  token writes (``S_t = exp(g_t) S_{t-1} + k_t w_t^T``)::

      (I + A) W = beta (V - exp(c) K S_0)
      O         = exp(c) Q S_0 + (M o Q K^T) W,   M[t, s] = exp(c_t - c_s), s <= t
      S_Q       = exp(c_Q) S_0 + (exp(c_Q - c) K)^T W

  ``(I + A)^-1`` is unit lower triangular and is built by block forward
  substitution, doubling the block: ``P_2b = P_b - P_b A_b P_b`` with ``P_b``
  the inverse's diagonal blocks of size ``b`` and ``A_b`` what ``A`` has below
  the diagonal of each ``2b`` block (two products a level, ``log2 Q - 1``
  levels; the Neumann doubling ``(I - A)(I + A^2)..`` would cancel large
  powers of ``A`` where keys repeat). The value heads of a key head that fit
  one 128-wide tile (two at ``Q`` 64) lie block-diagonally in ONE such chain.
  A grid step issues only the MXU passes that change its float32 result: the
  products with no bfloat16 operand (the chain, ``inv . rhs``, ``(M o Q K^T)
  W``) take the six passes of the highest precision; where the activations
  are bfloat16, ``[K; Q] S_0`` and ``K^T (d W)`` take three (``_on_state``:
  the other three terms of the six-pass sum multiply zeros) and ``Q K^T``,
  ``K K^T`` one; a chunk whose ``g`` and ``beta`` are all zero (how a slot is
  padded) is ``o = Q S_0`` alone. ``exp`` only sees differences ``<= 0``. The
  state stays on chip across a slot's chunks; ``h0``/``cont`` are
  ``ssd_chunk_scan``'s, so chunked and paged prefill resume a sequence.

Each has a plain-XLA twin (``*_xla``), the recurrence token by token, for
shapes the kernels refuse and as what the tests hold them to. The state is
float32 as in ``ssm.py``, for its reason: rounded to bfloat16 after every token
it loses what one wrote within a few hundred. On the CPU: the interpreter.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas import _backend
from deepspeed_tpu.ops.pallas.ssm import (LANES, TAP_ROWS, _per_channel,
                                          _tail_rows)


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

def _dot(a, b, precision=_HIGHEST, contract=((1,), (0,))):
    return jax.lax.dot_general(a, b, (contract, ((), ())), precision=precision,
                               preferred_element_type=jnp.float32)


def _on_state(a, x):
    """Activations ``a`` times float32 ``x``, to float32. The six-pass
    product splits each operand in three bfloat16 parts and sums ``a0 x0, a0
    x1, a1 x0, a1 x1, a0 x2, a2 x0``; where ``a`` IS bfloat16, ``a1 = a2 =
    0`` and three of the six multiply zeros. So ``x`` is split here
    (``hi + mid + lo`` is ``x`` to 24 bits) and the three terms that are left
    are one-pass products, the smallest summed first. Float32 activations
    take the six passes."""
    if a.dtype != jnp.bfloat16:
        return _dot(a, x)
    f32, bf16 = jnp.float32, jnp.bfloat16
    hi = x.astype(bf16)
    rest = x - hi.astype(f32)
    mid = rest.astype(bf16)
    lo = (rest - mid.astype(f32)).astype(bf16)
    return _dot(a, lo, None) + _dot(a, mid, None) + _dot(a, hi, None)


def _heads_packed(R: int, Q: int) -> int:
    """How many of a key head's ``R`` value heads share one inverse chain:
    the most that fit a ``LANES``-wide tile at chunks of ``Q`` and divide
    ``R`` (2 at ``Q`` 64, 1 at ``Q`` 128)."""
    return max(p for p in range(1, R + 1) if R % p == 0 and p * Q <= LANES)


def _scan_kernel(cont_ref, still_ref, q_ref, k_ref, kt_ref, v_ref, col_ref,
                 row_ref, h0_ref, y_ref, ht_ref, h_sc, *,
                 blocks_per_slot: int, P: int, pack: int):
    tb, e = pl.program_id(0), pl.program_id(1)
    slot = tb // blocks_per_slot

    @pl.when(jnp.logical_and(tb % blocks_per_slot == 0, cont_ref[slot] == 0))
    def _():
        h_sc[e] = h0_ref[0]

    # a chunk whose every g and beta is zero writes nothing (A = 0, W = 0,
    # every decay 1): o = Q S_0, and the state is what it was
    @pl.when(still_ref[tb] != 0)
    def _():
        y_ref[...] = _on_state(q_ref[...], h_sc[e])
        ht_ref[0] = h_sc[e]

    @pl.when(still_ref[tb] == 0)
    def _():
        y_ref[...], S_new = _scan_chunk(
            q_ref[...], k_ref[...], kt_ref[0, 0], v_ref[...], col_ref[0, 0],
            row_ref[0, 0], h_sc[e], P=P, pack=pack)
        h_sc[e] = S_new
        ht_ref[0] = S_new


def _scan_chunk(Qm, Km, Kt, Vm, col, row, S_all, *, P: int, pack: int):
    """One chunk of one key head from the state ``S_all`` ``[N, Eb]`` -> (``o``
    ``[Q, Eb]``, the new state), ``pack`` of its value heads at a time: their
    ``A`` is block diagonal in ONE ``[pack Q, pack Q]`` matrix (row ``i Q +
    t``, column ``i Q + s`` for head ``i`` of the pack), and the doubling never
    couples two blocks (``Q`` is a multiple of every ``2b``), so one chain of
    products on full tiles inverts them all."""
    f32 = jnp.float32
    Q, Rp = Qm.shape[0], row.shape[0]
    PQ = pack * Q
    stack = lambda xs: xs[0] if len(xs) == 1 else jnp.concatenate(xs, axis=0)
    beside = lambda xs: xs[0] if len(xs) == 1 else jnp.concatenate(xs, axis=1)
    # the activations' own products, K K^T (Q K^T) in every [Q, Q] block:
    # exact in one pass where they are bfloat16, float32 products otherwise
    own = None if Qm.dtype == Km.dtype == jnp.bfloat16 else _HIGHEST
    Kp, nt = stack([Km] * pack), ((1,), (1,))            # [PQ, N]
    KK, QK = _dot(Kp, Kp, own, nt), _dot(stack([Qm] * pack), Kp, own, nt)
    t = jax.lax.broadcasted_iota(jnp.int32, (PQ, PQ), 0)
    s = jax.lax.broadcasted_iota(jnp.int32, (PQ, PQ), 1)
    same = t // Q == s // Q                              # one head's block
    seen, before = jnp.logical_and(same, t >= s), jnp.logical_and(same, t > s)
    eye = (t == s).astype(f32)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, P), 1)
    # [K; Q] S for every head at once: each part of S is latched once
    KQS = _on_state(stack([Km, Qm]), S_all)              # [2Q, Eb]
    ys, writes, wholes = [], [], []
    for j in range(Rp):
        heads = range(j * pack, (j + 1) * pack)
        of = lambda x: stack([x[:, r * P:(r + 1) * P] for r in heads])
        cj, rj = col[:, j:j + 1], row[j:j + 1, :]        # [PQ, 1], [1, PQ]
        bj = col[:, Rp + j:Rp + j + 1]
        D = jnp.exp(jnp.where(seen, cj - rj, -jnp.inf))  # exp(c_t - c_s)
        A = jnp.where(before, bj * D * KK, 0.0)
        # (I + A)^-1 by block forward substitution, the block doubled
        inv = eye - jnp.where((t // 2 == s // 2), A, 0.0)
        b = 2
        while b < Q:
            below = jnp.logical_and(
                t // (2 * b) == s // (2 * b),
                jnp.logical_and((t // b) % 2 == 1, (s // b) % 2 == 0))
            inv = inv - _dot(inv, _dot(jnp.where(below, A, 0.0), inv))
            b *= 2
        carried = jnp.exp(cj)                            # exp(c_t)
        rhs = bj * (of(Vm).astype(f32) - carried * of(KQS[:Q]))
        W = _dot(inv, rhs)                               # [PQ, P]
        y = carried * of(KQS[Q:]) + _dot(jnp.where(seen, D * QK, 0.0), W)
        for i in range(pack):
            rows = slice(i * Q, (i + 1) * Q)
            last = cj[(i + 1) * Q - 1:(i + 1) * Q, :]    # [1, 1]
            ys.append(y[rows])
            # (K^T diag(d)) W = K^T (diag(d) W): W's rows scaled on the vector
            # unit, so that K^T stays the bfloat16 array it arrived as
            writes.append(jnp.exp(last - cj[rows]) * W[rows])
            # ([1, 1] -> [N, P] one axis at a time, as ``_ssd_scan_kernel``
            # does it: Mosaic broadcasts along the lanes or down the sublanes,
            # not both at once, and folds a multiplication by ones into one)
            wholes.append(jnp.where(lane >= 0, jnp.exp(last), 0.0))
    return beside(ys), beside(wholes) * S_all + _on_state(Kt, beside(writes))


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
    pack = _heads_packed(R, Q)      # of them, heads an inverse chain holds
    Rp, PQ = R // pack, pack * Q
    f32 = jnp.float32
    with jax.named_scope("gdn_chunk_scan"):
        # tokens on the lanes, a pack's heads side by side: lane i * Q + t is
        # token t of its head i; the same numbers down the sublanes in ONE
        # array (a [.., PQ, 1] array is 128 lanes wide in memory whatever it
        # holds), column j the sums of group j, column Rp + j its betas
        rows = lambda x: jnp.transpose(
            x.reshape(Hk, Rp, pack, nC, Q), (3, 0, 1, 2, 4)).reshape(
                nC, Hk, Rp, PQ)
        row = rows(jnp.cumsum(g.astype(f32).T.reshape(Hv, nC, Q), axis=-1))
        cols = jnp.swapaxes(jnp.concatenate(
            [row, rows(beta.astype(f32).T)], axis=2), 2, 3)  # [nC, Hk, PQ, 2 Rp]
        Kt = jnp.transpose(k.reshape(nC, Q, Hk, N), (0, 2, 3, 1))
        cont = cont.astype(jnp.int32).at[0].set(0)
        # 1 where a chunk's every g and beta is 0: nothing to write
        still = jnp.all(jnp.logical_and(g == 0, beta == 0).reshape(nC, -1),
                        axis=1).astype(jnp.int32)
        tok = lambda tb, e, c, z: (tb, e)
        slot = lambda tb, e, c, z: (tb // bps, 0, e)
        head = lambda tb, e, c, z: (tb, e, 0, 0)
        call = pl.pallas_call(
            functools.partial(
                _scan_kernel, blocks_per_slot=bps, P=P, pack=pack),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(nC, Hk),
                in_specs=[pl.BlockSpec((Q, N), tok), pl.BlockSpec((Q, N), tok),
                          pl.BlockSpec((1, 1, N, Q), head),
                          pl.BlockSpec((Q, Eb), tok),
                          pl.BlockSpec((1, 1, PQ, 2 * Rp), head),
                          pl.BlockSpec((1, 1, Rp, PQ), head),
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
        y, hT = call(cont, still, q, k, Kt, v, cols, row, h0.astype(f32))
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
