"""Power-retention kernels for the serving path (Brumby; Manifest AI's power
retention, Buckman, Gelada and Zhang, *Scaling Context Requires Rethinking
Attention*, 2025).

One layer, per token ``t``, KV head ``i`` (``Hk`` heads of ``d`` values, each
serving ``G = Hq / Hk`` query heads) and power 2. Attention form, ``s <= t``::

    w[t, s] = (q_t . k_s)^2 exp(c_t - c_s)         c the running sum of log g
    y_t     = sum_s w[t, s] v_s / (sum_s w[t, s] + d eps)

(the scores' ``1 / sqrt(d)`` squared is ``1 / d`` above and below the line, so
it is left out and ``eps`` is multiplied by ``d``). State form, the same
function, with a pair of maps ``pk``, ``pq`` such that ``pq(a) . pk(b) = (a .
b)^2``::

    S_t = g_t S_{t-1} + v_t pk(k_t)^T          [d, D]  float32
    z_t = g_t z_{t-1} + pk(k_t)                [D]     float32
    y_t = S_t pq(q_t) / (z_t . pq(q_t) + d eps)

THE EXPANSION. ``(a . b)^2 = sum_{i <= j} m_ij a_i a_j b_i b_j`` with ``m`` 1
on the diagonal and 2 off it: the ``d (d + 1) / 2`` products of the upper
triangle. ``pq(a)`` holds the products ``a_i a_j``, ``pk(b)`` the same times
``m_ij``: a 1 or a 2, so every entry of ``pk`` of bfloat16 values is an exact
float32 (``sqrt(2)`` on both sides would round). The pairs are laid out as ``d /
2 + 1`` tiles of ``d`` lanes so that a tile is built from two lane broadcasts
and one lane rotation (:func:`_tile`): tile ``t``, lane ``l`` holds pair ``(t,
l)`` where ``l >= t``, else pair ``(d - t, l + d - t)`` — row ``t`` of the
triangle has ``d - t`` entries and row ``d - t`` has ``t``, together one tile
(row 0 is a tile alone and row ``d / 2`` half of one: ``D = d (d / 2 + 1)`` =
8,320 at ``d`` 128, of which 8,256 hold a pair). :func:`expansion` gives the
same layout as index arrays, for the XLA twins and for whoever compares a state.
The expansion is formed on chip, a tile at a time; it is never an array in HBM.

THE POOL. A (sequence, layer) state is ``[Hk d + 8, D]`` float32 in
``ragged/state_pool.py``'s ``ssm [Lm, slots, N, E]`` (``N = Hk d + 8`` down the
sublanes, ``E = D`` on the lanes): row ``i d + c`` is ``S[i][c, :]``, value
channel ``c`` of KV head ``i``; row ``Hk d + i`` is ``z[i]`` (:func:`state_rows`,
:func:`state_cols`; 32.75 MiB at 8 heads of 128). ``D`` lies on the LANES
because that is where the expansion is cheap: one ``[8, 128]`` register then
holds a tile of ``pk`` or ``pq`` for eight heads at once, and a head's ``S``
takes it by a sublane broadcast; with ``D`` down the sublanes each tile would
be sixteen registers a vector and a lane broadcast a register.

- :func:`pr_decode_step`: one token a row, each row's state where it lies in
  the pool, aliased through the call. Grid ``(rows, blocks of D)``: at a row's
  first block the six expansions of every head (``pk(k)``, ``pq(q_j)``) are
  built into on-chip memory; every block then decays its ``[N, tD]`` of the
  state, adds ``v pk^T``, writes it back — one read and one write of a state
  a token — and accumulates ``S pq_j`` and ``z . pq_j`` elementwise; the
  lane sums are taken once, at the row's last block.
- :func:`pr_chunk_scan`: a pass's packed prompt rows, ``slots`` chunk slots of
  ``Cs`` rows, in chunks of ``C`` tokens: the attention form inside a chunk
  (scores squared, decayed, causal), ``exp(c_r - c_0) S_0 pq(q_r)`` for what
  lies before it, one division at the end, and ``S_C = exp(c_C - c_0) S_0 +
  sum_s exp(c_C - c_s) v_s pk(k_s)^T``. The state stays on chip across a
  slot's chunks (grid ``(Hk, slots, chunks)``, the head outermost so that a
  slot that continues the one before it finds the state where it was left).
  A chunk that holds no token (every key zero) is skipped. MXU passes: ``q
  k^T`` of bfloat16 rows is exact in one; a float32 operand is split into
  bfloat16 parts and only the products that matter are issued
  (:func:`_dot3`): to float32's result where the state is written, to 16
  bits where it is read (``y`` is rounded to the model's dtype next).

Each has a plain-XLA twin (``*_xla``: the recurrence token by token) for the
CPU's shapes and as what the tests hold the kernels to. ``S`` and ``z`` are
float32 between steps: rounded to bfloat16 after every token a slow head
(``g`` near 1) loses what one token wrote within a few hundred.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas import _backend


_HIGHEST = jax.lax.Precision.HIGHEST
#: tokens of one chunk of :func:`pr_chunk_scan`: the largest of these at or
#: under ``chunk`` that divides a chunk slot
PR_CHUNKS = (128, 64, 32, 16, 8)
#: a block of the decode kernel holds at most this many bytes of a row's
#: state (in and out, double-buffered: four of them on chip)
_DECODE_BLOCK_BYTES = 3 << 20


def state_cols(d: int) -> int:
    """``D``: the lanes of a state, ``d / 2 + 1`` tiles of ``d``."""
    return d * (d // 2 + 1)


def state_rows(Hk: int, d: int) -> int:
    """The sublanes of a state: ``Hk`` heads' ``S`` (``d`` rows each), then
    ``z`` a head, in whole tiles of 8."""
    return Hk * d + -(-Hk // 8) * 8


@functools.lru_cache(maxsize=None)
def expansion(d: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The layout of the module's docstring as ``(i, j, m)``, each ``[D]``:
    entry ``e`` of ``pq(a)`` is ``a[i[e]] a[j[e]] (m[e] > 0)`` and of ``pk(a)``
    that times ``m[e]`` (1 a diagonal pair, 2 any other, 0 no pair)."""
    if d % 2:
        raise ValueError(f"head size {d} is odd")
    T = d // 2 + 1
    i, j, m = (np.zeros((T, d), dt) for dt in (np.int32, np.int32, np.float32))
    lane = np.arange(d)
    for t in range(T):
        hi = lane >= t
        i[t], j[t] = np.where(hi, t, (d - t) % d), np.where(hi, lane,
                                                              (lane - t) % d)
        m[t] = np.where(i[t] == j[t], 1.0, 2.0)
        if 2 * t == d:              # row d / 2 pairs with itself: half a tile
            m[t] = np.where(hi, m[t], 0.0)
    return i.reshape(-1), j.reshape(-1), m.reshape(-1)


def expand(a: jax.Array, key: bool) -> jax.Array:
    """``pk(a)`` (``key``) or ``pq(a)`` of ``a`` ``[.., d]`` -> ``[.., D]``
    float32, in plain XLA."""
    i, j, m = expansion(a.shape[-1])
    a = a.astype(jnp.float32)
    return a[..., i] * a[..., j] * (m if key else (m > 0).astype(np.float32))


def _tile(a, t: int, key: bool):
    """Tile ``t`` of the expansion of the rows ``a`` ``[R, d]`` (float32):
    ``[R, d]``; ``key``: the rows are keys (their off-diagonal pairs count
    twice), else queries."""
    d = a.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, d), 1)
    two = (lambda x, diag: x * jnp.where(lane != diag, 2.0, 1.0)) if key \
        else (lambda x, diag: x)
    hi = two(a[:, t:t + 1] * a, t)
    if t == 0:
        return hi
    if 2 * t == d:                      # row d / 2 pairs with itself
        return jnp.where(lane >= t, hi, 0.0)
    lo = two(a[:, d - t:d - t + 1] * pltpu.roll(a, t, 1), 0)
    return jnp.where(lane >= t, hi, lo)


def _dot(a, b, precision=None, contract=((1,), (0,))):
    return jax.lax.dot_general(a, b, (contract, ((), ())), precision=precision,
                               preferred_element_type=jnp.float32)


def _split(x, parts: int):
    """Float32 ``x`` as ``parts`` bfloat16 arrays whose sum is ``x`` to 8
    bits a part, the largest first."""
    out = []
    for _ in range(parts):
        out.append(x.astype(jnp.bfloat16))
        x = x - out[-1].astype(jnp.float32)
    return out


def _dot3(a_parts, b_parts, contract=((1,), (0,)), depth: int = 2):
    """The product of two float32 arrays given as bfloat16 parts (largest
    first), in one-pass products: every pair of parts whose ranks sum to at
    most ``depth`` — at 2 the six-pass product's terms (float32's result; an
    operand that IS one or two bfloat16 parts leaves fewer), at 1 three of
    them (16 bits: what is read and rounded to bfloat16 next) — the smallest
    summed first."""
    terms = sorted(((i + j, i, j) for i in range(len(a_parts))
                    for j in range(len(b_parts)) if i + j <= depth),
                   reverse=True)
    acc = None
    for _, i, j in terms:
        p = _dot(a_parts[i], b_parts[j], None, contract)
        acc = p if acc is None else acc + p
    return acc


def gate(lg: jax.Array) -> jax.Array:
    """``exp(lg)`` for a log-gate ``lg <= 0``, float32: by its series where
    ``|lg|`` is small. The chip's ``exp`` is a part in a million off a call,
    always the same way, and a state multiplied by it token after token sums
    that up — 0.5% over 4,000 tokens of a gate of 0.9993 (PERF.md, PR 54) —
    where the chunked scan, which takes ``exp`` of a chunk's summed
    log-gates, does not."""
    lg = lg.astype(jnp.float32)
    series = 1.0 + lg * (1.0 + lg * (0.5 + lg * (1.0 / 6 + lg * (1.0 / 24))))
    return jnp.where(lg > -0.05, series, jnp.exp(lg))


def _lanes_fit(d: int) -> bool:
    """Whether the kernels take heads of ``d``: a tile is ``d`` lanes, so on
    the chip whole 128-lane registers; the interpreter takes any even
    multiple of 8."""
    return d % 128 == 0 or (_backend.interpret() and d % 8 == 0)


def _decode_block(T: int, d: int, N: int) -> int:
    """Tiles of ``D`` a block of the decode kernel holds: the most that
    divide ``T`` within :data:`_DECODE_BLOCK_BYTES`."""
    return max(n for n in range(1, T + 1)
               if T % n == 0 and (n == 1 or n * d * N * 4 <= _DECODE_BLOCK_BYTES))


# --------------------------------------------------------------------------- #
# one token per row, state in the pool
# --------------------------------------------------------------------------- #

def _decode_kernel(row_ref, kq_ref, vt_ref, g_ref, s_ref, num_ref, den_ref,
                   o_ref, phi_sc, acc_sc, zacc_sc, *, Hk: int, G: int, d: int,
                   tiles: int):
    del row_ref                         # read by the index maps
    db, nb = pl.program_id(1), pl.num_programs(1)
    T = d // 2 + 1
    Z = s_ref.shape[1] - Hk * d         # the z rows: heads in whole tiles of 8
    f32 = jnp.float32

    @pl.when(db == 0)
    def _():
        # the expansions of this row's vectors, every head at once: vector 0
        # the key, 1.. the head's queries; heads down the sublanes
        for j in range(G + 1):
            a = kq_ref[0, j]                                    # [Z, d]
            for t in range(T):
                phi_sc[j, :, t * d:(t + 1) * d] = _tile(a, t, j == 0)
        acc_sc[...] = jnp.zeros_like(acc_sc)
        zacc_sc[...] = jnp.zeros_like(zacc_sc)

    g8 = g_ref[0]                                               # [Z, d]
    for lt in range(tiles):
        cols = slice(lt * d, (lt + 1) * d)
        at = pl.ds(pl.multiple_of((db * tiles + lt) * d, d), d)
        pk8 = phi_sc[0, :, at]                                  # [Z, d]
        z = g8 * s_ref[0, Hk * d:, cols] + pk8
        o_ref[0, Hk * d:, cols] = z
        for j in range(G):
            zacc_sc[j] += z * phi_sc[1 + j, :, at]
        for i in range(Hk):
            rows = slice(i * d, (i + 1) * d)
            S = g8[i:i + 1, :] * s_ref[0, rows, cols] \
                + vt_ref[0, :, i:i + 1] * pk8[i:i + 1, :]
            o_ref[0, rows, cols] = S
            for j in range(G):
                acc_sc[i * G + j] += S * phi_sc[1 + j, pl.ds(i, 1), at]

    @pl.when(db == nb - 1)
    def _():
        # the lane sums, once a row: S pq as ones . acc^T (value channels come
        # out on the lanes), z . pq left as lane partial sums for the caller
        ones = jnp.ones((8, d), f32)
        for i in range(Hk):
            for j in range(G):
                num_ref[0, j, i:i + 1, :] = _dot(
                    ones, acc_sc[i * G + j], _HIGHEST, ((1,), (1,)))[:1]
        for j in range(G):
            den_ref[0, j] = zacc_sc[j]


def _vectors(q, k, Hk: int, Z: int):
    """``[S, G + 1, Z, d]`` float32: vector 0 the keys, ``1 + j`` query ``j``
    of each KV head; heads down the sublanes, padded to ``Z``."""
    S = k.shape[0]
    d = k.shape[1] // Hk
    G = q.shape[1] // k.shape[1]
    kq = jnp.concatenate([k.reshape(S, Hk, 1, d), q.reshape(S, Hk, G, d)],
                         axis=2).astype(jnp.float32)
    return jnp.pad(jnp.swapaxes(kq, 1, 2), ((0, 0), (0, 0), (0, Z - Hk), (0, 0)))


def pr_decode_step(pool: jax.Array, l, slots: jax.Array, lg: jax.Array,
                   q: jax.Array, k: jax.Array, v: jax.Array,
                   eps: float = 1e-6):
    """One token of power retention for ``S`` rows whose states lie in
    ``pool``.

    pool:  [Lm, NS, N, D] float32 — ALIASED (the module's docstring)
    l:     the layer among the pool's ``Lm`` (traced scalar)
    slots: [S] int32, each row's slot (rows of a padded bucket name the dump
           slot; a slot named twice keeps one of the two results)
    lg:    [S, Hk] float32, the log of the gate (<= 0)
    q:     [S, Hq * d]     k, v: [S, Hk * d] (normed and rotated; any float)

    Returns ``(y [S, Hq * d] float32, pool)``."""
    Lm, NS, N, D = pool.shape
    S, Hk = lg.shape
    d = k.shape[1] // Hk
    G = q.shape[1] // k.shape[1]
    Z = N - Hk * d
    if not _lanes_fit(d) or Hk > 128:
        return pr_decode_step_xla(pool, l, slots, lg, q, k, v, eps)
    T = d // 2 + 1
    tiles = _decode_block(T, d, N)
    f32 = jnp.float32
    with jax.named_scope("pr_decode_step"):
        heads = lambda x: jnp.pad(x, ((0, 0), (0, Z - Hk)))
        g = jnp.broadcast_to(heads(gate(lg))[:, :, None], (S, Z, d))
        # value channels down the sublanes, lane i KV head i
        vt = jnp.pad(jnp.swapaxes(v.astype(f32).reshape(S, Hk, d), 1, 2),
                     ((0, 0), (0, 0), (0, 128 - Hk)))
        row = lambda s, db, r: (s, 0, 0)
        vec = lambda s, db, r: (s, 0, 0, 0)
        state = pl.BlockSpec((1, N, tiles * d), lambda s, db, r: (r[s], 0, db))
        out = pl.BlockSpec((1, G, Z, d), vec)
        call = pl.pallas_call(
            functools.partial(_decode_kernel, Hk=Hk, G=G, d=d, tiles=tiles),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(S, T // tiles),
                in_specs=[pl.BlockSpec((1, G + 1, Z, d), vec),
                          pl.BlockSpec((1, d, 128), row),
                          pl.BlockSpec((1, Z, d), row), state],
                out_specs=[out, out, state],
                scratch_shapes=[pltpu.VMEM((G + 1, Z, D), f32),
                                pltpu.VMEM((Hk * G, d, d), f32),
                                pltpu.VMEM((G, Z, d), f32)]),
            out_shape=[jax.ShapeDtypeStruct((S, G, Z, d), f32),
                       jax.ShapeDtypeStruct((S, G, Z, d), f32),
                       jax.ShapeDtypeStruct((Lm * NS, N, D), pool.dtype)],
            input_output_aliases={4: 2},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=64 << 20),
            interpret=_backend.interpret(),
        )
        num, den, flat = call(
            (jnp.asarray(l, jnp.int32) * NS + slots).astype(jnp.int32),
            _vectors(q, k, Hk, Z), vt, g, pool.reshape(Lm * NS, N, D))
        # [S, G, Hk, d] -> query head i G + j
        num = jnp.swapaxes(num[:, :, :Hk], 1, 2)
        den = jnp.swapaxes(den[:, :, :Hk].sum(axis=-1), 1, 2)
        y = num / (den[..., None] + d * eps)
    return y.reshape(S, Hk * G * d), flat.reshape(pool.shape)


def _parts(h, Hk: int, d: int):
    """A state ``[.., N, D]`` as ``(S [.., Hk, d, D], z [.., Hk, D])``."""
    S = h[..., :Hk * d, :]
    return (S.reshape(S.shape[:-2] + (Hk, d, S.shape[-1])),
            h[..., Hk * d:Hk * d + Hk, :])


def _whole(S, z, N: int):
    """``(S, z)`` of :func:`_parts` as one state ``[.., N, D]``."""
    Hk, d, D = S.shape[-3:]
    flat = jnp.concatenate([S.reshape(S.shape[:-3] + (Hk * d, D)), z], axis=-2)
    pad = [(0, 0)] * (flat.ndim - 2) + [(0, N - flat.shape[-2]), (0, 0)]
    return jnp.pad(flat, pad)


def _retain_step(S, z, lg, q, k, v, eps: float):
    """One token of the recurrence: ``S`` ``[.., Hk, d, D]``, ``z`` ``[..,
    Hk, D]``, ``lg`` ``[.., Hk]``, ``q`` ``[.., Hk, G, d]``, ``k``, ``v``
    ``[.., Hk, d]`` -> (the new ``S``, ``z``, ``y`` ``[.., Hk, G, d]``)."""
    dot = functools.partial(jnp.einsum, precision=_HIGHEST)
    d = k.shape[-1]
    g = gate(lg)
    pk = expand(k, True)
    S = g[..., None, None] * S + v.astype(jnp.float32)[..., :, None] \
        * pk[..., None, :]
    z = g[..., None] * z + pk
    pq = expand(q, False)
    num = dot("...hcD,...hgD->...hgc", S, pq)
    den = dot("...hD,...hgD->...hg", z, pq)
    return S, z, num / (den[..., None] + d * eps)


def pr_decode_step_xla(pool, l, slots, lg, q, k, v, eps: float = 1e-6):
    """:func:`pr_decode_step` in plain XLA: gather the rows' states, one
    step of the recurrence, scatter them back."""
    Lm, NS, N, D = pool.shape
    S, Hk = lg.shape
    d = k.shape[1] // Hk
    G = q.shape[1] // k.shape[1]
    with jax.named_scope("pr_decode_step_xla"):
        flat = pool.reshape(Lm * NS, N, D)
        rows = l * NS + slots
        Sm, z, y = _retain_step(
            *_parts(flat[rows], Hk, d), lg.astype(jnp.float32),
            q.reshape(S, Hk, G, d), k.reshape(S, Hk, d), v.reshape(S, Hk, d),
            eps)
        return (y.reshape(S, Hk * G * d),
                flat.at[rows].set(_whole(Sm, z, N)).reshape(pool.shape))


# --------------------------------------------------------------------------- #
# a pass's packed prompt rows, state on chip across a slot
# --------------------------------------------------------------------------- #

def _scan_kernel(cont_ref, still_ref, q_ref, k_ref, v_ref, vt_ref, col_ref,
                 row_ref, s0_ref, z0_ref, y_ref, st_ref, zt_ref, s_sc, z_sc,
                 *, chunks: int, G: int, d: int, eps: float):
    i, slot, cb = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(jnp.logical_and(cb == 0, cont_ref[slot] == 0))
    def _():
        s_sc[...] = s0_ref[0]
        z_sc[...] = z0_ref[0, pl.ds(i, 1), :]

    # a chunk that holds no token (every key zero: how a slot is padded)
    # reads and writes nothing: the state is what it was
    @pl.when(still_ref[slot * chunks + cb] != 0)
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(still_ref[slot * chunks + cb] == 0)
    def _():
        _scan_chunk(q_ref, k_ref, v_ref, vt_ref, col_ref, row_ref, y_ref,
                    s_sc, z_sc, G=G, d=d, eps=eps)

    @pl.when(cb == chunks - 1)
    def _():
        st_ref[0] = s_sc[...]
        zt_ref[0, 0] = z_sc[...]


def _scan_chunk(q_ref, k_ref, v_ref, vt_ref, col_ref, row_ref, y_ref, s_sc,
                z_sc, *, G: int, d: int, eps: float):
    """One chunk of one KV head: ``y`` of its rows, and the state on chip
    (``s_sc`` ``[d, D]``, ``z_sc`` ``[1, D]``) moved to the chunk's end."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    T = d // 2 + 1
    C = k_ref.shape[0]
    nt = ((1,), (1,))

    stack = lambda xs: xs[0] if len(xs) == 1 else jnp.concatenate(xs, axis=0)
    exact = q_ref.dtype == k_ref.dtype == bf16
    Qs = stack([q_ref[:, j * d:(j + 1) * d] for j in range(G)])   # [G C, d]
    Qf, Kf = Qs.astype(f32), k_ref[...].astype(f32)
    col = stack([col_ref[0, 0]] * G)                              # [G C, 1]
    row = row_ref[0, 0]                                           # [1, C]
    last = col_ref[0, 0][C - 1:C, :]                              # [1, 1]

    # inside the chunk: scores squared, decayed, causal
    QK = _dot(Qs, k_ref[...], None if exact else _HIGHEST, nt)    # [G C, C]
    r = jax.lax.broadcasted_iota(jnp.int32, (G * C, C), 0) % C
    s = jax.lax.broadcasted_iota(jnp.int32, (G * C, C), 1)
    W = jnp.where(r >= s, QK * QK * jnp.exp(jnp.where(r >= s, col - row, 0.0)),
                  0.0)
    den = jnp.sum(W, axis=1, keepdims=True)
    vparts = [v_ref[...]] if v_ref.dtype == bf16 else _split(
        v_ref[...].astype(f32), 3)
    num = _dot3(_split(W, 2), vparts, depth=1)                    # [G C, d]

    # what lies before the chunk, and the chunk's own write, a tile at a time
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, d), 1)
    decay = jnp.exp(last - col_ref[0, 0])                         # [C, 1]
    whole = jnp.where(lane >= 0, jnp.exp(last), 0.0)              # [1, d]
    vt = [vt_ref[0, 0]] if vt_ref.dtype == bf16 else _split(
        vt_ref[0, 0].astype(f32), 3)                              # [d, C]
    before = jnp.zeros((G * C, d), f32)
    zacc = jnp.zeros((G * C, d), f32)
    for t in range(T):
        cols = slice(t * d, (t + 1) * d)
        pq = _tile(Qf, t, False)                                  # [G C, d]
        St, zt = s_sc[:, cols], z_sc[:, cols]
        before = before + _dot3(_split(pq, 2), _split(St, 2), nt, depth=1)
        zacc = zacc + pq * zt
        wk = _tile(Kf, t, True) * decay                           # [C, d]
        s_sc[:, cols] = whole * St + _dot3(vt, _split(wk, 3))
        z_sc[:, cols] = whole * zt + jnp.sum(wk, axis=0, keepdims=True)
    carried = jnp.exp(col)
    y = (num + carried * before) / (
        den + carried * jnp.sum(zacc, axis=1, keepdims=True) + d * eps)
    for j in range(G):
        y_ref[:, j * d:(j + 1) * d] = y[j * C:(j + 1) * C]


def pr_chunk_scan(q: jax.Array, k: jax.Array, v: jax.Array, lg: jax.Array,
                  h0: jax.Array, cont: jax.Array, chunk: int = 128,
                  eps: float = 1e-6):
    """Power retention over ``slots`` chunk slots of ``Cs`` packed rows each,
    in chunks of ``C`` tokens (``chunk``, or the largest of :data:`PR_CHUNKS`
    under it that divides ``Cs``).

    q:    [slots*Cs, Hq * d]     k, v: [slots*Cs, Hk * d] (normed and rotated;
          ``k`` ZERO on rows that hold no token)
    lg:   [slots*Cs, Hk] float32, the log of the gate (<= 0; zero on rows that
          hold no token)
    h0:   [slots, N, D] float32, the state a slot starts from
    cont: [slots] int32: 1 where a slot continues the slot before it (its
          ``h0`` is then not read)

    Returns ``(y [slots*Cs, Hq * d] float32, hT [slots, N, D] float32)``."""
    NS, N, D = h0.shape
    T_, Hk = lg.shape
    d = k.shape[1] // Hk
    G = q.shape[1] // k.shape[1]
    Cs = T_ // NS
    C = next((c for c in PR_CHUNKS if c <= chunk and Cs % c == 0), 0)
    if not C or not _lanes_fit(d) or (Hk * d) % (N - Hk * d):
        return pr_chunk_scan_xla(q, k, v, lg, h0, cont, eps)
    nC, bps = T_ // C, Cs // C
    Z = N - Hk * d
    f32 = jnp.float32
    with jax.named_scope("pr_chunk_scan"):
        # the running sum of log g inside a chunk, down the sublanes and on
        # the lanes (a [.., C, 1] array is 128 lanes wide in memory whatever
        # it holds)
        c = jnp.cumsum(lg.astype(f32).reshape(nC, C, Hk), axis=1)
        crow = jnp.swapaxes(c, 1, 2)[:, :, None, :]             # [nC, Hk, 1, C]
        ccol = jnp.swapaxes(c, 1, 2)[:, :, :, None]             # [nC, Hk, C, 1]
        vt = jnp.transpose(v.reshape(nC, C, Hk, d), (0, 2, 3, 1))
        cont = cont.astype(jnp.int32).at[0].set(0)
        # 1 where a chunk's every key is zero: no token, nothing to do
        still = jnp.all(k.reshape(nC, -1) == 0, axis=1).astype(jnp.int32)
        tok = lambda i, g, cb, *_: (g * bps + cb, i)
        head = lambda i, g, cb, *_: (g * bps + cb, i, 0, 0)
        call = pl.pallas_call(
            functools.partial(_scan_kernel, chunks=bps, G=G, d=d, eps=eps),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(Hk, NS, bps),
                in_specs=[pl.BlockSpec((C, G * d), tok),
                          pl.BlockSpec((C, d), tok), pl.BlockSpec((C, d), tok),
                          pl.BlockSpec((1, 1, d, C), head),
                          pl.BlockSpec((1, 1, C, 1), head),
                          pl.BlockSpec((1, 1, 1, C), head),
                          pl.BlockSpec((1, d, D), lambda i, g, cb, *_: (g, i, 0)),
                          pl.BlockSpec((1, Z, D),
                                       lambda i, g, cb, *_: (g, Hk * d // Z, 0))],
                out_specs=[pl.BlockSpec((C, G * d), tok),
                           pl.BlockSpec((1, d, D),
                                        lambda i, g, cb, *_: (g, i, 0)),
                           pl.BlockSpec((1, 1, 1, D),
                                        lambda i, g, cb, *_: (g, i, 0, 0))],
                scratch_shapes=[pltpu.VMEM((d, D), f32),
                                pltpu.VMEM((1, D), f32)]),
            out_shape=[jax.ShapeDtypeStruct((T_, Hk * G * d), f32),
                       jax.ShapeDtypeStruct((NS, N, D), f32),
                       jax.ShapeDtypeStruct((NS, Hk, 1, D), f32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",) * 3,
                vmem_limit_bytes=96 << 20),
            interpret=_backend.interpret(),
        )
        h0 = h0.astype(f32)
        y, hT, zT = call(cont, still, q, k, v, vt, ccol, crow, h0, h0)
        # the kernel wrote the heads' S; z (and the rows that pad it) here
        hT = jax.lax.dynamic_update_slice(
            hT, jnp.pad(zT[:, :, 0], ((0, 0), (0, Z - Hk), (0, 0))),
            (0, Hk * d, 0))
    return y, hT


def pr_chunk_scan_xla(q, k, v, lg, h0, cont, eps: float = 1e-6):
    """:func:`pr_chunk_scan` in plain XLA, in the RECURRENT form: token by
    token, slot after slot."""
    NS, N, D = h0.shape
    T_, Hk = lg.shape
    d = k.shape[1] // Hk
    G = q.shape[1] // k.shape[1]
    Cs = T_ // NS
    f32 = jnp.float32
    xs = (lg.astype(f32).reshape(NS, Cs, Hk), q.reshape(NS, Cs, Hk, G, d),
          k.reshape(NS, Cs, Hk, d), v.reshape(NS, Cs, Hk, d))

    def step(carry, tok):
        S, z, y = _retain_step(*carry, *tok, eps)
        return (S, z), y

    with jax.named_scope("pr_chunk_scan_xla"):
        ys, hs = [], []
        state = None
        for n in range(NS):
            start = _parts(h0[n].astype(f32), Hk, d)
            state = start if state is None else tuple(
                jnp.where(cont[n] != 0, a, b) for a, b in zip(state, start))
            state, y = jax.lax.scan(step, state, tuple(x[n] for x in xs))
            ys.append(y.reshape(Cs, Hk * G * d))
            hs.append(_whole(*state, N))
        return jnp.concatenate(ys), jnp.stack(hs)
